"""The gated short convolution of the LFM2 family (Liquid AI; the
``transformers`` ``Lfm2ShortConv``): the operator a hybrid stack puts where
most of its attention was, with no state but the last ``taps - 1`` tokens.

    [B, C, x] = split3(W_in u)
    z_t       = Σ_j w[j] ⊙ (B ⊙ x)_{t-(taps-1)+j}          (zeros before the sequence)
    out       = W_out (C ⊙ z)

No activation, no norm and no bias inside. Module paths:
``conv/{in_proj,conv,out_proj}``; the convolution itself is the one
``models/mamba.py`` has for Mamba-2 (``causal_depthwise_conv``).
"""
from __future__ import annotations

import functools
import logging

import flax.linen as nn
import jax.numpy as jnp

from raydp_tpu.models.mamba import (
    CONV_IMPLEMENTATION,
    _conv_init,
    causal_depthwise_conv,
)

logger = logging.getLogger(__name__)


class GatedConv(nn.Module):
    """``C ⊙ conv(B ⊙ x)`` from the in-projection's ``[.., 3·D]`` output:
    both gates and the depthwise causal convolution (``kernel``
    [taps, D]), float32 inside, one scope for a device trace."""

    taps: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, bcx):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(_conv_init(self.taps), (None, None)),
            (self.taps, x.shape[-1]), self.param_dtype,
        )
        z = causal_depthwise_conv(
            b.astype(jnp.float32) * x.astype(jnp.float32), kernel
        )
        return (c.astype(jnp.float32) * z).astype(self.dtype)


class ShortConv(nn.Module):
    """``cfg`` is a ``TransformerConfig`` (``conv_taps``). Input
    ``[B, S, d_model]`` → output ``[B, S, d_model]``."""

    cfg: object

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        init = nn.initializers.xavier_uniform()
        bcx = dense(
            3 * cfg.d_model, name="in_proj",
            kernel_init=nn.with_logical_partitioning(init, ("embed", None)),
        )(u)
        y = GatedConv(
            cfg.conv_taps, cfg.dtype, cfg.param_dtype, name="conv"
        )(bcx)
        return dense(
            cfg.d_model, name="out_proj",
            kernel_init=nn.with_logical_partitioning(init, (None, "embed")),
        )(y)


def report(cfg) -> None:
    """Static for a compiled step: two gauges and one log line where the
    step is built (as ``models/mamba.report``). Zero for a stack without
    such layers."""
    from raydp_tpu.utils.profiling import metrics

    layers = sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "conv")
    metrics.gauge_set("conv/layers", layers)
    metrics.gauge_set("conv/taps", cfg.conv_taps if layers else 0)
    if layers:
        logger.info(
            "hybrid stack: %d gated short-convolution and %d attention "
            "layers; convolution: %d taps over %d channels as %s",
            layers, cfg.kinds.count("attention"), cfg.conv_taps,
            cfg.d_model, CONV_IMPLEMENTATION,
        )
