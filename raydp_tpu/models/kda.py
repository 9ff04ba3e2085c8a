"""The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692, section
"neural parameterization", as its public ``fla`` layer has it): the linear
attention a hybrid stack puts where three of four attention layers were,
and the layer that carries the stack's positions.

    q̃, k̃, v = silu(conv4(y W_q)), silu(conv4(y W_k)), silu(conv4(y W_v))
    q = q̃ / ‖q̃‖₂ · d_k^-1/2,   k = k̃ / ‖k̃‖₂              (a head's d_k values)
    g = −exp(A_log_h) · softplus((y W_f↓) W_f↑ + dt_bias)     (per channel, ≤ 0)
    β = σ(y W_β)                                              (per head)
    S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t}) S_{t−1} + β_t k_t v_tᵀ;   o_t = S_tᵀ q_t
    out = W_o concat_h( rms(o_h) · w ⊙ σ(((y W_g↓) W_g↑ + b_g)_h) )

The output's norm is over EACH HEAD's ``d_v`` values with one learned
weight of ``d_v``, and the sigmoid gate multiplies AFTER it:
``models/mamba.GatedRMSNorm`` (the gate before a norm over all features)
is another function. Module paths (``kda`` in a block):
``kda/{q_proj, k_proj, v_proj, conv, f_down, f_up, decay, beta, scan,
g_down, g_up, gate_norm, out}``; the recurrence itself is ``ops/kda.py``.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.models.mamba import (
    CausalConv1d,
    _decay_rate_init,
    _replicated,
    _step_bias_init,
    conv_takes_kernel,
)
from raydp_tpu.ops.causal_conv import Unit
from raydp_tpu.ops.kda import IMPLEMENTATION as SCAN_IMPLEMENTATION
from raydp_tpu.ops.kda import PATHS as SCAN_PATHS
from raydp_tpu.ops.kda import kda_chunked, uses_kernels

logger = logging.getLogger(__name__)

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """The "kda" mixer's own sizes (the norm's epsilon is the stack's)."""

    heads: int = 32
    key_dim: int = 128               # a head's q and k
    value_dim: int = 128             # a head's v and o
    conv_taps: int = 4
    gate_rank: int = 128             # of both low-rank pairs (decay, gate)
    chunk: int = 64

    def state_bytes(self, layers: int) -> int:
        """What a sequence's float32 states hold, all layers."""
        return 4 * layers * self.heads * self.key_dim * self.value_dim

    def scan_chunk(self, sequence: int) -> int:
        """The chunk a sequence runs in: one that is no multiple of
        ``chunk`` (a test's) takes the largest power of two that divides
        both."""
        return math.gcd(self.chunk, sequence)

    def scan_runs_kernels(self, sequence: int) -> bool:
        """Whether the scan takes its Pallas kernels at this sequence
        length, for the chunk-local step and for the recurrence over
        chunk states alike: ``ops/kda.uses_kernels``, what ``kda_chunked``
        itself asks, of the shapes the mixer hands it."""
        return uses_kernels(
            self.key_dim, self.value_dim, self.scan_chunk(sequence)
        )

    def kept_inverse_bytes(self, layers: int, sequence: int) -> int:
        """What the chunks' float32 triangular inverses of one sequence
        hold, all layers: the residual the kernels' forward keeps for the
        backward (``ops/kda.KEPT``); none by the ``jax.numpy`` form."""
        if not self.scan_runs_kernels(sequence):
            return 0
        return 4 * layers * self.heads * sequence * self.scan_chunk(sequence)


class QKVConv(nn.Module):
    """The three causal depthwise convolutions (no bias) with their SiLU,
    and the L2 norm of each head's ``q`` and ``k``; ``q`` times
    ``d_k^-1/2``. Returns [B, S, H, d] arrays in the compute dtype.

    Where the convolution's kernels take a call with the norm inside
    (``ops/causal_conv.py``: on a TPU, at a shape they tile, a head of
    whole 128-lane registers: Kimi Linear's 128), ``q`` and ``k`` leave
    them normalised, scaled and in the compute dtype, and nothing float32
    of their size exists in HBM either way. Everywhere else (off the TPU,
    a decode step, Olmo-Hybrid's heads of 96) the convolution returns
    float32 and :meth:`unit` runs as ``jax.numpy`` after it: the same
    float32 arithmetic and one rounding either way. ``v`` has no norm:
    float32 out of its convolution and cast here."""

    kda: KDAConfig
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    mesh: Any = None

    @staticmethod
    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS
        )

    @nn.compact
    def __call__(self, q, k, v):
        kda = self.kda

        def conv(x, name, width, **norm):
            y = CausalConv1d(
                kda.conv_taps, self.dtype if norm else jnp.float32,
                self.param_dtype, use_bias=False, mesh=self.mesh, name=name,
                **norm,
            )(x)
            return y.reshape(*y.shape[:-1], kda.heads, width)

        unit = Unit(kda.key_dim, L2_EPS)
        # q's and k's calls are one shape: one answer for both.
        if q.ndim == 3 and conv_takes_kernel(
                q.shape[1], q.shape[2], kda.conv_taps, q.dtype, self.dtype,
                mesh=self.mesh, unit=unit):
            q = conv(q, "q", kda.key_dim, unit=unit,
                     scale=kda.key_dim ** -0.5)
            k = conv(k, "k", kda.key_dim, unit=unit)
        else:
            q = self.unit(conv(q, "q", kda.key_dim)) * kda.key_dim ** -0.5
            k = self.unit(conv(k, "k", kda.key_dim))
        v = conv(v, "v", kda.value_dim)
        return q.astype(self.dtype), k.astype(self.dtype), v.astype(self.dtype)


class ChannelDecay(nn.Module):
    """``g = −exp(A_log_h) · softplus(f + dt_bias)`` from the low-rank
    pair's output ``f`` [B, S, H·d_k]: ``A_log`` one scalar a head,
    ``dt_bias`` one a channel, float32. Returns [B, S, H, d_k]."""

    kda: KDAConfig
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, f):
        kda = self.kda
        a_log = self.param(
            "A_log", _replicated(_decay_rate_init), (kda.heads,),
            self.param_dtype,
        )
        dt_bias = self.param(
            "dt_bias", _replicated(_step_bias_init),
            (kda.heads * kda.key_dim,), self.param_dtype,
        )
        step = jax.nn.softplus(
            f.astype(jnp.float32) + dt_bias.astype(jnp.float32)
        ).reshape(*f.shape[:-1], kda.heads, kda.key_dim)
        return -jnp.exp(a_log.astype(jnp.float32))[:, None] * step


class HeadGatedRMSNorm(nn.Module):
    """``rms(o_h) · w ⊙ act(z_h)``: the norm over each head's values with
    one weight of ``d_v`` for all heads, the gate after it (``activation``:
    the sigmoid here, SiLU in ``models/gdn.py``); float32 inside."""

    epsilon: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    activation: Callable = jax.nn.sigmoid

    @nn.compact
    def __call__(self, o, z):
        scale = self.param(
            "scale", _replicated(nn.initializers.ones), (o.shape[-1],),
            self.param_dtype,
        )
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + self.epsilon
        )
        gate = self.activation(z.astype(jnp.float32)).reshape(o.shape)
        return (o * scale.astype(jnp.float32) * gate).astype(self.dtype)


class KimiDeltaMixer(nn.Module):
    """``cfg`` is a ``TransformerConfig`` with ``kda`` set. Input
    ``[B, S, d_model]`` → output ``[B, S, d_model]``."""

    cfg: object

    @nn.compact
    def __call__(self, x):
        cfg, kda = self.cfg, self.cfg.kda
        if not cfg.causal:
            raise ValueError("a delta-rule state runs over earlier tokens")
        keys, values = kda.heads * kda.key_dim, kda.heads * kda.value_dim
        init = nn.initializers.xavier_uniform()

        def dense(features, name, axes, use_bias=False):
            return nn.Dense(
                features, use_bias=use_bias, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name,
                kernel_init=nn.with_logical_partitioning(init, axes),
            )

        wide, narrow = ("embed", "heads"), ("embed", None)
        q, k, v = QKVConv(
            kda, cfg.dtype, cfg.param_dtype, mesh=cfg.mesh, name="conv",
        )(
            dense(keys, "q_proj", wide)(x), dense(keys, "k_proj", wide)(x),
            dense(values, "v_proj", wide)(x),
        )
        g = ChannelDecay(kda, cfg.param_dtype, name="decay")(
            dense(keys, "f_up", (None, "heads"))(
                dense(kda.gate_rank, "f_down", narrow)(x)
            )
        )
        beta = dense(kda.heads, "beta", wide)(x)
        with jax.named_scope("beta"):
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        with jax.named_scope("scan"):
            o = kda_chunked(q, k, v, g, beta, kda.scan_chunk(x.shape[-2]))
        z = dense(values, "g_up", (None, "heads"), use_bias=True)(
            dense(kda.gate_rank, "g_down", narrow)(x)
        )
        o = HeadGatedRMSNorm(
            cfg.norm_eps, cfg.dtype, cfg.param_dtype, name="gate_norm"
        )(o, z)
        return dense(cfg.d_model, "out", ("heads", "embed"))(
            o.reshape(*o.shape[:-2], values)
        )


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "kda")


def report(cfg, tokens_per_step: int, sequence: int = 0,
           convs=(0, 0, 0)) -> None:
    """Static for a compiled step: eight gauges and one log line where the
    step is built (as ``models/mamba.report``). All zero for a stack
    without such layers. ``sequence`` is a sequence's tokens (all of a
    step's where left out); ``convs`` the step's census of causal
    convolutions (``models/mamba.counting_convs``, whose gauges
    ``models/mamba.report`` sets), for the log line."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    kda = cfg.kda if layers else None
    chunks = layers * -(-tokens_per_step // kda.chunk) if kda else 0
    kernels = bool(kda) and kda.scan_runs_kernels(sequence or tokens_per_step)
    kept = kda.kept_inverse_bytes(
        layers, sequence or tokens_per_step) >> 20 if kda else 0
    metrics.gauge_set("kda/scan_kernel_layers", layers if kernels else 0)
    metrics.gauge_set("kda/state_kernel_layers", layers if kernels else 0)
    metrics.gauge_set("kda/kept_inverse_mib", kept)
    metrics.gauge_set("kda/layers", layers)
    metrics.gauge_set("kda/heads", kda.heads if kda else 0)
    metrics.gauge_set("kda/chunk", kda.chunk if kda else 0)
    metrics.gauge_set("kda/chunks_per_step", chunks)
    metrics.gauge_set(
        "kda/state_bytes_per_sequence", kda.state_bytes(layers) if kda else 0
    )
    if kda:
        logger.info(
            "delta-rule stack: layers %s; %d heads of %d (q, k) and %d (v), "
            "a decay per channel, %d-tap convolutions (the stack's: %d as "
            "Pallas kernels, %d of those with the L2 norm of q and k "
            "inside, %d in jax.numpy), gates of rank "
            "%d; chunk %d (%d chunks a step); scan: %s; the forward keeps "
            "%d MiB of chunk inverses a sequence; a chunk's work and the "
            "walk over the chunks at these shapes: %s",
            " ".join(cfg.kinds), kda.heads, kda.key_dim, kda.value_dim,
            kda.conv_taps, convs[0], convs[2], convs[1], kda.gate_rank,
            kda.chunk, chunks,
            SCAN_IMPLEMENTATION, kept, SCAN_PATHS[kernels],
        )
