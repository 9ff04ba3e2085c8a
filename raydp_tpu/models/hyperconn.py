"""Manifold-constrained hyper-connections: the residual stream as ``n``
streams, read and written through three per-token mappings, the
stream-to-stream one projected onto the doubly stochastic matrices.

Hyper-connections (Zhu et al. 2024, arXiv:2409.19606) widen the residual
path to ``n`` copies of the hidden state and let every sublayer ``F`` read
a learned mixture of them and write back to each with a learned weight;
mHC (arXiv:2512.24880) constrains the stream-to-stream matrix to the
Birkhoff polytope by Sinkhorn-Knopp so that the mixing can neither blow
the signal up nor kill it. Per sublayer, for ``x`` ``[T, n, D]``:

    u      = vec(x) / rms(vec(x))                       (no weight)
    H~_pre  = a_pre  * u phi_pre  + b_pre    in R^n
    H~_post = a_post * u phi_post + b_post   in R^n
    H~_res  = a_res  * mat(u phi_res) + b_res   in R^{n x n}
    H_pre = sigmoid(H~_pre)   H_post = 2 sigmoid(H~_post)
    H_res = SK(exp(clip(H~_res, lo, hi)))
    h  = sum_i H_pre[i] x[:, i]         y = F(norm(h))
    x'[:, i] = sum_j H_res[i, j] x[:, j] + H_post[i] y

``SK`` is ``iters`` rounds of "each row over (its sum + eps), then each
column over (its sum + eps)". The three ``phi`` are ONE ``[n, D, 2n + n²]``
parameter (columns pre, post, res; the same mathematics, one pass over
``x``), ``alpha`` ``[3]`` and ``bias`` ``[2n + n²]``; the parameters and
the mappings are float32 whatever the trunk's dtype, and the streams travel
in the trunk's.

Layout: the streams are ``[B, n, S, D]`` (streams before the sequence) and
the mappings ``[2n + n², B, S]`` (a mapping's entries before the tokens): a
4-long axis next to the feature axis would be padded to a whole tile in
the chip's memory, 4 to 16 sublanes in bfloat16.

:class:`HyperMaps` computes the mappings (scopes ``<name>/maps`` and
``<name>/sinkhorn``); :func:`read` and :func:`write` are the two mixings
(``<name>/pre``, ``<name>/post``). Each mixing is one differentiable
operation whose forward and whose backward pass ONCE over the streams in
their own dtype (``ops/stream_mix.py``: Pallas kernels over blocks of
tokens): products and sums are float32 inside a block's registers, an
output is rounded once where it is stored, and no float32 array of a
stream's size, of a cotangent's or of an accumulator's reaches HBM. That
path is taken where the kernels can tile what they are given
(:func:`one_pass`: a floating carrier, a feature width that is a multiple of
128); anything else runs :func:`plain_read` and :func:`plain_write`, the
formula above written out in ``jax.numpy``, which are also what the tests
hold the kernels to. How far ``H_res`` is from doubly
stochastic after its rounds is sown as ``hc_res_err_max`` into the step's
statistics (``models/stats.py``; the largest over sublayers and steps) and
reaches the gauge ``hc/res_row_sum_err_max`` with the epoch's loss
(:func:`report_epoch`).
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from raydp_tpu.models import stats
from raydp_tpu.ops import stream_mix

logger = logging.getLogger(__name__)

SUBLAYERS_PER_LAYER = 2          # attention (or another mixer), then FFN
# Init the papers give: the mappings' learned scalars, and the diagonal of
# ``b_res`` (H_res starts near the identity: 0.87 on the diagonal at n = 4).
ALPHA_INIT = 0.01
RES_DIAGONAL = 3.0

RES_ERR = stats.declare("hc_res_err_max", jnp.maximum)


@dataclasses.dataclass(frozen=True)
class HyperConfig:
    """The residual path's own sizes (``hc_mult``, ``hc_sinkhorn_iters``,
    ``hc_eps`` and the clamp of ``H~_res`` in the published configs)."""

    streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    # The standard deviations of ``phi`` and of a normal draw added to
    # every bias. The defaults are the papers' start: mappings that hardly
    # depend on the token, every stream read and written alike. A check
    # that has to see the mappings at work draws them wider (:func:
    # `_bias_init`; ``benchmark/configs/xing4_0_29b_a4b.json``).
    phi_std: float = 0.02
    bias_std: float = 0.0

    @property
    def maps(self) -> int:
        return 2 * self.streams + self.streams ** 2


class Maps(NamedTuple):
    pre: Any     # [n, B, S]
    post: Any    # [n, B, S]
    res: Any     # [n, n, B, S]


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of row then column normalisation of the positive
    matrices ``m`` ``[n, n, ...]`` (rows are axis 0, columns axis 1)."""
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
    return m


def doubly_stochastic_error(m):
    """The largest distance of any row or column sum of ``m`` ``[n, n,
    ...]`` from 1."""
    return jnp.maximum(
        jnp.max(jnp.abs(m.sum(axis=1) - 1.0)),
        jnp.max(jnp.abs(m.sum(axis=0) - 1.0)),
    )


class HyperMaps(nn.Module):
    """The three mappings of one sublayer from the streams ``x``
    ``[B, n, S, D]``."""

    cfg: HyperConfig
    norm_eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x) -> Maps:
        cfg = self.cfg
        n, d = cfg.streams, x.shape[-1]
        if x.shape[1] != n:
            raise ValueError(f"{x.shape[1]} streams, not {n}")
        phi = self.param(
            "phi", nn.initializers.normal(cfg.phi_std), (n, d, cfg.maps),
            self.param_dtype,
        )
        alpha = self.param(
            "alpha", nn.initializers.constant(ALPHA_INIT), (3,),
            self.param_dtype,
        )
        bias = self.param("bias", _bias_init(cfg), (cfg.maps,),
                          self.param_dtype)
        with jax.named_scope("maps"):
            x32 = x.astype(jnp.float32)
            # u phi = (x phi) / rms(vec(x)): the norm has no weight, so it
            # is one factor a token, taken after the product.
            rms = jnp.sqrt(
                jnp.mean(x32 * x32, axis=(1, 3)) + self.norm_eps
            )                                                  # [B, S]
            # One [S, D] x [D, 2n + n²] product a stream: contracting the
            # stream axis too would make the compiler lay the streams out
            # again with that axis next to the features.
            raw = sum(
                jnp.einsum(
                    "bsd,dk->kbs", x32[:, i], phi[i].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                ) for i in range(n)
            ) / rms
            scale = alpha.astype(jnp.float32)[
                np.repeat(np.arange(3), [n, n, n * n])
            ]
            raw = raw * scale[:, None, None] + bias.astype(
                jnp.float32
            )[:, None, None]
            pre = jax.nn.sigmoid(raw[:n])
            post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
            res = jnp.exp(jnp.clip(raw[2 * n:], *cfg.clamp)).reshape(
                (n, n) + raw.shape[1:]
            )
        with jax.named_scope("sinkhorn"):
            res = sinkhorn(res, cfg.sinkhorn_iters, cfg.eps)
            stats.sow(
                self, RES_ERR,
                jax.lax.stop_gradient(doubly_stochastic_error(res)),
            )
        return Maps(pre, post, res)


def _bias_init(cfg: HyperConfig):
    """``b_pre`` around the value at which H_pre is 1/n (the sublayer
    reads the streams' mean), ``b_post`` around 0 (H_post 1) and ``b_res``
    around :data:`RES_DIAGONAL` on the diagonal (H_res near the identity),
    each entry with a normal draw of ``bias_std`` added. Without a draw
    every stream is read alike, and a doubly stochastic H_res keeps the
    streams' sum whatever it is: nothing downstream can tell one H_res
    from another."""
    n = cfg.streams

    def init(key, shape, dtype=jnp.float32):
        res = RES_DIAGONAL * jnp.eye(n, dtype=dtype).reshape(-1)
        centre = jnp.concatenate([
            jnp.full((n,), -math.log(n - 1.0) if n > 1 else 0.0, dtype),
            jnp.zeros((n,), dtype), res,
        ])
        return (centre + cfg.bias_std * jax.random.normal(
            key, centre.shape, dtype
        )).reshape(shape)

    return init


def _per_stream(m):
    """A mapping ``[n, B, S]`` against the streams ``[B, n, S, D]``."""
    return jnp.moveaxis(m, 0, 1)[..., None]


def plain_read(x, maps: Maps):
    """:func:`read` as the formula is written, float32 arrays throughout:
    the reference, and the path of streams the kernels cannot tile."""
    h = jnp.sum(x.astype(jnp.float32) * _per_stream(maps.pre), axis=1)
    return h.astype(x.dtype)


def plain_write(x, y, maps: Maps):
    """:func:`write` as the formula is written (one term a source stream,
    so that no ``[B, n, n, S, D]`` array exists), float32 arrays
    throughout: the reference, and the path of streams the kernels cannot
    tile."""
    x32 = x.astype(jnp.float32)
    out = _per_stream(maps.post) * y.astype(jnp.float32)[:, None]
    for j in range(x.shape[1]):
        out = out + _per_stream(maps.res[:, j]) * x32[:, j][:, None]
    return out.astype(x.dtype)


def one_pass(dtype, width: int) -> bool:
    """Whether streams of this dtype and feature width are mixed by the
    one-pass kernels (``ops/stream_mix.tileable``)."""
    return stream_mix.tileable(dtype, width)


def _columns(*mappings):
    """Mappings ``[..., B, S]`` side by side as the kernels' per-token
    columns ``[B, S, k]``, in the order given (a matrix row by row)."""
    stacked = jnp.concatenate([
        m.reshape((-1,) + m.shape[-2:]).astype(jnp.float32)
        for m in mappings
    ])
    # The transposition happens HERE: left free, the compiler gives the
    # stacked mappings the columns' layout and pays for it with a copy in
    # every Sinkhorn round that made them (PERF.md §6, PR 37).
    stacked = with_layout_constraint(stacked, Layout((0, 1, 2)))
    return jnp.moveaxis(stacked, 0, -1)


def read(x, maps: Maps):
    """``h = sum_i H_pre[i] x[:, i]``: ``[B, n, S, D]`` to ``[B, S, D]``
    in ``x``'s dtype; float32 products and sums, one rounding."""
    if not one_pass(x.dtype, x.shape[-1]):
        return plain_read(x, maps)
    return stream_mix.read(x, _columns(maps.pre))


def write(x, y, maps: Maps):
    """``x'[:, i] = sum_j H_res[i, j] x[:, j] + H_post[i] y`` in ``x``'s
    dtype; float32 products and sums, one rounding."""
    if not one_pass(x.dtype, x.shape[-1]):
        return plain_write(x, y, maps)
    return stream_mix.write(x, y, _columns(maps.post, maps.res))


def expand(x, streams: int):
    """The streams' start: the embedding ``[B, S, D]`` repeated."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], streams) + x.shape[1:])


def reduce(x):
    """The streams' end: their plain sum, ``[B, S, D]``."""
    return jnp.sum(x.astype(jnp.float32), axis=1).astype(x.dtype)


def report(cfg) -> None:
    """Static for a compiled step: four gauges and one log line where the
    step is built (as ``models/mamba.report``). Zero for a stack with one
    residual stream; ``hc/one_pass_sublayers`` counts the sublayers whose
    mixings the kernels take (all of them or none: the carrier's dtype and
    width are the stack's)."""
    from raydp_tpu.utils.profiling import metrics

    hyper = getattr(cfg, "hyper", None)
    sublayers = SUBLAYERS_PER_LAYER * cfg.n_layers if hyper else 0
    kernels = bool(hyper) and one_pass(cfg.dtype, cfg.d_model)
    metrics.gauge_set("hc/streams", hyper.streams if hyper else 0)
    metrics.gauge_set(
        "hc/sinkhorn_iters", hyper.sinkhorn_iters if hyper else 0
    )
    metrics.gauge_set("hc/sublayers", sublayers)
    metrics.gauge_set("hc/one_pass_sublayers", sublayers if kernels else 0)
    if hyper:
        carrier = jnp.dtype(cfg.dtype).name
        path = (
            f"in one pass over the {carrier} streams, forward and backward "
            "(Pallas kernels)" if kernels else
            f"as float32 arrays (the plain formula: no kernel tiles {carrier} "
            f"streams of width {cfg.d_model})"
        )
        logger.info(
            "residual path: %d streams around each of %d sublayers, mixed "
            "by per-token float32 mappings %s; H_res through %d "
            "Sinkhorn-Knopp rounds (eps %g, clamp %s)",
            hyper.streams, sublayers, path,
            hyper.sinkhorn_iters, hyper.eps, list(hyper.clamp),
        )


def report_epoch(sown: dict) -> None:
    """``hc/res_row_sum_err_max`` from an epoch's statistics (the largest
    value any sublayer of any step sowed); nothing without streams."""
    from raydp_tpu.utils.profiling import metrics

    if RES_ERR in sown:
        metrics.gauge_set("hc/res_row_sum_err_max", float(sown[RES_ERR]))
