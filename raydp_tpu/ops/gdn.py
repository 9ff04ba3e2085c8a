"""Gated DeltaNet's recurrence (arXiv:2412.06464): a gated delta rule
whose decay is ONE scalar a head and token, chunked. Per head, with a
state ``S`` of keys × values (``d_k`` and ``d_v`` may differ) that starts
at zero,

    S̄   = e^{g_t} S_{t-1}
    S_t = S̄ + β_t k_t (v_t − S̄ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t

with ``g_t ≤ 0`` and ``β_t`` in (0, 1), or in (0, 2) where the layer
allows the transition ``e^{g}(I − β k kᵀ)`` a negative eigenvalue; the
rule reads ``β`` as it is given. :func:`gdn_recurrent` is that, token by
token. :func:`gdn_chunked` cuts a sequence into chunks of ``chunk``
tokens: with ``G`` the inclusive cumulative sum of ``g`` inside a chunk,
``Γ_ij = e^{G_i − G_j}`` (j ≤ i) is ONE [chunk, chunk] lower-triangular
matrix a head, every exponent ≤ 0, so where ``ops/kda.py`` (a decay a
channel) splits the causal pairs by dyadic levels this rule needs no
levels and no clamp:

    (I + A) w = β v − (β e^G ⊙ k) S_0,   A = β ⊙ (k kᵀ ⊙ Γ)   strictly lower
    o   = (e^G ⊙ q) S_0 + (q kᵀ ⊙ Γ) w
    S_C = e^{G_C} S_0 + (e^{G_C − G} ⊙ k)ᵀ w

Those are ``ops/kda.py``'s six chunk-local results with a scalar where it
has a vector, so the rest is that module's by import: the exact blocked
triangular inverse and its backward (``unit_lower_inverse``), the
recurrence over chunk states (``_across``) and the walk over segments
whose backward holds one segment's intermediates (``segment_walk``;
``SEGMENT_CHUNKS`` is read there). Plain ``jax.numpy`` batched products:
a chunk's [64, 64] arrays are 31 MB a layer at 4,096 tokens and 30 heads;
kernels on [64, 96] and [64, 192] tiles are a later change.

**Precision** (what ``ops/kda.py`` states for itself): ``g``, its
cumulative sums, ``β``, ``Γ``, ``A``'s inverse and the products with it,
the chunk states and their recurrence are float32 (the state's products
at ``Precision.HIGHEST``); the operands of ``q kᵀ`` and ``k kᵀ`` and of
the two products that make ``o`` are in ``v``'s dtype with float32
accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from raydp_tpu.ops.kda import (
    Rule,
    _across,
    kda_recurrent,
    segment_walk,
    unit_lower_inverse,
)

IMPLEMENTATION = (
    "chunked WY form, one [chunk, chunk] decay matrix a head, jax.numpy; "
    "the triangular inverse, the chunk states' lax.scan and the walk over "
    "segments are ops/kda.py's (ops/gdn.py)"
)
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# What the forward keeps besides its inputs: the output and the states the
# segments were entered with, [segments, b, h, d_k, d_v] float32.
KEPT = ("gdn_out", "gdn_segment_states")


def gdn_recurrent(q, k, v, g, beta):
    """The recurrence token by token, float32: ``q``, ``k`` [b, s, h,
    d_k], ``v`` [b, s, h, d_v], ``g`` [b, s, h] log-decays (≤ 0), ``beta``
    [b, s, h]. Returns ``o`` [b, s, h, d_v] float32. A scalar decay is a
    decay a channel with every channel alike."""
    return kda_recurrent(
        q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta
    )


def _chunk_local(q, k, v, g, beta):
    """What a chunk needs of itself alone: ``q``, ``k`` [..., c, d_k],
    ``v`` [..., c, d_v] (the chunk axis second to last), ``g``, ``beta``
    [..., c] float32. Returns ``ops/kda.py``'s six: ``U = T(βv)`` and ``W =
    T(β e^G k)`` float32, ``P = q kᵀ ⊙ Γ`` [..., c, c] and ``q e^G`` in
    ``v``'s dtype, ``k e^{G_C − G}`` float32 and ``e^{G_C}`` [..., 1]."""
    dtype, c = v.dtype, g.shape[-1]
    G = jnp.cumsum(g.astype(_F32), axis=-1)
    # Masked BEFORE the exponential: above the diagonal G_i − G_j ≥ 0.
    gamma = jnp.exp(jnp.where(
        np.tril(np.ones((c, c), bool)), G[..., :, None] - G[..., None, :],
        -jnp.inf,
    ))
    pairs = jnp.einsum(
        "...tid,...jd->...tij", jnp.stack([q, k], -3).astype(dtype),
        k.astype(dtype), preferred_element_type=_F32,
    ) * gamma[..., None, :, :]                                # [..., 2, c, c]
    bt = beta.astype(_F32)[..., None]                         # [..., c, 1]
    T = unit_lower_inverse(bt * pairs[..., 1, :, :])
    qf, kf, vf = (a.astype(_F32) for a in (q, k, v))
    decay = jnp.exp(G)[..., None]                             # [..., c, 1]
    U = jnp.einsum("...ij,...jv->...iv", T, bt * vf, precision=_HIGHEST)
    W = jnp.einsum(
        "...ij,...jk->...ik", T, bt * decay * kf, precision=_HIGHEST
    )
    to_end = kf * jnp.exp(G[..., -1:] - G)[..., None]
    return (U, W, pairs[..., 0, :, :].astype(dtype),
            (qf * decay).astype(dtype), to_end, decay[..., -1, :])


RULE = Rule(
    lambda *xs, keep: _chunk_local(*xs), _chunk_local, _across, KEPT
)


def gdn_chunked(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` [b, s, h, d_k], ``v`` [b, s, h, d_v] (``q`` already
    scaled), ``g`` [b, s, h] float32 log-decays (≤ 0, unbounded below),
    ``beta`` [b, s, h] float32; ``s`` a multiple of ``chunk``, a power of
    two. Returns ``o`` [b, s, h, d_v] in ``v``'s dtype. The state before
    the first token is zero."""
    return segment_walk(q, k, v, g, beta, chunk, RULE)
