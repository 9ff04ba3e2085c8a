"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692): a
gated delta rule whose decay is a vector over the key dimension, in a
chunked form with no clamp and no dropped term, plain ``jax.numpy``.

Per head, with a state ``S`` of keys × values that starts at zero,

    S̄   = Diag(exp(g_t)) S_{t-1}
    S_t = S̄ + β_t k_t (v_t − S̄ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t

:func:`kda_recurrent` is that, token by token. :func:`kda_chunked` cuts a
sequence into chunks of ``chunk`` tokens. With ``G`` the cumulative sum of
``g`` inside a chunk (inclusive) and ``S_0`` the state a chunk is entered
with, the corrections ``w_t = β_t (v_t − S̄ᵀ k_t)`` of a chunk solve a
unit-lower-triangular system (the WY/UT form of the delta rule),

    (I + A) w = β v − (β e^G ⊙ k) S_0,   A_ij = β_i Σ_d k_id k_jd e^{G_id − G_jd}  (j < i)
    o   = (e^G ⊙ q) S_0 + P w,           P_ij = Σ_d q_id k_jd e^{G_id − G_jd}      (j ≤ i)
    S_C = Diag(e^{G_C}) S_0 + (k ⊙ e^{G_C − G})ᵀ w

so a chunk costs matmuls, and a short recurrence carries ``S`` across the
chunks.

**The pairwise factor.** ``e^{G_i − G_j}`` differs per channel, so ``A`` and
``P`` are no single product of ``k``, ``q`` and a ``[chunk, chunk]`` decay.
The tensor over ``[chunk, chunk, d_k]`` is 17 GB a layer at 16k tokens and
is never formed; the factored form ``(k_i e^{G_i}) · (k_j e^{−G_j})``
overflows float32 once a channel's ``G`` passes −88 inside a chunk. Here
the causal pairs of a chunk are split by the highest bit in which ``i``
and ``j`` differ: at level ``h`` (1, 2, 4, … chunk/2) the pairs with ``i``
in the second half and ``j`` in the first half of one aligned block of
``2h`` tokens. All of them straddle the block's middle token ``r``, so

    e^{G_i − G_j} = e^{G_i − G_r} · e^{G_r − G_j},    both exponents ≤ 0.

At a level a token late in its block carries ``x_i e^{G_i − G_r}``, a token
early in it ``k_j e^{G_r − G_j}``, every other row is zero, and ONE batched
``[chunk, d_k] × [d_k, chunk]`` product over levels, chunks and heads holds
every pair at the level it belongs to (a constant mask reads it there):
factors in (0, 1], exact in every channel however strong the decay (a
factor that underflows belongs to a pair whose true weight is below
float32's smallest). The operands are log2(chunk) times the size of ``q``
and ``k``; every array keeps ``[chunk, d_k]`` or ``[chunk, chunk]`` as its
last two dimensions (blocks of 1 to 8 rows cost XLA more in padding and
copies than the zero rows do: PERF.md §6, PR 44). The triangular inverse
is block forward substitution over the same levels, ``T ← T − T a_h T``
with ``a_h`` the part of ``A`` a level holds: exact, no Neumann series.

**Precision.** ``g``, its cumulative sums, ``β``, ``A``'s inverse and the
products with it, the chunk states and their recurrence are float32 (the
state's products at ``Precision.HIGHEST``); the operands of the pairwise
products and of the two products that make ``o`` are in ``q``'s dtype with
float32 accumulation.

**The backward.** A sequence runs in segments of :data:`SEGMENT_CHUNKS`
chunks, one after the other. The forward keeps its inputs, its output and
the state each segment was entered with (:data:`KEPT`, named for a
checkpoint's policy as ``ops/flash_attention.KEPT`` are: a checkpointed
block then runs no second forward); the backward walks the segments from
the last to the first, rebuilds one segment's chunk quantities from its
inputs and its entering state, and differentiates that segment as
written (the triangular inverse by ``−Tᵀ dT Tᵀ``), handing the state's
cotangent on. So a backward holds one segment's intermediates — a few
``[segment, heads, d]`` float32 arrays — and never a sequence's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

IMPLEMENTATION = (
    "chunked WY form in jax.numpy, pairwise decay by dyadic levels, "
    "chunk states one after the other (ops/kda.py)"
)
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# Chunks a segment: what a backward holds at once (the module docstring).
SEGMENT_CHUNKS = 32
# What the forward keeps besides its inputs: the output and the states the
# segments were entered with, [segments, b, h, d_k, d_v] float32.
KEPT = ("kda_out", "kda_segment_states")


def kda_recurrent(q, k, v, g, beta):
    """The recurrence token by token (``lax.scan``), float32: ``q``, ``k``
    [b, s, h, d_k], ``v`` [b, s, h, d_v], ``g`` [b, s, h, d_k] log-decays
    (≤ 0), ``beta`` [b, s, h]. Returns ``o`` [b, s, h, d_v] float32."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    b, _, h, d_k = k.shape

    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t = token
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read),
            precision=_HIGHEST,
        )
        return state, jnp.einsum(
            "bhkv,bhk->bhv", state, q_t, precision=_HIGHEST
        )

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, d_k, v.shape[-1]), _F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(out, 0, 1)


@functools.lru_cache(maxsize=None)
def _levels(c: int):
    """The dyadic levels of a chunk of ``c`` tokens as constants: for each
    half-block size ``h`` = 1, 2, … c/2, ``late`` [levels, c] marks the
    tokens in the second half of their block of ``2h``, and ``pairs``
    [levels, c, c] the pairs (i, j) of one such block with ``i`` late and
    ``j`` not: every pair j < i lies in exactly one level."""
    at = np.arange(c)
    sizes = [1 << n for n in range(c.bit_length() - 1)]
    late = np.stack([(at // h) % 2 == 1 for h in sizes])
    same = np.stack([
        (at[:, None] // (2 * h)) == (at[None, :] // (2 * h)) for h in sizes
    ])
    pairs = same & late[:, :, None] & ~late[:, None, :]
    return sizes, late, pairs


def _pairwise(q, k, G, dtype):
    """``P`` (j ≤ i) and ``k``'s own strictly lower ``Σ_d k_i k_j e^{G_i −
    G_j}`` of every chunk: ``q``, ``k``, ``G`` [..., c, d_k] float32 (the
    chunk axis second to last) → two [..., c, c] float32. One batched
    product over the levels: at a level a late token's row carries
    ``e^{G_i − G_r}`` with ``r`` the first token of its own half-block,
    an early token's ``e^{G_r − G_j}`` with ``r`` the first token of the
    half-block after its own, other rows zero; both exponents are ≤ 0."""
    c, d = q.shape[-2], q.shape[-1]
    sizes, late, pairs = _levels(c)
    if not sizes:                                   # one token a chunk
        own = jnp.sum(q * k, -1)[..., None]
        return own, jnp.zeros_like(own)
    lead = G.shape[:-2]
    late_rows, early_rows = [], []
    for h, is_late in zip(sizes, late):
        blocks = G.reshape(*lead, c // h, h, d)
        starts = blocks[..., :1, :]
        on = jnp.asarray(is_late)[:, None]
        late_rows.append(jnp.exp(jnp.where(
            on, (blocks - starts).reshape(G.shape), -jnp.inf)))
        early_rows.append(jnp.exp(jnp.where(
            on, -jnp.inf,
            (jnp.roll(starts, -1, axis=-3) - blocks).reshape(G.shape))))
    late_f = jnp.stack(late_rows, -3)                         # [..., L, c, d]
    early = (k[..., None, :, :] * jnp.stack(early_rows, -3)).astype(dtype)
    rows = jnp.stack([q, k], -3)[..., None, :, :]             # [..., 2, 1, c, d]
    products = jnp.einsum(
        "...tlid,...ljd->...tlij",
        (rows * late_f[..., None, :, :, :]).astype(dtype), early,
        preferred_element_type=_F32,
    )
    both = jnp.sum(
        jnp.where(jnp.asarray(pairs), products, 0.0), axis=-3
    )                                                         # [..., 2, c, c]
    own = jnp.einsum(
        "...d,...d->...", q.astype(dtype), k.astype(dtype),
        preferred_element_type=_F32,
    )
    P = both[..., 0, :, :] + own[..., None] * jnp.eye(c, dtype=_F32)
    return P, both[..., 1, :, :]


def _inverse(a):
    """``(I + a)^-1`` for strictly lower ``a`` [..., c, c] (``c`` a power
    of two), float32, by block forward substitution with every array
    [c, c]: with ``T`` the inverse of the diagonal blocks of size ``h``
    (the identity at ``h`` = 1) and ``a_h`` the part of ``a`` under them
    inside blocks of ``2h``, the diagonal blocks of ``2h`` invert to
    ``T − T a_h T``."""
    c = a.shape[-1]
    _, _, pairs = _levels(c)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    for level in pairs:
        under = jnp.where(jnp.asarray(level), a, 0.0)
        inv = inv - jnp.einsum(
            "...ij,...jk,...kl->...il", inv, under, inv, precision=_HIGHEST
        )
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """``T = (I + a)^-1`` for strictly lower triangular ``a`` [..., c, c],
    float32; what lies on or above ``a``'s diagonal is not read."""
    return _inverse(a)


def _inverse_fwd(a):
    inv = _inverse(a)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    d_a = -jnp.einsum(
        "...ji,...jk,...lk->...il", inv, d_inv, inv, precision=_HIGHEST
    )
    return (jnp.tril(d_a, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _segment(q, k, v, g, beta, state, chunk: int):
    """Some whole chunks of a sequence, entered with ``state`` [b, h, d_k,
    d_v] float32: the shapes of :func:`kda_chunked`. Returns ``o`` in
    ``v``'s dtype and the state left."""
    b, s, h, d_k = k.shape
    d_v, dtype, n = v.shape[-1], v.dtype, s // chunk

    def chunks(a):                           # [b, s, h, ...] -> [b, n, h, c, ...]
        return jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 2, 3)

    qf, kf, vf = (chunks(a).astype(_F32) for a in (q, k, v))
    G = jnp.cumsum(chunks(g.astype(_F32)), axis=-2)
    bt = chunks(beta.astype(_F32))[..., None]                 # [b, n, h, c, 1]

    P, own = _pairwise(qf, kf, G, dtype)
    T = unit_lower_inverse(bt * own)
    decay = jnp.exp(G)
    U = jnp.einsum("...ij,...jv->...iv", T, bt * vf, precision=_HIGHEST)
    W = jnp.einsum(
        "...ij,...jk->...ik", T, bt * decay * kf, precision=_HIGHEST
    )
    to_end = kf * jnp.exp(G[..., -1:, :] - G)
    end = decay[..., -1, :]                                   # [b, n, h, d_k]

    # The recurrence over the chunks, float32: the state each chunk is
    # entered with.
    def carry(state, chunk_in):
        U_c, W_c, to_end_c, end_c = chunk_in
        w = U_c - jnp.einsum(
            "bhik,bhkv->bhiv", W_c, state, precision=_HIGHEST
        )
        left = end_c[..., None] * state + jnp.einsum(
            "bhik,bhiv->bhkv", to_end_c, w, precision=_HIGHEST
        )
        return left, (state, w)

    state, (entered, w) = jax.lax.scan(
        carry, state,
        tuple(jnp.moveaxis(a, 1, 0) for a in (U, W, to_end, end)),
    )
    entered, w = jnp.moveaxis(entered, 0, 1), jnp.moveaxis(w, 0, 1)
    out = jnp.einsum(
        "...ik,...kv->...iv", (qf * decay).astype(dtype),
        entered.astype(dtype), preferred_element_type=_F32,
    ) + jnp.einsum(
        "...ij,...jv->...iv", P.astype(dtype), w.astype(dtype),
        preferred_element_type=_F32,
    )
    out = jnp.moveaxis(out, 3, 2).reshape(b, s, h, d_v).astype(dtype)
    return out, state


def _segments(chunk: int, *arrays):
    """[b, s, ...] arrays as [segments, b, s / segments, ...]."""
    s = arrays[0].shape[1]
    count = s // (chunk * math.gcd(s // chunk, SEGMENT_CHUNKS))
    return tuple(
        jnp.moveaxis(a.reshape(a.shape[0], count, s // count, *a.shape[2:]),
                     1, 0)
        for a in arrays
    )


def _whole(a):
    """The inverse of :func:`_segments` for one array."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape(a.shape[0], a.shape[1] * a.shape[2], *a.shape[3:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunked(q, k, v, g, beta, chunk: int = 64):
    """``q``, ``k`` [b, s, h, d_k], ``v`` [b, s, h, d_v] (``q`` already
    scaled), ``g`` [b, s, h, d_k] float32 log-decays (≤ 0, unbounded
    below), ``beta`` [b, s, h] float32; ``s`` a multiple of ``chunk``, a
    power of two. Returns ``o`` [b, s, h, d_v] in ``v``'s dtype. The state
    before the first token is zero."""
    return _kda_fwd(q, k, v, g, beta, chunk)[0]


def _kda_fwd(q, k, v, g, beta, chunk):
    b, s, h, d_k = k.shape
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two")
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")

    def step(state, xs):
        out, left = _segment(*xs, state, chunk)
        return left, (out, state)

    _, (out, entered) = jax.lax.scan(
        step, jnp.zeros((b, h, d_k, v.shape[-1]), _F32),
        _segments(chunk, q, k, v, g, beta),
    )
    out = checkpoint_name(_whole(out), KEPT[0])
    entered = checkpoint_name(entered, KEPT[1])
    return out, (q, k, v, g, beta, entered)


def _kda_bwd(chunk, residuals, d_out):
    *inputs, entered = residuals

    def step(d_state, xs):
        *xs, state, d_o = xs
        _, vjp = jax.vjp(
            lambda *a: _segment(*a, chunk), *xs, state
        )
        *d_xs, d_state = vjp((d_o, d_state))
        return d_state, tuple(d_xs)

    *cut, d_cut = _segments(chunk, *inputs, d_out)
    _, grads = jax.lax.scan(
        step, jnp.zeros(entered.shape[1:], _F32), (*cut, entered, d_cut),
        reverse=True,
    )
    return tuple(_whole(a) for a in grads)


kda_chunked.defvjp(_kda_fwd, _kda_bwd)
