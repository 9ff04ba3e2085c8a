"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692): a
gated delta rule whose decay is a vector over the key dimension, in a
chunked form with no clamp and no dropped term. What a chunk needs of
itself alone, and the recurrence that carries the state from chunk to
chunk, run in Pallas kernels where the shapes fill the chip's tiles and
in plain ``jax.numpy`` elsewhere.

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

**Two implementations of the chunk-local step, chosen by the shapes.**
``U = T(βv)``, ``W = T(β e^G k)``, ``P``, ``q e^G``, ``k e^{G_C − G}`` and
``e^{G_C}`` read one chunk's ``q``, ``k``, ``v``, ``g``, ``β`` and nothing
else. :func:`_chunk_local_jnp` is the form above over all chunks at once:
its level operands and ``[chunk, chunk]`` intermediates are arrays in HBM,
about 40 times the bytes the rule needs (PERF.md §6, PR 44). Where
:func:`uses_kernels` holds (``d_k`` and ``d_v`` multiples of 128, the
chunk a multiple of 64: the published layer's [64, 128] tiles),
:func:`chunk_local` runs the same mathematics (:func:`_chunk_math`) a
chunk and head at a time on tiles in VMEM: ``kda_chunk_forward`` reads the
five inputs and writes the six results and, differentiated, the chunk's
``T``; ``kda_chunk_rebuild`` is the same body given ``T``, with no inverse
and no ``A``; ``kda_chunk_backward`` reads the inputs, ``T`` and the
results' cotangents and writes the five gradients, evaluating ``jax.vjp``
of :func:`_chunk_math` in the kernel body. Nothing with a level axis and
nothing ``[chunk, chunk]`` but ``P`` and the kept ``T`` is written to HBM.
In a tile a level's factor is ONE ``[chunk, d_k]`` array, ``e^{G_i −
G_r}`` on late rows and ``e^{G_r − G_j}`` on early ones, made from
sublane rotations of the cumulative sums; the level's pairs select their
entries from one ``[2·chunk, d_k] × [d_k, chunk]`` product of ``q`` over
``k`` stacked.

**Two implementations of the recurrence over chunk states, chosen with
it.** :func:`_across` is a ``lax.scan`` over a segment's chunks: two
float32 products a step, every chunk's entering state stacked in HBM for
one batched product to read back. Where :func:`uses_kernels` holds,
:func:`across` runs it in two more kernels over the grid (sequences,
groups of :data:`HEADS_A_STEP` heads, chunks), the chunks one after the
other: ``kda_state_forward`` loads a group's states into a VMEM scratch at
a segment's first chunk, reads the six chunk-local results as the chunk
kernels wrote them ([b, n, h, c, d]: no transposed copy), computes ``w =
U − W S``, ``o = (q e^G) S + P w`` and ``S ← e^{G_C} ⊙ S + (k e^{G_C −
G})ᵀ w`` a chunk, and writes ``o`` and, at the last chunk, the state left;
under differentiation (the backward's rebuild of ONE segment) it also
writes that segment's chunk states and ``w``, float32, which
``kda_state_backward`` reads as it walks the chunks from the last to the
first with the state's cotangent in the scratch. One ``custom_vjp`` holds
the pair, so nothing of the recurrence is differentiated by tracing and no
loop over chunks is left to XLA.

No option, field or name chooses: ``kda_chunked`` reads the shapes once
for both halves (a test may ask for either path by argument), and on a
backend that is no TPU the kernel bodies run in the Pallas interpreter.

**Precision.** ``g``, its cumulative sums, ``β``, ``A``'s inverse and the
products with it, the chunk states and their recurrence are float32 (the
state's products at ``Precision.HIGHEST``); the operands of the pairwise
products and of the two products that make ``o`` are in ``q``'s dtype with
float32 accumulation.

**The backward.** A sequence runs in segments of :data:`SEGMENT_CHUNKS`
chunks, one after the other. The forward keeps its inputs, its output,
the state each segment was entered with and, by the kernels, every
chunk's ``T`` (:data:`KEPT`, named for a checkpoint's policy as
``ops/flash_attention.KEPT`` are: a checkpointed block then runs no second
forward); the backward walks the segments from the last to the first,
rebuilds one segment's chunk quantities from its inputs and its entering
state, and differentiates that segment as written (the triangular inverse
by ``−Tᵀ dT Tᵀ``), handing the state's cotangent on. So a backward holds
one segment's intermediates — a few ``[segment, heads, d]`` float32 arrays
— and never a sequence's.

By the kernels ``T`` is a function of a chunk's ``k``, ``g`` and ``β`` that
costs 60 of the forward kernel's 84 MXU passes (ten six-pass float32
products), so it is computed ONCE: the forward pass writes it, float32, a
[64, 64] tile's two row blocks side by side in 128 lanes
(:func:`_packed`: 16 KiB a chunk and head, 134 MB a layer at 16k tokens
and 32 heads, half of what a float32 [..., 64, 64] array takes in HBM),
and a segment's rebuild (cumulative sums, the levels' factors, ``q``'s
level products for ``P``, ``U = T(βv)``, ``W = T(β e^G k)``, the three
decayed arrays: 18 passes) and its gradient kernel both read that array.
The ``jax.numpy`` form keeps no ``T`` and inverts again in its rebuild.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IMPLEMENTATION = (
    "chunked WY form, pairwise decay by dyadic levels: a chunk's work in "
    "Pallas kernels (forward and backward, each chunk's triangular "
    "inverse kept from the one for the other) and the chunk states one "
    "after the other in two more that hold the state in VMEM, where d_k "
    "and d_v are multiples of 128 and the chunk of 64; both in jax.numpy "
    "otherwise (ops/kda.py)"
)
# What makes a chunk's quantities and what carries the state over the
# chunks, by whether :func:`uses_kernels` says so.
PATHS = {
    True: "Pallas kernels kda_chunk_forward (kda_chunk_rebuild where the "
          "backward reads the kept inverses) and kda_chunk_backward; the "
          "chunk states by kda_state_forward and kda_state_backward",
    False: "jax.numpy, the chunk states by a lax.scan",
}
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# Chunks a segment: what a backward holds at once. By the kernels that is
# the six results of the chunk-local step and their cotangents, about
# 0.35 MB a chunk and head (0.36 GB at 32 heads) where the jax.numpy
# form's level operands alone were 2.5 GiB; 64 and 128 chunks a segment
# were 3% and 8% slower on the chip (PERF.md §6, PR 45).
SEGMENT_CHUNKS = 32
# What the forward keeps besides its inputs: the output, the states the
# segments were entered with, [segments, b, h, d_k, d_v] float32, and, where
# the kernels run, every chunk's triangular inverse T, float32 with no
# padded lane (:func:`_packed`): 16 KiB a chunk and head at a chunk of 64.
KEPT = ("kda_out", "kda_segment_states", "kda_chunk_inverses")


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
    # ``reshape``: a chunk of one token has no level, and no pair.
    late = np.array(
        [(at // h) % 2 == 1 for h in sizes], bool).reshape(len(sizes), c)
    same = np.array([
        (at[:, None] // (2 * h)) == (at[None, :] // (2 * h)) for h in sizes
    ], bool).reshape(len(sizes), c, c)
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


def _chunk_local_jnp(q, k, v, g, beta):
    """The chunk-local step in plain ``jax.numpy``: ``q``, ``k`` [..., c,
    d_k], ``v`` [..., c, d_v] (the chunk axis second to last), ``g`` [...,
    c, d_k] float32 log-decays, ``beta`` [..., c] float32. Returns what the
    recurrence over chunk states needs: ``U = T(βv)`` and ``W = T(β e^G
    k)`` float32, ``P`` [..., c, c] and ``q e^G`` in ``v``'s dtype, ``k
    e^{G_C − G}`` and ``e^{G_C}`` [..., d_k] float32."""
    dtype = v.dtype
    qf, kf, vf = (a.astype(_F32) for a in (q, k, v))
    G = jnp.cumsum(g.astype(_F32), axis=-2)
    bt = beta.astype(_F32)[..., None]                         # [..., c, 1]
    P, own = _pairwise(qf, kf, G, dtype)
    T = unit_lower_inverse(bt * own)
    decay = jnp.exp(G)
    U = jnp.einsum("...ij,...jv->...iv", T, bt * vf, precision=_HIGHEST)
    W = jnp.einsum(
        "...ij,...jk->...ik", T, bt * decay * kf, precision=_HIGHEST
    )
    to_end = kf * jnp.exp(G[..., -1:, :] - G)
    return (U, W, P.astype(dtype), (qf * decay).astype(dtype), to_end,
            decay[..., -1, :])


# --------------------------------------------------------------------------
# The chunk-local step as Pallas kernels. One function, :func:`_chunk_math`,
# holds the mathematics for ONE chunk of one head on [c, d] tiles in VMEM;
# the forward kernel evaluates it and the backward kernel evaluates its
# ``jax.vjp`` in the kernel body, so G, the levels' factors and A are
# rebuilt there and no per-level array leaves the chip's VMEM. Only
# operations Mosaic lowers on (8, 128) tiles: sublane rotations, selects
# by iota masks, [c, d] × [d, c] and [c, c] × [c, d] products.

# Chunk-heads a grid step: enough that a step's 0.35 µs is small beside
# its work (2 µs a chunk-head), few enough that both buffers of every
# block fit the default scoped VMEM (5 MB in the backward). 16 a step, and
# 2 or 4 of them unrolled into one straight line, were within 1.5% on the
# chip (PERF.md §6, PR 45).
CHUNKS_A_STEP = 8


def uses_kernels(d_k: int, d_v: int, chunk: int) -> bool:
    """Whether :func:`kda_chunked` takes the Pallas kernels at these
    shapes, read from the shapes alone: a head's keys and values fill
    whole 128-lane registers, and a chunk whole sublane tiles in either
    dtype with [chunk, chunk] tiles of at least half a register's lanes.
    Smaller chunks keep the ``jax.numpy`` form, which batches them all
    into one product."""
    return d_k % 128 == 0 and d_v % 128 == 0 and chunk % 64 == 0


def _interpret() -> bool:
    # Mosaic compiles the kernels for a TPU; elsewhere the same bodies run
    # in the Pallas interpreter (as ``ops/stream_mix.py``).
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _shift(x, by: int):
    """Row ``i`` of the result is row ``i − by`` of ``x`` [c, d], around
    the end (a sublane rotation)."""
    return pltpu.roll(x, by % x.shape[0], 0)


_shift.defvjp(
    lambda x, by: (_shift(x, by), None),
    lambda by, _, d: (_shift(d, -by),),
)


def _dot(a, b, contract):
    """A product with float32 accumulation; float32 operands multiply to
    float32's accuracy."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=_HIGHEST if a.dtype == _F32 else None,
        preferred_element_type=_F32,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pairs(x, y, dtype):
    """``x yᵀ`` and ``y yᵀ`` for float32 ``x``, ``y`` [c, d], the operands
    rounded to ``dtype``: ONE product of ``x`` over ``y`` stacked, so
    ``yᵀ`` is loaded once. The cotangents come back float32, unrounded."""
    return _pairs_fwd(x, y, dtype)[0]


def _pairs_fwd(x, y, dtype):
    c = x.shape[0]
    y = y.astype(dtype)
    stacked = jnp.concatenate([x.astype(dtype), y], axis=0)
    both = _dot(stacked, y, (1, 1))
    return (both[:c], both[c:]), (stacked, y)


def _pairs_bwd(dtype, res, d):
    stacked, y = res
    c = y.shape[0]
    d = jnp.concatenate(d, axis=0).astype(dtype)
    d_stacked = _dot(d, y, (1, 0))
    return d_stacked[:c], d_stacked[c:] + _dot(d, stacked, (0, 0))


_pairs.defvjp(_pairs_fwd, _pairs_bwd)


def _level_masks(c: int):
    """For each half-block size ``h``: the pairs (i, j) [c, c] of one
    block of ``2h`` with ``i`` in its second half and ``j`` in its first
    (:func:`_levels`' ``pairs``, from iotas: the highest bit in which i
    and j differ is ``h``, and i has it)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    apart = jnp.bitwise_xor(i, j)
    return [
        (1 << n, (jnp.right_shift(apart, n) == 1) & (i > j))
        for n in range(c.bit_length() - 1)
    ]


@jax.custom_vjp
def _inverse_tile(a):
    """:func:`_inverse` on one [c, c] tile. Level 1 needs no product:
    ``I − I a_1 I``."""
    c = a.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    inv = jnp.where(i == j, 1.0, 0.0).astype(_F32)
    for h, pairs in _level_masks(c):
        under = jnp.where(pairs, a, 0.0)
        inv = inv - (
            under if h == 1
            else _dot(_dot(inv, under, (1, 0)), inv, (1, 0))
        )
    return inv


def _inverse_tile_fwd(a):
    inv = _inverse_tile(a)
    return inv, inv


def _inverse_tile_bwd(inv, d_inv):
    c = inv.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    d_a = _dot(_dot(inv, d_inv, (0, 0)), inv, (1, 1))
    return (jnp.where(i > j, -d_a, 0.0),)


_inverse_tile.defvjp(_inverse_tile_fwd, _inverse_tile_bwd)


@jax.custom_vjp
def _kept_inverse(a, inv):
    """``inv``, which IS ``(I + a)^-1`` (the forward kernel's, kept for
    the backward): differentiated as :func:`_inverse_tile` of ``a``."""
    return inv


_kept_inverse.defvjp(
    lambda a, inv: (inv, inv),
    lambda inv, d: (*_inverse_tile_bwd(inv, d), jnp.zeros_like(inv)),
)


def _chunk_math(q, k, v, g, beta, dtype, inverse=None, values_only=False):
    """One chunk of one head: ``q``, ``k`` [c, d_k] and ``v`` [c, d_v]
    float32 (values of ``dtype``), ``g`` [c, d_k] float32, ``beta`` [1, c]
    float32 → :func:`_chunk_local_jnp`'s six results (``e^{G_C}`` as a
    [1, d_k] row) and ``T``, which a caller that kept it hands back as
    ``inverse``: ``A`` is then read by the derivative alone, and a caller
    that takes ``values_only`` leaves its half of the level products out.
    The pairwise factor at level ``h`` is ONE array: with
    ``B`` the cumulative sum at the first token of a token's own block of
    ``h``, a late token carries ``e^{G_i − B_i}`` and an early one
    ``e^{B_{j+h} − G_j}`` (``B`` of the next block), both exponents ≤ 0;
    what a row carries at a level where it is neither is masked out of
    the product by the level's pairs."""
    c, d_k = k.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (c, d_k), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    levels = _level_masks(c)

    G = g                                    # the inclusive cumulative sum
    for h, _ in levels:
        G = G + jnp.where(row >= h, _shift(G, h), 0.0)

    P = own = jnp.zeros((c, c), _F32)
    start = G
    for h, pairs in levels:
        late = jnp.bitwise_and(row, h) != 0
        # The last block of h is a late one: no row reads around the end.
        factor = jnp.exp(jnp.where(late, G - start, _shift(start, -h) - G))
        if values_only:
            of_q = _dot((q * factor).astype(dtype),
                        (k * factor).astype(dtype), (1, 1))
        else:
            of_q, of_k = _pairs(q * factor, k * factor, dtype)
            own = jnp.where(pairs, of_k, own)
        P = jnp.where(pairs, of_q, P)
        start = jnp.where(late, _shift(start, h), start)
    P = jnp.where(i == j, jnp.sum(q * k, axis=1, keepdims=True), P)

    bt = jnp.sum(jnp.where(i == j, beta, 0.0), axis=1, keepdims=True)
    if inverse is None:
        T = _inverse_tile(bt * own)
    else:
        T = inverse if values_only else _kept_inverse(bt * own, inverse)
    decay = jnp.exp(G)
    U = _dot(T, bt * v, (1, 0))
    W = _dot(T, bt * decay * k, (1, 0))
    last = jnp.sum(jnp.where(row == c - 1, G, 0.0), axis=0, keepdims=True)
    return (U, W, P.astype(dtype), (q * decay).astype(dtype),
            k * jnp.exp(last - G), jnp.exp(last), T)


def _tiles(refs, at):
    """A chunk-head's operands from the blocks of a grid step: ``q``,
    ``k``, ``v`` float32, ``g``, and ``beta`` as a [1, c] row."""
    q, k, v, g, beta = refs
    return (*(r[at].astype(_F32) for r in (q, k, v)), g[at],
            beta[0, pl.ds(at, 1), :])


def _fold(c: int) -> int:
    """Row blocks of a [c, c] float32 tile that lie side by side in one
    row of 128 lanes: 2 at a chunk of 64, 1 from 128 on."""
    return min(c, max(1, 128 // c))


def _packed(T):
    """``T`` [c, c] as it is kept in HBM, [c / fold, c · fold]: its row
    blocks side by side, so that a chunk of 64 fills 128-lane registers
    (a [64, 64] float32 array there takes the room of [64, 128])."""
    rows = T.shape[0] // _fold(T.shape[0])
    return jnp.concatenate(
        [T[at:at + rows] for at in range(0, T.shape[0], rows)], axis=1
    )


def _unpacked(kept):
    """The inverse of :func:`_packed`."""
    rows, wide = kept.shape
    c = math.isqrt(rows * wide)
    return jnp.concatenate(
        [kept[:, at:at + c] for at in range(0, wide, c)], axis=0
    )


def _forward_kernel(*refs, reads: bool):
    """q, k, v, g, beta → U, W, P, q e^G, k e^{G_C − G}, e^{G_C}. The
    chunk's T is read (``reads``: the block after beta's) and not
    inverted again, or written where the call has a block for it."""
    ins, kept, outs = refs[:5], refs[5:5 + reads], refs[5 + reads:]
    dtype = ins[2].dtype

    def chunk(at, _):
        *full, end, inverse = _chunk_math(
            *_tiles(ins, at), dtype,
            inverse=_unpacked(kept[0][at]) if reads else None,
            values_only=reads,
        )
        for ref, a in zip(outs, full):
            ref[at] = a
        outs[5][0, pl.ds(at, 1), :] = end
        if len(outs) > 6:
            outs[6][at] = _packed(inverse)
        return 0

    jax.lax.fori_loop(0, ins[0].shape[0], chunk, 0)


def _backward_kernel(*refs):
    """q, k, v, g, beta, T and the six results' cotangents → dq, dk, dv,
    dg, dbeta: the ``jax.vjp`` of :func:`_chunk_math` on a chunk's tiles."""
    ins, kept, cots, outs = refs[:5], refs[5], refs[6:12], refs[12:]
    dtype = ins[2].dtype

    def chunk(at, _):
        inverse = _unpacked(kept[at])
        _, vjp = jax.vjp(
            lambda *a: _chunk_math(*a, dtype, inverse=inverse)[:6],
            *_tiles(ins, at),
        )
        *d_full, d_beta = vjp((
            *(ref[at] for ref in cots[:5]), cots[5][0, pl.ds(at, 1), :]
        ))
        for ref, a in zip(outs, d_full):
            ref[at] = a.astype(ref.dtype)
        outs[4][0, pl.ds(at, 1), :] = d_beta
        return 0

    jax.lax.fori_loop(0, ins[0].shape[0], chunk, 0)


def _call(kernel, name, operands, outputs, interpret: bool):
    """``kernel`` over the chunk-heads of ``operands`` ([..., c, d]
    tiles; [..., d] rows go as [steps, chunk-heads a step, d]), every
    grid step independent."""
    lead = operands[0].shape[:-2]
    count = math.prod(lead)
    step = math.gcd(count, CHUNKS_A_STEP)

    def flat(a):
        if len(a.shape) == len(lead) + 2:
            return (count, *a.shape[-2:])
        return (count // step, step, a.shape[-1])

    def spec(a):
        first, *rest = flat(a)
        return pl.BlockSpec(
            (first * step // count, *rest), lambda at: (at, 0, 0)
        )

    def run(*operands):
        return pl.pallas_call(
            kernel,
            out_shape=[
                jax.ShapeDtypeStruct(flat(a), a.dtype) for a in outputs
            ],
            grid=(count // step,),
            in_specs=[spec(a) for a in operands],
            out_specs=[spec(a) for a in outputs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
            name=name,
        )(*(a.reshape(flat(a)) for a in operands))

    run.__name__ = name
    results = (run if interpret else jax.jit(run))(*operands)
    return tuple(r.reshape(a.shape) for r, a in zip(results, outputs))


def inverses_shape(lead, chunk: int):
    """The kept T of chunk-heads ``lead``: [*lead, c / fold, c · fold]."""
    fold = _fold(chunk)
    return (*lead, chunk // fold, chunk * fold)


def _forward_call(q, k, v, g, beta, interpret: bool, keep: bool = False,
                  inverses=None):
    """The chunk-local step's six results by the forward kernel: with
    ``keep`` the chunks' T as a seventh, with ``inverses`` (a ``keep``
    call's seventh) T read in place of the inverse's products."""
    like = jax.ShapeDtypeStruct
    lead, c = k.shape[:-2], k.shape[-2]
    results = (
        like(v.shape, _F32), like(k.shape, _F32), like((*lead, c, c), v.dtype),
        like(q.shape, v.dtype), like(k.shape, _F32),
        like((*lead, k.shape[-1]), _F32),
    )
    if keep:
        results += (like(inverses_shape(lead, c), _F32),)
    reads = inverses is not None
    return _call(
        functools.partial(_forward_kernel, reads=reads),
        "kda_chunk_rebuild" if reads else "kda_chunk_forward",
        (q, k, v, g, beta) + ((inverses,) if reads else ()), results,
        interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunk_local(q, k, v, g, beta, inverses, interpret: bool):
    return _forward_call(q, k, v, g, beta, interpret, inverses=inverses)


def _chunk_local_fwd(q, k, v, g, beta, inverses, interpret):
    results = _forward_call(q, k, v, g, beta, interpret, inverses=inverses)
    return results, (q, k, v, g, beta, inverses)


def _chunk_local_bwd(interpret, kept, cotangents):
    like = jax.ShapeDtypeStruct
    grads = _call(
        _backward_kernel, "kda_chunk_backward", (*kept, *cotangents),
        tuple(like(a.shape, a.dtype) for a in kept[:5]), interpret,
    )
    return (*grads, jnp.zeros_like(kept[5]))


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


def chunk_local(q, k, v, g, beta, inverses):
    """:func:`_chunk_local_jnp` by the Pallas kernels, given the chunks'
    ``T`` as the forward pass kept it (:func:`_forward_call`'s seventh
    result): the same results, one ``custom_vjp`` that inverts nothing,
    forward or backward. Compiled by Mosaic on a TPU, run in the Pallas
    interpreter anywhere else."""
    return _chunk_local(q, k, v, g, beta, inverses, _interpret())


def _chunks(chunk: int, q, k, v, g, beta):
    """[b, s, h, ...] arrays as the chunk-local step takes them, [b, n, h,
    c, ...], ``g`` and ``beta`` float32."""
    return tuple(
        jnp.moveaxis(
            a.reshape(a.shape[0], -1, chunk, *a.shape[2:]), 2, 3)
        for a in (q, k, v, g.astype(_F32), beta.astype(_F32))
    )


def _across(local, state, dtype):
    """Some whole chunks of a sequence from their chunk-local step's six
    results ``local`` [b, n, h, ...], entered with ``state`` [b, h, d_k,
    d_v] float32: ``o`` [b, n · c, h, d_v] in ``dtype`` and the state
    left."""
    U, W, P, q_decayed, to_end, end = local

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
        "...ik,...kv->...iv", q_decayed, entered.astype(dtype),
        preferred_element_type=_F32,
    ) + jnp.einsum(
        "...ij,...jv->...iv", P, w.astype(dtype),
        preferred_element_type=_F32,
    )
    b, n, h, c, d_v = out.shape
    out = jnp.moveaxis(out, 3, 2).reshape(b, n * c, h, d_v).astype(dtype)
    return out, state


# --------------------------------------------------------------------------
# The recurrence over chunk states as Pallas kernels. A grid step holds ONE
# chunk of :data:`HEADS_A_STEP` heads; the chunk axis is the grid's last and
# runs in order, so a head's state stays in a VMEM scratch from a segment's
# first chunk to its last, and no chunk's state is written to HBM but where
# the backward reads it. The state lies TRANSPOSED there, [d_v, d_k]: a
# chunk's decay is then a [1, d_k] row over its sublanes, the decay's
# cotangent a sum over them, and no [1, d] row has to become a column.

# Heads a grid step: blocks of 0.13 MB a head in the forward pass and 0.34
# in the backward, both buffers of each, and 64 KiB a head of scratch.
HEADS_A_STEP = 8


def _state_forward_kernel(*refs, keeps: bool):
    """U, W, P, q e^G, k e^{G_C − G}, e^{G_C} of a chunk and the state the
    segment is entered with → o, where the call ``keeps`` them for the
    backward the state the chunk is entered with (transposed) and its w,
    and the state the segment leaves."""
    U, W, P, q_decayed, to_end, end, entering = refs[:7]
    (out, *kept), left, state = refs[7:-2], refs[-2], refs[-1]
    dtype, heads = out.dtype, range(state.shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        for at in heads:
            state[at] = entering[at].T

    # Every head's W·S first, then every o, then every update: a group's
    # heads are independent, and side by side their six-pass products keep
    # the MXUs fed where one head's chain W·S → w → state leaves them
    # waiting (PERF.md §6, PR 48).
    ws = [U[at] - _dot(W[at], state[at], (1, 1)) for at in heads]
    for at in heads:
        out[at] = (
            _dot(q_decayed[at], state[at].astype(dtype), (1, 1))
            + _dot(P[at], ws[at].astype(dtype), (1, 0))
        ).astype(dtype)
        if keeps:
            kept[0][at], kept[1][at] = state[at], ws[at]
    for at in heads:
        state[at] = (end[at:at + 1, :] * state[at]
                     + _dot(ws[at], to_end[at], (0, 0)))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        for at in heads:
            left[at] = state[at].T


def _state_backward_kernel(*refs):
    """A chunk's W, P, q e^G, k e^{G_C − G}, e^{G_C}, the state it was
    entered with and its w as the forward kept them, o's cotangent and the
    cotangent of the state the segment leaves → the six results'
    cotangents and the entering state's; the grid walks the chunks from
    the last to the first."""
    W, P, q_decayed, to_end, end, entered, w, d_out, d_left = refs[:9]
    dU, dW, dP, dq_decayed, dto_end, dend, d_entering, d_state = refs[9:]
    dtype, heads = d_out.dtype, range(d_state.shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        for at in heads:
            d_state[at] = d_left[at].T

    for at in heads:
        dU[at] = (_dot(P[at], d_out[at], (0, 0))
                  + _dot(to_end[at], d_state[at], (1, 1)))
    for at in heads:
        S, dS, d_o = entered[at], d_state[at], d_out[at]
        dW[at] = -_dot(dU[at], S, (1, 0))
        dq_decayed[at] = _dot(d_o, S.astype(dtype), (1, 0)).astype(dtype)
        dP[at] = _dot(d_o, w[at].astype(dtype), (1, 1)).astype(dtype)
        dto_end[at] = _dot(w[at], dS, (1, 0))
        dend[at:at + 1, :] = jnp.sum(S * dS, axis=0, keepdims=True)
    for at in heads:
        d_state[at] = (
            end[at:at + 1, :] * d_state[at]
            + _dot(d_out[at], q_decayed[at], (0, 0))
            - _dot(dU[at], W[at], (0, 0))
        )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        for at in heads:
            d_entering[at] = d_state[at].T


def state_heads(h: int) -> int:
    """Heads a grid step of the state kernels: :data:`HEADS_A_STEP` or
    the largest divisor of ``h`` in it and, where that is no whole tile
    of 8 sublanes (the block of a group's [heads, d_k] decay rows), all
    ``h``."""
    heads = math.gcd(h, HEADS_A_STEP)
    return heads if heads % 8 == 0 else h


def _state_call(kernel, name, operands, outputs, reverse: bool,
                interpret: bool):
    """``kernel`` over a segment, grid (sequences, groups of heads,
    chunks), the chunks one after the other (from the last with
    ``reverse``). Of ``operands`` and of ``outputs`` the last is a state,
    [b, h, d_k, d_v], whose block stays for a group's whole walk; the
    others are [b, n, h, ...] as the chunk kernels write them and go a
    chunk of the group's heads a step. The scratch is the group's states,
    float32, transposed."""
    b, n, h = operands[0].shape[:3]
    heads = state_heads(h)
    d_k, d_v = operands[-1].shape[2:]

    def specs(arrays):
        *walked, state = arrays
        return [
            pl.BlockSpec(
                (None, None, heads, *a.shape[3:]),
                lambda i, j, t, zeros=(0,) * (len(a.shape) - 3): (
                    i, n - 1 - t if reverse else t, j, *zeros),
            ) for a in walked
        ] + [pl.BlockSpec(
            (None, heads, *state.shape[2:]), lambda i, j, t: (i, j, 0, 0)
        )]

    def run(*operands):
        return pl.pallas_call(
            kernel,
            out_shape=list(outputs),
            grid=(b, h // heads, n),
            in_specs=specs(operands),
            out_specs=specs(outputs),
            scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            name=name,
        )(*operands)

    run.__name__ = name
    return tuple((run if interpret else jax.jit(run))(*operands))


def _state_forward_call(local, state, interpret: bool, keeps: bool):
    """``o`` [b, n, h, c, d_v] in ``P``'s dtype, with ``keeps`` every
    chunk's entering state [b, n, h, d_v, d_k] and w [b, n, h, c, d_v],
    float32, and the state left."""
    like = jax.ShapeDtypeStruct
    U, _, P = local[:3]
    d_k, d_v = state.shape[2:]
    kept = (
        like((*U.shape[:3], d_v, d_k), _F32), like(U.shape, _F32)
    ) if keeps else ()
    return _state_call(
        functools.partial(_state_forward_kernel, keeps=keeps),
        "kda_state_forward", (*local, state),
        (like(U.shape, P.dtype), *kept, like(state.shape, _F32)),
        False, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _states(U, W, P, q_decayed, to_end, end, state, interpret: bool):
    return _state_forward_call(
        (U, W, P, q_decayed, to_end, end), state, interpret, keeps=False)


def _states_fwd(U, W, P, q_decayed, to_end, end, state, interpret):
    out, entered, w, left = _state_forward_call(
        (U, W, P, q_decayed, to_end, end), state, interpret, keeps=True)
    return (out, left), (W, P, q_decayed, to_end, end, entered, w)


def _states_bwd(interpret, kept, cotangents):
    like = jax.ShapeDtypeStruct
    W, P, q_decayed, to_end, end, _, w = kept
    d_out, d_left = cotangents
    return _state_call(
        _state_backward_kernel, "kda_state_backward",
        (*kept, d_out, d_left),
        tuple(like(a.shape, a.dtype)
              for a in (w, W, P, q_decayed, to_end, end, d_left)),
        True, interpret,
    )


_states.defvjp(_states_fwd, _states_bwd)


def across(local, state):
    """:func:`_across` by the Pallas kernels: the same two results, the
    state in VMEM from a segment's first chunk to its last, and one
    ``custom_vjp`` whose forward keeps a segment's chunk states and w and
    whose backward is the second kernel, so nothing of the recurrence is
    differentiated by tracing. Compiled by Mosaic on a TPU, run in the
    Pallas interpreter anywhere else."""
    out, left = _states(*local, state, _interpret())
    b, n, h, c, d_v = out.shape
    return jnp.moveaxis(out, 3, 2).reshape(b, n * c, h, d_v), left


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


class Rule(NamedTuple):
    """What the walk over segments needs of a delta rule, as functions of
    a segment's chunks ``q``, ``k``, ``v``, ``g``, ``beta`` [b, n, h, c,
    ...] (:func:`_chunks`): ``local(*xs, keep)`` the chunk-local step's six
    results ``U``, ``W``, ``P``, ``q e^G``, ``k e^{G_C − G}``, ``e^{G_C}``
    and, with ``keep``, whatever else the backward reads again;
    ``rebuild(*xs, *kept)`` the six from the inputs and that;
    ``across(six, state, dtype)`` the output and the state left; ``names``
    what the forward keeps besides its inputs, for a checkpoint's policy.
    Hashable: the static argument of :func:`segment_walk`. The two rules
    of this module name their functions when they are called (a test
    stands in for one by name)."""

    local: Callable
    rebuild: Callable
    across: Callable
    names: Tuple[str, ...]


KERNELS = Rule(
    lambda *xs, keep: _forward_call(*xs, _interpret(), keep),
    lambda *xs: chunk_local(*xs),
    lambda six, state, dtype: across(six, state),
    KEPT,
)
PLAIN = Rule(
    lambda *xs, keep: _chunk_local_jnp(*xs),
    lambda *xs: _chunk_local_jnp(*xs),
    lambda six, state, dtype: _across(six, state, dtype),
    KEPT,
)


def kda_chunked(q, k, v, g, beta, chunk: int = 64, kernels=None):
    """``q``, ``k`` [b, s, h, d_k], ``v`` [b, s, h, d_v] (``q`` already
    scaled), ``g`` [b, s, h, d_k] float32 log-decays (≤ 0, unbounded
    below), ``beta`` [b, s, h] float32; ``s`` a multiple of ``chunk``, a
    power of two. Returns ``o`` [b, s, h, d_v] in ``v``'s dtype. The state
    before the first token is zero. ``kernels`` asks for the Pallas
    kernels (true) or the ``jax.numpy`` form (false) of the chunk-local
    step whatever the shapes, as a test does; left out,
    :func:`uses_kernels` reads it from the shapes."""
    if kernels is None:
        kernels = uses_kernels(k.shape[-1], v.shape[-1], chunk)
    return _kda(q, k, v, g, beta, chunk, bool(kernels))


def _kda(q, k, v, g, beta, chunk, kernels):
    return segment_walk(q, k, v, g, beta, chunk, KERNELS if kernels else PLAIN)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def segment_walk(q, k, v, g, beta, chunk, rule):
    """A chunked delta rule over a whole sequence, ``rule`` (a
    :class:`Rule`) saying what a chunk costs of itself: the segments one
    after the other, and a backward that rebuilds ONE segment at a time
    from its inputs and its entering state. ``g`` is whatever ``rule``
    reads (a decay a channel here, one a head in ``ops/gdn.py``)."""
    return _forward(q, k, v, g, beta, chunk, rule, keep=False)[0]


def _forward(q, k, v, g, beta, chunk, rule, keep: bool):
    """``o``, the states the segments were entered with and, with
    ``keep``, whatever else ``rule.local`` keeps of every segment."""
    b, s, h, d_k = k.shape
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two")
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")

    def step(state, xs):
        local = rule.local(*_chunks(chunk, *xs), keep=keep)
        out, left = rule.across(local[:6], state, v.dtype)
        return left, (out, state, *local[6:])

    _, (out, *kept) = jax.lax.scan(
        step, jnp.zeros((b, h, d_k, v.shape[-1]), _F32),
        _segments(chunk, q, k, v, g, beta),
    )
    return _whole(out), *kept


def _walk_fwd(q, k, v, g, beta, chunk, rule):
    out, *kept = (
        checkpoint_name(a, name) for a, name in zip(
            _forward(q, k, v, g, beta, chunk, rule, keep=True), rule.names)
    )
    return out, (q, k, v, g, beta, *kept)


def _walk_bwd(chunk, rule, residuals, d_out):
    q, k, v, g, beta, entered, *kept = residuals

    def step(d_state, xs):
        (*xs, d_o), (state, *inverses) = xs[:6], xs[6:]

        def segment(*a):
            *a, state = a
            return rule.across(
                rule.rebuild(*_chunks(chunk, *a), *inverses), state, v.dtype
            )

        _, vjp = jax.vjp(segment, *xs, state)
        *d_xs, d_state = vjp((d_o, d_state))
        return d_state, tuple(d_xs)

    _, grads = jax.lax.scan(
        step, jnp.zeros(entered.shape[1:], _F32),
        (*_segments(chunk, q, k, v, g, beta, d_out), entered, *kept),
        reverse=True,
    )
    return tuple(_whole(a) for a in grads)


segment_walk.defvjp(_walk_fwd, _walk_bwd)
