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

**Two implementations, chosen by the shapes.** ``U = T(βv)``, ``W = T(β
e^G k)``, ``P``, ``q e^G``, ``k e^{G_C − G}`` and ``e^{G_C}`` read one
chunk's ``q``, ``k``, ``v``, ``g``, ``β`` and nothing else.
:func:`_chunk_local_jnp` is the form above over all chunks at once (its
level operands and ``[chunk, chunk]`` intermediates are arrays in HBM,
about 40 times the bytes the rule needs: PERF.md §6, PR 44), and
:func:`_across` carries the state by a ``lax.scan`` over a segment's
chunks. Where :func:`uses_kernels` holds (``d_k`` and ``d_v`` multiples
of 128, the chunk a multiple of 64: the published layer's [64, 128]
tiles), five Pallas kernels run the same mathematics on tiles in VMEM.
``kda_chunk_forward`` evaluates :func:`_chunk_math` a chunk and head at a
time and writes the six results and, differentiated, the chunk's ``T``;
``kda_chunk_rebuild`` is the same body given ``T``, with no inverse and
no ``A``; ``kda_chunk_backward`` evaluates ``jax.vjp`` of
:func:`_chunk_math` in its body. Nothing with a level axis and nothing
``[chunk, chunk]`` but ``P`` and the kept ``T`` is written to HBM. In a
tile a level's factor is ONE ``[chunk, d_k]`` array made from sublane
rotations of the cumulative sums, and the level's pairs select their
entries from one ``[2·chunk, d_k] × [d_k, chunk]`` product of ``q`` over
``k`` stacked. ``kda_state_forward`` runs the recurrence over the grid
(sequences, groups of :data:`HEADS_A_STEP` heads, chunks), the chunks one
after the other and a group's states in a VMEM scratch: ``w = U − W S``,
``o = (q e^G) S + P w``, ``S ← e^{G_C} ⊙ S + (k e^{G_C − G})ᵀ w`` a
chunk; in the backward's rebuild of ONE segment it also writes that
segment's chunk states and ``w``, float32, which ``kda_state_backward``
reads as it walks the chunks from the last to the first. Nothing of the
recurrence is differentiated by tracing and no loop over chunks is left
to XLA. No option, field or name chooses: ``kda_chunked`` reads the
shapes once for both halves (a test may ask for either path by
argument), and on a backend that is no TPU the kernel bodies run in the
Pallas interpreter.

**Who owns the layout.** A :class:`Rule` does, and with it the loop over
the segments. The kernels' rule (:data:`KERNELS`) reads and writes the
MODEL's arrays: ``q``, ``k``, ``v``, ``g``, ``o`` and their cotangents
are [b, s, h · d] (what ``QKVConv`` and the dense layers write: a reshape
of [b, s, h, d], no copy), a grid step's block a chunk's rows × the lanes
of a group of heads, a head inside it a slice of whole 128-lane registers
(wherever :func:`uses_kernels` holds there is no other kind). The
segment's index is a scalar-prefetch argument of every call's index
maps, so the loops scan the segments' INDICES and carry the state and
the arrays being written — ``o``, every chunk's ``T``, the five
gradients — which each call writes into in place; nothing of the
sequence's size is copied, sliced, stacked or transposed by XLA, forward
or backward. Only ``beta`` (1/128 of ``k``'s bytes) is read from a
chunk-major copy. What the kernels leave to one another — the six
results, the chunk states, ``w``, ``T`` — stays chunk-major, [b, n, h,
...]. A rule whose chunk step wants chunk-major INPUTS
(:func:`chunk_major`: :data:`PLAIN` here, both of ``ops/gdn.py``'s, whose
heads of 96 and 192 lanes are no whole registers) scans the segments'
slices and copies each to [b, n, h, c, ...], and every gradient back.

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
state, and differentiates that segment (the kernels' rule by its two
backward kernels, a chunk-major one as written; the triangular inverse by
``−Tᵀ dT Tᵀ``), handing the state's cotangent on. So a backward holds one
segment's intermediates — a few ``[segment, heads, d]`` float32 arrays —
and never a sequence's.

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
    "after the other in two more that hold the state in VMEM, all five on "
    "blocks of the model's own [b, s, h·d] arrays, where d_k and d_v are "
    "multiples of 128 and the chunk of 64; both in jax.numpy on "
    "chunk-major copies otherwise (ops/kda.py)"
)
# What makes a chunk's quantities and what carries the state over the
# chunks, by whether :func:`uses_kernels` says so; the rule that runs owns
# the layout.
PATHS = {
    True: "Pallas kernels kda_chunk_forward (kda_chunk_rebuild where the "
          "backward reads the kept inverses) and kda_chunk_backward; the "
          "chunk states by kda_state_forward and kda_state_backward; "
          "q, k, v, g, o and their gradients read and written in place "
          "as [b, s, h·d], the segment's index a scalar-prefetch argument",
    False: "jax.numpy, the chunk states by a lax.scan, each segment "
           "copied chunk-major",
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


def _head(ref, at, heads: int):
    """Where head ``at`` of a grid step's ``heads`` lies in its block: the
    tile ``at`` of a chunk-major block [heads, c, d], or, in a block
    [c, heads · d] of the model's own array, the head's ``d`` lanes (whole
    128-lane registers wherever Mosaic compiles the kernels)."""
    if len(ref.shape) == 3:
        return (at,)
    d = ref.shape[1] // heads
    first = at * d if isinstance(at, int) else pl.multiple_of(at * d, d)
    return (slice(None), pl.ds(first, d))


def _tiles(refs, at):
    """A chunk-head's operands from the blocks of a grid step: ``q``,
    ``k``, ``v`` and ``g`` float32, and ``beta`` as a [1, c] row."""
    *wide, beta = refs
    return (*(r[_head(r, at, beta.shape[0])].astype(_F32) for r in wide),
            beta[pl.ds(at, 1), :])


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

    def head(at, _):
        *full, end, inverse = _chunk_math(
            *_tiles(ins, at), dtype,
            inverse=_unpacked(kept[0][at]) if reads else None,
            values_only=reads,
        )
        for ref, a in zip(outs, full):
            ref[at] = a
        outs[5][pl.ds(at, 1), :] = end
        if len(outs) > 6:
            outs[6][at] = _packed(inverse)
        return 0

    jax.lax.fori_loop(0, ins[4].shape[0], head, 0)


def _backward_kernel(*refs):
    """q, k, v, g, beta, T and the six results' cotangents → dq, dk, dv,
    dg, dbeta: the ``jax.vjp`` of :func:`_chunk_math` on a chunk's tiles."""
    ins, kept, cots, outs = refs[:5], refs[5], refs[6:12], refs[12:]
    dtype, heads = ins[2].dtype, ins[4].shape[0]

    def head(at, _):
        inverse = _unpacked(kept[at])
        _, vjp = jax.vjp(
            lambda *a: _chunk_math(*a, dtype, inverse=inverse)[:6],
            *_tiles(ins, at),
        )
        *d_wide, d_beta = vjp((
            *(ref[at] for ref in cots[:5]), cots[5][pl.ds(at, 1), :]
        ))
        for ref, a in zip(outs, d_wide):
            ref[_head(ref, at, heads)] = a.astype(ref.dtype)
        outs[4][pl.ds(at, 1), :] = d_beta
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


class Segment(NamedTuple):
    """Which part of a sequence a kernel call runs: segment ``at`` (a
    traced index) of ``chunks`` chunks of ``chunk`` tokens each."""

    at: jax.Array
    chunks: int
    chunk: int


def step_heads(h: int, most: int) -> int:
    """Heads a grid step: ``most`` or the largest divisor of ``h`` in it
    and, where that is no whole tile of 8 sublanes (the block of a
    group's [heads, d_k] decay rows), all ``h``."""
    heads = math.gcd(h, most)
    return heads if heads % 8 == 0 else h


def _call(kernel, name, where: Segment, operands, outputs, h: int, most: int,
          walk=None, interpret: bool = False):
    """``kernel`` over segment ``where``, a chunk of :func:`step_heads`
    of the ``h`` heads a grid step, each array's blocks found by what it
    is. [b, s, h · d]: the model's array of the whole sequence, a block a
    chunk's rows × the group's lanes, found by the segment's index (the
    scalar-prefetch argument). [b, chunks, h, ...]: chunk-major, the
    segment's chunks or (longer) the whole sequence's. With ``walk`` the
    chunks are the grid's last axis and run in order (``True``: from the
    last), the last operand and result are states [b, h, d_k, d_v] whose
    blocks stay for a group's walk, and the scratch is the group's states,
    float32, transposed; without it every grid step is independent. A
    result given as an ARRAY is written into, other segments' blocks left
    as they are."""
    b, n, heads = operands[0].shape[0], where.chunks, step_heads(h, most)

    def spec(a, state: bool):
        zeros = (0,) * (len(a.shape) - 3)

        def index(*grid):
            *ids, segment = grid
            i, t, j = ids if walk is None else (
                ids[0], n - 1 - ids[2] if walk else ids[2], ids[1])
            if state:
                return i, j, 0, 0
            if len(a.shape) == 3:
                return i, segment[0] * n + t, j
            return i, t if a.shape[1] == n else segment[0] * n + t, j, *zeros

        if state:
            return pl.BlockSpec((None, heads, *a.shape[2:]), index)
        if len(a.shape) == 3:
            return pl.BlockSpec(
                (None, where.chunk, a.shape[2] // h * heads), index)
        return pl.BlockSpec((None, None, heads, *a.shape[3:]), index)

    def specs(arrays):
        return [spec(a, walk is not None and a is arrays[-1])
                for a in arrays]

    into = {at: a for at, a in enumerate(outputs)
            if not isinstance(a, jax.ShapeDtypeStruct)}
    taken = len(operands)

    def run(at, *operands):
        return pl.pallas_call(
            lambda _, *refs: kernel(
                *refs[:taken], *refs[taken + len(into):]),
            out_shape=[
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in outputs],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, n, h // heads) if walk is None
                else (b, h // heads, n),
                in_specs=specs(operands[:taken])
                + [pl.BlockSpec(memory_space=pl.ANY)] * len(into),
                out_specs=specs(outputs),
                scratch_shapes=[] if walk is None else [pltpu.VMEM(
                    (heads, *operands[taken - 1].shape[:1:-1]), _F32)],
            ),
            input_output_aliases={
                1 + taken + order: at for order, at in enumerate(into)},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3 if walk is None
                else ("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
            name=name,
        )(at, *operands)

    run.__name__ = name
    return tuple((run if interpret else jax.jit(run))(
        jnp.reshape(where.at, (1,)).astype(jnp.int32), *operands,
        *into.values()))


def inverses_shape(lead, chunk: int):
    """The kept T of chunk-heads ``lead``: [*lead, c / fold, c · fold]."""
    fold = _fold(chunk)
    return (*lead, chunk // fold, chunk * fold)


def _forward_call(where, q, k, v, g, rows, interpret: bool, keep=None,
                  inverses=None):
    """The chunk-local step's six results of segment ``where``, [b, n, h,
    ...], by the forward kernel, from the whole sequence's ``q``, ``k``,
    ``v``, ``g`` [b, s, h · d] and ``beta`` as ``rows`` [b, chunks, h, c].
    The segment's T is written into ``keep`` (every chunk's, [b, chunks,
    h, c / fold, c · fold]) as a seventh result, or with ``inverses``
    (such an array) read in place of the inverse's products."""
    like = jax.ShapeDtypeStruct
    (b, _, h, c), n = rows.shape, where.chunks
    d_k, d_v = k.shape[2] // h, v.shape[2] // h
    results = (
        like((b, n, h, c, d_v), _F32), like((b, n, h, c, d_k), _F32),
        like((b, n, h, c, c), v.dtype), like((b, n, h, c, d_k), v.dtype),
        like((b, n, h, c, d_k), _F32), like((b, n, h, d_k), _F32),
    )
    reads = inverses is not None
    return _call(
        functools.partial(_forward_kernel, reads=reads),
        "kda_chunk_rebuild" if reads else "kda_chunk_forward", where,
        (q, k, v, g, rows) + ((inverses,) if reads else ()),
        results + (() if keep is None else (keep,)), h, CHUNKS_A_STEP,
        interpret=interpret,
    )


def _backward_call(where, inputs, inverses, cotangents, grads,
                   interpret: bool):
    """The gradients of segment ``where``'s chunk-local step by the
    backward kernel, written into ``grads``: the whole sequence's, each in
    the shape of its one of ``inputs`` (:func:`_forward_call`'s five)."""
    return _call(
        _backward_kernel, "kda_chunk_backward", where,
        (*inputs, inverses, *cotangents), grads, inputs[4].shape[2],
        CHUNKS_A_STEP, interpret=interpret,
    )


def _chunk_major(chunk: int, a):
    """[b, s, h, ...] as [b, n, h, c, ...]: a copy."""
    return jnp.moveaxis(a.reshape(a.shape[0], -1, chunk, *a.shape[2:]), 2, 3)


def _chunks(chunk: int, q, k, v, g, beta):
    """[b, s, h, ...] arrays as a chunk-major chunk-local step takes them,
    [b, n, h, c, ...], ``g`` and ``beta`` float32."""
    return tuple(
        _chunk_major(chunk, a)
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
# runs in order, so a head's state stays in a VMEM scratch for a segment.
# It lies TRANSPOSED there, [d_v, d_k]: a chunk's decay is then a [1, d_k]
# row over its sublanes, the decay's cotangent a sum over them, and no
# [1, d] row has to become a column.

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
        out[_head(out, at, len(heads))] = (
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
    d_outs = [d_out[_head(d_out, at, len(heads))] for at in heads]

    @pl.when(pl.program_id(2) == 0)
    def _():
        for at in heads:
            d_state[at] = d_left[at].T

    for at in heads:
        dU[at] = (_dot(P[at], d_outs[at], (0, 0))
                  + _dot(to_end[at], d_state[at], (1, 1)))
    for at in heads:
        S, dS, d_o = entered[at], d_state[at], d_outs[at]
        dW[at] = -_dot(dU[at], S, (1, 0))
        dq_decayed[at] = _dot(d_o, S.astype(dtype), (1, 0)).astype(dtype)
        dP[at] = _dot(d_o, w[at].astype(dtype), (1, 1)).astype(dtype)
        dto_end[at] = _dot(w[at], dS, (1, 0))
        dend[at:at + 1, :] = jnp.sum(S * dS, axis=0, keepdims=True)
    for at in heads:
        d_state[at] = (
            end[at:at + 1, :] * d_state[at]
            + _dot(d_outs[at], q_decayed[at], (0, 0))
            - _dot(dU[at], W[at], (0, 0))
        )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        for at in heads:
            d_entering[at] = d_state[at].T


def _state_forward_call(where, local, state, interpret: bool, out=None):
    """The walk over segment ``where``'s chunk states from its six
    chunk-local results [b, n, h, ...]: ``o`` written into ``out`` (the
    whole sequence's, [b, s, h · d_v], in ``P``'s dtype) and the state
    left; with no ``out`` (the backward's rebuild of ONE segment) ``o``
    [b, n, h, c, d_v], every chunk's entering state [b, n, h, d_v, d_k]
    and w [b, n, h, c, d_v], float32, and the state left."""
    like = jax.ShapeDtypeStruct
    U, _, P = local[:3]
    d_k, d_v = state.shape[2:]
    results = (
        like(U.shape, P.dtype), like((*U.shape[:3], d_v, d_k), _F32),
        like(U.shape, _F32),
    ) if out is None else (out,)
    return _call(
        functools.partial(_state_forward_kernel, keeps=out is None),
        "kda_state_forward", where, (*local, state),
        (*results, like(state.shape, _F32)), U.shape[2], HEADS_A_STEP,
        walk=False, interpret=interpret,
    )


def _state_backward_call(where, kept, d_out, d_left, interpret: bool):
    """The six results' cotangents [b, n, h, ...] and the entering
    state's from what the rebuild's walk kept, ``o``'s cotangent ``d_out``
    [b, s, h · d_v] (the whole sequence's, read at ``where``) and the
    cotangent of the state left."""
    like = jax.ShapeDtypeStruct
    W, P, q_decayed, to_end, end, _, w = kept
    return _call(
        _state_backward_kernel, "kda_state_backward", where,
        (*kept, d_out, d_left),
        tuple(like(a.shape, a.dtype)
              for a in (w, W, P, q_decayed, to_end, end, d_left)),
        W.shape[2], HEADS_A_STEP, walk=True, interpret=interpret,
    )


# --------------------------------------------------------------------------
# The walk over a sequence's segments: a rule's own loop (it owns the layout).

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
    """A delta rule over a whole sequence, the segments one after the
    other, its backward rebuilding ONE segment at a time from its inputs
    and its entering state. It is handed the model's arrays ``xs`` = ``q``,
    ``k``, ``v``, ``g``, ``beta`` [b, s, h, ...]; how its chunk step gets a
    segment's tiles out of them is its own affair. ``forward(chunk, xs,
    keep)``: ``o`` [b, s, h, d_v], the states the segments were entered
    with [segments, b, h, d_k, d_v] and, with ``keep``, whatever else the
    backward reads again; ``backward(chunk, xs, kept, d_out)``: the five
    gradients, ``kept`` the forward's results after ``o``; ``names`` what
    the forward keeps besides its inputs, for a checkpoint's policy.
    Hashable: the static argument of :func:`segment_walk`."""

    forward: Callable
    backward: Callable
    names: Tuple[str, ...]


def chunk_major(local: Callable, rebuild: Callable, across: Callable,
                names: Tuple[str, ...]) -> Rule:
    """The rule of a chunk step on chunk-major ``q``, ``k``, ``v``, ``g``,
    ``beta`` [b, n, h, c, ...] (:func:`_chunks` of a segment: a copy):
    ``local(*xs, keep)`` the six results ``U``, ``W``, ``P``, ``q e^G``,
    ``k e^{G_C − G}``, ``e^{G_C}`` and, with ``keep``, whatever else the
    backward reads again; ``rebuild(*xs, *kept)`` the six from the inputs
    and that; ``across(six, state, dtype)`` the output [b, n · c, h, d_v]
    and the state left. The loops scan the segments' slices, and the
    backward differentiates a segment as written."""

    def forward(chunk, xs, keep):
        v = xs[2]

        def step(state, xs):
            results = local(*_chunks(chunk, *xs), keep=keep)
            out, left = across(results[:6], state, v.dtype)
            return left, (out, state, *results[6:])

        _, (out, *kept) = jax.lax.scan(
            step, _no_state(xs), _segments(chunk, *xs))
        return _whole(out), *kept

    def backward(chunk, xs, kept, d_out):
        entered, dtype = kept[0], xs[2].dtype

        def step(d_state, xs):
            (*xs, d_o), (state, *inverses) = xs[:6], xs[6:]

            def segment(*a):
                *a, state = a
                return across(
                    rebuild(*_chunks(chunk, *a), *inverses), state, dtype)

            _, vjp = jax.vjp(segment, *xs, state)
            *d_xs, d_state = vjp((d_o, d_state))
            return d_state, tuple(d_xs)

        _, grads = jax.lax.scan(
            step, jnp.zeros(entered.shape[1:], _F32),
            (*_segments(chunk, *xs, d_out), *kept), reverse=True,
        )
        return tuple(_whole(a) for a in grads)

    return Rule(forward, backward, names)


def _no_state(xs):
    b, _, h, d_k = xs[1].shape
    return jnp.zeros((b, h, d_k, xs[2].shape[-1]), _F32)


def _in_place(chunk: int, xs, *more):
    """What the kernels' index maps read of ``xs``: ``q``, ``k``, ``v``,
    ``g`` [b, s, h, d] as [b, s, h · d] (no copy) and ``beta`` [b, s, h]
    chunk-major, [b, chunks, h, c] float32 (the one copy: a chunk's [1, c]
    row of it is a head's, and a block [c, h] of the model's array holds
    that as a column); ``more`` such arrays as [b, s, h · d]; a segment's
    chunks and the segments' count."""
    *wide, beta = xs
    wide = [a.reshape(*a.shape[:2], -1) for a in (*wide, *more)]
    n = math.gcd(beta.shape[1] // chunk, SEGMENT_CHUNKS)
    return ((*wide[:4], _chunk_major(chunk, beta.astype(_F32))), wide[4:],
            n, beta.shape[1] // chunk // n)


def _unwritten(like, after, interpret: bool):
    """Arrays for a loop to write into, of the shapes and dtypes ``like``,
    their contents undefined: the results of a kernel that does nothing
    and is handed ``after``, an array the loop reads. (An allocation with
    no operand is scheduled where the PROGRAM starts, and a backward's
    five gradients would be held through every layer's forward: 1.5 GiB
    of Kimi Linear's step, PERF.md §6, PR 68.)"""
    anywhere = pl.BlockSpec(memory_space=pl.ANY)

    def kda_unwritten(after):
        return pl.pallas_call(
            lambda *refs: None,
            out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in like],
            in_specs=[anywhere], out_specs=[anywhere] * len(like),
            interpret=interpret, name="kda_unwritten",
        )(after)

    return (kda_unwritten if interpret else jax.jit(kda_unwritten))(after)


def _kernels_forward(chunk, xs, keep):
    """The loop carries the state and the arrays being written: ``o`` and,
    with ``keep``, every chunk's T [b, chunks, h, c / fold, c · fold]."""
    inputs, _, n, count = _in_place(chunk, xs)
    (b, chunks, h, _), v, interpret = inputs[4].shape, xs[2], _interpret()

    def step(carry, at):
        state, out, *inverses = carry
        where = Segment(at, n, chunk)
        results = _forward_call(where, *inputs, interpret, *inverses)
        out, left = _state_forward_call(
            where, results[:6], state, interpret, out)
        return (left, out, *results[6:]), state

    written = [jax.ShapeDtypeStruct((*v.shape[:2], h * v.shape[3]), v.dtype)]
    if keep:
        written.append(jax.ShapeDtypeStruct(
            inverses_shape((b, chunks, h), chunk), _F32))
    (_, out, *inverses), entered = jax.lax.scan(
        step, (_no_state(xs), *_unwritten(written, inputs[0], interpret)),
        jnp.arange(count))
    return out.reshape(v.shape), entered, *inverses


def _kernels_backward(chunk, xs, kept, d_out):
    """A segment's six results again from the kept T, its walk again for
    the chunk states and ``w``, then the two backward kernels, each
    gradient written where the model reads it."""
    inputs, (d_out,), n, count = _in_place(chunk, xs, d_out)
    (entered, inverses), interpret = kept, _interpret()

    def step(carry, segment):
        (d_state, *grads), (at, state) = carry, segment
        where = Segment(at, n, chunk)
        six = _forward_call(where, *inputs, interpret, inverses=inverses)
        _, states, w, _ = _state_forward_call(where, six, state, interpret)
        *d_six, d_state = _state_backward_call(
            where, (*six[1:], states, w), d_out, d_state, interpret)
        return (d_state, *_backward_call(
            where, inputs, inverses, d_six, grads, interpret)), None

    (_, *grads, d_rows), _ = jax.lax.scan(
        step,
        (jnp.zeros(entered.shape[1:], _F32),
         *_unwritten(inputs, d_out, interpret)),
        (jnp.arange(count), entered), reverse=True,
    )
    beta = xs[4]
    return (*(a.reshape(x.shape) for a, x in zip(grads, xs)),
            jnp.moveaxis(d_rows, 3, 2).reshape(beta.shape).astype(beta.dtype))


KERNELS = Rule(_kernels_forward, _kernels_backward, KEPT)
PLAIN = chunk_major(
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
    """A chunked delta rule over a whole sequence by ``rule`` (a
    :class:`Rule`), under one ``custom_vjp`` whose forward names what a
    checkpoint keeps. ``g`` is whatever ``rule`` reads (a decay a channel
    here, one a head in ``ops/gdn.py``)."""
    return _forward(q, k, v, g, beta, chunk, rule, keep=False)[0]


def _forward(q, k, v, g, beta, chunk, rule, keep: bool):
    """``o``, the states the segments were entered with and, with
    ``keep``, whatever else ``rule`` keeps of every segment."""
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two")
    if k.shape[1] % chunk:
        raise ValueError(
            f"sequence {k.shape[1]} is not a multiple of chunk {chunk}")
    return rule.forward(chunk, (q, k, v, g, beta), keep)


def _walk_fwd(q, k, v, g, beta, chunk, rule):
    out, *kept = (
        checkpoint_name(a, name) for a, name in zip(
            _forward(q, k, v, g, beta, chunk, rule, keep=True), rule.names)
    )
    return out, (q, k, v, g, beta, *kept)


def _walk_bwd(chunk, rule, residuals, d_out):
    return rule.backward(chunk, residuals[:5], residuals[5:], d_out)


segment_walk.defvjp(_walk_fwd, _walk_bwd)
