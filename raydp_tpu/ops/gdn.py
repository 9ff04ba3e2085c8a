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
has a vector, and the walk over segments whose backward holds one
segment's intermediates is that module's (``segment_walk``;
``SEGMENT_CHUNKS`` is read there). A rule there owns its layout; both of
this module's are ``chunk_major`` ones, which get each segment copied to
[b, n, h, c, ...]: a head of 96 or 192 lanes is no whole register, so a
head's slice of a [c, h · d] block of the model's own array (what
``ops/kda.KERNELS`` reads in place at 128) would be a lane shift here.

**Two rules, chosen by the shapes** (:func:`uses_kernels`; a test may ask
for either by argument). :data:`RULE` is plain ``jax.numpy`` batched
products with ``ops/kda.py``'s triangular inverse (``unit_lower_inverse``)
and its ``lax.scan`` over chunk states (``_across``): every ``Γ``, ``A``,
``T`` and chunk state is an array in HBM, a [64, 64] float32 tile padded
to 128 lanes and a [96, 192] state to 256. :data:`KERNELS` is the same
mathematics (:func:`_chunk_math`) a chunk and head at a time on tiles in
VMEM, whose blocks take a head's whole ``d_k`` and ``d_v`` (the
published 96 and 192: three quarters of a 128-lane register each way,
nothing padded in HBM): ``gdn_chunk_forward`` reads ``q``, ``k``, ``v``,
``g`` and ``β`` of a chunk and writes ``U = T(βv)``, ``W = T(β e^G k)``,
``P = q kᵀ ⊙ Γ``, ``q e^G`` and ``k e^{G_C − G}`` and, under
differentiation, the chunk's ``T`` (``e^{G_C}``, one scalar a chunk and
head, is ``jax.numpy``'s); ``gdn_chunk_rebuild`` is the same body given
``T``, with no inverse and no ``A``; ``gdn_chunk_backward`` evaluates
``jax.vjp`` of :func:`_chunk_math` in its body, so ``Γ``, ``A`` and their
cotangents never leave the chip. ``gdn_state_forward`` and
``gdn_state_backward`` walk a segment's chunks on the grid with
:data:`HEADS_A_STEP` heads' states ``[d_k, d_v]`` float32 in a VMEM
scratch; one ``custom_vjp`` holds the pair, so no loop over chunks is
left to XLA and only the ONE segment a backward rebuilds has its chunk
states in HBM. Mosaic compiles the kernels on a TPU; on any other backend
their bodies run in the Pallas interpreter. XLA cannot partition a Mosaic
call: over a mesh of several devices ``gdn_chunked(mesh=)`` lays the
kernels' rule over ``dp`` in a ``shard_map`` (``models/gdn.py`` asks for
the kernels only where that is sound).

**Precision** (what ``ops/kda.py`` states for itself, the same by either
rule): ``g``, its cumulative sums, ``β``, ``Γ``, ``A``'s inverse and the
products with it, the chunk states and their recurrence are float32 (the
state's products at ``Precision.HIGHEST``); the operands of ``q kᵀ`` and
``k kᵀ`` and of the two products that make ``o`` are in ``v``'s dtype with
float32 accumulation.

**The backward** is ``segment_walk``'s: the forward keeps its inputs, its
output, the state each segment was entered with and, by the kernels,
every chunk's ``T`` (:data:`KEPT`), float32 with no padded lane
(``ops/kda._packed``: 16 KiB a chunk and head); a segment's rebuild and
its gradient kernel read that ``T`` and invert nothing. The plain rule
keeps no ``T`` and inverts again in its rebuild.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from raydp_tpu.ops.kda import (
    CHUNKS_A_STEP,
    _across,
    _dot,
    _interpret,
    _inverse_tile,
    _kept_inverse,
    _packed,
    _pairs,
    _unpacked,
    chunk_major,
    inverses_shape,
    kda_recurrent,
    segment_walk,
    unit_lower_inverse,
)

# What runs, by whether :func:`uses_kernels` (and, in a model, the mesh:
# ``models/gdn.scan_takes_kernels``) says the kernels do.
IMPLEMENTATION = {
    False: "chunked WY form, one [chunk, chunk] decay matrix a head, "
           "jax.numpy; the triangular inverse, the chunk states' lax.scan "
           "and the walk over segments are ops/kda.py's (ops/gdn.py)",
    True: "chunked WY form, one [chunk, chunk] decay matrix a head held in "
          "VMEM: Pallas kernels gdn_chunk_forward (gdn_chunk_rebuild where "
          "the backward reads the kept inverses) and gdn_chunk_backward on "
          "[chunk, d_k] and [chunk, d_v] tiles, the chunk states by "
          "gdn_state_forward and gdn_state_backward; the walk over "
          "segments is ops/kda.py's (ops/gdn.py)",
}
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# What the forward keeps besides its inputs: the output, the states the
# segments were entered with, [segments, b, h, d_k, d_v] float32, and, where
# the kernels run, every chunk's triangular inverse T, float32 with no
# padded lane (``ops/kda._packed``).
KEPT = ("gdn_out", "gdn_segment_states", "gdn_chunk_inverses")


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


RULE = chunk_major(
    lambda *xs, keep: _chunk_local(*xs), _chunk_local, _across, KEPT
)


# --------------------------------------------------------------------------
# The chunk-local step as Pallas kernels. :func:`_chunk_math` holds the
# mathematics for ONE chunk of one head on [c, d_k], [c, d_v] and [c, c]
# tiles in VMEM; the forward kernel evaluates it and the backward kernel
# its ``jax.vjp``. ``g`` and ``beta`` come as [1, c] rows (a chunk on the
# lanes) and become columns, and cumulative sums, by masked sums over one
# [c, c] tile: selects by iota masks, lane and sublane reductions, [c, d]
# × [d, c] and [c, c] × [c, d] products, nothing else.

def uses_kernels(d_k: int, d_v: int, chunk: int) -> bool:
    """Whether :func:`gdn_chunked` takes the Pallas kernels at these
    shapes, read from the shapes alone: a chunk of whole sublane tiles in
    either dtype whose [chunk, chunk] tiles fill at least half a
    register's lanes, and heads whose keys and values are whole quarters
    of one and at most two (the blocks take a head's full ``d_k`` and
    ``d_v``: a [96, 192] state is 12 sublane tiles of 1.5 registers, and
    :data:`HEADS_A_STEP` states of up to [256, 256] with their blocks fit
    VMEM; Mosaic compiles every such shape for a v5e). Smaller chunks and
    narrower heads keep the ``jax.numpy`` form, which batches them all
    into one product."""
    return chunk % 64 == 0 and all(
        d % 32 == 0 and d <= 256 for d in (d_k, d_v))


def _chunk_math(q, k, v, g, beta, dtype, inverse=None, values_only=False):
    """One chunk of one head: ``q``, ``k`` [c, d_k] and ``v`` [c, d_v]
    float32 (values of ``dtype``), ``g`` and ``beta`` [1, c] float32 →
    ``U``, ``W``, ``P``, ``q e^G``, ``k e^{G_C − G}`` (:func:`_chunk_local`'s
    first five) and ``T``, which a caller that kept it hands back as
    ``inverse``: ``A`` is then read by the derivative alone, and a caller
    that takes ``values_only`` leaves ``k kᵀ`` out. ``Γ`` is masked BEFORE
    the exponential, every exponent ≤ 0."""
    c = k.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def column(row):                                  # [1, c] → [c, 1]
        return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)

    # G_i as a column and as a row, and G_C − G_i as a column.
    G = jnp.sum(jnp.where(j <= i, g, 0.0), axis=1, keepdims=True)
    G_row = jnp.sum(jnp.where(i <= j, column(g), 0.0), axis=0, keepdims=True)
    to_go = jnp.sum(jnp.where(j > i, g, 0.0), axis=1, keepdims=True)
    gamma = jnp.exp(jnp.where(j <= i, G - G_row, -jnp.inf))
    bt = column(beta)
    if values_only:
        of_q = _dot(q.astype(dtype), k.astype(dtype), (1, 1))
        T = inverse
    else:
        of_q, of_k = _pairs(q, k, dtype)
        A = bt * (of_k * gamma)
        T = _inverse_tile(A) if inverse is None else _kept_inverse(A, inverse)
    decay = jnp.exp(G)
    U = _dot(T, bt * v, (1, 0))
    W = _dot(T, bt * decay * k, (1, 0))
    return (U, W, (of_q * gamma).astype(dtype), (q * decay).astype(dtype),
            k * jnp.exp(to_go), T)


def _tiles(refs, at):
    """A chunk-head's operands from the blocks of a grid step: ``q``,
    ``k``, ``v`` float32, ``g`` and ``beta`` as [1, c] rows."""
    q, k, v, g, beta = refs
    return (*(r[at].astype(_F32) for r in (q, k, v)),
            g[0, pl.ds(at, 1), :], beta[0, pl.ds(at, 1), :])


def _forward_kernel(*refs, reads: bool):
    """q, k, v, g, beta → U, W, P, q e^G, k e^{G_C − G}. The chunk's T is
    read (``reads``: the block after beta's) and not inverted again, or
    written where the call has a block for it."""
    ins, kept, outs = refs[:5], refs[5:5 + reads], refs[5 + reads:]
    dtype = ins[2].dtype

    def chunk(at, _):
        *five, inverse = _chunk_math(
            *_tiles(ins, at), dtype,
            inverse=_unpacked(kept[0][at]) if reads else None,
            values_only=reads,
        )
        for ref, a in zip(outs, five):
            ref[at] = a
        if len(outs) > 5:
            outs[5][at] = _packed(inverse)
        return 0

    jax.lax.fori_loop(0, ins[0].shape[0], chunk, 0)


def _backward_kernel(*refs):
    """q, k, v, g, beta, T and the five results' cotangents → dq, dk, dv,
    dg, dbeta: the ``jax.vjp`` of :func:`_chunk_math` on a chunk's tiles."""
    ins, kept, cots, outs = refs[:5], refs[5], refs[6:11], refs[11:]
    dtype = ins[2].dtype

    def chunk(at, _):
        inverse = _unpacked(kept[at])
        _, vjp = jax.vjp(
            lambda *a: _chunk_math(*a, dtype, inverse=inverse)[:5],
            *_tiles(ins, at),
        )
        *d_tiles, d_g, d_beta = vjp(tuple(ref[at] for ref in cots))
        for ref, a in zip(outs, d_tiles):
            ref[at] = a.astype(ref.dtype)
        outs[3][0, pl.ds(at, 1), :] = d_g
        outs[4][0, pl.ds(at, 1), :] = d_beta
        return 0

    jax.lax.fori_loop(0, ins[0].shape[0], chunk, 0)


def _over_chunks(kernel, name, operands, outputs, interpret: bool):
    """``kernel`` over the chunk-heads of ``operands`` ([..., c, d] tiles;
    [..., c] rows go as [steps, chunk-heads a step, c]), every grid step
    independent (``ops/kda._call``'s blocks, inside the caller's jit)."""
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

    results = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(flat(a), a.dtype) for a in outputs],
        grid=(count // step,),
        in_specs=[spec(a) for a in operands],
        out_specs=[spec(a) for a in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name=name,
    )(*(a.reshape(flat(a)) for a in operands))
    return tuple(r.reshape(a.shape) for r, a in zip(results, outputs))


@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def _forward_call(q, k, v, g, beta, inverses=None, *, keep: bool = False,
                  interpret: bool):
    """The chunk-local step's first five results by the forward kernel:
    with ``keep`` the chunks' T as a sixth, with ``inverses`` (a ``keep``
    call's sixth) T read in place of the inverse's products. A ``jit`` of
    its own: a step's call sites of one shape lower once."""
    like = jax.ShapeDtypeStruct
    lead, c = k.shape[:-2], k.shape[-2]
    results = (
        like(v.shape, _F32), like(k.shape, _F32), like((*lead, c, c), v.dtype),
        like(q.shape, v.dtype), like(k.shape, _F32),
    )
    if keep:
        results += (like(inverses_shape(lead, c), _F32),)
    reads = inverses is not None
    return _over_chunks(
        functools.partial(_forward_kernel, reads=reads),
        "gdn_chunk_rebuild" if reads else "gdn_chunk_forward",
        (q, k, v, g, beta) + ((inverses,) if reads else ()), results,
        interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward_call(kept, cotangents, *, interpret: bool):
    like = jax.ShapeDtypeStruct
    return _over_chunks(
        _backward_kernel, "gdn_chunk_backward", (*kept, *cotangents),
        tuple(like(a.shape, a.dtype) for a in kept[:5]), interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunk_five(q, k, v, g, beta, inverses, interpret: bool):
    return _forward_call(q, k, v, g, beta, inverses, interpret=interpret)


def _chunk_five_fwd(q, k, v, g, beta, inverses, interpret):
    results = _forward_call(q, k, v, g, beta, inverses, interpret=interpret)
    return results, (q, k, v, g, beta, inverses)


def _chunk_five_bwd(interpret, kept, cotangents):
    grads = _backward_call(kept, cotangents, interpret=interpret)
    return (*grads, jnp.zeros_like(kept[5]))


_chunk_five.defvjp(_chunk_five_fwd, _chunk_five_bwd)


def _end(g):
    """``e^{G_C}`` [..., 1] of chunks' ``g`` [..., c]: one scalar a chunk
    and head, which no kernel need write."""
    return jnp.exp(jnp.sum(g, axis=-1, keepdims=True))


def chunk_local(q, k, v, g, beta, inverses=None, keep: bool = False):
    """:func:`_chunk_local` by the Pallas kernels. With ``keep`` the
    chunks' ``T`` as a seventh result (the forward pass); given
    ``inverses``, that seventh, the same six through one ``custom_vjp``
    that inverts nothing, forward or backward (a segment's rebuild)."""
    if inverses is not None:
        return (*_chunk_five(q, k, v, g, beta, inverses, _interpret()),
                _end(g))
    results = _forward_call(
        q, k, v, g, beta, keep=keep, interpret=_interpret())
    return (*results[:5], _end(g), *results[5:])


# --------------------------------------------------------------------------
# The recurrence over chunk states as Pallas kernels. A grid step holds ONE
# chunk of :func:`state_heads` heads; the chunk axis is the grid's last and
# runs in order, so a head's state [d_k, d_v] stays in a VMEM scratch from
# a segment's first chunk to its last. A chunk's decay e^{G_C} is one
# scalar a head and comes as a [1, d_v] row (its cotangent goes back as
# one, summed outside), so no block has the heads on its sublanes and any
# divisor of the heads may be a group.

# Heads a grid step, at most: blocks of 0.26 MB a head in the forward pass
# and 0.45 in the backward (a [64, 192] float32 tile takes 64 KiB of VMEM),
# both buffers of each, and 96 KiB a head of scratch.
HEADS_A_STEP = 6


def state_heads(h: int) -> int:
    """Heads a grid step of the state kernels: the largest divisor of
    ``h`` that is at most :data:`HEADS_A_STEP` (6 of the published 30)."""
    return max(n for n in range(1, HEADS_A_STEP + 1) if h % n == 0)


def _state_forward_kernel(*refs, keeps: bool):
    """U, W, P, q e^G, k e^{G_C − G}, e^{G_C} of a chunk and the state the
    segment is entered with → o, where the call ``keeps`` them for the
    backward the state the chunk is entered with and its w, and the state
    the segment leaves."""
    U, W, P, q_decayed, to_end, end, entering = refs[:7]
    (out, *kept), left, state = refs[7:-2], refs[-2], refs[-1]
    dtype, heads = out.dtype, range(state.shape[0])

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = entering[...]

    # Every head's W·S first, then every o, then every update: side by
    # side a group's six-pass products keep the MXU fed (ops/kda.py).
    ws = [U[at] - _dot(W[at], state[at], (1, 0)) for at in heads]
    for at in heads:
        out[at] = (
            _dot(q_decayed[at], state[at].astype(dtype), (1, 0))
            + _dot(P[at], ws[at].astype(dtype), (1, 0))
        ).astype(dtype)
        if keeps:
            kept[0][at], kept[1][at] = state[at], ws[at]
    for at in heads:
        state[at] = end[at] * state[at] + _dot(to_end[at], ws[at], (0, 0))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        left[...] = state[...]


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
        d_state[...] = d_left[...]

    for at in heads:
        dU[at] = (_dot(P[at], d_out[at], (0, 0))
                  + _dot(to_end[at], d_state[at], (1, 0)))
    for at in heads:
        S, dS, d_o = entered[at], d_state[at], d_out[at]
        dW[at] = -_dot(dU[at], S, (1, 1))
        dq_decayed[at] = _dot(d_o, S.astype(dtype), (1, 1)).astype(dtype)
        dP[at] = _dot(d_o, w[at].astype(dtype), (1, 1)).astype(dtype)
        dto_end[at] = _dot(w[at], dS, (1, 1))
        dend[at] = jnp.sum(S * dS, axis=0, keepdims=True)
    for at in heads:
        d_state[at] = (
            end[at] * d_state[at]
            + _dot(q_decayed[at], d_out[at], (0, 0))
            - _dot(W[at], dU[at], (0, 0))
        )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        d_entering[...] = d_state[...]


def _over_segment(kernel, name, operands, outputs, heads: int, reverse: bool,
                  interpret: bool):
    """``kernel`` over a segment, grid (sequences, groups of ``heads``
    heads, chunks), the chunks one after the other (from the last with
    ``reverse``). Of ``operands`` and of ``outputs`` the last is a state,
    [b, h, d_k, d_v], whose block stays for a group's whole walk; the
    others are [b, n, h, ...] as the chunk kernels write them and go a
    chunk of the group's heads a step. The scratch is the group's states,
    float32."""
    b, n, h = operands[0].shape[:3]

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

    return tuple(pl.pallas_call(
        kernel,
        out_shape=list(outputs),
        grid=(b, h // heads, n),
        in_specs=specs(operands),
        out_specs=specs(outputs),
        scratch_shapes=[pltpu.VMEM((heads, *operands[-1].shape[2:]), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(*operands))


@functools.partial(jax.jit, static_argnames=("heads", "keeps", "interpret"))
def _state_forward_call(local, state, *, heads: int, keeps: bool,
                        interpret: bool):
    """``o`` [b, n, h, c, d_v] in ``P``'s dtype, with ``keeps`` every
    chunk's entering state [b, n, h, d_k, d_v] and w [b, n, h, c, d_v],
    float32, and the state left."""
    like = jax.ShapeDtypeStruct
    U, _, P = local[:3]
    kept = (
        like((*U.shape[:3], *state.shape[2:]), _F32), like(U.shape, _F32)
    ) if keeps else ()
    return _over_segment(
        functools.partial(_state_forward_kernel, keeps=keeps),
        "gdn_state_forward", (*local, state),
        (like(U.shape, P.dtype), *kept, like(state.shape, _F32)),
        heads, False, interpret,
    )


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _state_backward_call(kept, d_out, d_left, *, heads: int, interpret: bool):
    like = jax.ShapeDtypeStruct
    W, P, q_decayed, to_end, end, _, w = kept
    return _over_segment(
        _state_backward_kernel, "gdn_state_backward", (*kept, d_out, d_left),
        tuple(like(a.shape, a.dtype)
              for a in (w, W, P, q_decayed, to_end, end, d_left)),
        heads, True, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _states(U, W, P, q_decayed, to_end, end, state, heads: int,
            interpret: bool):
    return _state_forward_call(
        (U, W, P, q_decayed, to_end, end), state, heads=heads, keeps=False,
        interpret=interpret)


def _states_fwd(U, W, P, q_decayed, to_end, end, state, heads, interpret):
    out, entered, w, left = _state_forward_call(
        (U, W, P, q_decayed, to_end, end), state, heads=heads, keeps=True,
        interpret=interpret)
    return (out, left), (W, P, q_decayed, to_end, end, entered, w)


def _states_bwd(heads, interpret, kept, cotangents):
    return _state_backward_call(
        kept, *cotangents, heads=heads, interpret=interpret)


_states.defvjp(_states_fwd, _states_bwd)


def across(local, state):
    """``ops/kda._across`` by the Pallas kernels: the same two results,
    the state in VMEM from a segment's first chunk to its last, and one
    ``custom_vjp`` whose forward keeps a segment's chunk states and w and
    whose backward is the second kernel, so nothing of the recurrence is
    differentiated by tracing."""
    *five, end = local
    b, n, h, c, d_v = five[0].shape
    rows = jnp.broadcast_to(end[..., None], (b, n, h, 1, d_v))
    out, left = _states(*five, rows, state, state_heads(h), _interpret())
    return jnp.moveaxis(out, 3, 2).reshape(b, n * c, h, d_v), left


KERNELS = chunk_major(
    lambda *xs, keep: chunk_local(*xs, keep=keep),
    lambda *xs: chunk_local(*xs),
    lambda six, state, dtype: across(six, state),
    KEPT,
)


def gdn_chunked(q, k, v, g, beta, chunk: int = 64, kernels=None, mesh=None):
    """``q``, ``k`` [b, s, h, d_k], ``v`` [b, s, h, d_v] (``q`` already
    scaled), ``g`` [b, s, h] float32 log-decays (≤ 0, unbounded below),
    ``beta`` [b, s, h] float32; ``s`` a multiple of ``chunk``, a power of
    two. Returns ``o`` [b, s, h, d_v] in ``v``'s dtype. The state before
    the first token is zero. ``kernels`` asks for the Pallas kernels
    (true) or the ``jax.numpy`` form (false) whatever the shapes, as a
    test and a model's own predicate do; left out, :func:`uses_kernels`
    reads it from the shapes. With the kernels and a ``mesh`` of more
    than one device each device walks its own sequences (``dp``, where it
    divides the batch), the sequence and the heads whole on every device:
    XLA partitions the ``jax.numpy`` form and cannot a Mosaic call."""
    if kernels is None:
        kernels = uses_kernels(k.shape[-1], v.shape[-1], chunk)

    def walk(*xs):
        return segment_walk(*xs, chunk, KERNELS if kernels else RULE)

    if not kernels or mesh is None or mesh.size == 1:
        return walk(q, k, v, g, beta)
    dp = mesh.shape.get("dp", 1)
    rows = PartitionSpec("dp" if dp > 1 and k.shape[0] % dp == 0 else None)
    return jax.shard_map(
        walk, mesh=mesh, in_specs=(rows,) * 5, out_specs=rows,
        # pallas_call's out_shape carries no varying-axes annotation.
        check_vma=False,
    )(q, k, v, g, beta)
