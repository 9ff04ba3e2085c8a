"""The Mamba-2 state-space scan in its chunked ("state-space dual") form
(Dao & Gu 2024, arXiv:2405.21060, listing 1): :func:`ssd_chunked` in plain
``jax.numpy``, and :func:`ssd_scan_packed`, the same mathematics in two
Pallas kernels.

Per head the recurrence is

    h_t = exp(dt_t · A) · h_{t-1} + dt_t · x_t ⊗ B_t        (P × N state)
    y_t = h_t · C_t + D · x_t

A sequence is cut into chunks of ``chunk`` tokens. Inside a chunk the
outputs are a masked matmul, ``(C Bᵀ ∘ L) X`` over the causal pairs with
``L[i, j] = exp(Σ_{j<k≤i} dt_k A)``; each chunk leaves a final state; a
short recurrence carries the states across the chunks of a sequence; and
the state a chunk was entered with reaches its outputs as
``C · state · decay``. The matmul operands are in the input's dtype with
float32 accumulation; ``dt``, ``A``, the cumulative log-decays, ``L`` and
the chunk states are float32: in both forms.

**The ``jax.numpy`` form** makes ``L`` for every chunk and head at once, a
float32 array [b, c, g, r, q, q] in HBM (537 MB a layer at Nemotron 3
Nano's 16,384 tokens), and JAX differentiates it as written. It is the
form for every shape the kernels decline, for every backend but a TPU, and
the reference the kernels are held to (``tests/test_ssd_kernel.py``).

**The kernels** (``ssd_forward``, ``ssd_backward``, one ``custom_vjp``)
hold a chunk's ``L``, the group's scores and the running states in VMEM: a
grid step is one chunk of eight heads that share a group's ``B`` and ``C``,
the chunks of a sequence one after the other. Nothing of size
[chunk, chunk] a head is written to HBM, forward or backward; the backward
rebuilds ``L`` and the scores from the operands and reads the state every
chunk was entered with, which the forward writes ([b, c, h · p, n]
float32). :func:`uses_kernels` reads which shapes they take from the
shapes alone; ``models/mamba.scan_takes_kernels`` asks the backend and the
mesh besides.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """``x`` [b, s, h, p] head inputs, ``dt`` [b, s, h] positive step
    sizes (after the softplus), ``A`` [h] negative decay rates, ``B`` and
    ``C`` [b, s, g, n] with ``h`` a multiple of the groups ``g``, ``D``
    [h] the skip weight; ``s`` a multiple of ``chunk``. Returns ``y``
    [b, s, h, p] in ``x``'s dtype. The state before the first token is
    zero."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    c, r, f32, dtype = s // chunk, h // g, jnp.float32, x.dtype

    dt = dt.astype(f32)
    # Cumulative log-decay inside each chunk, inclusive: [b, c, g, r, q].
    a = (dt * A.astype(f32)).reshape(b, c, chunk, g, r)
    a = jnp.cumsum(jnp.moveaxis(a, 2, -1), axis=-1)
    xf = x.astype(f32).reshape(b, c, chunk, g, r, p)
    xd = xf * dt.reshape(b, c, chunk, g, r, 1)        # dt_t · x_t
    Bc = B.reshape(b, c, chunk, g, n)
    Cc = C.reshape(b, c, chunk, g, n)

    # Within a chunk: (C Bᵀ ∘ L) X over the causal pairs.
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(
        causal, a[..., :, None] - a[..., None, :], -jnp.inf
    ))                                                # [b, c, g, r, q, q]
    scores = jnp.einsum(
        "bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32
    )
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcigrp",
        (scores[:, :, :, None] * L).astype(dtype), xd.astype(dtype),
        preferred_element_type=f32,
    )

    # Each chunk's final state, had it been entered with zero.
    to_end = jnp.exp(a[..., -1:] - a)                 # [b, c, g, r, q]
    states = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", Bc,
        (xd * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype),
        preferred_element_type=f32,
    )

    # The recurrence over the chunks of a sequence, in float32 on the
    # vector unit: the state each chunk is entered with.
    def carry(state, chunk_in):
        final, decay = chunk_in
        return decay[..., None, None] * state + final, state

    _, entered = jax.lax.scan(
        carry, jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(a[..., -1]), 1, 0)),
    )
    y = y + jnp.einsum(
        "bcign,cbgrpn->bcigrp", Cc, entered.astype(dtype),
        preferred_element_type=f32,
    ) * jnp.moveaxis(jnp.exp(a), -1, 2)[..., None]

    y = y + D.astype(f32).reshape(g, r, 1) * xf
    return y.reshape(b, s, h, p).astype(dtype)


# --------------------------------------------------------------------------
# The same scan as Pallas kernels. A grid step is ONE chunk of a block of
# heads that share a group's B and C; the chunk axis is the grid's last and
# runs in order, the block's states [heads · p, n] in a VMEM scratch from a
# sequence's first chunk to its last. Every block has the SEQUENCE on the
# lanes, as the compiler lays a Mamba-2 mixer's arrays out around the scan
# (``x`` [b, h · p, s], ``B`` and ``C`` [b, g · n, s]: ``ops/causal_conv``'s
# sequence-minor form writes them so): what is one number a head and token
# (``dt``, the decays, their cotangents) is then a row that a head's p
# sublanes share, and a sum over a head's channels runs down the sublanes,
# so nothing is broadcast along or summed across the lanes but the one
# column a head that a [chunk, chunk] decay tile needs. What is one
# product for the whole block (the state's part of the output, the chunk's
# own state, their cotangents) runs [heads · p, n] against [n, chunk]; the
# masked products are a head's own.

# Heads a grid step: at heads of 64 the blocks are [512, chunk], the states
# 256 KiB of scratch, and eight heads are one of Nemotron's groups and one
# tile of sublanes in the arrays that hold a row a head.
HEADS_A_STEP = 8
_F32 = jnp.float32


class Tiling(NamedTuple):
    """A call's static shape: ``chunk`` tokens, ``heads`` a grid step of
    ``p`` channels each, state ``n``, ``blocks`` of heads a group.
    Hashable: a static argument of the jitted calls."""

    chunk: int
    heads: int
    p: int
    n: int
    blocks: int

    @property
    def width(self) -> int:
        return self.heads * self.p

    def of(self, k: int) -> slice:
        """Head ``k``'s rows of a block."""
        return slice(k * self.p, (k + 1) * self.p)


def tiling_of(heads: int, groups: int, p: int, n: int,
              chunk: int) -> Optional[Tiling]:
    """The tiling of one call, a function of its shapes alone; None where
    the kernels decline them."""
    if groups < 1 or heads % groups:
        return None
    r = heads // groups
    step = math.gcd(r, HEADS_A_STEP)
    fits = (
        p % 64 == 0 and n % 128 == 0 and chunk % 128 == 0
        # B's rows follow x's in one array: at a whole block of n.
        and (heads * p) % n == 0
        # The arrays that hold a row a head go a block's heads a tile of
        # sublanes, or all the heads there are.
        and (step % 8 == 0 or step == heads)
    )
    return Tiling(chunk, step, p, n, r // step) if fits else None


def uses_kernels(heads: int, groups: int, p: int, n: int, chunk: int) -> bool:
    """Whether :func:`ssd_scan_packed` takes these shapes, read from the
    shapes alone (the caller asks the backend besides: only a TPU compiles
    the kernels): a head's channels are whole tiles of sublanes in either
    dtype, the state and the chunk whole registers' lanes, and a group's
    heads divide into blocks of eight (or the heads are one block)."""
    return tiling_of(heads, groups, p, n, chunk) is not None


def _dot(a, b, contract):
    """A product with float32 accumulation; float32 operands multiply to
    float32's accuracy (a test's dtype: the cells' is bfloat16)."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=jax.lax.Precision.HIGHEST if a.dtype == _F32 else None,
        preferred_element_type=_F32,
    )


def _over_chunk(row_ref, tile: Tiling):
    """``exp(a_last)`` of every head of the block as [heads · p, n]: what
    a chunk multiplies the states it is entered with by. Along the lanes
    first, then a head's row down its sublanes (Mosaic has no broadcast
    in both at once, and folds one written as two)."""
    last = jnp.exp(jnp.broadcast_to(
        row_ref[:, tile.chunk - 1:], (tile.heads, tile.n)))
    return jnp.concatenate([
        jnp.broadcast_to(last[k:k + 1], (tile.p, tile.n))
        for k in range(tile.heads)
    ], 0)


def _causal(q: int, transposed: bool):
    """The causal pairs of a chunk as a [q, q] mask: [i, j] with j ≤ i,
    or ``transposed`` [j, i]."""
    down = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    across = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return down <= across if transposed else down >= across


def _masked_decay(causal, later, earlier):
    """``exp(a_i − a_j)`` of one head over the ``causal`` pairs and zero
    over the others, from ``later`` = a_i and ``earlier`` = a_j, one a
    column [q, 1] and the other a row [1, q] as the mask has them."""
    return jnp.exp(jnp.where(causal, later - earlier, -jnp.inf))


def _forward_kernel(x_ref, b_ref, c_ref, dt_ref, row_ref, col_ref, skip_ref,
                    y_ref, *rest, tile: Tiling):
    """x [heads · p, q], B and C [n, q], dt and a (a row a head), a (a
    column a head), D over a head's rows → y and, where the call keeps
    them, the states the chunk is entered with; scratch: the block's
    states."""
    *kept, state_ref = rest
    dtype, q = x_ref.dtype, tile.chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    if kept:
        kept[0][...] = state
    B, C = b_ref[...], c_ref[...]
    dt, a = dt_ref[...], row_ref[...]
    into, to_end = jnp.exp(a), jnp.exp(a[:, q - 1:] - a)
    causal = _causal(q, transposed=True)
    scores = _dot(B, C, (0, 0))                             # [j, i]
    entered = _dot(state.astype(dtype), C, (1, 0))          # [heads · p, q]
    decayed = []
    for k in range(tile.heads):
        rows = tile.of(k)
        x = x_ref[rows, :].astype(_F32)
        xd = x * dt[k:k + 1]                                # dt_t · x_t
        masked = scores * _masked_decay(
            causal, a[k:k + 1], col_ref[:, k:k + 1])
        y_ref[rows, :] = (
            _dot(xd.astype(dtype), masked.astype(dtype), (1, 0))
            + into[k:k + 1] * entered[rows] + skip_ref[rows, :] * x
        ).astype(y_ref.dtype)
        decayed.append((xd * to_end[k:k + 1]).astype(dtype))
    own = _dot(jnp.concatenate(decayed, 0), B, (1, 1))      # [heads · p, n]
    state_ref[...] = _over_chunk(row_ref, tile) * state + own


def _backward_kernel(x_ref, b_ref, c_ref, dt_ref, row_ref, col_ref, skip_ref,
                     entered_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref,
                     da_ref, dskip_ref, dstate_ref, *, tile: Tiling):
    """The forward's operands, the states the chunk was entered with and
    dy → dx, dB and dC (summed over the block's heads), d dt and d a (a
    row a head), and D's sums over a sequence's chunks, a lane a token of
    a chunk; scratch: the cotangent of the states the chunk leaves. The
    grid walks the chunks from the last to the first. The [chunk, chunk]
    tiles are [i, j] here, the later token down the sublanes. d a needs
    no sum over a tile: a pair's weight times its cotangent, summed over
    the earlier tokens, is dy · y of the masked product, and summed over
    the later ones xd · d xd, both sums over a head's channels."""
    dtype, q = x_ref.dtype, tile.chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    state, dstate = entered_ref[...], dstate_ref[...]
    state_b, dstate_b = state.astype(dtype), dstate.astype(dtype)
    B, C = b_ref[...], c_ref[...]
    dt, a = dt_ref[...], row_ref[...]
    into, to_end = jnp.exp(a), jnp.exp(a[:, q - 1:] - a)
    over = _over_chunk(row_ref, tile)
    causal = _causal(q, transposed=False)
    scores = _dot(C, B, (0, 0))                             # [i, j]
    entered = _dot(state_b, C, (1, 0))                      # [heads · p, q]
    dxw = _dot(dstate_b, B, (1, 0))       # of xd · exp(a_last − a)
    of_states = state * dstate * over
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1

    dscores = jnp.zeros((q, q), _F32)
    d_entered, decayed, ddt, da = [], [], [], []
    for k in range(tile.heads):
        rows = tile.of(k)
        x, cot = x_ref[rows, :].astype(_F32), dy_ref[rows, :]
        dy = cot.astype(_F32)
        dt_k, into_k, to_end_k = (v[k:k + 1] for v in (dt, into, to_end))
        xd = x * dt_k
        xdb = xd.astype(dtype)
        xw = xd * to_end_k
        decay = _masked_decay(causal, col_ref[:, k:k + 1], a[k:k + 1])
        masked = (scores * decay).astype(dtype)
        within = _dot(xdb, masked, (1, 1))                  # y's part [p, i]
        dxd = _dot(cot, masked, (1, 0))                     # [p, j]
        dscores = dscores + _dot(cot, xdb, (0, 0)) * decay  # dy_i · xd_j
        to_end_part = dxw[rows] * xw
        da_k = jnp.sum(
            dy * (within + into_k * entered[rows])
            - xdb.astype(_F32) * dxd - to_end_part, axis=0, keepdims=True)
        # The chunk's last token: every token's exp(a_last − a), and the
        # states' exp(a_last).
        at_last = jnp.sum(to_end_part, keepdims=True) + jnp.sum(
            of_states[rows], keepdims=True)
        da.append(da_k + jnp.where(last, at_last, 0.0))
        dxd = dxd + dxw[rows] * to_end_k
        dx_ref[rows, :] = (
            dxd * dt_k + skip_ref[rows, :] * dy).astype(dx_ref.dtype)
        dskip_ref[rows, :] += dy * x
        ddt.append(jnp.sum(dxd * x, axis=0, keepdims=True))
        d_entered.append((into_k * dy).astype(dtype))
        decayed.append(xw.astype(dtype))
    ddt_ref[...] = jnp.concatenate(ddt, 0)
    da_ref[...] = jnp.concatenate(da, 0)
    d_entered = jnp.concatenate(d_entered, 0)               # [heads · p, i]
    decayed = jnp.concatenate(decayed, 0)                   # [heads · p, j]
    dscores = dscores.astype(dtype)
    db_ref[...] = (
        _dot(dstate_b, decayed, (0, 0)) + _dot(C, dscores, (1, 0))
    ).astype(db_ref.dtype)                                  # [n, j]
    dc_ref[...] = (
        _dot(state_b, d_entered, (0, 0)) + _dot(B, dscores, (1, 1))
    ).astype(dc_ref.dtype)                                  # [n, i]
    dstate_ref[...] = over * dstate + _dot(d_entered, C, (1, 1))


def _specs(tile: Tiling, chunks: int, reverse: bool):
    """The ``BlockSpec``s of a grid (sequences, blocks of heads, chunks)
    by what an array holds: ``wide`` [b, h · p (+ …), s] at the block's
    own rows, ``group(of)`` [b, …, s] at the ``of(j)``-th [n, chunk],
    ``row`` [b, h, s], ``col`` [b, h / heads, s, heads], ``skip``
    [h · p, chunk] (the same for every chunk), ``states`` [b, c, h · p, n];
    the chunks from the last with ``reverse``."""
    q = tile.chunk

    def at(t):
        return chunks - 1 - t if reverse else t

    return dict(
        wide=pl.BlockSpec(
            (None, tile.width, q), lambda i, j, t: (i, j, at(t))),
        group=lambda of: pl.BlockSpec(
            (None, tile.n, q), lambda i, j, t: (i, of(j), at(t))),
        row=pl.BlockSpec((None, tile.heads, q), lambda i, j, t: (i, j, at(t))),
        col=pl.BlockSpec(
            (None, None, q, tile.heads), lambda i, j, t: (i, j, at(t), 0)),
        skip=pl.BlockSpec((tile.width, q), lambda i, j, t: (j, 0)),
        states=pl.BlockSpec(
            (None, None, tile.width, tile.n),
            lambda i, j, t: (i, at(t), j, 0)),
    )


def _packed(spec, tile: Tiling, heads: int, groups: int):
    """The three ``BlockSpec``s that read ``x``, ``B`` and ``C`` out of
    ONE array [b, h · p + 2 · g · n, s], ``x``'s rows first, then ``B``'s,
    then ``C``'s (the convolution's result as it stands: no slice of it
    is copied)."""
    first = heads * tile.p // tile.n
    return (
        spec["wide"],
        spec["group"](lambda j: first + j // tile.blocks),
        spec["group"](lambda j: first + groups + j // tile.blocks),
    )


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


@functools.partial(
    jax.jit, static_argnames=("tile", "keeps", "interpret"))
def _forward_call(xbc, dt, row, col, skip, *, tile: Tiling, keeps: bool,
                  interpret: bool):
    """``y`` [b, h · p, s] and, with ``keeps``, the states every chunk is
    entered with [b, c, h · p, n] float32."""
    (b, _, s), heads, width = xbc.shape, dt.shape[1], skip.shape[0]
    chunks, like = s // tile.chunk, jax.ShapeDtypeStruct
    groups = (xbc.shape[1] - width) // (2 * tile.n)
    spec = _specs(tile, chunks, False)
    return pl.pallas_call(
        functools.partial(_forward_kernel, tile=tile),
        out_shape=[like((b, width, s), xbc.dtype)] + [
            like((b, chunks, width, tile.n), _F32)] * keeps,
        grid=(b, width // tile.width, chunks),
        in_specs=[*_packed(spec, tile, heads, groups), spec["row"],
                  spec["row"], spec["col"], spec["skip"]],
        out_specs=[spec["wide"]] + [spec["states"]] * keeps,
        scratch_shapes=[pltpu.VMEM((tile.width, tile.n), _F32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="ssd_forward",
    )(xbc, xbc, xbc, dt, row, col, skip)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _backward_call(xbc, dt, row, col, skip, entered, dy, *, tile: Tiling,
                   interpret: bool):
    """d xbc [b, h · p + 2 · g · n, s]; d dt and d a [b, h, s] float32;
    D's sums [b, h · p, chunk]. The kernel writes dx into d xbc's first
    rows and hands dB and dC a block of heads apart ([b, blocks · n, s],
    in ``x``'s dtype where a block is a whole group, float32 partial sums
    where a group is several): they are added up where they have to be
    and written behind dx in place."""
    (b, rows, s), heads, width = xbc.shape, dt.shape[1], skip.shape[0]
    chunks, like = s // tile.chunk, jax.ShapeDtypeStruct
    blocks, groups = width // tile.width, (rows - width) // (2 * tile.n)
    spec = _specs(tile, chunks, True)
    partial_sums = like(
        (b, blocks * tile.n, s), xbc.dtype if tile.blocks == 1 else _F32)
    own = spec["group"](lambda j: j)
    dxbc, dB, dC, ddt, da, dskip = pl.pallas_call(
        functools.partial(_backward_kernel, tile=tile),
        out_shape=[
            like(xbc.shape, xbc.dtype), partial_sums, partial_sums,
            like(dt.shape, _F32), like(row.shape, _F32),
            like((b, width, tile.chunk), _F32),
        ],
        grid=(b, blocks, chunks),
        in_specs=[*_packed(spec, tile, heads, groups), spec["row"],
                  spec["row"], spec["col"], spec["skip"], spec["states"],
                  spec["wide"]],
        out_specs=[
            spec["wide"], own, own, spec["row"], spec["row"],
            pl.BlockSpec(
                (None, tile.width, tile.chunk), lambda i, j, t: (i, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tile.width, tile.n), _F32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="ssd_backward",
    )(xbc, xbc, xbc, dt, row, col, skip, entered, dy)
    for at, part in enumerate((dB, dC)):
        if tile.blocks > 1:
            part = part.reshape(b, groups, tile.blocks, tile.n, s).sum(2)
        dxbc = jax.lax.dynamic_update_slice(
            dxbc, part.reshape(b, groups * tile.n, s).astype(xbc.dtype),
            (0, width + at * groups * tile.n, 0))
    return dxbc, ddt, da, dskip


def _operands(dt, a, D, tile: Tiling):
    """``dt`` and ``a`` [b, s, h] a row a head, ``a`` a column a head
    ([b, h / heads, s, heads]), and ``D`` over each head's rows and a
    chunk's lanes."""
    b, s, h = a.shape
    col = a.reshape(b, s, h // tile.heads, tile.heads).transpose(0, 2, 1, 3)
    skip = jnp.broadcast_to(
        jnp.repeat(D.astype(_F32), tile.p)[:, None], (h * tile.p, tile.chunk))
    return dt.swapaxes(1, 2), a.swapaxes(1, 2), col, skip


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scan(xbc, dt, a, D, tile: Tiling, interpret: bool):
    (y,) = _forward_call(
        xbc, *_operands(dt, a, D, tile), tile=tile, keeps=False,
        interpret=interpret)
    return y


def _scan_fwd(xbc, dt, a, D, tile, interpret):
    y, entered = _forward_call(
        xbc, *_operands(dt, a, D, tile), tile=tile, keeps=True,
        interpret=interpret)
    return y, (xbc, dt, a, D, entered)


def _scan_bwd(tile, interpret, residuals, dy):
    xbc, dt, a, D, entered = residuals
    dxbc, ddt, da, dskip = _backward_call(
        xbc, *_operands(dt, a, D, tile), entered, dy, tile=tile,
        interpret=interpret)
    dD = dskip.reshape(-1, D.shape[0], tile.p * tile.chunk).sum((0, 2))
    return dxbc, ddt.swapaxes(1, 2), da.swapaxes(1, 2), dD.astype(D.dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_packed(xbc, dt, A, D, chunk: int, groups: int, state: int, *,
                    mesh=None, interpret: bool = False):
    """:func:`ssd_chunked` by the Pallas kernels, of ``xbc`` [b, s,
    h · p + 2 · g · n]: a Mamba-2 mixer's convolved ``x``, ``B`` and ``C``
    side by side, as the convolution leaves them (the kernels read each
    out of the one array, and their backward writes one array's
    cotangent: no slice is copied either way). ``dt`` [b, s, h], ``A``
    and ``D`` [h]; returns ``y`` [b, s, h · p]. Two kernels tied by one
    ``custom_vjp`` write nothing of size [chunk, chunk] a head to HBM;
    the backward reads the forward's operands and the state every chunk
    was entered with ([b, c, h · p, n] float32; under a block's checkpoint
    the forward that makes them runs again, 1.3 ms a Nemotron layer,
    where keeping them by name held 384 MiB a checkpointed block and cost
    two released blocks: PERF.md §6, PR 62). The cumulative log-decays
    are made here in ``jax.numpy`` ([b, s, h] float32) and differentiated
    as written; ``xbc`` and ``y`` pass with the sequence as their last
    axis, which is how the compiler holds them around the call. The
    caller has asked :func:`uses_kernels`; a declined shape raises. With
    a ``mesh`` of more than one device each device runs the kernels on
    its own sequences (``dp``, where it divides the batch), the sequence
    and the heads whole on every device (``mamba.scan_takes_kernels``
    keeps a mesh that splits the heads, ``tp`` > 1, on the ``jax.numpy``
    form). Mosaic compiles the kernels, for a TPU; ``interpret`` runs them
    in the Pallas interpreter instead (the tests, on a CPU)."""
    (b, s, h), n = dt.shape, state
    p, odd = divmod(xbc.shape[2] - 2 * groups * n, h)
    tile = None if odd else tiling_of(h, groups, p, n, chunk)
    if tile is None or s % chunk:
        raise ValueError(
            f"no tiling for {h} heads in {groups} groups, state {n}, of "
            f"{xbc.shape[2]} channels, {s} tokens in chunks of {chunk}: "
            f"ask uses_kernels first"
        )

    def scan(xbc, dt, A, D):
        dt = dt.astype(_F32)
        a = jnp.cumsum(
            (dt * A.astype(_F32)).reshape(-1, s // chunk, chunk, h), axis=2
        ).reshape(dt.shape)
        return _scan(
            xbc.swapaxes(1, 2), dt, a, D, tile, interpret).swapaxes(1, 2)

    if mesh is None or mesh.size == 1:
        return scan(xbc, dt, A, D)
    dp = mesh.shape.get("dp", 1)
    rows = P("dp" if dp > 1 and b % dp == 0 else None)
    return jax.shard_map(
        scan, mesh=mesh, in_specs=(rows, rows, P(), P()), out_specs=rows,
        # pallas_call's out_shape carries no varying-axes annotation.
        check_vma=False,
    )(xbc, dt, A, D)
