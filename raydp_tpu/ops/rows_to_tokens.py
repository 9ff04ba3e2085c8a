"""Group-ordered rows summed back to their tokens, without a row gather.

``rows_to_tokens(src, weight, place, ends)`` gives, for ``src`` ``[C, D]``
and ``place`` ``[T, k]`` (pair ``(t, j)``'s row of ``src``, or anything
outside ``[0, ends[-1])`` for a pair that has none),

    out[t] = Σ_j weight[t, j] · src[place[t, j]]        (float32, cast once)

which is what a share of a routed layer needs twice a pass
(``models/moe.py``: the way back from expert order to tokens, and the
cotangent of the way there). Written as ``src[place]`` it is a gather of
``T·k`` rows of which a share holds a quarter or an eighth: XLA's TPU
gather walks every index, 28 ns a row at Mellum2's shapes (PERF.md §6,
PR 54).

What makes another way possible: ``ends`` ``[G]`` are the ends of ``G``
groups of ``src``'s rows (the held experts'), a token has at most one
pair in a group, and inside a group the rows ascend by token (the pairs'
sort is stable). So the rows of group ``g`` that belong to a tile of
``tq`` tokens are ONE contiguous run of ``src``. The kernel walks
(token tile, group): it copies the run's rows from HBM in chunks of ``r``
rows that start on a row tile's boundary (the next run's first chunk
while this one is summed), builds the one-hot selection ``place == the
chunk's row`` for the rows inside the run (``[r, tq]``, the tokens along
the lanes: a compare and an OR for each of a token's k pairs), and adds
``weight column · (selectionᵀ @ rows)`` into a float32 ``[tq, D]``
accumulator that stays in VMEM over the tile's groups.
The selection is 0 or 1 and a token matches at most one row of a run, so
``selection @ rows`` IS the token's row, exactly, in one pass of the MXU;
the float32 weight multiplies it in float32: the same products as
``src[place].astype(float32) * weight``, summed in another order. No row
is read by an index, and no array has ``T·k`` rows.

Nothing here rests on the order inside a group for its result: a run is
found as the smallest and the largest place of the tile's pairs in the
group, so an order that did not ascend would make runs longer, not wrong.

The MXU's work is ``T · G · D`` multiply-adds times the 128 rows a pass
holds whatever ``r`` is, so the kernel cannot cost less than ``T·G·D / 512``
cycles (0.79 ms at Mellum2's 16,384 × 16 × 2,304; it reads 1.27, the
accumulator's read-add-write beside each product): its time goes with the
GROUPS, where the gather's goes with the pairs a token, and :func:`pays`
says up to where it is the faster of the two.

Mosaic compiles the kernel for a TPU; anywhere else the same body runs in
the Pallas interpreter (as ``ops/grouped_matmul.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pays", "rows_to_tokens", "run_table", "tiles"]

_F32 = jnp.float32
_I32 = jnp.int32
# Tokens a grid step sums for: the accumulator is [TOKEN_TILE, D] float32.
# 512 read 10-15% faster than 256 at four cells' shapes and no slower than
# 1024 at three of them (PERF.md §6, PR 54).
TOKEN_TILE = 512
# The kernel's time goes with tokens x GROUPS x width, the gather's with
# tokens x PAIRS A TOKEN x width: op alone on a TPU v5 lite 2.0 ns against
# 6.3 ns per thousand of each (PERF.md §6, PR 54), so the kernel is the
# faster one up to about three groups a pair. 16 groups at 8 pairs a token
# read 1.09 ms against the gather's 1.70, 32 groups 2.08 against 1.70.
GROUPS_A_PAIR = 3
# A chunk's copy starts on a multiple of ALIGN rows (a bfloat16 row tile)
# and holds at most MAX_CHUNK rows (one pass of the MXU's contraction).
ALIGN = 16
MAX_CHUNK = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def pays(n_groups: int, pairs_a_token: int) -> bool:
    """Whether the kernel is the faster way to sum ``pairs_a_token`` rows a
    token out of ``n_groups`` groups (:data:`GROUPS_A_PAIR`)."""
    return n_groups <= GROUPS_A_PAIR * pairs_a_token


def tiles(n_tokens: int, n_rows: int, n_groups: int):
    """``(tq, r)`` from the call's shapes. ``r``, the rows of a chunk, is
    what a run holds when the ``n_rows`` are spread evenly over tiles and
    groups, one :data:`ALIGN` more for where it starts, in whole row tiles
    (a share's ``n_rows`` are one and a half times what uniform routing
    sends it, so an even run is already half as long again as the mean
    one; a longer run takes more chunks). ``tq``, the token tile, is the
    largest power of two up to :data:`TOKEN_TILE` that divides
    ``n_tokens`` and whose ``r`` is within :data:`MAX_CHUNK`: a product
    streams ``tq`` rows past each 128 columns the MXU loads, and the
    longer the stream the less the loads show."""
    def chunk(tq):
        even = n_rows // ((n_tokens // tq) * n_groups)
        return -(-(even + ALIGN) // ALIGN) * ALIGN

    fit = [t for t in (512, 256, 128, 64, 32, 16, 8)
           if t <= TOKEN_TILE and n_tokens % t == 0]
    tq = next((t for t in fit if chunk(t) <= MAX_CHUNK),
              fit[-1] if fit else n_tokens)
    return tq, max(1, min(chunk(tq), MAX_CHUNK, n_rows))


def run_table(place, ends, tq: int):
    """``(first, end)`` ``[T/tq · G]`` int32 of every (token tile, group)'s
    run: the smallest place of the tile's pairs inside the group and the
    largest plus one; ``first >= end`` where the tile has no pair there."""
    t, k = place.shape
    ends = ends.astype(_I32)
    starts = jnp.concatenate([jnp.zeros((1,), _I32), ends[:-1]])
    p = place.astype(_I32).reshape(t // tq, tq * k, 1)
    inside = (p >= starts) & (p < ends)
    first = jnp.min(jnp.where(inside, p, jnp.iinfo(_I32).max), axis=1)
    end = jnp.max(jnp.where(inside, p + 1, 0), axis=1)
    return first.reshape(-1), end.reshape(-1)


def _kernel(first_ref, end_ref, turned_ref, *refs, r: int, n_rows: int,
            n_groups: int, weighted: bool):
    if weighted:
        place_ref, weight_ref, src, out_ref, buf, sem, acc = refs
    else:
        src, out_ref, buf, sem, acc = refs
    k, tq = turned_ref.shape
    last = n_rows - r                    # the last row a copy may start at
    aligned = r % ALIGN == 0 and n_rows % ALIGN == 0
    exact = None if src.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def copy(slot, start):
        if aligned:
            start = pl.multiple_of(start, ALIGN)
        return pltpu.make_async_copy(
            src.at[pl.ds(start, r)], buf.at[slot], sem.at[slot]
        )

    def chunk_start(w):
        """Where (tile, group) ``w``'s first chunk starts."""
        first = jnp.minimum(first_ref[w], last)
        return first // ALIGN * ALIGN if aligned else first

    tile = pl.program_id(0)
    work0 = tile * n_groups
    n_work = pl.num_programs(0) * n_groups
    written = first_ref[n_work]          # rows of ``src`` some group wrote

    @pl.when(tile == 0)
    def _():
        copy(0, chunk_start(0)).start()

    acc[...] = jnp.zeros_like(acc)
    row = jax.lax.broadcasted_iota(_I32, (r, 1), 0)

    def add(slot, start, lo, hi):
        """``buf[slot]`` holds ``src[start : start + r]``: the pairs whose
        place is in ``[lo, hi)``. The selection is built with the tokens
        along the lanes (``turned_ref`` is ``place`` transposed): a compare
        and an OR of ``[r, tq]`` for each of a token's k pairs."""
        at = start + row
        wanted = jnp.broadcast_to(
            jnp.where((at >= lo) & (at < hi), at, -2), (r, tq))
        hit = wanted == turned_ref[0:1, :]
        for j in range(1, k):
            hit = hit | (wanted == turned_ref[j:j + 1, :])
        selection = jnp.where(hit, 1.0, 0.0).astype(src.dtype)
        if weighted:
            place = place_ref[...]
            gate = jnp.sum(
                jnp.where((place >= lo) & (place < hi), weight_ref[...], 0.0),
                axis=1, keepdims=True,
            )

        # Rows behind the last group are whatever the kernel that wrote
        # ``src`` left there, and 0 · NaN is NaN.
        @pl.when(start + r > written)
        def _():
            chunk = buf[slot]
            buf[slot] = jnp.where(at < written, chunk, jnp.zeros_like(chunk))

        rows = jax.lax.dot_general(
            selection, buf[slot], (((0,), (0,)), ((), ())),
            precision=exact, preferred_element_type=_F32,
        )
        acc[...] += gate * rows if weighted else rows

    def group(g, _):
        w = work0 + g
        slot = w % 2
        first, end = first_ref[w], end_ref[w]
        start = chunk_start(w)
        copy(slot, start).wait()

        @pl.when(w + 1 < n_work)
        def _():
            copy(1 - slot, chunk_start(w + 1)).start()

        def chunk(n, _):
            lo = start + n * r
            at = jnp.minimum(lo, last)
            here = jnp.where(n == 0, slot, 2)

            @pl.when(n > 0)
            def _():
                again = copy(2, at)
                again.start()
                again.wait()

            add(here, at, jnp.maximum(lo, first), jnp.minimum(lo + r, end))

        n_chunks = jnp.where(end > first, (end - start + r - 1) // r, 0)
        jax.lax.fori_loop(0, n_chunks, chunk, None)

    jax.lax.fori_loop(0, n_groups, group, None)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def rows_to_tokens(src, weight, place, ends):
    """``out[t] = Σ_j weight[t, j] · src[place[t, j]]`` over the pairs whose
    place lies in ``[0, ends[-1])``, float32 inside, in ``src``'s dtype:
    ``src`` ``[C, D]``, ``place`` ``[T, k]`` int32, ``ends`` ``[G]`` the
    ascending ends of the groups of ``src``'s rows (none beyond ``C``; a
    token has at most one pair in a group), ``weight`` ``[T, k]`` float32 or
    None for ones."""
    tq, r = tiles(place.shape[0], src.shape[0], ends.shape[0])
    return _token_sums(
        src, weight, place, ends, tq, r, jax.default_backend() != "tpu"
    )


# Jitted, as megablox's ``gmm`` is: the layers of a model call it at the
# same shapes, and a step program then holds the kernel once (with its
# gates, without) however many layers call it.
@functools.partial(jax.jit, static_argnames=("tq", "r", "interpret"))
def _token_sums(src, weight, place, ends, tq: int, r: int, interpret: bool):
    n_rows, d = src.shape
    t, k = place.shape
    n_groups = ends.shape[0]
    place = place.astype(_I32)
    first, end = run_table(place, ends, tq)
    first = jnp.concatenate([first, ends[-1:].astype(_I32)])
    weighted = weight is not None
    pairs = pl.BlockSpec((tq, k), lambda i, *_: (i, 0))
    operands = (place.T,) + (
        (place, weight.astype(_F32)) if weighted else ()
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, r=r, n_rows=n_rows, n_groups=n_groups, weighted=weighted,
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t // tq,),
            in_specs=[pl.BlockSpec((k, tq), lambda i, *_: (0, i))] + (
                [pairs, pairs] * weighted
            ) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tq, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((3, r, d), src.dtype),
                pltpu.SemaphoreType.DMA((3,)),
                pltpu.VMEM((tq, d), _F32),
            ],
        ),
        # One tile's last copy is the next tile's first: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret, name="rows_to_tokens",
    )(first, end, *operands, src)
