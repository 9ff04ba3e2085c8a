"""Pallas TPU kernels for the two mixings of a multi-stream residual path
(``models/hyperconn.py``): each mixing, forward and backward, is ONE pass
over the streams in their own dtype.

    read    h      = sum_i pre[i] x[i]
    write   x'[i]  = sum_j res[i, j] x[j] + post[i] y

for streams ``x`` ``[B, n, S, D]``, a sublayer's input or output ``[B, S,
D]`` and per-token coefficients. Four kernels, tied by two ``custom_vjp``s:

    read  forward   reads x            writes h
    read  backward  reads gh, x        writes gx[i] = pre[i] gh and
                                       g_pre[i] = sum_d gh x[i]
    write forward   reads x, y         writes x'
    write backward  reads gx', x, y    writes gx[j] = sum_i res[i, j] gx'[i],
                                       gy = sum_i post[i] gx'[i] and
                                       g_post[i] = sum_d gx'[i] y,
                                       g_res[i, j] = sum_d gx'[i] x[j]

Every grid step holds a ``[n, tile, D]`` block of tokens with the whole
feature axis, so the reductions over ``D`` end inside the step and every
grid axis is parallel. Inside a block the body walks ``[16, 896]``
chunks (at a width of 3,584), a few vector registers a stream: products and sums are float32
THERE, one rounding to the carrier's dtype where a chunk of an output is
stored. Nothing float32 of a stream's size exists in HBM, forward or
backward; the residuals are the operands themselves.

The coefficients arrive as COLUMNS ``[B, S, k]`` (tokens down the
sublanes, like the rows they multiply; ``k`` is ``n`` for ``read`` and
``n + n²`` for ``write``) and their gradients leave the same way: a
``[S, 1]`` column a coefficient would be padded to 128 lanes each in HBM's
(8, 128) tiling (PERF.md §6, PR 35), ``k`` entries side by side are padded
once. The caller lays them out (``models/hyperconn.py``, once a mixing).

A token count the tile does not divide ends in a ragged block: tokens are
independent, so what the padding rows compute is never stored. The kernels
are compiled by Mosaic for a TPU; on any other backend the same bodies run
in the Pallas interpreter (as ``ops/grouped_matmul.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One chunk of the walk inside a block: ROWS tokens (a packed bfloat16 tile
# is 16 rows) by the widest multiple of LANE lanes (a register) up to
# MAX_LANES that divides the feature width: 896 of 3,584. At [1, 4, 4096,
# 3584] bfloat16 on a TPU v5 lite the write's backward took 1.32 ms in
# 128-lane chunks, 0.73 in 256, 0.69 in 512 (wall clock), 0.67 in 896 and
# 0.61 with seven 512-lane chunks unrolled (device time; the other three
# kernels the same either way: 0.7 ms of a 254 ms step for seven times the
# straight-line code, not taken); 8 and 32 rows, 64-token blocks: no
# faster (PERF.md §6, PR 37).
ROWS = 16
LANE = 128
MAX_LANES = 1024
# Bytes of ONE stream's [tile, D] slab in a block: 32 tokens at D = 3,584
# in bfloat16. The write's backward holds 14 such slabs twice (double
# buffering): 6.4 MB of the 16 MB a kernel may use.
SLAB_BYTES = 256 * 1024


def tileable(dtype, width: int) -> bool:
    """Whether the kernels take streams of this dtype and feature width:
    a floating carrier whose features fill whole 128-lane registers."""
    return bool(jnp.issubdtype(dtype, jnp.floating)) and width % LANE == 0


def token_tile(tokens: int, width: int, dtype) -> int:
    """Tokens a block: a multiple of :data:`ROWS` near :data:`SLAB_BYTES`
    a stream, no more than the tokens there are (all of them under one
    row group)."""
    if tokens <= ROWS:
        return tokens
    fit = SLAB_BYTES // (width * jnp.dtype(dtype).itemsize)
    return max(ROWS, min(fit, tokens) // ROWS * ROWS)


def _walk(tile: int, width: int, group) -> None:
    """``group(rows, over_lanes)`` for every row group of a ``[tile, width]``
    block; ``over_lanes(chunk, carry)`` then runs ``carry = chunk(lanes,
    carry)`` over the group's [ROWS, lanes] chunks and returns the last."""
    rows = min(ROWS, tile)
    lanes = max(
        c for c in range(LANE, MAX_LANES + 1, LANE) if width % c == 0
    )

    def over_lanes(chunk, carry):
        return jax.lax.fori_loop(
            0, width // lanes,
            lambda c, acc: chunk(
                pl.ds(pl.multiple_of(c * lanes, lanes), lanes), acc
            ),
            carry,
        )

    def body(r, _):
        group(pl.ds(pl.multiple_of(r * rows, rows), rows), over_lanes)
        return 0

    jax.lax.fori_loop(0, tile // rows, body, 0)


def _columns(ref, at):
    """The coefficients of one row group, each as a ``[rows, 1]`` column."""
    m = ref[0, at, :]
    return [m[:, k:k + 1] for k in range(m.shape[1])]


def _store_columns(ref, at, sums):
    """``sums``, partial sums ``[rows, LANE]`` one a coefficient, reduced
    over their lanes into the columns of ``ref``."""
    rows = sums[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, len(sums)), 1)
    out = jnp.zeros((rows, len(sums)), jnp.float32)
    for k, s in enumerate(sums):
        out = jnp.where(lane == k, s.sum(axis=1, keepdims=True), out)
    ref[0, at, :] = out


def _f32(ref, *at):
    return ref[at].astype(jnp.float32)


def _zeros(tile: int, count: int):
    return (jnp.zeros((min(ROWS, tile), LANE), jnp.float32),) * count


def _fold(p):
    """A chunk's products ``[rows, lanes]`` summed register on register
    down to one register's lanes."""
    return sum(p[:, k:k + LANE] for k in range(0, p.shape[1], LANE))


def _read_kernel(cols_ref, x_ref, h_ref, *, n: int):
    tile, width = h_ref.shape[1:]

    def group(at, over_lanes):
        pre = _columns(cols_ref, at)

        def chunk(lanes, carry):
            h = sum(pre[i] * _f32(x_ref, 0, i, at, lanes) for i in range(n))
            h_ref[0, at, lanes] = h.astype(h_ref.dtype)
            return carry

        over_lanes(chunk, 0)

    _walk(tile, width, group)


def _read_bwd_kernel(cols_ref, x_ref, gh_ref, gx_ref, gcols_ref, *, n: int):
    tile, width = gh_ref.shape[1:]

    def group(at, over_lanes):
        pre = _columns(cols_ref, at)

        def chunk(lanes, sums):
            gh = _f32(gh_ref, 0, at, lanes)
            for i in range(n):
                gx_ref[0, i, at, lanes] = (pre[i] * gh).astype(gx_ref.dtype)
            return tuple(
                s + _fold(gh * _f32(x_ref, 0, i, at, lanes))
                for i, s in enumerate(sums)
            )

        _store_columns(gcols_ref, at, over_lanes(chunk, _zeros(tile, n)))

    _walk(tile, width, group)


def _write_kernel(cols_ref, x_ref, y_ref, out_ref, *, n: int):
    tile, width = y_ref.shape[1:]

    def group(at, over_lanes):
        cols = _columns(cols_ref, at)
        post, res = cols[:n], cols[n:]

        def chunk(lanes, carry):
            y = _f32(y_ref, 0, at, lanes)
            x = [_f32(x_ref, 0, j, at, lanes) for j in range(n)]
            for i in range(n):
                out = post[i] * y
                for j in range(n):
                    out = out + res[i * n + j] * x[j]
                out_ref[0, i, at, lanes] = out.astype(out_ref.dtype)
            return carry

        over_lanes(chunk, 0)

    _walk(tile, width, group)


def _write_bwd_kernel(cols_ref, x_ref, y_ref, g_ref, gx_ref, gy_ref,
                      gcols_ref, *, n: int):
    tile, width = y_ref.shape[1:]

    def group(at, over_lanes):
        cols = _columns(cols_ref, at)
        post, res = cols[:n], cols[n:]

        def chunk(lanes, sums):
            y = _f32(y_ref, 0, at, lanes)
            x = [_f32(x_ref, 0, j, at, lanes) for j in range(n)]
            g = [_f32(g_ref, 0, i, at, lanes) for i in range(n)]
            gy_ref[0, at, lanes] = sum(
                post[i] * g[i] for i in range(n)
            ).astype(gy_ref.dtype)
            for j in range(n):
                gx_ref[0, j, at, lanes] = sum(
                    res[i * n + j] * g[i] for i in range(n)
                ).astype(gx_ref.dtype)
            products = [g[i] * y for i in range(n)] + [
                g[i] * x[j] for i in range(n) for j in range(n)
            ]
            return tuple(s + _fold(p) for s, p in zip(sums, products))

        _store_columns(
            gcols_ref, at, over_lanes(chunk, _zeros(tile, n + n * n))
        )

    _walk(tile, width, group)


def _call(kernel, cols, operands, outputs, interpret: bool):
    """One kernel over blocks of ``tile`` tokens. ``operands`` and
    ``outputs`` are ``[B, n, S, D]`` streams or ``[B, S, D]`` single ones
    (arrays and ``ShapeDtypeStruct``s); the coefficients' columns ``cols``
    ``[B, S, k]`` go in first and, where the kernel has gradients of them,
    a float32 array of their shape comes out last.

    Streams and results are pinned to HBM: each kernel streams its blocks
    through its own double-buffered pipeline. Left to itself the compiler
    parks a whole 117 MB stream in the chip's 128 MiB of VMEM between two
    kernels, which reads well for the kernels (PERF.md §6, PR 37) and
    leaves that much less for the weights other operations prefetch."""
    b, s, d = operands[0].shape[0], *operands[0].shape[-2:]
    n = operands[0].shape[1]
    tile = token_tile(s, d, operands[0].dtype)

    def spec(a):
        if len(a.shape) == 4:
            return pl.BlockSpec(
                (1, a.shape[1], tile, d), lambda bi, ti: (bi, 0, ti, 0)
            )
        return pl.BlockSpec(
            (1, tile, a.shape[-1]), lambda bi, ti: (bi, ti, 0)
        )

    def in_hbm(a):
        # The interpreter has no memory spaces.
        return a if interpret else pltpu.with_memory_space_constraint(
            a, pltpu.HBM
        )

    def mix(cols, *operands):
        return pl.pallas_call(
            functools.partial(kernel, n=n),
            out_shape=[
                a if interpret else pltpu.HBM(a.shape, a.dtype)
                for a in outputs
            ],
            grid=(b, pl.cdiv(s, tile)),
            in_specs=[spec(a) for a in (cols, *operands)],
            out_specs=[spec(a) for a in outputs],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=interpret,
        )(cols, *map(in_hbm, operands))

    # A memory space constrains a traced value, not an eager one.
    return (mix if interpret else jax.jit(mix))(cols, *operands)


def _like(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _interpret() -> bool:
    # Mosaic compiles the kernels for a TPU; elsewhere the same bodies run
    # in the Pallas interpreter (as ``ops/grouped_matmul.py``).
    return jax.default_backend() != "tpu"


def read(x, cols):
    """``h = sum_i cols[..., i] x[:, i]``: ``x`` ``[B, n, S, D]`` and
    float32 ``cols`` ``[B, S, n]`` to ``[B, S, D]`` in ``x``'s dtype."""
    return _read(x, cols, _interpret())


def write(x, y, cols):
    """``x'[:, i] = sum_j res[i, j] x[:, j] + post[i] y`` in ``x``'s
    dtype, for float32 ``cols`` ``[B, S, n + n²]``: ``post`` first, then
    ``res`` row by row."""
    return _write(x, y, cols, _interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _read(x, cols, interpret: bool):
    h = jax.ShapeDtypeStruct(x.shape[:1] + x.shape[2:], x.dtype)
    return _call(_read_kernel, cols, (x,), [h], interpret)[0]


def _read_fwd(x, cols, interpret):
    return _read(x, cols, interpret), (x, cols)


def _read_bwd(interpret, residuals, gh):
    x, cols = residuals
    return tuple(_call(
        _read_bwd_kernel, cols, (x, gh), [_like(x), _like(cols)], interpret
    ))


_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _write(x, y, cols, interpret: bool):
    return _call(_write_kernel, cols, (x, y), [_like(x)], interpret)[0]


def _write_fwd(x, y, cols, interpret):
    return _write(x, y, cols, interpret), (x, y, cols)


def _write_bwd(interpret, residuals, g):
    x, y, cols = residuals
    return tuple(_call(
        _write_bwd_kernel, cols, (x, y, g),
        [_like(x), _like(y), _like(cols)], interpret,
    ))


_write.defvjp(_write_fwd, _write_bwd)
