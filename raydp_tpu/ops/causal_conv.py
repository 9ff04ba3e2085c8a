"""Pallas TPU kernels for the causal depthwise convolution with its SiLU
(``models/mamba.CausalConv1d``: the Mamba-2 mixer's one and the three of
a Kimi Delta Attention layer), ONE kernel forward and ONE backward:

    pre_t = b + sum_j w[j] x_{t-(k-1)+j}           y_t = silu(pre_t)

for ``x`` ``[B, S, C]`` in the compute dtype, ``w`` ``[k, C]`` and ``b``
``[C]`` float32 (``b`` may be absent), ``y`` in the dtype asked for.

    forward   reads x            writes y  (or n, each head normalised)
    backward  reads x, dy (dn)   writes dx, and dw, db once a channel block

Both walk a grid of (channel blocks, sequences, sequence blocks), the
sequence blocks one after the other: the forward from the first, with the
last tokens of the block before in a VMEM scratch (zeros at the first), the
backward from the last, with the first tokens of the FOLLOWING block's
``g = dy · silu'(pre)`` in one (``dx_t = sum_j w[j] g_{t+(k-1)-j}``). The
backward makes ``pre`` again from ``x`` (the residual is the operands and
nothing else), and the tokens of ``x`` before a block, which no carry
holds on a walk from the end, come through a second, one-tile
``BlockSpec`` on ``x``. ``dw[j] = sum_t g_t x_{t-(k-1)+j}`` and ``db =
sum_t g_t`` add up in float32 VMEM scratch over a channel block's whole
walk and are written at its end. No padded copy and nothing float32 of the
input's size exists in HBM.

**The L2 norm of a head inside** (``unit``: :class:`Unit`, a static
argument of the same two bodies; ``None`` leaves them what they are). A
delta-rule layer normalises each head of ``q`` and ``k`` after the SiLU
(``models/kda.QKVConv``); as ``jax.numpy`` around the kernels that cost a
float32 ``y`` written and read again, a float32 cotangent for the backward
to read and the broadcasts between them (69 ms of norm and 23 of a
``reshape`` a Kimi Linear step: PERF.md §6, PR 66). With ``unit`` the
forward writes ``n = scale · y · r``, ``r = rsqrt(sum y² + eps)`` over each
run of ``unit.width`` lanes, in the output's dtype (float32 inside, ONE
rounding), and the backward takes ``dn``: it makes ``y`` and ``r`` again
from ``pre`` and forms ``dy = scale · r · (dn − ŷ · sum(dn · ŷ))``, ``ŷ =
y r``, before ``g``. One lane reduction a head a row forward, two backward;
a channel block holds whole heads (at most four of 128 lanes: a constant's
worth of slices, not a shape's). ``scale`` (``d_k^-1/2`` for ``q``, 1 for
``k``) is an operand, a row after the taps, so ``q``'s and ``k``'s call
sites share one lowering each way. Only the channel-minor form takes it: on
the sublanes a head would be 128 rows of one lane.

**Two forms of one body** (:class:`Form`), by which axis of a block the
sequence runs along. A custom call's operands have ONE layout, and the
compiler lays the arrays around a convolution out by what consumes them:
around KDA's the channels are the lanes, ``[B, S, C]`` as written; around
Mamba-2's, whose chunked scan contracts over a chunk's positions, the
SEQUENCE is the lanes, ``[B, C, S]``, in_proj's product and the scan's
operands alike (at heads of 64 and of 128: ``tests/test_olmoe.py``).
A kernel that asks for the other layout pays two transposing copies a
call and moves its neighbours' (PERF.md §6, PR 59: +38 ms of ``data
formatting`` a Nemotron step). So ``sequence_minor`` hands the kernels
``x`` with its axes swapped, which the compiler then makes no copy of,
and the body reads every index through the form: a shifted token is a
rotation along the form's axis with the first (last) tile's worth taken
from the neighbour's, the taps and the bias broadcast along it, the
partial sums fold along it.

Inside a block the body is ``fori_loop``s over chunks of a few vector
registers an array (16 tokens by up to 512 channels, or 32 channels by
1,024 tokens, two such trips a loop body). Only the ``k`` taps and those
two trips are unrolled: the traced body is
the same few dozen operations whatever S, C and the block are, which is
what keeps the time to trace and lower a step that holds it flat. The
arithmetic is the ``jax.numpy`` form's
(``models/mamba.causal_depthwise_conv`` + ``jax.nn.silu``): float32
inside, the taps added in the same order, one rounding to the output's
dtype.

The two calls sit in ``jax.jit``s of their own with every static argument
hashable, so a step's call sites of one (shape, dtypes, taps, bias, form,
unit) share one lowering each way.

**On a device mesh** the pair sits in a ``shard_map`` (XLA cannot
partition a Mosaic kernel: "Mosaic kernels cannot be automatically
partitioned", as ``ops/flash_attention.sharded_flash_attention`` records):
each device convolves its own sequences (``dp``), whole, and the taps' and
the bias's cotangents are summed over the mesh by the ``shard_map``'s own
transpose.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_F32 = jnp.float32
# Bytes of one grid step's blocks (x and y; x, dy and dx), one buffer of
# each: the pipeline holds two, inside the 16 MiB a kernel may use by
# default. 1,024 tokens by 512 channels at bfloat16 in, float32 out.
BLOCK_BYTES = 4 * 2**20


class Form(NamedTuple):
    """How a 2-D block holds a sequence: ``axis`` is the sequence's (0:
    tokens down the sublanes, channels across the lanes; 1: channels down,
    tokens across). ``edge`` is one float32 tile along it, as far as a
    shifted token may reach into the neighbouring chunk; ``chunk`` the
    tokens a trip of the walk takes and ``halo`` those of the second
    ``BlockSpec`` on ``x`` (a whole packed bfloat16 tile); ``group`` the
    channels a trip takes (None: the block's all), ``channel_tile`` what a
    channel block is a multiple of and ``max_channels`` its most;
    ``unroll`` the trips of the walk over the tokens laid side by side in
    one loop body (a trip is one chain of rotation, exponential and
    reciprocal, each waited for: two chains interleave)."""

    axis: int
    edge: int
    chunk: int
    halo: int
    group: Optional[int]
    channel_tile: int
    max_channels: int
    unroll: int = 1


# Channels on the lanes: a float32 chunk is 8 vector registers at 512
# channels, and the backward's working set (x, its shifts, pre, g, dx and
# the k + 1 partial sums of one tile) stays near the 64 there are. A block
# is 512 of Kimi Linear's 4,096 channels. Two or four trips a loop body
# read the same within 2% forward and 9% backward (PERF.md §6, PR 59).
CHANNEL_MINOR = Form(0, 8, 16, 16, None, 128, 512)
# The sequence on the lanes: 32 channels by 1,024 tokens a trip and two
# trips a loop body, 256 channels a block (of Granite's 4,352 = 17·256, of
# Nemotron's 6,144). A lane rotation costs about four cycles a register
# and is waited for: at 16 channels by 512 tokens, one trip a body, the
# kernels alone read 1.97 ms forward and 3.79 backward at Nemotron's
# shape, as set here 0.87 and 2.00 (the sweep in PERF.md §6, PR 59).
SEQUENCE_MINOR = Form(1, 128, 1024, 128, 32, 32, 256, unroll=2)


class Blocks(NamedTuple):
    """A call's tiling: channels a block, tokens a forward block, tokens a
    backward block. Hashable: a static argument of the jitted calls."""

    channels: int
    tokens: int
    tokens_bwd: int


class Unit(NamedTuple):
    """The L2 norm the kernels take in: each run of ``width`` channels (a
    head's) of a token leaves as ``scale · y / sqrt(sum y² + eps)``.
    Hashable: a static argument of the jitted calls (``scale`` is not: it
    rides beside the taps, so calls that differ in it alone share one
    lowering)."""

    width: int
    eps: float


def form_of(sequence_minor: bool) -> Form:
    return SEQUENCE_MINOR if sequence_minor else CHANNEL_MINOR


def channel_block(channels: int, form: Form = CHANNEL_MINOR) -> Optional[int]:
    """The largest multiple of the form's channel tile, up to its most,
    that divides ``channels``; None where none does."""
    fits = [
        c for c in range(form.channel_tile, form.max_channels + 1,
                         form.channel_tile)
        if channels % c == 0
    ]
    return max(fits) if fits else None


def sequence_block(sequence: int, token_bytes: int,
                   form: Form = CHANNEL_MINOR) -> Optional[int]:
    """The longest block of tokens that divides ``sequence``, is whole
    tiles of the form's halo and holds at most :data:`BLOCK_BYTES` at
    ``token_bytes`` a token; None where none does."""
    for blocks in range(1, sequence // form.halo + 1):
        tokens = sequence // blocks
        if (sequence % blocks == 0 and tokens % form.halo == 0
                and tokens * token_bytes <= BLOCK_BYTES):
            return tokens
    return None


def blocks_of(sequence: int, channels: int, taps: int, x_dtype, out_dtype,
              sequence_minor: bool = False,
              unit: Optional[Unit] = None) -> Optional[Blocks]:
    """The tiling of one call, a function of its shape, dtypes, form and
    ``unit`` alone; None where the kernels decline it. With ``unit`` a
    channel block is whole heads (the widest multiple of the head's width
    that divides the channels): the norm never reaches past its block."""
    form = form_of(sequence_minor)
    if unit is not None:
        if sequence_minor or unit.width % form.channel_tile:
            return None
        form = form._replace(channel_tile=unit.width)
    x_dtype, out_dtype = jnp.dtype(x_dtype), jnp.dtype(out_dtype)
    floating = all(
        jnp.issubdtype(d, jnp.floating) and d.itemsize in (2, 4)
        for d in (x_dtype, out_dtype)
    )
    block = channel_block(channels, form)
    if not floating or block is None or not 2 <= taps <= form.edge + 1:
        return None
    x, out = x_dtype.itemsize, out_dtype.itemsize
    tokens = sequence_block(sequence, block * (x + out), form)
    tokens_bwd = sequence_block(sequence, block * (2 * x + out), form)
    if tokens is None or tokens_bwd is None:
        return None
    return Blocks(block, tokens, tokens_bwd)


def uses_kernel(sequence: int, channels: int, taps: int, x_dtype, out_dtype,
                sequence_minor: bool = False,
                unit: Optional[Unit] = None) -> bool:
    """Whether ``CausalConv1d`` takes the kernels at these shapes, read
    from the shapes alone (the caller asks the backend besides: only a TPU
    compiles them): the channels divide into blocks of whole registers
    (128 lanes, or 32 sublanes with the sequence on the lanes), the
    sequence into whole tiles (16 tokens, or 128), and a tap reaches no
    further back than one float32 tile. A single token (a decode step over
    a tail of ``taps - 1`` tokens), a sample row shorter than a tile and a
    width no register tiles keep the ``jax.numpy`` form. With ``unit``
    (the L2 norm of each head inside the kernels) besides: the channels
    are the lanes, a head is whole registers of 128 of them and a channel
    block whole heads (heads of 96 or 192, or any head with the sequence
    on the lanes, where it would lie down the sublanes, keep the norm
    outside)."""
    return blocks_of(
        sequence, channels, taps, x_dtype, out_dtype, sequence_minor, unit
    ) is not None


# ------------------------------------------------------------ the body

def _at(form: Form, tokens, channels):
    """The index of a 2-D block by the form's axes."""
    return (tokens, channels) if form.axis == 0 else (channels, tokens)


def _span(form: Form, a, start: int, stop: int):
    """``a``'s tokens ``start:stop``, every channel."""
    return a[_at(form, slice(start, stop), slice(None))]


def _extent(form: Form, a) -> int:
    return a.shape[form.axis]


def _earlier(form: Form, cur, before, by: int):
    """Token ``t`` of the result is token ``t - by`` of the sequence whose
    chunk ``cur`` is and whose ``edge`` tokens before it are ``before``."""
    if by == 0:
        return cur
    edge, n = form.edge, _extent(form, cur)
    rolled = pltpu.roll(cur, by, form.axis)
    at = jax.lax.broadcasted_iota(jnp.int32, before.shape, form.axis)
    head = jnp.where(
        at < by, pltpu.roll(before, by, form.axis),
        _span(form, rolled, 0, edge),
    )
    if n == edge:
        return head
    return jnp.concatenate(
        [head, _span(form, rolled, edge, n)], axis=form.axis
    )


def _later(form: Form, cur, after, by: int):
    """Token ``t`` of the result is token ``t + by`` of the sequence whose
    chunk ``cur`` is and whose ``edge`` tokens after it are ``after``."""
    if by == 0:
        return cur
    edge, n = form.edge, _extent(form, cur)
    rolled = pltpu.roll(cur, n - by, form.axis)
    at = jax.lax.broadcasted_iota(jnp.int32, after.shape, form.axis)
    tail = jnp.where(
        at >= edge - by, pltpu.roll(after, edge - by, form.axis),
        _span(form, rolled, n - edge, n),
    )
    if n == edge:
        return tail
    return jnp.concatenate(
        [_span(form, rolled, 0, n - edge), tail], axis=form.axis
    )


def _packs(form: Form, dtype) -> bool:
    """Whether a chunk of ``dtype`` is shifted as the 32-bit words it is
    packed in: a 16-bit array with the sequence on the lanes, where a word
    holds two CHANNELS of one token and a lane rotation is the scarce
    operation (one a register a shift; PERF.md §6, PR 59). With the
    tokens down the sublanes a word would hold two tokens."""
    return form.axis == 1 and jnp.dtype(dtype).itemsize == 2


def _carrier(form: Form, a):
    """``a`` as what :func:`_earlier` shifts: its packed words, or its
    float32 values."""
    if _packs(form, a.dtype):
        return pltpu.bitcast(a, jnp.uint32)
    return a.astype(_F32)


def _values(form: Form, carried, dtype):
    """The float32 values of a carrier of ``dtype``'s."""
    if _packs(form, dtype):
        return pltpu.bitcast(carried, dtype).astype(_F32)
    return carried


def _stored(form: Form, carried, dtype):
    """A carrier as the scratch of ``dtype`` holds it."""
    return pltpu.bitcast(carried, dtype) if _packs(form, dtype) else carried


def _weights(form: Form, params, tokens: int):
    """A channel group's taps (and bias), each as an array a chunk of
    ``tokens`` multiplies: a row a channel-minor chunk broadcasts down its
    sublanes where it is used, and with the sequence on the lanes a column
    broadcast ONCE a group to one tile of lanes and laid side by side (a
    lane broadcast is a permute, as scarce as a rotation)."""
    columns = [
        _span(form, params, j, j + 1) for j in range(_extent(form, params))
    ]
    if form.axis == 0:
        return columns
    return [
        jnp.concatenate(
            [jnp.broadcast_to(c, (c.shape[0], form.edge))]
            * (tokens // form.edge), axis=1,
        ) for c in columns
    ]


def _weighted(weights, shifted):
    """``sum_j w[j] shifted[j]`` in the ``jax.numpy`` form's order, on the
    bias where ``weights`` holds one after the taps."""
    out = weights[len(shifted)] if len(weights) > len(shifted) else None
    for w, x in zip(weights, shifted):
        out = w * x if out is None else out + w * x
    return out


def _fold(form: Form, p):
    """A chunk's products summed tile on tile down to one edge."""
    return sum(
        _span(form, p, at, at + form.edge)
        for at in range(0, _extent(form, p), form.edge)
    )


def _walk(form: Form, ref, chunk_tokens: int):
    """``(groups, channels_of, chunks, tokens_of)`` of a block: the trips
    over its channels and over its tokens, and each trip's slice."""
    tokens, channels = ref.shape[form.axis], ref.shape[1 - form.axis]
    group = channels if form.group is None else form.group

    def slice_of(size):
        return lambda i: pl.ds(pl.multiple_of(i * size, size), size)

    return (channels // group, slice_of(group), tokens // chunk_tokens,
            slice_of(chunk_tokens))


def _trips(form: Form, chunks: int, chunk, carry):
    """``carry = chunk(c, carry)`` over ``c`` in ``range(chunks)``,
    ``form.unroll`` of them a loop body where that divides the count."""
    unroll = form.unroll if chunks % form.unroll == 0 else 1

    def body(trip, carry):
        for at in range(unroll):
            carry = chunk(trip * unroll + at, carry)
        return carry

    return jax.lax.fori_loop(0, chunks // unroll, body, carry)


def _chunk_tokens(form: Form, tokens: int) -> int:
    """Tokens a trip: the form's chunk, halved until it divides the block
    (whole halo tiles do: :func:`sequence_block`)."""
    chunk = form.chunk
    while tokens % chunk:
        chunk //= 2
    return chunk


def _heads(a, unit: Unit):
    """A channel-minor chunk's heads, each ``[tokens, width]``: whole
    registers, and at most ``max_channels / 128`` of them, whatever the
    call's shape."""
    return [
        a[:, at:at + unit.width] for at in range(0, a.shape[1], unit.width)
    ]


def _head_sums(a, unit: Unit):
    """The array whose every lane holds the sum of ``a`` [tokens, C] over
    its head's lanes. By the lanes' own reduction: as a product with a
    block of ones on the otherwise idle MXU (three bfloat16 terms of each
    float32, exact) the fused pair read 4.31 ms where 3.29 (PERF.md §6,
    PR 66)."""
    return jnp.concatenate([
        jnp.broadcast_to(jnp.sum(h, axis=1, keepdims=True), h.shape)
        for h in _heads(a, unit)
    ], axis=1)


def _unit(y, scale, unit: Unit):
    """``scale · y / sqrt(sum y² + eps)`` a head: ``models/kda.QKVConv``'s
    arithmetic, one lane reduction a head a row."""
    return y * jax.lax.rsqrt(_head_sums(y * y, unit) + unit.eps) * scale


def _unit_pullback(y, dn, scale, unit: Unit):
    """``dy`` from the cotangent ``dn`` of :func:`_unit`'s result: with
    ``r = rsqrt(sum y² + eps)`` and ``ŷ = y r``, ``dy = scale · r · (dn −
    ŷ · sum(dn · ŷ))``; two lane reductions a head a row (``sum(dn · ŷ) =
    r · sum(dn · y)``: both are of products known before ``r`` is)."""
    r = jax.lax.rsqrt(_head_sums(y * y, unit) + unit.eps)
    return scale * r * (dn - (y * r) * (r * _head_sums(dn * y, unit)))


def _forward_kernel(x_ref, params_ref, y_ref, before_ref, *, form: Form,
                    taps: int, unit: Optional[Unit]):
    """x, the taps (and bias, and with ``unit`` the scale) → y; scratch:
    the ``edge`` tokens before the block, every channel of it, as
    :func:`_earlier` shifts them."""
    step = _chunk_tokens(form, _extent(form, x_ref))
    groups, channels_of, chunks, tokens_of = _walk(form, x_ref, step)
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        before_ref[...] = jnp.zeros_like(before_ref)

    def group(i, _):
        channels = channels_of(i)
        edge = _at(form, slice(None), channels)
        weights = _weights(form, params_ref[edge], step)
        if unit is not None:
            *weights, scale = weights

        def chunk(c, before):
            at = _at(form, tokens_of(c), channels)
            cur = _carrier(form, x_ref[at])
            pre = _weighted(weights, [
                _values(
                    form, _earlier(form, cur, before, taps - 1 - j), dtype)
                for j in range(taps)
            ])
            y = jax.nn.silu(pre)
            if unit is not None:
                y = _unit(y, scale, unit)
            y_ref[at] = y.astype(y_ref.dtype)
            return _span(form, cur, step - form.edge, step)

        before_ref[edge] = _stored(form, _trips(
            form, chunks, chunk, _carrier(form, before_ref[edge])
        ), before_ref.dtype)
        return 0

    jax.lax.fori_loop(0, groups, group, 0)


def _backward_kernel(x_ref, halo_ref, params_ref, dy_ref, dx_ref,
                     dparams_ref, after_ref, sums_ref, *, form: Form,
                     taps: int, unit: Optional[Unit]):
    """x, the ``halo`` tokens of x before the block, the taps (and bias),
    dy → dx and, at a channel block's last step, the sums over every token
    of ``g · x_{t-(k-1)+j}`` (and of ``g``), one a token-axis entry of
    ``dparams``; scratch: the first ``edge`` tokens of the following
    block's g, and the partial sums, one edge each. The grid walks the
    sequence blocks, and the loop the chunks, from the last to the
    first. With ``unit`` the parameters' last row is the scale and ``dy``
    is the NORMALISED output's cotangent: ``y`` and its norm are made
    again from ``pre`` as ``pre`` is from ``x``."""
    step = _chunk_tokens(form, _extent(form, x_ref))
    groups, channels_of, chunks, tokens_of = _walk(form, x_ref, step)
    n_sums = _extent(form, params_ref) - (unit is not None)
    dtype = x_ref.dtype
    batch, block = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(block == 0)
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    @pl.when(jnp.logical_and(batch == 0, block == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def group(i, _):
        channels = channels_of(i)
        edge = _at(form, slice(None), channels)
        weights = _weights(form, params_ref[edge], step)
        if unit is not None:
            *weights, scale = weights
        # The tokens before the block: zeros before the sequence's first
        # (whose halo block is clamped to the block itself).
        halo = _carrier(form, halo_ref[edge])
        halo = _span(form, halo, form.halo - form.edge, form.halo)
        halo = jnp.where(block == last, jnp.zeros_like(halo), halo)

        def chunk(trip, carry):
            cur, after, sums = carry
            c = chunks - 1 - trip
            at = _at(form, tokens_of(c), channels)
            # The chunk before this one is the next trip's own.
            prior = _carrier(form, x_ref[
                _at(form, tokens_of(jnp.maximum(c - 1, 0)), channels)
            ])
            before = jnp.where(
                c > 0, _span(form, prior, step - form.edge, step), halo
            )
            shifted = [
                _values(
                    form, _earlier(form, cur, before, taps - 1 - j), dtype)
                for j in range(taps)
            ]
            pre = _weighted(weights, shifted)
            sig = jax.nn.sigmoid(pre)
            dy = dy_ref[at].astype(_F32)
            if unit is not None:
                dy = _unit_pullback(pre * sig, dy, scale, unit)
            g = dy * (sig * (1.0 + pre * (1.0 - sig)))
            dx = _weighted(weights[:taps], [
                _later(form, g, after, taps - 1 - j) for j in range(taps)
            ])
            dx_ref[at] = dx.astype(dx_ref.dtype)
            products = [g * x for x in shifted] + [g] * (n_sums - taps)
            return prior, _span(form, g, 0, form.edge), tuple(
                s + _fold(form, p) for s, p in zip(sums, products)
            )

        _, after, sums = _trips(form, chunks, chunk, (
            _carrier(
                form, x_ref[_at(form, tokens_of(chunks - 1), channels)]),
            after_ref[edge],
            tuple(sums_ref[(k, *edge)] for k in range(n_sums)),
        ))
        after_ref[edge] = after
        for k, s in enumerate(sums):
            sums_ref[(k, *edge)] = s

        @pl.when(jnp.logical_and(batch == pl.num_programs(1) - 1,
                                 block == last))
        def _():
            for k, s in enumerate(sums):
                dparams_ref[_at(form, slice(k, k + 1), channels)] = s.sum(
                    axis=form.axis, keepdims=True
                )

        return 0

    jax.lax.fori_loop(0, groups, group, 0)


# ------------------------------------------------------------ the calls

def _specs(form: Form, channels: int, n_params: int, block_of_step):
    """``(walked, params)``: the ``BlockSpec`` factory of an array ``[B,
    tokens, channels]`` (or ``[B, channels, tokens]``) walked ``tokens`` a
    grid step, at the block ``at(step)`` of its token axis, and the spec
    of the parameters ``[n, C]`` (or ``[C, n]``)."""

    def walked(tokens: int, at=block_of_step):
        return pl.BlockSpec(
            (None, *_at(form, tokens, channels)),
            lambda c, i, t: (i, *_at(form, at(t), c)),
        )

    params = pl.BlockSpec(
        _at(form, n_params, channels), lambda c, i, t: _at(form, 0, c)
    )
    return walked, params


def _stacked(form: Form, w, b, scale=None):
    """The taps and, after them, the bias: ``[k (+ 1), C]``, or ``[C, k
    (+ 1)]`` with the sequence on the lanes; last, a row of the norm's
    ``scale`` where there is one."""
    rows = w if b is None else jnp.concatenate([w, b[None]], axis=0)
    if scale is not None:
        rows = jnp.concatenate(
            [rows, jnp.full_like(rows[:1], scale)], axis=0)
    return rows if form.axis == 0 else rows.T


def _grid(form: Form, x, blocks: Blocks, tokens: int):
    batch = x.shape[0]
    sequence, channels = x.shape[1 + form.axis], x.shape[2 - form.axis]
    return (channels // blocks.channels, batch, sequence // tokens)


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "form", "blocks", "unit", "interpret"),
)
def _forward_call(x, w, b, scale, *, out_dtype, form: Form, blocks: Blocks,
                  unit: Optional[Unit], interpret: bool):
    params = _stacked(form, w, b, scale)
    walked, params_spec = _specs(
        form, blocks.channels, _extent(form, params), lambda t: t
    )
    return pl.pallas_call(
        functools.partial(
            _forward_kernel, form=form, taps=w.shape[0], unit=unit),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        grid=_grid(form, x, blocks, blocks.tokens),
        in_specs=[walked(blocks.tokens), params_spec],
        out_specs=walked(blocks.tokens),
        scratch_shapes=[pltpu.VMEM(
            _at(form, form.edge, blocks.channels),
            x.dtype if _packs(form, x.dtype) else _F32,
        )],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="causal_conv_forward",
    )(x, params)


@functools.partial(
    jax.jit, static_argnames=("form", "blocks", "unit", "interpret"))
def _backward_call(x, w, b, scale, dy, *, form: Form, blocks: Blocks,
                   unit: Optional[Unit], interpret: bool):
    taps, tokens = w.shape[0], blocks.tokens_bwd
    params = _stacked(form, w, b, scale)
    n_sums = taps + (b is not None)
    grid = _grid(form, x, blocks, tokens)
    n, halos = grid[2], tokens // form.halo
    walked, params_spec = _specs(
        form, blocks.channels, _extent(form, params), lambda t: n - 1 - t
    )
    # The sums leave as whole tiles: the first ``n_sums`` entries along
    # the token axis are read.
    sums_shape = _at(form, form.edge, x.shape[2 - form.axis])
    dx, dparams = pl.pallas_call(
        functools.partial(
            _backward_kernel, form=form, taps=taps, unit=unit),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(sums_shape, _F32),
        ],
        grid=grid,
        in_specs=[
            walked(tokens),
            walked(form.halo, lambda t: jnp.maximum(
                (n - 1 - t) * halos - 1, 0)),
            params_spec,
            walked(tokens),
        ],
        out_specs=[
            walked(tokens),
            pl.BlockSpec(
                _at(form, form.edge, blocks.channels),
                lambda c, i, t: _at(form, 0, c),
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM(_at(form, form.edge, blocks.channels), _F32),
            pltpu.VMEM(
                (n_sums, *_at(form, form.edge, blocks.channels)), _F32
            ),
        ],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="causal_conv_backward",
    )(x, x, params, dy)
    sums = dparams if form.axis == 0 else dparams.T
    # The scale is no parameter: nothing is summed for it.
    return (dx, sums[:taps], None if b is None else sums[taps],
            None if scale is None else jnp.zeros_like(scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _conv(x, w, b, scale, out_dtype, form, blocks, unit, interpret):
    return _forward_call(
        x, w, b, scale, out_dtype=out_dtype, form=form, blocks=blocks,
        unit=unit, interpret=interpret,
    )


def _conv_fwd(x, w, b, scale, out_dtype, form, blocks, unit, interpret):
    y = _conv(x, w, b, scale, out_dtype, form, blocks, unit, interpret)
    return y, (x, w, b, scale)


def _conv_bwd(out_dtype, form, blocks, unit, interpret, residuals, dy):
    return _backward_call(
        *residuals, dy, form=form, blocks=blocks, unit=unit,
        interpret=interpret,
    )


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, kernel, bias=None, *, dtype,
                     sequence_minor: bool = False, mesh=None,
                     interpret: bool = False,
                     blocks: Optional[Blocks] = None,
                     unit: Optional[Unit] = None, scale=1.0):
    """``silu(bias + sum_j kernel[j] x_{t-(k-1)+j})`` over the sequence
    axis of ``x`` [B, S, C], zeros before the first token, in ``dtype``:
    the two kernels, tied by one ``custom_vjp`` (differentiable in ``x``,
    ``kernel`` [k, C] and ``bias`` [C] or None). ``sequence_minor`` says
    which layout the arrays around the call are in (the module's
    docstring): with it the kernels read and write ``[B, C, S]``. With a
    ``mesh`` of more than one device each device runs them on its own
    sequences (``dp``, where it divides the batch: the batch-1 sample of
    ``model.init`` stays whole), the sequence and the channels whole on
    every device. With ``unit`` each head of ``unit.width`` channels
    leaves L2-normalised and times ``scale`` (a number, or a traced
    scalar: an operand of the kernels, not a constant of their bodies),
    and the cotangent taken is that result's. The caller has asked
    :func:`uses_kernel`; ``blocks`` (a test's own tiling) is
    :func:`blocks_of` the shapes where left out. ``interpret`` runs the
    bodies in the Pallas interpreter (any backend)."""
    dtype, form = jnp.dtype(dtype), form_of(sequence_minor)
    if blocks is None:
        blocks = blocks_of(
            x.shape[1], x.shape[2], kernel.shape[0], x.dtype, dtype,
            sequence_minor, unit,
        )
    elif unit is not None and (
            sequence_minor or unit.width % form.channel_tile
            or blocks.channels % unit.width):
        blocks = None       # a test's tiling that blocks_of would not give
    if blocks is None:
        raise ValueError(
            f"no tiling for x {x.shape} {x.dtype} with {kernel.shape[0]} "
            f"taps to {dtype} (sequence_minor {sequence_minor}, {unit}): "
            "ask uses_kernel first"
        )
    has_bias = bias is not None

    def conv(x, kernel, *rest):
        if sequence_minor:
            x = x.swapaxes(1, 2)
        y = _conv(
            x, kernel, rest[0] if has_bias else None,
            None if unit is None else rest[-1], dtype, form, blocks, unit,
            interpret,
        )
        return y.swapaxes(1, 2) if sequence_minor else y

    # The parameters enter the kernels in float32, as the ``jax.numpy``
    # form reads them (the configurations hold them so: no cast is made).
    operands = (x, kernel.astype(_F32)) + (
        (bias.astype(_F32),) if has_bias else ()) + (
        () if unit is None else (jnp.asarray(scale, _F32),))
    if mesh is None or mesh.size == 1:
        return conv(*operands)
    dp = mesh.shape.get("dp", 1)
    rows = P("dp" if dp > 1 and x.shape[0] % dp == 0 else None)
    return jax.shard_map(
        conv, mesh=mesh, in_specs=(rows,) + (P(),) * (len(operands) - 1),
        out_specs=rows,
        # pallas_call's out_shape carries no varying-axes annotation.
        check_vma=False,
    )(*operands)
