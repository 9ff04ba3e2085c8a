"""The causal depthwise convolution's Pallas kernels (``ops/causal_conv.py``)
in the Pallas interpreter at tiny sizes (blocks of two trips of the walk: 32
tokens with the channels on the lanes, 256 with the sequence on them, in
chunks of 128 there, two a loop body): forward and the three gradients
against ``jax.vjp`` of the ``jax.numpy`` form (``models/mamba.causal_depthwise_conv`` + SiLU) with and
without a bias, to bfloat16 and to float32, over channel counts no power of
two divides (3 and 17 of the form's tile, as Granite's 4,352 = 17·256)
and 1, 2 and 5 sequence blocks, in both forms of the body; the tokens
carried across a block's edge each way; which shapes the predicate takes
and how a shape is tiled; the two ``conv/*_calls`` gauges against the calls
a traced gradient holds; the call on a device mesh (in a ``shard_map``, or
not taken at all: XLA partitions no Mosaic kernel); and what a step that
holds the kernels costs to LOWER: one kernel body each way however many
call sites, and a lowered text whose size does not follow the sequence.
The kernels with the L2 norm of each head inside (``unit``: q's and k's of
a delta-rule layer with heads of whole registers) run through the same
tests as cases of their own, against the ``jax.numpy`` convolution +
``models/kda.QKVConv.unit`` + scale + cast; without ``unit`` the traced
program is the one this file pinned before they could."""
import contextlib
import dataclasses
import functools
import hashlib
import itertools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from raydp_tpu.models import CausalLM
from raydp_tpu.models import kda as kda_model
from raydp_tpu.models import mamba
from raydp_tpu.models import step as model_step
from raydp_tpu.models.mamba import CausalConv1d, causal_depthwise_conv
from raydp_tpu.models.transformer import granite_h_micro
from raydp_tpu.ops import causal_conv
from raydp_tpu.ops.causal_conv import Blocks, Unit, causal_conv_silu
from raydp_tpu.utils.profiling import metrics

TAPS = 4
BF16, F32 = jnp.bfloat16, jnp.float32
# One rounding of a bfloat16 result (8 bits of mantissa); a float32
# result's 1e-5, both relative to the array's largest magnitude.
TOLERANCE = {jnp.dtype(BF16): 2.0 ** -8, jnp.dtype(F32): 1e-5}

# (bias, output dtype, channels, sequence blocks, x dtype, sequence_minor,
# (a head's width, scale) of the norm inside or None).
# The two calls the models make, each over its form's channel counts (3 and
# 17 of the form's tile: 384 and 2,176 in 128 lanes, 96 and 544 in 32
# sublanes) and 1, 2 and 5 sequence blocks (one and five at the narrow
# count alone: a case costs a second to compile): the Mamba-2 mixer's
# (a bias, bfloat16 out, the sequence on the lanes) and KDA's (no bias,
# float32 out, the channels on them; x in float32 at one corner, where dx
# then holds the float32 tolerance too). Then each form with the other's
# bias and output dtype, at two blocks of seventeen channel blocks. Last,
# q's and k's of a delta-rule layer with the norm inside (no bias, out in
# the compute dtype, the channels on the lanes): a channel block of ONE
# head (a head of 384 in 384 channels; heads of 128 in 2,176 = 17 · 128)
# and of FOUR (heads of 128 in 1,024 = 2 · 512), q's scale and k's; two
# sequence blocks, so the carries cross a boundary under the norm, and with
# them two sequences; x in bfloat16 and in float32.
MAMBA2, KDA = (True, BF16, True), (False, F32, False)
CHANNELS = {True: (96, 544), False: (384, 2176)}
CASES = [
    (bias, out, channels, blocks,
     F32 if (out, channels) == (F32, 384) else BF16, minor, None)
    for bias, out, minor in (MAMBA2, KDA)
    for channels, blocks in itertools.product(CHANNELS[minor], (1, 2, 5))
    if channels == CHANNELS[minor][0] or blocks == 2
] + [
    (bias, out, CHANNELS[minor][1], 2, BF16, minor, None)
    for bias, out, minor in ((False, F32, True), (True, BF16, False))
] + [
    (False, BF16, 1024, 2, BF16, False, (128, 128 ** -0.5)),
    (False, BF16, 2176, 2, BF16, False, (128, 1.0)),
    (False, F32, 384, 1, F32, False, (384, 384 ** -0.5)),
    (False, BF16, 512, 2, F32, False, (256, 1.0)),
]


def _id(case):
    bias, out, channels, blocks, x, sequence_minor, unit = case
    return "-".join((
        "bias" if bias else "nobias", f"x_{jnp.dtype(x).name}",
        f"out_{jnp.dtype(out).name}", f"c{channels}", f"blocks{blocks}",
        "seq_minor" if sequence_minor else "ch_minor",
    ) + (() if unit is None else (f"unit{unit[0]}_scale{unit[1]:.3f}",)))


def plain(x, kernel, bias, dtype, unit=None):
    """The form ``CausalConv1d`` runs wherever the kernels do not, and
    with ``unit`` (a head's width, scale) what ``QKVConv`` does to its
    float32 result."""
    y = jax.nn.silu(causal_depthwise_conv(x, kernel, bias))
    if unit is not None:
        width, scale = unit
        y = kda_model.QKVConv.unit(
            y.reshape(*y.shape[:-1], -1, width)) * scale
    return y.reshape(x.shape).astype(dtype)


def _norm(unit):
    """``causal_conv_silu``'s two arguments for a case's ``unit``."""
    if unit is None:
        return {}
    return dict(unit=Unit(unit[0], kda_model.L2_EPS), scale=unit[1])


@contextlib.contextmanager
def small_chunks():
    """The sequence-minor walk in chunks of one tile (1,024 tokens a trip
    as the module has it): a test's block of 256 tokens is then one loop
    body of two trips (the channel-minor form's 32 are two bodies of
    one)."""
    form = causal_conv.SEQUENCE_MINOR
    causal_conv.SEQUENCE_MINOR = form._replace(chunk=form.halo)
    try:
        yield
    finally:
        causal_conv.SEQUENCE_MINOR = form


def tiled(channels: int, sequence_minor: bool = False, unit=None) -> Blocks:
    """Blocks of two trips of the walk, of ``blocks_of``'s channels."""
    form = causal_conv.form_of(sequence_minor)
    tokens = 2 * form.halo
    block = causal_conv.blocks_of(
        tokens, channels, TAPS, BF16, BF16, sequence_minor,
        unit and Unit(unit[0], kda_model.L2_EPS)).channels
    return Blocks(block, tokens, tokens)


@functools.lru_cache(maxsize=None)
def both(case):
    """(y, dx, dkernel, dbias) by the kernels and by the ``jax.numpy``
    form. The reference differentiates at ``x`` in float32 and rounds its
    ``dx`` once, as the kernel does: ``jax.grad`` at a bfloat16 ``x``
    rounds each tap's term and adds them in bfloat16."""
    bias, out, channels, blocks, x_dtype, sequence_minor, unit = case
    batch = 2 if blocks == 2 else 1
    keys = jax.random.split(jax.random.PRNGKey(channels + blocks), 4)
    tiling = tiled(channels, sequence_minor, unit)
    shape = (batch, blocks * tiling.tokens, channels)
    x = jax.random.normal(keys[0], shape, F32).astype(x_dtype)
    kernel = jax.random.uniform(keys[1], (TAPS, channels), F32, -0.5, 0.5)
    b = jax.random.uniform(keys[2], (channels,), F32, -0.5, 0.5) if (
        bias) else None
    dy = jax.random.normal(keys[3], shape, F32).astype(out)

    @jax.jit
    def run(x, kernel, b, dy):
        y, vjp = jax.vjp(
            lambda *a: causal_conv_silu(
                *a, dtype=out, interpret=True, blocks=tiling,
                sequence_minor=sequence_minor, **_norm(unit)),
            x, kernel, b,
        )
        want, vjp_plain = jax.vjp(
            lambda *a: plain(*a, out, unit), x.astype(F32), kernel, b
        )
        dx, dk, db = vjp_plain(dy)
        return (y, *vjp(dy)), (want, dx.astype(x.dtype), dk, db)

    with small_chunks():
        return run(x, kernel, b, dy)


def _close(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    tolerance = TOLERANCE[jnp.dtype(got.dtype)]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tolerance * np.abs(want).max()


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_forward_matches_the_jnp_form(case):
    got, want = both(case)
    _close(got[0], want[0])


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_dx_matches_the_jnp_forms_gradient(case):
    got, want = both(case)
    _close(got[1], want[1])


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_dkernel_matches_the_jnp_forms_gradient(case):
    got, want = both(case)
    _close(got[2], want[2])


@pytest.mark.parametrize(
    "case", [case for case in CASES if case[0]], ids=_id)
def test_dbias_matches_the_jnp_forms_gradient(case):
    got, want = both(case)
    _close(got[3], want[3])


def test_no_bias_has_no_bias_gradient():
    case = next(case for case in CASES if not case[0])
    got, want = both(case)
    assert got[3] is None and want[3] is None


# ------------------------------------------------ across a block's edge

def _taps(channels=128):
    """Tap ``j`` weighs ``j + 1`` in every channel."""
    return jnp.broadcast_to(
        jnp.arange(1.0, TAPS + 1)[:, None], (TAPS, channels)
    ).astype(F32)


FORMS = pytest.mark.parametrize(
    "sequence_minor", [False, True], ids=["ch_minor", "seq_minor"])


def _edge_case(sequence_minor):
    """``(run, tokens a block)``: three blocks of 128 channels."""
    tiling = tiled(128, sequence_minor)

    def run(x):
        with small_chunks():
            return causal_conv_silu(
                x, _taps(), dtype=F32, interpret=True, blocks=tiling,
                sequence_minor=sequence_minor)

    return run, tiling.tokens


@FORMS
def test_an_impulse_at_a_blocks_last_token_shows_in_the_next_blocks_first(
        sequence_minor):
    run, rows = _edge_case(sequence_minor)
    x = jnp.zeros((1, 3 * rows, 128), BF16).at[0, rows - 1].set(1.0)
    y = run(x)[0]
    # out_t = sum_j w[j] x_{t-3+j}: the newest tap first, then the older.
    for ahead in range(TAPS):
        np.testing.assert_allclose(
            y[rows - 1 + ahead], jax.nn.silu(float(TAPS - ahead)), rtol=1e-6)
    lit = np.zeros(3 * rows, bool)
    lit[rows - 1:rows - 1 + TAPS] = True
    assert not np.asarray(y)[~lit].any()


@FORMS
def test_a_cotangent_at_a_blocks_first_token_reaches_the_block_before(
        sequence_minor):
    run, rows = _edge_case(sequence_minor)
    x = jnp.zeros((1, 3 * rows, 128), BF16)
    dy = jnp.zeros((1, 3 * rows, 128), F32).at[0, 2 * rows].set(1.0)
    dx = np.asarray(jax.vjp(run, x)[1](dy)[0][0], np.float32)
    # pre = 0 everywhere, silu'(0) = 1/2: dx_t = w[t + 3 - 2·rows] / 2.
    for j in range(TAPS):
        np.testing.assert_allclose(dx[2 * rows - 3 + j], (j + 1) / 2)
    lit = np.zeros(3 * rows, bool)
    lit[2 * rows - 3:2 * rows + 1] = True
    assert not dx[~lit].any()


@FORMS
def test_the_first_tokens_see_zeros_before_the_sequence_in_every_sequence(
        sequence_minor):
    """Two sequences, two blocks each: the second sequence's first tokens
    must not see the first one's last."""
    run, rows = _edge_case(sequence_minor)
    x = jnp.ones((2, 2 * rows, 128), BF16)
    y = run(x)
    np.testing.assert_allclose(y, plain(x, _taps(), None, F32), rtol=1e-6)
    np.testing.assert_allclose(y[1, 0], jax.nn.silu(float(TAPS)), rtol=1e-6)


def test_an_all_zero_head_has_eps_alone_under_the_root():
    """``x`` = 0: ``y`` = 0 in every head, the norm's root holds ``eps``
    alone (``r`` = 1,000), the output is 0 and ``dy = scale · r · dn``."""
    unit, scale = Unit(128, kda_model.L2_EPS), 0.5
    tiling = tiled(256, unit=(128, scale))
    x = jnp.zeros((1, 2 * tiling.tokens, 256), BF16)
    dn = jax.random.normal(jax.random.PRNGKey(0), x.shape, F32)
    y, vjp = jax.vjp(
        lambda x: causal_conv_silu(
            x, _taps(256), dtype=F32, interpret=True, blocks=tiling,
            unit=unit, scale=scale),
        x)
    assert not np.asarray(y).any()
    want = jax.vjp(
        lambda x: plain(x, _taps(256), None, F32, (128, scale)),
        x.astype(F32))[1](dn)[0]
    assert float(jnp.abs(want).max()) > 1e3
    _close(vjp(dn)[0], want.astype(BF16))


# ------------------------------------------------------- the predicate

CELLS = {
    # (S, channels, bias, output dtype, sequence_minor): the three cells'
    # convolutions as their mixers call them.
    "granite": (4096, 2 * 2048 + 2 * 128, True, BF16, True),
    "kimi": (16384, 4096, False, F32, False),
    "nemotron": (16384, 4096 + 2 * 8 * 128, True, BF16, True),
}


@pytest.mark.parametrize("shape, blocks", [
    (CELLS["granite"], Blocks(256, 4096, 2048)),
    (CELLS["kimi"], Blocks(512, 1024, 1024)),
    (CELLS["nemotron"], Blocks(256, 4096, 2048)),
], ids=list(CELLS))
def test_a_cells_shape_is_tiled_by_one_function_of_the_shape(shape, blocks):
    s, channels, _, out, sequence_minor = shape
    form = causal_conv.form_of(sequence_minor)
    assert causal_conv.uses_kernel(s, channels, TAPS, BF16, out, sequence_minor)
    got = causal_conv.blocks_of(s, channels, TAPS, BF16, out, sequence_minor)
    assert got == blocks
    # Whole trips, whole blocks, inside the budget of one buffer each.
    for tokens, token_bytes in ((got.tokens, 2 + jnp.dtype(out).itemsize),
                                (got.tokens_bwd, 4 + jnp.dtype(out).itemsize)):
        assert s % tokens == 0 and tokens % form.chunk == 0
        assert tokens * got.channels * token_bytes <= causal_conv.BLOCK_BYTES
    assert channels % got.channels == 0
    assert got.channels % form.channel_tile == 0


@pytest.mark.parametrize("s, channels, taps, x, out, minor, takes", [
    (4096, 4352, 4, BF16, BF16, False, True),
    (4096, 4352, 4, BF16, BF16, True, True),
    (64, 384, 4, F32, F32, False, True),
    (16, 128, 2, BF16, F32, False, True),
    (128, 32, 2, BF16, F32, True, True),
    (128, 16, 2, BF16, F32, True, False),      # half a channel group
    (1, 4352, 4, BF16, BF16, False, False),    # a decode step's one token
    (1, 4352, 4, BF16, BF16, True, False),
    (3, 4352, 4, BF16, BF16, False, False),    # a tail of taps - 1 tokens
    (8, 4352, 4, BF16, BF16, False, False),    # shorter than a tile
    (64, 4352, 4, BF16, BF16, True, False),    # shorter than ITS tile
    (40, 4352, 4, BF16, BF16, False, False),   # no whole tiles
    (4096, 160, 4, BF16, BF16, False, False),  # a test's width: no register
    (4096, 160, 4, BF16, BF16, True, True),    # five groups of 32 sublanes
    (4096, 168, 4, BF16, BF16, True, False),
    (4096, 4352 + 64, 4, BF16, BF16, False, False),
    (4096, 4352, 1, BF16, BF16, False, False),   # no convolution
    (4096, 4352, 10, BF16, BF16, False, False),  # beyond one tile back
    (4096, 4352, 10, BF16, BF16, True, True),    # a tile of 128 tokens
    (4096, 4352, 4, jnp.int32, BF16, False, False),
    (4096, 4352, 4, BF16, jnp.float16, False, True),
    (4096, 4352, 4, jnp.float64, F32, True, False),
])
def test_the_predicate_reads_the_shape_alone(
        s, channels, taps, x, out, minor, takes):
    assert causal_conv.uses_kernel(s, channels, taps, x, out, minor) is takes


# A head inside the kernels: (channels, a head's width, sequence_minor).
HEADS = [
    (4096, 128, False, True),      # Kimi Linear's: four heads a block
    (4096, 256, False, True),
    (768, 256, False, True),       # blocks of 256: 384 would halve a head
    (4096, 512, False, True),      # one head a block
    (4096, 1024, False, False),    # wider than any block
    (2880, 96, False, False),      # Olmo-Hybrid's q and k: no register
    (384, 96, False, False),       # nor where the plain kernels tile it
    (5760, 192, False, False),     # its v's width
    (4096, 64, False, False),
    (4096, 128, True, False),      # a head would lie down the sublanes
]


@pytest.mark.parametrize("channels, width, minor, takes", HEADS)
def test_the_predicate_takes_a_norm_over_heads_of_whole_registers(
        channels, width, minor, takes):
    shape = (16384, channels, TAPS, BF16, BF16, minor)
    unit = Unit(width, kda_model.L2_EPS)
    assert causal_conv.uses_kernel(*shape, unit) is takes
    if takes:
        blocks = causal_conv.blocks_of(*shape, unit)
        assert blocks.channels % width == 0 and channels % blocks.channels == 0
        assert blocks.channels <= causal_conv.CHANNEL_MINOR.max_channels
    if channels % 128 == 0:
        # Without the norm the same call is the kernels' either way.
        assert causal_conv.uses_kernel(*shape)


def test_kimi_linears_q_and_k_are_tiled_in_blocks_of_four_heads():
    """Half the bytes a token out: twice the tokens a forward block."""
    assert causal_conv.blocks_of(
        16384, 4096, TAPS, BF16, BF16, unit=Unit(128, kda_model.L2_EPS)
    ) == Blocks(512, 2048, 1024)


@pytest.mark.parametrize("shape, minor, blocks, width", [
    ((1, 8, 128), False, None, None),         # shorter than a tile
    ((1, 64, 384), False, None, 96),          # a head of no register
    ((1, 256, 128), True, None, 128),         # the sequence on the lanes
    ((1, 64, 384), False, Blocks(384, 32, 32), 256),  # a test's own tiling
    ((1, 256, 128), True, Blocks(128, 256, 256), 128),
], ids=["short", "width_96", "seq_minor", "blocks_halve_a_head",
        "blocks_seq_minor"])
def test_a_shape_the_kernels_decline_is_an_error_to_call_them_with(
        shape, minor, blocks, width):
    unit = width and Unit(width, kda_model.L2_EPS)
    with pytest.raises(ValueError, match="uses_kernel"):
        causal_conv_silu(
            jnp.zeros(shape, BF16), _taps(shape[-1]), dtype=BF16,
            interpret=True, sequence_minor=minor, blocks=blocks, unit=unit)


def test_the_channel_block_is_the_widest_that_divides():
    lanes, sublanes = causal_conv.CHANNEL_MINOR, causal_conv.SEQUENCE_MINOR
    assert causal_conv.channel_block(4096, lanes) == 512
    assert causal_conv.channel_block(6144, lanes) == 512
    assert causal_conv.channel_block(4352, lanes) == 256      # 17 · 256
    assert causal_conv.channel_block(2176, lanes) == 128      # 17 · 128
    assert causal_conv.channel_block(384, lanes) == 384
    assert causal_conv.channel_block(160, lanes) is None
    assert causal_conv.channel_block(4352, sublanes) == 256
    assert causal_conv.channel_block(6144, sublanes) == 256
    assert causal_conv.channel_block(2176, sublanes) == 128
    assert causal_conv.channel_block(384, sublanes) == 192
    assert causal_conv.channel_block(160, sublanes) == 160


def test_off_the_tpu_the_module_keeps_the_jnp_form():
    if jax.default_backend() != "cpu":
        pytest.skip("for a host without a TPU")
    assert not mamba.conv_takes_kernel(
        *CELLS["granite"][:2], TAPS, BF16, BF16, True)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The module's choice as a host with ONE TPU chip makes it (this
    one's backend is the CPU, in eight devices), the kernels in the
    interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(
        mamba.causal_conv, "causal_conv_silu",
        functools.partial(causal_conv_silu, interpret=True),
    )


@pytest.mark.parametrize(
    "use_bias, dtype, minor", [(True, BF16, True), (False, F32, False)],
    ids=["mamba2", "kda"])
def test_the_module_hands_the_kernels_what_the_jnp_form_gets(
        as_on_a_tpu, monkeypatch, use_bias, dtype, minor):
    module = CausalConv1d(
        TAPS, dtype, F32, use_bias=use_bias, sequence_minor=minor)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 256), F32).astype(
        BF16)
    variables = module.init(jax.random.PRNGKey(1), x)

    def run():
        # A function of its own a call: jax keeps a function's trace.
        def loss(variables, x):
            y = module.apply(variables, x)
            assert y.dtype == dtype
            return y.astype(F32).sum()

        traced = jax.jit(jax.value_and_grad(loss)).trace(variables, x)
        return str(traced.jaxpr), traced.lower().compile()(variables, x)

    assert mamba.conv_takes_kernel(256, 256, TAPS, BF16, dtype, minor)
    program, got = run()
    assert program.count("pallas_call") == 2
    monkeypatch.undo()
    program, want = run()
    assert "pallas_call" not in program
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3)


# ----------------------------------------------------------- the gauges

def _tiny(layer_types, key_dim=64, **sizes):
    """A stack of width 64 whose convolutions the kernels take at 128
    tokens: 96 channels (3 of 32 sublanes) in a Mamba-2 layer, 128 (one
    register of lanes) in each of a delta-rule layer's three: two heads
    of 64, which keep their norm outside the kernels (``key_dim`` 128:
    two of whole registers, which do not)."""
    return CausalLM(dataclasses.replace(
        granite_h_micro(
            n_layers=len(layer_types), layer_types=layer_types, d_model=64,
            n_heads=2, n_kv_heads=1, d_ff=128, vocab_size=128, max_len=128,
            **{**dict(ssm_heads=4, ssm_head_dim=16, ssm_state=16,
                      ssm_chunk=64), **sizes},
        ),
        kda=kda_model.KDAConfig(
            heads=2, key_dim=key_dim, value_dim=64, gate_rank=16, chunk=16),
    ))


def _surveyed_and_traced(model, batch=1):
    """The three ``conv/*_calls`` gauges as ``models/step.report`` sets them
    for ``model`` at [batch, 128], and what a gradient of it holds:
    ``(gauges, calls forward, calls backward, kernel bodies each way)``.
    A jaxpr's text names a ``jit`` where it is called and prints a body
    that several calls share once."""
    ids = jax.ShapeDtypeStruct((batch, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model_step.parameters(nn.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))))
    model_step.report(model, params, ids)

    def loss(params, ids):
        return model.apply(params, ids).astype(F32).sum()

    program = str(jax.make_jaxpr(jax.grad(loss))(params, ids))
    bodies = program.count("name=causal_conv_forward")
    assert bodies == program.count("name=causal_conv_backward")
    return (_gauges(), program.count("name=_forward_call"),
            program.count("name=_backward_call"), bodies)


def _gauges():
    return (metrics.gauge_value("conv/kernel_calls"),
            metrics.gauge_value("conv/jnp_calls"),
            metrics.gauge_value("conv/unit_kernel_calls"))


STACKS = {
    # (layer types, a delta-rule head's keys) -> CausalConv1d calls (one a
    # Mamba-2 layer, three a delta-rule layer: q, k and v), those of them
    # with the norm inside (q's and k's at heads of whole registers) and
    # the distinct kernel bodies each way (q and k differ in the scale
    # alone, an operand: one body; v's is another).
    "granite": (("mamba", "attention", "mamba"), 64, 2, 0, 1),
    "kimi": (("kda", "attention", "kda"), 64, 6, 0, 1),
    "kimi_heads_of_128": (("kda", "attention", "kda"), 128, 6, 4, 2),
    "both": (("mamba", "kda"), 128, 4, 2, 3),
    "neither": (("attention",), 64, 0, 0, 0),
}


@pytest.mark.parametrize("stack", list(STACKS))
def test_the_gauges_count_the_calls_a_gradient_holds(as_on_a_tpu, stack):
    layer_types, key_dim, calls, normed, shapes = STACKS[stack]
    gauges, forward, backward, bodies = _surveyed_and_traced(
        _tiny(layer_types, key_dim))
    assert gauges == (calls, 0, normed)
    assert forward == backward == calls and bodies == shapes


@pytest.mark.parametrize("key_dim", [64, 128])
def test_off_the_tpu_every_call_counts_as_the_jnp_form(key_dim):
    gauges, *kernels = _surveyed_and_traced(
        _tiny(("mamba", "kda"), key_dim))
    assert gauges == (0, 4, 0) and kernels == [0, 0, 0]


def test_a_call_the_kernels_decline_counts_as_the_jnp_form(as_on_a_tpu):
    """100 channels in the Mamba-2 layer's convolution (no 32 sublanes
    divide them); the delta-rule layer's three keep the kernels."""
    gauges, *kernels = _surveyed_and_traced(
        _tiny(("mamba", "kda"), ssm_state=18))
    assert gauges == (3, 1, 0) and kernels == [3, 3, 1]


def test_a_report_without_a_survey_reads_zero_and_zero():
    mamba.report(granite_h_micro(n_layers=1), tokens_per_step=4096)
    assert _gauges() == (0, 0, 0)


# ------------------------------------------------------ on a device mesh

def _mesh(**axes):
    devices = np.array(jax.devices()[:int(np.prod(list(axes.values())))])
    return Mesh(devices.reshape(tuple(axes.values())), tuple(axes))


def test_the_predicate_leaves_no_mosaic_call_for_the_compiler_to_partition(
        monkeypatch):
    shape = (*CELLS["granite"][:2], TAPS, BF16, BF16, True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Eight devices here and no mesh told: the step may be laid over them.
    assert jax.device_count() > 1
    assert not mamba.conv_takes_kernel(*shape)
    assert mamba.conv_takes_kernel(*shape, mesh=_mesh(dp=2))
    assert mamba.conv_takes_kernel(*shape, mesh=_mesh(dp=2, tp=2))
    assert mamba.conv_takes_kernel(*shape, mesh=_mesh(dp=1))
    # A sequence split over chips is not gathered for the kernel.
    assert not mamba.conv_takes_kernel(*shape, mesh=_mesh(dp=2, sp=2))
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert mamba.conv_takes_kernel(*shape)


@pytest.mark.parametrize("sequence_minor, batch", [(True, 4), (False, 1)],
                         ids=["seq_minor-dp_rows", "ch_minor-one_row"])
def test_on_a_mesh_each_chip_convolves_its_own_sequences(
        sequence_minor, batch):
    """dp = 2 by tp = 2: the rows over dp (one row stays whole), the taps'
    and the bias's cotangents summed over dp and counted once over tp."""
    mesh, tiling = _mesh(dp=2, tp=2), tiled(128, sequence_minor)
    keys = jax.random.split(jax.random.PRNGKey(batch), 4)
    shape = (batch, 2 * tiling.tokens, 128)
    x = jax.random.normal(keys[0], shape, F32)
    kernel = jax.random.uniform(keys[1], (TAPS, 128), F32, -0.5, 0.5)
    b = jax.random.uniform(keys[2], (128,), F32, -0.5, 0.5)
    dy = jax.random.normal(keys[3], shape, F32)

    @jax.jit
    def run(x, kernel, b, dy):
        y, vjp = jax.vjp(
            lambda *a: causal_conv_silu(
                *a, dtype=F32, interpret=True, blocks=tiling, mesh=mesh,
                sequence_minor=sequence_minor),
            x, kernel, b,
        )
        want, vjp_plain = jax.vjp(lambda *a: plain(*a, F32), x, kernel, b)
        return (y, *vjp(dy)), (want, *vjp_plain(dy))

    rows = NamedSharding(mesh, P("dp" if batch > 1 else None))
    with small_chunks():
        got, want = run(jax.device_put(x, rows), kernel, b, dy)
    for a, b in zip(got, want):
        _close(a, b)


def _granite_gradient_lowered_for_a_tpu(mesh, told: bool) -> str:
    """A tiny Granite's gradient over ``mesh`` (rows over dp, the
    parameters whole), lowered for a TPU: nothing is compiled."""
    model = _tiny(("mamba", "attention"), mesh=mesh if told else None)
    ids = jax.ShapeDtypeStruct(
        (2, 128), jnp.int32, sharding=NamedSharding(mesh, P("dp")))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
        jax.eval_shape(lambda: model_step.parameters(nn.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))))),
    )

    def loss(params, ids):
        return model.apply(params, ids).astype(F32).sum()

    return jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_granite_gradient_lowers_for_two_chips(monkeypatch):
    """What XLA refuses ("Mosaic kernels cannot be automatically
    partitioned") is never asked of it: with the mesh told the kernels sit
    in a ``shard_map``, without it the step keeps the ``jax.numpy`` form."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(dp=2)
    text = _granite_gradient_lowered_for_a_tpu(mesh, told=True)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    text = _granite_gradient_lowered_for_a_tpu(mesh, told=False)
    assert "tpu_custom_call" not in text
    # The call as one chip makes it, in a step laid over two.
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with pytest.raises(NotImplementedError, match="partitioned"):
        _granite_gradient_lowered_for_a_tpu(mesh, told=False)


def _qkv_gradient_lowered_for_a_tpu(mesh, told: bool) -> str:
    """The gradient of a delta-rule layer's three convolutions, heads of
    128 (q and k with the norm inside, v without), two rows over dp."""
    module = kda_model.QKVConv(
        kda_model.KDAConfig(heads=2, key_dim=128, value_dim=128), BF16, F32,
        mesh=mesh if told else None)
    x = jax.ShapeDtypeStruct(
        (2, 128, 256), BF16, sharding=NamedSharding(mesh, P("dp")))
    variables = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
        jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), *[
                jnp.zeros((1, 128, 256), BF16)] * 3)),
    )

    def loss(variables, x):
        # Squares: a sum alone would need no forward call at all.
        return sum(
            (a.astype(F32) ** 2).sum()
            for a in module.apply(variables, x, x, x))

    return jax.jit(jax.grad(loss)).trace(variables, x).lower(
        lowering_platforms=("tpu",)).as_text()


def test_the_norm_inside_lowers_for_two_chips(monkeypatch):
    """As Granite's above: the pair with ``unit`` sits in the same
    ``shard_map``, which keeps the channels, and so every head, whole on
    each device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(dp=2)
    text = _qkv_gradient_lowered_for_a_tpu(mesh, told=True)
    # q's and k's one body each way, v's another.
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 4
    assert text.count("call @_forward_call") == 3
    text = _qkv_gradient_lowered_for_a_tpu(mesh, told=False)
    assert "tpu_custom_call" not in text
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with pytest.raises(NotImplementedError, match="partitioned"):
        _qkv_gradient_lowered_for_a_tpu(mesh, told=False)


# ------------------------------------------- what lowering a step costs

# Kimi Linear's q and k as ``QKVConv`` calls them where the kernels take
# the norm: no bias, out in the compute dtype, heads of 128.
KIMI_QK = (16384, 4096, False, BF16, False)
# q's scale, k's, and a third: operands, not constants of the bodies.
SCALES = (128 ** -0.5, 1.0, 0.5)


def _gradient(s, channels, bias, out, sequence_minor, sites=2, width=None):
    """``(function, arguments)``: a gradient through ``sites``
    convolutions of one shape, with the norm over heads of ``width``
    inside where that is given, each site with a scale of its own."""
    x = jax.ShapeDtypeStruct((1, s, channels), BF16)
    kernel = jax.ShapeDtypeStruct((TAPS, channels), F32)
    b = jax.ShapeDtypeStruct((channels,), F32) if bias else None

    def loss(x, kernels, biases):
        for at, (kernel, b) in enumerate(zip(kernels, biases)):
            with jax.named_scope(f"site_{at}"):
                x = causal_conv_silu(
                    x, kernel, b, dtype=out, sequence_minor=sequence_minor,
                    **_norm(width and (width, SCALES[at % len(SCALES)])),
                ).astype(BF16)
        return (x.astype(F32) ** 2).sum()

    return (jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
            (x, [kernel] * sites, [b] * sites))


def _lowered(*shape, **more):
    """The TPU lowering (nothing is compiled) of :func:`_gradient`, as
    text."""
    gradient, arguments = _gradient(*shape, **more)
    return gradient.trace(*arguments).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("cell", list(CELLS) + ["kimi_qk"])
def test_call_sites_of_one_shape_share_one_kernel_body_each_way(cell):
    if cell == "kimi_qk":
        text = _lowered(*KIMI_QK, sites=3, width=128)
    else:
        text = _lowered(*CELLS[cell], sites=3)
    # One Mosaic body forward and one backward, called three times each.
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert text.count("call @_forward_call") == 3
    assert text.count("call @_backward_call") == 3


@FORMS
def test_the_lowered_text_does_not_grow_with_the_sequence(sequence_minor):
    _, channels, bias, out, _ = CELLS["granite"]
    short = _lowered(4096, channels, bias, out, sequence_minor)
    long = _lowered(16384, channels, bias, out, sequence_minor)
    assert abs(len(long) - len(short)) <= 0.03 * len(short)
    # Nor with the channels, whichever block divides them.
    wide = _lowered(4096, 6144, bias, out, sequence_minor)
    assert abs(len(wide) - len(short)) <= 0.05 * len(short)
    assert len(short) < 40_000


def test_with_the_norm_inside_it_does_not_grow_with_the_sequence_either():
    s, channels, bias, out, minor = KIMI_QK
    short = _lowered(4096, channels, bias, out, minor, width=128)
    long = _lowered(s, channels, bias, out, minor, width=128)
    assert abs(len(long) - len(short)) <= 0.03 * len(short)
    # Nor with the heads a block holds: four of 128, two of 256, one of
    # 512 are a constant's worth of slices, not a shape's.
    plain_text = _lowered(s, channels, bias, F32, minor)
    for width in (128, 256, 512):
        text = _lowered(s, channels, bias, out, minor, width=width)
        assert len(text) < len(plain_text) + 6_000
    assert len(short) < 40_000


# sha256 of the traced gradient's text (two sites, as ``_gradient`` makes
# it) at the parent of the PR that brought ``unit`` (PR 66): a jaxpr's text
# holds the kernels' bodies and no source location. Without ``unit`` the
# bodies, and so what Granite's, Nemotron's and Olmo-Hybrid's cells lower,
# are what they were.
PINNED = {
    "granite": "da57301dab089f61c9da72f3722d6711a8b83d26b94f68ca11027c2e38a44ebe",
    "kimi": "9afea3a967cc504c117c21b3cb92e812053031258e86d4fa538348ede6ec73f5",
    "nemotron": "8bec6508d04ddca567aa10a6aeaeb4bd61dd867bcc93a1c3dc749e47cde938fa",
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_without_the_norm_the_traced_program_is_the_pinned_one(cell):
    gradient, arguments = _gradient(*CELLS[cell])
    text = str(gradient.trace(*arguments).jaxpr)
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[cell]


# --------------------------------------------------- the script for the chip

def test_the_chips_script_measures_every_form():
    """``scripts/causal_conv_on_chip.py`` at a tiny shape: it cannot rot
    unseen (its times mean something on a TPU only)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts"))
    try:
        import causal_conv_on_chip as script
    finally:
        sys.path.pop(0)
    found = script.measure(
        (64, 256, 128, TAPS), repeats=1, dtype=F32, interpret=True,
        blocks=Blocks(256, 32, 32))
    assert set(found) == {*script.FORMS, "apart"}
    for form in script.FORMS:
        assert set(found[form]) == {
            "forward_ms", "forward_backward_ms", "share_of_least"}
        assert max(found["apart"][form].values()) < 1e-5
    assert script.KIMI == (*CELLS["kimi"][:2], 128, TAPS)
    assert script.least_bytes(script.KIMI) == (
        2 * 16384 * 4096 * 2, 5 * 16384 * 4096 * 2)
    if jax.default_backend() != "tpu":
        # Off the chip it measures nothing under a chip's name.
        assert script.main([]) == 3
