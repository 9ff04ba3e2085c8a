"""The state-space scan's Pallas kernels (``ops/ssd.py``: ``ssd_forward``,
``ssd_backward``) in the Pallas interpreter at the smallest shapes the
predicate takes (chunks of 128, heads of 64, state 128): forward and every
gradient against the ``jax.numpy`` form of the same file and, once, against
the recurrence itself, a step a token; eight heads in one group, thirty-two
in two groups of two blocks each (whose B and C cotangents are float32
partial sums), float32 and bfloat16; the state carried over a chunk's edge and a
cotangent carried back over one; every sequence of a batch from zero; which
shapes the predicate takes; the two ``ssm/scan_*_calls`` gauges against the
calls a traced gradient holds; the call on a device mesh (in a
``shard_map``, or not taken at all: XLA partitions no Mosaic kernel); and
what a step that holds the kernels costs to LOWER: one kernel body each way
however many call sites, and a lowered text whose size does not follow the
sequence."""
import functools
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from raydp_tpu.models import CausalLM, mamba
from raydp_tpu.models import step as model_step
from raydp_tpu.models.transformer import granite_h_micro
from raydp_tpu.ops import ssd
from raydp_tpu.utils.profiling import metrics
from tests.test_causal_conv_kernel import _mesh
from tests.test_ssd import _inputs, _recurrence, _rel

BF16, F32 = jnp.bfloat16, jnp.float32
NAMES = ("x", "dt", "A", "B", "C", "D")
# (sequences, tokens, heads, groups, dtype) at heads of 64, state 128 and
# chunks of 128; the recurrence is run beside the first.
CASES = {
    "one_group_of_eight": (2, 384, 8, 1, F32),
    "two_groups_of_two_blocks": (1, 256, 32, 2, F32),
    "bfloat16": (1, 256, 2, 1, BF16),       # all the heads one block
}
# A float32 sum in another order; one rounding of the operands of a
# bfloat16 product (8 bits of mantissa) through a chunk's sums.
TOLERANCE = {F32: 2e-5, BF16: 2e-2}


def _operands(case):
    b, s, h, g, dtype = CASES[case]
    x, dt, A, B, C, D = _inputs(b, s, h, 64, g, 128)
    return (x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D)


def _kernels(x, dt, A, B, C, D, chunk, interpret=True, **how):
    """``ssd_scan_packed`` with ``ssd_chunked``'s arguments and result:
    ``x``, ``B`` and ``C`` side by side, as a mixer's convolution leaves
    them; in the Pallas interpreter, which the caller asks for."""
    b, s = x.shape[:2]
    xbc = jnp.concatenate([a.reshape(b, s, -1) for a in (x, B, C)], axis=-1)
    return ssd.ssd_scan_packed(
        xbc, dt, A, D, chunk, *B.shape[2:], interpret=interpret, **how
    ).reshape(x.shape)


def _value_and_grads(scan, operands):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(scan(*a).astype(F32))),
        argnums=tuple(range(6))))(*operands)


@functools.lru_cache(maxsize=None)
def both(case):
    """``(y, gradients)`` by the kernels and by the ``jax.numpy`` form."""
    operands = _operands(case)
    return tuple(
        (scan(*operands, 128), *_value_and_grads(
            functools.partial(scan, chunk=128), operands)[1])
        for scan in (_kernels, ssd.ssd_chunked)
    )


@pytest.mark.parametrize("leaf", range(7), ids=("y",) + NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_jnp_form(case, leaf):
    got, want = (result[leaf] for result in both(case))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _rel(got.astype(F32), want.astype(F32)) < TOLERANCE[
        CASES[case][-1]]


@functools.lru_cache(maxsize=None)
def _recurrent():
    operands = _operands("one_group_of_eight")
    return (_recurrence(*operands),
            *_value_and_grads(_recurrence, operands)[1])


@pytest.mark.parametrize("leaf", range(7), ids=("y",) + NAMES)
def test_the_kernels_are_the_recurrence(leaf):
    assert _rel(both("one_group_of_eight")[0][leaf], _recurrent()[leaf]) < 1e-4


# ------------------------------------------------------ a chunk's edges

def _two_heads(b=1, chunks=2, seed=3):
    """Two heads in one block (all the heads there are)."""
    return _inputs(b, 128 * chunks, 2, 64, 1, 128, seed=seed)


def test_the_state_crosses_a_chunks_edge_and_a_cotangent_comes_back():
    operands = _two_heads()

    def later(scan):
        """The second chunk's outputs, and their gradient in ``x``."""
        return jax.jit(jax.value_and_grad(
            lambda x: jnp.sum(scan(x, *operands[1:], 128)[:, 128:] ** 2)
        ))(operands[0])

    (got, d_got), (want, d_want) = later(_kernels), later(ssd.ssd_chunked)
    assert _rel(got, want) < 1e-5
    # The first chunk's tokens reach the second's outputs by the state.
    assert float(jnp.abs(d_want[:, :128]).max()) > 1e-2
    assert _rel(d_got[:, :128], d_want[:, :128]) < 1e-4
    cut = later(functools.partial(
        lambda scan, x, *a: scan(x.at[:, :128].set(0.0), *a),
        ssd.ssd_chunked))[0]
    assert abs(float(cut) / float(want) - 1) > 0.1


def test_every_sequence_of_a_batch_starts_from_zero():
    operands = _two_heads(b=2)
    y = _kernels(*operands, 128)
    for row in range(2):
        alone = _kernels(*(
            a[row:row + 1] if a.ndim > 1 else a for a in operands), 128)
        np.testing.assert_allclose(y[row:row + 1], alone, rtol=1e-6,
                                   atol=1e-6)


# ---------------------------------------------------------- the predicate

@pytest.mark.parametrize("heads, groups, p, n, chunk, takes", [
    (64, 8, 64, 128, 128, True),      # Nemotron 3 Nano: a group a block
    (64, 1, 64, 128, 256, True),      # Granite 4.0 H Micro: eight blocks
    (128, 8, 64, 128, 128, True),     # two blocks a group
    (2, 1, 64, 128, 128, True),       # all the heads in one block
    (24, 1, 128, 256, 128, True),
    (4, 2, 64, 128, 128, False),      # blocks of two heads: no sublane tile
    (64, 8, 32, 128, 128, False),
    (64, 8, 64, 64, 128, False),
    (64, 8, 64, 128, 64, False),
    (64, 8, 64, 128, 5, False),       # the tests' chunks of 5 and 8
    (16, 8, 4, 8, 128, False),
    (64, 7, 64, 128, 128, False),
    (64, 0, 64, 128, 128, False),
])
def test_the_predicate_reads_the_shape_alone(
        heads, groups, p, n, chunk, takes):
    assert ssd.uses_kernels(heads, groups, p, n, chunk) is takes


def test_a_cells_shape_is_tiled_by_one_function_of_the_shape():
    assert ssd.tiling_of(64, 8, 64, 128, 128) == ssd.Tiling(128, 8, 64, 128, 1)
    assert ssd.tiling_of(64, 1, 64, 128, 256) == ssd.Tiling(256, 8, 64, 128, 8)


@pytest.mark.parametrize("tokens, heads", [(256, 4), (200, 8)],
                         ids=["heads", "half_a_chunk"])
def test_a_shape_the_kernels_decline_is_an_error_to_call_them_with(
        tokens, heads):
    with pytest.raises(ValueError, match="uses_kernels"):
        _kernels(*_inputs(1, tokens, heads, 64, 2, 128), 128)


def test_off_the_tpu_the_module_keeps_the_jnp_form():
    if jax.default_backend() != "cpu":
        pytest.skip("for a host without a TPU")
    assert not mamba.scan_takes_kernels(4096, 64, 64, 1, 128, 256)
    x, dt, _, B, C, _ = _two_heads()
    xbc = jnp.concatenate(
        [a.reshape(1, 256, -1) for a in (x, B, C)], axis=-1)
    scan = mamba.SelectiveScan(128, F32, groups=1, state=128)
    program = str(jax.make_jaxpr(
        lambda *a: scan.init_with_output(jax.random.PRNGKey(0), *a)[0]
    )(xbc, dt))
    assert "pallas_call" not in program


# ------------------------------------------------------------ the gauges

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The module's choice as a host with ONE TPU chip makes it (this
    one's backend is the CPU, in eight devices). Nothing a test traces
    under it runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _tiny(layer_types=("mamba", "attention", "mamba"), **sizes):
    """A stack of width 64 whose scans the kernels take at 256 tokens:
    two heads of 64 in one block, state 128, chunks of 128."""
    return CausalLM(granite_h_micro(
        n_layers=len(layer_types), layer_types=layer_types, d_model=64,
        n_heads=2, n_kv_heads=1, d_ff=128, vocab_size=128, max_len=256,
        **{**dict(ssm_heads=2, ssm_head_dim=64, ssm_state=128,
                  ssm_chunk=128), **sizes},
    ))


def _abstract_parameters(model, sharding=None):
    params = jax.eval_shape(
        lambda: model_step.parameters(nn.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)))))
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        params)


def _surveyed_and_traced(model):
    """The two gauges as ``models/step.report`` sets them for ``model`` at
    [1, 256], and the scan kernels' calls forward and backward in a
    gradient of it."""
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    params = _abstract_parameters(model)
    model_step.report(model, params, ids)

    def loss(params, ids):
        return model.apply(params, ids).astype(F32).sum()

    program = str(jax.make_jaxpr(jax.grad(loss))(params, ids))
    return (_gauges(), program.count("name=ssd_forward"),
            program.count("name=ssd_backward"))


def _gauges():
    return (metrics.gauge_value("ssm/scan_kernel_calls"),
            metrics.gauge_value("ssm/scan_jnp_calls"))


@pytest.mark.parametrize("sizes, kernels, plain", [
    ({}, 2, 0),
    (dict(ssm_chunk=64), 0, 2),       # a chunk the kernels decline
], ids=["taken", "declined"])
def test_the_gauges_count_the_calls_a_gradient_holds(
        as_on_a_tpu, sizes, kernels, plain):
    gauges, forward, backward = _surveyed_and_traced(_tiny(**sizes))
    assert gauges == (kernels, plain)
    # One body each way for the two call sites of one shape.
    assert forward == backward == min(kernels, 1)


def test_off_the_tpu_every_call_counts_as_the_jnp_form():
    assert _surveyed_and_traced(_tiny()) == ((0, 2), 0, 0)


def test_a_stack_without_scans_reads_zero_and_zero(as_on_a_tpu):
    assert _surveyed_and_traced(_tiny(("attention",))) == ((0, 0), 0, 0)


# ------------------------------------------------------ on a device mesh

def test_the_predicate_leaves_no_mosaic_call_for_the_compiler_to_partition(
        monkeypatch):
    shape = (4096, 64, 64, 1, 128, 256)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Eight devices here and no mesh told: the step may be laid over them.
    assert jax.device_count() > 1
    assert not mamba.scan_takes_kernels(*shape)
    assert mamba.scan_takes_kernels(*shape, mesh=_mesh(dp=2))
    assert mamba.scan_takes_kernels(*shape, mesh=_mesh(dp=1))
    # Neither a sequence nor the heads split over chips are gathered for
    # the kernels: XLA partitions the jax.numpy form over both.
    assert not mamba.scan_takes_kernels(*shape, mesh=_mesh(dp=2, sp=2))
    assert not mamba.scan_takes_kernels(*shape, mesh=_mesh(dp=2, tp=2))
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert mamba.scan_takes_kernels(*shape)
    # ``model.init``'s sample: one chunk, shorter than the configuration's.
    assert not mamba.scan_takes_kernels(1, 64, 64, 1, 128, 1)


def test_on_a_mesh_each_chip_scans_its_own_sequences():
    """dp = 2: the rows over dp, A's and D's cotangents summed over it."""
    mesh, operands = _mesh(dp=2), _two_heads(b=2, seed=5)

    def grads(scan):
        return _value_and_grads(lambda *a: scan(*a, 128), operands)[1]

    rows = NamedSharding(mesh, P("dp"))
    got = _value_and_grads(
        lambda *a: _kernels(*a, 128, mesh=mesh),
        tuple(jax.device_put(a, rows) if a.ndim > 1 else a
              for a in operands))[1]
    for a, b in zip(got, grads(ssd.ssd_chunked)):
        assert _rel(a, b) < 2e-5


def _granite_gradient_lowered_for_a_tpu(mesh, told: bool) -> str:
    """A tiny Granite's gradient over ``mesh`` (rows over dp, the
    parameters whole), lowered for a TPU: nothing is compiled."""
    model = _tiny(("mamba", "attention"), mesh=mesh if told else None)
    ids = jax.ShapeDtypeStruct(
        (2, 256), jnp.int32, sharding=NamedSharding(mesh, P("dp")))
    params = _abstract_parameters(model, NamedSharding(mesh, P()))

    def loss(params, ids):
        return model.apply(params, ids).astype(F32).sum()

    return jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_granite_gradient_lowers_for_two_chips(monkeypatch):
    """What XLA refuses ("Mosaic kernels cannot be automatically
    partitioned") is never asked of it: with the mesh told the scan's
    kernels sit in a ``shard_map`` beside the convolution's, without it
    the step keeps the ``jax.numpy`` forms."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(dp=2)
    text = _granite_gradient_lowered_for_a_tpu(mesh, told=True)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 4
    assert "ssd_forward" in text and "ssd_backward" in text
    text = _granite_gradient_lowered_for_a_tpu(mesh, told=False)
    assert "tpu_custom_call" not in text
    # Heads split over tp: the convolution's kernels alone, the scan in
    # the form XLA partitions over the heads.
    text = _granite_gradient_lowered_for_a_tpu(_mesh(dp=2, tp=2), told=True)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert "ssd_forward" not in text and "ssd_backward" not in text
    # The call as one chip makes it, in a step laid over two.
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with pytest.raises(NotImplementedError, match="partitioned"):
        _granite_gradient_lowered_for_a_tpu(mesh, told=False)


# ------------------------------------------- what lowering a step costs

# (tokens, heads, groups, chunk) of the two cells' scans: heads of 64,
# state 128.
CELLS = {"granite": (4096, 64, 1, 256), "nemotron": (16384, 64, 8, 128)}


def _lowered(s, h, g, chunk, sites=2):
    """The TPU lowering (nothing is compiled) of a gradient through
    ``sites`` scans of one shape, as text."""
    like = jax.ShapeDtypeStruct
    site = (like((1, s, h), F32), like((h,), F32),
            like((1, s, g, 128), BF16), like((1, s, g, 128), BF16),
            like((h,), F32))

    def loss(x, sites):
        for at, operands in enumerate(sites):
            with jax.named_scope(f"site_{at}"):
                x = _kernels(x, *operands, chunk, interpret=False)
        return (x.astype(F32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        like((1, s, h, 64), BF16), [site] * sites
    ).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("cell", list(CELLS))
def test_call_sites_of_one_shape_share_one_kernel_body_each_way(cell):
    text = _lowered(*CELLS[cell], sites=3)
    # One Mosaic body forward and one backward, called three times each.
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert text.count("call @_forward_call") == 3
    assert text.count("call @_backward_call") == 3


def test_the_lowered_text_does_not_grow_with_the_sequence():
    _, h, g, chunk = CELLS["nemotron"]
    short, long = (_lowered(s, h, g, chunk) for s in (4096, 16384))
    assert abs(len(long) - len(short)) <= 0.03 * len(short)
    # Nor with the heads: a block is eight of them, however many there are.
    wide = _lowered(4096, 2 * h, g, chunk)
    assert abs(len(wide) - len(short)) <= 0.05 * len(short)
    assert len(short) < 80_000


# --------------------------------------------------- the script for the chip

def test_the_chips_script_measures_both_forms():
    """``scripts/ssd_on_chip.py`` at a tiny shape: it cannot rot unseen
    (its times mean something on a TPU only)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts"))
    try:
        import ssd_on_chip
    finally:
        sys.path.pop(0)
    found = ssd_on_chip.measure(
        (256, 2, 64, 1, 128, 128), repeats=1, dtype=F32, interpret=True)
    assert set(found) == {"kernels", "jnp", "apart"}
    assert max(found["apart"].values()) < 2e-5
    assert ssd_on_chip.least_bytes(ssd_on_chip.CELLS["nemotron"]) == (
        16384 * 20736 * 3)
    if jax.default_backend() != "tpu":
        # Off the chip it measures nothing under a chip's name.
        assert ssd_on_chip.main([]) == 3
