"""How many blocks of a ``remat`` stack are checkpointed follows from the
shapes and the device's memory (PR 56): the rule as a pure function on the
calibration table's rows, the model it makes, what one abstract trace
counts of a block, the way back, and the two reports that follow the
decision (``models/step.py``)."""
import dataclasses
import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu.models import moe
from raydp_tpu.models import step as model_step
from raydp_tpu.models.moe import exchange_bytes
from raydp_tpu.models.transformer import (
    CausalLM, TransformerBlock, olmoe, tiny_transformer,
)
from raydp_tpu.ops.flash_attention import report as report_flash_tiles
from raydp_tpu.train import JAXEstimator
from raydp_tpu.train import estimator as estimator_module
from raydp_tpu.utils.profiling import metrics

MIB, GIB = 2 ** 20, 2 ** 30
# One chip of a TPU v5e as its backend reports it.
V5E = int(15.75 * GIB)
# The calibration table's rows (PERF.md section 6, PR 56), in MiB: what a
# block holds released and checkpointed, and what the step holds whatever
# the blocks do (state, the head's output and its gradient).
ROWS = {
    "granite": ([423] * 5 + [200], [16] * 5 + [32], 10540),
    "lfm2": ([384, 384, 453, 467, 467, 467, 453],
             [32, 32, 65, 32, 32, 32, 65], 8660),
    "xing4": ([643, 761, 761, 761, 761], [144] * 5, 9200),
    "laguna": ([1092, 1168, 1168, 1168, 1039],
               [259, 324, 324, 324, 259], 9492),
}


def _row(name):
    released, checkpointed, fixed = ROWS[name]
    return ([a * MIB for a in released], [b * MIB for b in checkpointed],
            fixed * MIB)


# ------------------------------------------------------------- the rule

@pytest.mark.parametrize("name, limit, want", [
    ("granite", None, ()),                    # no limit reported: the CPU
    ("lfm2", None, ()),
    ("granite", V5E, (0, 1, 2, 3, 4, 5)),     # whole, 13.5 GiB on the chip
    ("lfm2", V5E, (0, 1, 2, 3, 4, 5, 6)),     # whole, under 14 GiB
    ("xing4", V5E, (3, 4)),                   # some, from the last
    ("laguna", V5E, ()),                      # none: 12.5 GiB as it is
    ("laguna", 2 * V5E, (0, 1, 2, 3, 4)),
    ("granite", 8 * GIB, ()),                 # the state alone is over
])
def test_the_rule_on_the_calibration_rows(name, limit, want):
    assert model_step.released_blocks(*_row(name), limit) == want


@pytest.mark.parametrize("name", list(ROWS))
def test_the_rule_is_monotone_and_never_over(name):
    released, checkpointed, fixed = _row(name)
    counts = []
    for limit in range(8 * GIB, 26 * GIB, GIB // 4):
        out = model_step.released_blocks(released, checkpointed, fixed, limit)
        counts.append(len(out))
        room = limit * (1 - model_step.MARGIN)
        estimate = model_step.estimated_bytes(
            released, checkpointed, fixed, out)
        # Nothing released is what the configuration wrote: it ran before.
        assert not out or estimate <= room
        # No block left that would have fitted beside those released.
        for i in set(range(len(released))) - set(out):
            later = tuple(j for j in out if j > i)
            assert model_step.estimated_bytes(
                released, checkpointed, fixed, later + (i,)) > room
    assert counts == sorted(counts)
    assert counts[0] == 0 and counts[-1] == len(released)


def test_the_estimate_counts_a_checkpointed_blocks_second_forward():
    released, checkpointed = [100, 300, 200], [10, 10, 10]
    both = model_step.SLACK
    # All checkpointed: the inputs, the largest block made again, and the
    # largest block's backward at work.
    assert model_step.estimated_bytes(released, checkpointed, 1000, ()) == (
        1000 + int(both * (30 + 300 + 300)))
    # The largest released: what is made again is the largest that stays.
    assert model_step.estimated_bytes(released, checkpointed, 1000, (1,)) == (
        1000 + int(both * (300 + 20 + 200 + 300)))
    assert model_step.estimated_bytes(
        released, checkpointed, 1000, (0, 1, 2)) == (
        1000 + int(both * (600 + 0 + 300)))


# ------------------------------------------------- the model it makes

SEQ = 16


def _stack(released=(), remat=True, n_layers=3):
    cfg = tiny_transformer(
        vocab_size=64, d_model=32, n_heads=2, d_ff=64, max_len=SEQ,
        n_layers=n_layers, causal=True, dtype=jnp.float32, remat=remat,
        released=released,
    )
    model = CausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (2, SEQ)).astype(np.int32))
    return model, ids


def _checkpoints(jaxpr) -> int:
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name in ("remat", "checkpoint", "remat2")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _checkpoints(sub)
    return found


@pytest.mark.parametrize("released", [(), (2,), (0, 2), (0, 1, 2)])
def test_k_of_n_released_is_n_minus_k_checkpoints_and_the_same_gradients(
        released):
    whole, ids = _stack()
    variables = nn.unbox(whole.init(jax.random.PRNGKey(0), ids))
    model, _ = _stack(released)
    # The same tree under the same names: one model's variables run both.
    assert jax.tree_util.tree_structure(
        nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    ) == jax.tree_util.tree_structure(variables)
    assert model.cfg.checkpointed == tuple(
        i not in released for i in range(3))

    def loss(m):
        return lambda v: jnp.sum(m.apply(v, ids) ** 2)

    grad = jax.make_jaxpr(jax.grad(loss(model)))(variables)
    assert _checkpoints(grad.jaxpr) == 3 - len(released)
    plain, _ = _stack(remat=False)
    with jax.disable_jit():
        want = jax.grad(loss(whole))(variables)
        got = jax.grad(loss(model))(variables)
        none = jax.grad(loss(plain))(variables)
    for a, b, c in zip(*map(jax.tree_util.tree_leaves, (want, got, none))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_a_stack_without_remat_has_no_checkpoint_whatever_is_released():
    model, ids = _stack(released=(1,), remat=False)
    assert model.cfg.checkpointed == (False, False, False)
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    grad = jax.make_jaxpr(jax.grad(
        lambda v: jnp.sum(model.apply(v, ids) ** 2)))(variables)
    assert _checkpoints(grad.jaxpr) == 0


# ------------------------------------------ what one abstract trace counts

def test_a_dense_blocks_kept_bytes_are_a_hand_count_without_parameters():
    b, s, d, h, f = 2, SEQ, 32, 2, 64
    cfg = tiny_transformer(
        vocab_size=64, d_model=d, n_heads=h, d_ff=f, max_len=s, n_layers=1,
        causal=True, dtype=jnp.float32, remat=True,
    )
    x = jax.ShapeDtypeStruct((b, s, d), jnp.float32)
    block = TransformerBlock(cfg, "attention", "gelu")
    variables = nn.unbox(jax.eval_shape(
        lambda x: block.init(jax.random.PRNGKey(0), x, False), x))
    released, checkpointed = model_step.block_bytes(
        cfg, "attention", "gelu", variables, x)
    floats = (
        b * s * d           # the block's input
        + 2 * 2 * b * s     # two norms' two sums a row (the normed input
                            # is made of these and the input: not held)
        + b * s * 3 * d     # q, k, v, one product
        + b * h * s * s     # the scores (the mask and the softmax are
                            # made of them and of the rows below)
        + 2 * b * h * s     # a row's max and sum
        + b * s * d         # the heads' output
        + b * s * d         # the output projection (the residual sum
                            # under the second norm is made of it)
        + b * s * f         # mlp_up's product (the gelu is made of it)
    )
    assert released == 4 * floats
    # Under the checkpoint: the input (no kernel's name in a dense block).
    assert checkpointed == 4 * b * s * d
    # The parameters are the step's state: the count holds none of their
    # bytes, though the pullback's own list carries every one of them.
    parameters = sum(
        4 * int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(variables))
    assert parameters > released / 4


def test_kept_bytes_reads_through_calls_and_counts_an_array_once():
    def fun(w, x):
        y = jnp.tanh(x @ w)                 # held: the product, once
        return jnp.sum(jax.nn.silu(y) * y * jnp.exp(y))

    w = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    # The input (the weight's gradient reads it) and the product; tanh,
    # silu, exp and their products are made of the product. Under a
    # checkpoint: the input, and what the forward names for its policy.
    assert model_step.kept_bytes(fun, w, x) == (2 * 4 * 4 * 8, 4 * 4 * 8)

    def named(w, x):
        return fun(w, checkpoint_name(jnp.sin(x), "kept"))

    assert model_step.kept_bytes(named, w, x, names=("kept", "absent")) == (
        2 * 4 * 4 * 8, 2 * 4 * 4 * 8)


# ------------------------------------------------- through the estimator

def _frame(rows=8):
    rng = np.random.default_rng(0)
    return pd.DataFrame(
        rng.integers(0, 64, (rows, SEQ)).astype(np.int32),
        columns=[f"t{i}" for i in range(SEQ)])


def _estimator(**overrides):
    model, _ = _stack()
    return JAXEstimator(**{**dict(
        model=model, optimizer=optax.sgd(0.1), loss="lm_ce",
        self_supervised=True, batch_size=4, label_column=None,
        feature_columns=[f"t{i}" for i in range(SEQ)],
        feature_dtype=np.int32, seed=0, shuffle=False, epoch_mode="stream",
    ), **overrides})


def _gauges():
    return {
        name: metrics.gauge_value(f"checkpoint/{name}") for name in (
            "blocks", "blocks_checkpointed", "estimated_bytes",
            "limit_bytes", "fell_back")
    }


def test_a_backend_that_reports_no_limit_keeps_every_block_checkpointed():
    est = _estimator()
    est.fit_on_df(_frame(), num_epochs=1)
    assert est._step_model is est._model
    assert _gauges() == dict(
        blocks=3, blocks_checkpointed=3, estimated_bytes=0, limit_bytes=0,
        fell_back=0)


@pytest.mark.parametrize("limit, released", [
    (GIB, (0, 1, 2)), (1, ()),
])
def test_the_limit_decides_and_the_gauges_say_so(
        monkeypatch, limit, released):
    monkeypatch.setattr(model_step, "device_limit", lambda mesh: limit)
    est = _estimator()
    history = est.fit_on_df(_frame(), num_epochs=1)
    assert np.isfinite(history[-1]["train_loss"])
    assert est._step_model.cfg.released == released
    assert est._model.cfg.released == ()      # predict, save: as given
    got = _gauges()
    assert got["blocks"] == 3
    assert got["blocks_checkpointed"] == 3 - len(released)
    assert got["limit_bytes"] == limit and got["fell_back"] == 0
    assert 0 < got["estimated_bytes"]
    assert (got["estimated_bytes"] <= limit) == bool(released)


def test_the_loss_is_the_same_whichever_blocks_are_released(monkeypatch):
    losses = []
    for limit in (None, GIB):
        monkeypatch.setattr(model_step, "device_limit", lambda mesh: limit)
        est = _estimator()
        losses.append(est.fit_on_df(_frame(), num_epochs=2)[-1]["train_loss"])
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)


def test_a_first_dispatch_out_of_memory_rebuilds_once_all_checkpointed(
        monkeypatch):
    monkeypatch.setattr(model_step, "device_limit", lambda mesh: GIB)
    est = _estimator()
    built = []
    real = est._make_train_step

    def make():
        step = real()
        built.append(est._step_model.cfg.released)
        if not built[-1]:
            return step

        def too_big(*args):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm.")
        return too_big

    monkeypatch.setattr(est, "_make_train_step", make)
    history = est.fit_on_df(_frame(), num_epochs=2)
    assert np.isfinite(history[-1]["train_loss"])
    assert built == [(0, 1, 2), ()]           # once, and not per epoch
    got = _gauges()
    assert got["fell_back"] == 1 and got["blocks_checkpointed"] == 3


def test_another_failure_of_the_first_dispatch_is_not_caught(monkeypatch):
    monkeypatch.setattr(model_step, "device_limit", lambda mesh: GIB)
    est = _estimator()

    def make():
        def broken(*args):
            raise RuntimeError("INVALID_ARGUMENT: not a memory matter")
        return broken

    monkeypatch.setattr(est, "_make_train_step", make)
    with pytest.raises(estimator_module._profiling.CompileError):
        est.fit_on_df(_frame(), num_epochs=1)
    assert _gauges()["fell_back"] == 0


# ------------------------------- the reports that follow the decision

@pytest.mark.parametrize("released, layers", [
    ((), 3), ((2,), 2), ((0, 2), 1), ((0, 1, 2), 0),
])
def test_the_flash_report_keeps_the_checkpointed_blocks_calls(
        released, layers):
    cfg = dataclasses.replace(
        _stack(released)[0].cfg, attention_impl="flash", dtype=jnp.bfloat16)
    report_flash_tiles(cfg, seq_len=SEQ, batch=2)
    assert metrics.gauge_value("attention/flash_kept_layers") == layers
    # A head's output row in bf16 and its float32 lse, a call.
    assert metrics.gauge_value("attention/flash_kept_mib") * MIB == (
        layers * 2 * SEQ * 2 * (16 * 2 + 4))


@pytest.mark.parametrize("released, passes", [
    ((), 9), ((1,), 8), ((0, 1, 2), 6),
])
def test_the_exchange_count_is_two_passes_a_released_block(
        monkeypatch, released, passes):
    cfg = olmoe(
        vocab_size=64, d_model=32, n_heads=2, n_layers=3, n_experts=4,
        top_k=2, d_expert=16, max_len=SEQ, remat=True, released=released,
    )
    # Four chips' worth of exchange without a mesh to build.
    monkeypatch.setattr(
        moe.MoEConfig, "exchange_chips", property(lambda self: 4))
    moe.report(CausalLM(cfg), tokens_per_step=4 * SEQ)
    assert metrics.gauge_value("moe/exchange_bytes_per_step") == (
        passes * exchange_bytes(cfg.moe_config(), 4 * SEQ))
    assert exchange_bytes(cfg.moe_config(), 4 * SEQ) > 0


# -------------------------------------------------------------- on a mesh

def test_on_a_mesh_every_byte_is_one_chips(monkeypatch, eight_cpu_devices):
    """The expert-parallel group of ``tests/test_mellum2_window_moe.py``
    (its sizes, the benchmark's builder) over ``dp=4``: the state is
    counted as it lies on one chip, a block at the chip's share of the
    batch with the exchange's gathered rows at their gathered size, and a
    released step trains to the loss the checkpointed one does."""
    import importlib.util
    import os

    from raydp_tpu.parallel import MeshSpec

    sizes = importlib.import_module("tests.test_mellum2_window_moe")
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "mellum2_window_moe_lm.py")
    spec = importlib.util.spec_from_file_location("mellum2_builder_fit", path)
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)
    builder.QUERY_ROWS_AT_ONCE = 16
    monkeypatch.setattr(moe, "compact_rows", lambda cfg, tokens: 96)
    ids = np.random.default_rng(0).integers(
        0, 512, (sizes.CHIPS, sizes.SEQ)).astype(np.int32)
    frame = pd.DataFrame({f"t{i}": ids[:, i] for i in range(sizes.SEQ)})
    losses, estimates = {}, {}
    for limit in (None, GIB):
        monkeypatch.setattr(model_step, "device_limit", lambda mesh: limit)
        mesh = MeshSpec(dp=sizes.CHIPS)
        est = JAXEstimator(
            **builder.estimator_kwargs(sizes.SIZES, sizes.TRAFFIC, mesh),
            batch_size=sizes.CHIPS, mesh=mesh, seed=3, epoch_mode="stream",
            shuffle=False,
        )
        losses[limit] = est.fit_on_df(
            frame, num_epochs=1, num_shards=1)[-1]["train_loss"]
        estimates[limit] = metrics.gauge_value("checkpoint/estimated_bytes")
        released = est._step_model.cfg.released
        assert released == ((0, 1) if limit else ())
        assert metrics.gauge_value("moe/exchange_bytes_per_step") == (
            (4 if limit else 6) * exchange_bytes(
                est._model.cfg.moe_config(), sizes.CHIPS * sizes.SEQ))
    np.testing.assert_allclose(losses[None], losses[GIB], rtol=1e-5)
    state = sum(
        leaf.addressable_shards[0].data.nbytes
        for leaf in jax.tree_util.tree_leaves(est._state))
    whole = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(est._state))
    assert state < whole                    # experts and tables: a share
    # One chip's logits, [1, S, V / 4] float32 and their gradient, and the
    # gathered rows of two layers' exchange are inside the estimate.
    head = 2 * 4 * sizes.SEQ * 512
    gathered = 2 * 4 * sizes.CHIPS * sizes.SEQ * 64
    assert state + head + gathered < estimates[GIB] < 8 * (
        state + head + gathered)
