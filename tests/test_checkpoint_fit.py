"""How many blocks of a ``remat`` stack are checkpointed follows from the
shapes and the device's memory (PR 56; the count that walks the step, PR
60): the rule as a pure function on the calibration table's rows, the
estimate against every compiled step of that table, the model it makes,
what one abstract trace counts of a block, the way back, and the two
reports that follow the decision (``models/step.py``)."""
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
# One chip of a TPU v5e as its backend reports it (``bytes_limit``; my chip
# runs, PR 60): 2 MiB under 15.75 GiB.
V5E = 16_909_336_064


def _row(released, checkpointed, working, gradients, fixed, head, stays=0,
         passes=1, exits=1):
    return model_step.Stack(
        *([size * MIB for size in sizes]
          for sizes in (released, checkpointed, working, gradients)),
        fixed * MIB, head * MIB, stays * MIB, passes, exits)


# The calibration table's rows (PERF.md section 6, PR 60), in MiB and one
# chip's: what a block holds released and checkpointed, the most its two
# passes have live at once, what its parameters' gradients hold; what the
# step holds from end to end (state, batch), the logits, and what of the
# head stays beside the blocks' backward (a shared table: Granite, LFM2).
ROWS = {
    "granite": _row(
        [492] * 5 + [201], [16] * 5 + [32], [875] * 5 + [393],
        [147] * 5 + [116], 7407, 1568, 1568),
    "lfm2": _row(
        [384, 384, 744, 710, 710, 710, 744], [32, 32, 65, 32, 32, 32, 65],
        [644, 644, 1588, 1554, 1554, 1554, 1588],
        [116, 116, 188, 200, 200, 200, 188], 7636, 512, 512),
    "xing4": _row(
        [660] + [951] * 4, [144] * 5, [1318] + [1698] * 4,
        [246] + [247] * 4, 8690, 256),
    "laguna": _row(
        [1093, 1450, 1450, 1450, 1320], [259, 324, 324, 324, 259],
        [1573, 3936, 3936, 3936, 3806], [152, 272, 272, 272, 256], 7915, 784),
    "kimi": _row(
        # Since PR 66 (q and k leave their convolutions in bfloat16,
        # normalised: a KDA block holds two float32 [16384, 4096] less).
        [2155, 2029, 2029, 1254, 2029], [344, 344, 344, 202, 344],
        [2819, 5260, 5260, 4485, 5260], [197, 199, 199, 179, 199],
        6895, 1280),
    "sdar": _row(
        [1343] * 6, [194] * 6, [3926] * 6, [181] * 6, 7389, 594),
    "keye": _row(
        [1246] * 5, [194] * 5, [3829] * 5, [185] * 5, 6435, 1187),
    "nemotron": _row(
        [1883, 727, 1883, 727, 1883, 358, 727, 1883, 727],
        [84, 84, 84, 84, 84, 214, 84, 84, 84],
        [2960, 3330, 2960, 3330, 2960, 617, 3330, 2960, 3330],
        [77, 192, 77, 192, 77, 45, 192, 77, 192], 7633, 1024),
    # One chip of four: the state as it lies there, the exchange's
    # gathered rows at their gathered size.
    "mellum2": _row(
        [1163] * 4, [50] * 4, [3754] * 4, [388] * 4, 6811, 1536),
    # A stack run four times with an exit after every pass (PR 61): a
    # block's bytes count once an application, ``head`` is ONE exit's
    # logits and ``stays`` the head's own gradient.
    "ouro": _row(
        [401] * 6, [64] * 6, [587] * 6, [98] * 6, 5833, 1536, 384,
        passes=4, exits=4),
}


def _last(name, k):
    n = len(ROWS[name].released)
    return tuple(range(n - k, n))


# Every step of the table compiled for a described v5e (``memory_analysis()``
# arguments + temporaries, GiB): ``(cell, blocks released, compiled)``.
COMPILED = [
    ("granite", _last("granite", 0), 10.57),
    ("granite", _last("granite", 6), 13.45),
    ("lfm2", _last("lfm2", 0), 10.29),
    ("lfm2", _last("lfm2", 7), 13.53),
    ("xing4", _last("xing4", 0), 10.83),
    ("xing4", _last("xing4", 2), 11.65),
    ("xing4", _last("xing4", 3), 12.41),
    ("xing4", _last("xing4", 4), 13.18),
    ("xing4", _last("xing4", 5), 13.90),
    ("laguna", _last("laguna", 0), 12.29),
    ("laguna", _last("laguna", 1), 12.51),
    ("laguna", _last("laguna", 2), 13.52),
    ("laguna", (0, 4), 13.47),
    ("laguna", _last("laguna", 3), 13.69),
    ("laguna", _last("laguna", 5), 15.03),
    ("kimi", _last("kimi", 0), 13.18),
    ("kimi", _last("kimi", 1), 14.30),         # 14.292: 0.05 under the count
    # All five released is no row: the compiler refuses it (it read 17.91
    # GiB before PR 66 and reads 22.24 since, the count 21.65) and returns
    # no program; the rule's next candidate is.
    ("kimi", _last("kimi", 2), 14.43),
    ("sdar", _last("sdar", 0), 11.91),
    ("sdar", _last("sdar", 1), 12.04),
    ("sdar", _last("sdar", 2), 13.49),
    ("sdar", _last("sdar", 3), 13.81),
    ("sdar", _last("sdar", 4), 14.25),
    ("sdar", _last("sdar", 6), 15.81),         # refused: over by 60 MB
    ("keye", _last("keye", 0), 10.24),
    ("keye", _last("keye", 2), 11.90),
    ("keye", _last("keye", 3), 13.07),
    ("keye", _last("keye", 4), 13.82),
    ("nemotron", _last("nemotron", 0), 12.10),
    ("nemotron", (5, 7, 8), 13.89),
    ("nemotron", _last("nemotron", 4), 13.97),
    ("nemotron", _last("nemotron", 5), 15.78),
    ("mellum2", _last("mellum2", 0), 9.91),
    ("mellum2", _last("mellum2", 1), 10.28),
    ("mellum2", _last("mellum2", 2), 11.42),
    ("mellum2", _last("mellum2", 3), 12.43),
    ("mellum2", _last("mellum2", 4), 13.45),
    # With NO block released the compiled step reads 12.15 GiB and the
    # estimate 11.56: the compiler starts the last pass's second forward
    # before the exits and holds it (PERF.md section 7, PR 61). Not a row:
    # the rule never stops there at this limit.
    ("ouro", _last("ouro", 1), 13.06),
    ("ouro", _last("ouro", 2), 14.56),
]


# ------------------------------------------------------------- the rule

@pytest.mark.parametrize("name, limit, want", [
    ("granite", None, ()),                    # no limit reported: the CPU
    ("lfm2", None, ()),
    ("granite", V5E, (0, 1, 2, 3, 4, 5)),     # whole, 13.5 GiB on the chip
    ("lfm2", V5E, (0, 1, 2, 3, 4, 5, 6)),     # whole, under 14 GiB
    ("xing4", V5E, (0, 1, 2, 3, 4)),          # whole: 13.90 GiB compiled
    ("laguna", V5E, (3, 4)),                  # 13.52 compiled
    ("laguna", 2 * V5E, (0, 1, 2, 3, 4)),
    ("granite", 8 * GIB, ()),                 # the state alone is over
    ("sdar", V5E, (4, 5)),                    # 13.49 compiled
    ("keye", V5E, (2, 3, 4)),                 # 13.07
    ("kimi", V5E, (4,)),                      # 14.30: the row that binds
    ("nemotron", V5E, (5, 7, 8)),             # 13.89; block 6 does not fit
    ("mellum2", V5E, (1, 2, 3)),              # 12.43 a chip
    ("ouro", V5E, (4, 5)),                    # 14.56; 14.11 on the chip
])
def test_the_rule_on_the_calibration_rows(name, limit, want):
    assert model_step.released_blocks(ROWS[name], limit) == want


@pytest.mark.parametrize("name, out, compiled", COMPILED, ids=[
    f"{name}-{'.'.join(map(str, out)) or 'none'}" for name, out, _ in COMPILED
])
def test_the_estimate_is_no_lower_than_any_compiled_step(name, out, compiled):
    estimate = model_step.estimated_bytes(ROWS[name], out)
    assert estimate.total >= compiled * GIB
    assert estimate.total == ROWS[name].fixed + int(
        model_step.SLACK * (estimate.held + estimate.working))


def test_the_slack_is_the_least_tenth_that_bounds_the_table(monkeypatch):
    assert model_step.SLACK <= 1.6 and model_step.MARGIN == 0.05
    monkeypatch.setattr(
        model_step, "SLACK", round(model_step.SLACK - 0.1, 1))
    under = [
        (name, out) for name, out, compiled in COMPILED
        if model_step.estimated_bytes(ROWS[name], out).total < compiled * GIB
    ]
    assert ("kimi", (4,)) in under


@pytest.mark.parametrize("name", list(ROWS))
def test_the_rule_is_monotone_and_never_over(name):
    stack = ROWS[name]
    counts, kept = [], []
    for limit in range(8 * GIB, 26 * GIB, GIB // 4):
        out = model_step.released_blocks(stack, limit)
        counts.append(len(out))
        kept.append(sum(stack.released[i] for i in out))
        room = limit * (1 - model_step.MARGIN)
        # Nothing released is what the configuration wrote: it ran before.
        assert not out or model_step.estimated_bytes(stack, out).total <= room
        # No block left that would have fitted beside those released.
        for i in set(range(len(stack.released))) - set(out):
            later = tuple(j for j in out if j > i)
            assert model_step.estimated_bytes(
                stack, later + (i,)).total > room
    # More memory never releases less: fewer blocks only where kinds
    # differ (Nemotron: two Mamba blocks in the room of three small ones).
    assert kept == sorted(kept)
    assert counts == sorted(counts) or name == "nemotron"
    assert counts[0] == 0 and counts[-1] == len(stack.released)


def test_the_estimate_counts_a_checkpointed_blocks_second_forward():
    """The walk: block i's two passes run beside what blocks 0..i-1 hold
    and the gradients of blocks i+1.. ; the head beside what all hold."""
    slack = model_step.SLACK
    stack = model_step.Stack(
        released=[100, 300, 200], checkpointed=[10, 10, 10],
        working=[400, 700, 900], gradients=[1, 2, 4], fixed=1000, head=50,
        head_stays=0)

    def total(held, working):
        return model_step.Estimate(
            1000 + int(slack * (held + working)), held, working)

    # All checkpointed: the last block's second forward and backward
    # (its working set) beside the two inputs before it.
    assert model_step.estimated_bytes(stack, ()) == total(20, 900)
    # The last block released changes nothing there: what it keeps is in
    # its own working set, and it is NOT live while block 0's backward
    # runs (400 beside the later blocks' gradients alone).
    assert model_step.estimated_bytes(stack, (2,)) == total(20, 900)
    first = model_step.Stack(
        [100, 300, 200], [10, 10, 10], [2000, 700, 900], [1, 2, 4], 1000, 50,
        0)
    assert model_step.estimated_bytes(first, (2,)) == total(2 + 4, 2000)
    assert model_step.estimated_bytes(first, ()) == total(2 + 4, 2000)
    # A block released is held while every later block's backward runs.
    assert model_step.estimated_bytes(stack, (0, 1)) == total(400, 900)
    # The logits and their gradient are live beside what EVERY block
    # holds, and with no block's backward ...
    big = stack._replace(head=2000)
    assert model_step.estimated_bytes(big, (0, 1, 2)) == total(600, 4000)
    assert model_step.estimated_bytes(big, ()) == total(30, 4000)
    # ... unless the head shares the embedding's table: the logits'
    # gradient then stays beside every block's backward.
    tied = stack._replace(head_stays=50)
    assert model_step.estimated_bytes(tied, (0, 1)) == total(450, 900)


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
    released, checkpointed, working, gradients = model_step.block_bytes(
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
    # Every parameter's gradient is a product's or a sum's own array.
    assert gradients == parameters
    assert working > released


def test_a_dense_blocks_working_set_is_a_hand_count():
    """One sublayer, norm -> up -> gelu -> down -> residual: the most is
    live where the backward takes the gradient back through ``mlp_up``."""
    b, s, d, f = 2, SEQ, 32, 64
    cfg = tiny_transformer(
        vocab_size=64, d_model=d, n_heads=2, d_ff=f, max_len=s, n_layers=1,
        causal=True, dtype=jnp.float32, remat=True,
    )
    x = jax.ShapeDtypeStruct((b, s, d), jnp.float32)
    block = TransformerBlock(cfg, "none", "gelu")
    variables = nn.unbox(jax.eval_shape(
        lambda x: block.init(jax.random.PRNGKey(0), x, False), x))
    counted = model_step.block_bytes(cfg, "none", "gelu", variables, x)
    kept = (
        b * s * d           # the block's input
        + 2 * b * s         # the norm's two sums a row
        + b * s * f         # mlp_up's product
    )
    assert counted.released == 4 * kept
    assert counted.checkpointed == 4 * b * s * d
    assert counted.working == 4 * (
        kept                # all of it still read: the normed input and
                            # the gelu's slope are made of these
        + b * s * d         # the result's cotangent, the next block's
        + d + f * d         # mlp_down's bias and kernel gradients, written
        + b * s * f         # the gradient at the gelu's result
        + f + d * f         # mlp_up's bias and kernel gradients
        + b * s * d         # the gradient at the normed input, just
                            # written (the norm's own sums come after and
                            # are a row each)
    )
    # mlp_down's product is written in the forward and read by nothing:
    # the forward's own most (kept + b * s * d) is under the backward's.
    assert counted.gradients == 4 * (2 * d * f + f + 3 * d)


def test_two_traces_of_one_shape_give_one_count():
    model, ids = _stack()
    variables = nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)))
    x = jax.ShapeDtypeStruct((2, SEQ, 32), jnp.float32)
    layer = {"params": variables["params"]["encoder"]["block_1"]}
    counts = {
        model_step.block_bytes(model.cfg, "attention", "gelu", layer, x)
        for _ in range(2)
    }
    assert len(counts) == 1 and all(min(count) > 0 for count in counts)


def test_kept_bytes_reads_through_calls_and_counts_an_array_once():
    def fun(w, x):
        y = jnp.tanh(x @ w)                 # held: the product, once
        return jnp.sum(jax.nn.silu(y) * y * jnp.exp(y))

    w = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    # The input (the weight's gradient reads it) and the product; tanh,
    # silu, exp and their products are made of the product. Under a
    # checkpoint: the input, and what the forward names for its policy.
    # At work: both beside the weight's gradient (8 x 8) and the scalar
    # result's cotangent; the input is read last there, so its own
    # gradient then takes no more than its place.
    assert model_step.kept_bytes(fun, w, x) == (
        2 * 4 * 4 * 8, 4 * 4 * 8, 4 * (2 * 4 * 8 + 8 * 8 + 1), 4 * 8 * 8)

    def named(w, x):
        return fun(w, checkpoint_name(jnp.sin(x), "kept"))

    assert model_step.kept_bytes(
        named, w, x, names=("kept", "absent"))[:2] == (
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
            "blocks", "blocks_checkpointed", "estimated_bytes", "held_bytes",
            "working_bytes", "limit_bytes", "fell_back")
    }


def test_a_backend_that_reports_no_limit_keeps_every_block_checkpointed():
    est = _estimator()
    est.fit_on_df(_frame(), num_epochs=1)
    assert est._step_model is est._model
    assert _gauges() == dict(
        blocks=3, blocks_checkpointed=3, estimated_bytes=0, held_bytes=0,
        working_bytes=0, limit_bytes=0, fell_back=0)


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
    # The estimate's two parts where it is greatest, before the slack.
    assert 0 < got["held_bytes"] and 0 < got["working_bytes"]
    assert got["held_bytes"] + got["working_bytes"] < got["estimated_bytes"]


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
