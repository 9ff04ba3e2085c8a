"""The seam between the runner and what it runs (``models/step.py``):
``train/estimator.py`` knows no model family by name, a flax module that
is none of them trains through it with everything a step takes from a
model besides its loss, and the one build-time report is the eleven
reports it replaced."""
import ast
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest

from raydp_tpu.models import (
    CausalLM, blockdiff, dropout, gdn, hyperconn, kda, latent, loop, mamba,
    moe, mtp, olmoe,
    shortconv, sparse_index, stats, window,
)
from raydp_tpu.models import step as model_step
from raydp_tpu.models.transformer import report as report_stack
from raydp_tpu.ops.flash_attention import report as report_flash_tiles
from raydp_tpu.train import JAXEstimator
from raydp_tpu.utils.profiling import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_runner_imports_one_module_of_models_and_ops():
    """Top level or inside any function: ``models.step`` alone."""
    path = os.path.join(REPO, "raydp_tpu", "train", "estimator.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    of_models = {
        name for name in found
        if name.startswith(("raydp_tpu.models", "raydp_tpu.ops"))
    }
    assert of_models == {"raydp_tpu.models.step"}


@pytest.fixture
def gauges_set(monkeypatch):
    """Every ``gauge_set`` of the test, in order, as ``(name, value)``."""
    calls = []
    real = metrics.gauge_set

    def record(name, value):
        calls.append((name, float(value)))
        real(name, value)

    monkeypatch.setattr(metrics, "gauge_set", record)
    return calls


def _eleven_reports_by_hand(model, params, sample):
    """What ``JAXEstimator._build_steps`` called before ``models/step.py``,
    in that order with those arguments (the list the cell tests under
    ``tests/benchmark/`` copy parts of)."""
    cfg = getattr(model, "cfg", None)
    batch, seq_len = sample.shape[0], int(sample.shape[-1])
    tokens = int(np.prod(sample.shape)) * getattr(
        model, "positions_per_token", 1)
    dropout.report(*dropout.census(
        model.apply, params, sample, also=model_step.step_rngs(model)))
    report_stack(cfg)       # PR 57: the stack's layers by what they hold
    mamba.report(cfg, tokens_per_step=tokens)
    kda.report(cfg, tokens_per_step=tokens, sequence=seq_len)
    gdn.report(cfg, tokens_per_step=tokens, sequence=seq_len)   # PR 63
    shortconv.report(cfg)
    latent.report(cfg)
    window.report(cfg)
    sparse_index.report(cfg, seq_len=seq_len)
    blockdiff.report(model, batch=batch, seq_len=seq_len)
    hyperconn.report(cfg)
    loop.report(model)      # PR 61: a stack run several times, its exits
    mtp.report(model, params)   # PR 67: a module behind the stack
    report_flash_tiles(cfg, seq_len=seq_len, batch=batch)
    moe.report(model, tokens_per_step=tokens)


@pytest.fixture(scope="module")
def routed():
    """A tiny routed preset with dropout sites, its variables, a batch."""
    model = CausalLM(olmoe(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, n_experts=4,
        top_k=2, d_expert=16, max_len=16, dropout_rate=0.1,
        dtype=jnp.float32,
    ))
    sample = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    params = model_step.parameters(nn.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))))
    return model, params, sample


def test_the_one_report_is_the_eleven(routed, gauges_set):
    _eleven_reports_by_hand(*routed)
    by_hand = list(gauges_set)
    del gauges_set[:]
    model_step.report(*routed)
    assert gauges_set == by_hand
    values = dict(by_hand)
    assert len(values) == len(by_hand) > 40  # each gauge once
    assert values["train/dropout_sites"] > 0
    assert values["moe/experts_routed"] == 4
    assert values["ssm/layers"] == values["kda/layers"] == 0
    assert values["gdn/layers"] == values["gdn/kept_bytes_per_sequence"] == 0


# A model of no family under ``models/``: no ``cfg``, and one of each
# thing a step takes from a model besides its loss.
AUX = 3.0
SEAM_ROWS = stats.declare("seam_rows")


class Odd(nn.Module):
    step_rngs = ("noise",)

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        w = self.param("w", nn.initializers.ones, (x.shape[-1],))
        held = self.variable(
            moe.BUFFERS, "held", lambda: jnp.full((x.shape[-1],), 0.5))
        y = jnp.sum(x * w * held.value, axis=-1)
        if not deterministic:
            # Fails without a key for the collection the model names.
            y = y + 0.0 * jax.random.normal(self.make_rng("noise"), y.shape)
        self.sow("losses", "aux", jnp.float32(AUX))
        stats.sow(self, SEAM_ROWS, jnp.float32(x.shape[0]))
        return y


def _fit_odd(aux_losses):
    rng = np.random.default_rng(0)
    frame = pd.DataFrame(
        rng.standard_normal((16, 4)).astype(np.float32), columns=list("abcd"))
    frame["y"] = rng.standard_normal(16).astype(np.float32)
    est = JAXEstimator(
        model=Odd(), optimizer=optax.adamw(0.1, weight_decay=0.1), loss="mse",
        batch_size=8, feature_columns=list("abcd"), label_column="y",
        seed=2, shuffle=False, epoch_mode="stream", aux_losses=aux_losses,
    )
    return est, est.fit_on_df(frame, num_epochs=1)


def test_a_module_of_no_family_trains_through_the_seam(
        routed, gauges_set, monkeypatch):
    epochs = []
    monkeypatch.setattr(
        model_step, "report_epoch",
        lambda stats_sum, n_batches: epochs.append((stats_sum, n_batches)))
    _eleven_reports_by_hand(*routed)
    family = sorted({name for name, _ in gauges_set})
    for name in family:
        metrics.gauge_set(name, 7)

    est, history = _fit_odd(aux_losses=True)
    assert est.effective_epoch_mode == "stream"
    # Two steps; each sowed its rows, and the epoch's sum reached the report.
    (stats_sum, n_batches), = epochs
    assert n_batches == 2 and set(stats_sum) == {SEAM_ROWS}
    assert float(stats_sum[SEAM_ROWS]) == 16.0
    # The sown term is in the loss (its gradient is zero, so the steps
    # are the plain run's).
    _, plain = _fit_odd(aux_losses=False)
    assert history[0]["train_loss"] - plain[0]["train_loss"] == pytest.approx(
        AUX, abs=1e-5)
    # The weights moved; what no step may change did not, by one bit.
    params = est._state.params
    assert set(params) == {"params", moe.BUFFERS}
    assert not np.allclose(np.asarray(params["params"]["w"]), 1.0)
    np.testing.assert_array_equal(
        np.asarray(params[moe.BUFFERS]["held"]), np.full(4, 0.5, np.float32))
    # Zeros and silence from every family's build-time report (one chip
    # is what a layer with no exchange lies on).
    for name in family:
        want = 1.0 if name == "moe/exchange_chips" else 0.0
        assert metrics.gauge_value(name) == want, name
    # The state's layout is the rules given, for a model with no ``cfg``.
    assert model_step.logical_rules(Odd(), [("batch", "dp")]) == [
        ("batch", "dp")]
