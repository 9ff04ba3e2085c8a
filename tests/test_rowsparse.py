"""The row path of the train step (raydp_tpu/train/rowsparse.py): same
numbers as the dense step for a row-exact optimizer, the dense step
itself for any other, and no table-sized op but the row scatter."""
import logging
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models.dlrm import DLRM, PackedDLRM, tiny_dlrm
from raydp_tpu.parallel.mesh import MeshSpec
from raydp_tpu.train import rowsparse
from raydp_tpu.train.estimator import JAXEstimator
from raydp_tpu.utils.profiling import metrics

BATCH = 64
STEPS = 3


@pytest.fixture(autouse=True)
def tiny_tables_take_the_row_path(monkeypatch):
    """The crossover constants are sized for the chip (128 rows an id,
    32 MiB); the tables here are a few thousand rows."""
    monkeypatch.setattr(rowsparse, "MIN_ROWS_PER_ID", 1)
    monkeypatch.setattr(rowsparse, "MIN_TABLE_BYTES", 0)


class BagDLRM(nn.Module):
    """PackedDLRM with a bag of ``bag`` ids per table (multi-hot)."""

    cfg: object
    bag: int

    @nn.compact
    def __call__(self, x):
        d = self.cfg.dense_features
        sparse = x[:, d:].astype(jnp.int32).reshape(
            x.shape[0], self.cfg.n_tables, self.bag
        )
        return DLRM(self.cfg, name="dlrm")(x[:, :d], sparse)


def _zipf(rng, vocab, shape):
    return np.minimum(rng.zipf(1.1, size=shape), vocab) - 1


def _distinct(rng, vocab, shape):
    return rng.permutation(vocab)[: int(np.prod(shape))].reshape(shape)


CASES = {
    # vocab sizes, id generator, ids per table and sample
    "heavy_duplicates": ((50, 5000, 300, 2000), _zipf, 1),
    "all_distinct": ((1000, 5000, 70), _distinct, 1),
    "small_beside_large": ((8, 10_000), _zipf, 1),
    "multi_hot_bags": ((40, 5000, 900), _zipf, 3),
}


def _model_and_batches(case, steps=STEPS, batch=BATCH):
    vocab, draw, bag = CASES[case]
    cfg = tiny_dlrm(
        vocab_sizes=vocab, embedding_impl="take", dtype=jnp.float32
    )
    model = PackedDLRM(cfg) if bag == 1 else BagDLRM(cfg, bag)
    rng = np.random.default_rng(7)
    batches = []
    for _ in range(steps):
        dense = rng.standard_normal((batch, cfg.dense_features))
        ids = np.concatenate(
            [draw(rng, v, (batch, bag)) for v in vocab], axis=1
        )
        x = np.concatenate([dense, ids], axis=1).astype(np.float32)
        y = (dense[:, 0] + ids[:, 0] % 2 > 0.5).astype(np.float32)
        batches.append((x, y))
    return model, batches


def _estimator(model, tx, row_path=True, batch=BATCH, **kwargs):
    est = JAXEstimator(
        model=model, optimizer=tx, loss="bce", batch_size=batch,
        feature_columns=["x"], label_column="y", seed=3, **kwargs,
    )
    if not row_path:
        est._row_plan = False   # what a model with no row lookup gets
    return est


def _run_steps(est, batches):
    """The estimator's own jitted train step, ``len(batches)`` times."""
    est._init_state(batches[0][0])
    rng = jax.random.PRNGKey(11)
    out = []
    for x, y in batches:
        xd, yd = est._shard_batch(x, y)
        est._state, loss, gnorm, _ = est._train_step(est._state, xd, yd, rng)
        out.append((float(loss), float(gnorm)))
    return out


def _assert_same_state(a, b, rtol=1e-5, atol=1e-6):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = jax.tree_util.tree_leaves(b)
    for (path, u), v in zip(flat_a, flat_b):
        assert u.shape == v.shape, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(v), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path),
        )


def _state_of(est):
    return est._state.params, est._state.opt_state


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("opt", ["adagrad", "sgd"])
def test_row_path_equals_dense_path(opt, case):
    model, batches = _model_and_batches(case)
    tx = getattr(optax, opt)(0.05)
    row = _estimator(model, tx)
    got = _run_steps(row, batches)
    assert metrics.gauge_value("train/rowsparse_tables") >= 1
    dense = _estimator(model, tx, row_path=False)
    want = _run_steps(dense, batches)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_same_state(_state_of(row), _state_of(dense))
    assert int(row._state.step) == int(dense._state.step) == STEPS


def _decayed_sgd():
    return optax.chain(optax.add_decayed_weights(1e-2), optax.sgd(0.05))


NOT_ROW_EXACT = {
    "adam": lambda: optax.adam(1e-2),
    "adamw": lambda: optax.adamw(1e-2),
    "sgd_weight_decay": _decayed_sgd,
    "sgd_momentum": lambda: optax.sgd(0.05, momentum=0.9),
    "rmsprop": lambda: optax.rmsprop(1e-2),
    "adafactor": lambda: optax.adafactor(1e-2),
    "lamb": lambda: optax.lamb(1e-2),
}


@pytest.mark.parametrize("name", sorted(NOT_ROW_EXACT))
def test_probe_refuses_what_is_not_row_exact(name):
    assert not rowsparse.row_exact(NOT_ROW_EXACT[name]())


@pytest.mark.parametrize("name", ["adagrad", "sgd"])
def test_probe_accepts_row_exact(name):
    assert rowsparse.row_exact(getattr(optax, name)(1e-2))


@pytest.mark.parametrize("name", ["adam", "adamw", "sgd_weight_decay"])
def test_not_row_exact_runs_the_dense_step(name):
    """Three steps through the estimator equal a plain optax loop."""
    model, batches = _model_and_batches("heavy_duplicates")
    tx = NOT_ROW_EXACT[name]()
    est = _estimator(model, tx)
    est._init_state(batches[0][0])
    assert est._row_path() is None
    assert metrics.gauge_value("train/rowsparse_tables") == 0
    params = jax.tree_util.tree_map(jnp.copy, est._state.params)
    got = _run_steps(est, batches)

    opt_state = tx.init(params)
    want = []

    @jax.jit
    def plain(params, opt_state, x, y):
        def loss(p):
            logits = model.apply(p, x)
            return optax.sigmoid_binary_cross_entropy(logits, y).mean()

        value, grads = jax.value_and_grad(loss)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state, value,
                optax.global_norm(grads))

    for x, y in batches:
        params, opt_state, value, gnorm = plain(params, opt_state, x, y)
        want.append((float(value), float(gnorm)))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_same_state(_state_of(est), (params, opt_state))


def test_row_path_on_a_dp_mesh(eight_cpu_devices):
    """Replicated tables, batch sharded over dp: GSPMD's row path gives
    the single-device numbers."""
    model, batches = _model_and_batches("heavy_duplicates")
    tx = optax.adagrad(0.05)
    one = _estimator(model, tx)
    want = _run_steps(one, batches)
    many = _estimator(model, tx, mesh=MeshSpec(dp=8))
    got = _run_steps(many, batches)
    assert metrics.gauge_value("train/rowsparse_tables") == 3
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_same_state(_state_of(many), _state_of(one))


def test_row_sharded_table_stays_dense(eight_cpu_devices, caplog):
    from raydp_tpu.models.dlrm import LOGICAL_RULES

    model, batches = _model_and_batches("heavy_duplicates")
    est = _estimator(
        model, optax.adagrad(0.05), mesh=MeshSpec(dp=2, tp=2),
        logical_rules=LOGICAL_RULES,
    )
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        _run_steps(est, batches[:1])
    assert metrics.gauge_value("train/rowsparse_tables") == 0
    assert "(c) its rows are sharded over the mesh" in caplog.text


TABLE_OPS = re.compile(
    r"^\s*(?:ROOT )?\S+ = \w+\[(\d+),16\]\S* ([\w-]+)\((.*)$", re.M
)


def _table_sized_ops(est, x, y, rows):
    """Opcodes of the compiled step whose OUTPUT is a whole table."""
    est._init_state(x)
    step = jax.jit(est._make_train_step(), donate_argnums=(0,))
    text = step.lower(
        est._state, jnp.asarray(x), jnp.asarray(y), jax.random.PRNGKey(0)
    ).compile().as_text()
    found = set()
    for size, opcode, rest in TABLE_OPS.findall(text):
        if int(size) in rows:
            scatter = opcode == "fusion" and re.search(
                r'op_name="[^"]*/scatter"', rest
            )
            found.add("scatter" if scatter else opcode)
    return found


def test_no_table_sized_op_but_the_scatter():
    """A silent fall back to a dense gradient would show here."""
    model, batches = _model_and_batches("heavy_duplicates")
    x, y = batches[0]
    large = {5000, 2000}
    row = _table_sized_ops(_estimator(model, optax.adagrad(0.05)), x, y, large)
    assert row <= {"parameter", "scatter", "bitcast", "tuple",
                   "get-tuple-element"}, row
    assert "scatter" in row
    dense = _table_sized_ops(
        _estimator(model, optax.adagrad(0.05), row_path=False), x, y, large
    )
    assert dense - {"parameter", "scatter", "bitcast", "tuple",
                    "get-tuple-element"}, dense


def _frame(batches):
    import pandas as pd

    x = np.concatenate([b[0] for b in batches])
    cols = [f"f{i}" for i in range(x.shape[1])]
    df = pd.DataFrame(x, columns=cols)
    df["y"] = np.concatenate([b[1] for b in batches])
    return df, cols


@pytest.mark.parametrize("epoch_mode", ["stream", "scan"])
def test_fit_predict_evaluate_save_restore(tmp_path, epoch_mode):
    """Everything around the step works unchanged with the row path on,
    and a checkpoint reads back into either path."""
    from raydp_tpu.data.ml_dataset import MLDataset

    model, batches = _model_and_batches("heavy_duplicates", steps=4)
    df, cols = _frame(batches)

    def fit(row_path):
        est = JAXEstimator(
            model=model, optimizer=optax.adagrad(0.05), loss="bce",
            batch_size=BATCH, num_epochs=2, feature_columns=cols,
            label_column="y", seed=3, shuffle=False,
            epoch_mode=epoch_mode,
        )
        if not row_path:
            est._row_plan = False
        est.fit_on_df(df)
        return est

    row, dense = fit(True), fit(False)
    assert row.effective_epoch_mode == epoch_mode
    np.testing.assert_allclose(
        [h["train_loss"] for h in row.history],
        [h["train_loss"] for h in dense.history], rtol=1e-5,
    )
    _assert_same_state(_state_of(row), _state_of(dense))
    table = row._state.params["params"]["dlrm"]["emb_1"]["table"]
    assert table.shape == (5000, 16)
    acc = row._state.opt_state[0].sum_of_squares
    assert acc["params"]["dlrm"]["emb_1"]["table"].shape == (5000, 16)

    x = batches[0][0]
    np.testing.assert_allclose(
        row.predict(x), dense.predict(x), rtol=1e-5, atol=1e-6
    )
    ds = MLDataset.from_df(_ensure(df), num_shards=1)
    ev_row = row.evaluate(ds)
    ev_dense = dense.evaluate(ds)
    assert ev_row.keys() == ev_dense.keys()
    for key in ev_row:
        np.testing.assert_allclose(ev_row[key], ev_dense[key], rtol=1e-5)

    # Saved on the row path, restored into a dense-path estimator and
    # the other way round: the trees are the same trees.
    for src, dst_row_path in ((row, False), (dense, True)):
        path = src.save(str(tmp_path / f"ckpt_{dst_row_path}"))
        dst = JAXEstimator(
            model=model, optimizer=optax.adagrad(0.05), loss="bce",
            batch_size=BATCH, feature_columns=cols, label_column="y",
        )
        if not dst_row_path:
            dst._row_plan = False
        dst.restore_path(path, sample_x=x)
        _assert_same_state(_state_of(dst), _state_of(src), rtol=0, atol=0)
        np.testing.assert_allclose(
            dst.predict(x), src.predict(x), rtol=1e-6, atol=1e-7
        )


def _ensure(df):
    from raydp_tpu.train.estimator import _ensure_df

    return _ensure_df(df)


def _gauges():
    return (metrics.gauge_value("train/rowsparse_tables"),
            metrics.gauge_value("train/rowsparse_row_share"))


def test_gauges_and_log_line_kaggle_shaped(caplog):
    """dlrm_kaggle in small: tables under the batch stay dense (b)."""
    vocab = (146, 58, 101312, 22026, 30, 24, 1251, 63, 3, 9314)
    cfg = tiny_dlrm(vocab_sizes=vocab, embedding_impl="take")
    est = _estimator(PackedDLRM(cfg), optax.adagrad(1e-2), batch=256)
    x = np.zeros((256, cfg.dense_features + len(vocab)), np.float32)
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        est._init_state(x)
    on = [v for v in vocab if v > 256]
    tables, share = _gauges()
    assert tables == len(on) == 4
    assert share == pytest.approx(sum(on) / sum(vocab))
    assert "row path: 4 of 10 tables" in caplog.text
    assert "(b) a dense pass over it is cheaper" in caplog.text
    assert "dlrm/emb_0, dlrm/emb_1, dlrm/emb_4" in caplog.text


def test_crossover_as_measured_on_the_chip(caplog, monkeypatch):
    """128 rows an id and 32 MiB: at batch 256 a 600,000-row table takes
    the row path, one of 40,000 rows (128 an id, 2.4 MiB) and one of
    20,000 do not."""
    monkeypatch.undo()   # the constants as they are
    vocab = (600_000, 40_000, 20_000)
    cfg = tiny_dlrm(vocab_sizes=vocab, embedding_impl="take")
    est = _estimator(PackedDLRM(cfg), optax.adagrad(1e-2), batch=256)
    x = np.zeros((256, cfg.dense_features + len(vocab)), np.float32)
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        est._init_state(x)
    tables, share = _gauges()
    assert tables == 1
    assert share == pytest.approx(600_000 / sum(vocab))
    assert "cheaper (rows per id, bytes): dlrm/emb_1, dlrm/emb_2" in (
        caplog.text
    )


def test_gauges_zero_with_adam_and_reason_d(caplog):
    cfg = tiny_dlrm(vocab_sizes=(146, 101312), embedding_impl="take")
    est = _estimator(PackedDLRM(cfg), optax.adam(1e-3), batch=256)
    x = np.zeros((256, cfg.dense_features + 2), np.float32)
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        est._init_state(x)
    assert _gauges() == (0, 0)
    assert "row path: 0 of 2 tables" in caplog.text
    assert "(d) the optimizer is not row-exact: dlrm/emb_1" in caplog.text
    assert "(b)" in caplog.text


def test_gauges_zero_for_onehot_tables_reason_a(caplog):
    cfg = tiny_dlrm(vocab_sizes=(146, 4000), embedding_impl="onehot")
    est = _estimator(PackedDLRM(cfg), optax.adagrad(1e-2), batch=256)
    x = np.zeros((256, cfg.dense_features + 2), np.float32)
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        est._init_state(x)
    assert _gauges() == (0, 0)
    assert "(a) its lookup is not a gather: dlrm/emb_0, dlrm/emb_1" in (
        caplog.text
    )


def test_gauges_zero_and_no_line_for_a_model_without_tables(caplog):
    from raydp_tpu.models.mlp import MLP

    metrics.gauge_set("train/rowsparse_tables", 9)
    est = _estimator(MLP(hidden=(8,), out_dim=1), optax.sgd(0.1))
    with caplog.at_level(logging.INFO, logger=rowsparse.__name__):
        est._init_state(np.zeros((BATCH, 5), np.float32))
    assert _gauges() == (0, 0)
    assert "row path" not in caplog.text


def test_negative_ids_wrap_as_in_the_dense_lookup():
    uids, inverse, n_real = rowsparse.dedup(
        jnp.asarray([-1, 4, 9, 4, -10]), 10
    )
    assert int(n_real) == 3
    assert uids.tolist() == [0, 4, 9, 12, 14]
    assert inverse.tolist() == [2, 1, 2, 1, 0]
