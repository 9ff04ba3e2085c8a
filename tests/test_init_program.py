"""The init program (PR 50): ``JAXEstimator._init_program`` takes the key
and the sample row as arguments, so its text is the same for every seed
and every dataset of one shape (a new seed finds it in jax's persistent
compile cache), and the state a seed gives is what ``model.init`` +
``TrainState.create`` give without the jit."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest

import raydp_tpu.dataframe as rdf
from raydp_tpu.data.ml_dataset import MLDataset
from raydp_tpu.models import (
    MLP,
    MoEClassifier,
    MoEConfig,
    SequenceClassifier,
    tiny_transformer,
)
from raydp_tpu.models.stats import STATS
from raydp_tpu.parallel import MeshSpec
from raydp_tpu.telemetry import recorder
from raydp_tpu.train import JAXEstimator
from raydp_tpu.train.estimator import TrainState
from raydp_tpu.utils import profiling
from raydp_tpu.utils.profiling import compile_records, metrics

SEQ, VOCAB = 16, 64
TOKENS = [f"t{i}" for i in range(SEQ)]


def dense(seed, **kw):
    return JAXEstimator(
        model=MLP(hidden=(8,), out_dim=1), loss="mse", batch_size=64,
        feature_columns=["a", "b"], label_column="y", seed=seed,
        epoch_mode="stream", **kw,
    )


def dense_frame(seed):
    rng = np.random.default_rng(seed)
    frame = pd.DataFrame({
        "a": rng.standard_normal(256), "b": rng.standard_normal(256),
    })
    frame["y"] = 2 * frame.a - 3 * frame.b
    return frame


def _encoder_cfg():
    return tiny_transformer(
        max_len=SEQ, vocab_size=VOCAB, dropout_rate=0.0, n_layers=2
    )


def routed(seed, **kw):
    """A classifier whose FFNs are routed layers: ``model.init`` sows the
    router's losses and its statistics, which are not parameters."""
    cfg = _encoder_cfg()
    moe = MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=4, top_k=1)
    return JAXEstimator(
        model=MoEClassifier(cfg=cfg, moe=moe, num_classes=2),
        optimizer=optax.adam(3e-4), loss="softmax_ce", batch_size=64,
        feature_columns=TOKENS, label_column="label",
        feature_dtype=np.int32, label_dtype=np.int32, aux_losses=True,
        seed=seed, epoch_mode="stream", **kw,
    )


def encoder(seed, **kw):
    """A dense classifier whose weights carry logical axes (``heads`` and
    ``mlp`` go to ``tp``)."""
    return JAXEstimator(
        model=SequenceClassifier(_encoder_cfg(), num_classes=2),
        optimizer=optax.adam(3e-4), loss="softmax_ce", batch_size=64,
        feature_columns=TOKENS, label_column="label",
        feature_dtype=np.int32, label_dtype=np.int32, seed=seed,
        epoch_mode="stream", **kw,
    )


def token_frame(seed):
    rng = np.random.default_rng(seed)
    frame = pd.DataFrame(
        rng.integers(0, VOCAB, size=(256, SEQ)), columns=TOKENS
    )
    frame["label"] = rng.integers(0, 2, size=256)
    return frame


CASES = {
    "dense": (dense, dense_frame),
    "routed": (routed, token_frame),
    "encoder": (encoder, token_frame),
}


def first_row(est, frame):
    return frame[list(est.feature_columns)].to_numpy(est.feature_dtype)[:1]


def dataset(frame):
    return MLDataset.from_df(rdf.from_pandas(frame, num_partitions=2), 1)


def counters():
    return dict(metrics.snapshot().get("counters", {}))


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent cache in a directory of the test's own, writing
    every program however short its compile; as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    wanted = {
        "jax_compilation_cache_dir": str(tmp_path),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    before = {name: getattr(jax.config, name) for name in wanted}
    for name, value in wanted.items():
        jax.config.update(name, value)
    cc.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def init_text(est, frame):
    rng = jax.random.PRNGKey(est.seed)
    sample = jnp.asarray(first_row(est, frame))
    init, _ = est._init_program(rng, sample)
    return init.lower(rng, sample).as_text()


@pytest.mark.parametrize("case", ["dense", "routed"])
def test_two_seeds_and_two_datasets_lower_one_init_program(case):
    build, make_frame = CASES[case]
    one, other = make_frame(11), make_frame(12)
    assert not np.array_equal(
        first_row(build(3), one), first_row(build(4), other)
    )
    assert init_text(build(3), one) == init_text(build(4), other)


@pytest.mark.parametrize("case", ["dense", "routed"])
def test_a_new_seed_finds_the_init_program_in_the_cache(case, cache_dir):
    build, make_frame = CASES[case]
    build(3).fit(dataset(make_frame(11)), num_epochs=1)
    assert any(cache_dir.iterdir())
    recorder.clear()
    profiling._compile_log.clear()
    before = counters()
    build(4).fit(dataset(make_frame(12)), num_epochs=1)
    (span,) = [s for s in recorder.retained()
               if s.name == "train/init_state"]
    assert span.attrs == {"seed": 4, "sharded": True}
    mine = [r for r in compile_records() if r["owner"] == "train/init_state"]
    program = max(mine, key=lambda r: r["trace_s"])
    assert program["fun_name"] == "jit(<lambda>)"
    assert [r["cache"] for r in mine] == ["hit"] * len(mine), mine
    after = counters()
    assert after.get("compile/cache_misses", 0) == before.get(
        "compile/cache_misses", 0
    )
    assert after["compile/cache_hits"] > before.get("compile/cache_hits", 0)


# ------------------------------------------------ the state a seed gives

def creator(est, sample):
    """``model.init`` + ``TrainState.create`` of the estimator's seed,
    less the collections ``init`` sows, boxed as flax returns it: a
    function of NO argument that closes over the key and the row. Called
    as it is, the ops run one by one; under ``jax.jit`` it is the program
    the estimator ran until PR 50."""
    rng = jax.random.PRNGKey(est.seed)
    sample = jnp.asarray(sample)

    def create():
        variables = est._model.init(rng, sample)
        variables = {k: v for k, v in variables.items()
                     if k not in ("losses", "intermediates", STATS)}
        return TrainState.create(
            apply_fn=est._model.apply, params=variables, tx=est._tx
        )

    return create


def assert_same_state(state, plain, maxulp):
    """Parameters and optimizer state (bit for bit where ``maxulp`` is
    0); the step count, a Python 0 until a jit returns it, by value."""
    assert int(state.step) == int(plain.step) == 0
    got_leaves, got_tree = jax.tree_util.tree_flatten(
        (state.params, state.opt_state)
    )
    want_leaves, want_tree = jax.tree_util.tree_flatten(
        nn.unbox((plain.params, plain.opt_state))
    )
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        if maxulp and g.dtype.kind == "f":
            np.testing.assert_array_max_ulp(g, w, maxulp=maxulp)
        else:
            assert g.tobytes() == w.tobytes()


# Against the closure's program every bit; against the ops run one by
# one a scaled normal draw (the embeddings) may round its product the
# other way, as it did before: XLA fuses the scale into the draw.
REFERENCES = {"closure_jit": (True, 0), "no_jit": (False, 1)}


@pytest.mark.parametrize("reference", list(REFERENCES))
@pytest.mark.parametrize("case", ["dense", "routed"])
def test_state_is_what_the_seed_gave(case, reference):
    build, make_frame = CASES[case]
    jitted, maxulp = REFERENCES[reference]
    est = build(5)
    sample = first_row(est, make_frame(11))
    est._init_state(sample)
    create = creator(est, sample)
    want = jax.jit(create)() if jitted else create()
    assert_same_state(est._state, want, maxulp)


@pytest.mark.parametrize("reference", list(REFERENCES))
@pytest.mark.parametrize("case", ["routed", "encoder"])
def test_sharded_state_on_a_mesh_is_what_it_was(
        case, reference, eight_cpu_devices):
    build, make_frame = CASES[case]
    jitted, maxulp = REFERENCES[reference]
    est = build(5, mesh=MeshSpec(dp=4, tp=2), shard_params=True)
    sample = first_row(est, make_frame(11))
    est._init_state(sample)
    create = creator(est, sample)
    # The shardings are the logical rules' on the abstract boxed state.
    want = jax.tree_util.tree_leaves(nn.logical_to_mesh_sharding(
        nn.get_partition_spec(jax.eval_shape(create)), est._mesh,
        est.logical_rules,
    ))
    got = jax.tree_util.tree_leaves(est._state)
    assert len(got) == len(want)
    assert any(not s.is_fully_replicated for s in want)
    for leaf, sharding in zip(got, want):
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
    assert_same_state(
        est._state, jax.jit(create)() if jitted else create(), maxulp
    )
