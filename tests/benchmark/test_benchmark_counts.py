"""The operation and byte functions against hand counts, and the plain
references against the program's models at tiny presets."""
import json
import os

import numpy as np
import pytest

from bench_tree import BENCH_DIR, TINY_CONFIGS


def _config(bench_modules, name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        sizes = json.load(f)
    model = bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "configs", sizes["builder"] + ".py")
    )
    return sizes, model


def test_bert_base_flops_per_sample(bench_modules):
    sizes, model = _config(bench_modules, "bert_base")
    traffic = {"seq_len": 128}
    per_token = 12 * (4 * 768 * 768 + 2 * 768 * 3072)
    assert per_token == 84_934_656
    attention = 12 * 4 * 128 * 128 * 768
    by_hand = 3 * (2 * per_token * 128 + 2 * (768 * 768 + 768 * 2) + attention)
    assert model.flops_per_sample(sizes, traffic) == by_hand
    # ROADMAP S5 counts the pooler on every token: 6.74e10. Same to 1%.
    assert by_hand == pytest.approx(6.74e10, rel=0.01)
    # The embedding gather is not in it: a larger vocabulary changes nothing.
    assert model.flops_per_sample(
        dict(sizes, vocab_size=10 * sizes["vocab_size"]), traffic
    ) == by_hand


def test_bert_base_parameters_and_bytes(bench_modules):
    sizes, model = _config(bench_modules, "bert_base")
    n = model.n_params(sizes)
    assert n == pytest.approx(109.1e6, rel=0.01)
    assert model.bytes_per_step(sizes, {"seq_len": 128}, 128) == (
        32.0 * n + 4 * 128 * 128
    )


def test_dlrm_kaggle_flops_and_bytes(bench_modules):
    sizes, model = _config(bench_modules, "dlrm_kaggle")
    bottom = 13 * 512 + 512 * 256 + 256 * 64 + 64 * 16
    top = (16 + 351) * 512 + 512 * 256 + 256 * 1
    assert model.flops_per_sample(sizes, {}) == 6 * (bottom + top + 351 * 16)
    rows = 4096 * 26
    mlp = bottom + (512 + 256 + 64 + 16) + top + (512 + 256 + 1)
    by_hand = 4 * rows * 64 + 16 * mlp + 4 * 4096 * 40
    assert model.bytes_per_step(sizes, {}, 4096) == by_hand
    # The rows a batch touches, not the tables: 35 MB, not 2.16 GB.
    assert by_hand < 40e6


@pytest.mark.parametrize("name,traffic", [
    ("bert_tiny", {"seq_len": 16}), ("dlrm_tiny", {}),
])
def test_reference_agrees_with_the_programs_model(bench_modules, name, traffic):
    """In float32 the plain reference and the flax module are the same
    arithmetic: they agree to rounding, far inside the chip tolerance."""
    import flax.linen as nn
    import jax

    sizes = dict(TINY_CONFIGS[name], compute_dtype="float32")
    model = bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "configs", sizes["builder"] + ".py")
    )
    module = model.estimator_kwargs(sizes, traffic, None)["model"]
    x = model.check_batch(sizes, traffic, seed=5)
    params = nn.unbox(module.init(jax.random.PRNGKey(1), x[:1]))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply(params, x))
    want = np.asarray(model.reference_logits(params, x, sizes))
    assert got.shape == want.shape
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) / scale < 1e-4
    # ... and a reference that disagrees is seen: one block's output
    # projection zeroed moves the logits past the chip tolerance.
    broken = jax.tree_util.tree_map(lambda a: a, params)
    inner = broken["params"]
    key = "encoder" if "encoder" in inner else "dlrm"
    first = sorted(inner[key])[0]
    inner[key][first] = jax.tree_util.tree_map(
        lambda a: a * 0 + 1.0, inner[key][first]
    )
    moved = np.asarray(model.reference_logits(broken, x, sizes))
    assert np.max(np.abs(got - moved)) / scale > model.TOLERANCE


def _generator(bench_modules, name):
    return bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "generators", name + ".py")
    )


def test_generators_repeat_from_the_seed(bench_modules):
    criteo = _generator(bench_modules, "criteo")
    sizes = TINY_CONFIGS["dlrm_tiny"]
    import pandas as pd

    a, b, c = (pd.DataFrame(criteo.generate(seed, sizes, rows=300, form="raw"))
               for seed in (7, 7, 8))
    assert a.equals(b) and not a.equals(c)
    final = pd.DataFrame(criteo.generate(7, sizes, rows=300, form="final"))
    for t, size in enumerate(sizes["vocab_sizes"]):
        ids = final[f"C{t}"].to_numpy()
        assert ids.min() >= 0 and ids.max() < size
        assert np.array_equal(ids, ids.astype(np.int64))
    assert not final.isna().any().any() and a["I0"].isna().any()
    ranks = criteo.zipf_ranks(np.random.default_rng(0), 20000, 1000, 1.1)
    counts = np.bincount(ranks, minlength=1000)
    assert counts[0] > counts[9] > counts[99] > 0 and ranks.max() < 1000


def test_etl_reference_against_the_engine_on_2000_rows(bench_modules):
    import raydp_tpu
    import raydp_tpu.dataframe as rdf

    etl = bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "etl_criteo.py")
    )
    sizes = dict(TINY_CONFIGS["dlrm_tiny"], vocab_sizes=[50, 200, 3, 1000, 40])
    import pandas as pd

    raw = pd.DataFrame(_generator(bench_modules, "criteo").generate(
        11, sizes, rows=2000, form="raw"
    ))
    want = etl.reference_transform(raw, sizes, 3)
    raydp_tpu.init(app_name="bench-etl-test", num_workers=2)
    try:
        out = etl.engine_transform(
            rdf.from_pandas(raw, num_partitions=4), sizes, 3
        ).to_pandas()
    finally:
        raydp_tpu.stop()
    ok, detail = etl.compare(out, want, sizes)
    assert ok, detail
    assert (want["C3"] == 0).any() and want["C3"].max() > 0
    # A table whose every value survives stays inside the table (the
    # example's len(keep) + 1 would reach ``size``).
    for t, size in enumerate(sizes["vocab_sizes"]):
        assert out[f"C{t}"].max() < size
    spoiled = out.copy()
    spoiled.loc[0, "C1"] += 1
    assert not etl.compare(spoiled, want, sizes)[0]
    spoiled = out.copy()
    spoiled.loc[0, "I0"] += 1e-4
    assert not etl.compare(spoiled, want, sizes)[0]
