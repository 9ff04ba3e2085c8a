"""The ``olmoe_1b_7b`` configuration and its cell: the files load, the
widths are the source's, the operation counts agree with hand counts, the
generator is a function of the seed, the part rules partition a slice
recorded on the chip, and a tiny copy of the cell runs end to end on the
CPU through ``run_cell``."""
import json
import os

import numpy as np
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "olmoe_1b_7b.fit_s4096"
# The catalog row's ``config`` (model-configs guide, architectures.jsonl).
SOURCE = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}
TINY = {
    "builder": "olmoe_causal_lm",
    "hidden_size": 64, "intermediate_size": 32,
    "max_position_embeddings": 32, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 512,
    "compute_dtype": "float32", "param_dtype": "float32",
    "attention_impl": "dense",
    "optimizer": {"name": "adamw", "learning_rate": 4e-4},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


def test_widths_are_the_sources_and_only_depth_is_cut(cell, real_bench):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == {"num_hidden_layers"} == set(sizes["reduced"])
    assert sizes["num_hidden_layers"] == 1
    entry = next(c for c in real_bench["configs"] if c["name"] == "olmoe_1b_7b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == sizes["source"]
    for key in ("precision", "optimizer", "aux_losses", "attention_impl",
                "grouped_matmul", "documents"):
        assert sizes["assumed"][key]


def test_traffic_is_the_issues(cell):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 4096, "per_chip_batch": 2, "steps_per_epoch": 16,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 4096,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"step.moe_ms", "step.head_ms", "moe.permute_ms",
            "moe.grouped_matmul_roofline", "moe.load_max_over_mean",
            "attention.kernel_roofline", "model.mfu", "train_step_roofline",
            "step.rest_ms", "device.peak_hbm_gib"} <= layers
    assert "step.mlp_ms" not in layers


def test_counts_against_hand_counts(cell):
    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, e, k, v, s = 2048, 1024, 64, 8, 50304, 4096
    layer = 4 * d * d + d * e + 3 * e * d * f + 4 * d   # two norms, q/k norm
    assert layer == 419_569_664
    assert m.n_params(sizes) == 2 * v * d + layer + d == 625_616_896
    per_token = 4 * d * d + d * e + k * 3 * d * f + d * v
    forward = 2 * per_token * s + 4 * d * s * (s + 1) / 2
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    assert 2 * m.flops_per_sample(sizes, traffic) == pytest.approx(
        8.78e12, rel=2e-3
    )
    # 65,536 rows that exist, three [2048, 1024] products, three passes.
    assert m.moe_flops_per_step(sizes, traffic, 2) == pytest.approx(
        3 * 65536 * 3 * 2 * d * f
    )
    # Causal pairs S(S+1)/2, not S^2: 2 + 5 matmuls of 2 x head_dim a pair.
    assert m.attention_flops_per_step(sizes, traffic, 2) == pytest.approx(
        2 * 16 * (s * (s + 1) / 2) * 7 * 2 * 128
    )
    assert m.bytes_per_step(sizes, traffic, 2) == pytest.approx(
        32 * 625_616_896 + 4 * 2 * s
    )


def test_generator_is_a_function_of_the_seed(cell):
    spec = cell.traffic["data"]
    a = cell.generate(spec, 3000000011, rows=8)
    b = cell.generate(spec, 3000000011, rows=8)
    c = cell.generate(spec, 3000000012, rows=8)
    assert sorted(a) == sorted(b) and len(a) == 4096 + 2
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    ids = np.stack([a[f"t{i}"] for i in range(4096)], axis=1)
    assert ids.shape == (10, 4096) and int(a["valid"].sum()) == 8
    assert not np.array_equal(ids, np.stack(
        [c[f"t{i}"] for i in range(4096)], axis=1))
    assert 0 <= ids.min() and ids.max() < 50304
    # Zipf(1.0): the first id is about 1 / H(50304) = 8.8% of the tokens;
    # one end-of-text id per document of median 600 tokens.
    assert 0.07 < (ids == 0).mean() < 0.11
    assert 0.0005 < (ids == 50279).mean() < 0.004


def test_part_rules_partition_the_recorded_slice(bench_modules):
    import importlib

    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"
    ))
    with open(os.path.join(BENCH_DIR, "parts", "olmoe_causal_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert summary["steps"] == 2
    assert set(parts) == {"attention", "embed", "head", "moe_gmm",
                          "moe_permute", "moe_rest", "rest", "update"}
    assert sum(parts.values()) == pytest.approx(
        summary["step_device_ms"], rel=1e-9
    )
    assert parts["rest"] < 0.10 * summary["step_device_ms"]
    moe = parts["moe_gmm"] + parts["moe_permute"] + parts["moe_rest"]
    assert all(parts[p] > 0 for p in parts)
    assert parts["moe_permute"] < 0.25 * moe


@pytest.fixture(scope="module")
def olmoe_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "olmoe_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "olmoe_tiny", "source": "test", "file": path, "reduced": [],
        "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "olmoe_tiny.fit", CELL, "olmoe_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(bench_modules, olmoe_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        olmoe_tree, "olmoe_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1 and detail["max_abs_err_over_max_abs_ref"] < 1e-4
    # Two routed layers, 2 x 32 tokens, top-2: every pair reached an expert.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 2 * 64 * 2
    assert np.isfinite(metrics.gauge_value("moe/aux_loss"))
    if trace:
        assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert "step.moe_ms" not in line["metrics"]   # no TPU plane here
    else:
        assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
