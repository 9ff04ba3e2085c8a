"""The ``mellum2_12b_a2_5b`` configuration and its four-chip cell: the
files load, every width is the source's and only depth and its two lists
are cut, the traffic is ISSUE 53's, the parameter, operation and byte
counts agree with hand counts, the three new readers return nothing where
the program has no exchange, the part rules split the cell's scopes (the
``shard_map``'s name among them), and a tiny copy of the cell runs end to
end on the CPU's forced devices over a ``dp=4`` mesh through ``run_cell``.
Every entry of ``BENCHMARK.json`` is found by name."""
import importlib
import json
import os
import re

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "mellum2_12b_a2_5b.fit_ep4_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": ROPE, "sliding_window": 1024,
    "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
}
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types"]
NEW_METRICS = ["moe.exchange_ms", "moe.exchange_gb_per_s",
               "moe.chip_load_max_over_mean"]
TINY = {
    "builder": "mellum2_window_moe_lm", "model_type": "mellum",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse", "sparse"],
    "max_position_embeddings": 256, "max_window_layers": 0,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": dict(
            ROPE["full_attention"], original_max_position_embeddings=16),
        "sliding_attention": ROPE["sliding_attention"]},
    "sliding_window": 8, "tie_word_embeddings": False, "vocab_size": 512,
    "use_sliding_window": True,
    "deployment": {"chips_sharing_a_layer": 4, "chips_here": 4, "axis": "dp",
                   "placement": "by_load_of_one_pass"},
    "attention_impl": "dense", "remat": True, "compute_dtype": "float32",
    "param_dtype": "float32", "init": {"embedding_std": 1.0},
    "optimizer": {"name": "adamw", "learning_rate": 2e-5,
                  "warmup_steps": 2000},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


def _named(entries, name):
    """The entry of a ``BENCHMARK.json`` list with this name (never by
    position: later PRs append)."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.mark.parametrize("key", sorted(SOURCE))
def test_every_source_key_is_kept_or_cut(cell, key):
    """Each key of the source's config.json is in the file under its own
    name, with the source's value unless it is depth or one of its lists."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_only_depth_is_cut_and_the_group_is_whole(cell, real_bench):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert sizes["num_hidden_layers"] == 4
    assert sizes["layer_types"] == PERIOD == SOURCE["layer_types"][:4]
    assert sizes["mlp_layer_types"] == ["sparse"] * 4
    # Nothing is held for an absent chip: all the experts, the whole
    # vocabulary, over the four chips that share each layer.
    assert (sizes["num_experts"], sizes["vocab_size"]) == (64, 98304)
    assert "num_experts_routed" not in sizes and "first_expert" not in sizes
    deployment = sizes["deployment"]
    assert (deployment["chips_sharing_a_layer"], deployment["chips_here"],
            deployment["axis"]) == (4, 4, "dp")
    # The experts are placed on the chips by load where the weights are
    # drawn, and the file says so and why.
    assert deployment["placement"].startswith("by_load_of_one_pass")
    assert sizes["published"]["num_hidden_layers"] == 28
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = _named(real_bench["configs"], "mellum2_12b_a2_5b")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/mellum2_12b_a2_5b.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("routing", "qk_norm", "layer_types", "intermediate_size",
                "window", "rotary", "mtp_head", "precision", "optimizer",
                "weights", "documents", "per_chip_batch", "attention_impl",
                "remat", "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}
    # Half of a per-expert fan's 8: the routed sum reaches the logits and
    # a flipped eighth choice stays under the tolerance (PERF.md section 6).
    assert sizes["init"] == {"embedding_std": 1.0, "expert_stack_gain": 4.0}


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 4 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 4096, "per_chip_batch": 1, "steps_per_epoch": 16,
        "epoch_mode": "stream", "mesh": {"dp": 4}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 4096,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "mellum2_12b_a2_5b",
                     "traffic": "fit_ep4_s4096", "chips": 4,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "attention.kernel_roofline",
            "attention.window_ms", "attention.window_roofline",
            "collective.exposed_share", "step.attention_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "step.device_ms", "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", *NEW_METRICS} == {
        n for n in layers if not n.startswith("setup.")}
    # Thirteen cells, two of them on four chips (three allowed).
    assert len(real_bench["workloads"]) >= 13
    four = [w["name"] for w in real_bench["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(real_bench["workloads"]) // 4


@pytest.mark.parametrize("name,unit,better,source", [
    ("moe.exchange_ms", "ms", "lower", "device_trace"),
    ("moe.exchange_gb_per_s", "GB/s", "higher", "device_trace"),
    ("moe.chip_load_max_over_mean", "x", "lower", "program_counter"),
])
def test_the_new_metrics(real_bench, name, unit, better, source):
    metric = _named(real_bench["per_layer"], name)
    assert CELL in metric["workloads"]
    assert (metric["unit"], metric["layer"], metric["better"],
            metric["source"]) == (unit, "model", better, source)
    assert metric["moves"] == "train_samples_per_s"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, fe, v, s, w = 2304, 896, 98304, 4096, 1024
    attention = d * 128 * (2 * 32 + 2 * 4)
    expert, router, norms = 3 * d * fe, d * 64, 2 * d
    # ISSUE 53: 21.23M, 6.193M, 0.147M; a whole layer 417.8M.
    assert (attention, expert, router) == (21_233_664, 6_193_152, 147_456)
    layer = attention + router + 64 * expert + norms
    assert layer == pytest.approx(417.8e6, rel=1e-3)
    assert m.n_params(sizes) == 4 * layer + 2 * v * d + d
    a_chip = 4 * (attention + router + 16 * expert + norms) + 2 * v * d // 4 + d
    # ISSUE 53: 595.2M parameters = 9.52 GB a chip with gradients.
    assert m.n_params_a_chip(sizes) == a_chip
    assert a_chip == pytest.approx(595.2e6, rel=1e-3)
    assert 16 * a_chip == pytest.approx(9.52e9, rel=2e-3)
    assert 28 * layer + 2 * v * d == pytest.approx(12.15e9, rel=1e-3)

    metrics.gauge_set("moe/first_chip_pairs_per_step", 0)
    pairs = 4 * s * 8          # a chip's share: its own tokens' worth
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    try:
        # The program counts the pairs chip 0's experts received: the
        # traced chip's own, not the mean chip's.
        metrics.gauge_set("moe/first_chip_pairs_per_step", 1.03 * pairs)
        assert m.moe_flops_per_step(sizes, traffic, 1) == pytest.approx(
            3 * 1.03 * pairs * 2 * expert)
    finally:
        metrics.gauge_set("moe/first_chip_pairs_per_step", 0)
    all_pairs, band_pairs = s * (s + 1) / 2, s * w - w * (w - 1) / 2
    # ISSUE 53: 3.67M pairs a head in a sliding layer, 8.39M in the full one.
    assert band_pairs == pytest.approx(3.67e6, rel=1e-3)
    assert all_pairs == pytest.approx(8.39e6, rel=1e-3)
    per_token = 4 * (attention + router + 8 * expert) + d * v
    # ISSUE 53: 510M active matrix parameters a token, the head 44%.
    assert per_token == pytest.approx(510e6, rel=2e-3)
    assert d * v / per_token == pytest.approx(0.44, abs=0.005)
    attn = 2 * 2 * 128 * 32 * (all_pairs + 3 * band_pairs)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        3 * (2 * per_token * s + attn))
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        32 * all_pairs * 2 * 7 * 128)
    assert m.window_attention_flops_per_step(
        sizes, traffic, 1) == pytest.approx(3 * 32 * band_pairs * 2 * 7 * 128)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * a_chip + 4 * s
    # ISSUE 53: about 0.34 GB a layer and chip RECEIVED over the three
    # passes; sent plus received is twice that.
    moved = m.exchange_bytes_per_step(sizes, traffic, 1)
    assert moved == 4 * 3 * (2 * 3 * s) * (2 * d * 2 + 8 * 8)
    assert moved / 4 / 2 == pytest.approx(0.34e9, rel=0.02)


def test_the_programs_count_is_the_builders(cell):
    """``moe/exchange_bytes_per_step`` (the program's gauge) and the
    builder's ``exchange_bytes_per_step`` are written down twice and
    agree."""
    from raydp_tpu.models import moe
    from raydp_tpu.utils.profiling import metrics

    class Stub:
        shape = {"dp": 4}

    cfg = cell.model.model_config(cell.sizes)
    import dataclasses

    cfg = dataclasses.replace(cfg, mesh=Stub(), state_axis="dp")
    model = type("M", (), {"cfg": cfg})()
    moe.report(model, tokens_per_step=4 * 4096)
    assert metrics.gauge_value("moe/exchange_bytes_per_step") == (
        cell.model.exchange_bytes_per_step(cell.sizes, cell.traffic, 1))
    assert metrics.gauge_value("moe/exchange_chips") == 4
    assert (metrics.gauge_value("moe/experts_routed"),
            metrics.gauge_value("moe/experts_held")) == (64, 16)
    # 1.5 x a quarter of 16,384 x 8 pairs.
    assert metrics.gauge_value("moe/compact_rows") == 49152


def test_builder_builds_the_published_block(cell):
    cfg = cell.model.model_config(cell.sizes)
    assert cfg.kinds == ("window", "window", "window", "attention")
    assert cfg.ffn_kinds == ("moe",) * 4
    assert (cfg.d_model, cfg.d_expert, cfg.head_dim, cfg.n_heads,
            cfg.kv_heads) == (2304, 896, 128, 32, 4)
    assert (cfg.window.window, cfg.window.n_heads, cfg.window.rope_theta) == (
        1024, 32, 500000.0)
    assert cfg.rope_yarn.factor == 16.0 and cfg.rope_yarn.stretch == (
        pytest.approx(1.2772588722239782))
    assert cfg.embed_init_std == 1.0 and cfg.remat and not cfg.qk_norm
    assert cfg.attention_impl == "flash" and cfg.vocab_size == 98304
    # Without a mesh the builder names no axis.
    assert cfg.state_axis is None


def test_new_readers_find_nothing_in_a_program_without_the_exchange(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``exchange``, the
    other builders have no ``exchange_bytes_per_step``, and the gauge was
    never set."""
    from raydp_tpu.utils.profiling import metrics

    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    reader = cell.part("layers", "moe.exchange_ms")
    summary, _ = pt.reduce_profile(profile, reader.EXCHANGE)
    assert not any(v for k, v in summary["parts_ms"].items() if k != "rest")
    facts = {"cell": cell, "per_chip_batch": 1}
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    for name in NEW_METRICS[:2]:
        assert cell.part("layers", name).read(dict(facts, cell=ghost)) is None
    olmoe = bench_modules["harness"].load_cell(REPO, "olmoe_1b_7b.fit_s4096")
    assert cell.part("layers", "moe.exchange_gb_per_s").read(
        dict(facts, cell=olmoe)) is None
    metrics.gauge_set("moe/chip_load_max_over_mean", 0)
    assert cell.part("layers", NEW_METRICS[2]).read(facts) is None


def test_part_rules_partition_the_cells_scopes(cell):
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "mellum2_window_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/shard_map/exchange/all_gather": "embed",
        jvp + "tok_embed/shard_map/gather": "embed",
        jvp + "block_3/attn/shard_map/attn/jit(flash_attention)/pallas_call":
            "attention",
        back + "block_0/attn_window/q/dot_general": "attention",
        remat + "block_1/attn_window/shard_map/attn_window/"
                "jit(flash_attention)/pallas_call": "attention",
        jvp + "block_0/ln_attn/mul": "attention",
        jvp + "block_2/moe/shard_map/exchange/gather/all_gather":
            "moe_exchange",
        back + "block_2/moe/shard_map/exchange/scatter/all_gather":
            "moe_exchange",
        remat + "block_1/moe/shard_map/exchange/scatter/reduce_scatter":
            "moe_exchange",
        jvp + "block_3/moe/shard_map/permute/sort": "moe_permute",
        back + "block_3/moe/shard_map/unpermute/gather": "moe_permute",
        jvp + "block_3/moe/permute/reduce_sum": "moe_permute",
        jvp + "block_2/moe/shard_map/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_0/moe/shard_map/experts/jit(tgmm)/pallas_call":
            "moe_gmm",
        jvp + "block_1/moe/shard_map/experts/mul": "moe_rest",
        jvp + "block_1/moe/router/dot_general": "moe_rest",
        jvp + "block_1/ln_mlp/mul": "moe_rest",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/gather/sharding_constraint":
            "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_max": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    # The accepted kernel readers find the kernels under the module's scope
    # opened again inside the ``shard_map``; the new reader the exchange.
    load = cell.part
    full = load("layers", "attention.kernel_roofline").KERNELS[0][0]
    slide = load("layers", "attention.window_roofline").KERNELS[0][0]
    paths = [p for p in want if "pallas_call" in p and "flash" in p]
    assert re.search(full, paths[0]) and not re.search(slide, paths[0])
    assert re.search(slide, paths[1]) and not re.search(full, paths[1])
    exchange = load("layers", "moe.exchange_ms").EXCHANGE[0][0]
    hits = [p for p in want if re.search(exchange, p)]
    assert len(hits) == 3 and all("moe" in p for p in hits)


@pytest.fixture(scope="module")
def mellum2_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "mellum2_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "mellum2_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "mellum2_tiny.fit", CELL, "mellum2_tiny", {
        "seq_len": 32, "per_chip_batch": 1, "steps_per_epoch": 2,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


def test_tiny_cell_runs_end_to_end_over_the_mesh(bench_modules, mellum2_tree):
    """A traced run; the untraced line is the next test's."""
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        mellum2_tree, "mellum2_tiny.fit", seed=3000000011, seconds=0.3,
        trace=1, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert line["device"]["count"] >= 4
    assert metrics.gauge_value("moe/exchange_chips") == 4
    assert metrics.gauge_value("moe/experts_held") == 4
    assert metrics.gauge_value("attention/window_layers") == 1
    # 2 routed layers x 4 chips x 32 tokens x 4 experts a token, a step.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 2 * 128 * 4
    assert metrics.gauge_value("moe/chip_load_max_over_mean") >= 1.0
    # Chip 0's pairs are its own count, within the fullest chip's.
    first = metrics.gauge_value("moe/first_chip_pairs_per_step")
    mean = 2 * 128 * 4 / 4
    assert 0 < first <= mean * metrics.gauge_value(
        "moe/chip_load_max_over_mean") + 1e-6
    # No TPU plane here: the trace-read metrics are left out, the gauge's
    # is there.
    assert "moe.chip_load_max_over_mean" in line["metrics"]
    assert not {"moe.exchange_ms", "moe.exchange_gb_per_s"} & set(
        line["metrics"])


def test_the_exchange_left_out_makes_the_run_incorrect(
        bench_modules, mellum2_tree, monkeypatch):
    """Through the harness's own comparison, at float32's agreement: the
    same run against a reference that sums chip 0's experts alone ends as
    ``correct`` false (the builder's tolerance is the chip's, for bf16;
    here it is held to the tiny program's own 1e-4)."""
    harness = bench_modules["harness"]
    load = harness.load_cell

    def departed(root, name):
        found = load(root, name)
        plain = found.model.reference_logits
        found.model.reference_logits = (
            lambda params, ids, sizes: plain(
                params, ids, sizes, depart="chip_0_experts"))
        found.model.TOLERANCE = 1e-4
        return found

    monkeypatch.setattr(harness, "load_cell", departed)
    out = bench_modules["run"].run_cell(
        mellum2_tree, "mellum2_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu",
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
    assert set(out["line"]["metrics"]) == {"train_samples_per_s", "setup_s"}
