"""The ``laguna_xs_2`` configuration and its cell: the files load, the
widths are the source's and only the six cut keys differ, the traffic is
ISSUE 38's, the parameter, operation and byte counts agree with hand
counts, the new readers return nothing where the program has no such
scopes, the part rules split the cell's scopes, the gauges a built step
sets, and a tiny copy of the cell runs end to end on the CPU through
``run_cell``. Every entry of ``BENCHMARK.json`` is found by name."""
import importlib
import json
import os

import jax.numpy as jnp
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "laguna_xs_2.fit_s16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# The source's config.json as the catalog has it.
SOURCE = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads_per_layer", "num_experts", "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "shared_expert_intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "sliding_window", "rope_parameters",
          "partial_rotary_factor", "moe_routed_scaling_factor"]
NEW_METRICS = ["attention.window_ms", "attention.window_roofline"]
TINY = {
    "builder": "laguna_window_moe_lm", "model_type": "laguna",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_routed": 16,
    "first_expert": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    "layer_types": PERIOD + ["full_attention"],
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
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
    name, with the source's value unless it is one of the six cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_the_six_keys_differ(
    cell, real_bench
):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    # The first five entries of the three per-layer lists: the leading
    # dense layer, then one whole period, all routed.
    assert sizes["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert sizes[key] == SOURCE[key][:5]
    assert sizes["layer_types"][1:] == PERIOD[1:] + PERIOD[:1]
    assert (sizes["published"]["num_hidden_layers"],
            sizes["published"]["num_experts"],
            sizes["published"]["vocab_size"]) == (40, 256, 100352)
    # The router keeps its width and its experts a token; 32 are held.
    assert (sizes["num_experts"], sizes["num_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_tok"]) == (
        32, 256, 0, 8)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["vocab_size"] == 98 * 128
    assert sizes["deployment"]["chips_sharing_a_layer"] == 8
    # The floors of a model_config cut: leading dense layers once, four
    # layers after them, 8 experts, an eighth of the vocabulary.
    assert sizes["mlp_layer_types"].count("sparse") >= 4
    assert sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= SOURCE["vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = _named(real_bench["configs"], "laguna_xs_2")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/laguna_xs_2.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("gating", "window", "routing", "qk_norm", "rotary",
                "precision", "optimizer", "auxiliary_loss", "weights",
                "documents", "per_chip_batch", "attention_impl", "remat",
                "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}
    assert sizes["init"] == {"embedding_std": 1.0}


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 16384, "per_chip_batch": 1, "steps_per_epoch": 8,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 16384,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "laguna_xs_2",
                     "traffic": "fit_s16384", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    # Not ``moe.shared_ms``: the test PR 36 wrote holds that metric to
    # its own cell alone; the part ``moe_shared`` is in ``step.moe_ms``.
    assert {"step.moe_ms", "moe.permute_ms",
            "moe.grouped_matmul_roofline", "moe.load_max_over_mean",
            "attention.kernel_roofline", "step.attention_ms", "step.mlp_ms",
            "step.head_ms", "step.embed_ms", "step.update_ms",
            "step.rest_ms", "model.mfu", "step.device_ms",
            "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", *NEW_METRICS} == layers
    # One configuration, one cell: seven and nine with the older ones.
    assert len(real_bench["configs"]) >= 7
    assert len(real_bench["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) == 1


@pytest.mark.parametrize("name,unit,layer,better", [
    ("attention.window_ms", "ms", "model", "lower"),
    ("attention.window_roofline", "%", "kernel", "higher"),
])
def test_the_new_metrics_are_this_cells_alone(real_bench, name, unit, layer,
                                              better):
    metric = _named(real_bench["per_layer"], name)
    assert metric["workloads"] == [CELL]
    assert (metric["unit"], metric["layer"], metric["better"]) == (
        unit, layer, better)
    assert metric["moves"] == "train_samples_per_s"
    assert metric["source"] == "device_trace"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, fe, v, s, w = 2048, 8192, 512, 12544, 16384, 512
    full = d * (2 * 48 * 128 + 2 * 8 * 128 + 48)
    slide = d * (2 * 64 * 128 + 2 * 8 * 128 + 64)
    dense, expert, router = 3 * d * f, 3 * d * fe, d * 256
    # ISSUE 38: 29.46M, 37.88M, 50.33M, 3.15M, 0.52M.
    assert (full, slide, dense, expert, router) == (
        29_458_432, 37_879_808, 50_331_648, 3_145_728, 524_288)
    norms = 2 * d
    routed = router + 33 * expert + norms
    total = (full + dense + norms) + 3 * (slide + routed) + (
        full + routed) + 2 * v * d + d
    assert m.n_params(sizes) == total == 691_623_936      # ISSUE 38: 691.6M
    assert 16 * total == pytest.approx(11.07e9, rel=1e-3)
    # A whole routed layer is about 843M = 13.5 GB: eight chips share it.
    whole = slide + router + 257 * expert + norms
    assert whole == pytest.approx(847e6, rel=1e-2)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 4 * s * 8 * 32 / 256
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 65536
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    per_token = 2 * full + 3 * slide + dense + 4 * (router + expert) + d * v
    all_pairs, band_pairs = s * (s + 1) / 2, s * w - w * (w - 1) / 2
    # A sliding layer has a sixteenth of a full layer's pairs a head.
    assert all_pairs / band_pairs == pytest.approx(16.0, rel=0.02)
    attn = 2 * 256 * (2 * 48 * all_pairs + 3 * 64 * band_pairs)
    forward = 2 * (per_token * s + pairs * expert) + attn
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        49.34e12, rel=1e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 70000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 70000
        assert m.moe_flops_per_step(sizes, traffic, 1) == (
            3 * 70000 * 2 * expert)
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    # Pairs x heads x layers x 2 operations x (2 products of 128 forward,
    # 5 backward): the full layers' kernels and the sliding layers' apart.
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        2 * 48 * all_pairs * 2 * 7 * 128)
    assert m.window_attention_flops_per_step(
        sizes, traffic, 1) == pytest.approx(3 * 64 * band_pairs * 2 * 7 * 128)
    # ISSUE 38: 6.6 TFLOP forward in the full layers' kernels, 0.8 in the
    # sliding layers'.
    assert m.attention_flops_per_step(sizes, traffic, 1) / 3.5 == (
        pytest.approx(6.6e12, rel=0.01))
    assert m.window_attention_flops_per_step(sizes, traffic, 1) / 3.5 == (
        pytest.approx(0.81e12, rel=0.01))
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("attention", "window", "window", "window",
                         "attention")
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 4
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.head_dim) == (
        2048, 8192, 512, 128)
    assert (cfg.n_heads, cfg.kv_heads, cfg.rope_theta, cfg.rotary_dim) == (
        48, 8, 500000.0, 64)
    yarn = cfg.rope_yarn
    assert (yarn.factor, yarn.original_max_len, yarn.beta_fast,
            yarn.beta_slow) == (64.0, 4096, 64.0, 1.0)
    assert yarn.stretch == pytest.approx(1.41589, rel=1e-5)
    win = cfg.window
    assert (win.window, win.n_heads, win.rope_theta, win.rotary_dim) == (
        512, 64, 10000.0, 128)
    assert cfg.head_gate and not cfg.qk_norm and cfg.attention_scale is None
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (256, 32, 0, 8, 1)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", False, True, 2.5)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.positions == "rotary" and cfg.embed_init_std == 1.0
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 12544 and cfg.attention_impl == "flash"
    from raydp_tpu.models import laguna_xs_2
    assert laguna_xs_2(n_layers=1).embed_init_std == 0.02


@pytest.mark.parametrize("gauge,value", [
    ("attention/window_layers", 3), ("attention/window", 512),
    ("attention/flash_window_live_tiles", 63),
    ("attention/flash_window_masked_tiles", 63),
    ("attention/flash_live_tiles", 136), ("attention/flash_masked_tiles", 16),
    ("attention/latent_layers", 0), ("moe/experts_routed", 256),
    ("moe/experts_held", 32), ("moe/compact_rows", 24576),
    ("moe/shared_experts", 1),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone); the flash
    kernels' counts are ``tile_counts``'s own."""
    from raydp_tpu.models import latent, moe, window
    from raydp_tpu.utils.profiling import metrics

    # The package exports the function under the module's name.
    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")

    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    latent.report(model.cfg)
    window.report(model.cfg)
    flash_attention.report(model.cfg, seq_len=16384)
    moe.report(model, tokens_per_step=16384)
    assert metrics.gauge_value(gauge) == value
    assert (metrics.gauge_value("attention/flash_window_live_tiles"),
            metrics.gauge_value("attention/flash_window_masked_tiles")) == (
        flash_attention.tile_counts(16384, window=512))


def test_the_window_gauges_read_zero_for_the_other_models(bench_modules):
    from raydp_tpu.models import window
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    for name in ("olmoe_1b_7b.fit_s4096", "lfm2_8b_a1b.fit_s8192"):
        other = bench_modules["harness"].load_cell(REPO, name)
        cfg = other.model.model_config(other.sizes)
        window.report(cfg)
        flash_attention.report(cfg, seq_len=other.traffic["seq_len"])
        for gauge in ("attention/window_layers", "attention/window",
                      "attention/flash_window_live_tiles",
                      "attention/flash_window_masked_tiles"):
            assert metrics.gauge_value(gauge) == 0, (name, gauge)
        assert metrics.gauge_value("attention/flash_live_tiles") > 0


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``attn_window``."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    load = bench_modules["harness"].load_module
    for name, rules in (("attention.window_ms", "WINDOW"),
                        ("attention.window_roofline", "KERNELS")):
        reader = load(os.path.join(BENCH_DIR, "layers", name + ".py"))
        summary, _ = pt.reduce_profile(profile, getattr(reader, rules))
        assert not any(v for k, v in summary["parts_ms"].items()
                       if k != "rest")
    # The older reader still finds the ``attn`` kernels there.
    old = load(os.path.join(
        BENCH_DIR, "layers", "attention.kernel_roofline.py"))
    summary, _ = pt.reduce_profile(profile, old.KERNELS)
    assert summary["parts_ms"]["kernel"] > 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    for name in NEW_METRICS:
        reader = cell.part("layers", name)
        assert reader.read(dict(facts, cell=ghost)) is None


def test_the_kernel_readers_tell_the_two_kinds_of_layer_apart(bench_modules):
    """``attention.kernel_roofline`` reads the Pallas calls under ``attn``,
    ``attention.window_roofline`` those under ``attn_window``: neither
    pattern matches the other's scope."""
    import re

    load = bench_modules["harness"].load_module
    old = load(os.path.join(
        BENCH_DIR, "layers", "attention.kernel_roofline.py")).KERNELS
    new = load(os.path.join(
        BENCH_DIR, "layers", "attention.window_roofline.py")).KERNELS
    whole = load(os.path.join(
        BENCH_DIR, "layers", "attention.window_ms.py")).WINDOW
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    full = jvp + "block_4/attn/jit(flash_attention)/pallas_call"
    slide = jvp + "block_2/attn_window/jit(flash_attention)/pallas_call"
    proj = jvp + "block_2/attn_window/q/dot_general"
    assert re.search(old[0][0], full) and not re.search(old[0][0], slide)
    assert re.search(new[0][0], slide) and not re.search(new[0][0], full)
    assert not re.search(new[0][0], proj)
    assert re.search(whole[0][0], slide) and re.search(whole[0][0], proj)
    assert not re.search(whole[0][0], full)


def test_part_rules_partition_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "laguna_window_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "block_0/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_4/attn/q/dot_general": "attention",
        remat + "block_4/attn/gate/dot_general": "attention",
        jvp + "block_2/attn_window/jit(flash_attention)/pallas_call":
            "attention",
        back + "block_1/attn_window/kv/dot_general": "attention",
        remat + "block_3/attn_window/out/dot_general": "attention",
        jvp + "block_3/attn_window/mul": "attention",
        jvp + "block_0/ln_attn/mul": "attention",
        jvp + "block_3/moe/permute/sort": "moe_permute",
        back + "block_3/moe/unpermute/gather": "moe_permute",
        jvp + "block_2/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_4/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        jvp + "block_1/moe/shared/in/dot_general": "moe_shared",
        back + "block_2/moe/shared/out/dot_general": "moe_shared",
        remat + "block_4/moe/shared/mul": "moe_shared",
        jvp + "block_4/moe/experts/mul": "moe_rest",
        jvp + "block_4/moe/router/dot_general": "moe_rest",
        jvp + "block_4/ln_mlp/mul": "moe_rest",
        jvp + "block_0/ln_mlp/mul": "mlp",
        remat + "block_0/mlp_in/dot_general": "mlp",
        back + "block_0/mlp_out/dot_general": "mlp",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "embed", "attention", "moe_permute", "moe_gmm",
        "moe_shared", "moe_rest", "mlp", "head"}


@pytest.fixture(scope="module")
def laguna_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "laguna_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "laguna_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "laguna_tiny.fit", CELL, "laguna_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(bench_modules, laguna_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        laguna_tree, "laguna_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert metrics.gauge_value("attention/window_layers") == 3
    assert metrics.gauge_value("attention/window") == 8
    assert metrics.gauge_value("attention/flash_window_live_tiles") == 0
    assert metrics.gauge_value("moe/shared_experts") == 1
    assert metrics.gauge_value("moe/experts_routed") == 16
    assert metrics.gauge_value("moe/experts_held") == 4
    # 4 routed layers x 64 tokens x 2 experts a token, a step.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 4 * 64 * 2
    if trace:
        # No TPU plane here: the trace-read metrics are left out.
        assert not set(NEW_METRICS) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}


def test_flipped_reference_makes_the_run_incorrect(bench_modules,
                                                   laguna_tree):
    out = bench_modules["run"].run_cell(
        laguna_tree, "laguna_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True


@pytest.mark.parametrize("change", ["no_window", "float8_trunk"])
def test_a_departure_makes_the_run_incorrect(
        bench_modules, laguna_tree, monkeypatch, change):
    """Through the harness's own comparison: the same run with the
    reference asked to let the sliding layers see every earlier position,
    or with its trunk in float8 (the precision below the stated one),
    ends as ``correct`` false."""
    harness = bench_modules["harness"]
    load = harness.load_cell
    kwargs = {"no_window": {"depart": "no_window"},
              "float8_trunk": {"trunk": jnp.float8_e4m3fn}}[change]

    def with_another_reference(root, name):
        cell = load(root, name)
        reference = cell.model.reference_logits
        monkeypatch.setattr(
            cell.model, "reference_logits",
            lambda params, ids, sizes: reference(
                params, ids, sizes, **kwargs),
        )
        return cell

    monkeypatch.setattr(harness, "load_cell", with_another_reference)
    out = bench_modules["run"].run_cell(
        laguna_tree, "laguna_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu",
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
    detail = out["notes"]["reference_check"]
    assert detail["max_abs_err_over_max_abs_ref"] > detail["tolerance"]
