"""The ``xing4_0_29b_a4b`` configuration and its cell: the files load, the
widths are the source's and only the five cut keys differ, the traffic is
ISSUE 36's, the parameter, operation and byte counts agree with hand
counts, the new readers return nothing where the program has no such
scopes, the part rules split the cell's scopes and a slice recorded on the
chip, the gauges a built step sets, and a tiny copy of the cell runs end
to end on the CPU through ``run_cell``."""
import importlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "xing4_0_29b_a4b.fit_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}
CUT = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
       "vocab_size", "num_nextn_predict_layers"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "n_shared_experts",
          "hc_mult", "hc_sinkhorn_iters", "rope_scaling"]
NEW_METRICS = ["step.hc_ms", "hc.mix_roofline", "attention.latent_proj_ms",
               "moe.shared_ms"]
TINY = {
    "builder": "xing4_latent_moe_lm", "model_type": "xing4_0",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "max_position_embeddings": 64,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "n_routed_experts": 2,
    "num_experts_routed": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2,
    "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
    "attention_impl": "dense", "remat": True, "compute_dtype": "float32",
    "param_dtype": "float32",
    "init": {"embedding_std": 1.0, "hc_phi_std": 0.5, "hc_bias_std": 1.0},
    "optimizer": {"name": "adamw", "learning_rate": 2e-5,
                  "warmup_steps": 2000},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


@pytest.mark.parametrize("key", sorted(SOURCE))
def test_every_source_key_is_kept_or_cut(cell, key):
    """Each key of the source's config.json is in the file under its own
    name, with the source's value unless it is one of the five cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key] == SOURCE[key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_the_five_keys_differ(
    cell, real_bench
):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    assert (sizes["num_hidden_layers"], sizes["first_k_dense_replace"],
            sizes["num_nextn_predict_layers"]) == (5, 1, 0)
    # The router keeps its width and its experts a token; 8 are held.
    assert (sizes["n_routed_experts"], sizes["num_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_tok"]) == (
        8, 64, 0, 4)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["deployment"]["chips_sharing_a_layer"] == 8
    # The floors of a model_config cut: leading dense layers once, four
    # layers after them, 8 experts, an eighth of the vocabulary.
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["n_routed_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= SOURCE["vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = next(c for c in real_bench["configs"]
                 if c["name"] == "xing4_0_29b_a4b")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/xing4_0_29b_a4b.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("streams", "hyper_connection_sites", "mappings",
                "mappings_init", "rotary", "e_score_correction_bias",
                "group_limited_routing", "precision", "optimizer", "weights",
                "per_chip_batch", "attention_impl", "remat", "projections"):
        assert sizes["assumed"][key], key


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 4096, "per_chip_batch": 1, "steps_per_epoch": 16,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 4096,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    entry = next(w for w in real_bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["why"] == cell.workload["why"]
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "attention.kernel_roofline",
            "step.attention_ms", "step.mlp_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "step.device_ms", "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", *NEW_METRICS} == layers


@pytest.mark.parametrize("name,unit,layer", [
    ("step.hc_ms", "ms", "model"), ("hc.mix_roofline", "%", "kernel"),
    ("attention.latent_proj_ms", "ms", "model"),
    ("moe.shared_ms", "ms", "model"),
])
def test_the_new_metrics_are_this_cells_alone(real_bench, name, unit, layer):
    metric = next(m for m in real_bench["per_layer"] if m["name"] == name)
    assert metric["workloads"] == [CELL]
    assert (metric["unit"], metric["layer"]) == (unit, layer)
    assert metric["moves"] == "train_samples_per_s"
    assert metric["source"] == "device_trace"
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, fe, v, s = 3584, 9216, 1024, 16384, 4096
    latent = (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256
              + 32 * 128 * d)
    dense, expert, router = 3 * d * f, 3 * d * fe, d * 64
    maps = 4 * d * 24
    assert (latent, dense, expert, router, 2 * maps) == (
        28_409_856, 99_090_432, 11_010_048, 229_376, 688_128)
    norms = 2 * d + 768 + 512
    hc = 2 * (maps + 3 + 24)
    layer_dense = latent + norms + hc + dense
    layer_routed = latent + norms + hc + router + 9 * expert
    # ISSUE 36: 128.20M a dense layer, 128.43M a routed one here.
    assert layer_dense == pytest.approx(128.20e6, rel=1e-4)
    assert layer_routed == pytest.approx(128.43e6, rel=1e-4)
    total = layer_dense + 4 * layer_routed + 2 * v * d + d
    assert m.n_params(sizes) == total == 759_346_190      # ISSUE 36: 759.3M
    assert 16 * total == pytest.approx(12.15e9, rel=1e-3)
    # A whole routed layer is 745.0M = 11.9 GB: eight chips share it.
    whole = latent + norms + hc + router + 65 * expert
    assert whole == pytest.approx(745.0e6, rel=1e-3)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 4 * s * 4 * 8 / 64
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 8192
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    per_token = (5 * latent + 10 * maps + dense + 4 * (router + expert)
                 + d * v)
    attn = 5 * 32 * 2 * (192 + 128) * s * (s + 1) / 2
    forward = 2 * (per_token * s + pairs * expert) + attn
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        11.68e12, rel=2e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 9000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 9000
        assert m.moe_flops_per_step(sizes, traffic, 1) == (
            3 * 9000 * 2 * expert)
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    # S(S+1)/2 pairs x 32 heads x 5 layers x 2 operations x (192 + 128
    # forward; 3 x 192 + 2 x 128 backward).
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        5 * 32 * (s * (s + 1) / 2) * 2 * (320 + 832))
    # pre reads 4 and writes 1, post reads 4 + 1 and writes 4: 14 x D
    # bf16 values a token and sublayer forward, three passes, 10 sublayers.
    assert m.hc_bytes_per_step(sizes, traffic, 1) == 3 * 10 * s * 14 * d * 2
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("latent",) * 5
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 4
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_heads) == (
        3584, 9216, 1024, 32)
    lat, hyper = cfg.latent, cfg.hyper
    assert (lat.q_rank, lat.kv_rank, lat.nope_dim, lat.rope_dim,
            lat.v_dim) == (768, 512, 128, 64, 128)
    assert (lat.yarn.factor, lat.yarn.original_max_len, lat.yarn.beta_fast,
            lat.yarn.beta_slow) == (64.0, 4096, 32.0, 1.0)
    assert lat.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                              rel=1e-4)
    assert (hyper.streams, hyper.sinkhorn_iters, hyper.eps, hyper.clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    # The widths of the draws are the configuration's, not the library's.
    assert sizes["init"] == {
        "embedding_std": 1.0, "hc_phi_std": 0.5, "hc_bias_std": 1.0}
    assert (cfg.embed_init_std, hyper.phi_std, hyper.bias_std) == (
        1.0, 0.5, 1.0)
    from raydp_tpu.models import xing4_0
    library = xing4_0(n_layers=1)
    assert (library.embed_init_std, library.hyper.phi_std,
            library.hyper.bias_std) == (0.02, 0.02, 0.0)
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (64, 8, 0, 4, 1)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", True, True, 2.0)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.positions == "rotary" and cfg.rope_theta == 10000.0
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 16384 and cfg.attention_impl == "flash"
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}


@pytest.mark.parametrize("gauge,value", [
    ("hc/streams", 4), ("hc/sinkhorn_iters", 20), ("hc/sublayers", 10),
    ("attention/latent_layers", 5), ("attention/kv_latent_rank", 512),
    ("attention/latent_cache_bytes_per_token", 5760),
    ("moe/shared_experts", 1),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone)."""
    from raydp_tpu.models import hyperconn, latent, moe
    from raydp_tpu.utils.profiling import metrics

    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    latent.report(model.cfg)
    hyperconn.report(model.cfg)
    moe.report(model, tokens_per_step=4096)
    assert metrics.gauge_value(gauge) == value


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with BERT's scopes has no ``hc_`` part."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "bert_base_fit_s128_parts.trace.json.gz"))
    with open(os.path.join(
            BENCH_DIR, "parts", "xing4_latent_moe_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert parts["hc_mix"] == parts["hc_maps"] == parts["hc_ends"] == 0
    assert parts["moe_shared"] == parts["moe_gmm"] == 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    for name in NEW_METRICS:
        reader = cell.part("layers", name)
        assert reader.read(dict(facts, cell=ghost)) is None


def test_part_rules_partition_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "xing4_latent_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "hc_expand/broadcast_in_dim": "hc_ends",
        jvp + "hc_reduce/reduce_sum": "hc_ends",
        jvp + "block_0/hc_attn/maps/dot_general": "hc_maps",
        remat + "block_3/hc_ffn/sinkhorn/div": "hc_maps",
        back + "block_2/hc_ffn/maps/bsd,dk->kbs/dot_general": "hc_maps",
        jvp + "block_1/hc_attn/pre/reduce_sum": "hc_mix",
        back + "block_4/hc_ffn/post/mul": "hc_mix",
        remat + "block_0/hc_ffn/pre/mul": "hc_mix",
        jvp + "block_2/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_4/attn/kv_up/dot_general": "attention",
        remat + "block_1/attn/q_norm/mul": "attention",
        jvp + "block_2/attn/rope/concatenate": "attention",
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
        "update", "embed", "hc_mix", "hc_maps", "hc_ends", "attention",
        "moe_permute", "moe_gmm", "moe_shared", "moe_rest", "mlp", "head"}


def test_part_rules_partition_the_recorded_slice(bench_modules):
    """One step of the cell recorded on the chip (PR 36): the parts sum
    to the step, every part has time in it, and what the four new metrics
    read is there."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "xing4_0_29b_a4b_fit_s4096_parts.trace.json.gz"
    ))
    with open(os.path.join(
            BENCH_DIR, "parts", "xing4_latent_moe_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert summary["steps"] == 1
    assert set(parts) == {
        "attention", "embed", "hc_ends", "hc_maps", "hc_mix", "head", "mlp",
        "moe_gmm", "moe_permute", "moe_rest", "moe_shared", "rest", "update"}
    assert sum(parts.values()) == pytest.approx(
        summary["step_device_ms"], rel=1e-6)
    assert all(parts[p] > 0 for p in parts)
    assert parts["rest"] < 0.10 * summary["step_device_ms"]
    hc = parts["hc_mix"] + parts["hc_maps"] + parts["hc_ends"]
    assert 0.1 < hc / summary["step_device_ms"] < 0.5
    assert parts["moe_shared"] < parts["moe_gmm"] + parts["moe_permute"] + (
        parts["moe_rest"])
    # The latent projections, by the second reduction the reader makes.
    reader = importlib.import_module("harness").load_module(os.path.join(
        BENCH_DIR, "layers", "attention.latent_proj_ms.py"))
    proj, _ = pt.reduce_profile(profile, reader.PROJECTIONS)
    assert 0 < proj["parts_ms"]["proj"] < parts["attention"]


@pytest.fixture(scope="module")
def xing4_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "xing4_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "xing4_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "xing4_tiny.fit", CELL, "xing4_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(bench_modules, xing4_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        xing4_tree, "xing4_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert metrics.gauge_value("hc/streams") == 4
    assert metrics.gauge_value("hc/sublayers") == 6
    assert metrics.gauge_value("attention/latent_layers") == 3
    assert metrics.gauge_value("moe/shared_experts") == 1
    assert metrics.gauge_value("moe/experts_routed") == 8
    assert metrics.gauge_value("moe/experts_held") == 2
    assert 0 <= metrics.gauge_value("hc/res_row_sum_err_max") < 0.2
    # 2 routed layers x 64 tokens x 2 experts a token, a step.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 2 * 64 * 2
    if trace:
        # No TPU plane here: the trace-read metrics are left out.
        assert not set(NEW_METRICS) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}


def test_flipped_reference_makes_the_run_incorrect(bench_modules, xing4_tree):
    out = bench_modules["run"].run_cell(
        xing4_tree, "xing4_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True


def test_a_float8_trunk_makes_the_run_incorrect(
        bench_modules, xing4_tree, monkeypatch):
    """The control PERF.md quotes from the chip, through the harness's own
    comparison: the same run with the reference's trunk in float8 (the
    precision below the stated one) ends as ``correct`` false."""
    harness = bench_modules["harness"]
    load = harness.load_cell

    def with_float8_reference(root, name):
        cell = load(root, name)
        reference = cell.model.reference_logits
        monkeypatch.setattr(
            cell.model, "reference_logits",
            lambda params, ids, sizes: reference(
                params, ids, sizes, trunk=jnp.float8_e4m3fn),
        )
        return cell

    monkeypatch.setattr(harness, "load_cell", with_float8_reference)
    out = bench_modules["run"].run_cell(
        xing4_tree, "xing4_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu",
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
    detail = out["notes"]["reference_check"]
    assert detail["max_abs_err_over_max_abs_ref"] > detail["tolerance"]

