"""The ``kimi_linear_48b_a3b`` configuration and its cell: the files load,
the widths are the source's and only the four cut keys differ, the traffic
is ISSUE 44's, the parameter, operation and byte counts agree with hand
counts, the new readers return nothing where the program has no such
scopes, the part rules split the cell's scopes, the gauges a built step
sets, and a tiny copy of the cell runs end to end on the CPU through
``run_cell``. Every entry of ``BENCHMARK.json`` is found by name."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "kimi_linear_48b_a3b.fit_s16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
CUT = ["num_hidden_layers", "linear_attn_config", "num_experts",
       "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_token",
          "num_shared_experts", "routed_scaling_factor"]
NEW_METRICS = ["step.kda_ms", "kda.scan_ms", "kda.scan_roofline"]
TINY = {
    "builder": "kimi_delta_moe_lm", "model_type": "kimi_linear",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "head_dim": 16,
    "linear_attn_config": {
        "full_attn_layers": [2], "kda_layers": [1, 3], "head_dim": 16,
        "num_heads": 4, "short_conv_kernel_size": 4},
    "kda": {"gate_rank": 8, "chunk": 16}, "mla_use_nope": True,
    "model_max_length": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts": 4, "num_experts_routed": 16, "first_expert": 4,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "moe_layer_freq": 1,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0},
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
    name, with the source's value unless it is one of the four cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key] == SOURCE[key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_the_four_keys_differ(
    cell, real_bench
):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    # Layer 1 (KDA, dense) and one whole period after it, all routed, in
    # the published order; the mixer's own sizes are the source's.
    lin, published = sizes["linear_attn_config"], SOURCE["linear_attn_config"]
    assert sizes["num_hidden_layers"] == 5
    assert lin["kda_layers"] == published["kda_layers"][:4] == [1, 2, 3, 5]
    assert lin["full_attn_layers"] == published["full_attn_layers"][:1]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == published[key]
    # The router keeps its width and its experts a token; 8 are held.
    assert (sizes["num_experts"], sizes["num_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_token"]) == (
        8, 256, 0, 8)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["deployment"]["chips_sharing_a_layer"] == 32
    assert sizes["kda"] == {"gate_rank": 128, "chunk": 64}
    # The floors of a model_config cut: leading dense layers once, four
    # layers after them, 8 experts, an eighth of the vocabulary.
    assert sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] >= 4
    assert sizes["num_experts"] >= 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = _named(real_bench["configs"], "kimi_linear_48b_a3b")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/kimi_linear_48b_a3b.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("kda_parameterisation", "kda_chunk", "mla_use_nope",
                "head_dim", "q_lora_rank", "group_limited_routing",
                "e_score_correction_bias", "routing", "precision",
                "optimizer", "auxiliary_loss", "weights", "documents",
                "per_chip_batch", "attention_impl", "remat", "projections"):
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
    # Laguna's traffic over this model's slice.
    with open(os.path.join(
            BENCH_DIR, "workloads", "laguna_xs_2.fit_s16384.json")) as f:
        assert json.load(f)["traffic"] == cell.traffic
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "kimi_linear_48b_a3b",
                     "traffic": "fit_s16384", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    # Not ``moe.shared_ms`` nor ``attention.latent_proj_ms``: the test PR
    # 36 wrote holds those to its own cell alone; the parts ``moe_shared``
    # and ``attention`` hold their time.
    assert {"step.moe_ms", "moe.permute_ms",
            "moe.grouped_matmul_roofline", "moe.load_max_over_mean",
            "attention.kernel_roofline", "step.attention_ms", "step.mlp_ms",
            "step.head_ms", "step.embed_ms", "step.update_ms",
            "step.rest_ms", "model.mfu", "step.device_ms",
            "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", *NEW_METRICS} == layers
    # One configuration, one cell, three metrics: eight, ten and fifty.
    assert len(real_bench["configs"]) >= 8
    assert len(real_bench["workloads"]) >= 10
    assert len(real_bench["per_layer"]) >= 50
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) == 1


@pytest.mark.parametrize("name,unit,layer,better", [
    ("step.kda_ms", "ms", "model", "lower"),
    ("kda.scan_ms", "ms", "model", "lower"),
    ("kda.scan_roofline", "%", "kernel", "higher"),
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
    d, f, fe, v, s = 2304, 9216, 1024, 20480, 16384
    wide, rank = 32 * 128, 128
    kda = (3 * d * wide + 2 * (d * rank + rank * wide) + d * 32 + wide * d)
    kda_vectors = 3 * 4 * wide + 32 + 2 * wide + 128
    latent = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 + 32 * 128 * d
    dense, expert, router = 3 * d * f, 3 * d * fe, d * 256
    # ISSUE 44: 39.52M, 29.11M, 63.70M, 7.078M, 0.59M.
    assert (kda + kda_vectors, latent + 512, dense, expert, router) == (
        39_518_368, 29_114_880, 63_700_992, 7_077_888, 589_824)
    norms = 2 * d
    routed = router + 9 * expert + norms
    total = ((kda + kda_vectors + dense + norms)
             + 3 * (kda + kda_vectors + routed) + (latent + 512 + routed)
             + 2 * v * d + d)
    assert m.n_params(sizes) == total == 602_449_792      # ISSUE 44: 602.4M
    assert 16 * total == pytest.approx(9.64e9, rel=1e-3)
    # A whole routed KDA layer is 1,859M = 29.7 GB: thirty-two chips share.
    whole = kda + kda_vectors + router + 257 * expert + norms
    assert whole == pytest.approx(1859e6, rel=1e-3)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 4 * s * 8 * 8 / 256
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 16384
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    per_token = (4 * kda + latent + dense + 4 * (router + expert) + d * v)
    all_pairs = s * (s + 1) / 2
    attn = 2 * (192 + 128) * 32 * all_pairs
    scan = 4 * s * 32 * 7 * 128 * 128
    forward = 2 * (per_token * s + pairs * expert) + attn + scan
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        41.96e12, rel=1e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 20000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 20000
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    # The latent layer's kernels: pairs x heads x 2 operations x (192 +
    # 128 forward, 3 x 192 + 2 x 128 backward); ISSUE 44: 2.75 TFLOP
    # forward, about 9.6 in all.
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        32 * all_pairs * 2 * (320 + 832))
    assert 32 * all_pairs * 2 * 320 == pytest.approx(2.75e12, rel=1e-2)
    # The delta rule: three products of 2 d_k d_v and the decay a token
    # and head, backward twice the forward; q, k, v, o in bf16, g and beta
    # in float32, values and gradients once each.
    assert m.kda_flops_per_step(sizes, traffic, 1) == 3 * scan
    assert m.kda_bytes_per_step(sizes, traffic, 1) == (
        2 * 4 * s * (4 * wide * 2 + wide * 4 + 32 * 4))
    # Bound by bytes on a v5e: 7.9 ms against 3.7.
    assert m.kda_bytes_per_step(sizes, traffic, 1) / 819e9 > (
        m.kda_flops_per_step(sizes, traffic, 1) / 197e12)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("kda", "kda", "kda", "latent", "kda")
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 4
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_heads) == (
        2304, 9216, 1024, 32)
    kda = cfg.kda
    assert (kda.heads, kda.key_dim, kda.value_dim, kda.conv_taps,
            kda.gate_rank, kda.chunk) == (32, 128, 128, 4, 128, 64)
    lat = cfg.latent
    assert (lat.q_rank, lat.kv_rank, lat.nope_dim, lat.rope_dim, lat.v_dim,
            lat.yarn) == (None, 512, 128, 64, 128, None)
    assert lat.softmax_scale == 192 ** -0.5
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (256, 8, 0, 8, 1)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", True, True, 2.446)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.positions == "none" and cfg.embed_init_std == 1.0
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 20480 and cfg.attention_impl == "flash"
    from raydp_tpu.models import kimi_linear_48b_a3b
    whole = kimi_linear_48b_a3b()
    assert whole.kinds.count("kda") == 20 and whole.kinds.count("latent") == 7
    assert [i + 1 for i, k in enumerate(whole.kinds) if k == "latent"] == (
        SOURCE["linear_attn_config"]["full_attn_layers"])
    assert whole.ffn_kinds == ("swiglu",) + ("moe",) * 26


@pytest.mark.parametrize("gauge,value", [
    ("kda/layers", 4), ("kda/heads", 32), ("kda/chunk", 64),
    ("kda/chunks_per_step", 1024),
    ("kda/state_bytes_per_sequence", 4 * 32 * 128 * 128 * 4),
    ("latent/rotary_dims", 0), ("attention/latent_layers", 1),
    ("attention/kv_latent_rank", 512),
    ("attention/flash_live_tiles", 136), ("attention/flash_masked_tiles", 16),
    ("attention/flash_fused_bwd_layers", 1),
    ("moe/experts_routed", 256), ("moe/experts_held", 8),
    ("moe/compact_rows", 6144), ("moe/shared_experts", 1),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone). The MoE
    gauges read 256, 8, 6,144 and 1 without a line of ``models/moe.py``
    changed; the backward at 16k x 192 is the one kernel."""
    from raydp_tpu.models import kda, latent, moe
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    kda.report(model.cfg, tokens_per_step=16384)
    latent.report(model.cfg)
    flash_attention.report(model.cfg, seq_len=16384)
    moe.report(model, tokens_per_step=16384)
    assert metrics.gauge_value(gauge) == value


def test_the_kda_gauges_read_zero_for_the_other_models(bench_modules):
    from raydp_tpu.models import kda, latent
    from raydp_tpu.utils.profiling import metrics

    for name, rotated in (("xing4_0_29b_a4b.fit_s4096", 64),
                          ("granite_4_0_h_micro.fit_s4096", 0)):
        other = bench_modules["harness"].load_cell(REPO, name)
        cfg = other.model.model_config(other.sizes)
        kda.report(cfg, tokens_per_step=other.traffic["seq_len"])
        latent.report(cfg)
        for gauge in ("kda/layers", "kda/heads", "kda/chunk",
                      "kda/chunks_per_step", "kda/state_bytes_per_sequence"):
            assert metrics.gauge_value(gauge) == 0, (name, gauge)
        assert metrics.gauge_value("latent/rotary_dims") == rotated


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell, monkeypatch
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``kda`` part."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    with open(os.path.join(
            BENCH_DIR, "parts", "kimi_delta_moe_lm.json")) as f:
        summary, _ = pt.reduce_profile(profile, json.load(f))
    assert not any(v for k, v in summary["parts_ms"].items()
                   if k.startswith("kda_"))
    assert summary["parts_ms"]["attention"] > 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    parts = {"attention": 3.0, "head": 2.0}
    monkeypatch.setattr(pt, "summary", lambda facts: {"parts_ms": parts})
    for name in NEW_METRICS:
        assert cell.part("layers", name).read(facts) is None
    # With the parts there, the three read them.
    parts.update(kda_scan=80.0, kda_conv_gate=15.0, kda_proj=60.0)
    assert cell.part("layers", "step.kda_ms").read(facts) == 155.0
    assert cell.part("layers", "kda.scan_ms").read(facts) == 95.0
    share = cell.part("layers", "kda.scan_roofline").read(facts)
    least_ms = cell.model.kda_bytes_per_step(
        cell.sizes, cell.traffic, 1) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 80.0)
    assert 0 < share < 100


def test_part_rules_partition_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "kimi_delta_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "block_0/kda/scan/while/body/dot_general": "kda_scan",
        back + "block_4/kda/scan/transpose/cumsum": "kda_scan",
        jvp + "block_1/kda/conv/q/mul": "kda_conv_gate",
        remat + "block_2/kda/conv/rsqrt": "kda_conv_gate",
        back + "block_0/kda/decay/softplus": "kda_conv_gate",
        jvp + "block_0/kda/beta/logistic": "kda_conv_gate",
        back + "block_4/kda/gate_norm/mul": "kda_conv_gate",
        jvp + "block_1/kda/q_proj/dot_general": "kda_proj",
        remat + "block_1/kda/f_down/dot_general": "kda_proj",
        back + "block_2/kda/g_up/dot_general": "kda_proj",
        back + "block_2/kda/out/dot_general": "kda_proj",
        jvp + "block_2/ln_kda/mul": "kda_proj",
        jvp + "block_3/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_3/attn/q_up/dot_general": "attention",
        remat + "block_3/attn/kv_norm/mul": "attention",
        jvp + "block_3/ln_attn/mul": "attention",
        jvp + "block_3/moe/permute/sort": "moe_permute",
        back + "block_3/moe/unpermute/gather": "moe_permute",
        jvp + "block_2/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_4/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        jvp + "block_1/moe/shared/in/dot_general": "moe_shared",
        jvp + "block_4/moe/router/dot_general": "moe_rest",
        jvp + "block_4/ln_mlp/mul": "moe_rest",
        jvp + "block_0/ln_mlp/mul": "mlp",
        remat + "block_0/mlp_in/dot_general": "mlp",
        back + "block_0/mlp_out/dot_general": "mlp",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "embed", "kda_scan", "kda_conv_gate", "kda_proj",
        "attention", "moe_permute", "moe_gmm", "moe_shared", "moe_rest",
        "mlp", "head"}


@pytest.fixture(scope="module")
def kimi_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "kimi_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "kimi_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "kimi_tiny.fit", CELL, "kimi_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.fixture(scope="module")
def tiny_run(bench_modules, kimi_tree):
    """ONE traced run of the tiny cell (its step takes most of a minute
    to trace and compile on the CPU; the tests below read it)."""
    return bench_modules["run"].run_cell(
        kimi_tree, "kimi_tiny.fit", seed=3000000019, seconds=0.5,
        trace=1, platform="cpu",
    )


def test_tiny_cell_runs_end_to_end(tiny_run):
    line = tiny_run["line"]
    assert line["correct"] is True, tiny_run["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert tiny_run["notes"]["checks"]["losses_finite"] is True
    # A traced run's line holds the per-layer metrics; no TPU plane here,
    # so those read from a device trace are left out.
    assert "infeed.put_share" in line["metrics"]
    assert not set(NEW_METRICS) & set(line["metrics"])


def test_tiny_cell_agrees_with_the_token_by_token_reference(tiny_run):
    detail = tiny_run["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert detail["tolerance"] == 0.02


@pytest.mark.parametrize("gauge,value", [
    ("kda/layers", 2), ("kda/heads", 4), ("kda/chunk", 16),
    ("kda/chunks_per_step", 2 * 4),
    ("kda/state_bytes_per_sequence", 2 * 4 * 16 * 16 * 4),
    ("latent/rotary_dims", 0), ("attention/latent_layers", 1),
    ("moe/shared_experts", 1), ("moe/experts_routed", 16),
    ("moe/experts_held", 4),
    # 2 routed layers x 64 tokens x 2 experts a token, a step.
    ("moe/expert_tokens_per_step", 2 * 64 * 2),
])
def test_the_gauges_of_the_tiny_run(tiny_run, gauge, value):
    from raydp_tpu.utils.profiling import metrics

    assert metrics.gauge_value(gauge) == value
