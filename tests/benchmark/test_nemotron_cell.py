"""The ``nemotron_3_nano_30b_a3b`` configuration and its cell: the files
load, the widths are the source's and only the four cut keys differ, the
traffic is the other ``fit_s16384`` cells', the parameter, operation and
byte counts agree with hand counts, the new reader returns nothing where
the program has no such scopes, the part rules claim every scope of a
traced tiny step and tell the kinds of layer apart, the gauges a built
step sets, and a tiny copy of the cell runs end to end on the CPU through
``run_cell``. Every entry of ``BENCHMARK.json`` is found BY NAME and the
sets of metrics are held by ``<=``: later PRs append."""
import importlib
import json
import os
import re

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "nemotron_3_nano_30b_a3b.fit_s16384"
CONFIG = "nemotron_3_nano_30b_a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
CUT = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
       "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "moe_shared_expert_intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "mamba_num_heads",
          "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
          "chunk_size", "expand", "num_experts_per_tok", "n_shared_experts",
          "routed_scaling_factor"]
NEW_METRIC = "ssm.proj_ms"
REPORTED = {
    "step.ssm_ms", "ssm.scan_ms", "ssm.scan_roofline", NEW_METRIC,
    "step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
    "moe.load_max_over_mean", "step.attention_ms",
    "attention.kernel_roofline", "step.head_ms", "step.embed_ms",
    "step.update_ms", "step.rest_ms", "model.mfu", "train_step_roofline",
    "step.device_ms", "step.dispatch_share", "device.peak_hbm_gib",
    "device.idle_share", "device.idle_unattributed_share",
    "infeed.wait_share", "infeed.put_share", "setup.ready_s",
    "setup.init_state_s", "setup.step_program_s", "setup.trace_lower_s",
    "setup.backend_compile_s", "setup.cache_load_s",
    "setup.cache_miss_programs", "setup.unaccounted_s",
}
TINY = {
    "builder": "nemotron_hybrid_moe_lm", "model_type": "nemotron_h",
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "ME*ME", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 4,
    "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "n_routed_experts": 4, "n_experts_routed": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False,
    "use_bias": False, "use_conv_bias": True, "tie_word_embeddings": False,
    "sliding_window": None, "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5,
    "rope_theta": 10000, "max_position_embeddings": 256,
    "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0, "depth_scaled_outputs": 5},
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
    # The model's own first nine layers, in the published order.
    assert sizes["num_hidden_layers"] == 9
    assert sizes["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6)
    # The router keeps its width and its experts a token; 8 are held.
    assert (sizes["n_routed_experts"], sizes["n_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_tok"]) == (
        8, 128, 0, 6)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["deployment"]["chips_sharing_a_layer"] == 16
    # The floors of a model_config cut: a whole period, four layers after
    # the leading dense ones (there are none), 8 experts, an eighth of the
    # vocabulary.
    kept = sizes["hybrid_override_pattern"]
    assert set(kept) == set(PATTERN) and len(kept) >= 4
    assert sizes["n_routed_experts"] >= 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = _named(real_bench["configs"], CONFIG)
    assert entry["reduced"] == CUT
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == sizes["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("no_rotation_in_attention", "grouped_gate_norm", "expand",
                "gate_sum_epsilon", "time_step_limit",
                "group_limited_routing", "e_score_correction_bias", "init",
                "chunk", "precision", "optimizer", "auxiliary_loss",
                "documents", "per_chip_batch", "attention_impl", "remat",
                "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    for key in ("this_chip", "stages", "exchange"):
        assert len(sizes["deployment"][key]) > 20, key
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}
    assert sizes["init"] == {"embedding_std": 1.0, "depth_scaled_outputs": 9}
    assert (sizes["compute_dtype"], sizes["param_dtype"], sizes["remat"]) == (
        "bfloat16", "float32", True)


def test_the_traffic_is_the_other_16k_cells(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 16384, "per_chip_batch": 1, "steps_per_epoch": 8,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 16384,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    for other in ("kimi_linear_48b_a3b", "laguna_xs_2",
                  "keye_vl_2_0_30b_a3b"):
        with open(os.path.join(
                BENCH_DIR, "workloads", other + ".fit_s16384.json")) as f:
            assert json.load(f)["traffic"] == cell.traffic, other
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "fit_s16384", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    assert REPORTED <= {m["name"] for m in cell.per_layer()}
    # Not ``moe.shared_ms``: the test PR 36 wrote holds it to its own cell
    # alone (``==``); the part ``moe_shared`` holds the shared expert's time.
    # Nothing of another family's layers is reported here.
    assert not {m["name"] for m in cell.per_layer()} & {
        "step.mlp_ms", "step.kda_ms", "step.conv_ms", "step.hc_ms",
        "moe.exchange_ms", "collective.exposed_share"}


def test_the_new_metric_splits_the_state_space_layers_time(real_bench):
    metric = _named(real_bench["per_layer"], NEW_METRIC)
    assert {CELL, "granite_4_0_h_micro.fit_s4096"} <= set(metric["workloads"])
    assert (metric["unit"], metric["layer"], metric["better"]) == (
        "ms", "model", "lower")
    assert metric["moves"] == "train_samples_per_s"
    assert metric["source"] == "device_trace"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(
        os.path.join(BENCH_DIR, "layers", NEW_METRIC + ".py"))
    # It moves what ``ssm.scan_ms`` moves, in the cells that report that.
    scan = _named(real_bench["per_layer"], "ssm.scan_ms")
    assert scan["moves"] == metric["moves"]
    assert set(metric["workloads"]) <= set(scan["workloads"])


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, fe, v, s = 2688, 1856, 16384, 16384
    inner, bc, heads = 64 * 64, 2 * 8 * 128, 64
    mamba = d * (2 * inner + bc + heads) + inner * d
    mamba_vectors = 5 * (inner + bc) + 3 * heads + inner
    attention = 2 * d * 32 * 128 + 2 * d * 2 * 128
    expert, shared, router = 2 * d * fe, 2 * d * 3712, d * 128
    # ISSUE 57: 38.74M, 23.40M, 9.978M, 19.96M, 0.34M.
    assert (mamba + mamba_vectors, attention, expert, shared, router) == (
        38_742_208, 23_396_352, 9_977_856, 19_955_712, 344_064)
    routed = router + shared + 8 * expert
    assert routed == pytest.approx(100.13e6, rel=1e-3)
    total = (4 * (mamba + mamba_vectors) + 4 * routed + attention
             + 9 * d + 2 * v * d + d)
    assert m.n_params(sizes) == total == 666_962_944      # ISSUE 57: 667.0M
    assert 16 * total == pytest.approx(10.67e9, rel=1e-3)
    # A whole routed layer is 1,297.5M = 20.8 GB: sixteen chips share it.
    whole = router + shared + 128 * expert
    assert whole == pytest.approx(1297.5e6, rel=1e-3)
    # The whole model: 31.6B.
    model = (23 * (mamba + mamba_vectors) + 6 * attention + 23 * whole
             + 52 * d + 2 * 131072 * d + d)
    assert model == pytest.approx(31.6e9, rel=2e-3)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 4 * s * 6 * 8 / 128
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 24576
    # TWO products an expert: up and down.
    assert m.moe_flops_per_step(sizes, traffic, 1) == (
        3 * pairs * 2 * 2 * d * fe)
    per_token = (4 * mamba + attention + 4 * (router + shared) + d * v)
    all_pairs = s * (s + 1) / 2
    attn = 4 * 32 * 128 * all_pairs
    # The scores once a GROUP (8 x 128 state features), the mixing a head.
    chunk_pairs = (128 + 1) / 2
    scan_token = (2 * 8 * 128 * chunk_pairs + 2 * inner * chunk_pairs
                  + 4 * 128 * inner)
    assert m.ssd_flops_per_token(sizes) == scan_token
    forward = (2 * (per_token * s + pairs * expert) + attn
               + 4 * s * scan_token)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        38.44e12, rel=1e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 30000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 30000
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        attn * 3.5)
    assert m.ssd_flops_per_step(sizes, traffic, 1) == 3 * 4 * s * scan_token
    assert m.ssd_bytes_per_step(sizes, traffic, 1) == (
        3 * 4 * s * (2 * (2 * inner + bc) + 4 * heads))
    # Bound by bytes on a v5e: 5.0 ms against 2.8.
    assert m.ssd_bytes_per_step(sizes, traffic, 1) / 819e9 > (
        m.ssd_flops_per_step(sizes, traffic, 1) / 197e12)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.layers == (
        ("mamba", "none"), ("none", "moe"), ("mamba", "none"),
        ("none", "moe"), ("mamba", "none"), ("attention", "none"),
        ("none", "moe"), ("mamba", "none"), ("none", "moe"))
    assert (cfg.d_model, cfg.d_expert, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim) == (2688, 1856, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts, moe.expert_form) == (128, 8, 0, 6, 2, "relu2")
    assert moe.shared_experts * moe.d_ff == 3712
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", True, True, 2.5)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.positions == "none" and cfg.embed_init_std == 1.0
    assert cfg.attention_scale is None          # head_dim ** -0.5
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 16384 and cfg.attention_impl == "flash"
    from raydp_tpu.models import nemotron_3_nano_30b_a3b
    whole = nemotron_3_nano_30b_a3b()
    assert whole.layers[:9] == cfg.layers and whole.n_layers == 52
    for bad in ({"mlp_hidden_act": "silu"}, {"n_group": 2},
                {"use_conv_bias": False}, {"hybrid_override_pattern": "ME-"}):
        with pytest.raises(ValueError):
            m.model_config({**sizes, **bad})


@pytest.mark.parametrize("gauge,value", [
    ("stack/layers", 9), ("stack/sublayers", 9),
    ("stack/mixer_only_layers", 5), ("stack/ffn_only_layers", 4),
    ("ssm/layers", 4), ("ssm/groups", 8), ("ssm/gate_norm_group_size", 512),
    ("ssm/chunks_per_step", 4 * 128),
    ("ssm/state_bytes_per_sequence", 4 * 64 * 64 * 128 * 4),
    ("attention/flash_live_tiles", 136), ("attention/flash_masked_tiles", 16),
    ("attention/flash_fused_bwd_layers", 1),
    ("moe/experts_routed", 128), ("moe/experts_held", 8),
    ("moe/compact_rows", 9216), ("moe/shared_experts", 2),
    ("moe/expert_matrices", 2),
    ("moe/token_sum_layers", 4), ("moe/token_sum_rows", 9216),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone): 98,304 pairs
    a layer, 6,144 on held experts at uniform routing, one and a half
    times that in the compact path."""
    from raydp_tpu.models import mamba, moe
    from raydp_tpu.models.transformer import report
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    report(model.cfg)
    mamba.report(model.cfg, tokens_per_step=16384)
    flash_attention.report(model.cfg, seq_len=16384)
    moe.report(model, tokens_per_step=16384)
    assert metrics.gauge_value(gauge) == value


def test_the_new_gauges_read_as_before_for_the_other_models(bench_modules):
    from raydp_tpu.models import mamba, moe
    from raydp_tpu.models.transformer import report
    from raydp_tpu.utils.profiling import metrics

    for name, groups, layers, matrices in (
            ("granite_4_0_h_micro.fit_s4096", 1, 6, 0),
            ("kimi_linear_48b_a3b.fit_s16384", 0, 5, 3)):
        other = bench_modules["harness"].load_cell(REPO, name)
        cfg = other.model.model_config(other.sizes)
        report(cfg)
        mamba.report(cfg, tokens_per_step=other.traffic["seq_len"])
        model = other.model.estimator_kwargs(
            other.sizes, other.traffic, None)["model"]
        moe.report(model, tokens_per_step=other.traffic["seq_len"])
        assert metrics.gauge_value("ssm/groups") == groups
        assert metrics.gauge_value("ssm/gate_norm_group_size") == (
            4096 if groups else 0)
        assert (metrics.gauge_value("stack/layers"),
                metrics.gauge_value("stack/sublayers"),
                metrics.gauge_value("stack/mixer_only_layers")) == (
            layers, 2 * layers, 0)
        assert metrics.gauge_value("moe/expert_matrices") == matrices


def test_the_new_reader_finds_nothing_in_a_program_without_the_scopes(
    bench_modules, cell, monkeypatch
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``ssm`` part, and
    the reader returns None without raising."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    with open(os.path.join(
            BENCH_DIR, "parts", "nemotron_hybrid_moe_lm.json")) as f:
        summary, _ = pt.reduce_profile(profile, json.load(f))
    assert not any(v for k, v in summary["parts_ms"].items()
                   if k.startswith("ssm_"))
    assert summary["parts_ms"]["attention"] > 0
    assert summary["parts_ms"]["moe_gmm"] > 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    parts = {"attention": 3.0, "head": 2.0}
    monkeypatch.setattr(pt, "summary", lambda facts: {"parts_ms": parts})
    reader = cell.part("layers", NEW_METRIC)
    assert reader.read(facts) is None
    monkeypatch.setattr(pt, "summary", lambda facts: {})
    assert reader.read(facts) is None
    # With the parts there, it reads its own and the two beside it add up.
    parts.update(ssm_ssd=30.0, ssm_conv_gate=25.0, ssm_proj=120.0)
    monkeypatch.setattr(pt, "summary", lambda facts: {"parts_ms": parts})
    assert reader.read(facts) == 120.0
    assert cell.part("layers", "ssm.scan_ms").read(facts) == 55.0
    assert cell.part("layers", "step.ssm_ms").read(facts) == 175.0
    share = cell.part("layers", "ssm.scan_roofline").read(facts)
    least_ms = cell.model.ssd_bytes_per_step(
        cell.sizes, cell.traffic, 1) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 30.0)
    assert 0 < share < 100
    # Granite's rules name the same part, so the metric reads there too.
    with open(os.path.join(
            BENCH_DIR, "parts", "granite_hybrid_lm.json")) as f:
        assert "ssm_proj" in {part for _, part in json.load(f)}


def _rules():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "nemotron_hybrid_moe_lm.json")) as f:
        return pt, pt.compile_rules(json.load(f))


def test_part_rules_partition_the_cells_scopes():
    pt, rules = _rules()
    jvp = "jit(train_step)/jvp(ScaledOutputs)/encoder/"
    back = ("jit(train_step)/transpose(jvp(ScaledOutputs))/encoder/"
            "jvp(ScaledOutputs)/encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "block_0/mamba/ssd/while/body/dot_general": "ssm_ssd",
        back + "block_4/mamba/ssd/transpose/cumsum": "ssm_ssd",
        jvp + "block_2/mamba/conv/mul": "ssm_conv_gate",
        remat + "block_7/mamba/gate_norm/rsqrt": "ssm_conv_gate",
        jvp + "block_0/mamba/in_proj/dot_general": "ssm_proj",
        back + "block_2/mamba/out_proj/dot_general": "ssm_proj",
        jvp + "block_4/ln_mamba/mul": "ssm_proj",
        jvp + "block_5/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_5/attn/q/dot_general": "attention",
        remat + "block_5/attn/kv/dot_general": "attention",
        jvp + "block_5/ln_attn/mul": "attention",
        jvp + "block_1/moe/permute/sort": "moe_permute",
        back + "block_3/moe/unpermute/gather": "moe_permute",
        jvp + "block_6/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_8/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        jvp + "block_1/moe/experts/integer_pow": "moe_rest",
        jvp + "block_1/moe/shared/in/dot_general": "moe_shared",
        back + "block_3/moe/shared/out/dot_general": "moe_shared",
        jvp + "block_8/moe/router/dot_general": "moe_rest",
        jvp + "block_8/ln_mlp/mul": "moe_rest",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(ScaledOutputs)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "embed", "ssm_ssd", "ssm_conv_gate", "ssm_proj",
        "attention", "moe_permute", "moe_gmm", "moe_shared", "moe_rest",
        "head"}


@pytest.fixture(scope="module")
def tiny_step_scopes(bench_modules):
    """Every scope path of a tiny copy's LOWERED train step (forward,
    backward under the block checkpoint, update), from the locations jax
    writes into the program."""
    import jax
    import numpy as np

    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator

    cell = bench_modules["harness"].load_cell(REPO, CELL)
    mesh = MeshSpec(dp=1)
    traffic = dict(cell.traffic, seq_len=32)
    est = JAXEstimator(
        **cell.model.estimator_kwargs(TINY, traffic, mesh), batch_size=2,
        mesh=mesh, seed=0, epoch_mode="stream",
    )
    x = np.zeros((2, 32), np.int32)
    est._init_state(x)
    text = jax.jit(est._make_train_step()).lower(
        est._state, x, None, jax.random.PRNGKey(0)
    ).as_text(debug_info=True)
    return sorted(set(re.findall(r'"(jit\(train_step\)/[^"]+)"', text)))


def test_the_part_rules_claim_every_scope_of_a_traced_tiny_step(
        tiny_step_scopes):
    """No operation under the model's modules or the step's own parts
    falls to ``rest`` (``part_of`` gives a scope the part of the FIRST rule
    that claims it: one part a scope), and the kinds of layer are told
    apart: a Mamba layer's scopes are ``ssm_*``, the attention
    layer's ``attention``, a routed layer's ``moe_*`` (a block's own
    residual add goes with ``moe_rest``, as in the other shares' rules)."""
    pt, rules = _rules()
    assert len(tiny_step_scopes) > 200
    kinds = {"0": "ssm", "3": "ssm", "1": "moe", "4": "moe", "2": "attention"}
    seen = set()
    for scope in tiny_step_scopes:
        part = pt.part_of(scope, rules)
        seen.add(part)
        under = re.search(r"/block_(\d)/(\w+)", scope)
        if under and under.group(2) in (
                "mamba", "ln_mamba", "attn", "ln_attn", "moe", "ln_mlp"):
            assert part.startswith(kinds[under.group(1)]), (scope, part)
        if re.search(
                r"/block_\d+/|/tok_embed/|/ln_final/|/lm_head/|part:", scope):
            assert part != "rest", scope
    assert seen >= {"update", "embed", "ssm_ssd", "ssm_conv_gate",
                    "ssm_proj", "attention", "moe_permute", "moe_shared",
                    "moe_rest", "head"}


@pytest.fixture(scope="module")
def nemotron_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "nemotron_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "nemotron_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "nemotron_tiny.fit", CELL, "nemotron_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.fixture(scope="module")
def tiny_run(bench_modules, nemotron_tree):
    """ONE traced run of the tiny cell; the tests below read it."""
    return bench_modules["run"].run_cell(
        nemotron_tree, "nemotron_tiny.fit", seed=3000000019, seconds=0.5,
        trace=1, platform="cpu",
    )


def test_tiny_cell_runs_end_to_end(tiny_run):
    line = tiny_run["line"]
    assert line["correct"] is True, tiny_run["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert tiny_run["notes"]["checks"]["losses_finite"] is True
    # A traced run's line holds the per-layer metrics; no TPU plane here,
    # so those read from a device trace are left out (the new one too).
    assert "infeed.put_share" in line["metrics"]
    assert NEW_METRIC not in line["metrics"]
    assert "moe.load_max_over_mean" in line["metrics"]


def test_tiny_cell_agrees_with_the_token_by_token_reference(tiny_run):
    detail = tiny_run["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4


@pytest.mark.parametrize("gauge,value", [
    ("stack/layers", 5), ("stack/sublayers", 5), ("ssm/layers", 2),
    ("ssm/groups", 4), ("ssm/gate_norm_group_size", 16),
    ("ssm/chunks_per_step", 2 * (2 * 32 // 8)),
    ("moe/shared_experts", 2), ("moe/experts_routed", 16),
    ("moe/experts_held", 4), ("moe/expert_matrices", 2),
    # 2 routed layers x 64 tokens x 3 experts a token, a step.
    ("moe/expert_tokens_per_step", 2 * 64 * 3),
    ("moe/overflow_layer_steps", 0),
])
def test_the_gauges_of_the_tiny_run(tiny_run, gauge, value):
    from raydp_tpu.utils.profiling import metrics

    assert metrics.gauge_value(gauge) == value


def test_a_float8_trunk_makes_the_run_incorrect(bench_modules, nemotron_tree,
                                                monkeypatch):
    """The harness's OWN comparison says ``correct`` false for the
    reference computed one precision below the one the cell states."""
    import jax.numpy as jnp

    harness = bench_modules["harness"]
    load = harness.load_cell

    def lower(root, name):
        cell = load(root, name)
        plain = cell.model.reference_logits
        cell.model.reference_logits = lambda p, b, s: plain(
            p, b, s, trunk=jnp.float8_e4m3fn)
        return cell

    monkeypatch.setattr(harness, "load_cell", lower)
    out = bench_modules["run"].run_cell(
        nemotron_tree, "nemotron_tiny.fit", seed=11, seconds=0.2, trace=0,
        platform="cpu",
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
