"""The ``lfm2_8b_a1b`` configuration and its cell: the files load, the
widths are the source's and the cut is the chip's share, the parameter,
operation and byte counts agree with hand counts, the new readers return
nothing where the program has no such scopes, the part rules split a
profile with the cell's scopes, every departure exceeds the tolerance, and
a tiny copy of the cell runs end to end on the CPU through ``run_cell``."""
import importlib
import json
import os

import numpy as np
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "lfm2_8b_a1b.fit_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ATTENTION = (2, 6, 10, 14, 18, 21)
# The source's config.json as the catalog has it.
SOURCE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": [
        "full_attention" if i in ATTENTION else "conv" for i in range(24)
    ],
}
CUT = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
TINY = {
    "builder": "lfm2_moe_lm", "model_type": "lfm2_moe",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "max_position_embeddings": 64, "norm_eps": 1e-5, "rope_theta": 1000000,
    "conv_L_cache": 3, "conv_bias": False, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "num_experts": 2, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 2e-5,
                  "warmup_steps": 2000},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


def test_widths_are_the_sources_and_the_cut_is_the_chips_share(
    cell, real_bench
):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert sizes["num_hidden_layers"] == 7
    assert sizes["layer_types"] == SOURCE["layer_types"][:7] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention"]
    # The router keeps its width and its experts a token; 8 are held.
    assert (sizes["num_experts"], sizes["num_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_tok"]) == (
        8, 32, 0, 4)
    assert sizes["vocab_size"] * 4 == SOURCE["vocab_size"]
    assert sizes["published"]["num_experts"] == 32
    assert sizes["deployment"]["chips_sharing_a_layer"] == 4
    # The floors of a model_config cut: a whole period and four layers
    # after the dense ones, 8 experts, an eighth of the vocabulary.
    assert sizes["num_hidden_layers"] - sizes["num_dense_layers"] >= 4
    assert sizes["num_experts"] >= 8
    assert sizes["vocab_size"] * 8 >= SOURCE["vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = next(c for c in real_bench["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["reduced"] == CUT
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("tie_word_embeddings", "precision", "optimizer",
                "expert_bias", "auxiliary_loss", "weights", "documents",
                "per_chip_batch", "attention_impl", "convolution", "remat",
                "projections"):
        assert sizes["assumed"][key], key


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 8192, "per_chip_batch": 1, "steps_per_epoch": 16,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 8192,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    entry = next(w for w in real_bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) == 1
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"step.conv_ms", "conv.mix_roofline", "step.moe_ms",
            "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "attention.kernel_roofline",
            "step.attention_ms", "step.mlp_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "step.device_ms", "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share", "infeed.wait_share",
            "infeed.put_share"} <= layers
    assert "step.ssm_ms" not in layers
    # The two new metrics are this cell's alone.
    new = [m for m in real_bench["per_layer"]
           if m["name"] in ("step.conv_ms", "conv.mix_roofline")]
    assert all(m["workloads"] == [CELL] for m in new)
    assert [m["layer"] for m in new] == ["model", "kernel"]
    assert all(m["moves"] == "train_samples_per_s" for m in new)


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, fe, v, s = 2048, 7168, 1792, 16384, 8192
    conv = 3 * d * d + d * d + 3 * d              # in, out, the [3, D] kernel
    attention = 2 * d * d + 2 * d * 512 + 2 * 64  # q, out, kv, two head norms
    dense, expert, router = 3 * d * f, 3 * d * fe, d * 32
    assert (conv, attention, dense, expert, router) == (
        16_783_360, 10_485_888, 44_040_192, 11_010_048, 65_536)
    norms = 2 * d
    layer_dense = conv + dense + norms
    layer_attn = attention + router + 8 * expert + norms
    layer_conv = conv + router + 8 * expert + norms
    assert (layer_dense, layer_attn, layer_conv) == (
        60_827_648, 98_635_904, 104_933_376)
    total = 2 * layer_dense + 2 * layer_attn + 3 * layer_conv + v * d + d
    assert m.n_params(sizes) == total == 667_283_712      # ISSUE 32: 667.3M
    assert 16 * total == pytest.approx(10.68e9, rel=1e-3)
    # A whole routed layer with its operator is 5.9 GB: three do not fit.
    assert 16 * (conv + router + 32 * expert + norms) == pytest.approx(
        5.9e9, rel=0.01)

    # Before the first epoch the held pairs are the expectation at
    # uniform routing; afterwards what the program counted.
    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 5 * s * 4 * 8 / 32
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 40_960
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    per_token = 5 * 4 * d * d + 2 * (2 * d * d + 2 * d * 512) + 2 * dense + (
        5 * router) + d * v
    attn = 2 * 4 * d * s * (s + 1) / 2
    forward = 2 * (per_token * s + pairs * expert) + attn
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    # ISSUE 32: 15.8 TFLOP a step, 1.9 of them causal attention.
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        15.8e12, rel=0.03)
    assert 3 * attn == pytest.approx(1.65e12, rel=0.01)
    assert 3 * 2 * d * v * s / m.flops_per_sample(sizes, traffic) == (
        pytest.approx(0.11, abs=0.015))
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 30_000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 30_000
        assert m.moe_flops_per_step(sizes, traffic, 1) == (
            3 * 30_000 * 2 * expert)
        assert m.flops_per_sample(sizes, traffic) == pytest.approx(
            3 * (2 * (per_token * s + 30_000 * expert) + attn))
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        2 * 32 * (s * (s + 1) / 2) * 7 * 2 * 64)
    # B, C, x read and the output written, bf16, three passes, five layers.
    assert m.conv_bytes_per_step(sizes, traffic, 1) == 3 * 5 * s * 4 * d * 2
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_refuses_what_it_does_not_write_down(cell):
    m, sizes = cell.model, cell.sizes
    for change in ({"conv_bias": True}, {"norm_topk_prob": False},
                   {"use_expert_bias": False}, {"model_type": "lfm2"},
                   {"layer_types": ["conv"]}):
        with pytest.raises(ValueError):
            m.model_config(dict(sizes, **change))
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("conv", "conv", "attention", "conv", "conv", "conv",
                         "attention")
    assert cfg.ffn_kinds == ("swiglu",) * 2 + ("moe",) * 5
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.conv_taps) == (
        2048, 7168, 1792, 3)
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k) == (
        32, 8, 0, 4)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", True, True, 1.0)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.qk_norm == "head" and cfg.positions == "rotary"
    assert cfg.rope_theta == 1e6 and cfg.tie_head and not cfg.use_bias
    assert cfg.vocab_size == 16384 and cfg.attention_impl == "flash"
    # ISSUE 32's warm-up times ten: the loads stay level (the JSON says why).
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with BERT's scopes has no ``conv_`` part."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "bert_base_fit_s128_parts.trace.json.gz"))
    with open(os.path.join(BENCH_DIR, "parts", "lfm2_moe_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert parts["conv_mix"] == parts["conv_proj"] == parts["moe_gmm"] == 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    for name in ("step.conv_ms", "conv.mix_roofline"):
        reader = cell.part("layers", name)
        assert reader.read(dict(facts, cell=ghost)) is None


def test_part_rules_partition_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "lfm2_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "block_0/conv/conv/mul": "conv_mix",
        remat + "block_3/conv/conv/add": "conv_mix",
        back + "block_5/conv/conv/pad": "conv_mix",
        jvp + "block_0/conv/in_proj/dot_general": "conv_proj",
        back + "block_4/conv/out_proj/dot_general": "conv_proj",
        remat + "block_1/ln_conv/mul": "conv_proj",
        jvp + "block_2/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_6/attn/kv/dot_general": "attention",
        remat + "block_6/attn/q_norm/mul": "attention",
        jvp + "block_2/ln_attn/mul": "attention",
        jvp + "block_3/moe/permute/sort": "moe_permute",
        back + "block_3/moe/unpermute/gather": "moe_permute",
        jvp + "block_2/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_5/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        remat + "block_5/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        jvp + "block_4/moe/experts/mul": "moe_rest",
        jvp + "block_4/moe/router/dot_general": "moe_rest",
        jvp + "block_4/ln_mlp/mul": "moe_rest",
        jvp + "block_4/add": "moe_rest",
        jvp + "block_0/ln_mlp/mul": "mlp",
        remat + "block_1/mlp_in/dot_general": "mlp",
        back + "block_0/mlp_out/dot_general": "mlp",
        jvp + "block_1/add": "mlp",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    # Every part ISSUE 32 names has a rule, and nothing else does.
    assert {part for _, part in rules} == {
        "update", "embed", "conv_mix", "conv_proj", "attention",
        "moe_permute", "moe_gmm", "moe_rest", "mlp", "head"}


@pytest.fixture(scope="module")
def lfm2_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "lfm2_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lfm2_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "lfm2_tiny.fit", CELL, "lfm2_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(bench_modules, lfm2_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        lfm2_tree, "lfm2_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert metrics.gauge_value("conv/layers") == 3
    assert metrics.gauge_value("conv/taps") == 3
    assert metrics.gauge_value("moe/experts_routed") == 8
    assert metrics.gauge_value("moe/experts_held") == 2
    # 3 routed layers x 64 tokens x 2 experts a token, a step.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 3 * 64 * 2
    held = metrics.gauge_value("moe/held_pairs_per_step")
    assert 0 < held < 3 * 64 * 2
    assert metrics.gauge_value("moe/held_pair_share") == pytest.approx(
        held / (3 * 64 * 2))
    assert metrics.gauge_value("moe/load_max_over_mean") >= 1.0
    # The builder's counts follow the program's gauge.
    cell = bench_modules["harness"].load_cell(lfm2_tree, "lfm2_tiny.fit")
    assert cell.model.held_pairs_per_step(
        cell.sizes, cell.traffic, 2) == held
    if trace:
        # No TPU plane here: the trace-read metrics are left out.
        assert "step.conv_ms" not in line["metrics"]
        assert "conv.mix_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}


def test_a_departure_flips_correct(bench_modules, lfm2_tree):
    """``correct`` comes out false when the program and the reference
    disagree: every departure the builder lists, and the precision below
    the stated one, read over ``TOLERANCE`` on the tiny cell's weights."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    harness = bench_modules["harness"]
    cell = harness.load_cell(lfm2_tree, "lfm2_tiny.fit")
    m, sizes = cell.model, cell.sizes
    ids = m.check_batch(sizes, cell.traffic, 3000000011)
    assert ids.shape == (1, 32) and ids.dtype == np.int32
    model = m.estimator_kwargs(sizes, cell.traffic, None)["model"]
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    variables = {k: variables[k] for k in ("params", "buffers")}
    want = m.reference_logits(variables, ids, sizes)

    def err(got):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    assert err(model.apply(variables, ids, mutable=["moe_stats"])[0]) < 1e-5
    errors = {d: err(m.reference_logits(variables, ids, sizes, depart=d))
              for d in m.DEPARTURES}
    errors["float8_trunk"] = err(m.reference_logits(
        variables, ids, sizes, trunk=jnp.float8_e4m3fn))
    assert len(errors) == 9 and min(errors.values()) > m.TOLERANCE, errors
    assert 0.03 < m.TOLERANCE < 0.05 and set(m.UNSEEN_ON_THE_CHIP) < set(
        m.DEPARTURES)
    with pytest.raises(ValueError, match="departure"):
        m.reference_logits(variables, ids, sizes, depart="no_such")


def test_flipped_reference_makes_the_run_incorrect(bench_modules, lfm2_tree):
    out = bench_modules["run"].run_cell(
        lfm2_tree, "lfm2_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
