"""The ``granite_4_0_h_micro`` configuration and its cell: the files load,
the widths are the source's, the parameter, operation and byte counts
agree with hand counts, the new readers return nothing where the program
has no such scopes, the part rules split a profile with the cell's scopes,
every departure exceeds the tolerance, and a tiny copy of the cell runs
end to end on the CPU through ``run_cell``."""
import importlib
import json
import os

import numpy as np
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "granite_4_0_h_micro.fit_s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The widths and multipliers ISSUE 30 names, as the source's config.json
# has them; ``num_hidden_layers`` and ``layer_types`` are the two cut keys.
SOURCE = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
    "layer_types": [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)
    ],
}
TINY = {
    "builder": "granite_hybrid_lm",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "shared_intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3,
    "layer_types": ["mamba", "mamba", "attention"],
    "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "tie_word_embeddings": True, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 2e-5,
                  "warmup_steps": 2000},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


def test_widths_are_the_sources_and_only_depth_is_cut(cell, real_bench):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == {"num_hidden_layers", "layer_types"} == set(
        sizes["reduced"])
    assert sizes["num_hidden_layers"] == 6
    assert sizes["layer_types"] == SOURCE["layer_types"][:6] == (
        ["mamba"] * 5 + ["attention"])
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = next(c for c in real_bench["configs"]
                 if c["name"] == "granite_4_0_h_micro")
    assert entry["reduced"] == ["layer_types", "num_hidden_layers"]
    assert entry["source"].startswith(sizes["source"] + " ")
    assert real_bench["configs"][-1] == entry     # appended, not inserted
    for key in ("precision", "optimizer", "weights", "documents",
                "per_chip_batch", "attention_impl", "convolution", "remat",
                "projections"):
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
    assert real_bench["workloads"][-1]["name"] == CELL
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    assert {"step.ssm_ms", "ssm.scan_ms", "ssm.scan_roofline",
            "step.attention_ms", "step.mlp_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "train_step_roofline", "device.peak_hbm_gib"} <= layers
    assert "step.moe_ms" not in layers
    # The three new metrics are this cell's alone, at the end of the list.
    new = real_bench["per_layer"][-3:]
    assert [m["name"] for m in new] == [
        "step.ssm_ms", "ssm.scan_ms", "ssm.scan_roofline"]
    assert all(m["workloads"] == [CELL] for m in new)
    assert [m["layer"] for m in new] == ["model", "model", "kernel"]


def test_counts_against_hand_counts(cell):
    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, v, s = 2048, 8192, 100352, 4096
    in_proj, out_proj = d * (4096 + 4352 + 64), 4096 * d
    assert (in_proj, out_proj) == (17_432_576, 8_388_608)
    mamba = (in_proj + out_proj + 5 * 4352 + 3 * 64 + 4096   # conv, ssd, gate
             + 3 * d * f + 2 * d)                            # MLP, two norms
    attention = 2 * d * d + 2 * d * 512 + 3 * d * f + 2 * d
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert m.n_params(sizes) == 5 * mamba + attention + v * d + d
    assert m.n_params(sizes) == 647_259_328
    # One whole period of the pattern does not fit 16 GB at 16 B each.
    assert 16 * (9 * mamba + attention + v * d) > 15.2e9
    # The scan: causal pairs inside a chunk, the chunk state, the
    # carried-in part; N 128, H·P 4096, Q 256.
    scan = 2 * 128 * 128.5 + 2 * 4096 * 128.5 + 2 * 2 * 128 * 4096
    assert m.ssd_flops_per_token(sizes) == scan == 3_182_720
    assert m.ssd_flops_per_step(sizes, traffic, 1) == 3 * 5 * s * scan
    # x, y bf16 [64 x 64], B, C bf16 [128], dt float32 [64]; three passes.
    assert m.ssd_bytes_per_step(sizes, traffic, 1) == 3 * 5 * s * (
        2 * (2 * 4096 + 256) + 4 * 64)
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        32 * (s * (s + 1) / 2) * 7 * 2 * 64)
    per_token = (5 * (in_proj + out_proj + 3 * d * f)
                 + (2 * d * d + 2 * d * 512 + 3 * d * f) + d * v)
    forward = 2 * per_token * s + 4 * d * s * (s + 1) / 2 + 5 * s * scan
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    # ISSUE 30: 3.98 GFLOP a token, the head 1.23 of them (31%).
    assert m.flops_per_sample(sizes, traffic) / s == pytest.approx(
        3.98e9, rel=2e-3)
    assert 6 * d * v / (m.flops_per_sample(sizes, traffic) / s) == (
        pytest.approx(0.31, abs=0.005))
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * 647_259_328 + 4 * s


def test_builder_refuses_what_it_does_not_write_down(cell):
    m, sizes = cell.model, cell.sizes
    for change in ({"num_local_experts": 8}, {"mamba_expand": 4},
                   {"position_embedding_type": "rope"},
                   {"tie_word_embeddings": False},
                   {"layer_types": ["mamba"]}):
        with pytest.raises(ValueError):
            m.model_config(dict(sizes, **change))
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("mamba",) * 5 + ("attention",)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk,
            cfg.ssm_conv, cfg.ssm_groups) == (64, 64, 128, 256, 4, 1)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_scale, cfg.logits_scaling) == (12, 0.22, 1 / 64, 8)
    assert cfg.tie_head and cfg.positions == "none" and cfg.ffn == "swiglu"
    assert cfg.remat and cfg.attention_impl == "flash"
    # A fine-tune's warm-up: the rate climbs linearly to its peak.
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 2000}
    import jax.numpy as jnp
    import optax

    tx = m._optimizer(sizes["optimizer"])
    params = {"w": jnp.ones((3,))}
    state = tx.init(params)
    first, state = tx.update({"w": jnp.ones((3,))}, state, params)
    second, _ = tx.update({"w": jnp.ones((3,))}, state, params)
    assert float(jnp.abs(first["w"]).max()) == 0.0       # step 0: rate 0
    assert 0 < float(jnp.abs(second["w"]).max()) < 2e-5 / 1000


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with BERT's scopes has no ``ssm_`` part."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "bert_base_fit_s128_parts.trace.json.gz"))
    with open(os.path.join(BENCH_DIR, "parts", "granite_hybrid_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert parts["ssm_ssd"] == parts["ssm_conv_gate"] == parts["ssm_proj"] == 0
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    # No profile under benchmark_out/trace of this checkout-shaped cell:
    # every reader returns None and raises nothing.
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    for name in ("step.ssm_ms", "ssm.scan_ms", "ssm.scan_roofline"):
        reader = cell.part("layers", name)
        assert reader.read(dict(facts, cell=ghost)) is None


def test_part_rules_split_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "granite_hybrid_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(CausalLM)/encoder/"
    # With the blocks checkpointed the backward's scopes carry the
    # forward's path again, and the recomputed forward a marker more.
    back = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
            "encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        jvp + "tok_embed/take": "embed",
        jvp + "block_0/mamba/ssd/dot_general": "ssm_ssd",
        remat + "block_3/mamba/ssd/while/body/mul": "ssm_ssd",
        back + "block_1/mamba/conv/mul": "ssm_conv_gate",
        remat + "block_1/mamba/in_proj/dot_general": "ssm_proj",
        remat + "block_5/attn/jit(flash_attention)/pallas_call": "attention",
        remat + "block_5/mlp_in/dot_general": "mlp",
        "jit(train_step)/transpose(jvp(CausalLM))/encoder/block_0/mlp_in/"
        "dot_general": "mlp",
        jvp + "block_4/mamba/gate_norm/rsqrt": "ssm_conv_gate",
        jvp + "block_2/mamba/in_proj/dot_general": "ssm_proj",
        back + "block_2/mamba/out_proj/dot_general": "ssm_proj",
        jvp + "block_0/ln_mamba/mul": "ssm_proj",
        jvp + "block_0/mamba/split": "ssm_proj",
        jvp + "block_5/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_5/attn/kv/dot_general": "attention",
        jvp + "block_5/ln_attn/mul": "attention",
        jvp + "block_5/mlp_in/dot_general": "mlp",
        back + "block_0/mlp_out/dot_general": "mlp",
        jvp + "block_0/ln_mlp/mul": "mlp",
        jvp + "block_0/add": "mlp",
        jvp + "ln_final/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope


@pytest.fixture(scope="module")
def granite_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "granite_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "granite_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "granite_tiny.fit", CELL, "granite_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(bench_modules, granite_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        granite_tree, "granite_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    detail = out["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert metrics.gauge_value("ssm/layers") == 2
    assert metrics.gauge_value("ssm/chunks_per_step") == 2 * (2 * 32 // 8)
    if trace:
        # No TPU plane here: the trace-read metrics are left out.
        assert "step.ssm_ms" not in line["metrics"]
        assert "ssm.scan_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}


def test_a_departure_flips_correct(bench_modules, granite_tree):
    """``correct`` comes out false when the program and the reference
    disagree: every departure the builder lists, and the precision below
    the stated one, read over ``TOLERANCE`` on the tiny cell's weights,
    and the harness's own check says so."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    harness = bench_modules["harness"]
    cell = harness.load_cell(granite_tree, "granite_tiny.fit")
    m, sizes = cell.model, cell.sizes
    ids = m.check_batch(sizes, cell.traffic, 3000000011)
    assert ids.shape == (1, 32) and ids.dtype == np.int32
    model = m.estimator_kwargs(sizes, cell.traffic, None)["model"]

    def carrying(path, a):
        # Steps e^3 times larger, decay rates e^3 times smaller: the
        # carried state weighs at 16 state features as it does at 128.
        name = jax.tree_util.keystr(path)
        return a + 3.0 if "dt_bias" in name else (
            a - 3.0 if "A_log" in name else a)

    variables = jax.tree_util.tree_map_with_path(
        carrying, nn.unbox(model.init(jax.random.PRNGKey(0), ids)))
    want = m.reference_logits(variables, ids, sizes)

    def err(got):
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    assert err(model.apply(variables, ids)) < 1e-5
    errors = {d: err(m.reference_logits(variables, ids, sizes, depart=d))
              for d in m.DEPARTURES}
    errors["float8_trunk"] = err(m.reference_logits(
        variables, ids, sizes, trunk=jnp.float8_e4m3fn))
    assert len(errors) == 7 and min(errors.values()) > m.TOLERANCE, errors
    assert m.TOLERANCE <= 0.03        # BERT's order, not OLMoE's 25%


def test_flipped_reference_makes_the_run_incorrect(bench_modules, granite_tree):
    out = bench_modules["run"].run_cell(
        granite_tree, "granite_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True
