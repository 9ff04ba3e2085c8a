"""The ``olmo_hybrid_7b`` configuration and its cell: the files load, the
widths are the source's and only the three cut keys differ, the traffic
is ISSUE 63's, the parameter, operation and byte counts agree with hand
counts, the new readers return nothing where the program has no such
scopes, the part rules split the cell's scopes, the gauges a built step
sets, and a tiny copy of the cell runs end to end on the CPU through
``run_cell``. Every entry of ``BENCHMARK.json`` is found by name."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "olmo_hybrid_7b.fit_stage"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# The source's config.json as the catalog has it.
SOURCE = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
CUT = ["num_hidden_layers", "layer_types", "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "linear_num_key_heads",
          "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim"]
NEW_METRICS = ["step.gdn_ms", "gdn.scan_ms", "gdn.scan_roofline"]
SHARED_METRICS = [
    "infeed.wait_share", "infeed.put_share", "step.device_ms",
    "step.dispatch_share", "step.embed_ms", "step.attention_ms",
    "step.mlp_ms", "step.head_ms", "step.update_ms", "step.rest_ms",
    "model.mfu", "train_step_roofline", "attention.kernel_roofline",
    "device.idle_share", "device.idle_unattributed_share",
    "device.peak_hbm_gib", "setup.ready_s", "setup.init_state_s",
    "setup.step_program_s", "setup.trace_lower_s",
    "setup.backend_compile_s", "setup.cache_load_s",
    "setup.cache_miss_programs", "setup.unaccounted_s",
]
TINY = {
    "builder": "olmo_hybrid_lm", "model_type": "olmo_hybrid",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": PERIOD, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 12,
    "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "gdn": {"chunk": 16}, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0},
    "optimizer": {"name": "adamw", "learning_rate": 2e-5},
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
    name, with the source's value unless it is one of the three cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["reduced"][key]
        assert key in cell.sizes["published"]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_the_three_keys_differ(
        cell, real_bench):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    # One whole period, which is also the floor's four layers, in the
    # published order; an eighth of the vocabulary.
    assert sizes["num_hidden_layers"] == 4
    assert sizes["layer_types"] == SOURCE["layer_types"][:4] == PERIOD
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["published"]["num_hidden_layers"] == 32
    assert sizes["published"]["vocab_size"] == 100352
    assert sizes["gdn"] == {"chunk": 64}
    assert "eight pipeline stages" in sizes["deployment"]["this_chip"]
    assert "118.9 GB" in sizes["deployment"]["stages"]
    assert sizes["deployment"]["cost"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = _named(real_bench["configs"], "olmo_hybrid_7b")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/olmo_hybrid_7b.json"
    assert entry["source"] == sizes["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("norm_placement", "no_rotation", "gdn_parameterisation",
                "inits", "chunk", "precision", "optimizer", "documents",
                "per_chip_batch", "attention_impl", "remat", "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    assert sizes["optimizer"] == {"name": "adamw", "learning_rate": 2e-5}
    assert (sizes["param_dtype"], sizes["compute_dtype"]) == (
        "float32", "bfloat16")
    assert sizes["init"] == {"embedding_std": 1.0}


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
    # Xing4.0's traffic to the letter, over this model's slice.
    with open(os.path.join(
            BENCH_DIR, "workloads", "xing4_0_29b_a4b.fit_s4096.json")) as f:
        assert json.load(f)["traffic"] == cell.traffic
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "olmo_hybrid_7b",
                     "traffic": "fit_stage", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    names = {m["name"] for m in cell.end_to_end()}
    assert names == {"train_samples_per_s", "setup_s"}
    # ``<=``: a later PR may give every cell a further metric of a layer
    # they share.
    layers = {m["name"] for m in cell.per_layer()}
    assert {*SHARED_METRICS, *NEW_METRICS} <= layers
    assert not {m for m in layers if m.startswith(("moe.", "kda.", "ssm."))}
    assert len(real_bench["configs"]) >= 14
    assert len(real_bench["workloads"]) >= 16
    assert len(real_bench["per_layer"]) >= 72
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) >= 2


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_the_cell_joins_the_dense_lm_cells_metrics(real_bench, name):
    metric = _named(real_bench["per_layer"], name)
    assert metric["workloads"][-1] == CELL or CELL in metric["workloads"]
    assert "granite_4_0_h_micro.fit_s4096" in metric["workloads"]
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


@pytest.mark.parametrize("name,unit,layer,better", [
    ("step.gdn_ms", "ms", "model", "lower"),
    ("gdn.scan_ms", "ms", "model", "lower"),
    ("gdn.scan_roofline", "%", "kernel", "higher"),
])
def test_the_new_metrics_are_this_cells(real_bench, name, unit, layer,
                                        better):
    metric = _named(real_bench["per_layer"], name)
    # ``<=``: a later cell of the same family may join.
    assert {CELL} <= set(metric["workloads"])
    assert (metric["unit"], metric["layer"], metric["better"]) == (
        unit, layer, better)
    assert metric["moves"] == "train_samples_per_s"
    assert metric["source"] == "device_trace"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_counts_against_hand_counts(cell):
    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, v, s = 3840, 11008, 12544, 4096
    keys, values = 30 * 96, 30 * 192
    q = k = d * keys
    val = gate = out = d * values
    ab, convs = 2 * d * 30, 4 * (2 * keys + values)
    # ISSUE 63: q 11.06M, k 11.06M, v 22.12M, gate 22.12M, out 22.12M,
    # a and b 0.23M, convolutions 46k.
    assert (q, val, ab, convs) == (11_059_200, 22_118_400, 230_400, 46_080)
    mixer = q + k + val + gate + out + ab + convs + 2 * 30 + 192
    ffn, attention = 3 * d * f, 4 * d * d + 2 * d
    assert (mixer, ffn, attention) == (88_750_332, 126_812_160, 58_990_080)
    linear, full = mixer + ffn + 2 * d, attention + ffn + 2 * d
    assert (linear, full) == (215_570_172, 185_809_920)
    total = 3 * linear + full + 2 * v * d + d
    assert m.n_params(sizes) == total == 928_862_196      # ISSUE 63
    assert 16 * total == pytest.approx(14.86e9, rel=1e-3)
    assert 16 * total / 2 ** 30 == pytest.approx(13.84, rel=1e-3)
    # Five layers would be 17.8 GB; the whole model 7.43B = 118.9 GB.
    assert 16 * (total + linear) == pytest.approx(18.31e9, rel=1e-2)
    whole = 24 * linear + 8 * full + 2 * 100352 * d + d
    assert whole == pytest.approx(7.43e9, rel=1e-3)
    assert 16 * whole == pytest.approx(118.9e9, rel=1e-3)

    matrices = (3 * (mixer - convs - 252) + (attention - 2 * d) + 4 * ffn
                + d * v)
    pairs = s * (s + 1) / 2
    attn = 4 * d * pairs
    scan = 3 * s * 30 * 7 * 96 * 192
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        3 * (2 * matrices * s + attn + scan))
    # ISSUE 63: 21.6 TFLOP of matrices a step; the head 5.5% of them; the
    # scan about 1% of the whole.
    assert 6 * matrices * s == pytest.approx(21.6e12, rel=5e-3)
    assert d * v / matrices == pytest.approx(0.055, abs=1e-3)
    assert 3 * scan / m.flops_per_sample(sizes, traffic) < 0.01
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        30 * pairs * 2 * 7 * 128)
    assert m.gdn_flops_per_step(sizes, traffic, 1) == 3 * scan
    assert m.gdn_bytes_per_step(sizes, traffic, 1) == (
        2 * 3 * s * (2 * (2 * keys + 2 * values) + 2 * 4 * 30))
    # Bound by bytes on a v5e: 0.52 ms against 0.36.
    assert m.gdn_bytes_per_step(sizes, traffic, 1) / 819e9 > (
        m.gdn_flops_per_step(sizes, traffic, 1) / 197e12)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s
    # The update moves 26 GB at least: 32 ms at the chip's 819 GB/s.
    assert 28 * total / 819e9 == pytest.approx(0.032, rel=2e-2)


def test_builder_builds_the_published_block(cell):
    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("gdn", "gdn", "gdn", "attention")
    assert cfg.ffn_kinds == ("swiglu",) * 4
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim) == (3840, 11008, 30, 30, 128)
    gdn = cfg.gdn
    assert (gdn.heads, gdn.key_dim, gdn.value_dim, gdn.conv_taps, gdn.chunk,
            gdn.neg_eigval) == (30, 96, 192, 4, 64, True)
    assert cfg.branch_norm == "only" and cfg.qk_norm == "projection"
    assert cfg.positions == "none" and cfg.embed_init_std == 1.0
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 12544 and cfg.attention_impl == "flash"
    assert cfg.passes == 1 and cfg.hyper is None and cfg.kda is None
    from raydp_tpu.models import olmo_hybrid_7b
    whole = olmo_hybrid_7b()
    assert whole.kinds == ("gdn", "gdn", "gdn", "attention") * 8
    assert whole.vocab_size == 100352
    with pytest.raises(ValueError, match="not the block"):
        m.model_config(dict(sizes, rope_parameters={"rope_theta": 5e5}))
    with pytest.raises(ValueError, match="not the block"):
        m.model_config(dict(sizes, layer_types=PERIOD[:3]))
    kwargs = m.estimator_kwargs(sizes, cell.traffic, None)
    assert kwargs["loss"] == "lm_ce" and kwargs["self_supervised"] is True
    assert len(kwargs["feature_columns"]) == 4096


@pytest.mark.parametrize("gauge,value", [
    ("gdn/layers", 3), ("gdn/heads", 30), ("gdn/chunk", 64),
    ("gdn/chunks_per_step", 3 * 64),
    ("gdn/state_bytes_per_sequence", 3 * 30 * 96 * 192 * 4),
    # o in bf16 and two segments' entering states, three layers.
    ("gdn/kept_bytes_per_sequence",
     3 * 30 * 192 * (2 * 4096 + 4 * 2 * 96)),
    ("kda/layers", 0), ("ssm/layers", 0),
    ("attention/flash_live_tiles", 10), ("attention/flash_masked_tiles", 4),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone)."""
    from raydp_tpu.models import gdn, kda, mamba
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    gdn.report(model.cfg, tokens_per_step=4096)
    kda.report(model.cfg, tokens_per_step=4096)
    mamba.report(model.cfg, tokens_per_step=4096)
    flash_attention.report(model.cfg, seq_len=4096)
    assert metrics.gauge_value(gauge) == value


@pytest.mark.parametrize("other", [
    "kimi_linear_48b_a3b.fit_s16384", "granite_4_0_h_micro.fit_s4096",
    "ouro_2_6b.fit_s8192",
])
def test_the_gdn_gauges_read_zero_for_the_other_models(bench_modules, other):
    from raydp_tpu.models import gdn
    from raydp_tpu.utils.profiling import metrics

    other = bench_modules["harness"].load_cell(REPO, other)
    cfg = other.model.model_config(other.sizes)
    gdn.report(cfg, tokens_per_step=other.traffic["seq_len"])
    for gauge in ("gdn/layers", "gdn/heads", "gdn/chunk",
                  "gdn/chunks_per_step", "gdn/state_bytes_per_sequence",
                  "gdn/kept_bytes_per_sequence"):
        assert metrics.gauge_value(gauge) == 0, gauge


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
        bench_modules, cell, monkeypatch):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``gdn`` part."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    with open(os.path.join(BENCH_DIR, "parts", "olmo_hybrid_lm.json")) as f:
        summary, _ = pt.reduce_profile(profile, json.load(f))
    assert not any(v for k, v in summary["parts_ms"].items()
                   if k.startswith("gdn_"))
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    parts = {"attention": 3.0, "head": 2.0}
    monkeypatch.setattr(pt, "summary", lambda facts: {"parts_ms": parts})
    for name in NEW_METRICS:
        assert cell.part("layers", name).read(facts) is None
    # A builder without the two counting functions (another family's)
    # gives the share nothing to read, and it does not raise.
    parts.update(gdn_scan=8.0, gdn_conv_gate=5.0, gdn_proj=60.0)
    bare = type("Cell", (), {"model": object(), "sizes": cell.sizes,
                             "traffic": cell.traffic})
    assert cell.part("layers", "gdn.scan_roofline").read(
        dict(facts, cell=bare)) is None
    # With the parts there, the three read them.
    assert cell.part("layers", "step.gdn_ms").read(facts) == 73.0
    assert cell.part("layers", "gdn.scan_ms").read(facts) == 13.0
    share = cell.part("layers", "gdn.scan_roofline").read(facts)
    least_ms = cell.model.gdn_bytes_per_step(
        cell.sizes, cell.traffic, 1) / 819e9 * 1e3
    assert share == pytest.approx(100 * least_ms / 8.0)
    assert 0 < share < 100


JVP = "jit(train_step)/jvp(CausalLM)/encoder/"
BACK = ("jit(train_step)/transpose(jvp(CausalLM))/encoder/jvp(CausalLM)/"
        "encoder/checkpoint/")
REMAT = BACK + "rematted_computation/"


@pytest.mark.parametrize("scope,part", [
    (JVP + "tok_embed/take", "embed"),
    (JVP + "block_0/gdn/scan/while/body/dot_general", "gdn_scan"),
    (BACK + "block_2/gdn/scan/transpose/cumsum", "gdn_scan"),
    (JVP + "block_1/gdn/conv/q/mul", "gdn_conv_gate"),
    (REMAT + "block_2/gdn/conv/rsqrt", "gdn_conv_gate"),
    (BACK + "block_0/gdn/decay/softplus", "gdn_conv_gate"),
    (BACK + "block_0/gdn/decay/proj/dot_general", "gdn_conv_gate"),
    (JVP + "block_0/gdn/beta/logistic", "gdn_conv_gate"),
    (BACK + "block_1/gdn/gate_norm/mul", "gdn_conv_gate"),
    (JVP + "block_1/gdn/q_proj/dot_general", "gdn_proj"),
    (REMAT + "block_1/gdn/g_proj/dot_general", "gdn_proj"),
    (BACK + "block_2/gdn/out/dot_general", "gdn_proj"),
    (JVP + "block_2/ln_gdn_out/mul", "gdn_proj"),
    (JVP + "block_3/attn/jit(flash_attention)/pallas_call", "attention"),
    (BACK + "block_3/attn/qkv/dot_general", "attention"),
    (REMAT + "block_3/attn/q_norm/mul", "attention"),
    (JVP + "block_3/ln_attn_out/mul", "attention"),
    (JVP + "block_0/ln_mlp_out/mul", "mlp"),
    (REMAT + "block_3/mlp_in/dot_general", "mlp"),
    (BACK + "block_0/mlp_out/dot_general", "mlp"),
    (JVP + "ln_final/mul", "head"),
    ("jit(train_step)/jvp(CausalLM)/lm_head/dot_general", "head"),
    ("jit(train_step)/jvp(part:loss)/reduce_sum", "head"),
    ("jit(train_step)/part:update/mul", "update"),
    ("", "rest"),
])
def test_part_rules_partition_the_cells_scopes(scope, part):
    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "olmo_hybrid_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    assert pt.part_of(scope, rules) == part
    assert {part for _, part in rules} == {
        "update", "embed", "gdn_scan", "gdn_conv_gate", "gdn_proj",
        "attention", "mlp", "head"}


@pytest.fixture(scope="module")
def olmo_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "olmo_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "olmo_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "olmo_tiny.fit", CELL, "olmo_tiny", {
        "seq_len": 48, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 48},
    })
    return tiny_tree


@pytest.fixture(scope="module")
def tiny_run(bench_modules, olmo_tree):
    """ONE traced run of the tiny cell (the tests below read it)."""
    return bench_modules["run"].run_cell(
        olmo_tree, "olmo_tiny.fit", seed=3000000019, seconds=0.5,
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
    assert detail["tolerance"] == 0.05


def test_a_flipped_reference_makes_the_run_incorrect(
        bench_modules, olmo_tree):
    out = bench_modules["run"].run_cell(
        olmo_tree, "olmo_tiny.fit", seed=2147483659, seconds=0.2, trace=0,
        platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False
    assert out["notes"]["checks"]["losses_finite"] is True


@pytest.mark.parametrize("gauge,value", [
    ("gdn/layers", 3), ("gdn/heads", 2), ("gdn/chunk", 16),
    ("gdn/chunks_per_step", 3 * 6),
    ("gdn/state_bytes_per_sequence", 3 * 2 * 12 * 24 * 4),
    ("kda/layers", 0), ("checkpoint/blocks", 4),
    ("checkpoint/blocks_checkpointed", 4), ("stack/layers", 4),
    ("stack/sublayers", 8),
])
def test_the_gauges_of_the_tiny_run(tiny_run, gauge, value):
    from raydp_tpu.utils.profiling import metrics

    assert metrics.gauge_value(gauge) == value
