"""The ``ouro_2_6b`` configuration and its cell: the published config as a
literal (and the catalog's row where the catalog is there), only the
``reduced`` keys differ and no width nor ``total_ut_steps`` is among them,
the traffic is ``sdar_30b_a3b_chat.fit_s8192``'s, the part rules claim
every scope of a lowered tiny step once (the last exit under ``head``, the
exits before it under ``early_exits``), the builder's counts go with the
passes, and the new reader returns nothing where the program has no such
scopes. Every entry of ``BENCHMARK.json`` is found BY NAME and sets are
held by ``<=``: later PRs append."""
import importlib
import json
import os
import re

import pytest

from bench_tree import BENCH_DIR, REPO

CELL = "ouro_2_6b.fit_s8192"
CONFIG = "ouro_2_6b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
CUT = ["num_hidden_layers", "layer_types"]
WIDTHS = ["hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "vocab_size"]
NEW_METRIC = "loop.early_exits_ms"
REPORTED = {
    NEW_METRIC, "step.attention_ms", "attention.kernel_roofline",
    "step.mlp_ms", "step.head_ms", "step.embed_ms", "step.update_ms",
    "step.rest_ms", "model.mfu", "train_step_roofline", "step.device_ms",
    "step.dispatch_share", "device.peak_hbm_gib", "device.idle_share",
    "device.idle_unattributed_share", "infeed.wait_share",
    "infeed.put_share", "setup.ready_s", "setup.init_state_s",
    "setup.step_program_s", "setup.trace_lower_s", "setup.backend_compile_s",
    "setup.cache_load_s", "setup.cache_miss_programs", "setup.unaccounted_s",
}
TINY = {
    "builder": "ouro_loop_lm", "model_type": "ouro", "hidden_act": "silu",
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 128,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "exit": {"entropy_weight": 0.05}, "attention_impl": "dense",
    "remat": True, "compute_dtype": "float32", "param_dtype": "float32",
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
    name, with the source's value unless it is one of the two cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["reduced"][key]
        assert key in cell.sizes["published"]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_the_literal_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert row["config"] == SOURCE
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = _named(json.load(f)["configs"], CONFIG)
    assert entry["source"].startswith(row["source_url"])


def test_only_the_depth_is_cut_and_no_width_nor_the_passes(cell, real_bench):
    entry = _named(real_bench["configs"], CONFIG)
    assert entry["file"] == "benchmark/configs/ouro_2_6b.json"
    assert set(entry["reduced"]) == set(CUT) == set(cell.sizes["reduced"])
    assert not set(CUT) & set(WIDTHS + ["total_ut_steps"])
    assert "layers 0-5 of 48, run 4 times; whole vocabulary" in entry[
        "source"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    sizes = cell.sizes
    assert (sizes["num_hidden_layers"], sizes["layer_types"]) == (
        6, ["full_attention"] * 6)
    assert sizes["published"]["num_hidden_layers"] == 48
    assert sizes["total_ut_steps"] == sizes["published"]["total_ut_steps"] == 4
    for name in ("output_norms", "final_norm_in_the_loop", "gate",
                 "entropy_weight", "objective", "documents", "optimizer",
                 "precision", "remat", "weights"):
        assert sizes["assumed"][name], name
    assert sizes["exit"]["entropy_weight"] == 0.05
    assert set(sizes["deployment"]) >= {"this_chip", "stages"}
    assert (sizes["compute_dtype"], sizes["param_dtype"], sizes["remat"],
            sizes["attention_impl"]) == ("bfloat16", "float32", True, "flash")


def test_the_traffic_is_the_other_8k_cells(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 8192, "per_chip_batch": 1, "steps_per_epoch": 8,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 8192,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    with open(os.path.join(
            BENCH_DIR, "workloads", "sdar_30b_a3b_chat.fit_s8192.json")) as f:
        assert json.load(f)["traffic"] == cell.traffic
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "fit_s8192",
                     "chips": 1, "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    assert {"train_samples_per_s", "setup_s"} <= {
        m["name"] for m in cell.end_to_end()}
    assert REPORTED <= {m["name"] for m in cell.per_layer()}
    # Nothing of another family's layers is reported here.
    assert not {m["name"] for m in cell.per_layer()} & {
        "step.moe_ms", "step.ssm_ms", "step.kda_ms", "step.conv_ms",
        "step.hc_ms", "moe.exchange_ms", "collective.exposed_share",
        "diffusion.noise_ms"}


def test_the_new_metric_is_the_exits_before_the_last(real_bench):
    metric = _named(real_bench["per_layer"], NEW_METRIC)
    assert CELL in metric["workloads"]
    assert (metric["unit"], metric["layer"], metric["better"],
            metric["moves"], metric["source"]) == (
        "ms", "model", "lower", "train_samples_per_s", "device_trace")
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert os.path.exists(
        os.path.join(BENCH_DIR, "layers", NEW_METRIC + ".py"))
    # It moves what ``step.head_ms`` moves, in a cell that reports that.
    head = _named(real_bench["per_layer"], "step.head_ms")
    assert head["moves"] == metric["moves"]
    assert set(metric["workloads"]) <= set(head["workloads"])


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_counts_go_with_the_passes(cell, passes):
    """Operations multiply APPLICATIONS (passes x layers) and the head the
    passes; the parameters are one set whatever the passes."""
    model = cell.model
    sizes = dict(cell.sizes, total_ut_steps=passes)
    d, f, v, s = 2048, 5632, 49152, 8192
    layer = 4 * d * d + 3 * d * f
    assert model.applications(sizes) == 6 * passes
    assert model.n_params(sizes) == (
        6 * (layer + 4 * d) + 2 * v * d + d + ((d + 1) if passes > 1 else 0))
    pairs = s * (s + 1) / 2
    assert model.flops_per_sample(sizes, cell.traffic) == pytest.approx(
        3.0 * (2 * s * (6 * passes * layer + passes * d * v)
               + 6 * passes * 4 * d * pairs))
    assert model.attention_flops_per_step(sizes, cell.traffic, 1) == (
        pytest.approx(6 * passes * 16 * pairs * 4 * 128 * 3.5))
    one = model.bytes_per_step(dict(sizes, total_ut_steps=1), cell.traffic, 1)
    assert one == 32.0 * model.n_params(
        dict(sizes, total_ut_steps=1)) + 4.0 * s
    assert model.bytes_per_step(sizes, cell.traffic, 1) >= one
    if passes == 4:
        # ISSUE 61's arithmetic: 509.7M parameters, 1.0e14 operations.
        assert model.n_params(sizes) == pytest.approx(509.7e6, rel=1e-3)
        assert model.flops_per_sample(sizes, cell.traffic) == pytest.approx(
            1.0e14, rel=0.05)
        # The cost of the cut: four heads of the step's operations.
        heads = 3.0 * 2 * s * 4 * d * v / model.flops_per_sample(
            sizes, cell.traffic)
        assert heads == pytest.approx(0.20, abs=0.01)
        deep = dict(sizes, num_hidden_layers=48)
        assert 3.0 * 2 * s * 4 * d * v / model.flops_per_sample(
            deep, cell.traffic) == pytest.approx(0.03, abs=0.01)


def test_the_builder_builds_the_published_block(cell):
    from raydp_tpu.models import LoopLM

    kwargs = cell.model.estimator_kwargs(cell.sizes, cell.traffic, None)
    model, cfg = kwargs["model"], kwargs["model"].cfg
    assert isinstance(model, LoopLM) and model.entropy_weight == 0.05
    assert kwargs["loss"] == "loop_exit_ce" and kwargs["aux_losses"] is True
    assert (cfg.passes, cfg.branch_norm, cfg.n_layers, cfg.d_model,
            cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (4, True, 6, 2048, 16, 16, 128, 5632, 49152)
    assert (cfg.norm, cfg.norm_eps, cfg.positions, cfg.rope_theta,
            cfg.use_bias, cfg.tie_head, cfg.ffn, cfg.remat) == (
        "rmsnorm", 1e-6, "rotary", 1e6, False, False, "swiglu", True)
    with pytest.raises(ValueError, match="not the block"):
        cell.model.model_config(dict(cell.sizes, early_exit_threshold=0.5))


def test_the_new_reader_finds_nothing_in_a_program_without_the_scopes(
        bench_modules, cell, monkeypatch):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``early_exits``
    part, and the reader returns None without raising."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    with open(os.path.join(BENCH_DIR, "parts", "ouro_loop_lm.json")) as f:
        summary, _ = pt.reduce_profile(profile, json.load(f))
    assert not summary["parts_ms"].get("early_exits")
    assert summary["parts_ms"]["head"] > 0
    facts = {"cell": cell}
    reader = cell.part("layers", NEW_METRIC)
    monkeypatch.setattr(pt, "summary", lambda facts: {
        "parts_ms": {"attention": 3.0, "head": 2.0}})
    assert reader.read(facts) is None
    monkeypatch.setattr(pt, "summary", lambda facts: {})
    assert reader.read(facts) is None
    monkeypatch.setattr(pt, "summary", lambda facts: {
        "parts_ms": {"early_exits": 90.0, "head": 31.0}})
    assert reader.read(facts) == 90.0
    assert cell.part("layers", "step.head_ms").read(facts) == 31.0


def _rules():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "ouro_loop_lm.json")) as f:
        return pt, pt.compile_rules(json.load(f))


def test_part_rules_partition_the_cells_scopes():
    pt, rules = _rules()
    jvp = "jit(train_step)/jvp(LoopLM)/"
    back = ("jit(train_step)/transpose(jvp(LoopLM))/encoder/pass_2/"
            "jvp(LoopLM)/encoder/pass_2/checkpoint/")
    loss = "jit(train_step)/jvp(part:loss)/"
    want = {
        jvp + "encoder/tok_embed/tok_embed/take": "embed",
        jvp + "encoder/pass_0/block_0/attn/jit(flash_attention)/pallas_call":
            "attention",
        jvp + "encoder/pass_3/block_5/ln_attn/mul": "attention",
        jvp + "encoder/pass_1/block_2/ln_attn_out/rsqrt": "attention",
        back + "block_4/attn/qkv/dot_general": "attention",
        back + "rematted_computation/block_4/attn/out/dot_general":
            "attention",
        jvp + "encoder/pass_0/block_0/mlp_in/dot_general": "mlp",
        jvp + "encoder/pass_2/block_3/ln_mlp_out/mul": "mlp",
        back + "rematted_computation/block_1/mlp_out/dot_general": "mlp",
        back + "block_1/add": "mlp",
        jvp + "encoder/pass_0/ln_final/mul": "head",
        jvp + "encoder/pass_3/ln_final/mul": "head",
        jvp + "exit_0/exit_gate/dot_general": "early_exits",
        jvp + "exit_2/exit_gate/add": "early_exits",
        loss + "exit_0/jvp(lm_head)/dot_general": "early_exits",
        loss + "exit_1/transpose(jvp(lm_head))/dot_general": "early_exits",
        loss + "exit_2/reduce_max": "early_exits",
        loss + "exit_3/jvp(lm_head)/dot_general": "head",
        loss + "exit_3/exp": "head",
        loss + "reduce_sum": "head",
        "jit(train_step)/transpose(jvp(part:loss))/mul": "head",
        "jit(train_step)/jvp(CausalLM)/lm_head/dot_general": "head",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "embed", "early_exits", "attention", "mlp", "head"}


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
    that claims it: one part a scope). The last exit's head and loss are
    ``head``, the exits before it ``early_exits``, gates included; every
    pass's blocks split into ``attention`` and ``mlp``."""
    pt, rules = _rules()
    assert len(tiny_step_scopes) > 150
    seen, passes, exits = set(), set(), {}
    for scope in tiny_step_scopes:
        part = pt.part_of(scope, rules)
        seen.add(part)
        passes.update(re.findall(r"/pass_(\d)/", scope))
        at = re.search(r"/exit_(\d)(/|$)", scope)
        if at:
            exits.setdefault(at.group(1), set()).add(part)
        under = re.search(r"/block_\d/(\w+)", scope)
        if under and under.group(1) in (
                "attn", "ln_attn", "ln_attn_out"):
            assert part == "attention", scope
        if under and under.group(1) in (
                "mlp_in", "mlp_out", "ln_mlp", "ln_mlp_out"):
            assert part == "mlp", scope
        if re.search(r"/block_\d+/|/tok_embed/|/ln_final/|/lm_head/|/exit_\d"
                     r"|/exit_gate/|part:", scope):
            assert part != "rest", scope
    assert passes == {"0", "1", "2", "3"}
    assert exits == {"0": {"early_exits"}, "1": {"early_exits"},
                     "2": {"early_exits"}, "3": {"head"}}
    assert seen >= {"update", "embed", "early_exits", "attention", "mlp",
                    "head"}
    # The head's product of every exit keeps ``lm_head`` in its scope, the
    # gates ``exit_gate``, both inside their exit's.
    # (jax writes a product's own differentiation around the name.)
    assert any(re.search(r"/exit_3/jvp\(lm_head\)/", s)
               for s in tiny_step_scopes)
    assert any(re.search(r"/exit_0/transpose\(jvp\(lm_head\)\)/", s)
               for s in tiny_step_scopes)
    assert any(re.search(r"/exit_0/exit_gate/", s) for s in tiny_step_scopes)
    assert not any(re.search(r"/exit_3/exit_gate/", s)
                   for s in tiny_step_scopes)
