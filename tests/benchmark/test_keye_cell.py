"""The ``keye_vl_2_0_30b_a3b`` configuration and its cell: the files load,
the widths are the source's and only the three cut keys differ, the traffic
is ISSUE 51's (Laguna's and Kimi Linear's to the letter), the parameter,
operation and byte counts agree with hand counts, the four new readers
return nothing where the program has no such scopes, the part rules split
the cell's scopes, the gauges a built step sets, and a tiny copy of the
cell runs end to end on the CPU through ``run_cell``. Every entry of
``BENCHMARK.json`` is found by name, and the cell's per-layer set is held
by ``<=``: a later PR may append to it."""
import json
import os

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "keye_vl_2_0_30b_a3b.fit_s16384"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "sa_config", "rope_scaling"]
NEW_METRICS = ["attention.index_ms", "attention.select_ms",
               "attention.index_roofline", "attention.sparse_roofline"]
TINY = {
    "builder": "keye_sparse_moe_lm", "model_type": "KeyeVL2",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_local_experts": 8, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                  "q_chunk_size": 16, "topk": 8},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
    "init": {"embedding_std": 1.0, "depth_scaled_outputs": 2},
    "remat": True, "compute_dtype": "float32", "param_dtype": "float32",
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
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key] == SOURCE[key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_the_three_keys_differ(
    cell, real_bench
):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    # Five of 48 identical layers; the router keeps its width and its
    # experts a token, 16 are held; an eighth of the vocabulary.
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["num_experts_routed"], sizes["first_expert"],
            sizes["num_experts_per_tok"]) == (5, 16, 128, 0, 8)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["deployment"]["chips_sharing_a_layer"] == 8
    assert sizes["num_hidden_layers"] >= 4 and sizes["num_experts"] >= 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
        assert row["not_given"] == []
    entry = _named(real_bench["configs"], "keye_vl_2_0_30b_a3b")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/keye_vl_2_0_30b_a3b.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for key in ("stage", "index_loss_weight", "index_query_input",
                "index_norm", "index_rotation", "index_scales", "hadamard",
                "chunks", "ties", "mrope", "precision", "auxiliary_loss",
                "routing", "share_rows", "optimizer", "weights", "remat",
                "documents", "per_chip_batch", "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}
    assert sizes["init"] == {
        "embedding_std": 1.0, "depth_scaled_outputs": 5}
    assert len(sizes["deployment"]["placement"]) > 20


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
    # Laguna's and Kimi Linear's traffic, letter for letter.
    for other in ("laguna_xs_2.fit_s16384", "kimi_linear_48b_a3b.fit_s16384"):
        with open(os.path.join(BENCH_DIR, "workloads", other + ".json")) as f:
            assert json.load(f)["traffic"] == cell.traffic
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "keye_vl_2_0_30b_a3b",
                     "traffic": "fit_s16384", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    assert {m["name"] for m in cell.end_to_end()} == {
        "train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    # Held by <=: a later PR may append its metric to the cell. Not
    # ``attention.kernel_roofline`` (no flash kernel runs here), nor the
    # block-diffusion cell's two.
    assert {"step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "step.attention_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "step.device_ms", "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", "setup.ready_s", "setup.init_state_s",
            "setup.step_program_s", "setup.trace_lower_s",
            "setup.backend_compile_s", "setup.cache_load_s",
            "setup.cache_miss_programs", "setup.unaccounted_s",
            *NEW_METRICS} <= layers
    assert not {"attention.kernel_roofline", "attention.pair_roofline",
                "diffusion.noise_ms"} & layers
    # One configuration, one cell, four metrics: ten, twelve, sixty-four.
    assert len(real_bench["configs"]) >= 10
    assert len(real_bench["workloads"]) >= 12
    assert len(real_bench["per_layer"]) >= 64
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) == 1


@pytest.mark.parametrize("name,unit,layer,better", [
    ("attention.index_ms", "ms", "model", "lower"),
    ("attention.select_ms", "ms", "model", "lower"),
    ("attention.index_roofline", "%", "kernel", "higher"),
    ("attention.sparse_roofline", "%", "kernel", "higher"),
])
def test_the_new_metrics_are_this_cells_alone(real_bench, name, unit, layer,
                                              better):
    metric = _named(real_bench["per_layer"], name)
    assert CELL in metric["workloads"]
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
    d, fe, v, s, topk = 2048, 768, 18992, 16384, 2048
    attention = d * 32 * 128 * 2 + d * 2 * 4 * 128
    index = d * 16 * 64 + d * 64 + d * 16
    expert, router = 3 * d * fe, d * 128
    # ISSUE 51: 18.88M, 2.26M, 4.719M, 0.26M.
    assert (attention + 256, index + 128, expert, router) == (
        18_874_624, 2_261_120, 4_718_592, 262_144)
    layer = attention + 256 + index + 128 + 2 * d + router + 16 * expert
    assert layer == pytest.approx(96.90e6, rel=1e-3)
    total = 5 * layer + 2 * v * d + d
    assert m.n_params(sizes) == total
    assert total == pytest.approx(562.3e6, rel=1e-3)      # ISSUE 51
    assert 16 * total == pytest.approx(9.00e9, rel=1e-3)
    # A whole layer is 625.4M = 10.0 GB: eight chips share it.
    assert attention + 256 + index + 128 + 2 * d + router + 128 * expert == (
        pytest.approx(625.4e6, rel=1e-3))

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 5 * s * 8 * 16 / 128
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 81920
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    causal = s * (s + 1) / 2
    selected = topk * (topk + 1) / 2 + (s - topk) * topk
    assert m.causal_pairs(s) == causal == 134_225_920
    assert m.selected_pairs(sizes, s) == selected == 31_458_304
    # ISSUE 51: 23% of the causal pairs are selected; 0.27 TFLOP of index
    # scores and 0.52 of attention over the selection a layer, forward.
    assert selected / causal == pytest.approx(0.234, abs=1e-3)
    assert causal * 16 * 64 * 2 == pytest.approx(0.275e12, rel=1e-2)
    assert selected * 32 * 128 * 2 * 2 == pytest.approx(0.515e12, rel=1e-2)
    assert m.index_score_flops_per_step(sizes, traffic, 1) == (
        5 * causal * 2 * 16 * 64)
    assert m.sparse_attention_flops_per_step(sizes, traffic, 1) == (
        5 * selected * (32 * 2 * 7 * 128 + 2 * 2 * 16 * 64))
    forward = 2 * (5 * (attention + index + router) * s + d * v * s
                   + pairs * expert) + 2 * 2 * 128 * 5 * 32 * selected
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        3 * forward + 5 * 2 * 16 * 64 * (causal + 2 * selected))
    # Attention over the selection 7.7, its projections 9.3 and the index
    # branch's 1.1, index scores 2.0, experts 2.3, head 3.8, routers 0.1.
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        26.41e12, rel=1e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 90000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 90000
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    from raydp_tpu.models import CausalLM, keye_vl_2_0_30b_a3b

    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("sparse",) * 5 and cfg.ffn_kinds == ("moe",) * 5
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.d_expert) == (2048, 32, 4, 128, 768)
    assert cfg.qk_norm == "head" and cfg.positions == "mrope"
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    sp = cfg.sparse
    assert (sp.index_heads, sp.index_head_dim, sp.index_kv_heads, sp.topk,
            sp.q_chunk, sp.kv_chunk) == (16, 64, 1, 2048, 512, 512)
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (128, 16, 0, 8, 0)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("softmax", False, True, 1.0)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 18992 and cfg.embed_init_std == 1.0
    assert cfg.diffusion is None
    kwargs = m.estimator_kwargs(sizes, cell.traffic, None)
    assert isinstance(kwargs["model"], CausalLM)
    assert type(kwargs["model"]).__name__ == "DepthScaled"
    assert kwargs["loss"] == "lm_ce" and kwargs["aux_losses"]
    assert len(kwargs["feature_columns"]) == 16384
    whole = keye_vl_2_0_30b_a3b()
    assert whole.n_layers == 48 and whole.vocab_size == 151936
    assert whole.moe_config().held == 128


@pytest.mark.parametrize("change", [
    {"model_type": "qwen3_moe"}, {"attention_bias": True},
    {"norm_topk_prob": False}, {"tie_word_embeddings": True},
    {"mlp_only_layers": [0]}, {"use_sliding_window": True},
    {"decoder_sparse_step": 2}, {"num_local_experts": 16},
    {"rope_scaling": {"mrope_section": [2, 3, 4], "rope_type": "default"}},
    {"rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "yarn"}},
], ids=lambda c: next(iter(c)) + str(len(str(c))))
def test_builder_refuses_what_it_does_not_write_down(cell, change):
    with pytest.raises(ValueError):
        cell.model.model_config({**TINY, **change})


def test_the_deployed_model_scales_the_residual_outputs(cell):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.traverse_util import flatten_dict

    from raydp_tpu.models import CausalLM

    m = cell.model
    ids = jnp.zeros((1, 64), jnp.int32)
    key = jax.random.PRNGKey(5)
    plain = flatten_dict(nn.unbox(
        CausalLM(m.model_config(TINY)).init(key, ids))["params"])
    scaled = flatten_dict(nn.unbox(
        m.deployed_model(TINY).init(key, ids))["params"])
    halved = 0
    for path, leaf in plain.items():
        out = path[-3:] == ("attn", "out", "kernel") or path[-2:] == (
            "moe", "w_down")
        halved += out
        np.testing.assert_allclose(
            np.asarray(scaled[path]),
            np.asarray(leaf) * (0.5 if out else 1.0), rtol=1e-6)
    assert halved == 4
    assert cell.sizes["init"]["depth_scaled_outputs"] == 5
    # A file without the key scales nothing.
    bare = {**TINY, "init": {"embedding_std": 1.0}}
    same = flatten_dict(nn.unbox(
        m.deployed_model(bare).init(key, ids))["params"])
    for path, leaf in plain.items():
        np.testing.assert_array_equal(np.asarray(same[path]), np.asarray(leaf))


def test_the_check_batch_is_seeded(cell):
    import numpy as np

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    ids = m.check_batch(sizes, traffic, 3000000019)
    assert ids.shape == (1, 16384) and ids.dtype == np.int32
    assert 0 <= ids.min() and ids.max() < 18992
    np.testing.assert_array_equal(
        ids, m.check_batch(sizes, traffic, 3000000019))
    assert (ids != m.check_batch(sizes, traffic, 7)).any()


@pytest.mark.parametrize("gauge,value", [
    ("attention/sparse_layers", 5), ("attention/index_topk", 2048),
    ("attention/index_heads", 16),
    ("attention/flash_live_tiles", 0), ("attention/flash_kept_layers", 0),
    ("diffusion/block_length", 0),
    ("moe/experts_routed", 128), ("moe/experts_held", 16),
    ("moe/shared_experts", 0), ("moe/compact_rows", 24576),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone)."""
    import importlib

    from raydp_tpu.models import blockdiff, moe, sparse_index
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    sparse_index.report(model.cfg)
    blockdiff.report(model, batch=1, seq_len=16384)
    flash_attention.report(model.cfg, seq_len=16384)
    moe.report(model, tokens_per_step=16384)
    assert metrics.gauge_value(gauge) == value


def test_the_new_gauges_read_zero_for_the_other_models(bench_modules):
    from raydp_tpu.models import sparse_index
    from raydp_tpu.utils.profiling import metrics

    harness = bench_modules["harness"]
    for other in ("sdar_30b_a3b_chat.fit_s8192", "laguna_xs_2.fit_s16384",
                  "olmoe_1b_7b.fit_s4096"):
        cell = harness.load_cell(REPO, other)
        metrics.gauge_set("attention/sparse_layers", 7)
        metrics.gauge_set("attn/selected_share", 0.5)
        sparse_index.report(cell.model.model_config(cell.sizes))
        # An epoch of a model with routed layers and no sparse one.
        sparse_index.report_epoch({"expert_tokens": [1.0]})
        for gauge in ("attention/sparse_layers", "attention/index_topk",
                      "attention/index_heads", "attn/selected_share",
                      "attn/select_overfull_queries", "attn/index_kl"):
            assert metrics.gauge_value(gauge) == 0.0, (other, gauge)


@pytest.mark.parametrize("reader", NEW_METRICS)
@pytest.mark.parametrize("other", [
    "sdar_30b_a3b_chat.fit_s8192", "laguna_xs_2.fit_s16384",
    "bert_base.fit_s128"])
def test_new_readers_find_nothing_elsewhere(bench_modules, reader, other):
    """No profile, no scopes, no counts: None, never an exception."""
    harness = bench_modules["harness"]
    cell = harness.load_cell(REPO, other)
    read = cell.part("layers", reader).read
    assert read({"cell": cell, "peaks": None, "per_chip_batch": 1}) is None
    assert read({"cell": cell, "peaks": {"bf16_flops": 1e12},
                 "per_chip_batch": 1}) is None


def test_the_parts_partition_the_scopes(cell, bench_modules):
    import importlib

    pt = importlib.import_module("program_trace")
    sp = importlib.import_module("sparse_parts")
    with open(os.path.join(
            BENCH_DIR, "parts", cell.sizes["builder"] + ".json")) as f:
        rules = pt.compile_rules(json.load(f))
    split = pt.compile_rules(sp.RULES)
    top = "jit(train_step)/jvp(DepthScaled)/"
    jvp = top + "encoder/"
    back = ("jit(train_step)/transpose(jvp(DepthScaled))/encoder/"
            "jvp(DepthScaled)/encoder/checkpoint/")
    remat = back + "rematted_computation/"
    mapped = "attn/closed_call/while/body/closed_call/"
    want = {
        jvp + "tok_embed/take": ("embed", "rest"),
        jvp + "block_3/attn/q/dot_general": ("attention", "rest"),
        jvp + "block_3/attn/k_norm/mul": ("attention", "rest"),
        jvp + "block_3/ln_attn/mul": ("attention", "rest"),
        jvp + "block_3/attn/index/wq/dot_general": ("attention", "index"),
        remat + "block_0/attn/index/k_norm/mul": ("attention", "index"),
        back + "block_0/attn/index/wk/transpose": ("attention", "index"),
        jvp + "block_3/" + mapped
        + "index/sparse_index_scores/pallas_call": ("attention", "scores"),
        jvp + "block_3/" + mapped + "index/dynamic_slice": (
            "attention", "index"),
        jvp + "block_3/" + mapped
        + "select/sparse_select/pallas_call": ("attention", "select"),
        jvp + "block_2/attn/sparse/sparse_attention_forward/pallas_call": (
            "attention", "sparse"),
        back + "block_2/attn/sparse/sparse_attention_dq/pallas_call": (
            "attention", "sparse"),
        back + "block_2/attn/sparse/sparse_attention_dkv/pallas_call": (
            "attention", "sparse"),
        back + "block_2/attn/sparse/bshd->bhsd/transpose": (
            "attention", "sparse"),
        jvp + "block_2/attn/index_loss/reduce_sum": (
            "attention", "index_loss"),
        back + "block_2/attn/index_loss/div": ("attention", "index_loss"),
        jvp + "block_3/moe/permute/sort": ("moe_permute", "rest"),
        jvp + "block_2/moe/experts/jit(gmm)/pallas_call": ("moe_gmm", "rest"),
        jvp + "block_4/moe/router/dot_general": ("moe_rest", "rest"),
        jvp + "block_4/ln_mlp/mul": ("moe_rest", "rest"),
        jvp + "ln_final/mul": ("head", "rest"),
        top + "lm_head/dot_general": ("head", "rest"),
        "jit(train_step)/jvp(part:loss)/reduce_sum": ("head", "rest"),
        "jit(train_step)/part:update/mul": ("update", "rest"),
        # SDAR's and Laguna's attention: no part of the second split.
        jvp + "block_3/attn/jit(flash_attention)/pallas_call": (
            "attention", "rest"),
        "": ("rest", "rest"),
    }
    for scope, (part, second) in want.items():
        assert pt.part_of(scope, rules) == part, scope
        assert pt.part_of(scope, split) == second, scope
    assert {part for _, part in rules} == {
        "update", "embed", "attention", "moe_permute", "moe_gmm",
        "moe_rest", "head"}


@pytest.fixture(scope="module")
def keye_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "keye_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "keye_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "keye_tiny.fit", CELL, "keye_tiny", {
        "seq_len": 64, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 64},
    })
    return tiny_tree


@pytest.fixture(scope="module")
def tiny_run(bench_modules, keye_tree):
    """ONE traced run of the tiny cell; the tests below read it."""
    return bench_modules["run"].run_cell(
        keye_tree, "keye_tiny.fit", seed=3000000019, seconds=0.5,
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


def test_tiny_cell_agrees_with_the_reference(tiny_run):
    detail = tiny_run["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert detail["tolerance"] == 0.012


@pytest.mark.parametrize("gauge,low,high", [
    ("attention/sparse_layers", 2, 2), ("attention/index_topk", 8, 8),
    ("moe/experts_routed", 8, 8), ("moe/experts_held", 4, 4),
    # 2 layers x 128 tokens x 2 experts a token, a step.
    ("moe/expert_tokens_per_step", 512, 512),
    # min(t + 1, 8) of t + 1 keys a query over 64 positions, and ties.
    ("attn/selected_share", 0.23, 0.4),
    ("attn/index_kl", 1e-6, 10.0),
    ("attn/select_overfull_queries", 0, 2 * 4 * 128),
])
def test_the_gauges_of_the_tiny_run(tiny_run, gauge, low, high):
    from raydp_tpu.utils.profiling import metrics

    assert low <= metrics.gauge_value(gauge) <= high
