"""The ``sdar_30b_a3b_chat`` configuration and its cell: the files load, the
widths are the source's and only the three cut keys differ, the traffic is
ISSUE 47's, the parameter, operation and byte counts agree with hand
counts, the two new readers return nothing where the program has no such
scopes, the part rules split the cell's scopes, the gauges a built step
sets, and a tiny copy of the cell runs end to end on the CPU through
``run_cell``. Every entry of ``BENCHMARK.json`` is found by name."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "sdar_30b_a3b_chat.fit_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok"]
NEW_METRICS = ["attention.pair_roofline", "diffusion.noise_ms"]
TINY = {
    "builder": "sdar_block_diffusion_moe_lm", "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_routed": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256,
    "diffusion": {"block_length": 4, "mask_token_id": 254, "t_min": 1e-3,
                  "schedule": "linear"},
    "init": {"embedding_std": 1.0},
    "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
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
    # Six of 48 identical layers; the router keeps its width and its
    # experts a token, 16 are held; an eighth of the vocabulary.
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["num_experts_routed"], sizes["first_expert"],
            sizes["num_experts_per_tok"]) == (6, 16, 128, 0, 8)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["deployment"]["chips_sharing_a_layer"] == 8
    assert sizes["diffusion"] == {
        "block_length": 4, "mask_token_id": 18990, "t_min": 0.001,
        "schedule": "linear"}
    assert sizes["num_hidden_layers"] >= 4 and sizes["num_experts"] >= 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
        assert row["not_given"] == ["block length", "noise schedule"]
    entry = _named(real_bench["configs"], "sdar_30b_a3b_chat")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/sdar_30b_a3b_chat.json"
    assert entry["source"].startswith(sizes["source"] + " ")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # The two sizes the config lacks come first, each with its reason.
    assert list(sizes["assumed"])[:2] == ["block_length", "noise"]
    for key in ("block_length", "noise", "mask_token_id", "targets",
                "auxiliary_loss", "routing", "share_rows", "qk_norm", "rotary",
                "precision", "optimizer", "weights", "remat", "documents",
                "where_the_noise_is_drawn", "per_chip_batch",
                "attention_impl", "last_layer", "projections"):
        assert len(sizes["assumed"][key]) > 20, key
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}
    assert sizes["init"] == {
        "embedding_std": 1.0, "depth_scaled_outputs": 6}
    assert len(sizes["deployment"]["placement"]) > 20


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 1 and cell.workload["job"] == "fit_window"
    assert cell.traffic == {
        "seq_len": 8192, "per_chip_batch": 1, "steps_per_epoch": 8,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 8192,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    # LFM2's traffic with 8-step epochs.
    with open(os.path.join(
            BENCH_DIR, "workloads", "lfm2_8b_a1b.fit_s8192.json")) as f:
        assert dict(json.load(f)["traffic"], steps_per_epoch=8) == (
            cell.traffic)
    entry = _named(real_bench["workloads"], CELL)
    assert entry == {"name": CELL, "config": "sdar_30b_a3b_chat",
                     "traffic": "fit_s8192", "chips": 1,
                     "why": cell.workload["why"]}
    assert len(entry["why"]) <= 200
    assert {m["name"] for m in cell.end_to_end()} == {
        "train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    # Not ``moe.shared_ms``, ``attention.latent_proj_ms`` (Xing4.0's cell
    # alone) nor ``step.mlp_ms`` (no dense FFN here).
    assert {"step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "attention.kernel_roofline",
            "step.attention_ms", "step.head_ms", "step.embed_ms",
            "step.update_ms", "step.rest_ms", "model.mfu", "step.device_ms",
            "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", *NEW_METRICS} == layers
    # One configuration, one cell, two metrics: nine, eleven, fifty-two.
    assert len(real_bench["configs"]) >= 9
    assert len(real_bench["workloads"]) >= 11
    assert len(real_bench["per_layer"]) >= 52
    assert sum(w["chips"] == 4 for w in real_bench["workloads"]) == 1


@pytest.mark.parametrize("name,unit,layer,better", [
    ("attention.pair_roofline", "%", "kernel", "higher"),
    ("diffusion.noise_ms", "ms", "model", "lower"),
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
    d, fe, v, s, length = 2048, 768, 18992, 8192, 4
    attention = d * 32 * 128 * 2 + d * 2 * 4 * 128
    expert, router = 3 * d * fe, d * 128
    # ISSUE 47: 18.88M, 4.719M, 0.26M.
    assert (attention + 256, expert, router) == (
        18_874_624, 4_718_592, 262_144)
    layer = attention + 256 + 2 * d + router + 16 * expert
    total = 6 * layer + 2 * v * d + d
    assert m.n_params(sizes) == total == 645_623_296      # ISSUE 47: 645.6M
    assert 16 * total == pytest.approx(10.33e9, rel=1e-3)
    # A whole layer is 623.1M = 9.97 GB: eight chips share it.
    assert attention + 256 + 2 * d + router + 128 * expert == pytest.approx(
        623.1e6, rel=1e-3)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    pairs = 6 * 2 * s * 8 * 16 / 128
    assert m.held_pairs_per_step(sizes, traffic, 1) == pairs == 98304
    assert m.pair_positions_per_step(traffic, 1) == 16384
    assert m.moe_flops_per_step(sizes, traffic, 1) == 3 * pairs * 2 * expert
    # The pairs inside the mask: S·L own block, S(S−L)/2 noised -> clean,
    # S(S+L)/2 clean -> clean; half of a causal mask over 16,384.
    inside = s * length + s * (s - length) / 2 + s * (s + length) / 2
    assert m.mask_pairs(sizes, s) == inside == 67_141_632
    assert inside == pytest.approx(16384 * 16385 / 2 / 2, rel=1e-3)
    # ISSUE 47: 1.10 TFLOP forward and 3.85 with the backward a layer.
    assert inside * 32 * 512 == pytest.approx(1.10e12, rel=1e-2)
    assert m.attention_flops_per_step(sizes, traffic, 1) == (
        6 * 32 * inside * 2 * 7 * 128)
    assert m.attention_flops_per_step(sizes, traffic, 1) == pytest.approx(
        23.1e12, rel=1e-2)
    forward = (2 * (6 * (attention + router) * 2 * s + d * v * s
                    + pairs * expert)
               + 2 * 2 * 128 * 6 * 32 * inside)
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    # Attention 19.8 (its kernels' 23.1 count the backward's five products,
    # the model's FLOPs three passes of two), projections 11.3, experts
    # 2.8, head 1.9.
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(
        35.85e12, rel=1e-2)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 120000)
        assert m.held_pairs_per_step(sizes, traffic, 1) == 120000
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    assert m.bytes_per_step(sizes, traffic, 1) == 32 * total + 4 * s


def test_builder_builds_the_published_block(cell):
    from raydp_tpu.models import BlockDiffusionLM, sdar_30b_a3b

    m, sizes = cell.model, cell.sizes
    cfg = m.model_config(sizes)
    assert cfg.kinds == ("attention",) * 6 and cfg.ffn_kinds == ("moe",) * 6
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.d_expert) == (2048, 32, 4, 128, 768)
    assert cfg.qk_norm == "head" and cfg.positions == "rotary"
    assert cfg.rope_theta == 1e6 and cfg.rotary_dim is None
    assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-6
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (128, 16, 0, 8, 0)
    # The published block has no selection bias, and neither has the cell.
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("softmax", False, True, 1.0)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    diff = cfg.diffusion
    assert (diff.block_length, diff.mask_id, diff.t_min) == (4, 18990, 1e-3)
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 18992 and cfg.attention_impl == "flash"
    assert cfg.embed_init_std == 1.0
    kwargs = m.estimator_kwargs(sizes, cell.traffic, None)
    assert isinstance(kwargs["model"], BlockDiffusionLM)
    assert kwargs["loss"] == "blockdiff_ce" and kwargs["aux_losses"]
    # The host sends S ids a sequence, not 2·S.
    assert len(kwargs["feature_columns"]) == 8192
    whole = sdar_30b_a3b()
    assert whole.n_layers == 48 and whole.vocab_size == 151936
    assert whole.moe_config().held == 128
    assert not whole.moe_config().selection_bias


@pytest.mark.parametrize("change", [
    {"model_type": "qwen3_moe"}, {"attention_bias": True},
    {"norm_topk_prob": False}, {"tie_word_embeddings": True},
    {"mlp_only_layers": [0]}, {"use_sliding_window": True},
    {"decoder_sparse_step": 2},
    {"diffusion": dict(TINY["diffusion"], schedule="cosine")},
    {"diffusion": dict(TINY["diffusion"], mask_token_id=256)},
], ids=lambda c: next(iter(c)))
def test_builder_refuses_what_it_does_not_write_down(cell, change):
    with pytest.raises(ValueError):
        cell.model.model_config({**TINY, **change})


def _heaviest_first_onto_the_lightest(load, chips):
    """The placement written out in numpy: the experts heaviest first,
    each onto the chip with room that has received least."""
    import numpy as np

    room = len(load) // chips
    total, members = np.zeros(chips), [[] for _ in range(chips)]
    for expert in np.argsort(-load, kind="stable"):
        chip = min((c for c in range(chips) if len(members[c]) < room),
                   key=lambda c: (total[c], c))
        total[chip] += load[expert]
        members[chip].append(int(expert))
    return [sorted(m) for m in members]


@pytest.mark.parametrize("load,chips", [
    ([9, 1, 1, 1, 8, 2, 2, 2, 7, 3, 3, 3, 6, 4, 4, 4], 4),
    ([40, 40, 40, 40, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1], 4),
    ([5] * 16, 8),
    ("zipf", 8),
], ids=["graded", "four_heavy", "equal", "zipf_128"])
def test_the_placement_fills_every_chip_and_evens_the_loads(cell, load,
                                                            chips):
    import jax
    import numpy as np

    if load == "zipf":
        load = np.random.default_rng(47).zipf(1.3, size=128).clip(max=5000)
    load = np.asarray(load, np.float32)
    order = np.asarray(jax.jit(
        cell.model.balanced_placement, static_argnums=1
    )(load, chips))
    assert sorted(order.tolist()) == list(range(len(load)))
    room = len(load) // chips
    by_chip = [order[c * room:(c + 1) * room].tolist() for c in range(chips)]
    assert by_chip == _heaviest_first_onto_the_lightest(load, chips)
    totals = [load[m].sum() for m in by_chip]
    # No chip is further from another than the heaviest expert.
    assert max(totals) - min(totals) <= load.max()


def test_the_deployed_model_scales_the_outputs_and_places_the_experts(cell):
    """``placed_share(cfg, s).init`` is ``BlockDiffusionLM.init`` with the
    residual outputs times ``s`` and each layer's router reading the same
    columns in the placement's order (from the loads of one training pass
    of the scaled weights), chip 0's at the columns this chip holds; every
    other leaf is the one drawn."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.traverse_util import flatten_dict, unflatten_dict

    from raydp_tpu.models import BlockDiffusionLM, stats

    m = cell.model
    sizes = {**TINY, "num_experts_routed": 16, "first_expert": 4}
    cfg = m.model_config(sizes)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (1, 64)), jnp.int32)
    key = jax.random.PRNGKey(5)
    plain = BlockDiffusionLM(cfg).init(key, ids)
    model = m.placed_share(cfg, 0.5)
    assert isinstance(model, BlockDiffusionLM)
    placed = model.init(key, ids)
    was = flatten_dict(nn.unbox(plain["params"]))
    scaled = {
        path: leaf * 0.5 if path[-3:] == ("attn", "out", "kernel")
        or path[-2:] == ("moe", "w_down") else leaf
        for path, leaf in was.items()
    }
    _, sown = BlockDiffusionLM(cfg).apply(
        {"params": unflatten_dict(scaled)}, ids, deterministic=False,
        rngs={"noise": key}, mutable=[stats.STATS])
    loads = {
        path[:-1]: np.asarray(v)
        for path, v in flatten_dict(dict(sown[stats.STATS])).items()
        if path[-1] == "expert_tokens"
    }
    assert len(loads) == 2
    now = flatten_dict(placed["params"])
    moved = halved = 0
    for path, leaf in scaled.items():
        # The boxes with the logical axes are kept.
        assert type(now[path]) is type(flatten_dict(plain["params"])[path])
        got, leaf = np.asarray(nn.unbox(now[path])), np.asarray(leaf)
        halved += leaf is not was[path] and not np.array_equal(
            leaf, np.asarray(was[path]))
        if path[-2:] != ("router", "kernel"):
            np.testing.assert_array_equal(got, leaf)
            continue
        moved += 1
        load = loads[path[:-2]]
        chip_0 = _heaviest_first_onto_the_lightest(load, 4)[0]
        np.testing.assert_array_equal(got[:, 4:8], leaf[:, chip_0])
        assert sorted(map(tuple, got.T)) == sorted(map(tuple, leaf.T))
        # This chip's experts received a quarter of the pairs, as near
        # as sixteen loads allow.
        assert abs(load[chip_0].sum() - load.sum() / 4) <= load.max()
    assert (moved, halved) == (2, 4)
    # The cell's model: the scale of the depth it runs, the placement.
    kwargs = m.estimator_kwargs(cell.sizes, cell.traffic, None)
    assert type(kwargs["model"]).__name__ == "PlacedShare"
    assert cell.sizes["init"]["depth_scaled_outputs"] == 6
    # A file without the key scales nothing.
    assert m.deployed_model(TINY).cfg == m.model_config(TINY)


def test_the_check_batch_is_a_pair_under_the_stated_noise(cell):
    import numpy as np

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    pair = m.check_batch(sizes, traffic, 3000000019)
    ids, masked, t = m.check_noise(sizes, traffic, 3000000019)
    assert pair.shape == (1, 16384) and pair.dtype == np.int32
    np.testing.assert_array_equal(pair[:, 8192:], ids)
    np.testing.assert_array_equal(
        pair[:, :8192], np.where(masked, 18990, ids))
    assert t.shape == (1, 2048) and t.min() >= 1e-3 and t.max() <= 1.0
    # Half the tokens on average; the binomial band of 8,192 draws.
    assert abs(masked.mean() - 0.5) < 0.03
    # The same seed gives the same pair, another seed another.
    np.testing.assert_array_equal(
        pair, m.check_batch(sizes, traffic, 3000000019))
    assert (pair != m.check_batch(sizes, traffic, 7)).any()


@pytest.mark.parametrize("gauge,value", [
    ("diffusion/block_length", 4), ("diffusion/blocks_per_sequence", 2048),
    ("diffusion/pair_positions_per_step", 16384),
    ("attention/flash_pair_live_tiles", 72),
    ("attention/flash_pair_crossed_tiles", 16),
    ("attention/flash_pair_own_block_tiles", 0),
    ("attention/flash_live_tiles", 0), ("attention/flash_masked_tiles", 0),
    ("attention/flash_kept_layers", 6),
    # 6 layers x 2 rows x 8,192 x 32 heads x (128 bf16 + 4): 780 MiB.
    ("attention/flash_kept_mib", 6 * 2 * 8192 * 32 * 260 / 2 ** 20),
    ("attention/flash_fused_bwd_layers", 6),
    ("attention/flash_bwd_resident_mib", 12),
    ("moe/experts_routed", 128), ("moe/experts_held", 16),
    ("moe/shared_experts", 0),
])
def test_the_gauges_of_the_published_step(cell, gauge, value):
    """What ``JAXEstimator._build_steps`` reports for the cell's
    configuration (the reports take the configuration alone). The MoE
    gauges read 128 and 16 without a line of ``models/moe.py`` changed;
    the backward at 8,192 x 128 is the one kernel."""
    from raydp_tpu.models import blockdiff, moe
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    blockdiff.report(model, batch=1, seq_len=8192)
    flash_attention.report(model.cfg, seq_len=8192)
    moe.report(model, tokens_per_step=16384)
    assert metrics.gauge_value(gauge) == value


def test_the_share_runs_over_the_pairs_rows(cell):
    """``moe/compact_rows`` of the 16,384 rows a pair sends through a
    layer: the share's rows, not a row more."""
    from raydp_tpu.models import moe
    from raydp_tpu.utils.profiling import metrics

    model = cell.model.estimator_kwargs(
        cell.sizes, cell.traffic, None)["model"]
    moe.report(model, tokens_per_step=model.positions_per_token * 8192)
    rows = metrics.gauge_value("moe/compact_rows")
    assert rows == moe.compact_rows(model.cfg.moe_config(), 16384)
    # One and a half times the 16,384 pairs uniform routing sends to 16 of
    # 128 experts, as every share's cell.
    assert rows == 24576


def test_the_new_gauges_read_zero_for_the_other_models(bench_modules):
    from raydp_tpu.models import blockdiff
    from raydp_tpu.utils.profiling import metrics

    flash_attention = importlib.import_module(
        "raydp_tpu.ops.flash_attention")
    for name in ("lfm2_8b_a1b.fit_s8192", "olmoe_1b_7b.fit_s4096"):
        other = bench_modules["harness"].load_cell(REPO, name)
        model = other.model.estimator_kwargs(
            other.sizes, other.traffic, None)["model"]
        blockdiff.report(model, batch=1, seq_len=other.traffic["seq_len"])
        flash_attention.report(model.cfg, seq_len=other.traffic["seq_len"])
        for gauge in ("diffusion/block_length",
                      "diffusion/blocks_per_sequence",
                      "diffusion/pair_positions_per_step",
                      "attention/flash_pair_live_tiles",
                      "attention/flash_pair_crossed_tiles",
                      "attention/flash_pair_own_block_tiles"):
            assert metrics.gauge_value(gauge) == 0, (name, gauge)
        assert metrics.gauge_value("attention/flash_live_tiles") > 0


def test_new_readers_find_nothing_in_a_program_without_the_scopes(
    bench_modules, cell, monkeypatch
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with OLMoE's scopes has no ``noise`` part,
    and a builder without ``mask_pairs`` no pair roofline."""
    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "olmoe_1b_7b_fit_s4096_parts.trace.json.gz"))
    with open(os.path.join(
            BENCH_DIR, "parts", "sdar_block_diffusion_moe_lm.json")) as f:
        summary, _ = pt.reduce_profile(profile, json.load(f))
    assert not summary["parts_ms"].get("noise")
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12,
                                     "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 1}
    parts = {"attention": 3.0, "head": 2.0}
    monkeypatch.setattr(pt, "summary", lambda facts: {"parts_ms": parts})
    assert cell.part("layers", "diffusion.noise_ms").read(facts) is None
    # No profile on disk under this checkout: nothing to read.
    monkeypatch.setattr(
        importlib.import_module("glob"), "glob", lambda pattern: [])
    assert cell.part("layers", "attention.pair_roofline").read(facts) is None
    olmoe = bench_modules["harness"].load_cell(REPO, "olmoe_1b_7b.fit_s4096")
    assert cell.part("layers", "attention.pair_roofline").read(
        dict(facts, cell=olmoe)) is None
    parts.update(noise=0.25)
    assert cell.part("layers", "diffusion.noise_ms").read(facts) == 0.25


def test_the_pair_roofline_reads_both_scopes(cell, monkeypatch, tmp_path):
    """Everything between the rotated q, k, v and the output: the kernels
    and the layout moves under ``attn/jit(flash_attention)`` and what runs
    beside them under ``attn/pair``; not the projections."""
    pt = importlib.import_module("program_trace")
    reader = cell.part("layers", "attention.pair_roofline")
    rules = pt.compile_rules(reader.PAIR)
    jvp = "jit(train_step)/jvp(BlockDiffusionLM)/encoder/block_2/attn/"
    back = ("jit(train_step)/transpose(jvp(BlockDiffusionLM))/encoder/"
            "checkpoint/block_2/attn/")
    for scope, part in {
        jvp + "jit(flash_attention)/pallas_call": "pair",
        jvp + "jit(flash_attention)/bshd->bhsd/transpose": "pair",
        back + "jit(flash_attention)/pallas_call": "pair",
        jvp + "pair/bnlkgd,bnmkd->bnlkgm/dot_general": "pair",
        back + "pair/logaddexp/exp": "pair",
        jvp + "pair/concatenate": "pair",
        jvp + "q/dot_general": "rest",
        jvp + "q_norm/mul": "rest",
        jvp + "out/dot_general": "rest",
    }.items():
        assert pt.part_of(scope, rules) == part, scope
    # 40 ms of such scopes a step is 23.1 TFLOP over 197 TFLOP/s over it.
    monkeypatch.setattr(reader.glob, "glob", lambda pattern: ["x.pb"])
    monkeypatch.setattr(pt, "load_profile", lambda path: {})
    monkeypatch.setattr(
        pt, "reduce_profile",
        lambda profile, rules: ({"parts_ms": {"pair": 200.0}}, {}))
    facts = {"cell": cell, "peaks": {"bf16_flops": 197e12},
             "per_chip_batch": 1}
    share = reader.read(facts)
    assert share == pytest.approx(
        100 * cell.model.attention_flops_per_step(
            cell.sizes, cell.traffic, 1) / 197e12 / 0.2)
    assert 0 < share < 100


def test_part_rules_partition_the_cells_scopes(bench_modules):
    pt = importlib.import_module("program_trace")
    with open(os.path.join(
            BENCH_DIR, "parts", "sdar_block_diffusion_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    top = "jit(train_step)/jvp(BlockDiffusionLM)/"
    jvp = top + "encoder/"
    back = ("jit(train_step)/transpose(jvp(BlockDiffusionLM))/encoder/"
            "jvp(BlockDiffusionLM)/encoder/checkpoint/")
    remat = back + "rematted_computation/"
    want = {
        top + "noise/jit(_uniform)/rng_bit_generator": "noise",
        top + "noise/select_n": "noise",
        top + "noise/concatenate": "noise",
        top + "noise/tile/iota": "noise",
        jvp + "tok_embed/take": "embed",
        jvp + "block_3/attn/jit(flash_attention)/pallas_call": "attention",
        back + "block_3/attn/jit(flash_attention)/pallas_call": "attention",
        jvp + "block_0/attn/pair/logaddexp/exp": "attention",
        remat + "block_5/attn/pair/dot_general": "attention",
        back + "block_3/attn/q/dot_general": "attention",
        remat + "block_3/attn/k_norm/mul": "attention",
        jvp + "block_3/ln_attn/mul": "attention",
        jvp + "block_3/moe/permute/sort": "moe_permute",
        back + "block_3/moe/unpermute/gather": "moe_permute",
        jvp + "block_2/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "block_4/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        jvp + "block_4/moe/router/dot_general": "moe_rest",
        jvp + "block_4/ln_mlp/mul": "moe_rest",
        jvp + "ln_final/mul": "head",
        top + "lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/reduce_sum": "head",
        "jit(train_step)/part:update/mul": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "noise", "embed", "attention", "moe_permute", "moe_gmm",
        "moe_rest", "head"}


@pytest.fixture(scope="module")
def sdar_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "sdar_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "sdar_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "sdar_tiny.fit", CELL, "sdar_tiny", {
        "seq_len": 32, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 32},
    })
    return tiny_tree


@pytest.fixture(scope="module")
def tiny_run(bench_modules, sdar_tree):
    """ONE traced run of the tiny cell; the tests below read it."""
    return bench_modules["run"].run_cell(
        sdar_tree, "sdar_tiny.fit", seed=3000000019, seconds=0.5,
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


def test_tiny_cell_agrees_with_the_reference_given_the_same_noise(tiny_run):
    detail = tiny_run["notes"]["reference_check"]
    assert detail["rows"] == 1
    assert detail["max_abs_err_over_max_abs_ref"] < 1e-4
    assert detail["tolerance"] == 0.012


@pytest.mark.parametrize("gauge,value", [
    ("diffusion/block_length", 4), ("diffusion/blocks_per_sequence", 8),
    ("diffusion/pair_positions_per_step", 2 * 2 * 32),
    ("moe/experts_routed", 8), ("moe/experts_held", 4),
    # 2 layers x 128 pair positions x 2 experts a position, a step.
    ("moe/expert_tokens_per_step", 2 * 128 * 2),
])
def test_the_gauges_of_the_tiny_run(tiny_run, gauge, value):
    from raydp_tpu.utils.profiling import metrics

    assert metrics.gauge_value(gauge) == value


def test_the_tiny_run_counted_its_masked_tokens(tiny_run):
    """``diffusion/masked_tokens`` is summed on the device and fetched
    with each epoch's loss; the last epoch's share of its 256 tokens is
    the gauge, about a half."""
    from raydp_tpu.utils.profiling import metrics

    share = metrics.gauge_value("diffusion/masked_share")
    assert 0.25 < share < 0.75
    counted = metrics.snapshot()["counters"]["diffusion/masked_tokens"]
    assert counted >= share * 256
