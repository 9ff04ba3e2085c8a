"""The ``glm_4_7_flash`` configuration and its cell: the files load, the
widths are the source's and only the three cut keys differ (the
multi-token-prediction module is KEPT), the traffic is ISSUE 67's, the
parameter and operation counts agree with hand counts, the new readers
return nothing where the program has no such scopes or gauges, the part
rules split the cell's scopes, and a tiny copy of the cell runs end to end
on the CPU through ``run_cell``, ``correct`` deciding on BOTH heads and
both losses."""
import importlib
import json
import os

import jax.numpy as jnp
import pytest

from bench_tree import BENCH_DIR, REPO, add_cell

CELL = "glm_4_7_flash.fit_mtp_s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The source's config.json as the catalog has it.
SOURCE = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
WIDTHS = ["hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "n_shared_experts"]
NEW_METRICS = ["step.mtp_ms", "mtp.head_ms", "mtp.loss_over_main"]
TINY = {
    "builder": "glm4_mtp_moe_lm", "model_type": "glm4_moe_lite",
    "vocab_size": 500, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "max_position_embeddings": 64,
    "rms_norm_eps": 1e-5, "rope_theta": 1000000, "rope_scaling": None,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "n_routed_experts": 2,
    "num_experts_routed": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "mtp": {"loss_weight": 0.3},
    "attention_impl": "dense", "remat": True, "compute_dtype": "float32",
    "param_dtype": "float32", "init": {"embedding_std": 1.0},
    "optimizer": {"name": "adamw", "learning_rate": 2e-5,
                  "warmup_steps": 2000},
}


@pytest.fixture(scope="module")
def cell(bench_modules):
    return bench_modules["harness"].load_cell(REPO, CELL)


@pytest.mark.parametrize("key", sorted(SOURCE))
def test_every_source_key_is_kept_or_cut(cell, key):
    """Each key of the source's config.json is in the file under its own
    name, with the source's value unless it is one of the three cuts."""
    assert key in cell.sizes
    if key in CUT:
        assert cell.sizes[key] != SOURCE[key]
        assert cell.sizes["published"][key] == SOURCE[key]
        assert cell.sizes["reduced"][key]
    else:
        assert cell.sizes[key] == SOURCE[key]


def test_widths_are_the_sources_and_only_three_keys_are_cut(cell, real_bench):
    sizes = cell.sizes
    changed = {k for k, v in SOURCE.items() if sizes[k] != v}
    assert changed == set(CUT) == set(sizes["reduced"])
    assert not set(WIDTHS) & changed
    # The module is what makes this configuration another program: kept.
    assert sizes["num_nextn_predict_layers"] == 1
    assert "num_nextn_predict_layers" not in sizes["reduced"]
    assert sizes["published"]["num_nextn_predict_layers"] == 1
    assert (sizes["num_hidden_layers"], sizes["first_k_dense_replace"]) == (
        5, 1)
    # The router keeps its width and its experts a token; 8 are held.
    assert (sizes["n_routed_experts"], sizes["num_experts_routed"],
            sizes["first_expert"], sizes["num_experts_per_tok"]) == (
        8, 64, 0, 4)
    assert sizes["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert sizes["vocab_size"] % 128 == 32      # 151.25 tiles of lanes
    assert sizes["deployment"]["chips_sharing_a_layer"] == 8
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-4.7-Flash")
        assert row["config"] == SOURCE
        assert row["source_url"] == sizes["source"]
    entry = next(c for c in real_bench["configs"]
                 if c["name"] == "glm_4_7_flash")
    assert entry["reduced"] == CUT
    assert entry["file"] == "benchmark/configs/glm_4_7_flash.json"
    assert entry["source"] == sizes["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # (a) - (g) of ISSUE 67, and what every share cell states.
    for key in ("loss_weight", "module_input", "projection_order", "rotary",
                "e_score_correction_bias", "last_position", "gate_epsilon",
                "precision", "optimizer", "auxiliary_loss", "weights",
                "per_chip_batch", "attention_impl", "remat", "projections"):
        assert sizes["assumed"][key], key
    assert sizes["mtp"] == {"loss_weight": 0.3}
    assert sizes["optimizer"] == {
        "name": "adamw", "learning_rate": 2e-5, "warmup_steps": 20000}


def test_traffic_is_the_issues(cell, real_bench):
    assert cell.chips == 1
    # ``fit_window``'s window, and the other head's check after it.
    assert cell.workload["job"] == "fit_window_heads"
    assert cell.traffic == {
        "seq_len": 8192, "per_chip_batch": 2, "steps_per_epoch": 16,
        "epoch_mode": "stream", "mesh": {"dp": 1}, "trace_epochs": 1,
        "data": {"generator": "lm_tokens", "seq_len": 8192,
                 "invalid_every": 5},
        "staging": {"kind": "etl_select", "workers": 2, "partitions": 4,
                    "shards": 2},
    }
    entry = next(w for w in real_bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["why"] == cell.workload["why"]
    assert {m["name"] for m in cell.end_to_end()} == {
        "train_samples_per_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer()}
    # ``<=``: a later metric of a layer every cell has joins this cell too.
    assert {"step.moe_ms", "moe.permute_ms", "moe.grouped_matmul_roofline",
            "moe.load_max_over_mean", "attention.kernel_roofline",
            "step.attention_ms", "step.mlp_ms", "step.head_ms",
            "step.embed_ms", "step.update_ms", "step.rest_ms", "model.mfu",
            "step.device_ms", "step.dispatch_share", "train_step_roofline",
            "device.peak_hbm_gib", "device.idle_share",
            "device.idle_unattributed_share", "infeed.wait_share",
            "infeed.put_share", "setup.ready_s", *NEW_METRICS} <= layers
    # It joins no list that another cell's test holds to that cell alone.
    assert not {"attention.latent_proj_ms", "moe.shared_ms", "step.hc_ms",
                "hc.mix_roofline", "loop.early_exits_ms"} & layers


@pytest.mark.parametrize("name,unit,source", [
    ("step.mtp_ms", "ms", "device_trace"),
    ("mtp.head_ms", "ms", "device_trace"),
    ("mtp.loss_over_main", "x", "program_counter"),
])
def test_the_new_metrics_are_in_this_cell(real_bench, name, unit, source):
    metric = next(m for m in real_bench["per_layer"] if m["name"] == name)
    assert CELL in metric["workloads"]
    assert (metric["unit"], metric["layer"], metric["source"]) == (
        unit, "model", source)
    assert metric["moves"] == "train_samples_per_s"
    assert os.path.exists(os.path.join(BENCH_DIR, "layers", name + ".py"))


def test_counts_against_hand_counts(cell):
    from raydp_tpu.utils.profiling import metrics

    m, sizes, traffic = cell.model, cell.sizes, cell.traffic
    d, f, fe, v, s = 2048, 10240, 1536, 19360, 8192
    q = d * 768 + 768 * 20 * 256
    kv = d * 576 + 512 * 20 * 448
    out = 20 * 256 * d
    assert (d * 768, 768 * 20 * 256, d * 576, 512 * 20 * 448, out) == (
        1_572_864, 3_932_160, 1_179_648, 4_587_520, 10_485_760)
    latent = q + kv + out + 768 + 512
    dense, expert, router = 3 * d * f, 3 * d * fe, d * 64
    assert (latent, dense, expert, router) == (
        21_759_232, 62_914_560, 9_437_184, 131_072)
    layer_dense = latent + 2 * d + dense
    layer_routed = latent + 2 * d + router + 9 * expert
    module = layer_routed + 2 * d * d + 3 * d
    assert (layer_dense, layer_routed, module) == (
        84_677_888, 106_829_056, 115_223_808)
    per = m.layer_params(sizes)
    assert (per["latent"], per["dense"], per["routed"], per["mtp"]) == (
        latent, layer_dense, layer_routed, module)
    main = layer_dense + 4 * layer_routed + 2 * v * d + d
    assert main == 591_294_720 and v * d == 39_649_280
    assert m.n_params(sizes) == main + module == 706_518_528
    assert 16 * (main + module) == pytest.approx(11.30e9, rel=1e-3)
    # A whole routed layer is 635.3M = 10.2 GB: eight chips share it.
    assert latent + 2 * d + router + 65 * expert == pytest.approx(
        635.31e6, rel=1e-4)

    metrics.gauge_set("moe/held_pairs_per_step", 0)
    tokens = 2 * s
    pairs = 5 * tokens * 4 * 8 / 64         # four layers and the module's
    assert m.held_pairs_per_step(sizes, traffic, 2) == pairs == 40960
    # The part ``moe_gmm`` is the STACK's: four fifths of the pairs.
    assert m.moe_flops_per_step(sizes, traffic, 2) == pytest.approx(
        3 * 0.8 * pairs * 2 * expert)
    per_token = (6 * (latent - 1280) + dense + 5 * (router + expert)
                 + 2 * d * d + 2 * d * v)
    # ISSUE 67: 352.6M active multiply-adds a token with the held experts.
    assert per_token + 4 / 8 * 5 * expert == pytest.approx(352.6e6, rel=1e-3)
    attn = 6 * 20 * 2 * (256 + 256) * s * (s + 1) / 2
    forward = 2 * (per_token * s + pairs / 2 * expert) + attn
    assert m.flops_per_sample(sizes, traffic) == pytest.approx(3 * forward)
    # Forward and backward of a step, nothing recomputed: 34.7 TFLOP of
    # matrices and 24.7 of attention (the issue's 72 count the
    # checkpointed second forward and the backward kernels' own 3.5x).
    assert 2 * m.flops_per_sample(sizes, traffic) == pytest.approx(
        59.4e12, rel=5e-3)
    try:
        metrics.gauge_set("moe/held_pairs_per_step", 50000)
        assert m.held_pairs_per_step(sizes, traffic, 2) == 50000
        assert m.moe_flops_per_step(sizes, traffic, 2) == pytest.approx(
            3 * 0.8 * 50000 * 2 * expert)
    finally:
        metrics.gauge_set("moe/held_pairs_per_step", 0)
    # S(S+1)/2 pairs x 20 heads x SIX layers x 2 sequences x 2 operations
    # x (256 + 256 forward; 3 x 256 + 2 x 256 backward).
    assert m.attention_flops_per_step(sizes, traffic, 2) == pytest.approx(
        6 * 2 * 20 * (s * (s + 1) / 2) * 2 * (512 + 1280))
    assert m.bytes_per_step(sizes, traffic, 2) == 32 * 706_518_528 + 8 * s


def test_builder_builds_the_published_block(cell):
    from raydp_tpu.models import MTPLM

    m, sizes = cell.model, cell.sizes
    kwargs = m.estimator_kwargs(sizes, cell.traffic, None)
    model = kwargs["model"]
    assert isinstance(model, MTPLM) and kwargs["loss"] == "mtp_ce"
    assert (model.mtp_config.depth, model.mtp_config.loss_weight) == (1, 0.3)
    cfg = model.cfg
    assert cfg.kinds == ("latent",) * 5
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 4
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_heads) == (
        2048, 10240, 1536, 20)
    lat = cfg.latent
    assert (lat.q_rank, lat.kv_rank, lat.nope_dim, lat.rope_dim, lat.v_dim,
            lat.yarn) == (768, 512, 192, 64, 256, None)
    assert lat.softmax_scale == 256 ** -0.5 and cfg.hyper is None
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.first_expert, moe.top_k,
            moe.shared_experts) == (64, 8, 0, 4, 1)
    assert (moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == ("sigmoid", True, True, 1.8)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    assert cfg.positions == "rotary" and cfg.rope_theta == 1e6
    assert cfg.norm_eps == 1e-5 and cfg.embed_init_std == 1.0
    assert not cfg.tie_head and not cfg.use_bias and cfg.remat
    assert cfg.vocab_size == 19360 and cfg.attention_impl == "flash"
    # The kernels take 256 / 256 at this length in the one backward kernel.
    from raydp_tpu.ops.flash_attention import backward_is_fused
    assert backward_is_fused(8192, 256, 256, 2)
    assert not backward_is_fused(16384, 256, 256, 2)


def test_new_readers_find_nothing_in_a_program_without_the_module(
    bench_modules, cell
):
    """What the parent's traced runs see with this PR's benchmark files
    laid over them: a profile with BERT's scopes has no ``mtp`` part, and
    a program that sets no such gauges gives no ratio."""
    from raydp_tpu.utils.profiling import metrics

    pt = importlib.import_module("program_trace")
    profile = pt.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "bert_base_fit_s128_parts.trace.json.gz"))
    with open(os.path.join(BENCH_DIR, "parts", "glm4_mtp_moe_lm.json")) as f:
        rules = json.load(f)
    summary, _ = pt.reduce_profile(profile, rules)
    parts = summary["parts_ms"]
    assert parts["mtp_block"] == parts["mtp_glue"] == parts["mtp_head"] == 0
    ghost = type(cell)(**{**cell.__dict__, "bench_dir": "/nonexistent/b"})
    facts = {"cell": ghost, "peaks": {"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9},
             "per_chip_batch": 2}
    with metrics._lock:
        kept = {k: metrics._gauges.pop(k, None)
                for k in ("train/loss_main", "train/loss_mtp")}
    try:
        for name in NEW_METRICS:
            assert cell.part("layers", name).read(facts) is None
        metrics.gauge_set("train/loss_main", 9.5)
        metrics.gauge_set("train/loss_mtp", 9.88)
        assert cell.part("layers", "mtp.loss_over_main").read(
            facts) == pytest.approx(1.04)
    finally:
        with metrics._lock:
            for k, v in kept.items():
                metrics._gauges.pop(k, None)
                if v is not None:
                    metrics._gauges[k] = v


def test_part_rules_partition_the_cells_scopes():
    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "glm4_mtp_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    jvp = "jit(train_step)/jvp(MTPLM)/"
    back = "jit(train_step)/transpose(jvp(MTPLM))/"
    want = {
        jvp + "encoder/tok_embed/take": "embed",
        jvp + "encoder/block_2/attn/jit(flash_attention)/pallas_call":
        "attention",
        back + "encoder/checkpoint/rematted_computation/block_1/attn/q_norm/"
        "mul": "attention",
        jvp + "encoder/block_0/ln_attn/mul": "attention",
        jvp + "encoder/block_3/moe/permute/sort": "moe_permute",
        back + "encoder/checkpoint/block_3/moe/unpermute/gather":
        "moe_permute",
        jvp + "encoder/block_2/moe/experts/jit(gmm)/pallas_call": "moe_gmm",
        back + "encoder/block_4/moe/experts/jit(tgmm)/pallas_call": "moe_gmm",
        jvp + "encoder/block_1/moe/shared/in/dot_general": "moe_shared",
        jvp + "encoder/block_4/moe/router/dot_general": "moe_rest",
        jvp + "encoder/block_4/ln_mlp/mul": "moe_rest",
        jvp + "encoder/block_0/ln_mlp/mul": "mlp",
        back + "encoder/checkpoint/rematted_computation/block_0/mlp_in/"
        "dot_general": "mlp",
        jvp + "encoder/ln_final/mul": "head",
        "jit(train_step)/jvp(part:loss)/main_head/lm_head/dot_general": "head",
        "jit(train_step)/jvp(part:loss)/main_head/reduce_sum": "head",
        # The module: first match wins, before the head and block rules.
        "jit(train_step)/jvp(part:loss)/mtp_head/lm_head/dot_general":
        "mtp_head",
        "jit(train_step)/jvp(part:loss)/mtp_head/exp": "mtp_head",
        "jit(train_step)/jvp(part:loss)/mtp_head/transpose(jvp(lm_head))/"
        "dot_general": "mtp_head",
        "jit(train_step)/jvp(part:loss)/main_head/jvp(lm_head)/dot_general":
        "head",
        jvp + "mtp/block/attn/jit(flash_attention)/pallas_call": "mtp_block",
        back + "mtp/checkpoint/rematted_computation/block/moe/experts/"
        "jit(gmm)/pallas_call": "mtp_block",
        back + "mtp/checkpoint/block/moe/shared/out/dot_general": "mtp_block",
        jvp + "mtp/block/ln_mlp/mul": "mtp_block",
        jvp + "mtp/embed/take": "mtp_glue",
        jvp + "mtp/enorm/mul": "mtp_glue",
        jvp + "mtp/hnorm/mul": "mtp_glue",
        back + "mtp/eh_proj/dot_general": "mtp_glue",
        jvp + "mtp/norm/mul": "mtp_glue",
        "jit(train_step)/part:update/mul": "update",
        "jit(train_step)/part:grad_norm/sqrt": "update",
        "": "rest",
    }
    for scope, part in want.items():
        assert pt.part_of(scope, rules) == part, scope
    assert {part for _, part in rules} == {
        "update", "embed", "attention", "moe_permute", "moe_gmm",
        "moe_shared", "moe_rest", "mlp", "head", "mtp_block", "mtp_glue",
        "mtp_head"}


def test_the_programs_scopes_are_the_rules(cell):
    """The scopes ISSUE 67 names are in the lowered tiny step."""
    import jax

    from raydp_tpu.models import step as model_step
    from raydp_tpu.train import losses

    model = cell.model.model(TINY)
    ids = jnp.zeros((2, 16), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    variables = model_step.parameters(jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), variables))

    def objective(v):
        preds, _ = model.apply(
            v, ids, mutable=model_step.SOWN,
            **model_step.apply_kwargs(model, jax.random.PRNGKey(1)))
        with jax.named_scope("part:loss"):
            return losses.mtp_crossentropy(preds, ids)

    text = jax.jit(jax.grad(objective)).lower(variables).as_text(
        debug_info=True)
    import re

    pt = importlib.import_module("program_trace")
    with open(os.path.join(BENCH_DIR, "parts", "glm4_mtp_moe_lm.json")) as f:
        rules = pt.compile_rules(json.load(f))
    scopes = set(re.findall(r'loc\("(jit\([^"]+)"', text))
    for scope in ("/mtp/enorm/", "/mtp/hnorm/", "/mtp/embed/", "/mtp/eh_proj/",
                  "/mtp/norm/", "/mtp/checkpoint/block/ln_attn/",
                  "/mtp/checkpoint/rematted_computation/block/attn/q_down/",
                  "/mtp/checkpoint/block/moe/router/",
                  "/mtp/checkpoint/block/moe/shared/in/",
                  "part:loss)/mtp_head/jvp(lm_head)/",
                  "part:loss)/mtp_head/transpose(jvp(lm_head))/",
                  "part:loss)/main_head/jvp(lm_head)/"):
        assert any(scope in each for each in scopes), scope
    # Every op of the module falls to one of its three parts, and the
    # stack's blocks to none of them.
    by_part = {}
    for each in scopes:
        by_part.setdefault(pt.part_of(each, rules), set()).add(each)
    assert {"mtp_block", "mtp_glue", "mtp_head", "head", "attention",
            "mlp", "moe_rest", "embed"} <= set(by_part)
    assert all("mtp" in each for part in ("mtp_block", "mtp_glue", "mtp_head")
               for each in by_part[part])
    assert not any("/mtp/" in each or "mtp_head" in each
                   for part, found in by_part.items()
                   if not part.startswith("mtp_") for each in found)


@pytest.fixture(scope="module")
def glm_tree(tiny_tree):
    """The tiny tree with a tiny copy of the cell added as files."""
    path = os.path.join("benchmark", "configs", "glm_tiny.json")
    with open(os.path.join(tiny_tree, path), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "glm_tiny", "source": "test", "file": path,
        "reduced": [], "why": "tiny preset for the CPU tests",
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_cell(tiny_tree, "glm_tiny.fit", CELL, "glm_tiny", {
        "seq_len": 16, "per_chip_batch": 2, "steps_per_epoch": 4,
        "data": {"generator": "lm_tokens", "seq_len": 16},
    })
    return tiny_tree


@pytest.mark.parametrize("trace", [1])
def test_tiny_cell_runs_end_to_end(bench_modules, glm_tree, trace):
    from raydp_tpu.utils.profiling import metrics

    out = bench_modules["run"].run_cell(
        glm_tree, "glm_tiny.fit", seed=3000000011, seconds=0.5,
        trace=trace, platform="cpu",
    )
    line = out["line"]
    assert line["correct"] is True, out["notes"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    checks = out["notes"]["checks"]
    assert checks["logits_match_reference"] is True
    assert checks["mtp_logits_match_reference"] is True
    assert checks["losses_match_reference"] is True
    assert out["notes"]["reference_check"][
        "max_abs_err_over_max_abs_ref"] < 1e-4
    detail = out["notes"]["heads_check"]
    assert max(detail["errors"].values()) < 1e-4
    assert 0.5 < detail["program"]["loss_mtp"] / detail["program"][
        "loss_main"] < 2
    assert metrics.gauge_value("mtp/depth") == 1
    assert metrics.gauge_value("mtp/params") == 41576
    assert metrics.gauge_value("attention/latent_layers") == 2
    assert metrics.gauge_value("moe/experts_held") == 2
    # 1 routed layer of the stack and the module's x 32 tokens x 2 experts.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 2 * 32 * 2
    # No TPU plane here: the trace-read metrics are left out.
    assert not {"step.mtp_ms", "mtp.head_ms"} & set(line["metrics"])
    assert 0.5 < line["metrics"]["mtp.loss_over_main"]["value"] < 2


@pytest.mark.parametrize("control,check", [
    ("trunk_float8", "mtp_logits_match_reference"),
])
def test_a_control_on_the_module_makes_the_run_incorrect(
        bench_modules, glm_tree, monkeypatch, control, check):
    """Through the job's own comparison: the same run with the reference's
    trunk in float8 (the precision below the stated one) ends as
    ``correct`` false by the MODULE's check while the main head's, which
    the harness compares, still passes (the wrong forms of the module go
    through the same ``check_heads`` in ``tests/test_mtp.py``; a flipped
    reference is the harness's own, shown by the other cells' tests)."""
    harness = bench_modules["harness"]
    load = harness.load_cell

    def with_control(root, name):
        cell = load(root, name)
        real = cell.model.check_heads
        how = {"trunk": jnp.float8_e4m3fn} if control == "trunk_float8" else {
            "depart": control}
        monkeypatch.setattr(
            cell.model, "check_heads",
            lambda lm, params, ids, sizes: real(lm, params, ids, sizes, **how))
        return cell

    monkeypatch.setattr(harness, "load_cell", with_control)
    out = bench_modules["run"].run_cell(
        glm_tree, "glm_tiny.fit", seed=3000000011, seconds=0.3,
        trace=0, platform="cpu",
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"][check] is False
    assert out["notes"]["checks"]["logits_match_reference"] is True
    assert out["notes"]["checks"]["losses_finite"] is True
    # The untraced run's line: the end-to-end metrics and no other.
    assert set(out["line"]["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert out["line"]["failed"] == 0 and out["line"]["attempted"] >= 1
