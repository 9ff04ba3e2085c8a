"""The Nemotron-H style stack on the normal path, at tiny widths on the CPU
(hidden 64; every layer ONE sublayer in the pattern ``ME*ME``: a Mamba-2
mixer with 8 heads of 8 in 4 groups and a gated norm a group, grouped-query
attention 4/2 heads of 16 without positions, 16 ungated relu² experts of
width 32 of which a share is held beside a shared one of width 64; sequence
32, vocabulary 512), float32: the parameter tree of a layer of one
sublayer, the program against the benchmark's plain reference, whose scan
runs token by token (logits, loss, every gradient leaf), every departure
the builder lists and the lower precision against its tolerance, the
shares of one layer adding up to the uncut reference's layer, the scopes
and gauges a built step leaves, and the layer entries that keep their
meaning."""
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM, nemotron_3_nano_30b_a3b
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models import step as model_step
from raydp_tpu.models.moe import MoEConfig, MoELayer
from raydp_tpu.models.transformer import (
    NEMOTRON_H_PATTERN,
    TransformerBlock,
    TransformerConfig,
    hybrid_pattern_layers,
    tiny_transformer,
)
from raydp_tpu.train.losses import lm_crossentropy
from raydp_tpu.utils.profiling import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
SIZES = {
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
}
COLLECTIONS = ("params", moe_module.BUFFERS)


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(
        REPO, "benchmark", "configs", "nemotron_hybrid_moe_lm.py")
    spec = importlib.util.spec_from_file_location("nemotron_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _init(model, *args, seed=0):
    variables = jax.jit(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(seed), *args))
    )()
    return {k: variables[k] for k in COLLECTIONS if k in variables}


@pytest.fixture(scope="module")
def tiny(builder):
    """Model (the builder's: residual outputs scaled where they are
    drawn), seeded weights, ids of two sequences."""
    model = builder.deployed_model(SIZES)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    return model, _init(model, ids[:1]), ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def logits(builder, tiny):
    """(program, reference) logits of the seeded model."""
    model, variables, ids = tiny
    got = jax.jit(
        lambda v: model.apply(v, ids, mutable=[moe_module.STATS])[0]
    )(variables)
    return got, jax.jit(
        lambda v: builder.reference_logits(v, ids, SIZES))(variables)


# ------------------------------------------------ a layer of one sublayer

def test_parameter_tree_is_one_sublayer_a_layer(tiny):
    """One norm, one module and nothing else a layer, under the names a
    block of two has; an ungated expert has no ``w_gate`` and the shared
    expert's ``in`` is the up projection alone."""
    _, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)
    mamba = {"ln_mamba": {"scale": (64,)}, "mamba": {
        "in_proj": {"kernel": (64, 2 * 64 + 2 * 4 * 16 + 8)},
        "conv": {"kernel": (4, 64 + 2 * 4 * 16), "bias": (64 + 2 * 4 * 16,)},
        "ssd": {"A_log": (8,), "dt_bias": (8,), "D": (8,)},
        "gate_norm": {"scale": (64,)},
        "out_proj": {"kernel": (64, 64)},
    }}
    routed = {"ln_mlp": {"scale": (64,)}, "moe": {
        "router": {"kernel": (64, 16)},
        "w_up": (4, 64, 32), "w_down": (4, 32, 64),
        "shared": {"in": {"kernel": (64, 64)}, "out": {"kernel": (64, 64)}},
    }}
    attention = {"ln_attn": {"scale": (64,)}, "attn": {
        "q": {"kernel": (64, 4, 16)}, "kv": {"kernel": (64, 2, 2, 16)},
        "out": {"kernel": (4, 16, 64)},
    }}
    assert tree["params"] == {
        "encoder": {
            "tok_embed": {"embedding": (512, 64)},
            "block_0": mamba, "block_1": routed, "block_2": attention,
            "block_3": mamba, "block_4": routed,
            "ln_final": {"scale": (64,)},
        },
        "lm_head": {"kernel": (64, 512)},
    }
    assert tree[moe_module.BUFFERS] == {"encoder": {
        "block_1": {"moe": {"expert_bias": (16,)}},
        "block_4": {"moe": {"expert_bias": (16,)}},
    }}


def test_the_builder_scales_every_residual_output(builder):
    """``init.depth_scaled_outputs`` = 5: the four kinds of matrix that
    write into the residual stream are 5^-0.5 of the plain draw, and
    nothing else moves."""
    ids = jnp.zeros((1, SEQ), jnp.int32)
    scaled = _init(builder.deployed_model(SIZES), ids)
    plain = _init(CausalLM(builder.model_config(SIZES)), ids)
    flat = dict(jax.tree_util.tree_flatten_with_path(scaled)[0])
    moved = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(plain)[0]:
        ratio = float(jnp.max(jnp.abs(flat[path]))
                      / jnp.max(jnp.abs(leaf)))
        if abs(ratio - 1.0) > 1e-6:
            assert ratio == pytest.approx(5 ** -0.5, rel=1e-5)
            moved.add(tuple(k.key for k in path[3:]))
    assert moved == {
        ("mamba", "out_proj", "kernel"), ("attn", "out", "kernel"),
        ("moe", "w_down"), ("moe", "shared", "out", "kernel")}


@pytest.mark.parametrize("entries,want", [
    (("attention", "mamba:swiglu"),
     (("attention", "gelu"), ("mamba", "swiglu"))),
    (("mamba:none", "none:moe", "attention:none"),
     (("mamba", "none"), ("none", "moe"), ("attention", "none"))),
    (("conv:none", "none:swiglu"), (("conv", "none"), ("none", "swiglu"))),
    # An entry that names no FFN takes ``cfg.ffn``, as ever.
    (("none", "kda"), (("none", "gelu"), ("kda", "gelu"))),
])
def test_layer_entries_keep_their_meaning_and_may_name_one_sublayer(
        entries, want):
    cfg = tiny_transformer(n_layers=len(entries), layer_types=entries)
    assert cfg.layers == want
    assert cfg.kinds == tuple(m for m, _ in want)
    assert cfg.ffn_kinds == tuple(f for _, f in want)


@pytest.mark.parametrize("entries", [
    ("none:none",), ("mamba:nothing",), ("nothing:moe",),
    (":moe",), ("mamba:",),
])
def test_a_layer_names_at_least_one_sublayer_of_a_known_kind(entries):
    with pytest.raises(ValueError, match="does not name"):
        tiny_transformer(n_layers=1, layer_types=entries).layers


def test_the_published_pattern_is_one_sublayer_a_layer():
    cfg = nemotron_3_nano_30b_a3b()
    assert cfg.n_layers == 52 == len(NEMOTRON_H_PATTERN)
    assert (cfg.kinds.count("mamba"), cfg.ffn_kinds.count("moe"),
            cfg.kinds.count("attention")) == (23, 23, 6)
    assert all((m == "none") != (f == "none") for m, f in cfg.layers)
    assert hybrid_pattern_layers("M*E") == (
        "mamba:none", "attention:none", "none:moe")
    with pytest.raises(ValueError, match="hybrid pattern"):
        hybrid_pattern_layers("M-E")
    assert (cfg.ssm_groups, cfg.ssm_chunk, cfg.expert_form, cfg.positions,
            cfg.shared_experts * cfg.d_expert) == (8, 128, "relu2", "none",
                                                   3712)


@pytest.mark.parametrize("mixer,ffn,adds,norms", [
    ("mamba", "none", 1, ["ln_mamba"]),
    ("none", "swiglu", 1, ["ln_mlp"]),
    ("attention", "none", 1, ["ln_attn"]),
    ("attention", "swiglu", 2, ["ln_attn", "ln_mlp"]),
])
def test_a_block_of_one_sublayer_has_one_norm_and_one_add(mixer, ffn, adds,
                                                          norms):
    cfg = tiny_transformer(
        d_model=32, n_heads=2, d_ff=64, norm="rmsnorm", causal=True,
        positions="none", use_bias=False, dtype=jnp.float32, ssm_heads=4,
        ssm_head_dim=8, ssm_state=8, ssm_chunk=8,
    )
    block = TransformerBlock(cfg, mixer, ffn)
    x = jnp.ones((1, 8, 32))
    variables = nn.unbox(block.init(jax.random.PRNGKey(0), x))
    assert sorted(k for k in variables["params"] if k.startswith("ln_")) == (
        norms)
    text = str(jax.make_jaxpr(lambda v, x: block.apply(v, x))(variables, x))
    # The residual adds are the block's only [1, 8, 32] adds of two
    # [1, 8, 32] operands at its top level.
    top = [line for line in text.split("\n")
           if line.startswith("    ") and not line.startswith("     ")]
    assert sum(
        1 for line in top if ":f32[1,8,32] = add " in line
    ) == adds


# ---------------------------------------------- program against reference

def test_logits_match_the_plain_reference(logits):
    got, want = logits
    assert got.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(got, want) < 2e-5


@pytest.fixture(scope="module")
def gradients(builder, tiny):
    model, variables, ids = tiny
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        out = model.apply(
            {"params": params, **rest}, ids, mutable=[moe_module.STATS])[0]
        return lm_crossentropy(out, ids)

    got = jax.jit(jax.value_and_grad(loss))(variables["params"])
    want = jax.jit(
        lambda v: builder.reference_loss_and_grads(v, ids, SIZES)
    )(variables)
    return got, (want[0], want[1]["params"])


def _leaves(tree):
    return {
        "/".join(k.key for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


GRADIENT_LEAVES = sorted(
    [f"encoder/block_{i}/{leaf}" for i in (0, 3) for leaf in (
        "ln_mamba/scale", "mamba/in_proj/kernel", "mamba/conv/kernel",
        "mamba/conv/bias", "mamba/ssd/A_log", "mamba/ssd/dt_bias",
        "mamba/ssd/D", "mamba/gate_norm/scale", "mamba/out_proj/kernel")]
    + [f"encoder/block_{i}/{leaf}" for i in (1, 4) for leaf in (
        "ln_mlp/scale", "moe/router/kernel", "moe/w_up", "moe/w_down",
        "moe/shared/in/kernel", "moe/shared/out/kernel")]
    + [f"encoder/block_2/{leaf}" for leaf in (
        "ln_attn/scale", "attn/q/kernel", "attn/kv/kernel",
        "attn/out/kernel")]
    + ["encoder/tok_embed/embedding", "encoder/ln_final/scale",
       "lm_head/kernel"]
)


def test_loss_matches_and_every_leaf_has_a_gradient(gradients):
    (got, grads), (want, want_grads) = gradients
    assert abs(float(got) - float(want)) < 1e-5
    assert sorted(_leaves(grads)) == sorted(_leaves(want_grads)) == (
        GRADIENT_LEAVES)


@pytest.mark.parametrize("leaf", GRADIENT_LEAVES)
def test_a_gradient_leaf_matches_the_plain_reference(gradients, leaf):
    (_, grads), (_, want_grads) = gradients
    g, w = _leaves(grads)[leaf], _leaves(want_grads)[leaf]
    assert float(jnp.max(jnp.abs(w))) > 0
    assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * float(
        jnp.max(jnp.abs(w))) + 1e-8


def test_the_departures_are_the_builders_list(builder):
    assert set(builder.DEPARTURES) == {
        "whole_axis_gate_norm", "one_group", "relu_unsquared",
        "rotary_attention", "gates_times_one", "no_conv_bias",
        "independent_chunks", "no_shared_expert"}
    assert set(builder.UNSEEN_ON_THE_CHIP) == {"gates_times_one"}
    with pytest.raises(ValueError, match="unknown departure"):
        builder.reference_logits({}, None, SIZES, depart="no_such_thing")


@pytest.mark.parametrize("depart", [
    "whole_axis_gate_norm", "one_group", "relu_unsquared",
    "rotary_attention", "gates_times_one", "no_conv_bias",
    "independent_chunks", "no_shared_expert",
])
def test_tolerance_refuses_a_departure_from_the_mathematics(
        builder, tiny, logits, depart):
    """Each departure moves the float32 reference's logits by more than
    the cell's tolerance, where the program's own are 1e-6 from it."""
    _, variables, ids = tiny
    _, want = logits
    moved = _rel(jax.jit(lambda v: builder.reference_logits(
        v, ids, SIZES, depart=depart))(variables), want)
    assert moved > builder.TOLERANCE


def test_a_lower_precision_than_stated_fails_the_tolerance(builder, tiny,
                                                           logits):
    """The lower-precision negative: the configuration states float32 here,
    and the PROGRAM run with a bfloat16 trunk (what the cell states on the
    chip, one precision below this test's) is outside the comparison that
    holds the float32 program to 2e-5; the reference with its trunk in
    float8_e4m3 (one precision below the cell's) is outside the cell's
    own tolerance."""
    model, variables, ids = tiny
    _, want = logits
    import dataclasses

    lower = model.clone(cfg=dataclasses.replace(
        model.cfg, dtype=jnp.bfloat16))
    got = jax.jit(
        lambda v: lower.apply(v, ids, mutable=[moe_module.STATS])[0]
    )(variables)
    assert _rel(got, want) > 100 * 2e-5
    moved = _rel(jax.jit(lambda v: builder.reference_logits(
        v, ids, SIZES, trunk=jnp.float8_e4m3fn))(variables), want)
    assert moved > builder.TOLERANCE
    rounded = _rel(jax.jit(lambda v: builder.reference_logits(
        v, ids, SIZES, trunk=jnp.bfloat16))(variables), want)
    assert 1e-4 < rounded < moved


def test_remat_changes_nothing_but_memory(tiny):
    import dataclasses

    model, variables, ids = tiny
    assert model.cfg.remat and all(model.cfg.checkpointed)
    plain = model.clone(cfg=dataclasses.replace(model.cfg, remat=False))

    def loss(m):
        return jax.jit(jax.value_and_grad(lambda p: lm_crossentropy(
            m.apply({**variables, "params": p}, ids,
                    mutable=[moe_module.STATS])[0], ids)))(
            variables["params"])

    (a, ga), (b, gb) = loss(model), loss(plain)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=1e-7)


# --------------------------------------------- the shares of one layer

def _layer_sizes(held, first):
    return {**SIZES, "n_routed_experts": held, "first_expert": first}


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_references_layer(builder, shares):
    """16 relu² experts in 2 shares (and 4): each share's layer holds its
    experts, routes over all 16 and returns its own experts' part plus the
    shared expert's output, which every chip computes alike; the routed
    parts and the shared expert counted ONCE sum to the UNCUT reference's
    layer (the builder's plain loop over all 16 experts)."""
    experts, held = 16, 16 // shares
    cfg = MoEConfig(
        d_model=64, d_ff=32, n_experts=experts, top_k=3, scoring="sigmoid",
        selection_bias=True, normalize_gates=True, gate_scale=2.5,
        shared_experts=2, expert_form="relu2", aux_loss_weight=0.0,
        z_loss_weight=0.0, dtype=jnp.float32,
    )
    y = jax.random.normal(jax.random.PRNGKey(5), (SEQ, 64))
    whole = _init(MoELayer(cfg), y, seed=3)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (experts,))
    whole[moe_module.BUFFERS]["expert_bias"] = bias
    assert set(whole["params"]) == {"router", "w_up", "w_down", "shared"}
    identity = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut = builder._routed(
            whole["params"], bias, y, _layer_sizes(experts, 0), identity,
            None)
        alike = uncut - builder._routed(
            whole["params"], bias, y, _layer_sizes(experts, 0), identity,
            "no_shared_expert")
    total, pairs = jnp.zeros_like(uncut), 0.0
    import dataclasses

    for first in range(0, experts, held):
        share = dataclasses.replace(
            cfg, first_expert=first, held_experts=held)
        params = dict(whole["params"])
        for name in ("w_up", "w_down"):
            params[name] = whole["params"][name][first:first + held]
        part, sown = MoELayer(share).apply(
            {"params": params, moe_module.BUFFERS: whole[moe_module.BUFFERS]},
            y, mutable=[moe_module.STATS])
        # ... and each share is the reference GIVEN THAT SHARE.
        with jax.default_matmul_precision("highest"):
            want = builder._routed(
                params, bias, y, _layer_sizes(held, first), identity, None)
        np.testing.assert_allclose(part, want, rtol=2e-4, atol=2e-5)
        pairs += float(sown[moe_module.STATS]["held_tokens"].sum())
        total = total + (part - alike)
    assert pairs == SEQ * 3                     # every pair on one share
    np.testing.assert_allclose(total + alike, uncut, rtol=2e-4, atol=2e-5)


# ------------------------------------------------- scopes and gauges

@pytest.fixture(scope="module")
def lowered(tiny):
    model, variables, ids = tiny
    return jax.jit(
        lambda v: model.apply(v, ids, mutable=[moe_module.STATS])[0]
    ).lower(variables).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "block_0/ln_mamba", "block_0/mamba/in_proj", "block_0/mamba/conv",
    "block_0/mamba/ssd", "block_0/mamba/gate_norm", "block_0/mamba/out_proj",
    "block_1/ln_mlp", "block_1/moe/router", "block_1/moe/permute",
    "block_1/moe/experts", "block_1/moe/unpermute", "block_1/moe/shared",
    "block_2/ln_attn", "block_2/attn", "block_3/mamba/ssd",
    "block_4/moe/shared",
])
def test_the_layers_name_their_scopes(lowered, scope):
    assert f"{scope}/" in lowered


@pytest.mark.parametrize("absent", [
    "block_0/ln_mlp", "block_0/moe", "block_1/ln_mamba", "block_1/attn",
    "block_2/ln_mlp", "block_2/mamba", "w_gate",
])
def test_a_layer_of_one_sublayer_has_no_scope_of_the_other(lowered, absent):
    assert absent not in lowered


@pytest.mark.parametrize("gauge,value", [
    ("stack/layers", 5), ("stack/sublayers", 5),
    ("stack/mixer_only_layers", 3), ("stack/ffn_only_layers", 2),
    ("ssm/layers", 2), ("ssm/groups", 4), ("ssm/gate_norm_group_size", 16),
    ("ssm/chunks_per_step", 2 * 2 * SEQ // 8),
    ("moe/experts_routed", 16), ("moe/experts_held", 4),
    ("moe/expert_matrices", 2),
    ("moe/shared_experts", 2),
])
def test_the_gauges_of_a_built_step(tiny, gauge, value):
    model, variables, ids = tiny
    model_step.report(model, variables, ids)
    assert metrics.gauge_value(gauge) == value


def test_the_stack_gauges_of_a_block_of_two_and_of_no_stack():
    from raydp_tpu.models import olmoe
    from raydp_tpu.models.transformer import report

    report(olmoe(n_layers=2))
    assert (metrics.gauge_value("stack/layers"),
            metrics.gauge_value("stack/sublayers"),
            metrics.gauge_value("stack/mixer_only_layers"),
            metrics.gauge_value("stack/ffn_only_layers")) == (2, 4, 0, 0)
    moe_module.report(CausalLM(olmoe(n_layers=2)), tokens_per_step=64)
    assert metrics.gauge_value("moe/expert_matrices") == 3
    report(None)
    assert metrics.gauge_value("stack/layers") == 0


def test_the_block_checkpoint_rule_reads_a_layer_of_one_sublayer(tiny):
    """``block_bytes`` traces a block of one sublayer as a block: what it
    holds released is more than its input, under the checkpoint its input
    alone (dense attention names nothing)."""
    model, variables, ids = tiny
    cfg = model.cfg
    surveyed = model_step.survey(model, variables, ids)
    assert sorted(surveyed.blocks) == [f"block_{i}" for i in range(5)]
    x = surveyed.blocks["block_0"]
    for i, layer in enumerate(cfg.layers):
        own = {name: model_step._under(tree, f"block_{i}")
               for name, tree in variables.items()
               if model_step._under(tree, f"block_{i}") is not None}
        counted = model_step.block_bytes(cfg, *layer, own, x)
        assert counted.checkpointed == x.size * 4
        assert counted.working > counted.released > counted.checkpointed


def test_a_config_field_hands_the_form_on():
    cfg = TransformerConfig(expert_form="relu2", ffn="moe", n_experts=4,
                            top_k=2, d_expert=8)
    assert cfg.moe_config().expert_form == "relu2"
    assert TransformerConfig().moe_config().expert_form == "swiglu"
    assert MoEConfig().expert_weights == ("w_gate", "w_up", "w_down")
    assert MoEConfig(expert_form="relu2").expert_weights == (
        "w_up", "w_down")
    with pytest.raises(ValueError, match="unknown expert_form"):
        MoEConfig(expert_form="gelu").expert_weights
