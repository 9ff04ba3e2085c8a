"""The Xing4.0-29B-A4B style stack on the normal path, at tiny widths on
the CPU (hidden 64, 4 heads of 16 + 8 for q and k and 16 for v from
latents of 24 and 16, four residual streams, 8 experts of width 32 of
which a share is held beside a shared expert, sequence 32, vocabulary
512), float32: the program against the benchmark's plain reference
(logits, loss, every gradient), every departure the builder lists above
its tolerance, YaRN's frequencies against a plain-numpy transcription, the
latent mixer against a loop over heads, the eight shares of one routed
layer plus the shared expert ONCE summing to the uncut layer, the older
families' blocks untouched, and one fit through ``JAXEstimator``."""
import importlib.util
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import CausalLM, MoEConfig, MoELayer, lfm2_8b_a1b, olmoe
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models.latent import LatentAttention, LatentConfig
from raydp_tpu.models.transformer import (
    YarnScaling,
    rotary,
    xing4_0,
    yarn_inv_freq,
    yarn_mscale,
)
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
SIZES = {
    "model_type": "xing4_0", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "n_routed_experts": 2, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2,
    "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
    "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0, "hc_phi_std": 0.5, "hc_bias_std": 1.0},
}
COLLECTIONS = ("params", moe_module.BUFFERS)


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "xing4_latent_moe_lm.py")
    spec = importlib.util.spec_from_file_location("xing4_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _init(model, *args):
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), *args))
    return {k: variables[k] for k in COLLECTIONS if k in variables}


@pytest.fixture(scope="module")
def tiny(builder):
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    return model, _init(model, ids), ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _logits(model, variables, ids):
    return model.apply(variables, ids, mutable=[moe_module.STATS])[0]


# ---------------------------------------------- program against reference

def test_parameter_tree_is_the_share(tiny):
    _, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)
    maps = {"phi": (4, 64, 24), "alpha": (3,), "bias": (24,)}
    attn = {"ln_attn": {"scale": (64,)}, "hc_attn": maps, "hc_ffn": maps,
            "ln_mlp": {"scale": (64,)}, "attn": {
        "q_down": {"kernel": (64, 24)}, "q_norm": {"scale": (24,)},
        "q_up": {"kernel": (24, 4, 24)},
        "kv_down": {"kernel": (64, 24)}, "kv_norm": {"scale": (16,)},
        "kv_up": {"kernel": (16, 4, 32)}, "out": {"kernel": (4, 16, 64)}}}
    dense = {"mlp_in": {"kernel": (64, 256)}, "mlp_out": {"kernel": (128, 64)}}
    # The router keeps its 8 outputs; 2 experts' weights and the whole
    # shared expert are here.
    routed = {"moe": {
        "router": {"kernel": (64, 8)}, "w_gate": (2, 64, 32),
        "w_up": (2, 64, 32), "w_down": (2, 32, 64),
        "shared": {"in": {"kernel": (64, 64)}, "out": {"kernel": (32, 64)}}}}
    assert tree["params"] == {
        "encoder": {
            "tok_embed": {"embedding": (512, 64)},
            "block_0": {**attn, **dense}, "block_1": {**attn, **routed},
            "block_2": {**attn, **routed}, "ln_final": {"scale": (64,)},
        },
        "lm_head": {"kernel": (64, 512)},
    }
    bias = {"moe": {"expert_bias": (8,)}}
    assert tree[moe_module.BUFFERS] == {"encoder": {
        "block_1": bias, "block_2": bias}}


def test_parameter_count_is_the_builders(builder, tiny):
    _, variables, _ = tiny
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(variables["params"]))
    assert builder.n_params(SIZES) == held


def test_logits_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    want = builder.reference_logits(variables, ids, SIZES)
    assert want.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(_logits(model, variables, ids), want) < 2e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny

    def loss(v):
        return lm_crossentropy(_logits(model, v, ids), ids)

    got_loss, got = jax.value_and_grad(loss)(variables)
    want_loss, want = builder.reference_loss_and_grads(variables, ids, SIZES)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["params"]))
    seen = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got["params"]):
        assert float(jnp.abs(flat_want[path]).max()) > 0, path
        assert _rel(g, flat_want[path]) < 5e-4, jax.tree_util.keystr(path)
        seen += 1
    assert seen == len(flat_want) == 62
    # The selection bias has no gradient by construction.
    for g in jax.tree_util.tree_leaves(got[moe_module.BUFFERS]):
        assert float(jnp.abs(g).max()) == 0.0


DEPARTURES = [
    "one_sinkhorn_round", "h_res_identity", "h_post_unscaled",
    "single_stream_residual", "no_shared_expert", "no_latent_norm",
    "rope_on_all_dims", "no_shared_rope_key", "scale_without_mscale",
    "plain_rope_no_yarn", "gates_times_one", "uncut_layer",
]


def test_the_departures_are_the_builders(builder):
    assert list(builder.DEPARTURES) == DEPARTURES
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(DEPARTURES)


@pytest.mark.parametrize("departure", DEPARTURES + ["8_bit_trunk"])
def test_tolerance_refuses_a_departure_from_the_mathematics(
    builder, tiny, departure
):
    model, variables, ids = tiny
    got = _logits(model, variables, ids)
    if departure == "8_bit_trunk":
        other = builder.reference_logits(
            variables, ids, SIZES, trunk=jnp.float8_e4m3fn)
    else:
        other = builder.reference_logits(
            variables, ids, SIZES, depart=departure)
    assert _rel(got, other) > builder.TOLERANCE


def test_an_unknown_departure_is_refused(builder, tiny):
    _, variables, ids = tiny
    with pytest.raises(ValueError, match="departure"):
        builder.reference_logits(variables, ids, SIZES, depart="no_such")


def test_builder_refuses_what_it_does_not_write_down(builder):
    for change in ({"scoring_func": "softmax"}, {"topk_method": "greedy"},
                   {"n_group": 8}, {"norm_topk_prob": False},
                   {"tie_word_embeddings": True}, {"model_type": "xing3"},
                   {"num_key_value_heads": 2}, {"attention_bias": True},
                   {"num_nextn_predict_layers": 1}):
        with pytest.raises(ValueError):
            builder.model_config(dict(SIZES, **change))
    with pytest.raises(ValueError):
        builder.model_config(dict(SIZES, rope_scaling=dict(
            SIZES["rope_scaling"], type="linear")))


# ------------------------------------------------------------- YaRN

def _plain_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` in plain numpy."""
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1 - ramp
    return inter * (1 - mask) + extra * mask


@pytest.mark.parametrize("dim,base,factor,original", [
    (64, 10000.0, 64.0, 4096), (8, 10000.0, 64.0, 16),
    (128, 1e6, 4.0, 8192), (64, 10000.0, 40.0, 4096),
])
def test_yarn_frequencies_against_a_plain_transcription(
    dim, base, factor, original
):
    yarn = YarnScaling(factor, original, 32.0, 1.0, 1.0, 1.0)
    got = yarn_inv_freq(dim // 2, base, yarn)
    want = _plain_yarn(dim, base, factor, original, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = base ** (-np.arange(dim // 2) / (dim // 2))
    # The fastest frequencies are the plain ones, the slowest those over
    # ``factor``, and the blend lies between.
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / factor, rel=1e-6)
    assert np.all(got <= plain * (1 + 1e-6))
    assert np.all(got >= plain / factor * (1 - 1e-6))


def test_the_published_rope_blends_over_frequencies_10_to_23():
    got = yarn_inv_freq(32, 10000.0, YarnScaling(64.0, 4096, 32.0, 1.0, 1, 1))
    plain = 10000.0 ** (-np.arange(32) / 32)
    same = np.isclose(got, plain, rtol=1e-6)
    over = np.isclose(got, plain / 64, rtol=1e-6)
    assert same[:11].all() and not same[11:].any()
    assert over[23:].all() and not over[:23].any()
    assert yarn_mscale(64.0, 1.0) == pytest.approx(1.41589, rel=1e-5)
    assert yarn_mscale(1.0, 1.0) == 1.0
    lat = LatentConfig(yarn=YarnScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0))
    assert lat.softmax_scale == pytest.approx(192 ** -0.5 * 1.41589 ** 2,
                                              rel=1e-5)
    assert LatentConfig().softmax_scale == pytest.approx(192 ** -0.5)
    assert lat.cache_bytes_per_token(5) == 5760


def test_rotary_with_yarn_against_a_plain_rotation():
    yarn = YarnScaling(64.0, 16, 32.0, 1.0, 1.0, 1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 8))
    pos = jnp.arange(12)[None, :]
    got = np.asarray(rotary(x, pos, 10000.0, yarn))
    freq = _plain_yarn(8, 10000.0, 64.0, 16, 32.0, 1.0)
    want = np.zeros_like(got)
    for t in range(12):
        for i in range(4):
            c, s = math.cos(t * freq[i]), math.sin(t * freq[i])
            a, b = np.asarray(x[:, t, :, i]), np.asarray(x[:, t, :, i + 4])
            want[:, t, :, i] = a * c - b * s
            want[:, t, :, i + 4] = b * c + a * s
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # Without ``yarn`` the function is the one it was.
    plain = np.asarray(rotary(x, pos, 10000.0))
    assert not np.allclose(plain, got)
    # mscale != mscale_all_dim stretches the rotation itself.
    stretched = rotary(x, pos, 10000.0, YarnScaling(64.0, 16, 32.0, 1, 1, 0))
    np.testing.assert_allclose(
        np.asarray(stretched), want * yarn_mscale(64.0, 1.0), rtol=1e-5,
        atol=1e-6)


# ----------------------------------------------------- the latent mixer

def test_latent_attention_against_a_loop_over_heads(builder):
    cfg = builder.model_config(SIZES)
    layer = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))
    p = nn.unbox(layer.init(jax.random.PRNGKey(2), x))["params"]
    got = np.asarray(layer.apply({"params": p}, x))
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    xs = np.asarray(x, np.float64)

    def rms(a):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6)

    c_q = rms(xs @ p["q_down"]["kernel"]) * p["q_norm"]["scale"]
    down = xs @ p["kv_down"]["kernel"]
    c_kv = rms(down[..., :16]) * p["kv_norm"]["scale"]
    freq = _plain_yarn(8, 10000.0, 64.0, 16, 32.0, 1.0)
    angle = np.arange(SEQ)[:, None] * freq

    def turn(a):
        a1, a2 = a[..., :4], a[..., 4:]
        return np.concatenate([a1 * np.cos(angle) - a2 * np.sin(angle),
                               a2 * np.cos(angle) + a1 * np.sin(angle)], -1)

    k_rope = turn(down[..., 16:])                   # ONE head's worth
    scale = 24 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    want = np.zeros_like(xs)
    mask = np.tril(np.ones((SEQ, SEQ), bool))
    for h in range(4):
        q = c_q @ p["q_up"]["kernel"][:, h]
        kv = c_kv @ p["kv_up"]["kernel"][:, h]
        s = (q[..., :16] @ kv[..., :16].transpose(0, 2, 1)
             + turn(q[..., 16:]) @ k_rope.transpose(0, 2, 1)) * scale
        s = np.where(mask, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        want += (w @ kv[..., 16:]) @ p["out"]["kernel"][h]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_latent_attention_only_where_it_is_written_down(builder):
    cfg = builder.model_config(SIZES)
    x = jnp.zeros((1, SEQ, 64))
    for change in ({"attention_impl": "ring"}, {"positions": "learned"},
                   {"causal": False}):
        with pytest.raises((NotImplementedError, ValueError)):
            LatentAttention(cfg.__class__(**{**cfg.__dict__, **change})).init(
                jax.random.PRNGKey(0), x)
    model = CausalLM(cfg)
    assert not cfg.serves_from_kv_cache
    with pytest.raises(NotImplementedError):
        model.init_cache(2)


# ------------------------------------------------------ the share test

def _layer(first=0, held=None, shared=1):
    return MoELayer(MoEConfig(
        d_model=16, d_ff=8, n_experts=8, top_k=2, aux_loss_weight=0.0,
        z_loss_weight=0.0, scoring="sigmoid", selection_bias=True,
        normalize_gates=True, gate_scale=2.0, first_expert=first,
        held_experts=held, shared_experts=shared, dtype=jnp.float32,
    ))


def _plain_layer(variables, x, experts, shared):
    """``sum_j g_j E_j(x)`` over ``experts`` plus the shared expert
    (where asked), by a loop over tokens in float64."""
    p = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), variables["params"])
    bias = np.asarray(variables[moe_module.BUFFERS]["expert_bias"], np.float64)
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    out = np.zeros_like(tokens)
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    for t, y in enumerate(tokens):
        s = 1.0 / (1.0 + np.exp(-(y @ p["router"]["kernel"])))
        picked = np.argsort(-(s + bias), kind="stable")[:2]
        gates = s[picked] / (s[picked].sum() + 1e-6) * 2.0
        for e, g in zip(picked, gates):
            if e in experts:
                h = silu(y @ p["w_gate"][e]) * (y @ p["w_up"][e])
                out[t] += g * (h @ p["w_down"][e])
        if shared:
            gate, up = np.split(y @ p["shared"]["in"]["kernel"], 2)
            out[t] += (silu(gate) * up) @ p["shared"]["out"]["kernel"]
    return out.reshape(x.shape)


def _held_by(variables, first, held=1):
    return dict(variables, params=dict(
        variables["params"],
        **{w: variables["params"][w][first:first + held]
           for w in ("w_gate", "w_up", "w_down")}))


@pytest.fixture(scope="module")
def uncut():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 16))
    variables = _init(layer, x)
    variables[moe_module.BUFFERS]["expert_bias"] = 0.5 * jax.random.normal(
        jax.random.PRNGKey(6), (8,))
    return layer, variables, x


def test_the_shared_expert_sees_every_token(uncut):
    layer, variables, x = uncut
    got = layer.apply(variables, x, mutable=[moe_module.STATS])[0]
    want = _plain_layer(variables, x, set(range(8)), shared=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    routed_only = {k: v for k, v in variables["params"].items()
                   if k != "shared"}
    without = _layer(shared=0).apply(
        dict(variables, params=routed_only), x, mutable=[moe_module.STATS])[0]
    np.testing.assert_allclose(
        np.asarray(without),
        _plain_layer(variables, x, set(range(8)), shared=False),
        rtol=2e-4, atol=2e-5)
    assert not np.allclose(np.asarray(without), np.asarray(got), atol=1e-3)


def test_the_eight_shares_and_the_shared_expert_once_add_up(uncut):
    """The share test: each of eight chips holds 1 of the 8 experts,
    routes over all 8 and returns its own expert's part plus the shared
    expert's output, which every chip computes alike. The routed parts and
    the shared expert counted ONCE sum to the uncut layer and to the plain
    loop; summing the shares as they are counts it eight times."""
    layer, variables, x = uncut
    whole = np.asarray(layer.apply(variables, x, mutable=[moe_module.STATS])[0])
    plain = _plain_layer(variables, x, set(range(8)), shared=True)
    shared = _plain_layer(variables, x, set(), shared=True)
    as_they_are = np.zeros_like(whole)
    routed_parts = np.zeros_like(whole)
    for share in range(8):
        part = np.asarray(_layer(share, 1).apply(
            _held_by(variables, share), x, mutable=[moe_module.STATS])[0])
        np.testing.assert_allclose(
            part, _plain_layer(variables, x, {share}, shared=True),
            rtol=2e-4, atol=2e-5)
        as_they_are += part
        routed_parts += part - shared
    np.testing.assert_allclose(routed_parts + shared, whole, rtol=2e-4,
                               atol=5e-5)
    np.testing.assert_allclose(routed_parts + shared, plain, rtol=2e-4,
                               atol=5e-5)
    np.testing.assert_allclose(as_they_are, whole + 7 * shared, rtol=2e-4,
                               atol=5e-5)


def test_a_layer_without_a_shared_expert_is_the_layer_it_was():
    """No parameter, no op: the jaxpr of LFM2's and OLMoE's layers names
    nothing of the shared expert."""
    for cfg in (lfm2_8b_a1b(n_layers=3), olmoe(n_layers=1)):
        moe = cfg.moe_config()
        assert moe.shared_experts == 0
        layer = MoELayer(MoEConfig(**{
            **moe.__dict__, "d_model": 16, "d_ff": 8, "n_experts": 8,
            "held_experts": None, "first_expert": 0, "top_k": 2,
            "dtype": jnp.float32}))
        x = jnp.ones((2, 4, 16))
        variables = _init(layer, x)
        assert "shared" not in variables["params"]
    assert xing4_0().moe_config().shared_experts == 1


# ----------------------------------------------------------------- fit

def test_fit_trains_and_reports_the_new_gauges(builder, tiny):
    import pandas as pd

    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    model, _, _ = tiny
    rows = np.random.default_rng(1).integers(0, 64, (32, SEQ)).astype(np.int32)
    est = JAXEstimator(
        model=model, optimizer=optax.adamw(3e-3), loss="lm_ce",
        self_supervised=True, aux_losses=True, batch_size=8, seed=3,
        epoch_mode="stream",
        feature_columns=[f"t{i}" for i in range(SEQ)], feature_dtype=np.int32,
    )
    est._init_state(rows[:8])
    before = jax.tree_util.tree_map(np.asarray, est._state.params)
    frame = pd.DataFrame({f"t{i}": rows[:, i] for i in range(SEQ)})
    history = est.fit_on_df(frame, num_epochs=3, num_shards=2)
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    after = jax.tree_util.tree_map(np.asarray, est._state.params)
    for a, b in zip(jax.tree_util.tree_leaves(before[moe_module.BUFFERS]),
                    jax.tree_util.tree_leaves(after[moe_module.BUFFERS])):
        np.testing.assert_array_equal(a, b)
    moved = [not np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(before["params"]),
        jax.tree_util.tree_leaves(after["params"]))]
    assert all(moved)
    assert metrics.gauge_value("hc/streams") == 4
    assert metrics.gauge_value("hc/sinkhorn_iters") == 20
    assert metrics.gauge_value("hc/sublayers") == 6
    assert metrics.gauge_value("attention/latent_layers") == 3
    assert metrics.gauge_value("attention/kv_latent_rank") == 16
    assert metrics.gauge_value(
        "attention/latent_cache_bytes_per_token") == (16 + 8) * 2 * 3
    assert metrics.gauge_value("moe/shared_experts") == 1
    assert metrics.gauge_value("moe/experts_held") == 2
    assert metrics.gauge_value("conv/layers") == 0
    assert 0 <= metrics.gauge_value("hc/res_row_sum_err_max") < 0.2
