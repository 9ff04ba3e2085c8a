"""The Laguna-XS.2 style stack on the normal path, at tiny widths on the
CPU (hidden 64, heads of 16 over 2 key-value heads, 6 query heads in the
layers over all positions and 8 in the layers over a window of 8, half
the head rotated under YaRN in the first kind and all of it in the
second, a gate a head, 16 experts of width 32 of which a share is held
beside a shared expert, sequence 64, vocabulary 512), float32: the program
against the benchmark's plain reference (logits, loss, every gradient),
every departure the builder lists above its tolerance, the partial
rotation and the gate against a few lines of ``jax.numpy``, the eight
shares of a 256-expert layer plus the shared expert ONCE summing to the
uncut layer, the older families' blocks untouched, and one fit through
``JAXEstimator`` with the gauges and log lines of the built step."""
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import CausalLM, MoEConfig, MoELayer
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models.transformer import (
    MultiHeadAttention,
    TransformerConfig,
    WindowConfig,
    YarnScaling,
    laguna_xs_2,
    lfm2_8b_a1b,
    olmoe,
    rotary,
    xing4_0,
    yarn_inv_freq,
)
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
SIZES = {
    "builder": "laguna_window_moe_lm", "model_type": "laguna",
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_routed": 16,
    "first_expert": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "attention_impl": "dense", "remat": True, "compute_dtype": "float32",
    "param_dtype": "float32", "init": {"embedding_std": 1.0},
}


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there. Its
    blocks of query rows are cut to 16 so that the tiny sequence has four."""
    path = os.path.join(
        REPO, "benchmark", "configs", "laguna_window_moe_lm.py")
    spec = importlib.util.spec_from_file_location("laguna_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.QUERY_ROWS_AT_ONCE = 16
    return module


def _init(model, *args):
    return {"params": nn.unbox(
        model.init(jax.random.PRNGKey(0), *args))["params"]}


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
    model, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)

    def attention(heads):
        return {"q": {"kernel": (64, heads, 16)},
                "kv": {"kernel": (64, 2, 2, 16)},
                "gate": {"kernel": (64, heads)},
                "out": {"kernel": (heads, 16, 64)}}

    norms = {"ln_attn": {"scale": (64,)}, "ln_mlp": {"scale": (64,)}}
    dense = {"mlp_in": {"kernel": (64, 256)}, "mlp_out": {"kernel": (128, 64)}}
    # The router keeps its 16 outputs; 4 experts' weights and the whole
    # shared expert are here.
    routed = {"moe": {
        "router": {"kernel": (64, 16)}, "w_gate": (4, 64, 32),
        "w_up": (4, 64, 32), "w_down": (4, 32, 64),
        "shared": {"in": {"kernel": (64, 64)}, "out": {"kernel": (32, 64)}}}}
    # A module name a kind of layer: ``attn`` over all positions with 6
    # query heads, ``attn_window`` over the window with 8.
    full, slide = {"attn": attention(6)}, {"attn_window": attention(8)}
    assert tree["params"] == {
        "encoder": {
            "tok_embed": {"embedding": (512, 64)},
            "block_0": {**norms, **full, **dense},
            "block_1": {**norms, **slide, **routed},
            "block_2": {**norms, **slide, **routed},
            "block_3": {**norms, **slide, **routed},
            "block_4": {**norms, **full, **routed},
            "ln_final": {"scale": (64,)},
        },
        "lm_head": {"kernel": (64, 512)},
    }
    # No selection bias: no buffer beside the parameters.
    everything = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32)))
    assert moe_module.BUFFERS not in everything


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
    # 5 x (4 attention + 2 norms) + 2 dense + 4 x 6 routed + 3.
    assert seen == len(flat_want) == 59


DEPARTURES = [
    "no_window", "rotary_on_every_dim", "no_yarn", "no_head_gate",
    "gates_times_one", "window_heads_48",
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


@pytest.mark.parametrize("change", [
    {"model_type": "laguna2"}, {"attention_bias": True}, {"gating": False},
    {"tie_word_embeddings": True},
    {"moe_apply_router_weight_on_input": True},
    {"shared_expert_intermediate_size": 64}, {"num_attention_heads": 8},
    {"layer_types": ["full_attention"] * 4},
    {"mlp_layer_types": ["sparse", "dense", "sparse", "sparse", "sparse"]},
    {"num_attention_heads_per_layer": [6, 8, 8, 4, 6]},
], ids=lambda c: next(iter(c)))
def test_builder_refuses_what_it_does_not_write_down(builder, change):
    with pytest.raises(ValueError):
        builder.model_config({**SIZES, **change})


# ----------------------------------- the pieces against a few plain lines

def test_rotary_over_the_first_dims_of_a_head():
    """``dims=8`` of 16: features 0-3 pair with 4-7 at the 4 frequencies
    of an 8-wide head, features 8-15 pass through untouched."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 3, 16))
    pos = jnp.arange(12)[None, :]
    got = rotary(x, pos, 500000.0, dims=8)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(
        np.asarray(got[..., :8]),
        np.asarray(rotary(x[..., :8], pos, 500000.0)), rtol=1e-6)
    inv = 500000.0 ** (-np.arange(4) / 4)
    angle = np.arange(12)[:, None] * inv
    x1, x2 = np.asarray(x[0, :, 1, :4]), np.asarray(x[0, :, 1, 4:8])
    np.testing.assert_allclose(
        np.asarray(got[0, :, 1, :4]), x1 * np.cos(angle) - x2 * np.sin(angle),
        rtol=1e-4, atol=1e-5)
    # The whole head is the call it was.
    np.testing.assert_array_equal(
        np.asarray(rotary(x, pos, 1e4, dims=16)),
        np.asarray(rotary(x, pos, 1e4)))


def test_a_stated_attention_factor_is_the_rotations_stretch():
    yarn = YarnScaling(factor=64.0, original_max_len=4096, beta_fast=64.0,
                       beta_slow=1.0, attention_factor=1.25)
    assert yarn.stretch == 1.25
    derived = YarnScaling(factor=64.0, mscale=1.0, mscale_all_dim=0.0)
    assert derived.stretch == pytest.approx(0.1 * np.log(64.0) + 1.0)
    assert YarnScaling(factor=64.0, mscale=1.0,
                       mscale_all_dim=1.0).stretch == 1.0
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 2, 8))
    pos = jnp.arange(6)[None, :]
    plain = YarnScaling(factor=64.0, original_max_len=4096, beta_fast=64.0,
                        beta_slow=1.0, attention_factor=1.0)
    np.testing.assert_allclose(
        np.asarray(rotary(x, pos, 5e5, yarn)),
        1.25 * np.asarray(rotary(x, pos, 5e5, plain)), rtol=1e-5, atol=1e-6)
    # The published layer: frequencies 0-12 of 32 keep theta's, 28-31 are
    # slowed 64 times, a ramp between.
    pub = laguna_xs_2().rope_yarn
    got = yarn_inv_freq(32, 500000.0, pub)
    base = 500000.0 ** (-np.arange(32) / 32)
    ratio = got / base
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(
        1 / 64, rel=1e-4)
    assert np.all(np.diff(ratio) <= 1e-7)


def _one_layer(window, **kw):
    cfg = TransformerConfig(
        d_model=32, n_heads=6, n_kv_heads=2, head_size=8, n_layers=1,
        causal=True, positions="none", use_bias=False, dropout_rate=0.0,
        dtype=jnp.float32, window=WindowConfig(window=4, n_heads=4), **kw)
    return MultiHeadAttention(cfg, cfg.window if window else None)


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
def test_attention_with_a_head_size_a_gate_and_a_window(window):
    """48-over-2048 in small: 6 (or 4) heads of 8 over a hidden of 32, a
    sigmoid gate a head, the last 4 keys only in the window kind, against
    a loop over heads."""
    layer = _one_layer(window, head_gate=True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 10, 32))
    p = _init(layer, x)["params"]
    heads = 4 if window else 6
    assert p["q"]["kernel"].shape == (32, heads, 8)
    assert p["gate"]["kernel"].shape == (32, heads)
    got = np.asarray(layer.apply({"params": p}, x))
    want = np.zeros_like(got)
    i, j = np.arange(10)[:, None], np.arange(10)[None, :]
    see = (j <= i) & (j > i - 4) if window else (j <= i)
    for b in range(2):
        y = np.asarray(x[b], np.float64)
        gate = 1 / (1 + np.exp(-(y @ np.asarray(p["gate"]["kernel"]))))
        for h in range(heads):
            kv_head = h // (heads // 2)
            q = y @ np.asarray(p["q"]["kernel"][:, h])
            k = y @ np.asarray(p["kv"]["kernel"][:, 0, kv_head])
            v = y @ np.asarray(p["kv"]["kernel"][:, 1, kv_head])
            s = np.where(see, q @ k.T / np.sqrt(8), -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            a = (w / w.sum(-1, keepdims=True)) @ v
            want[b] += (gate[:, h:h + 1] * a) @ np.asarray(
                p["out"]["kernel"][h])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    ungated = _one_layer(window)
    assert "gate" not in _init(ungated, x)["params"]


def test_a_window_layer_has_no_cache_and_no_ring():
    layer = _one_layer(True)
    x = jnp.zeros((1, 8, 32))
    p = _init(layer, x)
    with pytest.raises(NotImplementedError, match="window"):
        layer.apply(p, x, cache_mode="prefill", mutable=["cache"])
    ring = MultiHeadAttention(
        TransformerConfig(
            d_model=32, n_heads=4, head_size=8, causal=True,
            positions="none", attention_impl="ring", dtype=jnp.float32,
            window=WindowConfig(window=4, n_heads=4)),
        WindowConfig(window=4, n_heads=4))
    with pytest.raises(NotImplementedError, match="window"):
        ring.init(jax.random.PRNGKey(0), x)
    assert not laguna_xs_2(n_layers=5).serves_from_kv_cache


def test_the_older_families_are_the_blocks_they_were():
    """No window layer, no gate, no head size of its own, the whole head
    rotated without scaling: what the other factories build."""
    for cfg in (olmoe(n_layers=1), lfm2_8b_a1b(n_layers=3),
                xing4_0(n_layers=3)):
        assert cfg.window is None and cfg.head_size is None
        assert cfg.rotary_dim is None and cfg.rope_yarn is None
        assert not cfg.head_gate and "window" not in cfg.kinds
        assert cfg.head_dim == cfg.d_model // cfg.n_heads
    cfg = laguna_xs_2()
    assert cfg.head_dim == 128 and cfg.d_model // cfg.n_heads != 128
    assert cfg.kinds == ("attention", "window", "window", "window") * 10
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 39
    assert (cfg.window.window, cfg.window.n_heads, cfg.window.rope_theta,
            cfg.window.rotary_dim) == (512, 64, 10000.0, None)
    assert (cfg.n_heads, cfg.kv_heads, cfg.rope_theta, cfg.rotary_dim) == (
        48, 8, 500000.0, 64)


# ------------------------------------------------------ the share test

def _layer(first=0, held=None, shared=1):
    return MoELayer(MoEConfig(
        d_model=16, d_ff=8, n_experts=256, top_k=8, aux_loss_weight=0.0,
        z_loss_weight=0.0, scoring="sigmoid", selection_bias=False,
        normalize_gates=True, gate_scale=2.5, first_expert=first,
        held_experts=held, shared_experts=shared, dtype=jnp.float32,
    ))


def _plain_layer(variables, x, experts, shared):
    """``sum_j g_j E_j(x)`` over ``experts`` plus the shared expert
    (where asked), by a loop over tokens in float64."""
    p = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), variables["params"])
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    out = np.zeros_like(tokens)
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    for t, y in enumerate(tokens):
        s = 1.0 / (1.0 + np.exp(-(y @ p["router"]["kernel"])))
        picked = np.argsort(-s, kind="stable")[:8]
        gates = s[picked] / (s[picked].sum() + 1e-6) * 2.5
        for e, g in zip(picked, gates):
            if e in experts:
                h = silu(y @ p["w_gate"][e]) * (y @ p["w_up"][e])
                out[t] += g * (h @ p["w_down"][e])
        if shared:
            gate, up = np.split(y @ p["shared"]["in"]["kernel"], 2)
            out[t] += (silu(gate) * up) @ p["shared"]["out"]["kernel"]
    return out.reshape(x.shape)


def _held_by(variables, first, held):
    return dict(variables, params=dict(
        variables["params"],
        **{w: variables["params"][w][first:first + held]
           for w in ("w_gate", "w_up", "w_down")}))


@pytest.mark.parametrize("shares", [8, 4], ids=lambda n: f"{n}_shares")
def test_the_shares_of_a_256_expert_layer_and_the_shared_expert_once_add_up(
    shares
):
    """Each of eight chips holds 32 of the 256 experts, routes over all
    256 (8 a token, sigmoid scores normalised and times 2.5, no selection
    bias) and returns its own experts' part plus the shared expert's
    output, which every chip computes alike. The routed parts and the
    shared expert counted ONCE sum to the uncut layer and to the plain
    loop."""
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 16))
    variables = _init(layer, x)
    held = 256 // shares
    whole = np.asarray(layer.apply(variables, x, mutable=[moe_module.STATS])[0])
    plain = _plain_layer(variables, x, set(range(256)), shared=True)
    shared = _plain_layer(variables, x, set(), shared=True)
    routed_parts = np.zeros_like(whole)
    for share in range(shares):
        first = share * held
        part = np.asarray(_layer(first, held).apply(
            _held_by(variables, first, held), x,
            mutable=[moe_module.STATS])[0])
        if share in (0, shares - 1):
            np.testing.assert_allclose(
                part, _plain_layer(
                    variables, x, set(range(first, first + held)), True),
                rtol=2e-4, atol=2e-5)
        routed_parts += part - shared
    np.testing.assert_allclose(routed_parts + shared, whole, rtol=2e-4,
                               atol=5e-5)
    np.testing.assert_allclose(routed_parts + shared, plain, rtol=2e-4,
                               atol=5e-5)


# ----------------------------------------------------------------- fit

def test_fit_trains_and_reports_the_new_gauges(builder, tiny, caplog):
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
    with caplog.at_level("INFO", logger="raydp_tpu.models.window"):
        est._init_state(rows[:8])
    lines = [r.getMessage() for r in caplog.records
             if r.name == "raydp_tpu.models.window"]
    assert len(lines) == 1
    for said in ("3 layers over the last 8 positions", "8 query heads over 2",
                 "16 of 16 features rotated at theta 10000",
                 "2 layers over all positions with 6 query heads",
                 "8 of 16 rotated at theta 500000", "YaRN x 64",
                 "rotation x 1.41589", "gate a head: True"):
        assert said in lines[0], (said, lines[0])
    before = jax.tree_util.tree_map(np.asarray, est._state.params)
    frame = pd.DataFrame({f"t{i}": rows[:, i] for i in range(SEQ)})
    history = est.fit_on_df(frame, num_epochs=3, num_shards=2)
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    after = jax.tree_util.tree_map(np.asarray, est._state.params)
    moved = [not np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(before["params"]),
        jax.tree_util.tree_leaves(after["params"]))]
    assert all(moved)
    assert metrics.gauge_value("attention/window_layers") == 3
    assert metrics.gauge_value("attention/window") == 8
    # Dense attention here: the flash kernels' gauges read 0.
    for gauge in ("attention/flash_live_tiles", "attention/flash_masked_tiles",
                  "attention/flash_window_live_tiles",
                  "attention/flash_window_masked_tiles",
                  "attention/latent_layers", "hc/streams", "conv/layers"):
        assert metrics.gauge_value(gauge) == 0, gauge
    assert metrics.gauge_value("moe/experts_routed") == 16
    assert metrics.gauge_value("moe/experts_held") == 4
    assert metrics.gauge_value("moe/shared_experts") == 1


def test_a_stack_without_window_layers_reports_zeros(caplog):
    from raydp_tpu.models import window
    from raydp_tpu.utils.profiling import metrics

    metrics.gauge_set("attention/window_layers", 7)
    with caplog.at_level("INFO", logger="raydp_tpu.models.window"):
        window.report(olmoe(n_layers=2))
        window.report(None)
    assert not caplog.records
    assert metrics.gauge_value("attention/window_layers") == 0
    assert metrics.gauge_value("attention/window") == 0


def test_flash_reports_the_two_kinds_of_layer_apart(monkeypatch, caplog):
    """The kernel is Mosaic-only, so the model's call runs it in the
    interpreter here; the gauges come from the shapes alone: at S = 256
    under a window of 128 the window layer's tiles are 128 wide."""
    import functools
    import sys

    from raydp_tpu.ops.flash_attention import tile_counts
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    module = sys.modules["raydp_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "flash_attention", functools.partial(
        module.flash_attention, interpret=True))
    cfg = laguna_xs_2(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, head_size=16,
        n_layers=2, d_ff=64, max_len=256, rotary_dim=8, n_experts=4,
        top_k=2, d_expert=16, attention_impl="flash", dtype=jnp.float32,
        window=WindowConfig(window=128, n_heads=2), rope_yarn=None,
    )
    assert cfg.kinds == ("attention", "window")
    est = JAXEstimator(
        model=CausalLM(cfg), optimizer=optax.adamw(2e-5), loss="lm_ce",
        feature_columns=["t"], batch_size=1, feature_dtype=np.int32, seed=0,
        aux_losses=True,
    )
    with caplog.at_level("INFO", logger="raydp_tpu.ops.flash_attention"):
        est._init_state(np.zeros((1, 256), np.int32))
    # The layer over all positions takes one 256-wide tile.
    assert metrics.gauge_value("attention/flash_live_tiles") == 1
    assert metrics.gauge_value("attention/flash_masked_tiles") == 1
    assert (metrics.gauge_value("attention/flash_window_live_tiles"),
            metrics.gauge_value("attention/flash_window_masked_tiles")) == (
        tile_counts(256, window=128)) == (3, 3)
    assert metrics.gauge_value("attention/window_layers") == 1
    assert metrics.gauge_value("attention/window") == 128
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 2
    assert "1 layers, S = 256 in 256 x 256 tiles, 1 live" in lines[0]
    assert "under a window of 128: 1 layers" in lines[1]
    assert "3 live a head and call, 3 of them masked" in lines[1]
    assert "2 kv steps a q tile, 2 q steps a kv tile" in lines[1]
    logits = est.predict(np.arange(256, dtype=np.int32)[None] % 64)
    assert np.isfinite(np.asarray(logits)).all()
