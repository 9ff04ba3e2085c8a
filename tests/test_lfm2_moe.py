"""The LFM2-8B-A1B style sparse hybrid on the normal path, at tiny widths
on the CPU (hidden 64, 4 query / 2 key-value heads of 16, 8 experts of
width 32 of which a share is held, sequence 32, vocabulary 512): the
program against the benchmark's plain reference (logits, loss, gradients),
the short convolution against a token-by-token loop, sigmoid-bias-
normalised routing against a plain loop, the four shares of one routed
layer summing to the uncut layer, shares that receive no pair or every
pair, the per-layer pattern of mixers and FFN kinds, per-head QK-norm, a
selection bias that training leaves as it was, the old families untouched,
and no serving from a K/V cache the stack does not have."""
import dataclasses
import functools
import importlib.util
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import (
    CausalLM,
    MoEConfig,
    MoELayer,
    bert_base,
    granite_h_micro,
    lfm2_8b_a1b,
    olmoe,
)
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models import stats as stats_module
from raydp_tpu.models.mamba import CausalConv1d, causal_depthwise_conv
from raydp_tpu.models.shortconv import ShortConv
from raydp_tpu.models.transformer import MultiHeadAttention, rotary
from raydp_tpu.ops.attention import reference_attention
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
SIZES = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "max_position_embeddings": 64, "norm_eps": 1e-5, "rope_theta": 1000000,
    "conv_L_cache": 3, "conv_bias": False, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "num_experts": 2, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
}
COLLECTIONS = ("params", moe_module.BUFFERS)


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "lfm2_moe_lm.py")
    spec = importlib.util.spec_from_file_location("lfm2_builder", path)
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
    conv = {"ln_conv": {"scale": (64,)}, "conv": {
        "in_proj": {"kernel": (64, 192)}, "conv": {"kernel": (3, 64)},
        "out_proj": {"kernel": (64, 64)}}}
    attn = {"ln_attn": {"scale": (64,)}, "attn": {
        "q": {"kernel": (64, 4, 16)}, "kv": {"kernel": (64, 2, 2, 16)},
        "q_norm": {"scale": (16,)}, "k_norm": {"scale": (16,)},
        "out": {"kernel": (4, 16, 64)}}}
    dense = {"ln_mlp": {"scale": (64,)}, "mlp_in": {"kernel": (64, 256)},
             "mlp_out": {"kernel": (128, 64)}}
    # The router keeps its 8 outputs; the weights of 2 experts are here.
    routed = {"ln_mlp": {"scale": (64,)}, "moe": {
        "router": {"kernel": (64, 8)}, "w_gate": (2, 64, 32),
        "w_up": (2, 64, 32), "w_down": (2, 32, 64)}}
    assert tree["params"] == {"encoder": {
        "tok_embed": {"embedding": (512, 64)},
        "block_0": {**conv, **dense}, "block_1": {**conv, **routed},
        "block_2": {**attn, **routed}, "block_3": {**conv, **routed},
        "ln_final": {"scale": (64,)},
    }}
    bias = {"moe": {"expert_bias": (8,)}}
    assert tree[moe_module.BUFFERS] == {"encoder": {
        "block_1": bias, "block_2": bias, "block_3": bias}}


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
    for path, g in jax.tree_util.tree_leaves_with_path(got["params"]):
        assert _rel(g, flat_want[path]) < 2e-4, jax.tree_util.keystr(path)
    # The selection bias has no gradient by construction.
    for g in jax.tree_util.tree_leaves(got[moe_module.BUFFERS]):
        assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("departure", [
    "no_expert_bias", "softmax_scores", "gates_not_normalised",
    "no_head_qk_norm", "conv_no_b_gate", "conv_no_c_gate", "conv_two_taps",
    "uncut_layer", "8_bit_trunk",
])
def test_tolerance_refuses_a_departure_from_the_mathematics(
    builder, tiny, departure
):
    model, variables, ids = tiny
    got = _logits(model, variables, ids)
    if departure == "8_bit_trunk":
        other = builder.reference_logits(
            variables, ids, SIZES, trunk=jnp.float8_e4m3fn)
    else:
        assert departure in builder.DEPARTURES
        other = builder.reference_logits(
            variables, ids, SIZES, depart=departure)
    assert _rel(got, other) > builder.TOLERANCE


# ------------------------------------------------- the short convolution

def test_short_conv_against_a_token_by_token_loop():
    cfg = lfm2_8b_a1b(d_model=16, n_heads=2, n_kv_heads=1, n_layers=1,
                      dtype=jnp.float32)
    op = ShortConv(cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 16))
    params = nn.unbox(op.init(jax.random.PRNGKey(2), u))["params"]
    got = op.apply({"params": params}, u)
    w_in = np.asarray(params["in_proj"]["kernel"], np.float64)
    w_out = np.asarray(params["out_proj"]["kernel"], np.float64)
    kernel = np.asarray(params["conv"]["kernel"], np.float64)     # [3, D]
    assert kernel.shape == (3, 16) and set(params) == {
        "in_proj", "conv", "out_proj"}
    want = np.zeros((2, 9, 16))
    for b in range(2):
        last = [np.zeros(16), np.zeros(16)]         # (B * x) at t-2, t-1
        for t in range(9):
            gate_b, gate_c, x = np.split(
                np.asarray(u[b, t], np.float64) @ w_in, 3)
            bx = gate_b * x
            z = kernel[0] * last[0] + kernel[1] * last[1] + kernel[2] * bx
            last = [last[1], bx]
            want[b, t] = (gate_c * z) @ w_out
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_the_convolution_is_the_one_mamba_runs():
    """``CausalConv1d`` (Mamba-2's 4 taps, bias, SiLU) is the shared
    function plus its epilogue, bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 8))
    conv = CausalConv1d(4, jnp.float32, jnp.float32)
    params = conv.init(jax.random.PRNGKey(4), x)
    p = nn.unbox(params)["params"]
    want = jax.nn.silu(causal_depthwise_conv(x, p["kernel"], p["bias"]))
    np.testing.assert_array_equal(conv.apply(params, x), want)
    # Causal: position t sees nothing after t.
    y = causal_depthwise_conv(x, p["kernel"])
    moved = causal_depthwise_conv(x.at[:, 7:].set(0.0), p["kernel"])
    np.testing.assert_array_equal(y[:, :7], moved[:, :7])
    assert y.dtype == jnp.float32


# ------------------------------------------------------------- routing

def _layer(first=0, held=None, **kw):
    defaults = dict(
        d_model=16, d_ff=8, n_experts=8, top_k=2, scoring="sigmoid",
        selection_bias=True, normalize_gates=True, aux_loss_weight=0.0,
        z_loss_weight=0.0, first_expert=first, held_experts=held,
        dtype=jnp.float32,
    )
    defaults.update(kw)
    return MoELayer(MoEConfig(**defaults))


FORMS = sorted(moe_module.EXPERT_FORMS)


@pytest.fixture(scope="module", params=FORMS)
def uncut(request):
    """One uncut routed layer (SwiGLU experts, and ungated relu² ones: the
    cases below run through both forms) with seeded weights and a
    selection bias large enough to change what some tokens get."""
    layer = _layer(expert_form=request.param)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 16))
    variables = _init(layer, x)
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (8,))
    variables[moe_module.BUFFERS]["expert_bias"] = bias
    return layer, variables, x


def _held_by(variables, first, held=2):
    """``variables`` with the weights of experts [first, first + held)."""
    return dict(variables, params=dict(
        variables["params"],
        **{w: variables["params"][w][first:first + held]
           for w in ("w_gate", "w_up", "w_down") if w in variables["params"]}))


def _form_of(variables) -> str:
    """The form of the experts whose stacked weights ``variables`` hold."""
    return "swiglu" if "w_gate" in variables["params"] else "relu2"


def _plain_routed(variables, x, experts, scale=1.0, top_k=2, shared=False,
                  scoring="sigmoid"):
    """``sum_j g_j E_j(x)`` over ``experts`` by a loop over tokens (plus
    the shared expert's output with ``shared``). ``scoring`` softmax has
    no selection bias."""
    p = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), variables["params"])
    bias = 0.0 if scoring == "softmax" else np.asarray(
        variables[moe_module.BUFFERS]["expert_bias"], np.float64)
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    out, chosen, unbiased = np.zeros_like(tokens), [], []

    def swiglu(h, up):
        return h / (1.0 + np.exp(-h)) * up

    def hidden(y, e):
        if "w_gate" in p:
            return swiglu(y @ p["w_gate"][e], y @ p["w_up"][e])
        return np.maximum(y @ p["w_up"][e], 0.0) ** 2

    for t, y in enumerate(tokens):
        s = y @ p["router"]["kernel"]
        if scoring == "softmax":
            s = np.exp(s - s.max())
            s = s / s.sum()
        else:
            s = 1.0 / (1.0 + np.exp(-s))
        picked = np.argsort(-(s + bias), kind="stable")[:top_k]
        chosen.append(set(picked))
        unbiased.append(set(np.argsort(-s, kind="stable")[:top_k]))
        gates = s[picked] / (s[picked].sum() + 1e-6) * scale
        for e, g in zip(picked, gates):
            if e in experts:
                out[t] += g * (hidden(y, e) @ p["w_down"][e])
        if shared:
            h = y @ p["shared"]["in"]["kernel"]
            h = swiglu(*np.split(h, 2)) if "w_gate" in p else (
                np.maximum(h, 0.0) ** 2)
            out[t] += h @ p["shared"]["out"]["kernel"]
    return out.reshape(x.shape), chosen, unbiased


def test_sigmoid_bias_normalised_routing_against_a_plain_loop(uncut):
    layer, variables, x = uncut
    got = layer.apply(variables, x, mutable=[moe_module.STATS])[0]
    want, chosen, unbiased = _plain_routed(variables, x, set(range(8)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
    # The bias changed the selection of some token, and only the selection:
    # the gates above are the scores.
    assert any(c != u for c, u in zip(chosen, unbiased))
    scaled = _layer(gate_scale=2.5, expert_form=_form_of(variables)).apply(
        variables, x, mutable=[moe_module.STATS])[0]
    np.testing.assert_allclose(
        np.asarray(scaled), 2.5 * want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("experts,held,top_k,shared,scale,scoring", [
    (8, 2, 2, 0, 1.0, "sigmoid"),          # LFM2's four shares (PR 32)
    (256, 8, 8, 1, 2.446, "sigmoid"),      # Kimi Linear's thirty-two (PR 44)
    (128, 16, 8, 0, 1.0, "softmax"),       # SDAR's eight (PR 47)
], ids=["four_of_8", "thirty_two_of_256_and_a_shared_expert",
        "eight_of_128_by_renormalised_softmax"])
@pytest.mark.parametrize("form", FORMS)
def test_the_shares_add_up_to_the_uncut_layer(experts, held, top_k, shared,
                                              scale, scoring, form):
    """The share test: each chip holds ``held`` of the experts, routes
    over all of them, and returns its own experts' part (plus the shared
    expert's output, which every chip computes alike); the routed parts
    and the shared expert counted ONCE sum to what the uncut layer, and
    the plain loop, give."""
    kw = dict(n_experts=experts, top_k=top_k, gate_scale=scale,
              shared_experts=shared, scoring=scoring,
              selection_bias=scoring == "sigmoid", expert_form=form)
    layer = _layer(**kw)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 7, 16))
    variables = _init(layer, x)
    if scoring == "sigmoid":
        variables[moe_module.BUFFERS]["expert_bias"] = (
            0.5 * jax.random.normal(jax.random.PRNGKey(6), (experts,)))
    else:
        # The published block, a renormalised softmax of 128 logits
        # (``norm_topk_prob``): no bias, no buffer beside the parameters.
        assert moe_module.BUFFERS not in variables
    plain = functools.partial(
        _plain_routed, variables, x, scale=scale, top_k=top_k,
        scoring=scoring)
    whole = layer.apply(variables, x, mutable=[moe_module.STATS])[0]
    alike = plain(set(), shared=True)[0] if shared else 0.0
    total, pairs = jnp.zeros_like(whole), 0.0
    for first in range(0, experts, held):
        part, sown = _layer(first, held, **kw).apply(
            _held_by(variables, first, held), x, mutable=[moe_module.STATS])
        want, _, _ = plain(set(range(first, first + held)),
                           shared=bool(shared))
        np.testing.assert_allclose(
            np.asarray(part), want, rtol=2e-4, atol=2e-5)
        stats = sown[moe_module.STATS]
        np.testing.assert_array_equal(
            stats["held_tokens"],
            stats["expert_tokens"][first:first + held])
        pairs += float(stats["held_tokens"].sum())
        total = total + (part - alike)
    assert pairs == 3 * 7 * top_k               # every pair on one share
    total = total + alike
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(whole), rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(total), plain(set(range(experts)), shared=bool(shared))[0],
        rtol=2e-4, atol=5e-5)


def test_a_shares_gradients_are_the_plain_formulas(uncut):
    """Through the sort, the kernels and both masked row moves: the
    gradients of a share's part with respect to its input, its router and
    its experts are autodiff's of every token through both held experts
    times the choice mask."""
    _, variables, x = uncut
    first = 4
    mine = _held_by(variables, first)["params"]
    bias = variables[moe_module.BUFFERS]

    def program(params, x):
        out, _ = _layer(first, 2, expert_form=_form_of(variables)).apply(
            {"params": params, moe_module.BUFFERS: bias}, x,
            mutable=[moe_module.STATS])
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    def plain(params, x):
        tokens = x.reshape(-1, 16)
        s = jax.nn.sigmoid(tokens @ params["router"]["kernel"])
        ranked = s + bias["expert_bias"]
        mask = jnp.argsort(jnp.argsort(-ranked, -1, stable=True), -1) < 2
        g = jnp.where(mask, s, 0.0)
        g = (g / (g.sum(-1, keepdims=True) + 1e-6))[:, first:first + 2]
        h = jnp.einsum("td,edf->tef", tokens, params["w_up"])
        if "w_gate" in params:
            h = h * jax.nn.silu(
                jnp.einsum("td,edf->tef", tokens, params["w_gate"]))
        else:
            h = jnp.square(jax.nn.relu(h))
        out = jnp.einsum("tef,efd,te->td", h, params["w_down"], g)
        out = out.reshape(x.shape)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    got = jax.grad(program, argnums=(0, 1))(mine, x)
    want = jax.grad(plain, argnums=(0, 1))(mine, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        assert _rel(a, b) < 2e-4


@pytest.mark.parametrize("case", ["no_pair", "every_pair"])
def test_an_empty_and_a_full_share_lose_nothing(uncut, case):
    """A bias that sends no token, or every token's both choices, to the
    share's two experts: the output is zero, or the whole layer's."""
    _, variables, x = uncut
    first = 6
    bias = jnp.zeros((8,)).at[first:first + 2].set(
        -10.0 if case == "no_pair" else 10.0)
    biased = dict(variables, **{moe_module.BUFFERS: {"expert_bias": bias}})
    mine = _held_by(biased, first)

    form = _form_of(variables)

    def part(v):
        return _layer(first, 2, expert_form=form).apply(
            v, x, mutable=[moe_module.STATS])

    out, sown = part(mine)
    held = sown[moe_module.STATS]["held_tokens"]
    grads = jax.grad(lambda v: jnp.sum(part(v)[0] ** 2))(mine)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))
    if case == "no_pair":
        assert float(held.sum()) == 0
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert float(jnp.abs(grads["params"]["w_down"]).max()) == 0.0
    else:
        assert float(held.sum()) == 3 * 7 * 2
        whole = _layer(expert_form=form).apply(
            biased, x, mutable=[moe_module.STATS])[0]
        want, _, _ = _plain_routed(biased, x, {first, first + 1})
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(whole), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


def _share_and_grads(variables, x, first=4):
    """A share's part, what it sowed, and the gradients of a weighted sum
    of it with respect to its input, its router and its stacked weights
    (three, or two for an ungated expert)."""
    layer = _layer(first, 2, expert_form=_form_of(variables))

    def part(v, x):
        out, sown = layer.apply(v, x, mutable=[moe_module.STATS])
        return jnp.sum(
            out * jnp.cos(jnp.arange(out.size).reshape(out.shape))
        ), (out, sown[moe_module.STATS])

    (d_v, d_x), (out, stats) = jax.grad(
        part, argnums=(0, 1), has_aux=True)(variables, x)
    p = d_v["params"]
    return out, stats, (
        d_x, p["router"]["kernel"],
        *(p[w] for w in moe_module.EXPERT_FORMS[_form_of(variables)]))


@pytest.mark.parametrize("routing,rows", [
    ("as_drawn", "below"), ("as_drawn", "at"), ("as_drawn", "above"),
    ("every_pair", "below"), ("every_pair", "tile"), ("no_pair", "tile"),
])
def test_a_compact_share_is_the_whole_share(uncut, monkeypatch, routing, rows):
    """The expert path over ``C`` rows against the same path over all
    ``T·k`` (what the layer was before it had a ``C``): output and every
    gradient equal, with ``C`` below, at and above the pairs that land on
    the share's experts. Below, the layer-step takes all the rows inside
    the guard and says so; nothing is dropped in any case."""
    from raydp_tpu.utils.profiling import metrics

    _, variables, x = uncut
    first, pairs = 4, 3 * 7 * 2
    if routing != "as_drawn":
        bias = jnp.zeros((8,)).at[first:first + 2].set(
            10.0 if routing == "every_pair" else -10.0)
        variables = dict(
            variables, **{moe_module.BUFFERS: {"expert_bias": bias}})
    mine = _held_by(variables, first)
    assert moe_module.compact_rows(_layer(first, 2).cfg, 3 * 7) == pairs
    want_out, stats, want = _share_and_grads(mine, x)
    assert len(want) == 2 + len(moe_module.EXPERT_FORMS[_form_of(mine)])
    assert "overflow" not in stats              # C = T·k: no guard
    held = int(stats["held_tokens"].sum())
    if routing == "as_drawn":
        assert 2 < held < pairs - 3
    else:
        assert held == (pairs if routing == "every_pair" else 0)
    c = {"below": held - 2, "at": held, "above": held + 3, "tile": 8}[rows]
    monkeypatch.setattr(moe_module, "compact_rows", lambda cfg, n: c)
    got_out, stats, got = _share_and_grads(mine, x)
    assert float(stats["overflow"]) == (held > c)
    assert float(jnp.abs(want_out).max()) > 0 or routing == "no_pair"
    np.testing.assert_allclose(
        np.asarray(got_out), np.asarray(want_out), rtol=1e-6, atol=1e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6,
            atol=1e-6 * max(1.0, float(jnp.abs(b).max())))
    if routing == "no_pair":
        np.testing.assert_array_equal(np.asarray(got_out), 0.0)
    if routing == "every_pair":
        whole = _layer(expert_form=_form_of(variables)).apply(
            variables, x, mutable=[moe_module.STATS])[0]
        np.testing.assert_allclose(
            np.asarray(got_out), np.asarray(whole), rtol=2e-4, atol=2e-5)
    if held:                    # what an epoch of one such step reports
        moe_module.report_epoch(
            {"aux_loss": 0.0, **jax.device_get(stats)}, n_steps=1)
        assert metrics.gauge_value("moe/overflow_layer_steps") == (held > c)


def test_compact_rows_follow_the_share_and_the_row_tile():
    """One and a half times the pairs uniform routing sends to the held
    experts, in whole row tiles of the grouped matmul, never above
    ``T·k``; all of them where every expert is held."""
    cell = lfm2_8b_a1b(n_layers=7, experts_held=8).moe_config()
    assert (cell.n_experts, cell.top_k, cell.held) == (32, 4, 8)
    assert moe_module.compact_rows(cell, 8192) == 12288
    assert moe_module.compact_rows(cell, 1000) == 1536      # 1,500 -> 3 tiles
    assert moe_module.compact_rows(cell, 100) == 400        # T·k
    half = MoEConfig(n_experts=8, top_k=2, held_experts=4)
    assert moe_module.compact_rows(half, 4096) == 6144      # 0.75 T·k
    assert moe_module.compact_rows(
        MoEConfig(n_experts=8, top_k=2, held_experts=6), 4096) == 8192
    assert moe_module.compact_rows(olmoe().moe_config(), 8192) == 65536


def test_the_compact_path_keeps_its_place_in_the_part_rules(
    tiny, monkeypatch
):
    """The benchmark splits a step by op name (``benchmark/parts/
    lfm2_moe_lm.json``, first match wins) and wants ``moe/permute``,
    ``moe/unpermute`` and ``moe/experts/jit(gmm)`` ADJACENT: JAX writes
    ``cond/branch_N_fun`` into the name of what a branch holds, so the
    compact path — every gather and kernel a step runs — stands outside
    the guard, and only the guard's rows-for-all remainder carries it."""
    with open(os.path.join(REPO, "benchmark", "parts", "lfm2_moe_lm.json")) as f:
        rules = [(re.compile(pattern), part) for pattern, part in json.load(f)]

    def part_of(name):
        return next((p for rule, p in rules if rule.search(name)), "rest")

    model, variables, ids = tiny
    pairs = ids.size * SIZES["num_experts_per_tok"]
    monkeypatch.setattr(moe_module, "compact_rows", lambda cfg, n: pairs // 2)
    text = jax.jit(jax.grad(
        lambda v: lm_crossentropy(_logits(model, v, ids), ids)
    )).lower(variables).as_text(debug_info=True)
    # Whole op names. A kernel is ``pallas_call`` inside its ``jit(gmm)``
    # or ``jit(tgmm)``, which the text names where it is called.
    names = set(re.findall(r'loc\("(jit\([^"]+)"', text))
    assert 'loc("pallas_call"' in text
    names |= {n + "/pallas_call" for n in names
              if re.search(r"/jit\(t?gmm\)$", n)}
    # ``ops/rows_to_tokens.py``'s kernel, inside its ``jit`` as the
    # grouped matmuls are inside theirs.
    sums = {n for n in names if n.endswith("/jit(_token_sums)")}
    routed = {n for n in names if re.search(r"/block_\d+/moe/", n)}
    guard = {n for n in routed if "/moe/cond/branch_" in n}
    path = routed - guard
    assert not [n for n in names - guard if "cond/branch_" in n]
    assert sums and sums <= path
    # The guard holds a whole expert path of its own, forward and backward,
    # and none of it counts as a row move or a kernel of the step.
    for op in ("gather", "jit(gmm)/pallas_call", "jit(tgmm)/pallas_call"):
        assert [n for n in guard if n.endswith(op)], op
    assert {part_of(n) for n in guard} == {"moe_rest"}
    # The compact path: kernels and gathers where rules 6 and 7 look.
    kernels = {n for n in path if n.endswith("/pallas_call")}
    assert kernels and {part_of(n) for n in kernels} == {"moe_gmm"}
    gathers = {n for n in path if n.endswith("/gather")}
    assert {part_of(n) for n in gathers} == {"moe_permute"}
    # The forward, its second run (the blocks are checkpointed; the way
    # back to tokens is not needed again) and the backward.
    forward, again, backward = (
        "/encoder/block_", "/rematted_computation/block_", "/checkpoint/block_")
    # Since PR 54 the way back to tokens (forward) and the cotangent of the
    # way there (backward) are ``ops/rows_to_tokens.py``'s kernel, a row
    # move like the gathers it took the place of.
    assert {part_of(n) for n in sums} == {"moe_permute"}
    for found, op, passes in (
        (kernels, "jit(gmm)", (forward, again, backward)),
        (kernels, "jit(tgmm)", (backward,)),
        (gathers, "/moe/permute/", (forward, again)),
        (gathers, "/moe/unpermute/", (backward,)),
        (sums, "/moe/permute/", (backward,)),
        (sums, "/moe/unpermute/", (forward,)),
    ):
        assert {p for p in (forward, again, backward)
                if [n for n in found if op in n and p in n]} == set(passes), op
    assert {part_of(n) for n in path} == {
        "moe_permute", "moe_gmm", "moe_rest"}


def test_every_part_rule_counts_the_token_sums_as_row_moves(tiny, monkeypatch):
    """The kernel's calls as a step names them (the forward, the forward
    run again inside a checkpointed block, the backward; a chip's share
    inside the exchange's ``shard_map`` too) against every
    ``benchmark/parts/*.json`` that splits the routed layer, read as the
    files are: wherever a file would count a grouped matmul of the same
    layer and pass as ``moe_gmm`` it counts the token sum as
    ``moe_permute``, so ``moe.permute_ms`` keeps reading the row moves and
    ``moe.grouped_matmul_roofline`` its kernels alone."""
    import glob

    model, variables, ids = tiny
    pairs = ids.size * SIZES["num_experts_per_tok"]
    monkeypatch.setattr(moe_module, "compact_rows", lambda cfg, n: pairs // 2)
    text = jax.jit(jax.grad(
        lambda v: lm_crossentropy(_logits(model, v, ids), ids)
    )).lower(variables).as_text(debug_info=True)
    found = {n[:-len("/jit(_token_sums)")]
             for n in re.findall(r'loc\("(jit\([^"]+)"', text)
             if n.endswith("/jit(_token_sums)")}
    forward = {n for n in found if "/checkpoint/" not in n}
    assert {n.rsplit("/", 1)[1] for n in forward} == {"unpermute"}
    assert {n.rsplit("/", 1)[1] for n in found - forward} == {"permute"}
    again = {n.replace("/encoder/", "/encoder/checkpoint/"
                       "rematted_computation/") for n in forward}
    scopes = found | again
    scopes |= {n.replace("/moe/", "/moe/shard_map/") for n in scopes}
    files = 0
    for path in sorted(glob.glob(
            os.path.join(REPO, "benchmark", "parts", "*.json"))):
        with open(path) as f:
            rules = [(re.compile(a), b) for a, b in json.load(f)]
        if not {"moe_permute", "moe_gmm"} <= {part for _, part in rules}:
            continue
        files += 1

        def part_of(name):
            return next((p for rule, p in rules if rule.search(name)), "rest")

        seen = 0
        for scope in scopes:
            twin = scope.rsplit("/", 1)[0] + "/experts/jit(gmm)/pallas_call"
            if part_of(twin) != "moe_gmm":
                continue        # a pass or a wrapper this file's model lacks
            seen += 1
            for call in ("/pallas_call", "/rows_to_tokens"):
                assert part_of(scope + "/jit(_token_sums)" + call) == (
                    "moe_permute"), (path, scope)
        assert seen, path
    assert files >= 8


def test_the_selection_bias_is_drawn_to_balance_the_init_sample():
    """A router of random weights on a Zipf-distributed corpus: without a
    bias the fullest expert gets several times the emptiest's tokens;
    under ``balancing_bias`` every expert is picked by ``k / E`` of the
    tokens to a few percent, on the sample and nearly so on a fresh one."""
    rng = np.random.default_rng(0)
    vocab, d, e, k, t = 4096, 64, 16, 2, 4096
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -1.0)
    emb = rng.standard_normal((vocab, d)).astype(np.float32)
    router = rng.uniform(-1, 1, (d, e)).astype(np.float32) * np.sqrt(
        6 / (d + e))

    def scores(n):
        ids = np.searchsorted(cdf, rng.random(n) * cdf[-1]).clip(0, vocab - 1)
        # A hidden state that depends on the token and on its neighbour,
        # as behind a short convolution: identical tokens do not move as
        # one block between experts.
        x = emb[ids] + 0.5 * emb[np.roll(ids, 1)]
        x = x / np.sqrt((x * x).mean(-1, keepdims=True))
        return jax.nn.sigmoid(jnp.asarray(x @ router))

    def loads(s, b):
        _, picked = jax.lax.top_k(s + b, k)
        counts = np.bincount(np.asarray(picked).ravel(), minlength=e)
        return counts / counts.mean()

    sample, fresh = scores(t), scores(4 * t)
    bias = moe_module.balancing_bias(sample, k)
    assert bias.shape == (e,) and abs(float(bias.mean())) < 1e-6
    plain = loads(sample, 0.0)
    assert plain.max() / plain.min() > 2.0
    assert np.abs(loads(sample, bias) - 1).max() < 0.05
    assert np.abs(loads(fresh, bias) - 1).max() < 0.25
    # A quarter of the experts get a quarter of the pairs.
    quarter = abs(loads(fresh, bias)[:4].mean() - 1)
    assert quarter < 0.05 and 2 * quarter < abs(
        loads(fresh, 0.0)[:4].mean() - 1)
    # The layer draws it from the tokens ``init`` is given.
    layer = _layer(n_experts=e, top_k=k, d_model=d)
    x = jnp.asarray(emb[:512])
    drawn = layer.init(jax.random.PRNGKey(0), x)[moe_module.BUFFERS][
        "expert_bias"]
    assert drawn.shape == (e,) and float(jnp.abs(drawn).max()) > 0


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="routed over"):
        MoEConfig(n_experts=8, first_expert=6, held_experts=4).held
    with pytest.raises(ValueError, match="scoring"):
        _layer(scoring="tanh").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 16)))
    assert MoEConfig(n_experts=8).held == 8


def test_olmoes_layer_is_the_layer_it_was():
    """Softmax scores used as they are, no bias, no buffer collection, all
    experts held, both auxiliary terms sown: the defaults."""
    cfg = olmoe(n_layers=1).moe_config()
    assert (cfg.scoring, cfg.selection_bias, cfg.normalize_gates,
            cfg.gate_scale, cfg.first_expert, cfg.held) == (
        "softmax", False, False, 1.0, 0, 64)
    assert (cfg.aux_loss_weight, cfg.z_loss_weight) == (1e-2, 1e-3)
    layer = MoELayer(MoEConfig(d_model=16, d_ff=8, n_experts=4, top_k=2,
                               dtype=jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 16))
    variables = layer.init(jax.random.PRNGKey(8), x)
    assert set(variables) == {"params", "losses", moe_module.STATS}
    assert set(variables[moe_module.STATS]) == {"expert_tokens"}
    sown = stats_module.step_stats(variables)
    assert set(sown) == {"expert_tokens"}
    assert set(moe_module.with_aux_loss(sown, variables)) == {
        "aux_loss", "expert_tokens"}
    assert moe_module.with_aux_loss({}, variables) == {}


# ------------------------------------------------- the per-layer pattern

def test_layer_types_name_mixer_and_ffn_kind():
    cfg = lfm2_8b_a1b()
    assert len(cfg.layers) == 24
    assert [i for i, k in enumerate(cfg.kinds) if k == "attention"] == [
        2, 6, 10, 14, 18, 21]
    assert cfg.kinds.count("conv") == 18
    assert cfg.ffn_kinds == ("swiglu",) * 2 + ("moe",) * 22
    assert lfm2_8b_a1b(n_layers=7).layers[:3] == (
        ("conv", "swiglu"), ("conv", "swiglu"), ("attention", "moe"))
    # An entry without a kind takes the configuration's ``ffn``.
    mixed = dataclasses.replace(
        cfg, n_layers=2, layer_types=("conv", "attention:swiglu"))
    assert mixed.layers == (("conv", "moe"), ("attention", "swiglu"))
    assert bert_base().layers == (("attention", "gelu"),) * 12
    assert granite_h_micro().ffn_kinds == ("swiglu",) * 40
    assert olmoe().layers == (("attention", "moe"),) * 16
    for bad in (("conv:relu",), ("linear:moe",), ("conv:moe:x",), ("conv",) * 2):
        with pytest.raises(ValueError, match="layer_types"):
            dataclasses.replace(cfg, n_layers=1, layer_types=bad).layers
    assert not cfg.serves_from_kv_cache


def test_per_head_qk_norm_against_three_lines_of_jnp():
    cfg = lfm2_8b_a1b(d_model=32, n_heads=4, n_kv_heads=2, n_layers=1,
                      max_len=16, dtype=jnp.float32)
    attn = MultiHeadAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 32))
    params = nn.unbox(attn.init(jax.random.PRNGKey(10), x))["params"]
    scale_q = 1.0 + 0.1 * jnp.arange(8.0)
    params = dict(params, q_norm={"scale": scale_q},
                  k_norm={"scale": scale_q[::-1]})
    assert params["q"]["kernel"].shape == (32, 4, 8)

    def rms(a, w):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * w

    q = rms(jnp.einsum("bsd,dhk->bshk", x, params["q"]["kernel"]), scale_q)
    kv = jnp.einsum("bsd,dthk->bsthk", x, params["kv"]["kernel"])
    k = rms(kv[:, :, 0], scale_q[::-1])
    pos = jnp.arange(6)[None]
    ctx = reference_attention(
        rotary(q, pos, 1e6), rotary(k, pos, 1e6), kv[:, :, 1], causal=True)
    want = jnp.einsum("bshk,hkd->bsd", ctx, params["out"]["kernel"])
    got = attn.apply({"params": params}, x)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # The whole-projection norm is still ``True`` (OLMoE) or "projection".
    whole = dataclasses.replace(cfg, qk_norm="projection")
    shapes = jax.eval_shape(
        lambda: MultiHeadAttention(whole).init(jax.random.PRNGKey(0), x))
    assert nn.unbox(shapes)["params"]["q_norm"]["scale"].shape == (32,)
    assert olmoe().qk_norm is True
    with pytest.raises(ValueError, match="qk_norm"):
        MultiHeadAttention(dataclasses.replace(cfg, qk_norm="rows")).init(
            jax.random.PRNGKey(0), x)


def test_no_serving_from_a_cache_the_stack_does_not_have(tiny):
    model, variables, ids = tiny
    for call in (
        lambda: model.apply(variables, ids, jnp.array([4, 4]),
                            method=CausalLM.prefill, mutable=["cache"]),
        lambda: model.init_cache(2),
    ):
        with pytest.raises(NotImplementedError):
            call()


# ------------------------------------------------------ the normal path

def test_fit_trains_and_leaves_the_selection_bias_as_drawn(builder, tiny):
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
        assert np.abs(a).max() > 0
    moved = [not np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(before["params"]),
        jax.tree_util.tree_leaves(after["params"]))]
    assert all(moved)
    # Gauges: static where the step is built, routing per epoch.
    assert metrics.gauge_value("conv/layers") == 3
    assert metrics.gauge_value("conv/taps") == 3
    assert metrics.gauge_value("moe/experts_routed") == 8
    assert metrics.gauge_value("moe/experts_held") == 2
    # 1.5 x 512 x 2 / 8 = 192 rows, in row tiles of 512: all 512 pairs.
    assert metrics.gauge_value("moe/compact_rows") == 8 * SEQ * 2
    assert metrics.gauge_value("moe/overflow_layer_steps") == 0
    assert metrics.gauge_value("ssm/layers") == 0
    pairs = metrics.gauge_value("moe/expert_tokens_per_step")
    assert pairs == 3 * 8 * SEQ * 2
    held = metrics.gauge_value("moe/held_pairs_per_step")
    assert 0 < held < pairs
    assert metrics.gauge_value("moe/held_pair_share") == pytest.approx(
        held / pairs)
    assert metrics.gauge_value("moe/load_max_over_mean") >= 1.0
    assert metrics.gauge_value("moe/aux_loss") == 0.0
