"""The Granite-4.0-H style hybrid stack on the normal path, at tiny widths
on the CPU (hidden 64, 4 query / 2 key-value heads of 16, 4 scan heads of
32, state 16, chunk 8, sequence 32, vocabulary 512, layers mamba, mamba,
attention): the program against the benchmark's plain reference (logits,
loss, gradients), the chunked scan against the token-by-token recurrence,
grouped-query attention against K and V repeated, each multiplier, the
gate-before-norm order and the tied head against a few lines of
``jax.numpy``, every departure the chip's check must catch, the old
families' parameter trees and checkpoints unchanged, and no serving from a
K/V cache the stack does not have."""
import dataclasses
import importlib.util
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import CausalLM, SequenceClassifier, bert_base, olmoe
from raydp_tpu.models.mamba import GatedRMSNorm, Mamba2Mixer
from raydp_tpu.models.transformer import (
    MultiHeadAttention,
    TransformerBlock,
    granite_h_micro,
)
from raydp_tpu.ops.attention import reference_attention
from raydp_tpu.ops.flash_attention import flash_attention
from raydp_tpu.ops.ssd import ssd_chunked
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
SIZES = {
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
    "tie_word_embeddings": True, "attention_impl": "dense",
    "compute_dtype": "float32", "param_dtype": "float32",
}


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "granite_hybrid_lm.py")
    spec = importlib.util.spec_from_file_location("granite_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny(builder):
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    return model, variables, ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _rms(a, scale, eps=1e-5):
    return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + eps) * scale


# ---------------------------------------------- program against reference

def test_parameter_tree_is_the_hybrid_stacks(tiny):
    _, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables["params"])
    mlp = {"ln_mlp": {"scale": (64,)}, "mlp_in": {"kernel": (64, 256)},
           "mlp_out": {"kernel": (128, 64)}}
    mamba = dict(mlp, ln_mamba={"scale": (64,)}, mamba={
        "in_proj": {"kernel": (64, 2 * 128 + 2 * 16 + 4)},
        "conv": {"kernel": (4, 160), "bias": (160,)},
        "ssd": {"A_log": (4,), "dt_bias": (4,), "D": (4,)},
        "gate_norm": {"scale": (128,)},
        "out_proj": {"kernel": (128, 64)},
    })
    attention = dict(mlp, ln_attn={"scale": (64,)}, attn={
        "q": {"kernel": (64, 4, 16)}, "kv": {"kernel": (64, 2, 2, 16)},
        "out": {"kernel": (4, 16, 64)},
    })
    # No position table, no head of its own: the logits use tok_embed.
    assert tree == {"encoder": {
        "tok_embed": {"embedding": (512, 64)}, "block_0": mamba,
        "block_1": mamba, "block_2": attention, "ln_final": {"scale": (64,)},
    }}


def test_scan_parameters_start_where_the_published_code_puts_them():
    cfg = granite_h_micro(n_layers=1, layer_types=("mamba",))
    x = jnp.zeros((1, 8, 2048), jnp.float32)
    p = nn.unbox(Mamba2Mixer(cfg).init(jax.random.PRNGKey(3), x))["params"]
    a, dt = np.exp(p["ssd"]["A_log"]), jax.nn.softplus(p["ssd"]["dt_bias"])
    assert a.shape == (64,) and 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    np.testing.assert_array_equal(p["ssd"]["D"], np.ones(64, np.float32))
    assert p["in_proj"]["kernel"].shape == (2048, 4096 + 4352 + 64)
    bias = np.asarray(p["conv"]["bias"])
    assert np.abs(bias).max() <= 0.5 and np.abs(bias).mean() > 0.2


def test_logits_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    got = model.apply(variables, ids)
    want = builder.reference_logits(variables, ids, SIZES)
    assert got.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(got, want) < 1e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    loss, grads = jax.jit(jax.value_and_grad(
        lambda v: lm_crossentropy(model.apply(v, ids), ids)
    ))(variables)
    want_loss, want_grads = builder.reference_loss_and_grads(
        variables, ids, SIZES
    )
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    errors = jax.tree_util.tree_map(_rel, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors


def test_remat_changes_nothing_but_memory(tiny):
    model, variables, ids = tiny
    again = CausalLM(dataclasses.replace(model.cfg, remat=True))
    loss = lambda m: jax.value_and_grad(  # noqa: E731
        lambda v: lm_crossentropy(m.apply(v, ids), ids))(variables)
    (a, ga), (b, gb) = loss(model), loss(again)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    errors = jax.tree_util.tree_map(_rel, ga, gb)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-5


def _carrying(variables):
    """The same weights with the scan's steps e^3 times larger and its decay
    rates e^3 times smaller: the same decay a token and twenty times the
    input, so that at 16 state features the carried state weighs in the
    output as it does at the published 128 (where the departure that drops
    it reads 3-8% on the chip; at these widths, as initialised, 0.5-1.7%)."""
    def shift(path, a):
        name = jax.tree_util.keystr(path)
        return a + 3.0 if "dt_bias" in name else (
            a - 3.0 if "A_log" in name else a)

    return jax.tree_util.tree_map_with_path(shift, variables)


def test_tolerance_refuses_every_departure_from_the_mathematics(
    builder, tiny
):
    """What the chip's check must catch (the builder's ``DEPARTURES`` and
    a trunk in the precision below bfloat16). Here, in float32, the program
    is within 1e-5 of the reference and each departure is over the chip's
    ``TOLERANCE``."""
    model, variables, ids = tiny
    variables = _carrying(variables)
    want = builder.reference_logits(variables, ids, SIZES)
    assert _rel(model.apply(variables, ids), want) < 1e-5
    errors = {
        d: _rel(builder.reference_logits(variables, ids, SIZES, depart=d),
                want)
        for d in builder.DEPARTURES
    }
    errors["float8_trunk"] = _rel(builder.reference_logits(
        variables, ids, SIZES, trunk=jnp.float8_e4m3fn), want)
    assert len(errors) == 7
    assert min(errors.values()) > 2 * builder.TOLERANCE, errors
    with pytest.raises(ValueError):
        builder.reference_logits(variables, ids, SIZES, depart="typo")


# ------------------------------------------------------------- the scan

def _recurrence(x, dt, A, B, C, D):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = h_t C_t + D
    x_t``, one step a token."""
    b, s, h, p = x.shape
    r = h // B.shape[2]
    B, C = jnp.repeat(B, r, axis=2), jnp.repeat(C, r, axis=2)

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        state = jnp.exp(dt_t * A)[..., None, None] * state + (
            (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)),
    )
    return jnp.moveaxis(y, 0, 1)


@pytest.fixture(scope="module")
def scan_inputs():
    rng = np.random.default_rng(1)
    b, s, h, p, g, n = 2, SEQ, 4, 8, 2, 16
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32))
    # Decays near one (0.90-0.99 a token): every chunk boundary carries
    # state that the outputs after it depend on.
    dt = jnp.asarray(rng.uniform(0.05, 0.2, (b, s, h)).astype(np.float32))
    A = -jnp.asarray(rng.uniform(0.2, 0.5, (h,)).astype(np.float32))
    return f(b, s, h, p), dt, A, f(b, s, g, n), f(b, s, g, n), f(h)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_scan_is_the_recurrence(scan_inputs, chunk):
    want = _recurrence(*scan_inputs)
    got = ssd_chunked(*scan_inputs, chunk)
    assert _rel(got, want) < 1e-5
    if chunk < SEQ:
        # ... and the carried state matters: scanning the chunks one by
        # one from a zero state is another function.
        alone = jnp.concatenate([
            ssd_chunked(*(a[:, i:i + chunk] if a.ndim > 1 else a
                          for a in scan_inputs), chunk)
            for i in range(0, SEQ, chunk)
        ], axis=1)
        assert _rel(alone, want) > 0.05


def test_chunked_scan_gradients_are_the_recurrences(scan_inputs):
    def loss(fn):
        return jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))
        )(*scan_inputs)

    want = loss(_recurrence)
    got = loss(lambda *a: ssd_chunked(*a, 8))
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-4


def test_scan_refuses_a_sequence_its_chunks_do_not_tile(scan_inputs):
    with pytest.raises(ValueError, match="multiple of chunk"):
        ssd_chunked(*scan_inputs, 5)


# ------------------------------------------- grouped-query attention

@pytest.mark.parametrize("causal", [True, False])
def test_grouped_attention_is_attention_with_k_and_v_repeated(causal):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, 256, 4, 16)).astype(np.float32))
    k, v = (jnp.asarray(rng.standard_normal((2, 256, 2, 16)).astype(
        np.float32)) for _ in range(2))

    def plain(q, k, v):
        k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.1
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((256, 256), bool)),
                               scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    def dense(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=0.1)

    def flash(q, k, v):
        # The kernel bodies in the Pallas interpreter, two q and two kv
        # tiles: the dk/dv grid walks 2 heads x 2 tiles a key-value head.
        return flash_attention(q, k, v, causal=causal, scale=0.1,
                               block_q=128, block_kv=128, interpret=True)

    grad = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)
    want, want_grads = plain(q, k, v), grad(plain)
    for fn in (dense, flash):
        assert _rel(fn(q, k, v), want) < 1e-5
        for g, w in zip(grad(fn), want_grads):
            assert g.shape == w.shape and _rel(g, w) < 1e-4


def test_flash_attention_without_groups_lowers_as_it_did():
    """Equal head counts take the pair's dk/dv kernel that was there before
    grouping existed: its innermost grid index IS the q tile, no remainder
    by the tiles of a head. The one backward kernel (PR 40) has the head
    in the group as a grid dimension of its own: groups add no index
    arithmetic to it."""
    from raydp_tpu.ops.flash_attention import _flash_bwd_pair, _flash_fwd_rule

    q = jnp.zeros((1, 256, 4, 16), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 16), jnp.float32)

    def text(k):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                            interpret=True)), argnums=(0, 1, 2)))(q, k, k))

    def pair(k):
        def both(q, k, v):
            out, res = _flash_fwd_rule(q, k, v, True, 128, 128, True, 0.25,
                                       None)
            return _flash_bwd_pair(True, 128, 128, True, 0.25, None, res, out)
        return str(jax.make_jaxpr(both)(q, k, k))

    index_math = re.compile(r":i32\[\] = rem ")
    assert not index_math.search(pair(q))
    assert index_math.search(pair(kv))
    # The one kernel finds a tile's first and last neighbour by floor
    # divisions of its own; a group adds none to them.
    assert len(index_math.findall(text(q))) == len(
        index_math.findall(text(kv)))


def test_attention_module_groups_scales_and_takes_no_positions(tiny):
    model, variables, _ = tiny
    cfg = model.cfg
    attn = variables["params"]["encoder"]["block_2"]["attn"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, SEQ, 64)).astype(np.float32))
    got = MultiHeadAttention(cfg).apply({"params": attn}, x)
    q = jnp.einsum("bsd,dhk->bshk", x, attn["q"]["kernel"])
    kv = jnp.einsum("bsd,dthk->bsthk", x, attn["kv"]["kernel"])
    k, v = (jnp.repeat(kv[:, :, i], 2, axis=2) for i in (0, 1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 64.0     # not 1/sqrt(16)
    scores = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    want = jnp.einsum("bqhd,hdm->bqm", ctx, attn["out"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    # The default scale is another function.
    other = MultiHeadAttention(
        dataclasses.replace(cfg, attention_scale=None)
    ).apply({"params": attn}, x)
    assert _rel(other, want) > 1e-3


# ---------------------------- multipliers, gate order, tied head, pattern

def test_gate_goes_in_before_the_norm():
    rng = np.random.default_rng(6)
    y, z = (jnp.asarray(rng.standard_normal((2, 8, 32)).astype(np.float32))
            for _ in range(2))
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 32).astype(np.float32))
    got = GatedRMSNorm(1e-5, jnp.float32, jnp.float32).apply(
        {"params": {"scale": scale}}, y, z)
    np.testing.assert_allclose(
        got, _rms(y * jax.nn.silu(z), scale), rtol=1e-5, atol=1e-6)
    assert _rel(got, _rms(y, scale) * jax.nn.silu(z)) > 0.1


def test_residual_multiplier_scales_both_branches(tiny):
    model, variables, _ = tiny
    cfg = model.cfg
    blk = variables["params"]["encoder"]["block_0"]
    x = jnp.asarray(np.random.default_rng(7).standard_normal(
        (2, SEQ, 64)).astype(np.float32))
    got = TransformerBlock(cfg, "mamba").apply({"params": blk}, x)
    mixed = Mamba2Mixer(cfg).apply(
        {"params": blk["mamba"]}, _rms(x, blk["ln_mamba"]["scale"]))
    x1 = x + 0.22 * mixed
    gate, up = jnp.split(
        _rms(x1, blk["ln_mlp"]["scale"]) @ blk["mlp_in"]["kernel"], 2, -1)
    want = x1 + 0.22 * ((jax.nn.silu(gate) * up) @ blk["mlp_out"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_embedding_multiplier_tied_head_and_logit_scaling(tiny):
    model, variables, ids = tiny
    enc = variables["params"]["encoder"]
    table = enc["tok_embed"]["embedding"]
    hidden = model.apply(
        variables, ids, None, True,
        method=lambda m, *a: m.encoder(*a),
    )
    np.testing.assert_allclose(
        model.apply(variables, ids), hidden @ table.T / 8.0,
        rtol=1e-5, atol=1e-7)
    # Zero blocks: the stack is the embedding times 12, normed.
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "kernel" in jax.tree_util.keystr(path) else a, variables)
    got = model.apply(
        zeroed, ids, None, True, method=lambda m, *a: m.encoder(*a))
    np.testing.assert_allclose(
        got, _rms(12.0 * table[ids], enc["ln_final"]["scale"]),
        rtol=1e-5, atol=1e-6)
    # One table, two gradients: the lookup's rows and the head's product.
    grads = jax.grad(lambda v: lm_crossentropy(model.apply(v, ids), ids))(
        variables)
    unseen = np.setdiff1d(np.arange(512), np.asarray(ids))
    assert float(jnp.abs(grads["params"]["encoder"]["tok_embed"][
        "embedding"][unseen]).max()) > 0


def test_layer_pattern_is_checked_and_the_factory_is_the_published_one():
    cfg = granite_h_micro()
    assert cfg.kinds.count("attention") == 4 and len(cfg.kinds) == 40
    assert [i for i, k in enumerate(cfg.kinds) if k == "attention"] == [
        5, 15, 25, 35]
    assert (cfg.kv_heads, cfg.head_dim, cfg.attention_scale) == (8, 64, 1 / 64)
    assert cfg.ssm_heads * cfg.ssm_head_dim == 2 * cfg.d_model
    assert bert_base().kinds == ("attention",) * 12
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, layer_types=("mamba",)).kinds
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, n_layers=1, layer_types=("linear",)).kinds


# ---------------------------------------- the old families stay as they were

def _shapes(model, *args):
    return jax.tree_util.tree_map(
        lambda a: tuple(a.shape),
        nn.unbox(model.init(jax.random.PRNGKey(0), *args))["params"],
    )


def _round_trip(tmp_path, make, x):
    first = make()
    first._init_state(x)
    first.save(str(tmp_path / "ckpt"))
    second = make()
    second.restore(str(tmp_path / "ckpt"), sample_x=x)
    np.testing.assert_array_equal(first.predict(x), second.predict(x))


def test_bert_tree_and_checkpoint_under_the_default_pattern(tmp_path):
    from raydp_tpu.train import JAXEstimator

    cfg = bert_base(vocab_size=100, d_model=32, n_heads=2, n_layers=2,
                    d_ff=64, max_len=16)
    assert cfg.layer_types is None and cfg.kv_heads == 2
    model = SequenceClassifier(cfg=cfg, num_classes=2)
    tree = _shapes(model, jnp.zeros((1, 16), jnp.int32))
    assert set(tree["encoder"]["block_1"]) == {
        "attn", "ln_attn", "ln_mlp", "mlp_down", "mlp_up"}
    assert tree["encoder"]["block_0"]["attn"]["qkv"] == {
        "bias": (3, 2, 16), "kernel": (32, 3, 2, 16)}
    x = np.random.default_rng(0).integers(0, 100, (4, 16)).astype(np.int32)
    _round_trip(tmp_path, lambda: JAXEstimator(
        model=model, optimizer=optax.adamw(2e-5), loss="softmax_ce",
        feature_columns=["t"], label_column="y", batch_size=4,
        feature_dtype=np.int32, label_dtype=np.int32, seed=0,
    ), x)


def test_olmoe_tree_and_checkpoint_under_the_default_pattern(tmp_path):
    from raydp_tpu.train import JAXEstimator

    cfg = olmoe(vocab_size=128, d_model=32, n_heads=2, n_layers=1,
                max_len=16, n_experts=4, top_k=2, d_expert=16,
                dtype=jnp.float32)
    assert cfg.serves_from_kv_cache and not cfg.tie_head
    model = CausalLM(cfg)
    tree = _shapes(model, jnp.zeros((1, 16), jnp.int32))
    assert tree["lm_head"] == {"kernel": (32, 128)}
    assert set(tree["encoder"]["block_0"]) == {
        "attn", "ln_attn", "ln_mlp", "moe"}
    assert set(tree["encoder"]["block_0"]["attn"]) == {
        "qkv", "out", "q_norm", "k_norm"}
    x = np.random.default_rng(0).integers(0, 128, (4, 16)).astype(np.int32)
    _round_trip(tmp_path, lambda: JAXEstimator(
        model=model, optimizer=optax.adamw(2e-5), loss="lm_ce",
        self_supervised=True, aux_losses=True,
        feature_columns=[f"t{i}" for i in range(16)], label_column=None,
        batch_size=4, feature_dtype=np.int32, seed=0,
    ), x)


# ------------------------------------------------------ serving, gauges

@pytest.mark.parametrize("method", ["prefill", "decode_step", "init_cache"])
def test_serving_a_hybrid_stack_raises(tiny, method):
    model, variables, ids = tiny
    args = {
        "prefill": (ids, jnp.full((2,), SEQ)),
        "decode_step": (ids[:, :1], jnp.zeros((2,), jnp.int32), 8),
        "init_cache": (2,),
    }[method]
    with pytest.raises(NotImplementedError, match="R4"):
        model.apply(variables, *args, method=getattr(CausalLM, method),
                    mutable=["cache"])
    # Grouped heads alone are refused as well: the cache would be wrong.
    gqa = CausalLM(dataclasses.replace(
        model.cfg, n_layers=1, layer_types=("attention",)))
    assert not gqa.cfg.serves_from_kv_cache
    assert CausalLM(dataclasses.replace(
        gqa.cfg, n_kv_heads=None)).cfg.serves_from_kv_cache


def test_step_reports_the_stack_once_where_it_is_built(builder, caplog):
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    kwargs = builder.estimator_kwargs(
        dict(SIZES, optimizer={"name": "adamw", "learning_rate": 2e-5}),
        {"seq_len": SEQ}, None)
    est = JAXEstimator(**kwargs, batch_size=2, seed=0)
    with caplog.at_level("INFO", logger="raydp_tpu.models.mamba"):
        est._init_state(np.zeros((2, SEQ), np.int32))
    assert metrics.gauge_value("ssm/layers") == 2
    # 2 sequences x 32 tokens in chunks of 8, two state-space layers.
    assert metrics.gauge_value("ssm/chunks_per_step") == 2 * 8
    assert metrics.gauge_value("ssm/state_bytes_per_sequence") == (
        2 * 4 * 32 * 16 * 4)
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "2 mamba and 1 attention" in lines[0]
    assert "4 query / 2 key-value heads of 16" in lines[0]
    assert "chunk 8" in lines[0] and "ops/ssd.py" in lines[0]
    # A stack without state-space layers reads zero.
    JAXEstimator(
        model=SequenceClassifier(cfg=bert_base(
            vocab_size=100, d_model=32, n_heads=2, n_layers=1, d_ff=64,
            max_len=16), num_classes=2),
        optimizer=optax.adamw(2e-5), loss="softmax_ce",
        feature_columns=["t"], label_column="y", batch_size=4,
        feature_dtype=np.int32, label_dtype=np.int32, seed=0,
    )._init_state(np.zeros((4, 16), np.int32))
    assert metrics.gauge_value("ssm/layers") == 0
    assert metrics.gauge_value("ssm/chunks_per_step") == 0


# sha256 and length of ``str(jax.make_jaxpr(grad of a tiny Granite step's
# loss))`` with the memory addresses jax prints for a checkpoint's policy
# taken out, as the PARENT of PR 57 (commit 8b8d49a) printed them by this
# same function: the gated norm's groups (one here), the one-sublayer
# layers the stack may now hold (none here) and the expert's form (no
# expert here) leave a Granite step the program it was. Regenerate from a
# parent tree if jax changes how it prints.
PARENT_STEPS = {
    False: (107291, "157043d9e11a6ed4376034a5b4a7a2ec57ea5d54086d2e663633b2"
                    "dfc8d45be6"),
    True: (163068, "0541f9056d45eea157aaad1a97ab9a676d3036d2908e84760e66e78"
                   "540689e3c"),
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_granite_step_traces_to_the_parents_program(remat):
    import hashlib

    cfg = granite_h_micro(
        n_layers=3, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
        vocab_size=64, ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_chunk=8,
        max_len=64, layer_types=("mamba", "attention", "mamba"),
        dtype=jnp.bfloat16, remat=remat,
    )
    model = CausalLM(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), ids)))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.grad(
        lambda v, ids: lm_crossentropy(model.apply(v, ids), ids)
    ))(variables, ids)))
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (
        PARENT_STEPS[remat])
