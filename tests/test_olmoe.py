"""The OLMoE-style decoder on the normal path, at tiny widths on the CPU
(hidden 64, 8 experts top-2, vocabulary 512, sequence 32): the program
against the benchmark's plain reference (logits, loss, gradients), the
pieces of the block against a few lines of ``jax.numpy``, the permutation's
backward, no scatter of ``T·k`` rows in the compiled step, BERT's parameter
tree and checkpoints unchanged; and the routed layer and the flash kernels
at the published widths compiled for a described TPU v5e."""
import functools
import importlib.util
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import CausalLM, SequenceClassifier, bert_base
from raydp_tpu.models.moe import (
    STATS,
    MoEConfig,
    MoELayer,
    combine_rows,
    compact_rows,
    moe_aux_loss,
    take_rows,
)
from raydp_tpu.models.transformer import MultiHeadAttention, rotary
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = {
    "vocab_size": 512, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2,
    "max_position_embeddings": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "num_experts": 8, "num_experts_per_tok": 2, "intermediate_size": 32,
    "norm_topk_prob": False, "tie_word_embeddings": False,
    "attention_impl": "dense", "compute_dtype": "float32",
    "param_dtype": "float32",
}
SEQ = 32


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "olmoe_causal_lm.py")
    spec = importlib.util.spec_from_file_location("olmoe_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny(builder):
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    return model, {"params": variables["params"]}, ids


def _program_loss(model, variables, ids):
    logits, sown = model.apply(variables, ids, mutable=["losses", STATS])
    return lm_crossentropy(logits, ids) + moe_aux_loss(sown)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# ---------------------------------------------- program against reference

def test_logits_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    got = model.apply(variables, ids)
    want = builder.reference_logits(variables, ids, SIZES)
    assert got.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(got, want) < 1e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    loss, grads = jax.jit(jax.value_and_grad(
        lambda v: _program_loss(model, v, ids)
    ))(variables)
    want_loss, want_grads = builder.reference_loss_and_grads(
        variables, ids, SIZES
    )
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    errors = jax.tree_util.tree_map(_rel, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors


@pytest.mark.parametrize("departure", [
    "no_qk_norm", "renormalised_top_k", "8_bit_trunk",
])
def test_tolerance_refuses_a_departure_from_the_mathematics(
    builder, tiny, departure
):
    """What the chip's check must catch: QK-norm left out of the program,
    the top-k probabilities renormalised to sum to one, or a trunk in the
    precision below bfloat16. Here, in float32 at init, the program is
    within 1e-5 of the reference and each departure over 2% (on the chip,
    on a trained state: 5-12% against 32-70%, tolerance 25%)."""
    import dataclasses

    model, variables, ids = tiny
    want = builder.reference_logits(variables, ids, SIZES)
    if departure == "no_qk_norm":
        got = CausalLM(dataclasses.replace(model.cfg, qk_norm=False)).apply(
            variables, ids
        )
    elif departure == "renormalised_top_k":
        got = builder.reference_logits(
            variables, ids, dict(SIZES, norm_topk_prob=True)
        )
    else:
        got = builder.reference_logits(
            variables, ids, SIZES, trunk=jnp.float8_e4m3fn
        )
    assert _rel(got, want) > 0.02


def test_extreme_imbalance_loses_no_token(builder, tiny):
    """A zero router in every layer sends all 64 tokens to experts 0 and
    1; the program still equals the reference on every position."""
    model, variables, ids = tiny
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if "router" in jax.tree_util.keystr(path) else a, variables,
    )
    got, sown = model.apply(zeroed, ids, mutable=["losses", STATS])
    want = builder.reference_logits(zeroed, ids, SIZES)
    assert _rel(got, want) < 1e-5
    for counts in jax.tree_util.tree_leaves(sown[STATS]):
        np.testing.assert_array_equal(
            np.asarray(counts), [2 * SEQ, 2 * SEQ, 0, 0, 0, 0, 0, 0]
        )


def test_dropless_layer_is_every_expert_on_every_token_masked():
    cfg = MoEConfig(d_model=64, d_ff=32, n_experts=8, top_k=2,
                    dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (3, 20, 64)).astype(np.float32))
    layer = MoELayer(cfg)
    p = nn.unbox(layer.init(jax.random.PRNGKey(1), x))["params"]
    got, sown = layer.apply({"params": p}, x, mutable=["losses", STATS])
    tokens = x.reshape(-1, 64)
    probs = jax.nn.softmax(tokens @ p["router"]["kernel"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -2][:, None]
    weights = jnp.where(probs >= kth, probs, 0.0)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", tokens, p["w_gate"])) * (
        jnp.einsum("td,edf->tef", tokens, p["w_up"]))
    want = jnp.einsum("tef,efd,te->td", h, p["w_down"], weights)
    np.testing.assert_allclose(
        np.asarray(got.reshape(-1, 64)), np.asarray(want), atol=2e-5
    )
    assert float(sown[STATS]["expert_tokens"].sum()) == 60 * 2


# ------------------------------------------------- the permutation's vjp

def test_take_rows_vjp_is_the_plain_gathers_gradient():
    rng = np.random.default_rng(2)
    t, k, d = 16, 4, 8
    x = jnp.asarray(rng.standard_normal((t, d)).astype(np.float32))
    order = jnp.asarray(rng.permutation(t * k).astype(np.int32))
    inverse = jnp.argsort(order).astype(jnp.int32)
    cot = jnp.asarray(rng.standard_normal((t * k, d)).astype(np.float32))

    def plain(x):
        return jnp.sum(x[order // k] * cot)

    def ours(x):
        return jnp.sum(take_rows(x, order, inverse, k) * cot)

    np.testing.assert_allclose(jax.grad(ours)(x), jax.grad(plain)(x),
                               rtol=1e-6, atol=1e-6)
    rows = jnp.asarray(rng.standard_normal((t * k, d)).astype(np.float32))
    np.testing.assert_allclose(
        jax.grad(lambda r: jnp.sum(take_rows(r, inverse, order) * cot))(rows),
        jax.grad(lambda r: jnp.sum(r[inverse] * cot))(rows),
        rtol=1e-6, atol=1e-6,
    )
    # ... and neither direction lowers to a scatter; the plain one does.
    lowered = jax.jit(jax.grad(ours)).lower(x).as_text()
    assert "scatter" not in lowered and "stablehlo.gather" in lowered
    assert "scatter" in jax.jit(jax.grad(plain)).lower(x).as_text()


def test_combine_rows_vjp_is_the_plain_formulas_gradient():
    rng = np.random.default_rng(3)
    t, k, d = 12, 2, 8
    rows = jnp.asarray(rng.standard_normal((t * k, d)).astype(np.float32))
    gate = jnp.asarray(rng.random((t, k)).astype(np.float32))
    order = jnp.asarray(rng.permutation(t * k).astype(np.int32))
    inverse = jnp.argsort(order).astype(jnp.int32)
    cot = jnp.asarray(rng.standard_normal((t, d)).astype(np.float32))

    def plain(rows, gate):
        pairs = rows[inverse].reshape(t, k, d)
        return jnp.sum(jnp.sum(pairs * gate[..., None], axis=1) * cot)

    def ours(rows, gate):
        return jnp.sum(combine_rows(rows, gate, order, inverse) * cot)

    assert float(ours(rows, gate)) == pytest.approx(float(plain(rows, gate)))
    for got, want in zip(jax.grad(ours, (0, 1))(rows, gate),
                         jax.grad(plain, (0, 1))(rows, gate)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _scatter_update_rows(hlo: str):
    """Leading dimension of the updates operand of every scatter."""
    rows = []
    for line in hlo.splitlines():
        if " scatter(" not in line:
            continue
        shapes = re.findall(r"\w+\[([\d,]*)\]", line.split(" scatter(")[1])
        updates = shapes[-1] if shapes else ""
        rows.append(int(updates.split(",")[0]) if updates else 1)
    return rows


def test_compiled_step_has_no_scatter_over_the_token_expert_pairs(tiny):
    """65,536 rows on the chip, 2 x 32 x 2 = 128 here: the only scatter of
    rows a step may keep is the embedding's gradient (one row a token)."""
    model, variables, ids = tiny
    tx = optax.adamw(4e-4)

    def step(variables, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda v: _program_loss(model, v, ids)
        )(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return optax.apply_updates(variables, updates), opt_state, loss

    hlo = jax.jit(step).lower(
        variables, tx.init(variables), ids
    ).compile().as_text()
    pairs = ids.size * SIZES["num_experts_per_tok"]
    assert pairs not in _scatter_update_rows(hlo)
    assert all(rows <= ids.size for rows in _scatter_update_rows(hlo))


def _eqns(jaxpr, into="pallas_call"):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, down to
    but not into a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == into:
            continue
        for sub in jax.tree_util.tree_leaves(
            list(eqn.params.values()),
            is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr"),
        ):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub, into)


def test_a_layer_that_holds_every_expert_has_no_guard_and_all_its_rows(tiny):
    """Only a share runs its experts over fewer rows than ``T·k`` behind a
    guard (``models/moe.compact_rows``): with all 8 experts held the step
    has no ``cond``, and every kernel and row gather of the routed layers
    sees all 2 x 32 x 2 = 128 pairs, as before there was a guard."""
    model, variables, ids = tiny
    pairs = ids.size * SIZES["num_experts_per_tok"]
    assert compact_rows(model.cfg.moe_config(), ids.size) == pairs
    eqns = list(_eqns(jax.make_jaxpr(jax.grad(
        lambda v: _program_loss(model, v, ids)))(variables).jaxpr))
    assert not [e for e in eqns if e.primitive.name == "cond"]
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2 * 9                  # 3 products x 3 passes
    assert all(
        any(pairs in v.aval.shape for v in e.invars + e.outvars)
        for e in kernels
    )
    # Rows of the hidden width that a gather moves: two each way a layer.
    moved = [e.outvars[0].aval.shape[0] for e in eqns
             if e.primitive.name == "gather"
             and e.outvars[0].aval.shape[1:] == (SIZES["hidden_size"],)]
    assert moved == [pairs] * (2 * 4), moved


# ------------------------------------- the block's pieces, three lines each

def test_rotary_against_three_lines_of_jnp():
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 8, 3, 16)).astype(np.float32))
    got = rotary(x, jnp.arange(8)[None, :], 10000.0)
    angle = np.arange(8)[:, None] * 10000.0 ** (-np.arange(8) / 8)
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want = jnp.concatenate([x[..., :8] * cos - x[..., 8:] * sin,
                            x[..., 8:] * cos + x[..., :8] * sin], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Scores depend on the distance alone: shift both by 3 positions.
    q, k = x[:, :, :1], x[:, :, 1:2]
    near = jnp.einsum("bqhd,bkhd->bqk", rotary(q, jnp.arange(8)[None], 1e4),
                      rotary(k, jnp.arange(8)[None], 1e4))
    far = jnp.einsum("bqhd,bkhd->bqk", rotary(q, 3 + jnp.arange(8)[None], 1e4),
                     rotary(k, 3 + jnp.arange(8)[None], 1e4))
    np.testing.assert_allclose(near, far, rtol=1e-4, atol=1e-4)


def test_rms_norm_and_qk_norm_against_three_lines_of_jnp(tiny):
    model, variables, ids = tiny
    cfg = model.cfg
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, SEQ, 64)).astype(np.float32))
    attn = variables["params"]["encoder"]["block_0"]["attn"]
    attn = dict(attn, q_norm={"scale": attn["q_norm"]["scale"] * 1.5})
    got = MultiHeadAttention(cfg).apply({"params": attn}, x)

    def rms(a, scale):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * scale

    qkv = jnp.einsum("bsd,dthk->bsthk", x, attn["qkv"]["kernel"])
    q = rms(qkv[:, :, 0].reshape(2, SEQ, 64), attn["q_norm"]["scale"])
    k = rms(qkv[:, :, 1].reshape(2, SEQ, 64), attn["k_norm"]["scale"])
    pos = jnp.arange(SEQ)[None]
    q = rotary(q.reshape(2, SEQ, 4, 16), pos, 1e4)
    k = rotary(k.reshape(2, SEQ, 4, 16), pos, 1e4)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    scores = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     qkv[:, :, 2])
    want = jnp.einsum("bqhd,hdm->bqm", ctx, attn["out"]["kernel"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # No bias, no position table, a head of its own.
    assert set(attn["qkv"]) == {"kernel"} and set(attn["out"]) == {"kernel"}
    enc = variables["params"]["encoder"]
    assert "pos_embed" not in enc and set(enc["ln_final"]) == {"scale"}
    assert set(variables["params"]["lm_head"]) == {"kernel"}


# ------------------------------------------------- BERT stays what it was

def test_bert_parameter_tree_and_checkpoint_are_unchanged(tmp_path):
    """The shared block reads its kind from the configuration; BERT's
    defaults give the tree PR 25's checkpoints were written with."""
    from raydp_tpu.train import JAXEstimator

    cfg = bert_base(vocab_size=100, d_model=32, n_heads=2, n_layers=2,
                    d_ff=64, max_len=16)
    model = SequenceClassifier(cfg=cfg, num_classes=2)
    ids = jnp.zeros((1, 16), jnp.int32)
    tree = jax.tree_util.tree_map(
        lambda a: tuple(a.shape),
        nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"],
    )
    block = {
        "attn": {"out": {"bias": (32,), "kernel": (2, 16, 32)},
                 "qkv": {"bias": (3, 2, 16), "kernel": (32, 3, 2, 16)}},
        "ln_attn": {"bias": (32,), "scale": (32,)},
        "ln_mlp": {"bias": (32,), "scale": (32,)},
        "mlp_down": {"bias": (32,), "kernel": (64, 32)},
        "mlp_up": {"bias": (64,), "kernel": (32, 64)},
    }
    assert tree == {
        "encoder": {
            "block_0": block, "block_1": block,
            "ln_final": {"bias": (32,), "scale": (32,)},
            "pos_embed": {"embedding": (16, 32)},
            "tok_embed": {"embedding": (100, 32)},
        },
        "head": {"bias": (2,), "kernel": (32, 2)},
        "pooler": {"bias": (32,), "kernel": (32, 32)},
    }

    def estimator():
        return JAXEstimator(
            model=model, optimizer=optax.adamw(2e-5), loss="softmax_ce",
            feature_columns=["t"], label_column="y", batch_size=4,
            feature_dtype=np.int32, label_dtype=np.int32, seed=0,
        )

    x = np.random.default_rng(0).integers(0, 100, (4, 16)).astype(np.int32)
    first = estimator()
    first._init_state(x)
    first.save(str(tmp_path / "ckpt"))
    second = estimator()
    second.restore(str(tmp_path / "ckpt"), sample_x=x)
    np.testing.assert_array_equal(first.predict(x), second.predict(x))


# ------------------------------ published widths, compiled for the chip

@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described host of four TPU v5e chips (nothing runs
    on them). Only the process that is given this file loads the TPU's
    library."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    """One of them, as a sharding."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def test_routed_layer_compiles_for_the_chip_at_published_widths(
    one_chip, monkeypatch
):
    """8,192 tokens, 64 experts of 2048 x 1024, 8 a token: Mosaic accepts
    the grouped-matmul tiling, the layer fits, and no scatter moves the
    65,536 (token, expert) rows in either direction."""
    # The kernels are compiled for the described chip, not interpreted.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = MoELayer(MoEConfig(d_model=2048, d_ff=1024, n_experts=64, top_k=8))
    x = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: nn.unbox(layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))["params"]
    )

    def loss(p, x):
        out, sown = layer.apply({"params": p}, x, mutable=["losses", STATS])
        return jnp.mean(out.astype(jnp.float32) ** 2) + moe_aux_loss(sown)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _on(one_chip, params), _on(one_chip, x)
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 9      # 3 products x 3 passes
    assert all(rows < 8192 for rows in _scatter_update_rows(hlo))
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


def test_flash_kernels_compile_for_the_chip_at_the_cells_shape(one_chip):
    from raydp_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((2, 4096, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(
            jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on(one_chip, (q, q, q))
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    # No S x S scores: dense attention keeps 2 x 16 x 4096^2 float32 here.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_grouped_flash_kernels_compile_for_the_chip_at_granites_shape(
    one_chip
):
    """32 query heads over 8 key-value heads of 64 (half a lane tile), the
    published softmax scale: Mosaic accepts the grouped index maps and the
    backward's grid over key-value heads and their groups; dk and dv come
    out in the key-value shape, nothing repeated."""
    from raydp_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, scale=1 / 64).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on(one_chip, (q, kv, kv))
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    dq, dk, dv = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert dq.shape == q.shape and dk.shape == dv.shape == kv.shape
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25e9


def test_chunked_scan_compiles_for_the_chip_at_published_widths(one_chip):
    """One sequence of 4,096 tokens, 64 heads of 64, state 128, chunks of
    256, forward and backward in plain ``jax.numpy``: it compiles, and XLA
    fuses the [tokens, heads, chunk] decay and score arrays (268 MB each in
    float32, were they stored) into the matmuls that use them: 118 MB of
    temporaries, not a handful of those arrays."""
    from raydp_tpu.ops.ssd import ssd_chunked

    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (
        jax.ShapeDtypeStruct((1, 4096, 64, 64), bf16),
        jax.ShapeDtypeStruct((1, 4096, 64), f32),
        jax.ShapeDtypeStruct((64,), f32),
        jax.ShapeDtypeStruct((1, 4096, 1, 128), bf16),
        jax.ShapeDtypeStruct((1, 4096, 1, 128), bf16),
        jax.ShapeDtypeStruct((64,), f32),
    )

    def loss(*a):
        return jnp.sum(ssd_chunked(*a, 256).astype(f32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *_on(one_chip, args)
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.25e9, temp


# (tokens, heads, groups, chunk) of the two cells' scans: heads of 64,
# state 128.
SCAN_CELLS = {"granite": (4096, 64, 1, 256), "nemotron": (16384, 64, 8, 128)}


@pytest.mark.parametrize("cell", list(SCAN_CELLS))
def test_scan_kernels_compile_for_the_chip_at_the_cells_shapes(one_chip, cell):
    """Mosaic takes both kernels (``ops/ssd.py``) as the cells tile them,
    and a gradient through one call is the two custom calls with no
    float32 [chunk, chunk] array a head between them: what is kept is the
    chunks' entering states (268 MB in Nemotron, 34 in Granite), and the
    temporaries stay under three times that."""
    from raydp_tpu.ops import ssd

    s, h, g, chunk = SCAN_CELLS[cell]
    bf16, f32, like = jnp.bfloat16, jnp.float32, jax.ShapeDtypeStruct
    channels = h * 64 + 2 * g * 128
    args = (like((1, s, channels), bf16), like((1, s, h), f32),
            like((h,), f32), like((h,), f32))

    def loss(*a):
        return jnp.sum(ssd.ssd_scan_packed(
            *a, chunk, g, 128, interpret=False).astype(f32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(4)))).lower(
        *_on(one_chip, args)).compile()
    hlo = compiled.as_text()
    calls = re.findall(r"%(ssd_\w+?)[.\d]* = ", hlo)
    assert sorted(calls) == ["ssd_backward", "ssd_forward"]
    # The decay matrix would be chunk / 64 times ``x``'s elements; the
    # largest array is the operand (x, B and C side by side) or the states.
    states = (s // chunk) * h * 64 * 128
    largest = max(
        np.prod([int(n) for n in dims.split(",")])
        for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", hlo))
    assert largest == max(states, s * channels)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 4 * states


def _olmo_hybrids_scan(one_chip, kernels: bool):
    """The compiled gradient of one layer's scan at Olmo-Hybrid's shape:
    one sequence of 4,096 tokens, 30 heads with keys of 96 and values of
    192 (neither a multiple of the 128 lanes) in chunks of 64."""
    from raydp_tpu.ops import gdn

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.ShapeDtypeStruct((1, 4096, 30, 96), bf16)
    args = (keys, keys, jax.ShapeDtypeStruct((1, 4096, 30, 192), bf16),
            jax.ShapeDtypeStruct((1, 4096, 30), f32),
            jax.ShapeDtypeStruct((1, 4096, 30), f32))

    def loss(*a):
        return jnp.sum(
            gdn.gdn_chunked(*a, 64, kernels=kernels).astype(f32) ** 2)

    return jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        *_on(one_chip, args)
    ).compile()


def test_scalar_decay_delta_rule_compiles_for_the_chip_at_olmo_hybrids_shape(
        one_chip):
    """``ops/gdn.py``'s plain ``jax.numpy`` rule, forward and backward:
    the TPU's compiler takes it, no kernel stands in it, the loops left
    are the walk over the two segments each way and the chunk states' scan
    inside them, and its temporaries are 1.32 GB (a backward holds one
    32-chunk segment's float32 intermediates, [64, 64] tiles padded to
    128 lanes and [96, 192] states to 256: what the kernels on [64, 96]
    and [64, 192] tiles keep in VMEM)."""
    compiled = _olmo_hybrids_scan(one_chip, kernels=False)
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    assert "f32[2,1,30,96,192]" in hlo       # the two segments' states
    assert 1.2e9 < compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_scalar_decay_kernels_compile_for_the_chip_at_olmo_hybrids_shape(
        one_chip, monkeypatch):
    """The same gradient by the kernels' rule: Mosaic accepts blocks that
    take 96 and 192 as they are (six calls: the chunk-local step's
    forward, which writes every chunk's inverse ``T``, the backward's
    rebuild, which reads it and inverts nothing, and the gradient; the
    walk over the chunk states in the forward pass, again in the rebuild,
    where it also writes a segment's states and ``w``, and backwards), the
    only loops left are the two walks over the segments, no float32
    [64, 64] array is in any buffer, ``T`` is kept with no padded lane,
    and the temporaries are 0.82 GB where the plain rule's are 1.32."""
    from raydp_tpu.ops import gdn, kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gdn.uses_kernels(96, 192, 64)
    compiled = _olmo_hybrids_scan(one_chip, kernels=True)
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == hlo.count("tpu_custom_call") == 6
    named = [re.search(r"%(gdn_[a-z_]+)", line).group(1) for line in calls]
    assert sorted(named) == [
        "gdn_chunk_backward", "gdn_chunk_forward", "gdn_chunk_rebuild",
        "gdn_state_backward", "gdn_state_forward", "gdn_state_forward"]
    a_segments_states = "f32[1,32,30,96,192]"
    walks = [line.split(" custom-call(")[0] for line in calls
             if "%gdn_state_forward" in line]
    assert sorted(a_segments_states in line for line in walks) == [False, True]
    loops = re.findall(r"= \([^\n]*\) while\(", hlo)
    assert len(loops) == 2, len(loops)
    for body in re.findall(r"while\([^\n]*body=%([\w.]+)", hlo):
        text = hlo.split(f"\n%{body} (")[1].split("\n}\n")[0]
        assert " while(" not in text and "gdn_state_" in text, body
    for line in hlo.splitlines():
        if re.search(r" (dot|convolution)\(", line):
            assert not re.search(r"f32\[[\d,]*96,192\]", line), line
    assert set(re.findall(r"f32\[[\d,]*64,64\]", hlo)) == set()
    assert "f32[2,1,30,96,192]" in hlo       # the two segments' states
    kept = kda.inverses_shape((2, 1, 32, 30), 64)
    assert kept == (2, 1, 32, 30, 32, 128)
    (layout,) = set(re.findall(r"f32\[2,1,32,30,32,128\]\{[^}]*\}", hlo))
    assert layout.endswith("{5,4,3,2,1,0:T(8,128)}"), layout
    assert 4 * int(np.prod(kept)) == 31_457_280
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9


@pytest.fixture(scope="module")
def kimi_linears_scan(one_chip):
    """The compiled gradient of one layer's scan at Kimi Linear's shape,
    as text and with its memory analysis: one sequence of 16,384 tokens,
    32 heads of 128 / 128 in chunks of 64, the operands as the model holds
    them (``QKVConv`` and the dense layers write [b, s, h · d]; the mixer
    hands ``kda_chunked`` a reshape of that, and reads ``o`` through one)."""
    from raydp_tpu.ops import kda

    bf16, f32 = jnp.bfloat16, jnp.float32
    wide = jax.ShapeDtypeStruct((1, 16384, 32 * 128), bf16)
    args = (wide, wide, wide,
            jax.ShapeDtypeStruct((1, 16384, 32 * 128), f32),
            jax.ShapeDtypeStruct((1, 16384, 32), f32))

    def loss(*a):
        *a, beta = a
        heads = (a.reshape(1, 16384, 32, 128) for a in a)
        o = kda.kda_chunked(*heads, beta, 64).reshape(1, 16384, -1)
        return jnp.sum(o.astype(f32) ** 2)

    default_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
            *_on(one_chip, args)
        ).compile()
    finally:
        jax.default_backend = default_backend
    return compiled.as_text(), compiled.memory_analysis()


def test_delta_rule_kernels_compile_for_the_chip_at_kimi_linears_shape(
        kimi_linears_scan):
    """One sequence of 16,384 tokens, 32 heads of 128 / 128 in chunks of
    64, forward and backward: Mosaic accepts the kernels (six calls: the
    chunk-local step's forward, which writes every chunk's inverse ``T``,
    the backward's rebuild, which reads it and inverts nothing, and the
    gradient; the walk over the chunk states in the forward pass, again
    in the rebuild, where it also writes a segment's states and ``w``,
    and backwards; and before each loop one that does nothing and gives
    it the arrays to write into), the only loops left are the two walks
    over the segments, and what the ``jax.numpy`` form wrote for every
    segment is
    in no buffer: nothing with the six levels' axis, and of float32
    [chunk, chunk] arrays none (the output product's gradient of ``P`` is
    the state kernel's, rounded in VMEM). ``T`` is kept as
    [1, 256 chunks, 32 heads, 32, 128] float32, a [64, 64] tile's two row
    blocks side by side: 134 MB a layer, where a float32 [..., 64, 64]
    array, its last dimension padded to 128 lanes, is 268."""
    import re

    from raydp_tpu.ops import kda

    assert kda.uses_kernels(128, 128, 64)
    hlo, memory = kimi_linears_scan
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == hlo.count("tpu_custom_call") == 8
    named = [re.search(r"%(kda_[a-z_]+)", line).group(1) for line in calls]
    # ``kda_unwritten`` does nothing: what a loop writes into, allocated
    # where the loop starts and not where the program does.
    assert sorted(named) == [
        "kda_chunk_backward", "kda_chunk_forward", "kda_chunk_rebuild",
        "kda_state_backward", "kda_state_forward", "kda_state_forward",
        "kda_unwritten", "kda_unwritten"]
    # The forward pass's walk writes o and the state left; the rebuild's
    # also a segment's entering states and w, which the backward reads.
    a_segments_states = "f32[1,32,32,128,128]"
    walks = [line.split(" custom-call(")[0] for line in calls
             if "%kda_state_forward" in line]
    assert sorted(a_segments_states in line for line in walks) == [False, True]
    # Two loops, the walks over the segments, and nothing loops inside
    # them: no product on the [..., 128, 128] state is left to XLA.
    loops = re.findall(r"= \([^\n]*\) while\(", hlo)
    assert len(loops) == 2, len(loops)
    for line in hlo.splitlines():
        if re.search(r" (dot|convolution)\(", line):
            assert not re.search(r"f32\[[\d,]*128,128\]", line), line
    assert not re.search(r"\[[\d,]*,6,64,128\]", hlo)
    squares = set(re.findall(r"f32\[[\d,]*64,64\]", hlo))
    assert squares == set(), squares
    assert "bf16[1,32,32,64,64]" in hlo
    # The kept inverses: one array of all eight segments', written a
    # segment at a time by the forward kernel and read by the rebuild and
    # the gradient kernels, in tiles with no padded lane.
    kept = kda.inverses_shape((1, 256, 32), 64)
    assert kept == (1, 256, 32, 32, 128)
    (layout,) = set(re.findall(r"f32\[1,256,32,32,128\]\{[^}]*T[^}]*\}", hlo))
    assert layout.endswith("{4,3,2,1,0:T(8,128)}"), layout
    assert 4 * int(np.prod(kept)) == 134_217_728
    for name in ("forward", "rebuild", "backward"):
        (call,) = [line for line in calls if f"%kda_chunk_{name}" in line]
        assert "f32[1,256,32,32,128]" in call, name
    # 0.79 GB at PR 59, with the 134 MB of T and a segment's states and w
    # from the state kernel (0.94 with the loop's stacked residuals at PR
    # 46, 0.69 at PR 45, which kept no T); the jax.numpy form's
    # temporaries were 2.00 GiB (PR 44).
    assert memory.temp_size_in_bytes < 0.8e9, memory.temp_size_in_bytes


def test_the_delta_rule_moves_nothing_as_large_as_q_at_kimi_linears_shape(
        kimi_linears_scan):
    """The same compiled gradient, read for what stands BETWEEN the
    model's arrays and the kernels: the five kernels take ``q``, ``k``,
    ``v``, ``g``, ``o``'s cotangent and write ``o`` and the four wide
    gradients as [1, 16384, 4096] arrays of the whole sequence, the
    segment's index a scalar operand of each call; the two loops' bodies
    hold those calls, the state's copy and the entering states' slice or
    update, and no other instruction of 4 MiB or more (``beta``'s rows are
    2 MiB, turned once each way outside the loops); and nowhere in the
    program is an array of ``q``'s size or more copied, transposed,
    sliced, stacked or concatenated."""
    import re

    hlo, _ = kimi_linears_scan
    calls = {}
    for line in hlo.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            # Of the two walks forward, the forward pass's (the first).
            calls.setdefault(re.search(r"%(kda_[a-z_]+)", line).group(1), line)
    sequence = r"\[1,16384,4096\]"
    assert len(re.findall("bf16" + sequence, calls["kda_chunk_forward"])) == 3
    assert len(re.findall("f32" + sequence, calls["kda_chunk_forward"])) == 1
    # Read: q, k, v, g; written in place: dq, dk, dv, dg (operand and result).
    assert len(re.findall(sequence, calls["kda_chunk_backward"])) >= 12
    for name in ("kda_state_forward", "kda_state_backward"):
        assert re.search("bf16" + sequence, calls[name]), name
    for name, line in calls.items():
        assert name == "kda_unwritten" or re.search(
            r"s32\[1\]", line.split(" custom-call(")[1]), name

    def mib(line):
        result = line.split(" = ")[1].split(" ")[0]
        return max((
            int(np.prod([int(n) for n in dims.split(",") if n] or [1]))
            * {"f32": 4, "bf16": 2, "s32": 4, "pred": 1, "u32": 4}[dtype]
            for dtype, dims in re.findall(
                r"(f32|bf16|s32|pred|u32)\[([\d,]*)\]", result)), default=0
        ) / 2 ** 20

    kinds = ("copy|transpose|dynamic-slice|dynamic-update-slice|concatenate"
             "|gather|scatter")
    moves = rf" ({kinds}|fusion)\("
    bodies = re.findall(r"while\([^\n]*body=%([\w.]+)", hlo)
    assert len(bodies) == 2
    for body in bodies:
        text = hlo.split(f"\n%{body} (")[1].split("\n}\n")[0]
        assert " while(" not in text and "kda_state_" in text, body
        large = [line.strip()[:160] for line in text.splitlines()
                 if re.search(moves, line) and mib(line) >= 4
                 and "128,128]" not in line.split(" = ")[1].split(" ")[0]]
        assert not large, large
    # What the loops write into comes from a kernel handed an array they
    # read: an allocation with no operand is scheduled where the program
    # starts, and held from there.
    assert not [line for line in hlo.splitlines()
                if "AllocateBuffer" in line and mib(line) >= 64]
    entry = hlo.split("\nENTRY ")[1]
    moved = [line.strip()[:160] for line in entry.splitlines()
             if re.search(rf" ({kinds})\(", line)
             and mib(line) >= 128]
    assert not moved, moved


def test_a_share_of_the_routed_layer_compiles_at_lfm2s_widths(
    one_chip, monkeypatch
):
    """8,192 tokens, 32 experts routed over by sigmoid score + bias, 4 a
    token, the 8 experts of 2048 x 1792 that one chip of four holds: the
    group sizes sum to fewer rows than the arrays have and Mosaic accepts
    it; only the held experts' weights exist; no scatter moves the 32,768
    (token, expert) rows in either direction."""
    from raydp_tpu.models.moe import BUFFERS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = MoELayer(MoEConfig(
        d_model=2048, d_ff=1792, n_experts=32, top_k=4, scoring="sigmoid",
        selection_bias=True, normalize_gates=True, aux_loss_weight=0.0,
        z_loss_weight=0.0, first_expert=0, held_experts=8,
    ))
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: nn.unbox(layer.init(jax.random.PRNGKey(0), jnp.zeros(
            x.shape, x.dtype)))
    )
    params, buffers = variables["params"], variables[BUFFERS]
    assert params["w_gate"].shape == (8, 2048, 1792)
    assert params["router"]["kernel"].shape == (2048, 32)
    assert buffers["expert_bias"].shape == (32,)

    def loss(p, x, b):
        out, _ = layer.apply({"params": p, BUFFERS: b}, x, mutable=[STATS])
        return jnp.mean(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _on(one_chip, params), _on(one_chip, x), _on(one_chip, buffers)
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 9      # 3 products x 3 passes
    assert all(rows < 8192 for rows in _scatter_update_rows(hlo))
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    # The kernels a step runs see 1.5 x 8,192 rows; all 32,768 only inside
    # the guard's branches (the layer-step whose held pairs exceed them).
    assert compact_rows(layer.cfg, 8192) == 12288
    kernels = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    path = [k for k in kernels if "/cond/branch_" not in k]
    # ... and since PR 54 the two token-side sums read those rows too.
    sums = [k for k in path if "rows_to_tokens" in k]
    assert len(sums) == 2 and len(path) == 11 and len(kernels) > len(path)
    assert all("[12288," in k and "[32768," not in k for k in path)


def test_a_shares_token_sums_compile_for_the_chip_at_mellum2s_shapes(
    one_chip, monkeypatch
):
    """16,384 tokens of the group, 8 pairs a token, the 16 experts of 64
    that a chip holds, 49,152 of 131,072 rows of width 2,304: Mosaic takes
    the kernel (the runs' copies from HBM at a row offset read from SMEM,
    a selection multiplied from its transposed side) with the gates and
    without, at the tiles the shapes give."""
    from raydp_tpu.ops import rows_to_tokens as op

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op.tiles(16384, 49152, 16) == (512, 112)
    assert op.tiles(16384, 24576, 16) == (512, 64)      # SDAR, Keye
    assert op.pays(16, 8) and not op.pays(32, 8)        # Laguna: the gather
    shapes = _on(one_chip, (
        jax.ShapeDtypeStruct((49152, 2304), jnp.bfloat16),
        jax.ShapeDtypeStruct((16384, 8), jnp.float32),
        jax.ShapeDtypeStruct((16384, 8), jnp.int32),
        jax.ShapeDtypeStruct((16,), jnp.int32),
    ))
    for gated in (True, False):
        compiled = jax.jit(lambda src, gate, place, ends: op.rows_to_tokens(
            src, gate if gated else None, place, ends)).lower(*shapes).compile()
        hlo = compiled.as_text()
        assert hlo.count("tpu_custom_call") == 1 and "gather(" not in hlo
        assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def test_grouped_flash_kernels_compile_at_lfm2s_sequence(one_chip):
    """The first shape over 4,096 tokens: S = 8,192, 32 query heads over 8
    key-value heads of 64, scale 1/8."""
    from raydp_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(
            jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on(one_chip, (q, kv, kv))
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    # No S x S scores: one head's would be 268 MB in float32.
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("case,s,heads,kv_heads,d,d_v,window,calls", [
    ("laguna_xs_2, a full layer", 16384, 48, 8, 128, 128, None, 2),
    ("laguna_xs_2, a window layer", 16384, 64, 8, 128, 128, 512, 2),
    ("xing4_0_29b_a4b", 4096, 32, 32, 192, 128, None, 2),
    ("twice Laguna's sequence", 32768, 8, 1, 128, 128, None, 3),
])
def test_the_backward_compiles_as_one_kernel_where_vmem_holds_it(
        one_chip, case, s, heads, kv_heads, d, d_v, window, calls):
    """PR 40: a head's float32 dq, dk and dv stay in VMEM over its tiles
    (24 MiB at S = 16,384 and d = 128, with a raised ``vmem_limit_bytes``)
    and Mosaic accepts it; at S = 32,768 the same call compiles to the dq
    and dk/dv kernels."""
    from raydp_tpu.ops.flash_attention import flash_attention

    def shape(h, width):
        return jax.ShapeDtypeStruct((1, s, h, width), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*_on(
        one_chip, (shape(heads, d), shape(kv_heads, d), shape(kv_heads, d_v))
    )).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == calls
    backward = [line for line in hlo.splitlines()
                if "tpu_custom_call" in line and "transpose" in line]
    assert len(backward) == calls - 1
    # No [B, H, S, 1] float32 column goes into the one kernel.
    columns = [line for line in backward if f",{s},1]" in line]
    assert len(columns) == (0 if calls == 2 else 1)


def test_short_convolution_compiles_to_few_passes_at_lfm2s_widths(one_chip):
    """[1, 8192, 2048]: both gates and the three shifted multiply-adds are
    loop fusions between the two projections, forward and backward; no
    convolution op, no [8192, 3 x 2048] float32 array is kept."""
    from raydp_tpu.models.shortconv import ShortConv
    from raydp_tpu.models.transformer import lfm2_8b_a1b

    op = ShortConv(lfm2_8b_a1b(n_layers=1))
    u = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: nn.unbox(op.init(jax.random.PRNGKey(0), jnp.zeros(
            u.shape, u.dtype)))["params"]
    )
    assert params["in_proj"]["kernel"].shape == (2048, 6144)
    assert params["conv"]["kernel"].shape == (3, 2048)

    def loss(p, u):
        return jnp.mean(op.apply({"params": p}, u).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _on(one_chip, params), _on(one_chip, u)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def _float32_results(hlo: str, elements: int):
    """Results of at least ``elements`` float32 values that an instruction
    outside every fused computation writes: arrays in the chip's memory,
    not values inside a fusion."""
    found, inside = [], None
    for line in hlo.splitlines():
        opened = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if opened:
            inside = opened.group(2)
        elif line.startswith("}"):
            inside = None
        elif inside and "fused_computation" not in inside and " = " in line:
            head = line.split(" = ", 1)[1]
            result = (head[:head.index(")") + 1] if head.startswith("(")
                      else head.split(" ", 1)[0])
            found += [
                dims for dims in re.findall(r"\bf32\[([\d,]+)\]", result)
                if np.prod([int(d) for d in dims.split(",")]) >= elements
            ]
    return found


@pytest.mark.parametrize("tokens", [4096, 4104])    # 4104: a ragged block
def test_stream_mixing_is_one_pass_at_xing4s_widths(
    one_chip, monkeypatch, tokens
):
    """Four bfloat16 streams of [tokens, 3584] read and written around a
    sublayer: Mosaic accepts the four kernels of ``ops/stream_mix.py``
    (aligned slices, the scoped VMEM), and forward and backward leave no
    float32 array of a stream's size in the chip's memory."""
    from raydp_tpu.models import hyperconn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d = 4, 3584
    x = jax.ShapeDtypeStruct((1, n, tokens, d), jnp.bfloat16)
    maps = hyperconn.Maps(*(
        jax.ShapeDtypeStruct(lead + (1, tokens), jnp.float32)
        for lead in ((n,), (n,), (n, n))
    ))

    def loss(x, maps):
        h = hyperconn.read(x, maps)
        return jnp.sum(hyperconn.write(x, jnp.tanh(h), maps).astype(
            jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *_on(one_chip, (x, maps))
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 4
    assert _float32_results(hlo, tokens * d) == []


@pytest.mark.parametrize("s, backward, temporaries", [
    (16384, ["sparse_attention_backward"], 0.6e9),
    (32768, ["sparse_attention_dkv", "sparse_attention_dq"], 2.5e9),
])
def test_sparse_attention_kernels_compile_for_the_chip_at_keyes_shape(
        one_chip, monkeypatch, s, backward, temporaries):
    """One sequence of 16,384 tokens, 32 query heads over 4 key-value heads
    of 128, 16 index heads of 64 over one index key head, 2,048 keys a
    query, forward and backward: Mosaic accepts the kernels of
    ``ops/sparse_attention.py`` at the tiles they are built with (all 32
    heads of a tile in one grid step, the select's [128, S] rows in VMEM,
    the one backward kernel's 68 MiB of resident gradients in single
    buffers), the scores are in HBM a block of 512 query rows at a time
    and no [S, S] array is, of any type. At 32,768 tokens the rule on the
    call's shapes sends the backward to the ``dq`` and ``dk/dv`` kernels,
    which compile too."""
    import re

    from raydp_tpu.ops import sparse_attention as sa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sa, "vmem_bytes", lambda: 128 * 2 ** 20)
    bf16 = jnp.bfloat16
    like = jax.ShapeDtypeStruct
    args = (like((1, s, 32, 128), bf16), like((1, s, 4, 128), bf16),
            like((1, s, 4, 128), bf16), like((1, s, 16, 64), bf16),
            like((1, s, 64), bf16), like((1, s, 16), jnp.float32))

    def loss(*a):
        out, kl, _ = sa.sparse_attention(*a, 2048)
        return jnp.sum(out.astype(jnp.float32)) + jnp.mean(kl)

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *_on(one_chip, args)
    ).compile()
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    named = sorted(
        re.search(r"%(sparse_[a-z_]+)", line).group(1) for line in calls)
    assert named == backward + [
        "sparse_attention_forward", "sparse_index_scores", "sparse_select"]
    assert f"[{s},{s}]" not in hlo and f"[1,{s},{s}]" not in hlo
    assert f"f32[512,{s}]" in hlo
    # The one kernel takes lse, delta and the thresholds as rows; the
    # pair's dq kernel takes padded [.., S, 1] columns, its largest
    # temporaries (2.2 GB at 32,768 tokens).
    columns = [line for line in calls if f"f32[1,32,{s},1]" in
               line.split(" custom-call(")[1]]
    assert len(columns) == (0 if len(backward) == 1 else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


# (S, channels, bias, output dtype, sequence_minor) of the three cells'
# causal convolutions, as their mixers call them: "kimi" is its v's,
# "kimi_qk" its q's and k's, which hold the L2 norm of each head of 128
# inside (PR 66) and write the compute dtype.
CONV_CELLS = {
    "granite": (4096, 2 * 2048 + 2 * 128, True, jnp.bfloat16, True),
    "kimi": (16384, 4096, False, jnp.float32, False),
    "kimi_qk": (16384, 4096, False, jnp.bfloat16, False),
    "nemotron": (16384, 4096 + 2 * 8 * 128, True, jnp.bfloat16, True),
}


@pytest.mark.parametrize("cell", list(CONV_CELLS))
def test_causal_convolution_kernels_compile_for_the_chip_at_the_cells_shapes(
        one_chip, cell):
    """Mosaic takes both kernels (``ops/causal_conv.py``) in both forms of
    the body as the cells tile them, and a gradient through one call is
    the two custom calls with, between them, the result and its cotangent
    at most: no padded copy, no float32 copy of the input."""
    from raydp_tpu.ops.causal_conv import Unit, causal_conv_silu

    s, channels, bias, out, sequence_minor = CONV_CELLS[cell]
    norm = dict(unit=Unit(128, 1e-6), scale=128 ** -0.5) if (
        cell == "kimi_qk") else {}
    like, f32 = jax.ShapeDtypeStruct, jnp.float32
    args = (like((1, s, channels), jnp.bfloat16), like((4, channels), f32),
            like((channels,), f32) if bias else None)

    def loss(x, kernel, b):
        y = causal_conv_silu(
            x, kernel, b, dtype=out, sequence_minor=sequence_minor, **norm)
        return jnp.sum(y.astype(f32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2) if bias else (0, 1)))
    compiled = compiled.lower(*_on(one_chip, args)).compile()
    calls = re.findall(r"%(causal_conv_\w+?)[.\d]* = ", compiled.as_text())
    assert sorted(calls) == ["causal_conv_backward", "causal_conv_forward"]
    size = s * channels * jnp.dtype(out).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * size


def _mixer_gradient(cfg, x, variables_on, mixer=None):
    """The compiled gradient of one mixer's squared output (a
    ``Mamba2Mixer``'s where none is given), as text; ``variables_on``
    places the abstract variables."""
    from raydp_tpu.models.mamba import Mamba2Mixer

    mixer = (mixer or Mamba2Mixer)(cfg)
    variables = jax.eval_shape(lambda: mixer.init(
        jax.random.PRNGKey(0), jnp.zeros((1,) + x.shape[1:], x.dtype)))

    def loss(variables, x):
        return jnp.sum(mixer.apply(variables, x).astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables_on(variables), x).compile().as_text()


@pytest.mark.parametrize("scan", ["jnp", "kernels"])
def test_the_compiler_lays_a_mamba2_mixers_convolution_out_sequence_minor(
        one_chip, monkeypatch, scan, head_dim=64, groups=8):
    """What ``Mamba2Mixer`` tells its convolution's kernels
    (``sequence_minor=True``) is what the compiler does on its own: in the
    mixer's gradient, ``in_proj``'s product, which the convolution reads,
    and the convolution's result are laid out ``{1,2,0}``, the sequence on
    the lanes, at Nemotron's heads of 64 in 8 groups (here) as at heads of
    128 in one (``head_dim=128, groups=1``). With the convolution and the
    scan in ``jax.numpy`` (the CPU's choice of form) the chunked scan that
    consumes them is why, which contracts over a chunk's positions; with
    both as their kernels (as a host with one TPU chooses) the scan's
    blocks have the sequence on their lanes for the same reason, and no
    array of the sequence's length is copied into another layout between
    the four kernels. A kernel that asked for ``{2,1,0}`` there cost
    Nemotron 6.7% in transposing copies (PERF.md §6, PR 59)."""
    from raydp_tpu.models.transformer import granite_h_micro

    if scan == "kernels":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = granite_h_micro(
        n_layers=1, layer_types=("mamba",), ssm_heads=4096 // head_dim,
        ssm_head_dim=head_dim, ssm_groups=groups, ssm_chunk=128)
    tokens, conv = 1024, 4096 + 2 * groups * cfg.ssm_state
    hlo = _mixer_gradient(
        cfg, jax.ShapeDtypeStruct(
            (1, tokens, cfg.d_model), jnp.bfloat16, sharding=one_chip),
        functools.partial(_on, one_chip))
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*tpu_custom_call", hlo)
    assert sorted(calls) == ([] if scan == "jnp" else [
        "causal_conv_backward", "causal_conv_forward", "ssd_backward",
        "ssd_forward"])
    width = 4096 + conv + cfg.ssm_heads
    layouts = {
        width: set(re.findall(
            rf"= bf16\[1,{tokens},{width}\](\{{[\d,]+)[^ ]* fusion\(", hlo)),
    }
    if scan == "jnp":
        layouts[conv] = set(re.findall(
            rf"= bf16\[1,{tokens},{conv}\](\{{[\d,]+)[^ ]* fusion\(", hlo))
    else:
        # The convolution's kernel writes [1, channels, tokens] as asked,
        # and nothing turns an array of the sequence's length around.
        assert not re.findall(
            rf"bf16\[1,(?:{tokens},\d+|\d+,{tokens})\]\S* (?:copy|transpose)\(",
            hlo)
    assert all(found == {"{1,2,0"} for found in layouts.values()), layouts


@pytest.mark.parametrize("dp, tp", [(4, 1), (2, 2)])
def test_a_mamba2_mixers_gradient_compiles_for_four_chips(
        four_chips, monkeypatch, dp, tp):
    """With the model's mesh told XLA is not asked to partition a Mosaic
    call (it cannot). dp = 4: each chip runs the convolution's and the
    scan's kernels on its own sequence inside ``shard_map``s, and the
    taps' sums meet in an all-reduce. dp = 2 by tp = 2: the convolution's
    kernels on a chip's two sequences; the scan, whose heads ``tp``
    splits, stays the ``jax.numpy`` form that XLA partitions over them
    (``mamba.scan_takes_kernels``)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.models.transformer import granite_h_micro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(four_chips).reshape(dp, tp), ("dp", "tp"))
    cfg = granite_h_micro(n_layers=1, layer_types=("mamba",), mesh=mesh)
    hlo = _mixer_gradient(
        cfg, jax.ShapeDtypeStruct(
            (4, 1024, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P("dp"))),
        functools.partial(_on, NamedSharding(mesh, P())))
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    names = sorted(re.findall(r"%(\w+?)[.\d]* = ", line)[0] for line in calls)
    convolution = ["causal_conv_backward", "causal_conv_forward"]
    assert names == convolution + (
        ["ssd_backward", "ssd_forward"] if tp == 1 else [])
    rows = 4 // dp
    # The convolution's result is the scan's operand as it stands.
    assert all(f"bf16[{rows},4352,1024]" in line for line in calls)
    assert sum(f"bf16[{rows},4096,1024]" in line for line in calls) == (
        2 if tp == 1 else 0)
    assert "all-reduce" in hlo


@pytest.mark.parametrize("dp, tp", [(4, 1), (2, 2)])
def test_a_gated_delta_mixers_gradient_compiles_for_four_chips(
        four_chips, monkeypatch, dp, tp):
    """The ``gdn`` mixer at the published head sizes with the model's mesh
    told. dp = 4: each chip walks its own sequence by the scan's kernels
    inside a ``shard_map``. dp = 2 by tp = 2: the scan, whose heads ``tp``
    splits, stays the plain rule that XLA partitions over them
    (``models/gdn.scan_takes_kernels``)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.models.gdn import GatedDeltaMixer
    from raydp_tpu.models.transformer import olmo_hybrid_7b

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(four_chips).reshape(dp, tp), ("dp", "tp"))
    cfg = olmo_hybrid_7b(
        n_layers=1, layer_types=("gdn:swiglu",), d_model=256, mesh=mesh,
        dtype=jnp.bfloat16)
    hlo = _mixer_gradient(
        cfg, jax.ShapeDtypeStruct(
            (4, 128, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P("dp"))),
        functools.partial(_on, NamedSharding(mesh, P())), GatedDeltaMixer)
    names = set(re.findall(r"%(gdn_[a-z_]+?)[.\d]* = ", hlo))
    assert names == ({
        "gdn_chunk_forward", "gdn_chunk_rebuild", "gdn_chunk_backward",
        "gdn_state_forward", "gdn_state_backward"} if tp == 1 else set())
    if tp == 1:
        # A chip's own sequence: one of four rows, two chunks, thirty heads.
        assert "f32[1,2,30,64,192]" in hlo

