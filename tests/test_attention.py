"""Attention op tests: ring and Ulysses vs reference on a real 8-device
mesh; pallas flash attention (interpret mode on CPU) vs reference."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.ops import (
    flash_attention,
    reference_attention,
    ring_attention,
    ulysses_attention,
)
from raydp_tpu.ops.flash_attention import (
    _flash_bwd_fused,
    _flash_bwd_pair,
    _flash_fwd_rule,
    _flash_vjp,
    _tile_live,
    _tile_whole,
    backward_is_fused,
    fused_backward_vmem,
    scale_rides_on_q,
    tile_counts,
)
from raydp_tpu.parallel import MeshSpec


def _qkv(b=2, s=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, s, h, d)), dtype=dtype
    ) / np.sqrt(d)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(eight_cpu_devices, causal):
    mesh = MeshSpec(sp=8).build()
    q, k, v = _qkv(s=64)
    expected = reference_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal, batch_axis=None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_dp_sp_mesh(eight_cpu_devices, causal):
    mesh = MeshSpec(dp=2, sp=4).build()
    q, k, v = _qkv(b=4, s=32)
    expected = reference_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(eight_cpu_devices, causal):
    mesh = MeshSpec(sp=4).build()
    q, k, v = _qkv(b=2, s=32, h=8)
    expected = reference_attention(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh, causal=causal, batch_axis=None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_ulysses_rejects_bad_heads(eight_cpu_devices):
    mesh = MeshSpec(sp=8).build()
    q, k, v = _qkv(h=4)  # 4 heads, sp=8
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh, batch_axis=None)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret(causal):
    q, k, v = _qkv(b=2, s=128, h=2, d=32)
    expected = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_sharded_flash_attention_on_a_mesh(eight_cpu_devices):
    """Mosaic kernels cannot be partitioned by XLA, so on a mesh the
    kernel runs per device under shard_map (batch over dp, heads over
    tp): forward and grads match the reference, output stays sharded."""
    from raydp_tpu.ops.flash_attention import sharded_flash_attention

    mesh = MeshSpec(dp=2, tp=2).build()
    q, k, v = _qkv(b=2, s=32, h=2, d=16)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    def flash(q, k, v):
        return sharded_flash_attention(
            q, k, v, mesh=mesh, causal=True, interpret=True
        )

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=True)

    got = jax.jit(flash)(q, k, v)
    assert got.sharding.spec == jax.sharding.PartitionSpec("dp", None, "tp")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref(q, k, v)), rtol=2e-4, atol=2e-5
    )
    g_flash = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )
    # model.init feeds a batch-1 sample, which dp=2 does not divide:
    # that dimension stays whole instead of failing the shard_map.
    one = jax.jit(flash)(q[:1], k[:1], v[:1])
    np.testing.assert_allclose(
        np.asarray(one), np.asarray(ref(q[:1], k[:1], v[:1])),
        rtol=2e-4, atol=2e-5,
    )


def test_flash_attention_grad_interpret():
    q, k, v = _qkv(b=1, s=64, h=2, d=16)

    def loss_flash(q):
        return flash_attention(q, k, v, block_q=32, block_kv=32,
                               interpret=True).sum()

    def loss_ref(q):
        return reference_attention(q, k, v).sum()

    g_flash = jax.grad(loss_flash)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(
        np.asarray(g_flash), np.asarray(g_ref), rtol=1e-3, atol=1e-4
    )


def test_ring_attention_grads(eight_cpu_devices):
    """SP must be trainable: grads through shard_map + ppermute."""
    mesh = MeshSpec(sp=4).build()
    q, k, v = _qkv(b=1, s=32, h=2, d=8)

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh, causal=True,
                               batch_axis=None) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )


def test_flash_rejects_indivisible():
    q, k, v = _qkv(s=48)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)


def test_long_context_ring_attention_2k(eight_cpu_devices):
    """Long-sequence evidence (SURVEY §5.7): seq 2048 sharded sp=8 —
    each device holds a 256-token block, K/V rotate the full ring —
    matches dense attention, forward and backward."""
    mesh = MeshSpec(sp=8).build()
    q, k, v = _qkv(b=1, s=2048, h=2, d=16, seed=3)
    expected = reference_attention(q, k, v, causal=True)
    got = ring_attention(q, k, v, mesh, causal=True, batch_axis=None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-5
    )

    def ring_loss(q_, k_, v_):
        return jnp.sum(
            ring_attention(q_, k_, v_, mesh, causal=True, batch_axis=None)
            ** 2
        )

    def dense_loss(q_, k_, v_):
        return jnp.sum(reference_attention(q_, k_, v_, causal=True) ** 2)

    g_ring = jax.grad(ring_loss)(q, k, v)
    g_dense = jax.grad(dense_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g_ring), np.asarray(g_dense), rtol=5e-3, atol=5e-4
    )


def test_long_context_causal_lm_sp_mesh(eight_cpu_devices):
    """A causal LM forward at seq 1024 on a dp2×sp4 mesh with ring
    attention through the model stack (the long-context training
    configuration, end to end)."""
    import flax.linen as nn

    from raydp_tpu.models.transformer import CausalLM, tiny_transformer

    mesh = MeshSpec(dp=2, sp=4).build()
    cfg = tiny_transformer(
        max_len=1024, vocab_size=128, n_layers=1, dropout_rate=0.0,
        causal=True, attention_impl="ring", mesh=mesh,
        dtype=jnp.float32,
    )
    model = CausalLM(cfg=cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 128, size=(2, 1024)), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    logits = jax.jit(model.apply)(params, ids)
    assert logits.shape == (2, 1024, 128)
    assert np.isfinite(np.asarray(logits)).all()

    dense_cfg = tiny_transformer(
        max_len=1024, vocab_size=128, n_layers=1, dropout_rate=0.0,
        causal=True, attention_impl="dense", dtype=jnp.float32,
    )
    dense_logits = jax.jit(CausalLM(cfg=dense_cfg).apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense_logits), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_kernels_full_parity(causal):
    """The blockwise pallas BACKWARD (dq + dkv kernels, no S x S
    materialization) matches reference-attention gradients for q, k AND
    v, with a non-trivial cotangent."""
    q, k, v = _qkv(b=2, s=96, h=2, d=32)
    w = jnp.asarray(
        np.random.RandomState(3).randn(2, 96, 2, 32).astype(np.float32)
    )

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_kv=32, interpret=True)
        return (out * w).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) * w).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_forward(causal):
    """MXU low-precision path: bf16 q/k/v through the pallas kernel vs
    an fp32 reference over the SAME (bf16-quantized) inputs. The kernel
    keeps its softmax/accumulation in fp32 (_masked_scores), so the
    output should track the fp32 reference to bf16 resolution (~2^-8),
    not drift with sequence length."""
    # NOTE: _qkv's / np.sqrt(d) promotes bf16 back to fp32 (the fp32
    # no-op-astype trap this test exists to close) — cast AFTER.
    q, k, v = (t.astype(jnp.bfloat16)
               for t in _qkv(b=2, s=128, h=2, d=32))
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    expected = reference_attention(q32, k32, v32, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32,
                          interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(expected),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_backward(causal):
    """bf16 gradients (dq, dk, dv) from the blockwise backward kernels
    stay within low-precision tolerance of the fp32 reference grads."""
    q, k, v = (t.astype(jnp.bfloat16)
               for t in _qkv(b=1, s=64, h=2, d=16, seed=5))
    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_kv=32, interpret=True)
        return (out.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q32, k32, v32)
    for a, b, name in zip(gf, gr, "qkv"):
        assert a.dtype == jnp.bfloat16, f"d{name} dtype {a.dtype}"
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b),
            rtol=6e-2, atol=6e-2, err_msg=f"d{name} mismatch",
        )


# -- the three kinds of tile, the scale on q, the tile counts (PR 35) --------

@pytest.mark.parametrize("scale", [2.0 ** -3, 128 ** -0.5],
                         ids=["scale_on_q", "scale_on_scores"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("blocks", [(32, 32), (32, 64), (64, 32)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_flash_causal_tiles_of_every_kind_match_reference(blocks, group,
                                                          scale):
    """S = 256 is at least four tiles a side, so dead, whole and crossed
    tiles all occur (and with ``block_q != block_kv`` a crossed tile is
    not on the tile diagonal): forward and all three gradients."""
    rng = np.random.default_rng(11)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 256, h, 16)), jnp.float32)
    q, k, v, w = mk(4), mk(4 // group), mk(4 // group), mk(4)
    live, masked = tile_counts(256, *blocks)
    assert 0 < masked < live < (256 // blocks[0]) * (256 // blocks[1])

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=blocks[0], block_kv=blocks[1],
                               interpret=True)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=True, scale=scale)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=1e-4, atol=1e-5,
    )
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch",
        )


# -- q and k of one width, v of another (latent attention, PR 36) -----------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("widths", [(192, 128), (24, 16), (16, 48)],
                         ids=lambda w: f"qk{w[0]}_v{w[1]}")
def test_flash_with_two_head_widths_matches_reference(widths, group, causal):
    """``q`` and ``k`` ``d_qk`` wide, ``v`` and the output ``d_v``: forward
    and all three gradients against dense attention, with dead, whole and
    crossed tiles (S = 128 in 32-wide tiles)."""
    d_qk, d_v = widths
    rng = np.random.default_rng(36)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 128, h, d)), jnp.float32)
    q, k, v, w = mk(2, d_qk), mk(2 // group, d_qk), mk(2 // group, d_v), mk(
        2, d_v)
    scale = d_qk ** -0.5 * 1.4159 ** 2

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=32, block_kv=32, interpret=True)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    out = flash(q, k, v)
    assert out.shape == (1, 128, 2, d_v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(plain(q, k, v)), rtol=1e-4, atol=1e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


def test_flash_with_equal_widths_is_the_call_it_was():
    """``d_qk = d_v``: the jaxpr of forward and backward is the one a call
    with no notion of a second width traces (every tile ``d`` wide)."""
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                        interpret=True)), argnums=(0, 1, 2)))(q, q, q))
    assert "f32[1,2,128,16]" in text and ",24]" not in text
    assert text.count("pallas_call") == 2       # forward, backward


@pytest.mark.parametrize("q_shape,k_shape,v_shape", [
    ((1, 64, 2, 24), (1, 64, 2, 16), (1, 64, 2, 16)),   # k not as wide as q
    ((1, 64, 2, 24), (1, 64, 2, 24), (1, 64, 1, 16)),   # v of other heads
    ((1, 64, 3, 24), (1, 64, 2, 24), (1, 64, 2, 16)),   # heads do not group
])
def test_flash_refuses_shapes_that_do_not_belong_together(q_shape, k_shape,
                                                          v_shape):
    with pytest.raises(ValueError, match="query heads"):
        flash_attention(jnp.zeros(q_shape), jnp.zeros(k_shape),
                        jnp.zeros(v_shape), interpret=True)


@pytest.mark.parametrize("s,block_q,block_kv", [
    (256, 32, 32), (256, 32, 64), (256, 64, 32), (384, 128, 32),
    (512, 64, 256), (96, 32, 96), (128, 128, 128),
])
def test_tile_kinds_agree_with_the_mask_itself(s, block_q, block_kv):
    """Dead = no entry of the tile's mask set, whole = every entry set,
    for every (qi, ki); the counts are the sums."""
    mask = np.tril(np.ones((s, s), bool))
    live = masked = 0
    for qi in range(s // block_q):
        for ki in range(s // block_kv):
            tile = mask[qi * block_q:(qi + 1) * block_q,
                        ki * block_kv:(ki + 1) * block_kv]
            assert _tile_live(qi, ki, True, block_q, block_kv) == tile.any()
            assert _tile_whole(qi, ki, block_q, block_kv) == tile.all()
            live += tile.any()
            masked += tile.any() and not tile.all()
    assert tile_counts(s, block_q, block_kv) == (live, masked)


def test_tile_counts_at_the_cells_sizes():
    assert tile_counts(8192) == (36, 8)         # lfm2_8b_a1b.fit_s8192
    assert tile_counts(4096) == (10, 4)         # the two S = 4,096 cells
    assert tile_counts(8192, causal=False) == (64, 0)
    assert tile_counts(1024, 256, 512) == (6, 4)
    assert tile_counts(96) == (1, 1)            # one tile of the whole S


@pytest.mark.parametrize("scale,rides", [
    (2.0 ** -3, True), (1 / 64, True), (64 ** -0.5, True), (1.0, True),
    (128 ** -0.5, False), (0.3, False), (0.0, False),
])
def test_only_a_power_of_two_rides_on_q(scale, rides):
    assert scale_rides_on_q(scale) is rides


def test_whole_tiles_build_no_mask_and_a_riding_scale_no_tile_multiply():
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, block_q=32, block_kv=32,
                            interpret=True, **kw)), argnums=(0, 1, 2)))(
                                q, q, q))

    assert "iota" not in text(causal=False)
    # Two kernels, one masked body each: a row and a column iota.
    assert text(causal=True).count(" iota[") == 4
    tile_mul = re.compile(r":f32\[32,32\] = mul \w+ 0\.\d+:f32\[\]")
    assert len(tile_mul.findall(text(causal=True, scale=0.3))) == 4
    assert not tile_mul.search(text(causal=True, scale=0.25))


def test_step_reports_the_flash_tiles_where_it_is_built(monkeypatch, caplog):
    """The kernel is Mosaic-only, so the model's call runs it in the
    interpreter here; the gauges come from the shapes alone."""
    import functools
    import sys

    import optax

    from raydp_tpu.models.transformer import CausalLM, tiny_transformer
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    module = sys.modules["raydp_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "flash_attention", functools.partial(
        module.flash_attention, interpret=True))

    def build(impl, seq):
        JAXEstimator(
            model=CausalLM(cfg=tiny_transformer(
                max_len=seq, vocab_size=64, n_layers=1, dropout_rate=0.0,
                causal=True, attention_impl=impl, dtype=jnp.float32)),
            optimizer=optax.adamw(2e-5), loss="lm_ce", feature_columns=["t"],
            batch_size=1, feature_dtype=np.int32, seed=0,
        )._init_state(np.zeros((1, seq), np.int32))

    with caplog.at_level("INFO", logger="raydp_tpu.ops.flash_attention"):
        build("flash", 384)                     # 3 x 3 tiles of 128
    assert metrics.gauge_value("attention/flash_live_tiles") == 6
    assert metrics.gauge_value("attention/flash_masked_tiles") == 3
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1 and "128 x 128 tiles, 6 live" in lines[0]
    assert "3 of them masked" in lines[0] and "on the q tile" in lines[0]
    build("dense", 32)
    assert metrics.gauge_value("attention/flash_live_tiles") == 0
    assert metrics.gauge_value("attention/flash_masked_tiles") == 0


# -- a window: a second edge on the same predicates (PR 38) ------------------

def _banded(q, k, v, window, scale=None):
    """Dense softmax attention over the last ``window`` keys of each
    query, written out here (no repo code): the yardstick of the windowed
    kernels."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
        d ** -0.5 if scale is None else scale)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = jnp.where((j <= i) & (j > i - window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("group", [1, 6, 8])
@pytest.mark.parametrize("window", [16, 32, 48, 100],
                         ids=lambda w: f"w{w}")
def test_windowed_flash_matches_a_dense_banded_softmax(window, group):
    """Forward and all three gradients in 32-wide tiles at S = 256: a
    window smaller than a tile, equal to one, one and a half and three
    tiles wide; groups of 1, 6 and 8 query heads a key-value head."""
    rng = np.random.default_rng(38)
    heads = 8 if group == 8 else 6
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 256, h, 16)), jnp.float32)
    q, k, v, w = mk(heads), mk(heads // group), mk(heads // group), mk(heads)
    live, masked = tile_counts(256, 32, 32, window=window)
    assert 0 < masked <= live < tile_counts(256, 32, 32)[0]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=32, block_kv=32, interpret=True)

    plain = lambda q, k, v: _banded(q, k, v, window)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference_attention(q, k, v, causal=True, window=window)),
        np.asarray(plain(q, k, v)), rtol=1e-5, atol=1e-6)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_windowed_flash_in_unequal_tiles(blocks):
    """Bands whose first tile is not a whole number of the other kind's
    tiles away: forward and gradients at a window of 40."""
    rng = np.random.default_rng(39)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 256, h, 16)), jnp.float32)
    q, k, v, w = mk(4), mk(2), mk(2), mk(4)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=40,
                               block_q=blocks[0], block_kv=blocks[1],
                               interpret=True)

    plain = lambda q, k, v: _banded(q, k, v, 40)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=1e-4, atol=1e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("window", [128, 4096])
def test_a_window_of_the_whole_sequence_is_plain_causal_bit_for_bit(window):
    """``window >= S`` excludes nothing: the call IS the causal call (the
    same jaxpr, the same bits), forward and backward."""
    rng = np.random.default_rng(40)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 128, h, 16)), jnp.float32)
    q, k, v = mk(4), mk(2), mk(2)

    def run(**kw):
        fn = lambda q, k, v: jnp.sum(flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=32, block_kv=32, interpret=True,
            **kw) ** 2)
        return fn, jax.value_and_grad(fn, argnums=(0, 1, 2))(q, k, v)

    fn_w, (out_w, grads_w) = run(window=window)
    fn_c, (out_c, grads_c) = run()
    assert np.asarray(out_w) == np.asarray(out_c)
    for a, b in zip(grads_w, grads_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(jax.grad(fn_w))(q, k, v)) == str(
        jax.make_jaxpr(jax.grad(fn_c))(q, k, v))


@pytest.mark.parametrize("s,blocks,window", [
    (256, (32, 32), 16), (256, (32, 32), 32), (256, (32, 32), 33),
    (256, (32, 64), 100), (256, (64, 32), 48), (512, (128, 128), 128),
    (256, (32, 32), 255), (16384, (512, 512), 512),
])
def test_tile_counts_under_a_window_against_brute_force(s, blocks, window):
    """A tile is live where any of its (query, key) pairs is inside the
    band, masked where some but not all are: counted pair by pair on the
    tile's corners."""
    bq, bkv = blocks
    live = masked = 0
    for qi in range(s // bq):
        for ki in range(s // bkv):
            i = np.arange(qi * bq, (qi + 1) * bq)[:, None]
            j = np.arange(ki * bkv, (ki + 1) * bkv)[None, :]
            if s > 1024:      # corners are enough; the band is convex
                i, j = i[[0, -1]], j[:, [0, -1]]
            inside = (j <= i) & (j > i - window)
            live += bool(inside.any())
            masked += bool(inside.any() and not inside.all())
    assert tile_counts(s, bq, bkv, window=window) == (live, masked)


def test_tile_counts_of_the_sliding_layers_at_the_cells_shape():
    """S = 16,384 under a window of 512: the tiles are 512 wide by
    default, every q tile but the first has two live tiles, both crossed
    by an edge; the band's grid is 2 steps wide, not 32."""
    from raydp_tpu.ops.flash_attention import band_tiles

    assert tile_counts(16384, window=512) == (63, 63)
    assert band_tiles(16384, 512, 512, 512) == (2, 2)
    assert tile_counts(16384) == (136, 16)            # all positions: 1024²
    assert tile_counts(16384, window=16384) == tile_counts(16384)


def test_window_without_causal_is_refused():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        reference_attention(q, q, q, causal=False, window=8)


def test_windowed_kernels_fetch_only_the_band():
    """The grids' innermost dimensions span the band: the forward's grid is
    (1, h, 8, 2) and the one backward kernel's (1, h_kv, group, 8, 2) at
    S = 256, 32-wide tiles, a window of 32; the pair's are (1, h, 8, 2)
    for dq and (1, h_kv, 8, group x 2) for dk/dv; without a window the
    same shapes take 8 steps."""
    q = jnp.zeros((1, 256, 4, 16), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 16), jnp.float32)

    def found(fn, *args):
        return sorted(re.findall(
            r"grid=\(([\d, ]+)\)", str(jax.make_jaxpr(fn)(*args))))

    def grids(**kw):
        return found(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                            interpret=True, **kw)), argnums=(0, 1, 2)),
                     q, kv, kv)

    def pair(window):
        def both(q, k, v):
            _, res = _flash_fwd_rule(q, k, v, True, 32, 32, True, 0.25,
                                     window)
            return _flash_bwd_pair(True, 32, 32, True, 0.25, window, res, q)
        return found(both, q, kv, kv)

    assert grids(window=32) == sorted(["1, 4, 8, 2", "1, 2, 2, 8, 2"])
    assert grids() == sorted(["1, 4, 8, 8", "1, 2, 2, 8, 8"])
    assert pair(32) == sorted(["1, 4, 8, 2", "1, 4, 8, 2", "1, 2, 8, 4"])
    assert pair(None) == sorted(["1, 4, 8, 8", "1, 4, 8, 8", "1, 2, 8, 16"])


# -- the backward as one kernel, and as the pair it replaced (PR 40) ---------

@pytest.mark.parametrize("scale", [2.0 ** -3, 128 ** -0.5],
                         ids=["scale_on_q", "scale_on_scores"])
@pytest.mark.parametrize("mask", [(False, None), (True, None), (True, 48)],
                         ids=["all_pairs", "causal", "window48"])
@pytest.mark.parametrize("blocks", [(32, 32), (32, 64), (64, 32)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=lambda w: f"qk{w[0]}_v{w[1]}")
@pytest.mark.parametrize("group", [1, 4])
def test_both_backward_paths_match_reference_and_each_other(
        group, widths, blocks, mask, scale):
    """The one kernel through ``flash_attention`` (these shapes fit any
    VMEM) and the dq + dk/dv pair by its rule function, on the same
    residuals: each against dense attention's gradients, and the two
    against each other (dk and dv accumulate in the same order over the
    same tiles; dq's tile product is asked of the MXU the other way
    round)."""
    causal, window = mask
    d_qk, d_v = widths
    rng = np.random.default_rng(40)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 128, h, d)), jnp.float32)
    q, k, v, w = mk(4, d_qk), mk(4 // group, d_qk), mk(4 // group, d_v), mk(
        4, d_v)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, block_q=blocks[0],
                               block_kv=blocks[1], interpret=True)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window)

    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    _, res = _flash_fwd_rule(q, k, v, causal, *blocks, True, scale, window)
    pair = _flash_bwd_pair(causal, *blocks, True, scale, window, res, w)
    for one, two, want, name in zip(grads(flash), pair, grads(plain), "qkv"):
        assert one.shape == two.shape == want.shape
        for got in (one, two):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
                err_msg=f"d{name} mismatch")
        np.testing.assert_allclose(
            np.asarray(one), np.asarray(two), rtol=1e-6, atol=1e-6,
            err_msg=f"d{name}: one kernel against the pair")


@pytest.mark.parametrize("cell,s,d,d_v,fused,resident_mib", [
    ("laguna_xs_2, full and window layers", 16384, 128, 128, True, 24),
    ("lfm2_8b_a1b", 8192, 64, 64, True, 6),
    ("xing4_0_29b_a4b", 4096, 192, 128, True, 8),
    ("olmoe_1b_7b", 4096, 128, 128, True, 6),
    ("granite_4_0_h_micro", 4096, 64, 64, True, 3),
    ("twice Laguna's sequence", 32768, 128, 128, False, 48),
])
def test_which_backward_a_call_takes_follows_from_its_shapes(
        cell, s, d, d_v, fused, resident_mib):
    """Plain ints in, the chip's VMEM (here the stated constant) the
    measure: the five LM cells' calls run the one kernel in bf16, a
    32,768-token call at d = 128 the pair."""
    resident, needed = fused_backward_vmem(s, d, d_v, 2)
    assert resident == resident_mib * 2 ** 20 == 4 * s * (2 * d + d_v)
    assert needed > 2 * resident
    assert backward_is_fused(s, d, d_v, 2) is fused


def test_a_call_too_long_for_vmem_runs_the_pair(monkeypatch):
    """With a VMEM the accumulators do not fit, the same call holds the dq
    and dk/dv kernels (three Pallas calls) and gives the pair's gradients;
    nothing but the shapes and the chip chooses."""
    import sys

    module = sys.modules["raydp_tpu.ops.flash_attention"]
    q, k, v = _qkv(b=1, s=96, h=2, d=16, seed=40)
    args = (True, 32, 32, True, 0.25, None)

    def grads():
        return jax.grad(lambda *a: jnp.sum(_flash_vjp(*a, *args) ** 2),
                        argnums=(0, 1, 2))

    fused = grads()(q, k, v)
    assert str(jax.make_jaxpr(grads())(q, k, v)).count("pallas_call") == 2
    monkeypatch.setattr(module, "_VMEM_BYTES", 2 ** 20)
    assert not backward_is_fused(96, 16, 16, 4)
    assert str(jax.make_jaxpr(grads())(q, k, v)).count("pallas_call") == 3
    out, res = _flash_fwd_rule(q, k, v, *args)
    pair = _flash_bwd_pair(*args, res, 2 * out)
    for got, same, near in zip(grads()(q, k, v), pair, fused):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
        np.testing.assert_allclose(np.asarray(got), np.asarray(near),
                                   rtol=1e-6, atol=1e-6)
