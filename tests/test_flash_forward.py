"""The Pallas flash attention kernels' FORWARD (interpret mode on the CPU)
against the reference: dtypes, tiles of every kind, the shapes a call
refuses, the tile predicates and counts, the scale that rides on q, and the
gauges a built step reports. Split out of ``tests/test_attention.py`` (PR
53), cases unchanged."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_cases import _qkv
from raydp_tpu.ops import flash_attention, reference_attention
from raydp_tpu.ops.flash_attention import (
    _tile_live,
    _tile_whole,
    scale_rides_on_q,
    tile_counts,
)
from raydp_tpu.parallel import MeshSpec


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


def test_flash_rejects_indivisible():
    q, k, v = _qkv(s=48)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)


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
