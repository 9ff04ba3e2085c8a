"""Attention op tests: dense, ring and Ulysses against the reference on a
real 8-device mesh. The flash kernels' tests are in
``test_flash_forward.py``, ``test_flash_backward.py`` and
``test_flash_window_widths.py``; ``attention_cases.py`` holds what they
share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_cases import _qkv
from raydp_tpu.ops import (
    reference_attention,
    ring_attention,
    ulysses_attention,
)
from raydp_tpu.parallel import MeshSpec


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
