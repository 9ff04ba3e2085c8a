"""The Pallas flash attention kernels' BACKWARD (interpret mode on the CPU):
gradients against the reference, the one kernel and the dq + dk/dv pair
against each other over masks without a window, and which of the two a
call takes. Split out of ``tests/test_attention.py`` (PR 53), cases
unchanged; the window's cases of the both-paths check are in
``test_flash_window_widths.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_cases import _qkv, check_both_backward_paths
from raydp_tpu.ops import flash_attention, reference_attention
from raydp_tpu.ops.flash_attention import (
    _flash_bwd_pair,
    _flash_fwd_rule,
    _flash_vjp,
    backward_is_fused,
    fused_backward_vmem,
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


@pytest.mark.parametrize("scale", [2.0 ** -3, 128 ** -0.5],
                         ids=["scale_on_q", "scale_on_scores"])
@pytest.mark.parametrize("mask", [(False, None), (True, None)],
                         ids=["all_pairs", "causal"])
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
    check_both_backward_paths(group, widths, blocks, mask, scale)


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
