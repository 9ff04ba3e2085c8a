"""What the attention test files share (``tests/test_attention.py``: dense,
ring and Ulysses; ``test_flash_forward.py``; ``test_flash_backward.py``;
``test_flash_window_widths.py``): seeded operands, the dense banded softmax
the windowed kernels are held to, and the one check whose cases lie in two
files (the backward as one kernel and as the pair: the window's cases with
the window's tests). Not collected: no ``test_`` in its name."""
import jax
import jax.numpy as jnp
import numpy as np

from raydp_tpu.ops import flash_attention, reference_attention
from raydp_tpu.ops.flash_attention import _flash_bwd_pair, _flash_fwd_rule


def _qkv(b=2, s=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, s, h, d)), dtype=dtype
    ) / np.sqrt(d)
    return mk(), mk(), mk()


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


def check_both_backward_paths(group, widths, blocks, mask, scale):
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
