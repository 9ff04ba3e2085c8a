"""The chunked state-space scan (``ops/ssd.py``) in SEVERAL GROUPS of B and
C and over a sequence of several chunks, float32 on the CPU: against the
recurrence itself, one step a token (forward and every gradient), at the
published chunk of 128 with 8 groups; and the gated norm a group against
the whole-axis norm it is with one group
(``models/mamba.GatedRMSNorm``)."""
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models.mamba import GatedRMSNorm
from raydp_tpu.ops.ssd import ssd_chunked


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _recurrence(x, dt, A, B, C, D):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``, ``y_t = h_t C_t + D
    x_t``, one step a token; head ``h`` reads the B and C of group
    ``h // (heads / groups)``."""
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


def _inputs(b, s, h, p, g, n, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32))
    # Decays near one (0.90-0.99 a token): every chunk boundary carries
    # state that the outputs after it depend on.
    dt = jnp.asarray(rng.uniform(0.05, 0.2, (b, s, h)).astype(np.float32))
    A = -jnp.asarray(rng.uniform(0.2, 0.5, (h,)).astype(np.float32))
    return f(b, s, h, p), dt, A, f(b, s, g, n), f(b, s, g, n), f(h)


@pytest.fixture(scope="module")
def eight_groups():
    """Three chunks of 128 tokens, 16 heads of 4 in 8 groups, state 8."""
    return _inputs(1, 384, 16, 4, 8, 8)


NAMES = ("x", "dt", "A", "B", "C", "D")


def test_eight_groups_at_chunk_128_are_the_recurrence(eight_groups):
    want = _recurrence(*eight_groups)
    got = ssd_chunked(*eight_groups, 128)
    assert _rel(got, want) < 1e-5
    # The groups matter: with every head on group 0's B and C it is
    # another function.
    x, dt, A, B, C, D = eight_groups
    one = ssd_chunked(x, dt, A, B[:, :, :1], C[:, :, :1], D, 128)
    assert _rel(one, want) > 0.1


@pytest.fixture(scope="module")
def eight_group_gradients(eight_groups):
    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=tuple(range(6))
        ))(*eight_groups)

    return grads(lambda *a: ssd_chunked(*a, 128)), grads(_recurrence)


@pytest.mark.parametrize("leaf", range(6), ids=NAMES)
def test_eight_group_gradients_are_the_recurrences(eight_group_gradients,
                                                   leaf):
    got, want = eight_group_gradients
    assert _rel(got[leaf], want[leaf]) < 1e-4


# --------------------------------------------------- the gated norm a group

def _gated_rms(y, z, scale, groups, eps=1e-5):
    y = y * jax.nn.silu(z)
    grouped = y.reshape(*y.shape[:-1], groups, -1)
    grouped = grouped / jnp.sqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * scale


@pytest.fixture(scope="module")
def norm_inputs():
    rng = np.random.default_rng(6)
    y, z = (jnp.asarray(rng.standard_normal((2, 8, 64)).astype(np.float32))
            for _ in range(2))
    return y, z, jnp.asarray(rng.uniform(0.5, 1.5, 64).astype(np.float32))


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_gated_norm_norms_each_group_on_its_own(norm_inputs, groups):
    y, z, scale = norm_inputs
    norm = GatedRMSNorm(1e-5, jnp.float32, jnp.float32, groups=groups)
    variables = nn.unbox(norm.init(jax.random.PRNGKey(0), y, z))
    # One learned weight a feature, whatever the groups.
    assert variables["params"]["scale"].shape == (64,)
    got = norm.apply({"params": {"scale": scale}}, y, z)
    np.testing.assert_allclose(
        got, _gated_rms(y, z, scale, groups), rtol=1e-5, atol=1e-6)
    if groups > 1:
        assert _rel(got, _gated_rms(y, z, scale, 1)) > 0.05


def test_the_gated_norm_at_one_group_is_the_whole_axis_norm_to_the_bit(
        norm_inputs):
    """``groups=1`` (Granite's) computes what the norm computed before it
    had groups, bit for bit, and traces to the same operations."""
    y, z, scale = norm_inputs

    def before(y, z, scale):
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-5)
        return (y * scale.astype(jnp.float32)).astype(jnp.float32)

    norm = GatedRMSNorm(1e-5, jnp.float32, jnp.float32)
    now = lambda y, z, scale: norm.apply(  # noqa: E731
        {"params": {"scale": scale}}, y, z)
    assert np.array_equal(
        np.asarray(jax.jit(now)(y, z, scale)),
        np.asarray(jax.jit(before)(y, z, scale)))
    strip = lambda f: re.sub(  # noqa: E731
        r"\s+", " ", str(jax.make_jaxpr(f)(y, z, scale)))
    assert strip(now) == strip(before)
    with pytest.raises(ValueError, match="do not divide"):
        GatedRMSNorm(1e-5, jnp.float32, jnp.float32, groups=3).init(
            jax.random.PRNGKey(0), y, z)
