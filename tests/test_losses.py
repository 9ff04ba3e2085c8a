"""The causal-LM loss (``train/losses.py:lm_crossentropy``) against the
sliced formula it replaced, which stays here as the plain reference:
value, gradient, what the last position may see, and the shapes the
lowered program is allowed to contain."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raydp_tpu.train.losses import LOSSES, lm_crossentropy

SHAPES = [(1, 9, 17), (2, 16, 32), (3, 127, 50)]


def sliced_reference(logits, tokens):
    """What ``lm_crossentropy`` was until PR 31, in float32."""
    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1, :].astype(jnp.float32),
            tokens[:, 1:].astype(jnp.int32),
        )
    )


def _inputs(shape, dtype, seed=0):
    b, s, v = shape
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(3.0 * rng.normal(size=shape), dtype)
    tokens = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
    return logits, tokens


def _rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_value_and_gradient_equal_the_sliced_formula(shape, dtype):
    logits, tokens = _inputs(shape, dtype)
    got, got_grad = jax.value_and_grad(lm_crossentropy)(logits, tokens)
    want, want_grad = jax.value_and_grad(sliced_reference)(logits, tokens)
    assert got.dtype == jnp.float32 and got_grad.dtype == dtype
    assert got_grad.shape == logits.shape
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    # A bf16 gradient is the float32 one rounded once, on both sides: a
    # last-bit difference before the rounding may land on either side.
    assert _rel(got_grad, want_grad) < (1e-6 if dtype == jnp.float32 else 2 ** -8)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_last_position_gets_no_gradient_and_its_token_no_say(shape):
    logits, tokens = _inputs(shape, jnp.float32)
    loss, grad = jax.value_and_grad(lm_crossentropy)(logits, tokens)
    assert np.all(np.asarray(grad[:, -1, :]) == 0.0)
    assert np.any(np.asarray(grad[:, -2, :]) != 0.0)
    # The first token is no target either (nothing predicts it), and the
    # last token is the target of the position before it.
    vocab = shape[2]
    first = tokens.at[:, 0].set((tokens[:, 0] + 1) % vocab)
    loss_first, grad_first = jax.value_and_grad(lm_crossentropy)(logits, first)
    assert float(loss_first) == float(loss)
    np.testing.assert_array_equal(np.asarray(grad_first), np.asarray(grad))
    last = tokens.at[:, -1].set((tokens[:, -1] + 1) % vocab)
    grad_last = jax.grad(lm_crossentropy)(logits, last)
    assert np.all(np.asarray(grad_last[:, -1, :]) == 0.0)
    np.testing.assert_array_equal(
        np.asarray(grad_last[:, :-2, :]), np.asarray(grad[:, :-2, :]))
    assert np.any(np.asarray(grad_last[:, -2, :]) != np.asarray(grad[:, -2, :]))


def test_float_tokens_are_ids_as_before():
    logits, tokens = _inputs((2, 16, 32), jnp.float32)
    assert float(lm_crossentropy(logits, tokens.astype(jnp.float32))) == float(
        lm_crossentropy(logits, tokens))
    assert LOSSES["lm_ce"] is lm_crossentropy


def test_the_program_holds_the_logits_in_their_own_shape_only():
    """The mechanism itself: nothing of shape ``[B, S-1, V]`` exists, and
    the label's gradient is neither a scatter nor padded back."""
    logits, tokens = _inputs((2, 16, 32), jnp.float32)
    fn = jax.value_and_grad(lm_crossentropy)
    jaxpr = str(jax.make_jaxpr(fn)(logits, tokens))
    lowered = jax.jit(fn).lower(logits, tokens).as_text()
    for text in (jaxpr, lowered):
        assert not re.search(r"2x15x32|\[2,\s*15,\s*32\]", text)
        assert "scatter" not in text
        assert not re.search(r"\bpad\b", text)
    # The reference does contain them: the patterns can find what they ban.
    ref = jax.value_and_grad(sliced_reference)
    ref_jaxpr = str(jax.make_jaxpr(ref)(logits, tokens))
    ref_lowered = jax.jit(ref).lower(logits, tokens).as_text()
    assert re.search(r"\[2,\s*15,\s*32\]", ref_jaxpr)
    assert "2x15x32" in ref_lowered
    assert "scatter" in ref_jaxpr and re.search(r"\bpad\b", ref_jaxpr)


@pytest.mark.parametrize("axes,spec,ids_spec", [
    ((2, 1), P("dp"), P("dp")),
    ((1, 2), P(None, None, "tp"), P()),
], ids=["batch_sharded", "vocab_sharded"])
def test_sharded_value_and_gradient_equal_one_device(
        eight_cpu_devices, axes, spec, ids_spec):
    logits, tokens = _inputs((2, 16, 32), jnp.float32)
    want, want_grad = jax.jit(jax.value_and_grad(lm_crossentropy))(logits, tokens)
    mesh = Mesh(np.array(eight_cpu_devices[:2]).reshape(axes), ("dp", "tp"))
    sharded = jax.device_put(logits, NamedSharding(mesh, spec))
    ids = jax.device_put(tokens, NamedSharding(mesh, ids_spec))
    got, got_grad = jax.jit(jax.value_and_grad(lm_crossentropy))(sharded, ids)
    assert got_grad.sharding.is_equivalent_to(sharded.sharding, 3)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(got_grad), np.asarray(want_grad), rtol=1e-6, atol=1e-9)


def test_a_causal_lm_step_writes_the_norm_and_the_gradient_once():
    """Two things XLA would otherwise fuse into the head's products and
    compute again for every tile: the final norm's output
    (``CausalLM.__call__``) and the logits' gradient (the loss's backward).
    Both sit behind an ``optimization_barrier``; the model's own gradients
    agree with the sliced formula."""
    import flax.linen as nn

    from raydp_tpu.models.transformer import CausalLM, tiny_transformer

    model = CausalLM(cfg=tiny_transformer(
        max_len=16, vocab_size=32, dropout_rate=0.0, causal=True))
    _, tokens = _inputs((2, 16, 32), jnp.float32)
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), tokens))

    def step(loss):
        return jax.value_and_grad(
            lambda v: loss(model.apply(v, tokens), tokens))

    jaxpr = str(jax.make_jaxpr(step(lm_crossentropy))(variables))
    assert jaxpr.count("optimization_barrier") >= 3  # h, its cotangent, dlogits
    got, got_grads = step(lm_crossentropy)(variables)
    want, want_grads = step(sliced_reference)(variables)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    errors = jax.tree_util.tree_map(_rel, got_grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-5, errors
