"""The old programs are the old programs (PR 61): with ``passes`` = 1 and
``branch_norm`` off — what every configuration before the looped LM has —
a tiny ``CausalLM``, ``BlockDiffusionLM`` and routed share have the
parameter tree and the gradient's jaxpr the PARENT of that PR gave them,
and ``lm_crossentropy`` / ``weighted_crossentropy`` trace to the parent's
program (so their value and the logits' gradient are the parent's to the
bit) although ``_weighted_ce``'s weights can now get a cotangent. The
pins are ``(len, sha256)`` of the text with memory addresses stripped:
regenerate them from a PARENT tree with :func:`program` if jax changes how
it prints."""
import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import (
    BlockDiffusionConfig, BlockDiffusionLM, CausalLM, lfm2_8b_a1b, olmoe,
    sdar_30b_a3b, tiny_transformer,
)
from raydp_tpu.train import losses

IDS = jnp.zeros((2, 32), jnp.int32)


def _dense(**more):
    return CausalLM(tiny_transformer(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64,
        causal=True, norm="rmsnorm", positions="rotary", use_bias=False,
        ffn="swiglu", **more))


def _models():
    return {
        "dense": (_dense(), "lm_ce"),
        "dense_remat": (_dense(remat=True), "lm_ce"),
        "routed": (CausalLM(olmoe(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, n_experts=4,
            top_k=2, d_expert=16, max_len=64)), "lm_ce"),
        "share": (CausalLM(lfm2_8b_a1b(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3,
            d_ff=64, n_experts=8, top_k=2, d_expert=16, experts_held=2,
            first_expert=2, max_len=64, remat=True,
            layer_types=("conv:swiglu", "attention:moe", "conv:moe"))),
            "lm_ce"),
        "blockdiff": (BlockDiffusionLM(sdar_30b_a3b(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, head_size=8,
            n_layers=2, n_experts=4, top_k=2, d_expert=16, max_len=64,
            diffusion=BlockDiffusionConfig(block_length=4, mask_id=63))),
            "blockdiff_ce"),
    }


def program(name: str):
    """``(parameter tree, gradient jaxpr)`` of one tiny model, as text."""
    model, loss = _models()[name]
    loss = losses.LOSSES[loss]
    variables = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), IDS)))
    rngs = {"dropout": jax.random.PRNGKey(1), "noise": jax.random.PRNGKey(2)}

    def objective(v, ids):
        preds, _ = model.apply(
            v, ids, deterministic=False, rngs=rngs,
            mutable=["losses", "moe_stats"])
        return loss(preds, ids)

    tree = "\n".join(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(variables))
    text = str(jax.make_jaxpr(jax.grad(objective))(variables, IDS))
    return tree, re.sub(r" at 0x[0-9a-f]+", "", text)


def loss_program(name: str):
    logits = jax.ShapeDtypeStruct((2, 32, 64), jnp.float32)
    weights = jax.ShapeDtypeStruct((2, 32), jnp.float32)
    if name == "lm_ce":
        fn, args = losses.lm_crossentropy, (logits, IDS)
    else:
        fn, args = losses.weighted_crossentropy, (logits, IDS, weights)
    return re.sub(r" at 0x[0-9a-f]+", "", str(
        jax.make_jaxpr(jax.value_and_grad(fn))(*args)))


def _pin(text: str):
    return len(text), hashlib.sha256(text.encode()).hexdigest()


PARENT = {
    "dense": (
        (1034, "65103401b1401a41d6330f4c32cfdda69284cbdc7e0ca1a5ff6f6f5fab1d"
               "0afc"),
        (51025, "69bd0a5cda9114a62c9cb112f5ee5f51231a4e1aa04ea5867976a275feb"
                "d64ba")),
    "dense_remat": (
        (1034, "65103401b1401a41d6330f4c32cfdda69284cbdc7e0ca1a5ff6f6f5fab1d"
               "0afc"),
        (75953, "0b245bcc73481de6a219134741ad388ad39c8ed9f6d7863ee46a9feade0"
                "9b843")),
    "routed": (
        (1888, "7527f525c0f42d50742932f1367e4dfcee16bf0d1bf790c0dcecc184c608"
               "b1d5"),
        (336675, "5123c15f30a55ef8da4fb02e367ff8e318683c2892fc8e3e5f8422baa4"
                 "2654cf")),
    "share": (
        (2501, "1e5e43d8ef3884ce59aba89f8c0c305a650c6c3209e4c7782af23114a21d"
               "b2b6"),
        (444444, "c451dac0fe53fa69b38fe0bb69b65b5fad23eb41a8cbd4a15aded6ee10"
                 "4206b5")),
    "blockdiff": (
        (2001, "9de4fb39c544e643ee1756b8549697b9c6fa63f920d913bc348040ab4551"
               "7440"),
        (336123, "a4e235822a5a85995306c0c325e0bf803fbde2667259b87942ec8c9b02"
                 "26b628")),
}
PARENT_LOSSES = {
    "lm_ce": (2976, "1c6b87cbe099281c9c45a8b131e8777ca1ffb8c03a6b7725992175b"
                    "1ff54fd48"),
    "weighted_ce": (2276, "30bcb18cce0f9d6f6b357f13bd35d9a158e75e22b2349e7cf"
                          "9422b6a0e30c728"),
}


@pytest.fixture(scope="module")
def programs():
    return {name: program(name) for name in PARENT}


@pytest.mark.parametrize("what", ["tree", "jaxpr"])
@pytest.mark.parametrize("name", sorted(PARENT))
def test_one_pass_without_output_norms_is_the_parents_program(
        programs, name, what):
    at = ("tree", "jaxpr").index(what)
    assert _pin(programs[name][at]) == PARENT[name][at]


@pytest.mark.parametrize("name", sorted(PARENT_LOSSES))
def test_the_losses_trace_to_the_parents_program(name):
    assert _pin(loss_program(name)) == PARENT_LOSSES[name]


def _plain(logits, targets, weights, count):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) / count


@pytest.mark.parametrize("shape", [(2, 16), (1, 16)], ids=["token", "row"])
def test_weights_that_learn_get_the_tokens_cross_entropy(shape):
    """``_weighted_ce((count, True), ...)``: the value and the logits'
    gradient are those of ``(count, False)`` to the bit, and the weights'
    cotangent is ``jax.grad`` of the plain expression, summed over the
    axes the weights broadcast along."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 16, 24)), jnp.float32) * 3
    targets = jnp.asarray(rng.integers(0, 24, (2, 16)), jnp.int32)
    weights = jnp.asarray(rng.random(shape), jnp.float32)
    count = 7

    def run(learn):
        return jax.value_and_grad(
            lambda x, w: losses._weighted_ce(
                (count, learn), x, targets, w) * 1.5, argnums=(0, 1),
        )(logits, weights)

    (still, (dx0, dw0)), (value, (dx, dw)) = run(False), run(True)
    assert value == still and bool((dx == dx0).all())
    assert not dw0.any() and dw.shape == weights.shape
    want, (wx, ww) = jax.value_and_grad(
        lambda x, w: _plain(x, targets, w, count) * 1.5, argnums=(0, 1),
    )(logits, weights)
    np.testing.assert_allclose(value, want, rtol=1e-6)
    np.testing.assert_allclose(dx, wx, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(dw, ww, rtol=1e-5, atol=1e-7)


if __name__ == "__main__":        # python tests/test_loop_parent_programs.py
    print("PARENT = {")
    for each in _models():
        tree, text = program(each)
        print(f"    {each!r}: ({_pin(tree)!r}, {_pin(text)!r}),")
    print("}\nPARENT_LOSSES = {")
    for each in ("lm_ce", "weighted_ce"):
        print(f"    {each!r}: {_pin(loss_program(each))!r},")
    print("}")
