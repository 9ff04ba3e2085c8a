"""The accepted programs are the parent's (PR 63): with the norm placement
stated once (``branch_norm`` False | True | "only") and the delta rules'
walk over segments shared (``ops/kda.segment_walk``), tiny stacks of the
accepted kinds — a looped stack with norms on inputs AND outputs, a KDA
layer beside a latent one and a routed FFN, a Mamba-2 layer beside grouped
attention, a multi-stream residual path, layers of one sublayer — have the
parameter tree and the gradient's jaxpr the PARENT of that PR gave them,
and ``kda_chunked``'s gradient traces to the parent's program by either
path. The pins are ``(len, sha256)`` of the text with memory addresses
stripped (as ``tests/test_loop_parent_programs.py``): regenerate them from
a PARENT tree (``PYTHONPATH=<parent> python <this file>``) if jax changes
how it prints."""
import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from raydp_tpu.models import (
    CausalLM, HyperConfig, KDAConfig, LoopLM, granite_h_micro,
    kimi_linear_48b_a3b, ouro_2_6b, tiny_transformer,
)
from raydp_tpu.models.latent import LatentConfig
from raydp_tpu.ops.kda import kda_chunked
from raydp_tpu.train import losses

IDS = jnp.zeros((2, 32), jnp.int32)


def _tiny(**more):
    return tiny_transformer(**{**dict(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64,
        causal=True, norm="rmsnorm", positions="rotary", use_bias=False,
        ffn="swiglu"), **more})


def _models():
    return {
        "ouro": (LoopLM(ouro_2_6b(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_len=64, passes=2, remat=True), entropy_weight=0.05),
            "loop_exit_ce"),
        "kimi": (CausalLM(kimi_linear_48b_a3b(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, dense_layers=1,
            d_ff=64, max_len=64, n_experts=4, top_k=2, d_expert=16,
            attention_impl="dense", remat=True,
            layer_types=("kda:swiglu", "latent:moe"),
            latent=LatentConfig(q_rank=None, kv_rank=8, nope_dim=8,
                                rope_dim=4, v_dim=8),
            kda=KDAConfig(heads=2, key_dim=8, value_dim=8, conv_taps=4,
                          gate_rank=4, chunk=8))), "lm_ce"),
        "granite": (CausalLM(granite_h_micro(
            n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
            vocab_size=64, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
            ssm_chunk=8, max_len=64, layer_types=("mamba", "attention"),
            remat=True)), "lm_ce"),
        "hyper": (CausalLM(_tiny(
            hyper=HyperConfig(streams=2, sinkhorn_iters=2))), "lm_ce"),
        "one_sublayer": (CausalLM(_tiny(
            n_layers=3, remat=True,
            layer_types=("attention:none", "none:swiglu", "conv"))),
            "lm_ce"),
    }


def program(name: str):
    """``(parameter tree, gradient jaxpr)`` of one tiny model, as text."""
    model, loss = _models()[name]
    loss = losses.LOSSES[loss]
    variables = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), IDS)))
    rngs = {"dropout": jax.random.PRNGKey(1)}

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


def scan_program(kernels: bool):
    """The jaxpr of ``kda_chunked``'s gradient over two segments' worth
    of chunks, by the ``jax.numpy`` form or the (interpreted) kernels."""
    like = jax.ShapeDtypeStruct
    d = 128 if kernels else 8
    chunk = 64 if kernels else 8
    s = 2 * chunk
    shapes = (like((1, s, 2, d), jnp.bfloat16), like((1, s, 2, d), jnp.bfloat16),
              like((1, s, 2, d), jnp.bfloat16), like((1, s, 2, d), jnp.float32),
              like((1, s, 2), jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kda_chunked(*a, chunk, kernels=kernels).astype(
            jnp.float32)), argnums=(0, 1, 2, 3, 4)))(*shapes))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


def _pin(text: str):
    return len(text), hashlib.sha256(text.encode()).hexdigest()


PARENT = {
    "ouro": (
        (1411, "3c910f1b06f0ea5502b5c379dd063f6a6fe9c5b164e7f7b4cce91dbee53c"
               "3e9b"),
        (193679, "38c2118389c9937a4a28d3491afb41e384d5751504cf94b27bcc113af1"
                 "9dad29")),
    "kimi": (
        (2764, "544e0e77bf13b1008cce9c4a6ea0d27a2cffc4be7083042905ba15580d44"
               "8843"),
        (467829, "6bf59caf38e111226d37f1904da3aa90915b62a829cebd8c7fb75b85bc"
                 "3df6d0")),
    "granite": (
        (1501, "3e426bf59a797ba489f27bf5f4ffef3fea8fe9f91748e6f6efcec0a1abe2"
               "42fe"),
        (104393, "9e0ef95e62f97ca328fce67f3465f9529dae66975beb66d06d4a00101d"
                 "3a13e4")),
    "hyper": (
        (2134, "1aa98943dc0bd95f7d6e41ee49abe1d3d1d592a024119573decea3157b97"
               "547c"),
        (139551, "41ec0c23e3828f43422104616aadf4552f0794496de4ffb63088a73956"
                 "15e154")),
    "one_sublayer": (
        (1111, "3fe516db0d340bb432f1183b44f1959b45c7e70dba1684859f525f01cd47"
               "205f"),
        (67664, "9c8ee669665126c21dd8b8f7a042b4789b96a044267d1e44c25635f1638"
                "4c4be")),
}
# The kernels' program is PR 68's, which changed it on purpose (the five
# kernels read and write the model's [b, s, h · d] arrays in place, the
# segment's index a scalar: ``ops/kda.KERNELS`` owns its layout and its
# loop); PR 63's parent gave (197576, "5d40005d...8864c6"). The plain
# form's is still that parent's.
PARENT_SCANS = {
    "plain": (77236, "ff82bcc18afa9eca43db3de7feecc45de495e7aa137ea6be0206f4"
                     "e674567bda"),
    "kernels": (191198, "77ee945f15d3c0ca56eff3ee1b1d1477d35bb57ea3e122ff372"
                        "9290c111ef814"),
}


@pytest.fixture(scope="module")
def programs():
    return {name: program(name) for name in PARENT}


@pytest.mark.parametrize("what", ["tree", "jaxpr"])
@pytest.mark.parametrize("name", [
    "granite", "hyper", "kimi", "one_sublayer", "ouro"])
def test_the_accepted_kinds_of_stack_are_the_parents_programs(
        programs, name, what):
    at = ("tree", "jaxpr").index(what)
    assert _pin(programs[name][at]) == PARENT[name][at]


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_kda_scan_walks_its_segments_as_the_parent_did(path):
    assert _pin(scan_program(path == "kernels")) == PARENT_SCANS[path]


if __name__ == "__main__":   # PYTHONPATH=<parent tree> python <this file>
    print("PARENT = {")
    for each in _models():
        tree, text = program(each)
        print(f"    {each!r}: ({_pin(tree)!r}, {_pin(text)!r}),")
    print("}\nPARENT_SCANS = {")
    for each in ("plain", "kernels"):
        print(f"    {each!r}: {_pin(scan_program(each == 'kernels'))!r},")
    print("}")
