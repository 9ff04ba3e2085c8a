"""The accepted programs are the parent's (PR 66): with the exits' targets,
counts and scopes made per exit in ``train/losses._exits_ce``, the
encoder able to hand out the state before its final norm, and a step's
trace beginning with ``stats.begin_step``, tiny stacks of the accepted LM
kinds — Ouro's looped stack first (its loss goes through the changed
function), a latent-attention share inside hyper-connected streams, a KDA
layer beside a latent one, a short-convolution hybrid's share, a routed
stack that holds every expert, a state-space hybrid — have the parameter
tree and the gradient's jaxpr that the PARENT of PR 67 gave them. The pins
are ``(len, sha256)`` of the text with memory addresses stripped (as
``tests/test_norm_placement_parent_programs.py``): regenerate them from a
PARENT tree (``PYTHONPATH=<parent> python <this file>``) if jax changes
how it prints."""
import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from raydp_tpu.models import (
    CausalLM, HyperConfig, KDAConfig, LoopLM, granite_h_micro,
    kimi_linear_48b_a3b, lfm2_8b_a1b, olmoe, ouro_2_6b, xing4_0,
)
from raydp_tpu.models import step as model_step
from raydp_tpu.models.latent import LatentConfig
from raydp_tpu.train import losses

IDS = jnp.zeros((2, 32), jnp.int32)
TINY = dict(vocab_size=64, d_model=32, n_layers=2, d_ff=64, max_len=64)
LATENT = LatentConfig(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8)


def _models():
    return {
        "ouro": (LoopLM(ouro_2_6b(
            **TINY, n_heads=4, passes=3, remat=True), entropy_weight=0.05),
            "loop_exit_ce"),
        "xing4": (CausalLM(xing4_0(
            **TINY, n_heads=2, dense_layers=1, n_experts=8, experts_held=2,
            first_expert=2, top_k=2, d_expert=16, attention_impl="dense",
            remat=True, latent=LATENT,
            hyper=HyperConfig(streams=2, sinkhorn_iters=2))), "lm_ce"),
        "kimi": (CausalLM(kimi_linear_48b_a3b(
            **TINY, n_heads=2, dense_layers=1, n_experts=4, top_k=2,
            d_expert=16, attention_impl="dense", remat=True,
            layer_types=("kda:swiglu", "latent:moe"),
            latent=LatentConfig(q_rank=None, kv_rank=8, nope_dim=8,
                                rope_dim=4, v_dim=8),
            kda=KDAConfig(heads=2, key_dim=8, value_dim=8, conv_taps=4,
                          gate_rank=4, chunk=8))), "lm_ce"),
        "lfm2": (CausalLM(lfm2_8b_a1b(
            **{**TINY, "n_layers": 3}, n_heads=4, n_kv_heads=2, n_experts=8,
            experts_held=2, top_k=2, d_expert=16, attention_impl="dense",
            remat=True)), "lm_ce"),
        "olmoe": (CausalLM(olmoe(
            **TINY, n_heads=2, n_experts=4, top_k=2, d_expert=16,
            attention_impl="dense", remat=True)), "lm_ce"),
        "granite": (CausalLM(granite_h_micro(
            **TINY, n_heads=4, n_kv_heads=2, ssm_heads=4, ssm_head_dim=8,
            ssm_state=8, ssm_chunk=8, layer_types=("mamba", "attention"),
            remat=True)), "lm_ce"),
    }


def program(name: str):
    """``(parameter tree, gradient jaxpr)`` of one tiny model, as text: a
    train step's loss as ``JAXEstimator`` takes it (``apply_kwargs``, the
    sown collections, ``step_stats``)."""
    model, loss = _models()[name]
    loss = losses.LOSSES[loss]
    variables = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), IDS)))

    def objective(v, ids):
        preds, sown = model.apply(
            v, ids, mutable=model_step.SOWN,
            **model_step.apply_kwargs(model, jax.random.PRNGKey(1)))
        return loss(preds, ids) + model_step.aux_loss(sown), (
            model_step.step_stats(sown))

    tree = "\n".join(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(variables))
    text = str(jax.make_jaxpr(jax.grad(objective, has_aux=True))(
        variables, IDS))
    return tree, re.sub(r" at 0x[0-9a-f]+", "", text)


def _pin(text: str):
    return len(text), hashlib.sha256(text.encode()).hexdigest()


PARENT = {
    'ouro': ((1411, '3c910f1b06f0ea5502b5c379dd063f6a'
              '6fe9c5b164e7f7b4cce91dbee53c3e9b'),
              (285893, '4f32cffbc5d299c3445731a691071de3'
              '91821e8892ef26219ffa6db05a65f61e')),
    'xing4': ((3416, '799fc24895ce215c1a2ee4b52a87ff02'
              '43c85ebbea02616387bf1fa5cdafa112'),
              (504047, '2cf43e327de237f2e11d000a24cb0520'
              '051fa970438fd5065f27b32bf803f32a')),
    'kimi': ((2764, '544e0e77bf13b1008cce9c4a6ea0d27a'
              '2cffc4be7083042905ba15580d448843'),
              (468117, '9756b082875a0221e07fcc0fbe2d550b'
              'a0df3104dd596052231bcec6cbf1ae9d')),
    'lfm2': ((2146, '344171c11551af6f6533a951747acc0f'
              'dd3a7af39ad81e8b3619a18811d52825'),
              (366156, 'd66df0cc7400b9d769cd5bd07441216c'
              '9259b39eff66cb8c7d699ab3a0bd0e9d')),
    'olmoe': ((1888, '7527f525c0f42d50742932f1367e4dfc'
              'ee16bf0d1bf790c0dcecc184c608b1d5'),
              (442653, 'c634c28cf543270713f732d2020eb97e'
              'ce076d5ffb4f60b6c4d8a1b44c44f136')),
    'granite': ((1501, '3e426bf59a797ba489f27bf5f4ffef3f'
              'ea8fe9f91748e6f6efcec0a1abe242fe'),
              (104663, '5552a8e75f8f5b1db60e40deb029b332'
              '8876423b3795dbcc7b7f3c10878e1e16')),
}


@pytest.fixture(scope="module")
def programs():
    return {name: program(name) for name in PARENT}


@pytest.mark.parametrize("what", ["tree", "jaxpr"])
@pytest.mark.parametrize("name", [
    "ouro", "xing4", "kimi", "lfm2", "olmoe", "granite"])
def test_the_accepted_lm_steps_are_the_parents_programs(programs, name, what):
    at = ("tree", "jaxpr").index(what)
    assert _pin(programs[name][at]) == PARENT[name][at]


if __name__ == "__main__":   # PYTHONPATH=<parent tree> python <this file>
    print("PARENT = {")
    for each in _models():
        tree, text = program(each)
        print(f"    {each!r}: ({_pin(tree)!r},\n              {_pin(text)!r}),")
    print("}")
