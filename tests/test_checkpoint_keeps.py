"""What a checkpointed block keeps of its flash attention call (PR 39).

The forward rule of ``ops/flash_attention.py`` names the two residuals
only the forward kernel can make (its output in [B, H, S, D_v] and a
lane-dense [B, H, S] float32 ``lse``), and ``TransformerEncoder`` wraps a
checkpointed block with a policy that keeps those names: the backward then
holds no second forward call. The kernels are Mosaic-only, so the model's
call runs them in the Pallas interpreter here.
"""
import functools
import importlib.util
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models.latent import LatentConfig
from raydp_tpu.models.transformer import CausalLM, tiny_transformer
from raydp_tpu.models.window import WindowConfig
from raydp_tpu.ops.flash_attention import (
    KEPT,
    _flash_bwd_pair,
    _flash_fwd_rule,
    flash_attention,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
# Two layers of one mixer each: (overrides, query heads, output width).
MIXERS = {
    "full": (dict(n_heads=2), 2, 16),
    "window": (dict(
        n_heads=2, n_kv_heads=2, head_size=16,
        layer_types=("window", "window"),
        window=WindowConfig(window=16, n_heads=4),
    ), 4, 16),
    "latent_192_128": (dict(
        n_heads=2, positions="rotary", layer_types=("latent", "latent"),
        latent=LatentConfig(q_rank=24, kv_rank=16, nope_dim=128, rope_dim=64,
                            v_dim=128),
    ), 2, 128),
}


@pytest.fixture
def interpreted(monkeypatch):
    module = sys.modules["raydp_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "flash_attention", functools.partial(
        module.flash_attention, interpret=True))


@pytest.fixture
def plain_checkpoint(monkeypatch):
    """Calling it takes the policy off the block's checkpoint: what the
    parent's ``nn.remat(TransformerBlock)`` was."""
    def take_off():
        monkeypatch.setattr(jax.checkpoint_policies,
                            "save_only_these_names", lambda *names: None)
    return take_off


def _model(mixer, impl="flash", remat=True):
    overrides, _, _ = MIXERS[mixer]
    cfg = tiny_transformer(**{**dict(
        vocab_size=64, d_model=32, d_ff=64, max_len=SEQ, n_layers=2,
        causal=True, attention_impl=impl, dtype=jnp.float32, remat=remat,
    ), **overrides})
    model = CausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (1, SEQ)).astype(np.int32))
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))

    def loss(params):
        return jnp.sum(model.apply(params, ids) ** 2)
    return loss, variables


def _eqns(jaxpr, name):
    """Every equation of a primitive whose name starts with ``name``,
    through every nested jaxpr, each as often as it is written."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith(name):
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _eqns(sub, name)
    return found


def _forward_calls(jaxpr):
    """The forward kernel is the call whose second result is the
    [B, H, S, 1] column of ``lse``; the backward kernel has three results,
    all of them wide."""
    return [e for e in _eqns(jaxpr, "pallas_call")
            if len(e.params["out_avals"]) == 2
            and e.params["out_avals"][1].shape[-1] == 1]


def _kept(jaxpr):
    """(shape, dtype) of what enters each block's backward: the operands
    of the checkpoint's equation in the gradient."""
    blocks = _eqns(jaxpr, "remat") + _eqns(jaxpr, "checkpoint")
    assert len(blocks) == 2
    return [sorted((tuple(v.aval.shape), str(v.aval.dtype))
                   for v in eqn.invars) for eqn in blocks]


# (a) one forward call a layer, where the plain checkpoint holds two.

@pytest.mark.parametrize("mixer", list(MIXERS))
def test_the_backward_of_a_checkpointed_block_runs_no_second_forward(
        mixer, interpreted, plain_checkpoint):
    loss, variables = _model(mixer)
    kept = jax.make_jaxpr(jax.grad(loss))(variables).jaxpr
    assert len(_eqns(kept, "pallas_call")) == 4      # 2 a layer
    assert len(_forward_calls(kept)) == 2
    plain_checkpoint()
    loss, variables = _model(mixer)
    plain = jax.make_jaxpr(jax.grad(loss))(variables).jaxpr
    assert len(_eqns(plain, "pallas_call")) == 6
    assert len(_forward_calls(plain)) == 4


def test_on_a_mesh_the_names_are_seen_through_the_shard_map(
        eight_cpu_devices, monkeypatch):
    """``sharded_flash_attention`` (dp=2, tp=2: a head a device): the
    policy reaches the names inside the ``shard_map``."""
    from raydp_tpu.parallel import MeshSpec

    module = sys.modules["raydp_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "sharded_flash_attention", functools.partial(
        module.sharded_flash_attention, interpret=True))
    mesh = MeshSpec(dp=2, tp=2).build(eight_cpu_devices[:4])
    monkeypatch.setitem(MIXERS, "on_a_mesh", (dict(n_heads=2, mesh=mesh), 2, 16))
    loss, variables = _model("on_a_mesh")
    grad = jax.make_jaxpr(jax.grad(loss))(variables).jaxpr
    assert len(_eqns(grad, "pallas_call")) == 4
    assert len(_forward_calls(grad)) == 2


# (b) the same loss and gradients, to the bit.

@pytest.mark.parametrize("mixer", list(MIXERS))
def test_keeping_changes_no_bit_of_loss_or_gradient(
        mixer, interpreted, plain_checkpoint):
    def run(remat=True):
        loss, variables = _model(mixer, remat=remat)
        # Operation by operation: compiled as one program a checkpoint's
        # body fuses, and rounds, differently from the same code unwrapped.
        with jax.disable_jit():
            value, grads = jax.value_and_grad(loss)(variables)
        return [np.asarray(value)] + [
            np.asarray(g) for g in jax.tree_util.tree_leaves(grads)]

    kept, unwrapped = run(), run(remat=False)
    plain_checkpoint()
    plain = run()
    assert len(kept) == len(plain) == len(unwrapped) > 10
    for got, same, also in zip(kept, plain, unwrapped):
        np.testing.assert_array_equal(got, same)
        np.testing.assert_array_equal(got, also)


# (c) what is kept: the kernel's output and a DENSE lse.

@pytest.mark.parametrize("mixer", list(MIXERS))
def test_a_block_keeps_the_output_and_a_lane_dense_lse(mixer, interpreted):
    _, heads, width = MIXERS[mixer]
    loss, variables = _model(mixer)
    for kept in _kept(jax.make_jaxpr(jax.grad(loss))(variables).jaxpr):
        assert kept.count(((1, heads, SEQ, width), "float32")) == 1
        assert kept.count(((1, heads, SEQ), "float32")) == 1
        assert not [s for s, _ in kept if s == (1, heads, SEQ, 1)]
        # Nothing else of a head's size: not q, k or v.
        assert len([s for s, _ in kept if len(s) == 4 and s[2] == SEQ]) == 1


def test_the_plain_checkpoint_kept_neither(interpreted, plain_checkpoint):
    plain_checkpoint()
    loss, variables = _model("full")
    for kept in _kept(jax.make_jaxpr(jax.grad(loss))(variables).jaxpr):
        assert not [s for s, _ in kept if s[:2] == (1, 2)]


# (d) a block that calls no flash kernel keeps what it kept.

@pytest.mark.parametrize("mixer", ["full", "window"])
def test_a_dense_block_keeps_what_it_kept(mixer, plain_checkpoint):
    def kept():
        loss, variables = _model(mixer, impl="dense")
        return _kept(jax.make_jaxpr(jax.grad(loss))(variables).jaxpr)

    with_policy = kept()
    plain_checkpoint()
    assert with_policy == kept()
    # No activation of a head's shape: the block's input and weights.
    assert not [s for s, _ in with_policy[0] if len(s) == 4 and s[0] == 1]


# (e) outside any checkpoint the names are identities.

def _inputs(d, d_v):
    rng = np.random.default_rng(39)
    q = rng.standard_normal((1, SEQ, 2, d)).astype(np.float32)
    k = rng.standard_normal((1, SEQ, 1, d)).astype(np.float32)
    v = rng.standard_normal((1, SEQ, 1, d_v)).astype(np.float32)
    w = rng.standard_normal((1, SEQ, 2, d_v)).astype(np.float32)
    return q, k, v, w


@pytest.mark.parametrize("case,d,d_v,kw", [
    ("full", 16, 16, {}), ("window", 16, 16, {"window": 24}),
    ("two_widths", 24, 16, {}),
])
def test_outside_a_checkpoint_the_call_gives_the_parents_bits(
        case, d, d_v, kw):
    """``tests/data/flash_attention_parent_pr38.npz``: output and three
    gradients of these calls at PR 39's parent commit (24ab4f4), where the
    residual ``lse`` was the kernel's [B, H, S, 1] column and the backward
    the dq and dk/dv kernels. The pair's rule still gives those bits; the
    one kernel gives dk's and dv's (the same tiles in the same order) and
    dq to a rounding of float32 (its tile products run transposed)."""
    recorded = np.load(os.path.join(
        REPO, "tests", "data", "flash_attention_parent_pr38.npz"))
    q, k, v, w = _inputs(d, d_v)
    call = functools.partial(flash_attention, causal=True, block_q=32,
                             block_kv=32, interpret=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(call(q, k, v)), recorded[f"{case}.out"])
    grads = jax.grad(
        lambda *a: jnp.sum(call(*a) * w), argnums=(0, 1, 2))(q, k, v)
    window = kw.get("window")
    _, res = _flash_fwd_rule(q, k, v, True, 32, 32, True, d ** -0.5, window)
    pair = _flash_bwd_pair(True, 32, 32, True, d ** -0.5, window, res, w)
    for got, two, name in zip(grads, pair, "qkv"):
        want = recorded[f"{case}.d{name}"]
        np.testing.assert_array_equal(np.asarray(two), want, err_msg=name)
        if name == "q":
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=1e-6, atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)


def test_the_kernels_take_lse_in_the_shapes_they_took():
    """Rows for the one backward kernel, [B, H, 1, S], laid out from the
    dense residual, and no [B, H, S, 1] float32 column into any backward
    call (the pair's dq kernel takes one still, its dk/dv kernel rows);
    the names sit in the forward rule and nowhere in the primal call."""
    q, k, v, _ = _inputs(16, 16)
    call = functools.partial(flash_attention, causal=True, block_q=32,
                             block_kv=32, interpret=True)
    grad = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(call(*a)), argnums=(0, 1, 2)))(q, k, v).jaxpr
    _, backward = _eqns(grad, "pallas_call")
    assert len(backward.params["out_avals"]) == 3
    assert [tuple(v.aval.shape) for v in backward.invars[4:6]] == [
        (1, 2, 1, SEQ)] * 2
    assert not [v for v in backward.invars
                if tuple(v.aval.shape) == (1, 2, SEQ, 1)]

    def pair(q, k, v):
        out, res = _flash_fwd_rule(q, k, v, True, 32, 32, True, 0.25, None)
        return _flash_bwd_pair(True, 32, 32, True, 0.25, None, res, out)
    _, dq, dkv = _eqns(jax.make_jaxpr(pair)(q, k, v).jaxpr, "pallas_call")
    assert tuple(dq.invars[4].aval.shape) == (1, 2, SEQ, 1)
    assert tuple(dkv.invars[4].aval.shape) == (1, 2, 1, SEQ)
    assert sorted(e.params["name"] for e in _eqns(grad, "name")) == sorted(
        KEPT)
    assert not _eqns(jax.make_jaxpr(call)(q, k, v).jaxpr, "name")


# (f) the gauges, where the step is built: what is kept (PR 39) and which
# backward the calls take (PR 40).

def _published(config):
    """The cell's configuration as its builder makes it (no kernel runs:
    the report takes the configuration and the step's shapes alone)."""
    with open(os.path.join(
            REPO, "benchmark", "configs", config + ".json")) as f:
        sizes = json.load(f)
    path = os.path.join(
        REPO, "benchmark", "configs", sizes["builder"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "builder_" + sizes["builder"], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.model_config(sizes)


@pytest.mark.parametrize("config,seq,batch,layers,mib,fused,resident", [
    ("laguna_xs_2", 16384, 1, 5, 1170.0, 5, 24.0),
    ("lfm2_8b_a1b", 8192, 1, 2, 66.0, 2, 6.0),
    ("xing4_0_29b_a4b", 4096, 1, 5, 162.5, 5, 8.0),
    ("granite_4_0_h_micro", 4096, 1, 1, 16.5, 1, 3.0),
    ("olmoe_1b_7b", 4096, 2, 0, 0.0, 1, 6.0),     # flash, but no checkpoint
])
def test_the_step_reports_the_layers_it_keeps_and_their_size(
        config, seq, batch, layers, mib, fused, resident, caplog):
    from raydp_tpu.ops.flash_attention import report
    from raydp_tpu.utils.profiling import metrics

    cfg = _published(config)
    assert cfg.attention_impl == "flash" and cfg.remat == (layers > 0)
    with caplog.at_level("INFO", logger="raydp_tpu.ops.flash_attention"):
        report(cfg, seq_len=seq, batch=batch)
    assert metrics.gauge_value("attention/flash_kept_layers") == layers
    assert metrics.gauge_value("attention/flash_kept_mib") == pytest.approx(
        mib, abs=0.3)
    assert metrics.gauge_value("attention/flash_fused_bwd_layers") == fused
    assert metrics.gauge_value(
        "attention/flash_bwd_resident_mib") == resident
    lines = [r.getMessage() for r in caplog.records]
    said = (f"the block checkpoint keeps the output and lse of {layers} "
            f"layers' calls ({mib:.0f} MiB)") if layers else (
        "no checkpoint around the calls")
    assert lines and all(said in line for line in lines), lines
    assert all("the backward is one kernel" in line for line in lines)


def test_a_fit_sets_the_gauges_and_a_dense_model_reads_zero(
        interpreted, caplog):
    import optax

    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    def build(impl, remat):
        overrides, _, _ = MIXERS["window"]
        JAXEstimator(
            model=CausalLM(cfg=tiny_transformer(**{**dict(
                vocab_size=64, d_model=32, d_ff=64, max_len=SEQ, n_layers=2,
                causal=True, attention_impl=impl, dtype=jnp.float32,
                remat=remat), **overrides})),
            optimizer=optax.adamw(2e-5), loss="lm_ce", feature_columns=["t"],
            batch_size=3, feature_dtype=np.int32, seed=0,
        )._init_state(np.zeros((3, SEQ), np.int32))

    with caplog.at_level("INFO", logger="raydp_tpu.ops.flash_attention"):
        build("flash", True)
    assert metrics.gauge_value("attention/flash_kept_layers") == 2
    # Two layers of 4 heads, 3 x 64 positions of 16 floats and an lse.
    assert metrics.gauge_value("attention/flash_kept_mib") == (
        2 * 4 * 3 * SEQ * (16 * 4 + 4) / 2 ** 20)
    assert "lse of 2 layers' calls" in caplog.records[-1].getMessage()
    assert metrics.gauge_value("attention/flash_fused_bwd_layers") == 2
    # dq, dk and dv of one head in float32: 64 positions of 3 x 16.
    assert metrics.gauge_value("attention/flash_bwd_resident_mib") == (
        4 * SEQ * 3 * 16 / 2 ** 20)
    build("flash", False)
    assert metrics.gauge_value("attention/flash_kept_layers") == 0
    assert metrics.gauge_value("attention/flash_fused_bwd_layers") == 2
    build("dense", True)
    assert metrics.gauge_value("attention/flash_kept_layers") == 0
    assert metrics.gauge_value("attention/flash_kept_mib") == 0
    assert metrics.gauge_value("attention/flash_fused_bwd_layers") == 0
    assert metrics.gauge_value("attention/flash_bwd_resident_mib") == 0
