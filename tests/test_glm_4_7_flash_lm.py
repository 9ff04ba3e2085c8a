"""The GLM-4.7-Flash style configuration WITH its multi-token-prediction
module on the normal path, at tiny widths on the CPU, float32: a few steps
through ``JAXEstimator.fit`` against the benchmark's plain reference (the
first step's loss and every parameter's move), the gauges a fit sets, the
library's preset, and the ONE test that ties the share to the model: the
eight shares' results of a routed layer AND of the module's block, the
shared expert (and everything else every chip computes alike) counted
once, add up to the uncut reference's."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest

from raydp_tpu.models import MoELayer, glm_4_7_flash
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models.transformer import TransformerBlock
from raydp_tpu.train import JAXEstimator
from raydp_tpu.utils.profiling import metrics

from test_mtp import SEQ, SIZES, init, load_builder

RATE = 0.5


@pytest.fixture(scope="module")
def builder():
    return load_builder()


def _estimator(builder, optimizer):
    return JAXEstimator(
        model=builder.model(SIZES), optimizer=optimizer, loss="mtp_ce",
        self_supervised=True, aux_losses=True, batch_size=4, seed=3,
        epoch_mode="stream", shuffle=False,
        feature_columns=[f"t{i}" for i in range(SEQ)], feature_dtype=np.int32,
    )


@pytest.fixture(scope="module")
def one_step(builder):
    """One SGD step through ``fit``: the state before, after, the loss the
    epoch reported and the reference's loss and gradients on that batch."""
    rows = np.random.default_rng(1).integers(
        0, SIZES["vocab_size"], (4, SEQ)).astype(np.int32)
    est = _estimator(builder, optax.sgd(RATE))
    est._init_state(rows)
    before = jax.tree_util.tree_map(np.asarray, est._state.params)
    frame = pd.DataFrame({f"t{i}": rows[:, i] for i in range(SEQ)})
    history = est.fit_on_df(frame, num_epochs=1, num_shards=1)
    after = jax.tree_util.tree_map(np.asarray, est._state.params)
    loss, grads = jax.jit(
        lambda v, x: builder.reference_loss_and_grads(v, x, SIZES)
    )(before, jnp.asarray(rows))
    return before, after, history, float(loss), grads


def test_the_first_steps_loss_is_the_references(one_step):
    _, _, history, loss, _ = one_step
    assert history[0]["train_loss"] == pytest.approx(loss, rel=1e-5)
    main = metrics.gauge_value("train/loss_main")
    module = metrics.gauge_value("train/loss_mtp")
    assert main + 0.3 * module == pytest.approx(loss, rel=1e-5)
    assert 0.5 < module / main < 2


@pytest.mark.parametrize("tree", ["encoder", "lm_head", "mtp"])
def test_every_parameter_moves_by_the_references_gradient(one_step, tree):
    before, after, _, _, grads = one_step
    flat = dict(jax.tree_util.tree_leaves_with_path(grads["params"][tree]))
    moved = jax.tree_util.tree_map(
        lambda a, b: (a - b) / RATE, before["params"][tree],
        after["params"][tree])
    for path, step in jax.tree_util.tree_leaves_with_path(moved):
        want = np.asarray(flat[path])
        scale = np.abs(want).max()
        assert scale > 0, path
        np.testing.assert_allclose(
            step, want, rtol=0, atol=2e-3 * scale + 5e-7,
            err_msg=jax.tree_util.keystr(path))
    # What no step may change did not, by one bit.
    for a, b in zip(jax.tree_util.tree_leaves(before[moe_module.BUFFERS]),
                    jax.tree_util.tree_leaves(after[moe_module.BUFFERS])):
        np.testing.assert_array_equal(a, b)


def test_fit_trains_and_reports_the_gauges(builder):
    rows = np.random.default_rng(2).integers(0, 64, (16, SEQ)).astype(np.int32)
    est = _estimator(builder, optax.adamw(3e-3))
    frame = pd.DataFrame({f"t{i}": rows[:, i] for i in range(SEQ)})
    history = est.fit_on_df(frame, num_epochs=3, num_shards=2)
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    assert all(np.isfinite(h["train_loss"]) for h in history)
    assert metrics.gauge_value("mtp/depth") == 1
    assert metrics.gauge_value("mtp/params") == builder.layer_params(
        SIZES)["mtp"]
    assert metrics.gauge_value("mtp/loss_weight") == pytest.approx(0.3)
    assert metrics.gauge_value("loop/exits_live") == 0
    assert metrics.gauge_value("attention/latent_layers") == 2
    assert metrics.gauge_value("moe/shared_experts") == 1
    assert metrics.gauge_value("moe/experts_held") == 2
    # The module's routed layer is counted as a layer: 1 in the stack and
    # the module's, 4 x 16 tokens, 2 experts a token.
    assert metrics.gauge_value("moe/expert_tokens_per_step") == 2 * 64 * 2
    assert 0 < metrics.gauge_value("moe/held_pair_share") < 1
    assert metrics.gauge_value("checkpoint/blocks") == 3
    main = metrics.gauge_value("train/loss_main")
    module = metrics.gauge_value("train/loss_mtp")
    assert history[-1]["train_loss"] == pytest.approx(
        main + 0.3 * module, rel=1e-4)
    # ``predict`` is the main head's.
    assert est.predict(rows[:3]).shape == (3, SEQ, SIZES["vocab_size"])


def test_the_librarys_preset_is_the_published_model():
    cfg = glm_4_7_flash()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.d_expert,
            cfg.vocab_size, cfg.max_len) == (
        47, 2048, 20, 10240, 1536, 154880, 202752)
    assert cfg.kinds == ("latent",) * 47
    assert cfg.ffn_kinds == ("swiglu",) + ("moe",) * 46
    lat = cfg.latent
    assert (lat.q_rank, lat.kv_rank, lat.nope_dim, lat.rope_dim, lat.v_dim,
            lat.yarn) == (768, 512, 192, 64, 256, None)
    assert lat.softmax_scale == 256 ** -0.5
    moe = cfg.moe_config()
    assert (moe.n_experts, moe.held, moe.top_k, moe.shared_experts,
            moe.scoring, moe.selection_bias, moe.normalize_gates,
            moe.gate_scale) == (64, 64, 4, 1, "sigmoid", True, True, 1.8)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.hyper, cfg.tie_head) == (
        1e6, 1e-5, None, False)


# ------------------------------------------------------ the share test

def _share_of(variables, share):
    """The variables chip ``share`` of eight holds of a routed layer's."""
    def cut(tree):
        if not isinstance(tree, dict):
            return tree
        return {k: v[share:share + 1] if k in ("w_gate", "w_up", "w_down")
                else cut(v) for k, v in tree.items()}
    return cut(variables)


def _without_routed(variables):
    def zero(tree):
        if not isinstance(tree, dict):
            return tree
        return {k: jnp.zeros_like(v[:1]) if k == "w_down" else (
            v[:1] if k in ("w_gate", "w_up") else zero(v))
            for k, v in tree.items()}
    return zero(variables)


@pytest.mark.parametrize("what", ["routed_layer", "module_block"])
def test_the_eight_shares_add_up_to_the_uncut_reference(builder, what):
    """Each of eight chips holds 1 of the 8 experts, routes over all 8 and
    returns its own expert's part plus what every chip computes alike (the
    shared expert; in the module's block the attention sublayer and the
    residual too). The routed parts and the common part counted ONCE sum
    to the builder's reference GIVEN ALL EIGHT experts (the uncut layer);
    summing the shares as they are counts the common part eight times."""
    uncut = dict(SIZES, n_routed_experts=8, first_expert=0)
    cfg = builder.model_config(uncut)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    identity = lambda a: a  # noqa: E731
    if what == "routed_layer":
        make = lambda c: MoELayer(c.moe_config())  # noqa: E731
        variables = init(make(cfg), x)
        bias = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (8,))
        variables[moe_module.BUFFERS]["expert_bias"] = bias
        with jax.default_matmul_precision("highest"):
            want = builder._routed(
                variables["params"], bias, x.reshape(-1, 64), uncut,
                identity, None).reshape(x.shape)
    else:
        make = lambda c: TransformerBlock(c, *c.layers[-1])  # noqa: E731
        variables = init(make(cfg), x)
        bias = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (8,))
        variables[moe_module.BUFFERS]["moe"]["expert_bias"] = bias
        with jax.default_matmul_precision("highest"):
            want = builder._block(
                variables["params"], bias, x, False, uncut, identity, None)

    def run(sizes, held):
        out = make(builder.model_config(sizes)).apply(
            held, x, mutable=[moe_module.STATS])[0]
        return np.asarray(out)

    whole = run(uncut, variables)
    np.testing.assert_allclose(whole, want, rtol=2e-4, atol=2e-5)
    common = run(dict(uncut, n_routed_experts=1), _without_routed(variables))
    as_they_are = np.zeros_like(whole)
    for share in range(8):
        as_they_are += run(
            dict(uncut, n_routed_experts=1, first_expert=share),
            _share_of(variables, share))
    np.testing.assert_allclose(
        as_they_are - 7 * common, want, rtol=2e-4, atol=1e-4)
    assert not np.allclose(as_they_are, want, atol=1e-2)
    assert not np.allclose(common, want, atol=1e-3)
