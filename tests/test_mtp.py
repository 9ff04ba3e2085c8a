"""The multi-token-prediction module (``models/mtp.py``) and its loss
(``train/losses.mtp_crossentropy``) at tiny widths on the CPU, float32,
seeded weights, against the benchmark's plain reference
(``benchmark/configs/glm4_mtp_moe_lm.py``): both heads' logits, ``L``,
``L_main``, ``L_mtp`` and every gradient; ONE table and ONE head whose
gradients are the sums over both uses; positions S-2 and S-1 carry no
module loss and ``t_0`` rolled into the last input changes none; lambda 0
gives the main stack ``CausalLM``'s gradients; deterministic logits are
``CausalLM``'s; each wrong form of the module fails the comparison that
decides ``correct``; the loss's notes reach the step's statistics; the
block checkpoint's walk meets the module's block."""
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import CausalLM, MTPConfig, MTPLM, stats
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models import mtp as mtp_module
from raydp_tpu.models import step as model_step
from raydp_tpu.train import losses

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 16
SIZES = {
    "model_type": "glm4_moe_lite", "vocab_size": 500, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000, "rope_scaling": None,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 24,
    "n_routed_experts": 2, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 1.8,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
    "mtp": {"loss_weight": 0.3},
    "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
    "init": {"embedding_std": 1.0},
}
COLLECTIONS = ("params", moe_module.BUFFERS)


def load_builder():
    path = os.path.join(REPO, "benchmark", "configs", "glm4_mtp_moe_lm.py")
    spec = importlib.util.spec_from_file_location("glm4_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def builder():
    return load_builder()


def init(model, *args):
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), *args))
    return {k: variables[k] for k in COLLECTIONS if k in variables}


@pytest.fixture(scope="module")
def tiny(builder):
    model = builder.model(SIZES)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (2, SEQ)).astype(np.int32))
    return model, init(model, ids), ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _program_loss(model, variables, ids):
    """``(L, {loss/main, loss/mtp, ...})`` as a train step makes them."""
    preds, sown = model.apply(
        variables, ids, mutable=model_step.SOWN,
        **model_step.apply_kwargs(model, jax.random.PRNGKey(1)))
    return losses.mtp_crossentropy(preds, ids), model_step.step_stats(sown)


# ---------------------------------------------- program against reference

def test_the_tree_has_one_table_and_one_head(builder, tiny):
    _, variables, _ = tiny
    params = variables["params"]
    assert set(params) == {"encoder", "lm_head", "mtp"}
    assert set(params["mtp"]) == {
        "enorm", "hnorm", "eh_proj", "block", "norm"}
    assert params["mtp"]["eh_proj"]["kernel"].shape == (128, 64)
    assert set(params["mtp"]["block"]) == set(params["encoder"]["block_1"])
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    assert sum("embedding" in p for p in paths) == 1
    assert sum("lm_head" in p for p in paths) == 1
    # The module's block has a selection bias of its own.
    assert variables[moe_module.BUFFERS]["mtp"]["block"]["moe"][
        "expert_bias"].shape == (8,)
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert builder.n_params(SIZES) == held
    assert mtp_module.n_params(variables) == builder.layer_params(SIZES)["mtp"]


def test_both_heads_and_both_losses_match_the_plain_reference(builder, tiny):
    model, variables, ids = tiny
    want = builder.reference_heads(variables, ids, SIZES)
    main, module = model.apply(variables, ids, method="both_logits")
    assert main.shape == module.shape == (2, SEQ, SIZES["vocab_size"])
    assert _rel(main, want["logits"]) < 2e-5
    assert _rel(module, want["logits1"]) < 2e-5
    total, noted = _program_loss(model, variables, ids)
    assert float(total) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert float(noted["loss/main"]) == pytest.approx(
        float(want["loss_main"]), rel=1e-5)
    assert float(noted["loss/mtp"]) == pytest.approx(
        float(want["loss_mtp"]), rel=1e-5)
    assert float(total) == pytest.approx(
        float(noted["loss/main"]) + 0.3 * float(noted["loss/mtp"]), rel=1e-6)


@pytest.fixture(scope="module")
def program_gradients(tiny):
    """The program's gradients of ``L`` at a lambda, made once each."""
    model, variables, ids = tiny
    made = {}

    def at(weight):
        if weight not in made:
            lm = MTPLM(model.cfg, MTPConfig(loss_weight=weight))
            made[weight] = jax.jit(jax.grad(
                lambda v: _program_loss(lm, v, ids)[0]))(variables)
        return made[weight]

    return at


@pytest.fixture(scope="module")
def gradients(builder, tiny, program_gradients):
    _, variables, ids = tiny
    _, want = jax.jit(
        lambda v, x: builder.reference_loss_and_grads(v, x, SIZES)
    )(variables, ids)
    return program_gradients(0.3), want


def test_every_gradient_matches_the_plain_reference(gradients):
    got, want = gradients
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["params"]))
    seen = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got["params"]):
        assert float(jnp.abs(flat_want[path]).max()) > 0, path
        assert _rel(g, flat_want[path]) < 5e-4, jax.tree_util.keystr(path)
        seen += 1
    assert seen == len(flat_want) == 48
    # The selection biases have no gradient by construction.
    for g in jax.tree_util.tree_leaves(got[moe_module.BUFFERS]):
        assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("where", ["table", "head"])
def test_a_shared_matrix_gets_the_sum_over_both_uses(
        program_gradients, gradients, where):
    """The table's (the head's) gradient under ``L_main + lambda L_mtp``
    is the reference's, whose second use of the matrix is written out, and
    it is NOT its gradient under ``L_main`` alone (lambda 0, which
    ``CausalLM``'s test ties to the one use): the second use adds to it."""
    pick = {"table": lambda t: t["params"]["encoder"]["tok_embed"]["embedding"],
            "head": lambda t: t["params"]["lm_head"]["kernel"]}[where]
    main, both = pick(program_gradients(0.0)), pick(program_gradients(0.3))
    assert _rel(both, pick(gradients[1])) < 5e-4
    second = both - main
    assert float(jnp.abs(main).max()) > 0
    assert float(jnp.abs(second).max()) > 0.01 * float(jnp.abs(main).max())


def test_lambda_zero_gives_the_main_stack_causal_lms_gradients(
        tiny, program_gradients):
    model, variables, ids = tiny
    got = program_gradients(0.0)
    plain = {"params": {k: variables["params"][k]
                        for k in ("encoder", "lm_head")},
             moe_module.BUFFERS: {
                 "encoder": variables[moe_module.BUFFERS]["encoder"]}}
    causal = CausalLM(model.cfg)
    want = jax.jit(jax.grad(lambda v: losses.lm_crossentropy(causal.apply(
        v, ids, deterministic=False, mutable=model_step.SOWN,
        rngs={"dropout": jax.random.PRNGKey(1)})[0], ids)))(plain)
    for name in ("encoder", "lm_head"):
        for g, w in zip(jax.tree_util.tree_leaves(got["params"][name]),
                        jax.tree_util.tree_leaves(want["params"][name])):
            assert _rel(g, w) < 1e-5
    for g in jax.tree_util.tree_leaves(got["params"]["mtp"]):
        assert float(jnp.abs(g).max()) == 0.0


def test_deterministic_logits_are_causal_lms_bit_for_bit(tiny):
    model, variables, ids = tiny
    plain = {"params": {k: variables["params"][k]
                        for k in ("encoder", "lm_head")},
             moe_module.BUFFERS: {
                 "encoder": variables[moe_module.BUFFERS]["encoder"]}}
    want = jax.jit(CausalLM(model.cfg).apply)(plain, ids)
    got = jax.jit(model.apply)(variables, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    main, _ = jax.jit(
        lambda v, x: model.apply(v, x, method="both_logits"))(variables, ids)
    assert _rel(main, want) < 1e-6
    # Given the main logits (``evaluate``) the loss is the next-token one.
    assert float(losses.mtp_crossentropy(got, ids)) == float(
        losses.lm_crossentropy(got, ids))


def test_the_last_two_positions_carry_no_module_loss(tiny):
    """Whatever the module's state holds at S-2 and S-1, and whatever the
    rolled-in ``t_0`` puts into its last input, both losses stay."""
    model, variables, ids = tiny
    preds, _ = model.apply(
        variables, ids, mutable=model_step.SOWN,
        **model_step.apply_kwargs(model, jax.random.PRNGKey(1)))
    want = float(losses.mtp_crossentropy(preds, ids))
    main, module = preds.states
    noise = 3.0 * jax.random.normal(jax.random.PRNGKey(2), module.shape)
    other = preds._replace(states=(main, module.at[:, -2:].add(noise[:, -2:])))
    assert float(losses.mtp_crossentropy(other, ids)) == want
    moved = preds._replace(states=(main, module.at[:, -3:].add(noise[:, -3:])))
    assert float(losses.mtp_crossentropy(moved, ids)) != want
    # The main head masks S-1 alone.
    last = preds._replace(states=(main.at[:, -1:].add(noise[:, -1:]), module))
    assert float(losses.mtp_crossentropy(last, ids)) == want

    # ``t_0`` reaches the module's LAST input alone: a module fed another
    # token there gives the same states before it (causal).
    block = mtp_module.MTPModule(model.cfg)
    hbar = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    embed = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    own = {"params": variables["params"]["mtp"],
           moe_module.BUFFERS: variables[moe_module.BUFFERS]["mtp"]}
    a = block.apply(own, hbar, embed, mutable=model_step.SOWN)[0]
    b = block.apply(own, hbar, embed.at[:, -1].set(7.0),
                    mutable=model_step.SOWN)[0]
    np.testing.assert_allclose(a[:, :-1], b[:, :-1], rtol=1e-6, atol=1e-6)
    assert not np.allclose(a[:, -1], b[:, -1], atol=1e-3)


# ------------------------------------------------- the check can say no

WRONG_FORMS = {
    "mtp_embeds_this_token": "mtp_logits_match_reference",
    "mtp_targets_one_ahead": "losses_match_reference",
    "gates_times_one": "mtp_logits_match_reference",
    "no_hnorm": "mtp_logits_match_reference",
    "mtp_block_is_last_block": "mtp_logits_match_reference",
}


def test_the_wrong_forms_are_the_builders(builder):
    assert set(WRONG_FORMS) <= set(builder.DEPARTURES)
    assert set(builder.LOSS_ONLY) <= set(builder.DEPARTURES)
    # What the chip's rounding hides is pinned HERE, in float32.
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(WRONG_FORMS)
    assert builder.LIMITS == {
        "main_logits": builder.TOLERANCE, "mtp_logits": builder.TOLERANCE,
        "loss_main": builder.LOSS_TOLERANCE,
        "loss_mtp": builder.LOSS_TOLERANCE}


def test_the_comparison_passes_the_program(builder, tiny):
    model, variables, ids = tiny
    checks, detail = builder.check_heads(model, variables, ids[:1], SIZES)
    assert checks == {"mtp_logits_match_reference": True,
                      "losses_match_reference": True}
    assert max(detail["errors"].values()) < 1e-5
    assert set(detail["errors"]) == set(builder.LIMITS)


@pytest.mark.parametrize("form", sorted(WRONG_FORMS))
def test_each_wrong_form_of_the_module_fails_the_comparison(
        builder, tiny, form):
    """ISSUE 67's five: the module fed ``Emb(t_s)``, its targets one ahead,
    routed scaling 1, ``hnorm`` left out, the module's block holding the
    stack's last block's weights."""
    model, variables, ids = tiny
    assert form in builder.DEPARTURES
    checks, detail = builder.check_heads(
        model, variables, ids[:1], SIZES, depart=form)
    assert checks[WRONG_FORMS[form]] is False, detail
    if form in builder.LOSS_ONLY:
        assert checks["mtp_logits_match_reference"] is True
        assert detail["errors"]["loss_main"] < 1e-5


@pytest.mark.parametrize("form", [
    "no_shared_expert", "no_latent_norm", "no_shared_rope_key",
    "uncut_layer", "gates_times_one", "8_bit_trunk"])
def test_tolerance_refuses_a_departure_of_the_stack(builder, tiny, form):
    model, variables, ids = tiny
    got = model.apply(variables, ids)
    if form == "8_bit_trunk":
        other = builder.reference_logits(
            variables, ids, SIZES, trunk=jnp.float8_e4m3fn)
    else:
        other = builder.reference_logits(variables, ids, SIZES, depart=form)
    assert _rel(got, other) > builder.TOLERANCE
    with pytest.raises(ValueError, match="departure"):
        builder.reference_logits(variables, ids, SIZES, depart="no_such")


def test_builder_refuses_what_it_does_not_write_down(builder):
    for change in ({"scoring_func": "softmax"}, {"topk_method": "greedy"},
                   {"n_group": 8}, {"norm_topk_prob": False},
                   {"tie_word_embeddings": True}, {"model_type": "xing4_0"},
                   {"num_key_value_heads": 2}, {"attention_bias": True},
                   {"num_nextn_predict_layers": 0},
                   {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError):
            builder.model_config(dict(SIZES, **change))


def test_the_model_only_where_it_is_written_down(tiny):
    model, _, ids = tiny
    cfg = model.cfg
    for change in ({"tie_head": True}, {"passes": 2}, {"causal": False},
                   {"logits_scaling": 2.0}, {"embedding_multiplier": 2.0}):
        with pytest.raises((NotImplementedError, ValueError)):
            MTPLM(cfg.__class__(**{**cfg.__dict__, **change})).init(
                jax.random.PRNGKey(0), ids)
    with pytest.raises(NotImplementedError):
        MTPLM(cfg, MTPConfig(depth=2)).init(jax.random.PRNGKey(0), ids)


# ------------------------------------------------------ the loss's notes

def test_a_loss_notes_what_the_steps_statistics_take():
    name = stats.declare("loss/main")
    model_step.apply_kwargs(object(), jax.random.PRNGKey(0))   # begin_step
    assert model_step.step_stats({}) == {}
    stats.note(name, jnp.float32(2.0))
    assert model_step.step_stats({}) == {name: 2.0}
    assert model_step.step_stats({}) == {}      # taken once
    stats.note(name, jnp.float32(3.0))
    model_step.apply_kwargs(object(), jax.random.PRNGKey(0))
    assert model_step.step_stats({}) == {}      # an earlier trace's: gone
    with pytest.raises(ValueError, match="not declared"):
        stats.note("no_such_statistic", 1.0)


def test_per_exit_targets_counts_and_scopes_in_the_one_function():
    """``_exits_ce`` with each exit's own targets and count is the sum of
    the weighted cross-entropies one at a time, value and gradients."""
    rng = np.random.default_rng(0)
    states = tuple(jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
                   for _ in range(2))
    head = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    targets = tuple(jnp.asarray(rng.integers(0, 24, (2, 8))) for _ in range(2))
    weights = jnp.asarray(rng.random((2, 2, 8)), jnp.float32)
    exits = ((14, "main_head"), (12, "mtp_head"))

    def one(states, head):
        return losses._exits_ce(exits, states, head, targets, weights)[0]

    def plain(states, head):
        return sum(
            jnp.sum(weights[t] * optax.softmax_cross_entropy_with_integer_labels(
                states[t] @ head, targets[t])) / exits[t][0] for t in range(2))

    got, grads = jax.value_and_grad(one, argnums=(0, 1))(states, head)
    want, wanted = jax.value_and_grad(plain, argnums=(0, 1))(states, head)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(wanted)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-6)
    text = jax.jit(one).lower(states, head).as_text(debug_info=True)
    assert "mtp_head" in text and "main_head" in text
    _, parts = losses._exits_ce(exits, states, head, targets, weights)
    assert float(parts[0] + parts[1]) == pytest.approx(float(got), rel=1e-6)


# ------------------------------------------- the block checkpoint's walk

def _state(model, variables):
    from flax.training.train_state import TrainState

    return TrainState.create(
        apply_fn=model.apply, params=variables, tx=optax.sgd(0.1),
    ).replace(step=jnp.zeros((), jnp.int32))


@pytest.mark.parametrize("limit,released", [
    (1 << 40, (0, 1, 2)), (1, ())])
def test_the_walk_meets_the_modules_block(tiny, monkeypatch, limit, released):
    """Behind ``ln_final`` the walk finds a third block, ``mtp/block``,
    and two head passes in sequence; released, the module's block is the
    plain block on the same parameters."""
    from jax.sharding import Mesh

    from raydp_tpu.utils.profiling import metrics

    model, variables, ids = tiny
    seen = {}
    rule = model_step.released_blocks
    monkeypatch.setattr(model_step, "device_limit", lambda mesh: limit)
    monkeypatch.setattr(
        model_step, "released_blocks",
        lambda stack, lim: seen.setdefault("stack", stack) and rule(stack, lim))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    sample = jax.ShapeDtypeStruct(ids.shape, ids.dtype)
    fitted = model_step.fit_checkpoint(
        model, _state(model, variables), sample, mesh)
    stack = seen["stack"]
    assert len(stack.released) == 3 and (stack.passes, stack.exits) == (1, 2)
    # The module's block is a routed layer as the stack's last.
    assert [stack.released[2], stack.checkpointed[2]] == [
        stack.released[1], stack.checkpointed[1]]
    assert stack.head == 4 * ids.size * SIZES["vocab_size"]
    assert stack.head_stays == 4 * 64 * SIZES["vocab_size"]
    assert fitted.cfg.released == released
    assert metrics.gauge_value("checkpoint/blocks") == 3
    assert metrics.gauge_value("checkpoint/blocks_checkpointed") == (
        3 - len(released))
    if released:
        a = _program_loss(fitted, variables, ids)[0]
        b = _program_loss(model, variables, ids)[0]
        assert float(a) == pytest.approx(float(b), rel=1e-6)
        text = str(jax.make_jaxpr(
            lambda v: jax.grad(lambda v: _program_loss(fitted, v, ids)[0])(v)
        )(variables))
        assert "checkpoint" not in text and "remat" not in text
    back = model_step.checkpoint_all(fitted)
    assert back.cfg.released == ()
    assert metrics.gauge_value("checkpoint/blocks_checkpointed") == 3


def test_the_build_time_gauges(tiny):
    from raydp_tpu.utils.profiling import metrics

    model, variables, _ = tiny
    mtp_module.report(model, variables)
    assert metrics.gauge_value("mtp/depth") == 1
    assert metrics.gauge_value("mtp/params") == mtp_module.n_params(variables)
    assert metrics.gauge_value("mtp/loss_weight") == pytest.approx(0.3)
    mtp_module.report(CausalLM(model.cfg), variables)
    assert metrics.gauge_value("mtp/depth") == 0
    assert metrics.gauge_value("mtp/params") == 0
    mtp_module.report_epoch({}, 4)              # nothing sown: silent
    mtp_module.report_epoch({"loss/main": 8.0, "loss/mtp": 10.0}, 4)
    assert metrics.gauge_value("train/loss_main") == 2.0
    assert metrics.gauge_value("train/loss_mtp") == 2.5
