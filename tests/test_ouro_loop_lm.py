"""A looped LM (PR 61): a stack run ``passes`` times over one set of
weights, a norm on each sublayer's output, an exit gate and the head after
every pass, the expected loss over the exits. The program (``LoopLM``,
``train/losses.loop_exit_crossentropy``) against the benchmark builder's
plain reference at ``passes`` 1, 2 and 4: the last exit's logits, the loss
and EVERY gradient leaf; the exit distribution; the lower-precision
negative and the reference's departures; ``passes`` = 1 without output
norms and entropy is ``CausalLM`` + ``lm_ce`` to the bit; the block
checkpoint's walk over applications; one tiny fit through
``JAXEstimator``. One jit a case family; every assertion a case of a
parametrised test."""
import dataclasses
import functools
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest

from raydp_tpu.models import CausalLM, LoopLM, ouro_2_6b
from raydp_tpu.models import loop
from raydp_tpu.models import step as model_step
from raydp_tpu.models.stats import STATS
from raydp_tpu.models.transformer import TransformerConfig
from raydp_tpu.train import JAXEstimator
from raydp_tpu.train.losses import lm_crossentropy, loop_exit_crossentropy
from raydp_tpu.utils.profiling import metrics

SEQ, VOCAB = 32, 128
# Layers at each number of passes: four applications at most (a trace and
# a compile an application, of program and reference).
LAYERS = {1: 1, 2: 2, 4: 1}
# Sequences a batch (the reference traces each): two where the layers are.
ROWS = {1: 1, 2: 2, 4: 1}
TOLERANCE = 1e-4      # float32 program against the float32 reference
PASSES = (1, 2, 4)
SIZES = {
    "model_type": "ouro", "hidden_act": "silu", "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 96, "vocab_size": VOCAB,
    "num_hidden_layers": 2, "layer_types": ["full_attention"] * 2,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "exit": {"entropy_weight": 0.05}, "attention_impl": "dense",
    "remat": True, "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 2e-5},
}
TRAFFIC = {"seq_len": SEQ}
RNGS = {"dropout": jax.random.PRNGKey(0)}


@functools.lru_cache(maxsize=None)
def builder():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", "ouro_loop_lm.py")
    spec = importlib.util.spec_from_file_location("ouro_builder_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.QUERY_ROWS_AT_ONCE = 16      # two blocks of query rows
    module.VOCAB_AT_ONCE = 64
    return module


def sizes_at(passes: int) -> dict:
    return dict(
        SIZES, total_ut_steps=passes, num_hidden_layers=LAYERS[passes],
        layer_types=["full_attention"] * LAYERS[passes])


def leaves_of(passes: int):
    names = [
        f"encoder/block_{i}/{leaf}" for i in range(LAYERS[passes])
        for leaf in (
            "attn/qkv/kernel", "attn/out/kernel", "ln_attn/scale",
            "ln_attn_out/scale", "ln_mlp/scale", "ln_mlp_out/scale",
            "mlp_in/kernel", "mlp_out/kernel")
    ] + ["encoder/ln_final/scale", "encoder/tok_embed/embedding",
         "lm_head/kernel"]
    return names + (
        ["exit_gate/kernel", "exit_gate/bias"] if passes > 1 else [])


def _at(tree, name: str):
    for key in name.split("/"):
        tree = tree[key]
    return tree


@functools.lru_cache(maxsize=None)
def case(passes: int) -> dict:
    """Program and reference on one perturbed state, everything a test
    reads: two jits (the program's value and gradient, the reference's)."""
    sizes, build = sizes_at(passes), builder()
    model = build.estimator_kwargs(sizes, TRAFFIC, None)["model"]
    ids = np.concatenate([
        build.check_batch(sizes, TRAFFIC, seed)
        for seed in (3, 4)[:ROWS[passes]]])

    @jax.jit
    def draw(key):
        # Norm weights off 1, a gate bias off 0: nothing passes by default.
        flat, tree = jax.tree_util.tree_flatten(
            nn.unbox(model.init(key, ids)))
        keys = jax.random.split(jax.random.fold_in(key, 5), len(flat))
        return tree.unflatten([
            leaf + 0.1 * jax.random.normal(each, leaf.shape)
            for leaf, each in zip(flat, keys)])

    variables = draw(jax.random.PRNGKey(0))

    @jax.jit
    def program(v):
        def objective(v):
            preds, sown = model.apply(
                v, ids, deterministic=False, rngs=RNGS, mutable=[STATS])
            return loop_exit_crossentropy(preds, ids), (preds, sown[STATS])
        (loss, aux), grads = jax.value_and_grad(objective, has_aux=True)(v)
        return loss, grads, aux, model.apply(v, ids)

    @jax.jit
    def reference(v):
        # ``reference_loss_and_grads`` with the shares kept: one trace.
        def objective(v):
            loss, *shares = build.reference_loss(v, ids, sizes)
            return loss, shares
        with jax.default_matmul_precision("highest"):
            (loss, shares), grads = jax.value_and_grad(
                objective, has_aux=True)(v)
        return loss, grads, shares, build.reference_logits(v, ids, sizes)

    loss, grads, (preds, sown), logits = program(variables)
    want_loss, want_grads, shares, want_logits = reference(variables)
    return dict(
        sizes=sizes, ids=ids, variables=variables, loss=loss,
        grads=grads["params"], preds=preds, sown=sown, logits=logits,
        want_loss=want_loss, want_grads=want_grads["params"],
        want_shares=shares, want_logits=want_logits,
    )


def _relative(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("passes", PASSES)
def test_the_last_exits_logits_and_the_loss_are_the_references(passes):
    c = case(passes)
    assert c["logits"].shape == (ROWS[passes], SEQ, VOCAB)
    assert _relative(c["logits"], c["want_logits"]) < TOLERANCE
    np.testing.assert_allclose(c["loss"], c["want_loss"], rtol=1e-5)


@pytest.mark.parametrize("passes, leaf", [
    (passes, leaf) for passes in PASSES for leaf in leaves_of(passes)])
def test_every_gradient_leaf_is_the_references(passes, leaf):
    """A shared block's gradient is the sum over its applications; the
    gate's comes through the weights of the cross-entropies and the
    entropy; the head's is the sum over the exits."""
    c = case(passes)
    got, want = _at(c["grads"], leaf), _at(c["want_grads"], leaf)
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0
    assert _relative(got, want) < TOLERANCE


@pytest.mark.parametrize("passes", PASSES)
def test_the_parameter_tree_is_one_set_of_weights(passes):
    c = case(passes)
    paths = {
        "/".join(key.key for key in path) for path, _ in
        jax.tree_util.tree_leaves_with_path(c["variables"]["params"])}
    assert paths == set(leaves_of(passes))
    build = builder()
    assert build.n_params(c["sizes"]) == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(c["variables"]))
    assert build.applications(c["sizes"]) == passes * LAYERS[passes]


@pytest.mark.parametrize("passes", PASSES)
def test_the_exit_distribution_sums_to_one_and_is_the_references(passes):
    c = case(passes)
    probs = jnp.exp(c["preds"].log_probs)
    assert probs.shape == (passes, ROWS[passes], SEQ)
    np.testing.assert_allclose(probs.sum(axis=0), 1.0, atol=1e-6)
    tokens = float(c["sown"][loop.TOKENS])
    assert tokens == ROWS[passes] * SEQ
    shares, entropy = c["want_shares"]
    np.testing.assert_allclose(
        c["sown"][loop.EXIT_MASS] / tokens, shares, rtol=1e-5)
    np.testing.assert_allclose(
        c["sown"][loop.ENTROPY] / tokens, entropy, rtol=1e-5, atol=1e-7)
    if passes > 1:
        assert 0.02 < float(shares.min()) and float(entropy) > 0.1
        gate = c["grads"]["exit_gate"]
        assert float(jnp.abs(gate["kernel"]).max()) > 1e-4
        assert float(jnp.abs(gate["bias"]).max()) > 1e-4


@pytest.mark.parametrize("what", ["bfloat16", *builder().DEPARTURES])
def test_a_lower_precision_and_every_departure_fail_the_tolerance(what):
    """The reference with bfloat16 where the configuration says float32
    is in use (this program computes in float32), and each change to the
    mathematics, read against the float32 reference: above the tolerance
    the program passes."""
    c, build = case(2), builder()
    given = {"trunk": jnp.bfloat16} if what == "bfloat16" else {
        "depart": what}
    got = jax.jit(lambda v: build.reference_logits(
        v, c["ids"][:1], c["sizes"], **given))(c["variables"])
    assert _relative(got, c["want_logits"][:1]) > 30 * TOLERANCE


def test_an_unknown_departure_is_refused():
    c = case(1)
    with pytest.raises(ValueError, match="unknown departure"):
        builder().reference_logits(
            c["variables"], c["ids"], c["sizes"], depart="nothing")


# ---------------------------------------------------- the one-pass model

@functools.lru_cache(maxsize=None)
def one_pass():
    """``LoopLM`` at one pass, no output norms, β = 0 beside ``CausalLM``
    + ``lm_ce``, in the configuration's precision (bf16 compute)."""
    cfg = ouro_2_6b(
        vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=1, d_ff=96,
        max_len=64, passes=1, branch_norm=False, remat=True)
    ids = np.random.default_rng(1).integers(0, VOCAB, (2, SEQ)).astype(
        np.int32)
    out = {}
    for name, model, loss in (
            ("loop", LoopLM(cfg, entropy_weight=0.0), loop_exit_crossentropy),
            ("causal", CausalLM(cfg), lm_crossentropy)):

        @jax.jit
        def run(key):
            variables = nn.unbox(model.init(key, ids))
            value, grads = jax.value_and_grad(lambda v: loss(
                model.apply(v, ids, deterministic=False, rngs=RNGS), ids)
            )(variables)
            return variables, value, grads, model.apply(variables, ids)

        out[name] = run(jax.random.PRNGKey(0))
    return out


@pytest.mark.parametrize("what", ["variables", "loss", "gradients", "logits"])
def test_one_pass_without_norms_and_entropy_is_causal_lm_to_the_bit(what):
    at = ("variables", "loss", "gradients", "logits").index(what)
    got, want = one_pass()["loop"][at], one_pass()["causal"][at]
    assert jax.tree_util.tree_structure(got) == (
        jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and bool((a == b).all())


def test_saturated_gates_leave_the_loss_and_its_gradient_finite():
    """A sigmoid at ±200 is an exact 0 or 1 in float32: the exit
    distribution is made in log space, so ``p log p`` stays 0 there."""
    logits = jnp.asarray([[[200.0, -200.0, 0.0]], [[-200.0, 200.0, 0.0]]])

    def entropy(g):
        log_p = loop.exit_log_probs(g)
        return -jnp.sum(jnp.exp(log_p) * log_p)

    value, grad = jax.value_and_grad(entropy)(logits)
    assert np.isfinite(value) and bool(jnp.isfinite(grad).all())
    np.testing.assert_allclose(
        jnp.exp(loop.exit_log_probs(logits)).sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        jnp.exp(loop.exit_log_probs(logits))[:, 0, 2], [0.5, 0.25, 0.25])


@pytest.mark.parametrize("bad", [
    dict(tie_head=True), dict(use_bias=True), dict(logits_scaling=2.0),
    dict(causal=False)])
def test_a_head_the_exits_cannot_take_one_at_a_time_is_refused(bad):
    cfg = dataclasses.replace(ouro_2_6b(
        vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=1, d_ff=96,
        max_len=64), **bad)
    with pytest.raises((NotImplementedError, ValueError)):
        LoopLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_a_stack_run_several_times_keeps_no_decode_cache():
    from raydp_tpu.models import TransformerEncoder

    cfg = ouro_2_6b(vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=1,
                    d_ff=96, max_len=64, passes=2)
    with pytest.raises(NotImplementedError, match="decode cache"):
        TransformerEncoder(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
            cache_mode="prefill")


@pytest.mark.parametrize("more, norms", [
    (dict(layer_types=("mamba:none", "none:swiglu"), ssm_heads=4,
          ssm_head_dim=16, ssm_state=8, ssm_chunk=8),
     {"block_0": {"ln_mamba", "ln_mamba_out"},
      "block_1": {"ln_mlp", "ln_mlp_out"}}),
    (dict(layer_types=("conv", "attention"), passes=1),
     {"block_0": {"ln_conv", "ln_conv_out", "ln_mlp", "ln_mlp_out"},
      "block_1": {"ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"}}),
    (dict(hyper="streams"),
     {"block_0": {"ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"},
      "block_1": {"ln_attn", "ln_attn_out", "ln_mlp", "ln_mlp_out"}}),
], ids=["one_sublayer", "conv", "hyper"])
def test_the_output_norm_is_stated_once_for_every_kind_of_layer(more, norms):
    """``branch_norm`` gives each sublayer a layer HAS an output norm
    under its input norm's name + ``_out``: one-sublayer layers, other
    mixers and the multi-stream residual path alike (shapes only)."""
    from raydp_tpu.models import HyperConfig

    more = dict(more)
    if more.pop("hyper", None):
        more.update(hyper=HyperConfig(streams=2, sinkhorn_iters=2), passes=1)
    cfg = ouro_2_6b(vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=2,
                    d_ff=96, max_len=64, passes=more.pop("passes", 2), **more)
    tree = jax.eval_shape(lambda: nn.unbox(CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))))
    encoder = tree["params"]["encoder"]
    for block, want in norms.items():
        assert {n for n in encoder[block] if n.startswith("ln_")} == want


# ------------------------------------------- the block checkpoint's walk

def _parent_estimate(stack, out):
    """``estimated_bytes`` as the parent of PR 61 wrote it."""
    held = [stack.released[i] if i in out else stack.checkpointed[i]
            for i in range(len(stack.released))]
    parts = max(
        [(sum(held), 2 * stack.head)] + [(
            sum(held[:i]) + sum(stack.gradients[i + 1:]) + stack.head_stays,
            stack.working[i],
        ) for i in range(len(held))], key=sum)
    return (stack.fixed + int(model_step.SLACK * sum(parts)), *parts)


STACK = model_step.Stack(
    released=[400, 300, 500], checkpointed=[60, 70, 80],
    working=[900, 800, 2000], gradients=[100, 110, 120], fixed=10_000,
    head=700, head_stays=50)


@pytest.mark.parametrize("out", [(), (2,), (0, 2), (0, 1, 2)])
def test_one_pass_walks_the_stack_as_the_parent_did(out):
    assert (STACK.passes, STACK.exits) == (1, 1)
    assert tuple(model_step.estimated_bytes(STACK, out)) == (
        _parent_estimate(STACK, out))


@pytest.mark.parametrize("out, held, working", [
    # Nothing released, two passes: block 2 of the LAST pass's backward
    # runs beside both passes' kept arrays before it (2 x 210 - 80), no
    # other block's gradient yet, and the head's.
    ((), 210 + 130 + 50, 2000),
    # Block 2 released: kept in BOTH applications.
    ((2,), 630 + 130 + 50, 2000),
])
def test_two_passes_count_kept_bytes_twice_and_gradients_once(
        out, held, working):
    twice = STACK._replace(passes=2, exits=2)
    estimate = model_step.estimated_bytes(twice, out)
    assert (estimate.held, estimate.working) == (held, working)
    # In the FIRST pass's backward every other block's gradient is there,
    # once a block however many applications it has: block 2 of pass 0.
    light = twice._replace(working=[1, 1, 20_000], gradients=[
        5000, 6000, 7000])
    first = model_step.estimated_bytes(light, ())
    assert (first.held, first.working) == (130 + 11_000 + 50, 20_000)
    # The exits run one at a time beside EVERY application's kept arrays
    # and the head's own gradient.
    heady = twice._replace(head=5000)
    assert tuple(model_step.estimated_bytes(heady, ()))[1:] == (
        2 * 210 + 50, 10_000)
    # A release holds or frees a block in all its applications: the rule's
    # choice at a limit that one pass meets and two do not.
    limit = STACK.fixed + 4000
    assert model_step.released_blocks(STACK, limit) == (0, 1, 2)
    assert model_step.released_blocks(twice, limit) == ()


def _tiny_stack(monkeypatch, passes):
    seen = {}
    rule = model_step.released_blocks
    monkeypatch.setattr(model_step, "device_limit", lambda mesh: 2 ** 30)
    monkeypatch.setattr(
        model_step, "released_blocks",
        lambda stack, limit: seen.setdefault("stack", stack) and rule(
            stack, limit))
    more = {} if passes == 1 else {"passes": passes}
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, d_ff=64, max_len=16,
        n_layers=3, causal=True, dtype=jnp.float32, remat=True,
        dropout_rate=0.0, **more)
    est = JAXEstimator(
        model=CausalLM(cfg), optimizer=optax.sgd(0.1), loss="lm_ce",
        self_supervised=True, batch_size=4, label_column=None,
        feature_columns=[f"t{i}" for i in range(16)],
        feature_dtype=np.int32, seed=0, shuffle=False, epoch_mode="stream")
    est._init_state(np.zeros((4, 16), np.int32))
    return seen["stack"]


# ``_tiny_stack(monkeypatch, 1)`` on the PARENT of PR 61.
PARENT_STACK = (
    [75780, 75780, 75780], [8192, 8192, 8192], [125316, 125316, 125316],
    [34176, 34176, 34176], 121732, 16384, 0)


def test_fit_checkpoint_builds_the_parents_stack_at_one_pass(monkeypatch):
    one = _tiny_stack(monkeypatch, 1)
    assert tuple(one)[:9] == PARENT_STACK + (1, 1)
    # PR 63: the blocks' gradients in their parameters' dtype, beside the
    # count of what they are made of (float32 compute here: the same).
    assert one._fields[9:] == ("parameters",)
    assert len(one.parameters) == 3 and min(one.parameters) >= min(
        one.gradients)
    two = _tiny_stack(monkeypatch, 2)
    # Every application has the same shapes: a block's counts are those
    # of one pass; what differs is how often the walk meets them. A
    # ``CausalLM`` over a looped stack has ONE exit, after the last pass.
    assert two[:7] == one[:7] and (two.passes, two.exits) == (2, 1)
    assert model_step.estimated_bytes(two, ()).held > (
        model_step.estimated_bytes(one, ()).held)


# ------------------------------------------------- through the estimator

@pytest.fixture(scope="module")
def fitted():
    sizes = dict(sizes_at(2), num_hidden_layers=1,
                 layer_types=["full_attention"])
    est = JAXEstimator(
        **builder().estimator_kwargs(sizes, {"seq_len": 16}, None),
        batch_size=4, seed=0, shuffle=False, epoch_mode="stream")
    ids = np.random.default_rng(0).integers(0, VOCAB, (8, 16)).astype(
        np.int32)
    frame = pd.DataFrame({f"t{i}": ids[:, i] for i in range(16)})
    history = est.fit_on_df(frame, num_epochs=2)
    return est, ids, history


def test_a_looped_lm_trains_through_the_estimator(fitted):
    est, ids, history = fitted
    assert len(history) == 2 and all(
        np.isfinite(h["train_loss"]) for h in history)
    assert isinstance(est._model, LoopLM)
    logits = est.predict(ids[:4])
    assert logits.shape == (4, 16, VOCAB)
    # ``evaluate``-style use: the loss of an array is the last exit's.
    np.testing.assert_allclose(
        loop_exit_crossentropy(jnp.asarray(logits), ids[:4]),
        lm_crossentropy(jnp.asarray(logits), ids[:4]))


@pytest.mark.parametrize("gauge, want", [
    ("loop/passes", 2), ("loop/applications", 2), ("loop/exits_live", 1),
    ("checkpoint/blocks", 1),
])
def test_the_gauges_where_the_step_is_built(fitted, gauge, want):
    assert metrics.gauge_value(gauge) == want


def test_the_gauges_of_an_epoch(fitted):
    shares = [metrics.gauge_value(f"loop/exit_share_{t}") for t in (0, 1)]
    assert sum(shares) == pytest.approx(1.0, abs=1e-5)
    assert all(0.0 < share < 1.0 for share in shares)
    assert 0.0 < metrics.gauge_value("loop/exit_entropy") < np.log(2) + 1e-6
    assert metrics.gauge_value("loop/expected_pass") == pytest.approx(
        shares[0] + 2 * shares[1])


def test_another_model_reports_no_loop(fitted):
    model = CausalLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, d_ff=64, max_len=16,
        n_layers=1, causal=True))
    loop.report(model)
    assert [metrics.gauge_value(f"loop/{name}") for name in (
        "passes", "applications", "exits_live")] == [0, 0, 0]
    loop.report_epoch({})        # nothing sown: nothing set, no error
    assert loop.exit_bytes(model, None) is None
