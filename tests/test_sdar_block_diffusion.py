"""Block diffusion's training step on an SDAR style stack, at tiny widths
on the CPU (hidden 64, 4 query heads over 2 key-value heads of 16 with a
norm a head, 8 experts of width 32 of which 4 are held, blocks of 4,
sequence 32, vocabulary 256), float32: the program against the benchmark's
plain reference given the same share and the same noise (logits, loss,
every gradient), every departure the builder lists above its tolerance,
the noise (a function of (seed, step), the same on one device and on a
dp = 2 mesh, its share and its floor), the weighted loss against optax and
its written-out backward against autodiff, the step's keys, and one fit
through ``JAXEstimator`` with the scopes, gauges, counter and log line of
the built step."""
import importlib.util
import logging
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from raydp_tpu.models import (
    BlockDiffusionConfig,
    BlockDiffusionLM,
    CausalLM,
    blockdiff,
    sdar_30b_a3b,
)
from raydp_tpu.models import dropout
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models import step as model_step
from raydp_tpu.models.transformer import MultiHeadAttention, olmoe
from raydp_tpu.train.losses import (
    blockdiff_crossentropy,
    lm_crossentropy,
    weighted_crossentropy,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 32
SIZES = {
    "builder": "sdar_block_diffusion_moe_lm", "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_routed": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256,
    "diffusion": {"block_length": 4, "mask_token_id": 254, "t_min": 1e-3,
                  "schedule": "linear"},
    "init": {"embedding_std": 1.0},
    "attention_impl": "dense", "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
}
TRAFFIC = {"seq_len": SEQ, "per_chip_batch": 2}


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there. Its
    blocks of query rows are cut to 16 so that the tiny pair has four."""
    path = os.path.join(
        REPO, "benchmark", "configs", "sdar_block_diffusion_moe_lm.py")
    spec = importlib.util.spec_from_file_location("sdar_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.QUERY_ROWS_AT_ONCE = 16
    return module


@pytest.fixture(scope="module")
def tiny(builder):
    """The model, seeded weights, and a seeded pair with its noise."""
    model = BlockDiffusionLM(builder.model_config(SIZES))
    ids, masked, t = builder.check_noise(SIZES, TRAFFIC, 7)
    pair = jnp.asarray(builder.check_batch(SIZES, TRAFFIC, 7))
    # ``init`` is given the clean ids, as a step is, and noises them itself.
    variables = jax.jit(lambda: {
        k: v for k, v in nn.unbox(
            model.init(jax.random.PRNGKey(0), jnp.asarray(ids))).items()
        if k in ("params", moe_module.BUFFERS)
    })()
    return model, variables, pair, (jnp.asarray(ids), masked, t)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _logits(model, variables, pair):
    return model.apply(variables, pair, mutable=[moe_module.STATS])[0]


# ---------------------------------------------- program against reference

def test_parameter_tree_is_causal_lms_and_the_share(builder, tiny):
    model, variables, _, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)
    block = {
        "ln_attn": {"scale": (64,)}, "ln_mlp": {"scale": (64,)},
        "attn": {"q": {"kernel": (64, 4, 16)},
                 "kv": {"kernel": (64, 2, 2, 16)},
                 "q_norm": {"scale": (16,)}, "k_norm": {"scale": (16,)},
                 "out": {"kernel": (4, 16, 64)}},
        # The router keeps its 8 outputs; 4 experts' weights are here.
        "moe": {"router": {"kernel": (64, 8)}, "w_gate": (4, 64, 32),
                "w_up": (4, 64, 32), "w_down": (4, 32, 64)},
    }
    assert tree["params"] == {
        "encoder": {"tok_embed": {"embedding": (256, 64)},
                    "block_0": block, "block_1": block,
                    "ln_final": {"scale": (64,)}},
        "lm_head": {"kernel": (64, 256)},
    }
    # No selection bias: no buffer beside them.
    assert moe_module.BUFFERS not in tree
    # The tree ``CausalLM`` builds from the same configuration: a next-token
    # checkpoint is continued under the diffusion objective as it is.
    causal = jax.eval_shape(lambda: nn.unbox(CausalLM(
        model.cfg.__class__(**{**model.cfg.__dict__, "diffusion": None})
    ).init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.int32))))
    assert jax.tree_util.tree_map(
        lambda a: tuple(a.shape), causal["params"]) == tree["params"]
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(variables["params"]))
    assert builder.n_params(SIZES) == held


def test_logits_match_the_plain_reference(builder, tiny):
    model, variables, pair, _ = tiny
    want = jax.jit(
        lambda v: builder.reference_logits(v, pair, SIZES))(variables)
    assert want.shape == (1, SEQ, SIZES["vocab_size"])
    assert _rel(_logits(model, variables, pair), want) < 2e-5


def test_loss_and_gradients_match_the_plain_reference(builder, tiny):
    """The evaluation mode on the pair the noise makes, the weights the
    training mode would hand the loss, the written-out loss."""
    model, variables, pair, (ids, masked, t) = tiny
    weights = jnp.where(masked, np.repeat(1.0 / t, 4, axis=1), 0.0)

    def loss(v):
        return blockdiff_crossentropy(
            (_logits(model, v, pair), weights), ids)

    got_loss, got = jax.jit(jax.value_and_grad(loss))(variables)
    want_loss, want = jax.jit(
        lambda v: builder.reference_loss_and_grads(v, ids, masked, t, SIZES)
    )(variables)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["params"]))
    seen = 0
    for path, g in jax.tree_util.tree_leaves_with_path(got["params"]):
        assert float(jnp.abs(flat_want[path]).max()) > 0, path
        assert _rel(g, flat_want[path]) < 5e-4, jax.tree_util.keystr(path)
        seen += 1
    # 2 x (5 attention + 2 norms + 4 routed) + 3.
    assert seen == len(flat_want) == 25


DEPARTURES = [
    "plain_causal_pair", "noised_sees_own_clean_block", "clean_sees_noised",
    "own_block_causal", "block_8", "positions_run_on", "no_qk_norm",
    "gates_not_renormalised", "trunk_float8",
]


def test_the_departures_are_the_builders(builder):
    assert list(builder.DEPARTURES) == DEPARTURES
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(DEPARTURES)
    # The four the mask and the positions stand on are seen on the chip.
    assert not set(builder.UNSEEN_ON_THE_CHIP) & {
        "plain_causal_pair", "noised_sees_own_clean_block",
        "clean_sees_noised", "positions_run_on"}


@pytest.mark.parametrize("departure", DEPARTURES)
def test_tolerance_refuses_a_departure_from_the_mathematics(
    builder, tiny, departure
):
    model, variables, pair, _ = tiny
    other = jax.jit(lambda v: builder.reference_logits(
        v, pair, SIZES, depart=departure))(variables)
    assert _rel(_logits(model, variables, pair), other) > builder.TOLERANCE


def test_an_unknown_departure_is_refused(builder, tiny):
    _, variables, pair, _ = tiny
    with pytest.raises(ValueError, match="departure"):
        builder.reference_logits(variables, pair, SIZES, depart="no_such")


# ----------------------------------------------------- the two modes

def test_training_draws_what_evaluation_is_given(tiny):
    """Training takes S ids, draws the noise and returns (logits,
    weights); evaluation given the pair that noise makes returns the same
    logits. One function runs the pair in both."""
    model, variables, _, (ids, _, _) = tiny
    key = dropout.key_for(jax.random.PRNGKey(11))
    (logits, weights), sown = model.apply(
        variables, ids, deterministic=False, rngs={"noise": key},
        mutable=[moe_module.STATS, "intermediates"])
    masked, t = sown["intermediates"]["noise"][0]
    assert logits.shape == (1, SEQ, 256) and weights.shape == (1, SEQ)
    assert masked.shape == (1, SEQ) and t.shape == (1, SEQ // 4)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.where(masked, np.repeat(1.0 / np.asarray(t), 4, axis=1), 0.0),
        rtol=1e-6)
    pair = blockdiff.make_pair(ids, masked, 254)
    assert pair.shape == (1, 2 * SEQ)
    np.testing.assert_array_equal(np.asarray(pair[:, SEQ:]), np.asarray(ids))
    np.testing.assert_array_equal(
        np.asarray(pair[:, :SEQ])[np.asarray(masked)], 254)
    np.testing.assert_allclose(
        np.asarray(_logits(model, variables, pair)), np.asarray(logits),
        rtol=1e-5, atol=1e-6)
    # What the step sows about itself: the masked tokens and the tokens.
    stats = sown[moe_module.STATS]
    assert float(stats[blockdiff.MASKED]) == float(masked.sum())
    assert float(stats[blockdiff.TOKENS]) == SEQ
    # On the host the same function makes the pair from numpy arrays.
    np.testing.assert_array_equal(
        blockdiff.make_pair(np.asarray(ids), np.asarray(masked), 254),
        np.asarray(pair))


def test_init_noises_the_clean_ids_a_step_is_given(tiny):
    """``model.init`` runs the training mode under the ``params`` key: 36
    ids are nine blocks and no pair ``[B, 2·S]``, and what a layer draws
    from its first batch has seen the masked tokens."""
    model, variables, _, _ = tiny
    ids = jnp.arange(SEQ + 4, dtype=jnp.int32)[None]
    fresh = jax.jit(lambda: nn.unbox(
        model.init(jax.random.PRNGKey(0), ids)))()
    # Sown by the training mode alone: the tokens it masked, of 36.
    masked = float(fresh[moe_module.STATS][blockdiff.MASKED])
    assert 0 < masked < SEQ + 4
    assert float(fresh[moe_module.STATS][blockdiff.TOKENS]) == SEQ + 4
    assert set(fresh) == {"params", moe_module.STATS}
    with pytest.raises(ValueError, match="pair"):
        model.apply(variables, ids)


def test_evaluation_refuses_what_cannot_be_a_pair(tiny):
    model, variables, _, _ = tiny
    with pytest.raises(ValueError, match="pair"):
        model.apply(variables, jnp.zeros((1, SEQ + 4), jnp.int32))
    with pytest.raises(ValueError, match="diffusion"):
        BlockDiffusionLM(olmoe(n_layers=1)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # No decode plane: grouped heads, and no cache of finished blocks.
    assert not model.cfg.serves_from_kv_cache


def test_only_the_attention_mixer_knows_the_pair_mask():
    cfg = sdar_30b_a3b(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, head_size=16,
        n_layers=1, n_experts=4, top_k=2, d_expert=16,
        layer_types=("conv",), dtype=jnp.float32,
        diffusion=BlockDiffusionConfig(4, 63, 1e-3))
    with pytest.raises(NotImplementedError, match="pair"):
        BlockDiffusionLM(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    ring = sdar_30b_a3b(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, head_size=16,
        n_layers=1, n_experts=4, top_k=2, d_expert=16, dtype=jnp.float32,
        attention_impl="ring", diffusion=BlockDiffusionConfig(4, 63, 1e-3))
    with pytest.raises(NotImplementedError, match="pair mask"):
        BlockDiffusionLM(ring).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))


def test_given_positions_are_what_rotary_rotates_by():
    """``0 … S-1`` given is the call without positions; the pair's
    positions are not."""
    cfg = olmoe(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                n_experts=4, top_k=2, d_expert=16, dtype=jnp.float32)
    layer = MultiHeadAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    p = layer.init(jax.random.PRNGKey(0), x)
    plain = layer.apply(p, x)
    np.testing.assert_array_equal(
        np.asarray(layer.apply(p, x, positions=jnp.arange(8)[None, :])),
        np.asarray(plain))
    twice = layer.apply(p, x, positions=blockdiff.pair_positions(4))
    assert not np.allclose(np.asarray(twice), np.asarray(plain))
    np.testing.assert_array_equal(
        np.asarray(blockdiff.pair_positions(4)), [[0, 1, 2, 3, 0, 1, 2, 3]])


# ------------------------------------------------------------- the noise

def test_the_noise_is_a_function_of_its_key_alone():
    cfg = BlockDiffusionConfig(block_length=4, mask_id=1, t_min=1e-3)
    key = dropout.key_for(jax.random.PRNGKey(5))
    masked, t = blockdiff.draw_noise(key, 8, 1024, cfg)
    again, t_again = blockdiff.draw_noise(key, 8, 1024, cfg)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t_again))
    other, _ = blockdiff.draw_noise(
        dropout.key_for(jax.random.PRNGKey(6)), 8, 1024, cfg)
    assert (np.asarray(other) != np.asarray(masked)).any()
    assert t.shape == (8, 256) and float(t.min()) >= 1e-3
    assert float(t.max()) <= 1.0
    # E[m] = E[t] = 0.5005; 8,192 tokens in blocks that share a level:
    # five standard deviations of the block sum are 0.031.
    assert abs(float(masked.mean()) - 0.5005) < 0.031
    # A token is masked with its block's probability: blocks with t under
    # a quarter mask under a quarter of their tokens, roughly.
    low = np.repeat(np.asarray(t) < 0.25, 4, axis=1)
    assert np.asarray(masked)[low].mean() < 0.2
    assert np.asarray(masked)[~low].mean() > 0.55
    with pytest.raises(ValueError, match="blocks of 4"):
        blockdiff.draw_noise(key, 1, 30, cfg)
    # One draw: a single RngBitGenerator op in the lowered program.
    text = jax.jit(lambda k: blockdiff.draw_noise(
        jax.random.wrap_key_data(k, impl=dropout.GENERATOR), 8, 1024, cfg)
    ).lower(jax.random.key_data(key)).as_text()
    assert text.count("rng_bit_generator") == 1
    assert "threefry" not in text


def test_the_noise_is_the_same_on_one_device_and_on_a_dp_mesh():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg = BlockDiffusionConfig(block_length=4, mask_id=1, t_min=1e-3)
    key = jax.random.key_data(dropout.key_for(jax.random.PRNGKey(9)))
    ids = jnp.arange(4 * 64, dtype=jnp.int32).reshape(4, 64) % 50 + 2

    def noised(key, ids):
        masked, t = blockdiff.draw_noise(
            jax.random.wrap_key_data(key, impl=dropout.GENERATOR),
            ids.shape[0], ids.shape[1], cfg)
        return blockdiff.make_pair(ids, masked, cfg.mask_id), t

    one = jax.jit(noised)(key, ids)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    two = jax.jit(noised, in_shardings=(NamedSharding(mesh, P()), rows),
                  out_shardings=(rows, rows))(key, ids)
    assert len(two[0].sharding.device_set) == 2
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- the loss

def test_the_weighted_loss_is_optaxs_and_its_backward_autodiffs():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((2, 8, 32)).astype(np.float32))
    targets = jnp.asarray(rng.integers(0, 32, (2, 8)).astype(np.int32))
    weights = jnp.asarray(
        rng.random((2, 8)).astype(np.float32) * (rng.random((2, 8)) < 0.5))

    def plain(x):
        ce = optax.softmax_cross_entropy_with_integer_labels(x, targets)
        return jnp.sum(ce * weights) / 16

    got, grad = jax.value_and_grad(
        lambda x: weighted_crossentropy(x, targets, weights))(logits)
    want, want_grad = jax.value_and_grad(plain)(logits)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want_grad),
                               rtol=1e-5, atol=1e-7)
    # An unweighted position has no gradient; no gradient reaches weights.
    assert not np.asarray(grad)[np.asarray(weights) == 0].any()
    assert not np.asarray(jax.grad(
        lambda w: weighted_crossentropy(logits, targets, w))(weights)).any()
    # Weight 1 everywhere but the last position and the targets shifted is
    # the next-token loss but for its count (S - 1 terms, not S).
    shifted = jnp.roll(targets, -1, axis=1)
    ones = jnp.ones((2, 8)).at[:, -1].set(0.0)
    assert float(weighted_crossentropy(logits, shifted, ones)) * 8 / 7 == (
        pytest.approx(float(lm_crossentropy(logits, targets)), rel=1e-6))
    # No second copy of the logits: the backward is one expression over
    # them (no log_softmax, no scatter into zeros).
    text = str(jax.make_jaxpr(jax.grad(
        lambda x: blockdiff_crossentropy((x, weights), targets)))(logits))
    assert "scatter" not in text and "log_softmax" not in text


# ----------------------------------------------------- the step's keys

def _tiny_estimator(builder, seed=3, **kw):
    from raydp_tpu.train import JAXEstimator

    return JAXEstimator(
        **{**builder.estimator_kwargs(
            dict(SIZES, optimizer={"name": "adamw", "learning_rate": 3e-3}),
            TRAFFIC, None), **kw},
        batch_size=4, seed=seed, epoch_mode="stream",
    )


def test_only_a_model_that_names_them_gets_further_keys(builder):
    """BERT's, DLRM's and the causal LMs' steps get the ``dropout`` key and
    nothing else, and a causal LM's lowered step draws no random bits at
    all: the programs they were."""
    from raydp_tpu.train import JAXEstimator

    est = _tiny_estimator(builder)
    assert model_step.step_rngs(est._model) == ("noise",)
    assert BlockDiffusionLM.positions_per_token == 2
    causal = JAXEstimator(
        model=CausalLM(olmoe(vocab_size=64, d_model=32, n_heads=2,
                             n_layers=1, n_experts=4, top_k=2, d_expert=16,
                             max_len=16, dtype=jnp.float32)),
        optimizer=optax.adamw(1e-3), loss="lm_ce", self_supervised=True,
        aux_losses=True, batch_size=2, seed=1, epoch_mode="stream",
        feature_columns=[f"t{i}" for i in range(16)], feature_dtype=np.int32,
    )
    assert model_step.step_rngs(causal._model) == ()
    x = np.zeros((2, 16), np.int32)
    causal._init_state(x)
    text = jax.jit(causal._make_train_step()).lower(
        causal._state, jnp.asarray(x), None, jax.random.PRNGKey(0)).as_text()
    assert "rng_bit_generator" not in text


# ------------------------------------------------------------- one fit

@pytest.fixture(scope="module")
def fitted(builder):
    """One fit of three epochs, what its built step reported, and the
    losses of its step from the fresh state under two keys."""
    import pandas as pd

    from raydp_tpu.utils.profiling import metrics

    rows = np.random.default_rng(1).integers(0, 250, (16, SEQ)).astype(
        np.int32)
    frame = pd.DataFrame({f"t{i}": rows[:, i] for i in range(SEQ)})
    records, handler = [], logging.Handler()
    handler.emit = records.append
    loggers = [logging.getLogger("raydp_tpu.models.blockdiff"),
               logging.getLogger("raydp_tpu.ops.flash_attention")]
    for log in loggers:
        log.addHandler(handler)
        log.setLevel(logging.INFO)
    # The registry is the process's: count from where an earlier file left it.
    before = metrics.snapshot()["counters"].get("diffusion/masked_tokens", 0)
    try:
        est = _tiny_estimator(builder, donate_state=False)
        est._init_state(rows[:4])
        keys = [jax.random.fold_in(jax.random.PRNGKey(3), i) for i in (0, 1)]
        x = jnp.asarray(rows[:4])
        steps = [float(est._train_step(est._state, x, None, k)[1])
                 for k in (keys[0], keys[0], keys[1])]
        history = est.fit_on_df(frame, num_epochs=3, num_shards=2)
    finally:
        for log in loggers:
            log.removeHandler(handler)
    gauges = dict(metrics.snapshot()["gauges"])
    counted = metrics.snapshot()["counters"]["diffusion/masked_tokens"] - (
        before)
    return est, history, steps, gauges, counted, records


def test_fit_trains_and_the_same_key_gives_the_same_loss(fitted):
    """The step's noise is a function of its key, so of (seed, step): the
    same state, rows and key give the same loss to the bit, another step's
    key another."""
    _, history, steps, _, _, _ = fitted
    losses = [h["train_loss"] for h in history]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert steps[0] == steps[1] != steps[2]


def test_two_epochs_noise_a_sequence_differently(fitted):
    """The noise key is a function of (seed, step): the same rows come
    back every epoch and are masked afresh (16 rows of 32 tokens: two
    epochs masking the same number of tokens would be a coincidence of one
    in some twenty, three of them one in four hundred)."""
    est, _, _, _, _, _ = fitted
    model, ids = est._model, jnp.zeros((4, SEQ), jnp.int32)

    @jax.jit
    def masked_at(params, rng):
        # The keys ``JAXEstimator``'s step makes of its step key.
        key = dropout.key_for(jax.random.fold_in(rng, 1))
        _, sown = model.apply(
            params, ids, deterministic=False, rngs={"noise": key},
            mutable=["intermediates", "moe_stats", "losses"])
        return sown["intermediates"]["noise"][0][0]

    steps = [jax.random.fold_in(jax.random.PRNGKey(3), i) for i in (0, 1)]
    first, second = (np.asarray(masked_at(est._state.params, k))
                     for k in steps)
    assert (first != second).any()
    np.testing.assert_array_equal(
        second, np.asarray(masked_at(est._state.params, steps[1])))


def test_the_built_step_reports_itself(fitted):
    _, _, _, gauges, counted, records = fitted
    assert gauges["diffusion/block_length"] == 4
    assert gauges["diffusion/blocks_per_sequence"] == 8
    assert gauges["diffusion/pair_positions_per_step"] == 2 * 4 * SEQ
    # Dense attention here: no flash call, so no tile of any kind.
    for name in ("attention/flash_pair_live_tiles",
                 "attention/flash_pair_crossed_tiles",
                 "attention/flash_pair_own_block_tiles",
                 "attention/flash_live_tiles"):
        assert gauges[name] == 0, name
    # ``models/moe.py`` without a line changed: 8 routed, 4 held, and the
    # share's rows of the 2 x 4 x 32 pair positions a layer.
    assert gauges["moe/experts_routed"] == 8
    assert gauges["moe/experts_held"] == 4
    assert gauges["moe/compact_rows"] == 2 * 4 * SEQ * 2
    assert gauges["moe/expert_tokens_per_step"] == 2 * (2 * 4 * SEQ) * 2
    # Masked tokens summed on the device, fetched with each epoch's loss:
    # three epochs of 16 x 32 tokens, about half of them.
    assert 0.3 < gauges["diffusion/masked_share"] < 0.7
    assert 0.3 * 3 * 512 < counted < 0.7 * 3 * 512
    lines = [r.getMessage() for r in records
             if r.name == "raydp_tpu.models.blockdiff"]
    assert len(lines) == 1
    for said in ("blocks of 4 tokens", "8 a sequence", "mask id 254",
                 "U[0.001, 1]", "256 pair positions", "own-block term"):
        assert said in lines[0], said


def test_the_flash_report_names_the_form_the_own_block_term_takes(builder):
    """At the published widths and flash attention: 72 live tiles a head
    and pair, 16 crossed, none for the own-block term, and the INFO line
    says where that term runs."""
    from raydp_tpu.utils.profiling import metrics

    flash = __import__("importlib").import_module(
        "raydp_tpu.ops.flash_attention")
    cfg = sdar_30b_a3b(n_layers=6, attention_impl="flash", remat=True)
    records, handler = [], logging.Handler()
    handler.emit = records.append
    log = logging.getLogger("raydp_tpu.ops.flash_attention")
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        flash.report(cfg, seq_len=8192, batch=1)
    finally:
        log.removeHandler(handler)
    assert metrics.gauge_value("attention/flash_pair_live_tiles") == 72
    assert metrics.gauge_value("attention/flash_pair_crossed_tiles") == 16
    assert metrics.gauge_value("attention/flash_pair_own_block_tiles") == 0
    assert metrics.gauge_value("attention/flash_fused_bwd_layers") == 6
    line = records[-1].getMessage()
    for said in ("pair mask in blocks of 4", "72 live", "would compute 136",
                 "16 of them masked", "no noised key read", "[2048, 4, 4]",
                 "one kernel"):
        assert said in line, said


def test_the_scopes_the_part_rules_and_readers_read(builder, tiny):
    """``noise`` around the draw, the select and the pair's ids and
    positions; ``attn/pair`` around what lies between the rotated q, k, v
    and attention's output."""
    model, variables, _, (ids, _, _) = tiny
    key = dropout.key_for(jax.random.PRNGKey(0))
    text = jax.jit(lambda v, x: model.apply(
        v, x, deterministic=False, rngs={"noise": key},
        mutable=[moe_module.STATS])[0][0]).lower(variables, ids).as_text(
            debug_info=True)
    assert "BlockDiffusionLM/noise/" in text
    assert "block_0/attn/pair/" in text and "block_1/attn/pair/" in text
    assert "BlockDiffusionLM/encoder/checkpoint/block_0/" in text
    assert "BlockDiffusionLM._run_pair" not in text
    # The whole training-mode forward draws random bits once.
    assert text.count("stablehlo.rng_bit_generator") == 1
