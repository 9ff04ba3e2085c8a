"""Attention over a learned selection on a Keye-VL-2.0 style stack, at tiny
widths on the CPU (hidden 64, 4 query heads over 2 key-value heads of 16
with a norm a head, 4 index heads of 8 over one index key head, each query
keeping 8 keys, 8 experts of width 32 of which 4 are held, sequence 64,
vocabulary 256), float32, the kernels in the Pallas interpreter: the
program against the benchmark's plain reference given the same share
(logits, loss, every gradient), every departure the builder lists above
its tolerance, the two detachments, queries before ``topk`` against plain
causal attention, ties at the threshold, M-RoPE against plain rotary, the
eight shares of one layer against the uncut layer, and one fit through
``JAXEstimator`` with the scopes and gauges of the built step."""
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM, SparseIndexConfig, keye_vl_2_0_30b_a3b
from raydp_tpu.models import moe as moe_module
from raydp_tpu.models import sparse_index, stats
from raydp_tpu.models.transformer import rotary, rotary_angles
from raydp_tpu.ops import sparse_attention as sa
from raydp_tpu.ops.attention import reference_attention
from raydp_tpu.train.losses import lm_crossentropy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
SIZES = {
    "builder": "keye_sparse_moe_lm", "model_type": "KeyeVL2",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 64, "max_window_layers": 2,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_local_experts": 8, "num_experts_routed": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                  "q_chunk_size": 16, "topk": 8},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 256,
    "init": {"embedding_std": 1.0}, "remat": True,
    "compute_dtype": "float32", "param_dtype": "float32",
}
TRAFFIC = {"seq_len": SEQ, "per_chip_batch": 2}


@pytest.fixture(scope="module", autouse=True)
def small_tiles():
    """Tiles of the tiny sequence: four query tiles, two key tiles, two
    blocks of rows for the selection. The loops over heads run as the
    module runs them off the chip."""
    small = {
        "BLOCK_Q": 16, "BLOCK_KV": 32, "SELECT_ROWS": 32,
        "SELECT_TILE_ROWS": 16, "SELECT_CHUNK": 32,
    }
    saved = {name: getattr(sa, name) for name in small}
    for name, value in small.items():
        setattr(sa, name, value)
    yield saved
    for name, value in saved.items():
        setattr(sa, name, value)


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there. Its
    blocks of query rows are cut to 16 so that the tiny sequence has four."""
    path = os.path.join(REPO, "benchmark", "configs", "keye_sparse_moe_lm.py")
    spec = importlib.util.spec_from_file_location("keye_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.QUERY_ROWS_AT_ONCE = 16
    return module


@pytest.fixture(scope="module")
def tiny(builder):
    """The model, seeded weights and seeded ids."""
    model = CausalLM(builder.model_config(SIZES))
    ids = jnp.asarray(builder.check_batch(
        SIZES, dict(TRAFFIC, seq_len=SEQ), 7)).repeat(2, axis=0)
    ids = ids.at[1].set(jnp.roll(ids[1], 5) % 250)
    variables = jax.jit(lambda: {"params": nn.unbox(
        model.init(jax.random.PRNGKey(0), ids))["params"]})()
    return model, variables, ids


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _apply(model, variables, ids, **kwargs):
    return model.apply(
        variables, ids, mutable=["losses", stats.STATS], **kwargs)


def _program_loss(model, params, ids, weight=1.0):
    logits, mut = _apply(model, {"params": params}, ids)
    return lm_crossentropy(logits, ids) + weight * moe_module.moe_aux_loss(
        mut)


def _draws(seed, b=2, s=SEQ, h=4, h_kv=2, d=16, h_i=3, d_i=8):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    return (f(b, s, h, d), f(b, s, h_kv, d), f(b, s, h_kv, d),
            f(b, s, h_i, d_i), f(b, s, d_i), f(b, s, h_i))


# ---------------------------------------------- program against reference

def test_parameter_tree_is_the_trunks_with_an_index_branch(tiny):
    _, variables, _ = tiny
    tree = jax.tree_util.tree_map(lambda a: tuple(a.shape), variables)
    block = {
        "ln_attn": {"scale": (64,)}, "ln_mlp": {"scale": (64,)},
        "attn": {"q": {"kernel": (64, 4, 16)},
                 "kv": {"kernel": (64, 2, 2, 16)},
                 "q_norm": {"scale": (16,)}, "k_norm": {"scale": (16,)},
                 "out": {"kernel": (4, 16, 64)},
                 "index": {"wq": {"kernel": (64, 4, 8)},
                           "wk": {"kernel": (64, 8)},
                           "k_norm": {"scale": (8,), "bias": (8,)},
                           "weights": (64, 4)}},
        "moe": {"router": {"kernel": (64, 8)}, "w_gate": (4, 64, 32),
                "w_up": (4, 64, 32), "w_down": (4, 32, 64)},
    }
    assert tree["params"] == {
        "encoder": {"tok_embed": {"embedding": (256, 64)},
                    "block_0": block, "block_1": block,
                    "ln_final": {"scale": (64,)}},
        "lm_head": {"kernel": (64, 256)},
    }


def test_logits_match_the_reference(builder, tiny):
    model, variables, ids = tiny
    got = _apply(model, variables, ids)[0]
    want = builder.reference_logits(variables, ids, SIZES)
    assert got.shape == (2, SEQ, 256)
    assert _rel(got, want) < 1e-4 < builder.TOLERANCE


def test_loss_and_every_gradient_match_the_reference(builder, tiny):
    model, variables, ids = tiny
    got, got_grads = jax.value_and_grad(
        lambda p: _program_loss(model, p, ids))(variables["params"])
    want, want_grads = builder.reference_loss_and_grads(variables, ids, SIZES)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(want_grads["params"]))
    assert len(flat) == len(ref) == 2 * 16 + 3
    for path, leaf in flat:
        assert float(jnp.max(jnp.abs(ref[path]))) > 0, path
        assert _rel(leaf, ref[path]) < 2e-3, jax.tree_util.keystr(path)


def test_every_departure_reads_above_the_tolerance(builder, tiny):
    _, variables, ids = tiny
    want = builder.reference_logits(variables, ids, SIZES)
    positions = np.stack([
        np.broadcast_to(np.arange(SEQ), (2, SEQ)),
        np.broadcast_to(np.arange(SEQ) // 8, (2, SEQ)),
        np.broadcast_to(np.arange(SEQ) % 8, (2, SEQ)),
    ])
    unequal = builder.reference_logits(
        variables, ids, SIZES, positions=positions)
    assert set(builder.UNSEEN_ON_THE_CHIP) <= set(builder.DEPARTURES)
    for depart in builder.DEPARTURES:
        if depart == "plain_rotary_bands":
            # One id where three differ: seen where they do.
            got, base = builder.reference_logits(
                variables, ids, SIZES, depart=depart, positions=positions
            ), unequal
            assert _rel(builder.reference_logits(
                variables, ids, SIZES, depart=depart), want) == 0.0
        else:
            got, base = builder.reference_logits(
                variables, ids, SIZES, depart=depart), want
        assert _rel(got, base) > builder.TOLERANCE, depart
    with pytest.raises(ValueError):
        builder.reference_logits(variables, ids, SIZES, depart="no_such")


def test_unequal_ids_run_through_the_model(builder, tiny):
    """Three ids a token, given to the model, against the reference."""
    model, variables, ids = tiny
    positions = np.stack([
        np.broadcast_to(np.arange(SEQ), (2, SEQ)),
        np.broadcast_to(np.arange(SEQ) // 8, (2, SEQ)),
        np.broadcast_to(np.arange(SEQ) % 8, (2, SEQ)),
    ])
    got = _apply(model, variables, ids, positions=jnp.asarray(positions))[0]
    want = builder.reference_logits(
        variables, ids, SIZES, positions=positions)
    assert _rel(got, want) < 1e-4
    assert _rel(got, _apply(model, variables, ids)[0]) > 1e-2


# ------------------------------------------------------ the two detachments

def test_without_the_index_loss_the_branch_gets_nothing(tiny):
    """Weight 0: the branch's gradient is exactly zero and every other
    gradient is the weighted step's to the bit; the LM loss alone gives
    the branch nothing either."""
    model, variables, ids = tiny
    params = variables["params"]
    with_loss = jax.grad(lambda p: _program_loss(model, p, ids))(params)
    without = jax.grad(lambda p: _program_loss(model, p, ids, 0.0))(params)
    lm_only = jax.grad(
        lambda p: lm_crossentropy(_apply(model, {"params": p}, ids)[0], ids)
    )(params)
    for tree in (without, lm_only):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            same = dict(jax.tree_util.tree_leaves_with_path(with_loss))[path]
            if "index" in jax.tree_util.keystr(path):
                assert float(jnp.max(jnp.abs(leaf))) == 0.0, path
                assert float(jnp.max(jnp.abs(same))) > 0.0, path
            else:
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(same))


def test_the_index_loss_reaches_the_branch_alone(tiny):
    model, variables, ids = tiny
    grads = jax.grad(lambda p: moe_module.moe_aux_loss(
        _apply(model, {"params": p}, ids)[1]))(variables["params"])
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        inside = "index" in jax.tree_util.keystr(path)
        assert (float(jnp.max(jnp.abs(leaf))) > 0.0) == inside, path


# --------------------------------------------------------- the operation

@pytest.fixture
def backward(request, monkeypatch):
    """The rule on the call's shapes forced each way: a chip with no VMEM
    sends every call to the ``dq`` and ``dk/dv`` kernels."""
    if request.param == "pair":
        monkeypatch.setattr(sa, "vmem_bytes", lambda: 0)
    return request.param


# A ``topk`` under, at and over a tile at four query and two key tiles;
# then eight query and four key tiles, so that every resident key tile is
# reached from several query tiles and each of the two batch rows starts
# from zero and is written once.
@pytest.mark.parametrize("topk, seq, backward", [
    (8, SEQ, "fused"), (24, SEQ, "fused"), (200, SEQ, "fused"),
    (8, SEQ, "pair"), (24, SEQ, "pair"), (200, SEQ, "pair"),
    (24, 2 * SEQ, "fused"),
], indirect=["backward"])
def test_the_kernels_match_the_dense_formula(topk, seq, backward):
    args = _draws(topk, s=seq)
    shapes = [a.shape[1:] for a in args]
    assert sa.backward_is_fused(
        seq, shapes[0][1], shapes[1][1], shapes[0][2], *shapes[3][1:],
        jnp.float32,
    ) == (backward == "fused")
    got = sa.sparse_attention(*args, topk)
    want = sa.reference_sparse_attention(*args, topk)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5
    cot = _draws(topk + 1, s=seq)[0], jnp.asarray(
        np.random.default_rng(3).standard_normal((2, seq)), jnp.float32)

    def loss(fn, *a):
        out, kl, _ = fn(*a, topk)
        return (out * cot[0]).sum() + (kl * cot[1]).sum()

    grads = jax.grad(lambda *a: loss(sa.sparse_attention, *a), range(6))
    calls = str(jax.make_jaxpr(grads)(*args))
    for name, there in (("sparse_attention_backward", backward == "fused"),
                        ("sparse_attention_dq", backward == "pair"),
                        ("sparse_attention_dkv", backward == "pair")):
        assert (name in calls) == there, name
    got = grads(*args)
    want = jax.grad(
        lambda *a: loss(sa.reference_sparse_attention, *a), range(6))(*args)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-4


def test_two_heads_a_trip_give_the_gradients_of_one(monkeypatch):
    """The loops over a group's heads unrolled as the chip runs them
    (off the chip they run one head a trip: ``_heads_a_trip``): with every
    causal key selected (no threshold for the interpreter's last bit to
    move) outputs and gradients are the dense formula's."""
    assert sa._heads_a_trip(8) == 1
    monkeypatch.setattr(sa, "_heads_a_trip", lambda group: 2)
    args = _draws(21)

    def loss(fn, *a):
        out, kl, _ = fn(*a, 200)
        return (out * args[0]).sum() + kl.sum()

    got = jax.value_and_grad(
        lambda *a: loss(sa.sparse_attention, *a), range(6))(*args)
    want = jax.value_and_grad(
        lambda *a: loss(sa.reference_sparse_attention, *a), range(6))(*args)
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert _rel(g, w) < 1e-4


def test_the_chips_checks_run_at_the_tests_tiles():
    """``scripts/sparse_attention_on_chip.py`` is where the chip is asked
    what the interpreter cannot promise; its parts run here so that they
    stay runnable: the backward's mask, built as the kernel builds it, is
    the selection's on every pair, and both backward paths give the
    gradients of the dense formula taken a block of query rows at a
    time."""
    path = os.path.join(REPO, "scripts", "sparse_attention_on_chip.py")
    spec = importlib.util.spec_from_file_location("sparse_on_chip", path)
    on_chip = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(on_chip)
    _, _, _, q_idx, k_idx, w = _draws(4, b=1)
    (layer,) = on_chip.mask_agreement(
        {"layer": (q_idx[0], k_idx[0], w[0])}, 8, rows=32).values()
    assert layer["pairs_that_differ"] == 0
    assert layer["selection_pairs"] == layer["backward_mask_pairs"] >= sum(
        min(t + 1, 8) for t in range(SEQ))
    assert layer["backward_holds_of_float32"] > 0.99
    errors = on_chip.gradient_errors(
        5, SEQ, 8, 16, h=4, h_kv=2, d=16, h_i=3, d_i=8, dtype=jnp.float32)
    assert errors["rule"]["kernels"] == ["sparse_attention_backward"]
    assert errors["pair"]["kernels"] == [
        "sparse_attention_dkv", "sparse_attention_dq"]
    for path in errors.values():
        assert max(path["error"].values()) < 1e-4, path
    assert on_chip.main(["--skip-model"]) == 3    # no TPU here


def test_the_rule_is_the_calls_own_shapes_against_the_chips_vmem(
        small_tiles, monkeypatch):
    """At the module's own tiles: the cell's call (16,384 tokens, 32 heads
    over 4 of 128, 16 index heads of 64, bf16) keeps 68 MiB resident and
    takes the one kernel; 32,768 tokens would keep 136 of the chip's 128
    and take the pair; no VMEM, no one kernel."""
    for name, value in small_tiles.items():
        monkeypatch.setattr(sa, name, value)
    cell = (16384, 32, 4, 128, 16, 64, jnp.bfloat16)
    resident, needed = sa.fused_backward_vmem(*cell)
    assert resident == 68 * 2 ** 20 < needed <= 120 * 2 ** 20
    assert sa.backward_is_fused(*cell)
    long = (32768,) + cell[1:]
    assert sa.fused_backward_vmem(*long)[0] == 136 * 2 ** 20
    assert not sa.backward_is_fused(*long)
    monkeypatch.setattr(sa, "vmem_bytes", lambda: 64 * 2 ** 20)
    assert not sa.backward_is_fused(*cell)
    assert sa.backward_is_fused(2048, *cell[1:])


def test_queries_before_topk_attend_causally():
    q, k, v, q_idx, k_idx, w = _draws(11)
    out, _, count = sa.sparse_attention(q, k, v, q_idx, k_idx, w, 24)
    causal = reference_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True)
    np.testing.assert_allclose(out[:, :24], causal[:, :24], atol=1e-5)
    assert _rel(out[:, 24:], causal[:, 24:]) > 1e-2
    # Exactly topk barring ties (three index heads tie at 0 now and then).
    want = np.minimum(np.arange(SEQ) + 1, 24)
    np.testing.assert_array_equal(np.asarray(count[0, :24]), want[:24])
    assert (np.asarray(count) >= want).all()
    assert (np.asarray(count) == want).mean() > 0.8
    # topk past the sequence: plain causal attention everywhere.
    full = sa.sparse_attention(q, k, v, q_idx, k_idx, w, 200)[0]
    np.testing.assert_allclose(full, causal, atol=1e-5)


def test_a_tie_at_the_threshold_keeps_all_tied_keys(builder):
    """Index scores that take few values: whole groups of keys tie at the
    threshold; program and dense formula keep them all, and the count
    passes topk. -0.0 ties with +0.0."""
    q, k, v, q_idx, k_idx, w = _draws(5, h_i=1)
    q_idx = jnp.ones_like(q_idx)
    k_idx = jnp.broadcast_to(
        (jnp.arange(SEQ) % 3 - 1.0)[None, :, None], k_idx.shape)
    w = jnp.ones_like(w)
    got = sa.sparse_attention(q, k, v, q_idx, k_idx, w, 8)
    want = sa.reference_sparse_attention(q, k, v, q_idx, k_idx, w, 8)
    assert float(got[2].max()) > 8
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    assert _rel(got[0], want[0]) < 1e-5
    tau, _, count = sa.select_threshold(
        jnp.asarray([[0.0, -0.0, -1.0, -jnp.inf] * 4] * 16, jnp.float32), 5)
    assert float(tau[0]) == 0.0 and float(count[0]) == 8.0
    # A row shorter than topk keeps every live key.
    tau, _, count = sa.select_threshold(
        jnp.asarray([[0.5, -jnp.inf] * 8] * 16, jnp.float32), 100)
    assert float(tau[0]) == -np.inf and float(count[0]) == 8.0


def test_the_threshold_is_the_order_statistic():
    rng = np.random.default_rng(9)
    scores = jnp.asarray(rng.standard_normal((32, 256)) * 10.0 ** rng.integers(
        -20, 20, (32, 1)), jnp.float32)
    tau, lse, count = sa.select_threshold(scores, 17)
    want = np.sort(np.asarray(scores), axis=1)[:, -17]
    np.testing.assert_array_equal(np.asarray(tau), want)
    np.testing.assert_array_equal(np.asarray(count), 17.0)
    kept = np.where(np.asarray(scores) >= want[:, None], scores, -np.inf)
    np.testing.assert_allclose(
        np.asarray(lse), jax.nn.logsumexp(kept, axis=1), rtol=1e-5)


# ------------------------------------------------------------------ M-RoPE

def test_mrope_is_plain_rotary_on_equal_ids_and_differs_band_by_band():
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 12, 3, 16)), jnp.float32)
    pos = jnp.arange(12)[None, :]
    plain = rotary(x, pos, 1e7)
    equal = jnp.broadcast_to(pos, (3, 1, 12))
    np.testing.assert_array_equal(
        np.asarray(rotary(x, equal, 1e7, sections=(2, 3, 3))),
        np.asarray(plain))
    half = 8
    for band, (lo, hi) in enumerate([(0, 2), (2, 5), (5, 8)]):
        ids = equal.at[band].add(3)
        got = rotary(x, ids, 1e7, sections=(2, 3, 3))
        moved = np.abs(np.asarray(got - plain)).max(axis=(0, 1, 2))
        inside = np.zeros(16, bool)
        inside[lo:hi] = inside[half + lo:half + hi] = True
        assert (moved[inside] > 0).all() and (moved[~inside] == 0).all()
    angles = rotary_angles(equal.at[1].add(3), half, 1e7, None, (2, 3, 3))
    np.testing.assert_array_equal(
        np.asarray(angles[..., :2]),
        np.asarray(rotary_angles(pos, half, 1e7)[..., :2]))
    with pytest.raises(ValueError):
        rotary(x, equal, 1e7, sections=(2, 3, 4))


# ------------------------------------------------- the share ties to the model

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(builder):
    """Eight experts over EIGHT shares of one: each share's layer gives
    attention's output (the same in all) plus its own expert's part; the
    parts add up to the uncut layer, which the reference agrees with."""
    sizes = dict(SIZES, num_hidden_layers=1, num_experts=8, first_expert=0)
    whole = CausalLM(builder.model_config(sizes))
    ids = jnp.asarray(builder.check_batch(sizes, TRAFFIC, 3))
    variables = {"params": nn.unbox(
        whole.init(jax.random.PRNGKey(1), ids))["params"]}
    want = builder.reference_logits(variables, ids, sizes)
    assert _rel(_apply(whole, variables, ids)[0], want) < 1e-4

    block = variables["params"]["encoder"]["block_0"]
    embed = variables["params"]["encoder"]["tok_embed"]["embedding"][ids]

    def layer_out(cfg_sizes, moe_params):
        cfg = builder.model_config(cfg_sizes)
        from raydp_tpu.models.transformer import TransformerBlock

        params = {**block, "moe": moe_params}
        return TransformerBlock(cfg, "sparse", "moe").apply(
            {"params": params}, embed, mutable=["losses", stats.STATS])[0]

    uncut = layer_out(sizes, block["moe"])
    parts = []
    for first in range(8):
        share = dict(sizes, num_experts=1, first_expert=first)
        moe = {"router": block["moe"]["router"], **{
            name: block["moe"][name][first:first + 1]
            for name in ("w_gate", "w_up", "w_down")}}
        parts.append(layer_out(share, moe))
    none = layer_out(
        dict(sizes, num_experts=1, first_expert=0),
        {"router": block["moe"]["router"], **{
            name: jnp.zeros_like(block["moe"][name][:1])
            for name in ("w_gate", "w_up", "w_down")}})
    # x + attention is in every share once; the experts' parts add up.
    total = none + sum(part - none for part in parts)
    assert _rel(total, uncut) < 1e-5


# ------------------------------------------------------- through the estimator

def test_the_mixer_refuses_what_it_does_not_run(tiny):
    model, variables, ids = tiny
    with pytest.raises(NotImplementedError):
        SparseIndexConfig(index_kv_heads=2)
    cfg = keye_vl_2_0_30b_a3b()
    assert cfg.kinds == ("sparse",) * 48 and not cfg.serves_from_kv_cache
    assert (cfg.sparse.index_heads, cfg.sparse.index_head_dim,
            cfg.sparse.topk) == (16, 64, 2048)
    assert cfg.positions == "mrope" and cfg.mrope_section == (16, 24, 24)
    with pytest.raises(NotImplementedError):
        model.apply(variables, ids[:, :8], jnp.asarray([8, 8]),
                    method=model.prefill, mutable=["cache"])


def test_fit_reports_the_selection(builder, small_tiles, monkeypatch):
    """One fit through ``JAXEstimator``: the step's loss carries the index
    losses, the epoch's gauges say what was selected, and the reports read
    zero for a model without the mixer."""
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    kwargs = builder.estimator_kwargs(
        dict(SIZES, optimizer={"name": "adamw", "learning_rate": 1e-3}),
        dict(TRAFFIC, seq_len=SEQ), None)
    import pandas as pd

    x = np.random.default_rng(0).integers(0, 256, (8, SEQ)).astype(np.int32)
    frame = pd.DataFrame({f"t{i}": x[:, i] for i in range(SEQ)})
    est = JAXEstimator(**kwargs, batch_size=2, seed=0, epoch_mode="stream")
    history = est.fit_on_df(frame, num_epochs=2, num_shards=2)
    assert np.isfinite(history[-1]["train_loss"])
    share = metrics.gauge_value("attn/selected_share")
    pairs = sum(min(t + 1, 8) for t in range(SEQ))
    assert share >= pairs / (SEQ * (SEQ + 1) / 2) - 1e-6
    assert share < 0.5
    assert metrics.gauge_value("attn/index_kl") > 0.0
    assert metrics.gauge_value("attn/select_overfull_queries") >= 0.0
    assert metrics.gauge_value("attention/sparse_layers") == 2
    assert metrics.gauge_value("attention/index_topk") == 8
    # Both layers' backward is the one kernel; a row's dk and dv of two
    # key-value heads of 16 and dkI of 8, float32, stay resident.
    assert metrics.gauge_value("attention/sparse_fused_bwd_layers") == 2
    assert metrics.gauge_value("attention/sparse_bwd_resident_mib") == (
        4 * SEQ * (2 * 2 * 16 + 8) / 2 ** 20)
    logits = est.predict(x[:2])
    assert logits.shape == (2, SEQ, 256)
    # Zero for every other model.
    cell = keye_vl_2_0_30b_a3b(n_layers=5)
    for seq, layers, mib in ((16384, 5, 68), (32768, 0, 0)):
        with monkeypatch.context() as at_full_size:
            for name, value in small_tiles.items():
                at_full_size.setattr(sa, name, value)
            sparse_index.report(cell, seq_len=seq)
        assert metrics.gauge_value("attention/sparse_layers") == 5
        assert metrics.gauge_value(
            "attention/sparse_fused_bwd_layers") == layers
        assert metrics.gauge_value(
            "attention/sparse_bwd_resident_mib") == mib
    sparse_index.report(keye_vl_2_0_30b_a3b(
        layer_types=("attention",) * 48, sparse=None), seq_len=16384)
    sparse_index.report_epoch({"expert_tokens": np.ones(4)})
    for gauge in ("attn/selected_share", "attn/index_kl",
                  "attn/select_overfull_queries", "attention/sparse_layers",
                  "attention/index_topk", "attention/index_heads",
                  "attention/sparse_fused_bwd_layers",
                  "attention/sparse_bwd_resident_mib"):
        assert metrics.gauge_value(gauge) == 0.0, gauge
