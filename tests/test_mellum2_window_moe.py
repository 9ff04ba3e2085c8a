"""The Mellum 2 style stack on the normal path as the WHOLE group of chips
that shares each layer, at tiny widths on the CPU's forced devices (hidden
64, 8 query heads over 2 key-value heads of 16, one layer over a window of
8 and one over all positions under YaRN, 16 experts of width 32, 4 a
token, 4 a chip, sequence 64, vocabulary 512 of which a chip holds 128),
float32: ONE estimator on a ``dp=4`` mesh, built by the benchmark's builder,
whose ``predict`` and one ``fit`` step of plain SGD are held against the
builder's plain reference, which knows no mesh (logits, loss, every
gradient), every departure the builder lists, what a chip holds at rest,
the vocabulary's lookup against the plain one, the published block the
factory writes down, and the gauges of the built step."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import moe as moe_module
from raydp_tpu.models.transformer import (
    LOGICAL_RULES,
    embed_over,
    mellum2_12b_a2_5b,
    vocab_rules,
)
from raydp_tpu.parallel import MeshSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, CHIPS = 64, 4
SIZES = {
    "builder": "mellum2_window_moe_lm", "model_type": "mellum",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse", "sparse"],
    "max_position_embeddings": 256, "max_window_layers": 0,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 8, "tie_word_embeddings": False, "vocab_size": 512,
    "use_sliding_window": True,
    "deployment": {"chips_sharing_a_layer": 4, "chips_here": 4, "axis": "dp"},
    "attention_impl": "dense", "remat": True, "compute_dtype": "float32",
    "param_dtype": "float32", "init": {"embedding_std": 1.0},
    "optimizer": {"name": "sgd", "learning_rate": 256.0},
}
TRAFFIC = {"seq_len": SEQ, "per_chip_batch": 1}
VOCAB_TABLES = ("tok_embed/embedding", "lm_head/kernel")


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there. Its
    blocks of query rows are cut to 16 so that the tiny sequence has four."""
    path = os.path.join(
        REPO, "benchmark", "configs", "mellum2_window_moe_lm.py")
    spec = importlib.util.spec_from_file_location("mellum2_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.QUERY_ROWS_AT_ONCE = 16
    return module


def _flat(tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _largest_shard(leaf) -> int:
    return max(int(np.prod(s.data.shape)) for s in leaf.addressable_shards)


@pytest.fixture(scope="module")
def group(builder, eight_cpu_devices):
    """The estimator on the four-chip mesh: ``predict`` on four sequences,
    what every chip holds before and after ONE ``fit`` step of SGD at rate
    256 (a parameter's change is then minus 256 gradients: a power of two,
    large enough that a norm scale's 1.0 does not round the change away),
    the step's loss and statistics, beside the reference's loss and
    gradients on the parameters the step started from."""
    import pandas as pd

    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    mesh = MeshSpec(dp=CHIPS)
    est = JAXEstimator(
        **builder.estimator_kwargs(SIZES, TRAFFIC, mesh), batch_size=CHIPS,
        mesh=mesh, seed=3, epoch_mode="stream", shuffle=False,
    )
    ids = np.random.default_rng(0).integers(
        0, SIZES["vocab_size"], (CHIPS, SEQ)).astype(np.int32)
    est._init_state(ids)
    built = {name: metrics.gauge_value(name) for name in (
        "moe/exchange_chips", "moe/exchange_bytes_per_step",
        "moe/experts_routed", "moe/experts_held", "moe/compact_rows")}
    logits = est.predict(ids)
    before = jax.tree_util.tree_map(np.asarray, est._state.params)
    at_rest = {k: (leaf.size, _largest_shard(leaf))
               for k, leaf in _flat(est._state).items() if leaf.ndim >= 2}
    want_loss, want_grads = jax.jit(
        lambda p, x: builder.reference_loss_and_grads(p, x, SIZES)
    )(before, jnp.asarray(ids))
    frame = pd.DataFrame({f"t{i}": ids[:, i] for i in range(SEQ)})
    history = est.fit_on_df(frame, num_epochs=1, num_shards=1)
    after = jax.tree_util.tree_map(np.asarray, est._state.params)
    still = {k: (leaf.size, _largest_shard(leaf))
             for k, leaf in _flat(est._state).items() if leaf.ndim >= 2}
    return {
        "est": est, "ids": ids, "logits": logits, "before": before,
        "grads": _flat(jax.tree_util.tree_map(
            lambda a, b: (a - b) / 256.0, before, after)["params"]),
        "want_grads": _flat(want_grads["params"]), "want_loss": want_loss,
        "loss": history[-1]["train_loss"], "at_rest": at_rest,
        "still": still, "built": built,
        "chip_load": metrics.gauge_value("moe/chip_load_max_over_mean"),
        "overflow": metrics.gauge_value("moe/overflow_layer_steps"),
        "pairs": metrics.gauge_value("moe/expert_tokens_per_step"),
    }


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


LEAVES = sorted(
    [f"encoder/block_{i}/{name}" for i, attn in ((0, "attn_window"),
                                                 (1, "attn"))
     for name in ("ln_attn/scale", "ln_mlp/scale", f"{attn}/q/kernel",
                  f"{attn}/kv/kernel", f"{attn}/out/kernel",
                  "moe/router/kernel", "moe/w_gate", "moe/w_up",
                  "moe/w_down")]
    + ["encoder/tok_embed/embedding", "encoder/ln_final/scale",
       "lm_head/kernel"]
)


# ------------------------------------- (i) the mesh against the reference

def test_predict_over_the_mesh_is_the_references_logits(builder, group):
    want = builder.reference_logits(
        group["before"], jnp.asarray(group["ids"]), SIZES)
    assert group["logits"].shape == (CHIPS, SEQ, SIZES["vocab_size"])
    assert _rel(group["logits"], np.asarray(want)) < 2e-5


def test_the_steps_loss_is_the_references(group):
    assert group["loss"] == pytest.approx(float(group["want_loss"]), rel=1e-5)


def test_the_tree_is_the_uncut_stacks(group):
    assert sorted(group["grads"]) == LEAVES == sorted(group["want_grads"])
    shapes = {k: v.shape for k, v in group["grads"].items()}
    assert shapes["encoder/block_0/moe/w_gate"] == (16, 64, 32)
    assert shapes["encoder/block_1/attn/kv/kernel"] == (64, 2, 2, 16)
    assert shapes["encoder/tok_embed/embedding"] == (512, 64)
    assert shapes["lm_head/kernel"] == (64, 512)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_over_the_mesh_is_the_references(group, leaf):
    """Through ``fit``: the exchange's two collectives and their
    transposes, the vocabulary's lookup and head, the gradient all-reduce
    of what is replicated."""
    got, want = group["grads"][leaf], np.asarray(group["want_grads"][leaf])
    assert np.max(np.abs(want)) > 1e-6
    assert _rel(got, want) < 5e-4, leaf


@pytest.mark.parametrize("depart,least", [
    ("no_window", 0.1), ("no_yarn", 0.05), ("yarn_in_window", 0.05),
    ("gates_as_they_are", 1e-3), ("chip_0_experts", 2e-3),
])
def test_every_departure_is_told_from_the_program(builder, group, depart,
                                                  least):
    """In float32 the program agrees with the reference to 2e-5; each
    departure reads at least fifty times that (the tiny experts reach the
    logits less than the published ones do)."""
    assert depart in builder.DEPARTURES
    off = builder.reference_logits(
        group["before"], jnp.asarray(group["ids"]), SIZES, depart=depart)
    assert _rel(group["logits"], np.asarray(off)) > least


def test_the_departures_are_listed_once(builder):
    assert len(set(builder.DEPARTURES)) == len(builder.DEPARTURES) == 5
    # The check on the chip sees every one of them (PERF.md section 6).
    assert builder.UNSEEN_ON_THE_CHIP == ()
    with pytest.raises(ValueError, match="unknown departure"):
        builder.reference_logits({}, None, SIZES, depart="no_such")


# --------------------------------------------- (iv) what a chip holds at rest

@pytest.mark.parametrize("when", ["at_rest", "still"])
@pytest.mark.parametrize("table", VOCAB_TABLES)
def test_no_chip_holds_more_than_a_quarter_of_the_vocabulary(group, when,
                                                             table):
    """The table and both of its AdamW-shaped moments (here SGD keeps
    none: the parameter itself, and whatever the optimizer mirrors), at
    init and after a step's donated update."""
    found = {k: v for k, v in group[when].items() if k.endswith(table)}
    assert found
    for key, (size, shard) in found.items():
        assert shard * CHIPS == size, key


@pytest.mark.parametrize("when", ["at_rest", "still"])
def test_a_chip_holds_four_of_sixteen_experts_and_attention_whole(group,
                                                                  when):
    for key, (size, shard) in group[when].items():
        if "/moe/w_" in key:
            assert shard * CHIPS == size, key
        elif not key.endswith(VOCAB_TABLES):
            assert shard == size, key


def test_adamw_moments_lie_where_their_tables_do(builder, eight_cpu_devices):
    """The configuration's own optimizer: ``mu`` and ``nu`` of the two
    tables and of the experts are a quarter a chip (abstractly: shardings
    alone, nothing is allocated)."""
    from raydp_tpu.train import JAXEstimator

    mesh = MeshSpec(dp=CHIPS)
    sizes = dict(SIZES, optimizer={"name": "adamw", "learning_rate": 2e-5})
    est = JAXEstimator(
        **builder.estimator_kwargs(sizes, TRAFFIC, mesh), batch_size=CHIPS,
        mesh=mesh, seed=3,
    )
    sample = jnp.zeros((1, SEQ), jnp.int32)
    _, shardings = est._init_program(jax.random.PRNGKey(0), sample)
    specs = {k: s.spec for k, s in _flat(shardings).items()}
    quarter = [k for k, spec in specs.items() if "dp" in tuple(spec)]
    for table in VOCAB_TABLES + ("moe/w_gate", "moe/w_up", "moe/w_down"):
        mine = [k for k in quarter if k.endswith(table)]
        # The parameter, mu and nu (of each of the two layers' experts).
        assert len(mine) == (3 if table in VOCAB_TABLES else 6), table
    assert len(quarter) == 2 * 3 + 3 * 6


def test_the_estimator_lays_the_vocabulary_by_the_models_own_axis(group):
    """No ``logical_rules`` were passed: the model's ``state_axis`` is the
    one place the layout is stated in."""
    assert group["est"]._model.cfg.state_axis == "dp"
    assert dict(group["est"].logical_rules)["vocab"] == "dp"


def test_vocab_rules_move_the_vocabulary_alone():
    moved = dict(vocab_rules("dp"))
    assert moved["vocab"] == "dp" and dict(LOGICAL_RULES)["vocab"] is None
    assert {k: v for k, v in moved.items() if k != "vocab"} == {
        k: v for k, v in LOGICAL_RULES if k != "vocab"}


# ------------------------------------------------- the placement by load

def test_placement_fills_the_chips_evenly_heaviest_first(builder):
    load = jnp.asarray([9.0, 1.0, 8.0, 2.0, 7.0, 3.0, 6.0, 4.0])
    order = np.asarray(builder.balanced_placement(load, 4))
    # 9 | 8 | 7 | 6, then the lightest onto the fullest: 9+1, 8+2, 7+3, 6+4.
    assert order.tolist() == [0, 1, 2, 3, 4, 5, 6, 7]
    skew = jnp.asarray([9.0, 8.0, 7.0, 6.0, 4.0, 3.0, 2.0, 1.0])
    chips = np.asarray(skew)[np.asarray(
        builder.balanced_placement(skew, 4))].reshape(4, 2).sum(axis=1)
    assert chips.tolist() == [10.0, 10.0, 10.0, 10.0]
    assert np.asarray(skew).reshape(4, 2).sum(axis=1).max() == 17.0


def test_placed_experts_compute_what_init_drew(builder, eight_cpu_devices):
    """``deployed_group``'s ``init`` with ``place`` renumbers each layer's experts (router
    columns and the three stacked weights alike): the logits are the
    unplaced model's on the same key, the experts are a permutation of
    its experts, and the chips' loads on the pass that placed them are
    no further apart than before."""
    import flax.linen as nn

    from raydp_tpu.models import CausalLM

    cfg = builder.model_config(SIZES, MeshSpec(dp=CHIPS))
    ids = jnp.asarray(np.random.default_rng(4).integers(
        0, 8, (1, SEQ)).astype(np.int32))           # a few frequent words
    batch = jnp.tile(ids, (CHIPS, 1))
    key = jax.random.PRNGKey(5)
    plain_model, placed_model = CausalLM(cfg), builder.deployed_group(
        cfg, place=True)
    plain = nn.unbox(jax.jit(plain_model.init)(key, ids))["params"]
    placed = nn.unbox(jax.jit(placed_model.init)(key, ids))["params"]

    def run(model, params):
        logits, sown = jax.jit(lambda p, x: model.apply(
            {"params": p}, x, mutable=[moe_module.STATS, "losses"]
        ))(params, batch)
        loads = _flat(sown[moe_module.STATS])
        return np.asarray(logits), {
            k: np.asarray(v) for k, v in loads.items()
            if k.endswith("chip_tokens")}

    want, before = run(plain_model, plain)
    got, after = run(placed_model, placed)
    assert _rel(got, want) < 1e-5
    moved = 0
    for block in ("block_0", "block_1"):
        a, b = (p["encoder"][block]["moe"] for p in (plain, placed))
        order = [int(np.argmax(np.all(
            np.asarray(a["w_gate"]) == np.asarray(b["w_gate"][j]),
            axis=(1, 2)))) for j in range(16)]
        assert sorted(order) == list(range(16))
        moved += order != list(range(16))
        for name in ("w_up", "w_down"):
            np.testing.assert_array_equal(
                np.asarray(a[name])[order], np.asarray(b[name]))
        np.testing.assert_array_equal(
            np.asarray(a["router"]["kernel"])[:, order],
            np.asarray(b["router"]["kernel"]))
    assert moved
    for key_, loads in after.items():
        assert loads.sum() == before[key_].sum()
        assert loads.max() <= before[key_].max()
    assert builder.deployed_model(SIZES, MeshSpec(dp=CHIPS)).__class__ is (
        CausalLM)      # the file names no placement and no gain: none is made


def test_the_expert_stacks_gain_is_the_files(builder):
    """``init.expert_stack_gain`` multiplies the three stacked expert
    matrices where the weights are drawn and nothing else; without the key
    the model is the library's."""
    import flax.linen as nn

    from raydp_tpu.models import CausalLM

    ids = jnp.zeros((1, SEQ), jnp.int32)
    key = jax.random.PRNGKey(6)
    gained = builder.deployed_model(
        {**SIZES, "init": {**SIZES["init"], "expert_stack_gain": 4.0}})
    assert gained.__class__ is not CausalLM
    assert gained.cfg == builder.model_config(SIZES)
    # One program for both draws: the same key, so the same numbers.
    plain, got = (_flat(nn.unbox(v)["params"]) for v in jax.jit(
        lambda k, x: (CausalLM(gained.cfg).init(k, x), gained.init(k, x))
    )(key, ids))
    assert got.keys() == plain.keys()
    stacks = [k for k in plain if k.split("/")[-1] in (
        "w_gate", "w_up", "w_down")]
    assert len(stacks) == 2 * 3
    for k in plain:
        np.testing.assert_array_equal(
            np.asarray(got[k]),
            np.asarray(plain[k]) * (4.0 if k in stacks else 1.0), err_msg=k)


# ---------------------------------------------------- the vocabulary's lookup

def test_the_lookup_over_the_axis_is_the_plain_one(eight_cpu_devices):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = MeshSpec(dp=CHIPS).build()
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 32, (CHIPS, 6)).astype(np.int32))
    weight = jnp.asarray(rng.standard_normal((CHIPS, 6, 8)).astype(np.float32))
    spread = jax.device_put(table, NamedSharding(mesh, P("dp")))
    ids_d = jax.device_put(ids, NamedSharding(mesh, P("dp")))

    def over(t):
        return jnp.sum(embed_over(t, ids_d, mesh, "dp") * weight)

    def plain(t):
        return jnp.sum(t[ids] * weight)

    np.testing.assert_array_equal(
        np.asarray(embed_over(spread, ids_d, mesh, "dp")),
        np.asarray(table[ids]))
    np.testing.assert_allclose(
        np.asarray(jax.jit(jax.grad(over))(spread)),
        np.asarray(jax.grad(plain)(table)), atol=1e-6)
    with pytest.raises(ValueError, match="chips of axis"):
        embed_over(table[:30], ids_d, mesh, "dp")


# ------------------------------------------------ the block and its gauges

def test_the_factory_writes_down_the_published_block():
    cfg = mellum2_12b_a2_5b()
    assert cfg.kinds == ("window", "window", "window", "attention") * 7
    assert set(cfg.ffn_kinds) == {"moe"}
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.vocab_size, cfg.n_layers) == (2304, 32, 4, 128, 98304, 28)
    assert (cfg.n_experts, cfg.top_k, cfg.d_expert) == (64, 8, 896)
    win, yarn = cfg.window, cfg.rope_yarn
    assert (win.window, win.n_heads, win.rope_theta, win.rotary_dim) == (
        1024, 32, 500000.0, None)
    assert (yarn.factor, yarn.original_max_len, yarn.beta_fast,
            yarn.beta_slow) == (16.0, 8192, 32.0, 1.0)
    assert yarn.stretch == pytest.approx(1.2772588722239782)
    assert cfg.rope_theta == 500000.0 and cfg.rotary_dim is None
    assert not cfg.qk_norm and not cfg.tie_head and not cfg.use_bias
    moe = cfg.moe_config()
    assert (moe.scoring, moe.normalize_gates, moe.selection_bias,
            moe.shared_experts, moe.gate_scale) == (
        "softmax", True, False, 0, 1.0)
    assert (moe.aux_loss_weight, moe.z_loss_weight) == (0.0, 0.0)
    # No axis named: every expert on the chip, no collective.
    assert moe.exchange_chips == 1 and cfg.chips_along(cfg.state_axis) == 1
    assert mellum2_12b_a2_5b(n_layers=4).kinds == (
        "window", "window", "window", "attention")


def test_the_built_steps_gauges(group):
    built = group["built"]
    assert built["moe/exchange_chips"] == CHIPS
    assert (built["moe/experts_routed"], built["moe/experts_held"]) == (16, 4)
    # 256 tokens x 4 experts a token = 1,024 pairs a layer; a chip's
    # expert path runs over 1.5 x a quarter of them.
    assert built["moe/compact_rows"] == moe_module.compact_rows(
        moe_module.MoEConfig(d_model=64, d_ff=32, n_experts=16, top_k=4,
                             held_experts=4), CHIPS * SEQ)
    # 2 layers x 3 passes (blocks checkpointed) x sent + received rows x
    # (64 features in and out in float32 + 4 gates and 4 choices).
    rows = 2 * 3 * (CHIPS * SEQ) // CHIPS
    assert built["moe/exchange_bytes_per_step"] == 2 * 3 * rows * (
        2 * 64 * 4 + 8 * 4)
    cfg = group["est"]._model.cfg.moe_config()
    assert moe_module.exchange_bytes(cfg, CHIPS * SEQ) == rows * (
        2 * 64 * 4 + 8 * 4)
    assert group["pairs"] == 2 * CHIPS * SEQ * 4
    assert 1.0 <= group["chip_load"] < 1.5 and group["overflow"] == 0.0
