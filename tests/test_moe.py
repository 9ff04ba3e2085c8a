"""MoE tests: routing conservation, single-expert equivalence to a dense
FFN, no token lost under extreme imbalance, aux loss, expert-sharded
execution on the mesh. The cases that run the expert path run it for both
forms of expert (``FORMS``: SwiGLU, and the ungated relu² that has no
``w_gate``)."""
import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
import pytest

from raydp_tpu.models.moe import (
    EXPERT_FORMS,
    STATS,
    MoEConfig,
    MoELayer,
    moe_aux_loss,
    tiny_moe,
)
from raydp_tpu.parallel import MeshSpec


def _tokens(t=32, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((t, d)).astype(np.float32))


def _init(layer, x):
    """Parameters alone: what init sowed would be summed into an apply's."""
    return {"params": nn.unbox(layer.init(jax.random.PRNGKey(0), x))["params"]}


FORMS = sorted(EXPERT_FORMS)


def _leaves(form, *more):
    """The leaves a case compares: the router, the form's stacked
    matrices, and what the case adds."""
    return ["router", *EXPERT_FORMS[form], *more]


def _hidden(p, x):
    """``[T, E, F]``: every token through the first half of every expert
    whose stacked weights ``p`` holds, whatever their form."""
    up = jnp.einsum("td,edf->tef", x, p["w_up"])
    if "w_gate" in p:
        return jax.nn.silu(jnp.einsum("td,edf->tef", x, p["w_gate"])) * up
    return jnp.square(jax.nn.relu(up))


def _every_expert_masked(params, x, top_k):
    """The layer written the plain way: every token through every expert,
    times the router's probabilities where they are among the k largest."""
    p = params["params"]
    probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    # Ties (a zero router) go to the lowest indices, as lax.top_k's do.
    rank = jnp.argsort(jnp.argsort(-probs, axis=-1, stable=True), axis=-1)
    weights = jnp.where((probs >= kth) & (rank < top_k), probs, 0.0)
    return jnp.einsum("tef,efd,te->td", _hidden(p, x), p["w_down"], weights)


@pytest.mark.parametrize("form", FORMS)
def test_single_expert_equals_dense_ffn(form):
    """E=1, k=1: the MoE must reduce to a plain FFN of the expert's form
    with gate weight exactly 1 (softmax over one expert)."""
    cfg = tiny_moe(n_experts=1, top_k=1, expert_form=form)
    x = _tokens(16, cfg.d_model)
    layer = MoELayer(cfg)
    params = _init(layer, x)
    out, _ = layer.apply(params, x, mutable=["losses", STATS])

    p = params["params"]
    assert set(p) == {"router", *EXPERT_FORMS[form]}
    if form == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"][0]) * (x @ p["w_up"][0])
    else:
        h = jnp.maximum(x @ p["w_up"][0], 0.0) ** 2
    want = h @ p["w_down"][0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("form", FORMS)
def test_topk_dispatch_conservation(form):
    """Every token reaches exactly top_k experts, weighted by its k
    largest router probabilities as they are: the layer equals "every
    expert on every token, masked", and the expert counts sum to T·k."""
    cfg = tiny_moe(n_experts=4, top_k=2, expert_form=form)
    x = _tokens(24, cfg.d_model, seed=1)
    layer = MoELayer(cfg)
    params = _init(layer, x)
    out, state = layer.apply(params, x, mutable=["losses", STATS])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_every_expert_masked(params, x, 2)),
        atol=1e-5,
    )
    counts = np.asarray(state[STATS]["expert_tokens"])
    assert counts.sum() == 24 * 2


@pytest.mark.parametrize("form", FORMS)
def test_no_token_is_lost_when_every_token_picks_the_same_experts(form):
    """A zero router sends every token to experts 0 and 1 (ties go to the
    lowest index): twelve times their fair share. No capacity, so every
    token still gets both experts' outputs."""
    cfg = tiny_moe(n_experts=4, top_k=2, expert_form=form)
    x = _tokens(48, cfg.d_model, seed=2)
    layer = MoELayer(cfg)
    params = _init(layer, x)
    params["params"]["router"]["kernel"] = jnp.zeros_like(
        params["params"]["router"]["kernel"]
    )
    out, state = layer.apply(params, x, mutable=["losses", STATS])
    counts = np.asarray(state[STATS]["expert_tokens"])
    np.testing.assert_array_equal(counts, [48, 48, 0, 0])
    want = _every_expert_masked(params, x, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert (np.abs(np.asarray(out)).sum(axis=-1) > 1e-6).all()


def test_aux_loss_sown():
    cfg = tiny_moe()
    x = _tokens(16, cfg.d_model)
    layer = MoELayer(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)
    _, state = layer.apply(params, x, mutable=["losses"])
    aux = moe_aux_loss(state)
    # Switch aux loss is ≥ 1 at uniform routing, scaled by weight.
    assert float(aux) > 0.0


def test_expert_sharded_on_mesh(eight_cpu_devices):
    """Experts sharded over dp + expert FFN over tp must match the
    single-device result."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from raydp_tpu.models.transformer import param_shardings

    cfg = tiny_moe(n_experts=4, top_k=2)
    x = _tokens(32, cfg.d_model, seed=3)
    layer = MoELayer(cfg)
    params = _init(layer, x)
    want, _ = layer.apply(params, x, mutable=["losses"])

    mesh = MeshSpec(dp=4, tp=2).build()
    _, shardings = param_shardings(
        layer, mesh, x,
        rules=(("expert", "dp"), ("embed", None), ("mlp", "tp")),
    )
    params_sh = jax.device_put(params, {"params": shardings["params"]})
    assert params_sh["params"]["w_up"].sharding.spec[0] == "dp"
    xd = jax.device_put(x, NamedSharding(mesh, P("dp")))

    @jax.jit
    def run(p, x):
        out, _ = layer.apply(p, x, mutable=["losses"])
        return out

    got = run(params_sh, xd)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
    )


def test_moe_block_trains():
    """A TransformerBlock whose FFN is routed (attention + MoE) takes
    gradient steps and the combined task+aux loss decreases."""
    import optax
    from raydp_tpu.models.transformer import (
        TransformerBlock,
        tiny_transformer,
    )

    block = TransformerBlock(tiny_transformer(
        d_model=32, n_heads=4, dtype=jnp.float32, ffn="moe", n_experts=4,
        top_k=2, d_expert=64,
    ))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 32)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((4, 8, 32)).astype(np.float32))
    params = nn.unbox(block.init(jax.random.PRNGKey(0), x))
    tx = optax.adam(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def loss_fn(p):
            out, state = block.apply(p, x, mutable=["losses"])
            return jnp.mean((out - y) ** 2) + moe_aux_loss(state)

        l, g = jax.value_and_grad(loss_fn)(params)
        u, opt2 = tx.update(g, opt)
        return optax.apply_updates(params, u), opt2, l

    losses = []
    for _ in range(20):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses


def test_moe_classifier_through_estimator(eight_cpu_devices):
    """Expert parallelism at the product level: MoEClassifier trains via
    JAXEstimator.fit with expert weights sharded over dp (the ep axis)
    and the Switch aux loss in the objective."""
    import jax.tree_util as jtu
    import optax
    import pandas as pd

    from raydp_tpu.models import MoEClassifier
    from raydp_tpu.models.moe import MoEConfig
    from raydp_tpu.models.transformer import tiny_transformer
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator

    SEQ, VOCAB = 16, 64
    rng = np.random.default_rng(0)
    ids = rng.integers(10, VOCAB, size=(512, SEQ))
    pos = rng.random(512) < 0.5
    ids[pos, rng.integers(0, SEQ, pos.sum())] = 7
    pdf = pd.DataFrame({f"t{i}": ids[:, i] for i in range(SEQ)})
    pdf["label"] = pos.astype(np.int64)

    cfg = tiny_transformer(
        max_len=SEQ, vocab_size=VOCAB, dropout_rate=0.0, n_layers=2
    )
    moe = MoEConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=4, top_k=1,
    )
    est = JAXEstimator(
        model=MoEClassifier(cfg=cfg, moe=moe, num_classes=2),
        optimizer=optax.adam(3e-4),
        loss="softmax_ce",
        num_epochs=3,
        batch_size=64,
        feature_columns=[f"t{i}" for i in range(SEQ)],
        label_column="label",
        feature_dtype=np.int32,
        label_dtype=np.int32,
        mesh=MeshSpec(dp=2, tp=2),
        aux_losses=True,
        seed=0,
        shuffle=False,
    )
    history = est.fit_on_df(pdf)
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    # expert tensors sharded over the ep(dp) axis
    expert_leaves = [
        (jtu.keystr(path), x)
        for path, x in jtu.tree_leaves_with_path(est._state.params)
        if "w_up" in jtu.keystr(path) or "w_down" in jtu.keystr(path)
    ]
    assert expert_leaves
    assert all(
        "dp" in str(x.sharding.spec) for _, x in expert_leaves
    ), [str(x.sharding.spec) for _, x in expert_leaves]
    # the sown collections were stripped from trainable state
    assert "losses" not in est._state.params
    assert STATS not in est._state.params


# --------------------------------------------- the exchange on a mesh axis

CHIPS = 4


def _skewed(cfg, x, params):
    """The layer's parameters with the router pushed toward chip 1's
    experts (a quarter of them): most pairs land on one chip."""
    held = cfg.n_experts // CHIPS
    push = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    push[:, held:2 * held] = np.abs(
        np.asarray(x).mean(axis=tuple(range(x.ndim - 1)))
    )[:, None] * 4.0
    router = params["params"]["router"]["kernel"] + jnp.sign(
        jnp.asarray(x).mean(axis=tuple(range(x.ndim - 1)))
    )[:, None] * push
    return {"params": {**params["params"], "router": {"kernel": router}}}


def _loss_and_grads(layer, params, x):
    def loss(p, x):
        y, mut = layer.apply(p, x, mutable=[STATS])
        weight = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
            y.shape
        )
        return jnp.sum(y * weight), (y, mut[STATS])

    return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(params, x)


@pytest.fixture(scope="module", params=FORMS)
def exchanged(request, eight_cpu_devices):
    """One layer of 16 experts (of either form), 4 a token, on one device
    and over the four chips of ``dp`` (4 experts a chip), under a routing
    skewed enough that chip 1's pairs exceed ``compact_rows`` (patched to
    128 of 512 pairs, the uniform share): outputs, gradients and
    statistics of both."""
    import dataclasses
    from unittest import mock

    from jax.sharding import NamedSharding, PartitionSpec as P
    from raydp_tpu.models import moe

    mesh = MeshSpec(dp=CHIPS).build()
    whole = tiny_moe(
        n_experts=16, top_k=4, normalize_gates=True, aux_loss_weight=0.0,
        z_loss_weight=0.0, expert_form=request.param,
    )
    over = dataclasses.replace(whole, expert_axis="dp", mesh=mesh)
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (CHIPS, 32, whole.d_model)
    ).astype(np.float32)) + 0.5
    params = _skewed(whole, x, _init(MoELayer(whole), x))
    want = _loss_and_grads(MoELayer(whole), params, x)
    spread = {"params": {
        name: jax.device_put(leaf, NamedSharding(
            mesh, P("dp") if name.startswith("w_") else P()
        )) for name, leaf in params["params"].items()
    }}
    xd = jax.device_put(x, NamedSharding(mesh, P("dp")))

    def rows(cfg, n_tokens):
        pairs = n_tokens * cfg.top_k
        return pairs if cfg.held == cfg.n_experts else pairs // CHIPS

    with mock.patch.object(moe, "compact_rows", rows):
        got = _loss_and_grads(MoELayer(over), spread, xd)
        # The guide's tie: each chip's share layer alone, on one device.
        shares = [
            MoELayer(dataclasses.replace(
                whole, first_expert=c * 4, held_experts=4
            )).apply(
                {"params": {
                    name: leaf[c * 4:(c + 1) * 4] if name.startswith("w_")
                    else leaf for name, leaf in params["params"].items()
                }}, x, mutable=[STATS],
            )[0] for c in range(CHIPS)
        ]
    return {"want": want, "got": got, "shares": shares,
            "form": request.param}


def test_the_exchange_drops_no_pair_under_skew(exchanged):
    """(ii) The layer over the mesh axis equals the one-device layer with
    every expert, forward, while one chip's pairs exceed its compact rows:
    the guard's branch is taken there (and only there) and no pair is
    lost."""
    (_, (want, _)), (_, (got, stats)) = exchanged["want"], exchanged["got"]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    chips = np.asarray(stats["chip_tokens"])
    assert chips.sum() == 4 * 32 * 4 and chips[1] > 128 > chips.max(
        initial=0, where=np.arange(CHIPS) != 1
    )
    assert float(stats["overflow"]) == 1.0
    np.testing.assert_array_equal(
        chips, np.asarray(stats["expert_tokens"]).reshape(CHIPS, -1).sum(1)
    )


@pytest.mark.parametrize("exchanged,leaf", [
    (form, leaf) for form in FORMS for leaf in _leaves(form, "tokens")
], indirect=["exchanged"])
def test_the_exchange_gives_the_one_device_gradients(exchanged, leaf):
    """(ii) Every gradient through the two collectives and the guard's
    overflow branch equals the one-device layer's; an ungated expert has
    no ``w_gate`` to have one."""
    (want_p, want_x), _ = exchanged["want"]
    assert set(want_p["params"]) == set(_leaves(exchanged["form"]))
    (got_p, got_x), _ = exchanged["got"]
    want, got = (want_x, got_x) if leaf == "tokens" else (
        jax.tree_util.tree_leaves(want_p["params"][leaf])[0],
        jax.tree_util.tree_leaves(got_p["params"][leaf])[0],
    )
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4
    )


def test_the_four_chips_parts_add_up_to_the_uncut_layer(exchanged):
    """(iii) What each chip's share layer gives alone sums to the uncut
    layer, which is what the reduce-scatter adds up."""
    _, (want, _) = exchanged["want"]
    np.testing.assert_allclose(
        np.asarray(sum(exchanged["shares"])), np.asarray(want),
        atol=2e-5, rtol=2e-5,
    )
    assert float(jnp.max(jnp.abs(exchanged["shares"][0] - want))) > 1e-2


# ------------------------------------------- a share's token-side sums

def _routed(expert, first, held, rows):
    """``expert`` ``[T, k]`` sorted as ``moe._sorted_experts`` sorts a
    share's pairs: ``live`` ``[T, k]``, ``inverse`` ``[T·k]`` as the parent
    reads it (cut to ``rows - 1``), ``runs`` as ``moe._experts`` makes
    them, and the live pairs."""
    t, k = expert.shape
    pairs = jnp.arange(t * k, dtype=jnp.int32)
    key = jnp.asarray(expert, jnp.int32).reshape(-1) - first
    live = (key >= 0) & (key < held)
    key = jnp.where(live, key, held)
    _, order = jax.lax.sort_key_val(key, pairs)
    _, inverse = jax.lax.sort_key_val(order, pairs)
    counts = jnp.bincount(key, length=held + 1)[:held]
    ends = jnp.minimum(jnp.cumsum(counts), rows).astype(jnp.int32)
    live = live.reshape(t, k)
    return live, jnp.minimum(inverse, rows - 1), (
        jnp.where(live, inverse.reshape(t, k), -1), ends
    ), int(counts.sum())


def _token_sum_cases():
    """``{case: (expert [T, k], first, held, rows or None for the live
    pairs, what the case must show)}``: eight experts, two a token, the
    share holds experts 2 and 3; token tiles of 8."""
    rng = np.random.default_rng(11)
    drawn = np.stack([rng.permutation(8)[:2] for _ in range(32)])
    every = drawn.copy()
    every[0] = (3, 2)                          # both pairs live
    every[1] = (0, 7)                          # none
    quiet = drawn.copy()
    quiet[8:16] = (0, 1)                       # tile 1: no live row
    absent = np.where(drawn == 3, 4, drawn)    # expert 3: no row
    long = np.where(np.arange(32)[:, None] % 4 > 0, (2, 5), drawn)
    return {
        "a_token_with_every_pair_live_and_one_with_none":
            (every, 2, 2, 24, lambda live: live[0].all() and not live[1].any()),
        "a_token_tile_with_no_live_row":
            (quiet, 2, 2, 24, lambda live: not live[8:16].any()),
        "an_expert_with_no_row":
            (absent, 2, 2, 24, lambda live: live.any()),
        "live_pairs_exactly_the_rows": (drawn, 2, 2, None, None),
        "live_pairs_over_the_rows": (drawn, 2, 2, -5, None),
        "rows_behind_the_live_pairs_are_not_numbers":
            (drawn, 2, 2, 40, None),
        "pairs_not_a_multiple_of_the_chunk":
            (drawn[:21], 2, 2, 17, None),
        "runs_longer_than_a_chunk":
            (long, 2, 2, 48, lambda live: live[:, 0].sum() >= 24),
    }


@pytest.mark.parametrize("gated", [True, False], ids=["gates", "ones"])
@pytest.mark.parametrize("case", sorted(_token_sum_cases()))
def test_a_shares_token_sums_are_the_parents_formula(case, gated, monkeypatch):
    """``ops/rows_to_tokens.py`` against ``rows[inverse]``, the mask and
    the float32 gated sum over k, on bfloat16 rows and float32 gates: to
    one bfloat16 step of the output. With more live pairs than rows the
    result is the formula's over the rows there are (the guard discards
    the layer-step), finite and read in bounds."""
    from raydp_tpu.ops import rows_to_tokens as op

    monkeypatch.setattr(op, "TOKEN_TILE", 8)
    monkeypatch.setattr(op, "MAX_CHUNK", 16)
    expert, first, held, rows, shows = _token_sum_cases()[case]
    t, k = expert.shape
    live, inverse, runs, n_live = _routed(expert, first, held, 10 ** 6)
    rows = n_live if rows is None else n_live + rows if rows < 0 else rows
    live, inverse, runs, _ = _routed(expert, first, held, rows)
    assert shows is None or shows(np.asarray(live))
    assert (n_live > rows) == (case == "live_pairs_over_the_rows")
    rng = np.random.default_rng(12)
    src = rng.standard_normal((rows, 32)).astype(np.float32)
    src[min(n_live, rows):] = np.nan           # what no group wrote
    src = jnp.asarray(src, jnp.bfloat16)
    gate = jnp.asarray(rng.random((t, k)), jnp.float32)
    pairs = src[inverse].reshape(t, k, -1).astype(jnp.float32)
    inside = live & (runs[0] < rows)
    pairs = jnp.where(inside[..., None], pairs, 0)
    want = jnp.sum(pairs * (gate[..., None] if gated else 1.0), axis=1)
    got = jax.jit(op.rows_to_tokens)(src, gate if gated else None, *runs)
    assert got.shape == (t, 32) and got.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2 ** -8, atol=1e-6
    )
    if case == "live_pairs_exactly_the_rows":
        # ... and the layer's two places call it: the same numbers.
        from raydp_tpu.models.moe import combine_rows, take_rows

        order = jnp.argsort(inverse)[:rows]
        assert jnp.array_equal(
            combine_rows(src, gate, order, inverse, live, runs),
            op.rows_to_tokens(src, gate, *runs),
        )
        x = jnp.zeros((t, 32), jnp.bfloat16)
        _, pull = jax.vjp(
            lambda x: take_rows(x, order, inverse, k, live, runs), x)
        assert jnp.array_equal(
            pull(src)[0], op.rows_to_tokens(src, None, *runs))


@pytest.fixture(scope="module", params=FORMS)
def compact_share(request):
    """A share (experts 2 and 3 of 8, of either form, two a token) whose
    expert path runs over 48 of its 128 pairs, so that both token-side
    sums are the kernel's: the layer's output and gradients, and the same
    of the dense formula restricted to the held experts."""
    from unittest import mock

    from raydp_tpu.models import moe

    cfg = tiny_moe(
        n_experts=8, top_k=2, first_expert=2, held_experts=2,
        aux_loss_weight=0.0, z_loss_weight=0.0, expert_form=request.param,
    )
    layer = MoELayer(cfg)
    x = _tokens(t=64)
    params = _init(layer, x)

    def dense(p, x):
        p = p["params"]
        probs = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
        _, chosen = jax.lax.top_k(probs, 2)
        picked = jax.nn.one_hot(chosen, 8).sum(axis=1)[:, 2:4]
        return jnp.einsum(
            "tef,efd,te->td", _hidden(p, x), p["w_down"],
            probs[:, 2:4] * picked)

    def both(fn):
        def loss(p, x):
            y = fn(p, x)
            return jnp.sum(y * jnp.cos(jnp.arange(y.size)).reshape(y.shape)), y
        return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(params, x)

    with mock.patch.object(moe, "compact_rows", lambda cfg, n: 48):
        text = str(jax.make_jaxpr(jax.grad(
            lambda p, x: layer.apply(p, x, mutable=[STATS])[0].sum(),
            argnums=(0, 1)))(params, x))
        got = both(lambda p, x: layer.apply(p, x, mutable=[STATS])[0])
    return {"got": got, "want": both(dense), "jaxpr": text}


@pytest.mark.parametrize("compact_share,leaf", [
    (form, leaf) for form in FORMS
    for leaf in _leaves(form, "output", "tokens")
], indirect=["compact_share"])
def test_a_compact_shares_gradients_are_the_dense_formulas(compact_share, leaf):
    """The share layer through both kernel calls (the way back to tokens,
    forward; the cotangent of the way there, backward) against
    ``jax.grad`` of every token through every held expert."""
    ((got_p, got_x), got_y) = compact_share["got"]
    ((want_p, want_x), want_y) = compact_share["want"]
    assert "rows_to_tokens" in compact_share["jaxpr"]
    got, want = {"output": (got_y, want_y), "tokens": (got_x, want_x)}.get(
        leaf) or (
        jax.tree_util.tree_leaves(got_p["params"][leaf])[0],
        jax.tree_util.tree_leaves(want_p["params"][leaf])[0],
    )
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-4
    )


def _arrays_outside_the_guard(jaxpr, found):
    """Every equation's outputs with the primitive's name, through calls
    and custom rules but neither into a ``cond``'s branches (the guard's
    ``T·k``-row path) nor into a kernel's body."""
    for eqn in jaxpr.eqns:
        found.extend((eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                     if hasattr(v.aval, "shape"))
        if eqn.primitive.name in ("cond", "pallas_call"):
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _arrays_outside_the_guard(inner, found)
    return found


@pytest.mark.parametrize("which", ["share", "whole", "a_share_of_many"])
def test_no_array_of_all_the_pairs_rows_is_left_on_a_shares_compact_path(
    which,
):
    """Forward and backward of a share whose expert path runs over 48 of
    128 pairs: outside the guard's branches no ``[T·k, D]`` array and no
    gather of ``T·k`` rows. The layer that holds every expert still has
    both (the walk finds what it looks for), and so does a share that
    holds more than three experts for each of a token's two: the kernel's
    time goes with the experts held (``ops/rows_to_tokens.pays``)."""
    from unittest import mock

    from raydp_tpu.models import moe

    cfg = tiny_moe(n_experts=16, top_k=2, dtype=jnp.bfloat16, **{
        "share": dict(first_expert=2, held_experts=6), "whole": {},
        "a_share_of_many": dict(first_expert=2, held_experts=7),
    }[which])
    layer, x = MoELayer(cfg), _tokens(t=64)
    params = _init(layer, x)
    with mock.patch.object(moe, "compact_rows", lambda cfg, n: (
            48 if cfg.held < cfg.n_experts else n * cfg.top_k)):
        closed = jax.make_jaxpr(jax.grad(
            lambda p, x: layer.apply(p, x, mutable=[STATS])[0].astype(
                jnp.float32).sum(), argnums=(0, 1)))(params, x)
    found = _arrays_outside_the_guard(closed.jaxpr, [])
    wide = [(name, shape) for name, shape in found
            if shape in ((128, 32), (64, 2, 32))]
    assert bool(wide) == (which != "share"), wide
    assert (("pallas_call", (64, 32)) in found) == (which == "share")


@pytest.mark.parametrize("layout", ["whole", "share", "over_a_mesh_axis"])
def test_the_token_sum_gauges_say_where_the_kernel_runs(
    layout, eight_cpu_devices,
):
    """``moe/token_sum_rows`` and ``moe/token_sum_layers`` beside
    ``moe/compact_rows``: ``T·k`` and 0 where every expert is held, ``C``
    and the routed layers for a share and for a chip of a mesh axis."""
    import dataclasses

    from raydp_tpu.models import moe
    from raydp_tpu.models.transformer import TransformerConfig
    from raydp_tpu.utils.profiling import metrics

    cfg = MoEConfig(n_experts=64, top_k=8)
    if layout == "share":
        cfg = dataclasses.replace(cfg, held_experts=16)
    if layout == "over_a_mesh_axis":
        cfg = dataclasses.replace(
            cfg, expert_axis="dp", mesh=MeshSpec(dp=CHIPS).build())

    class Model:
        pass

    model = Model()
    model.moe = cfg
    model.cfg = TransformerConfig(
        n_layers=4, ffn="moe", n_experts=64, top_k=8)
    assert model.cfg.ffn_kinds.count("moe") == 4
    moe.report(model, 16384)
    want = (131072, 0) if layout == "whole" else (49152, 4)
    assert metrics.gauge_value("moe/compact_rows") == want[0]
    assert (metrics.gauge_value("moe/token_sum_rows"),
            metrics.gauge_value("moe/token_sum_layers")) == want


def test_the_chips_readings_run_at_the_tests_shapes():
    """``scripts/moe_token_sum_on_chip.py`` is where the chip is asked what
    the three forms of the sum cost; its parts run here so that they stay
    runnable: the parent's form, the one ``C``-row gather with ``tgmm``
    and the kernel give the float32 formula's numbers."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "moe_token_sum_on_chip.py")
    spec = importlib.util.spec_from_file_location("moe_token_sum", path)
    on_chip = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(on_chip)
    a = on_chip.draw(7, 512, 4, 16, 4, 128)
    assert a["src"].shape == (1024, 128) and a["src"].dtype == jnp.bfloat16
    for gated in (True, False):
        errors = on_chip.errors(a, gated)
        assert set(errors) == {"parent", "gathered", "runs"}
        assert max(errors.values()) < 2 ** -7, errors
    assert on_chip.main(["--skip-cliff"]) == 3        # no TPU here


# sha256 and length of ``str(jax.make_jaxpr(grad of the layer's sum))`` for
# the configurations below, by this same function. ``whole`` and
# ``share_of_many`` as the PARENT of PR 53 (commit e58c280) and the parent
# of PR 54 (commit d5f323b) printed them: with no axis named, a layer that
# holds every expert and a share whose token-side sums stay gathers (seven
# held experts for a token's two: ``ops/rows_to_tokens.pays``) trace to the
# programs they were. ``share`` was the parent's too (382419, 94dd0429…)
# until PR 54 moved it: its compact path's two token-side sums are the
# kernel of ``ops/rows_to_tokens.py``, whose body the text holds.
PARENT_JAXPRS = {
    "whole": (188756, "ca557149f039b8c526c3f30843b797f349f9cac7bf62aa2c897fb7"
                      "4dc8c374ee"),
    "share": (423386, "cb6158ed992e7c06027c51b97da40496bc7f62122d903b1005688a"
                      "a0dcfd041a"),
    "share_of_many": (382471, "2588e9522acf575e439f818fbd77fa57eb710fdcf135c4"
                              "c74cc0d15d1c2543fa"),
}


@pytest.mark.parametrize("which", sorted(PARENT_JAXPRS))
def test_without_an_axis_the_layer_traces_to_the_parents_program(which):
    """(v) One whole configuration and two shares (compact rows patched to
    32 of their 128 pairs, so the guard is in the program). PR 54 took the
    ``share`` pin again (the kernel is in its text) and added
    ``share_of_many``, which is the parent's."""
    import hashlib
    from unittest import mock

    from raydp_tpu.models import moe

    cfg = {
        "whole": tiny_moe(n_experts=8, top_k=2),
        "share": tiny_moe(
            n_experts=8, top_k=2, first_expert=2, held_experts=2,
            normalize_gates=True, dtype=jnp.bfloat16,
        ),
        "share_of_many": tiny_moe(
            n_experts=16, top_k=2, first_expert=2, held_experts=7,
            normalize_gates=True, dtype=jnp.bfloat16,
        ),
    }[which]
    layer = MoELayer(cfg)
    x = jnp.zeros((2, 32, 32), jnp.float32)
    variables = jax.eval_shape(
        lambda: nn.unbox(layer.init(jax.random.PRNGKey(0), x))
    )

    def loss(v, x):
        y, _ = layer.apply(v, x, mutable=[STATS, "losses"])
        return jnp.sum(y.astype(jnp.float32))

    def rows(cfg, n_tokens):
        return n_tokens * cfg.top_k if cfg.held == cfg.n_experts else 32

    with mock.patch.object(moe, "compact_rows", rows):
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            variables, x
        ))
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (
        PARENT_JAXPRS[which]
    )
    assert ("rows_to_tokens" in text) == (which == "share")


def test_the_exchange_wants_every_expert_and_an_even_split(
    eight_cpu_devices,
):
    import dataclasses

    mesh = MeshSpec(dp=CHIPS).build()
    for bad in (dict(held_experts=2), dict(n_experts=6, top_k=2)):
        cfg = dataclasses.replace(
            tiny_moe(n_experts=8, top_k=2, expert_axis="dp", mesh=mesh),
            **bad,
        )
        with pytest.raises(ValueError, match="chips of axis"):
            cfg.exchange_chips
    assert tiny_moe(n_experts=8, top_k=2).exchange_chips == 1
