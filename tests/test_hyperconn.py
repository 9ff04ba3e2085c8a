"""Manifold-constrained hyper-connections (``models/hyperconn.py``) at tiny
sizes on the CPU, float32: Sinkhorn-Knopp's result is doubly stochastic
and the clamp holds, the mappings and both mixings against plain numpy
written per token, the streams' start and end, a bfloat16 carrier against
float32, the gauges, and the older families' blocks untouched."""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM, bert_base, granite_h_micro, olmoe
from raydp_tpu.models import hyperconn, stats
from raydp_tpu.models.hyperconn import HyperConfig, HyperMaps, Maps
from raydp_tpu.models.latent import LatentConfig
from raydp_tpu.models.transformer import TransformerBlock, xing4_0

N, D, B, S = 4, 16, 2, 6
# The draws the benchmark's configuration widens the mappings' init to
# (``benchmark/configs/xing4_0_29b_a4b.json``); the library starts at the
# papers' (``HyperConfig()``).
WIDE = HyperConfig(phi_std=0.5, bias_std=1.0)


def _streams(key=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), (B, N, S, D), dtype)


def _maps(x, cfg=WIDE, key=1, **params):
    module = HyperMaps(cfg, 1e-6)
    variables = nn.unbox(module.init(jax.random.PRNGKey(key), x))
    variables = {"params": {**variables["params"], **params}}
    maps, sown = module.apply(variables, x, mutable=[stats.STATS])
    return maps, variables["params"], sown[stats.STATS]


def _plain_maps(p, x, cfg, rounds=None):
    """Per token, as the papers write it: ``x`` [B, n, S, D] float64."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    n = cfg.streams
    phi = p["phi"].reshape(n * x.shape[-1], -1)
    pre = np.zeros((B, S, n))
    post = np.zeros((B, S, n))
    res = np.zeros((B, S, n, n))
    for b in range(B):
        for s in range(S):
            vec = x[b, :, s, :].reshape(-1)
            u = vec / np.sqrt((vec * vec).mean() + 1e-6)
            raw = u @ phi
            pre[b, s] = 1 / (1 + np.exp(-(
                p["alpha"][0] * raw[:n] + p["bias"][:n])))
            post[b, s] = 2 / (1 + np.exp(-(
                p["alpha"][1] * raw[n:2 * n] + p["bias"][n:2 * n])))
            m = np.exp(np.clip(
                p["alpha"][2] * raw[2 * n:] + p["bias"][2 * n:], *cfg.clamp
            )).reshape(n, n)
            for _ in range(cfg.sinkhorn_iters if rounds is None else rounds):
                m = m / (m.sum(axis=1, keepdims=True) + cfg.eps)
                m = m / (m.sum(axis=0, keepdims=True) + cfg.eps)
            res[b, s] = m
    return pre, post, res


# ------------------------------------------------------------- Sinkhorn

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_sinkhorn_gives_doubly_stochastic_matrices(seed):
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(seed), (N, N, 3, 5)))
    out = hyperconn.sinkhorn(m, 20, 1e-6)
    np.testing.assert_allclose(np.asarray(out.sum(axis=0)), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out.sum(axis=1)), 1.0, atol=1e-4)
    assert float(out.min()) > 0
    assert float(hyperconn.doubly_stochastic_error(out)) < 1e-4
    # One round leaves the rows off (the columns were normalised last).
    once = hyperconn.sinkhorn(m, 1, 1e-6)
    assert float(hyperconn.doubly_stochastic_error(once)) > 1e-2
    np.testing.assert_allclose(np.asarray(once.sum(axis=0)), 1.0, atol=1e-5)


def test_sinkhorn_is_rows_then_columns():
    m = np.array([[1.0, 2.0], [3.0, 4.0]])
    out = np.asarray(hyperconn.sinkhorn(jnp.asarray(m), 1, 0.0))
    rows = m / m.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(out, rows / rows.sum(axis=0, keepdims=True),
                               rtol=1e-6)


@pytest.mark.parametrize("value", [-1e4, -31.0, 31.0, 1e4])
def test_the_clamp_holds_at_thirty(value):
    """A bias far outside the clamp gives what +-30 gives: finite, and
    H_res still doubly stochastic."""
    x = _streams()
    cfg = HyperConfig()
    edge = float(np.clip(value, -30, 30))
    bias = lambda v: jnp.concatenate([  # noqa: E731
        jnp.zeros(2 * N), v * jnp.eye(N).reshape(-1)])
    got, _, _ = _maps(x, cfg, bias=bias(value), alpha=jnp.zeros(3))
    want, _, _ = _maps(x, cfg, bias=bias(edge), alpha=jnp.zeros(3))
    assert np.isfinite(np.asarray(got.res)).all()
    np.testing.assert_array_equal(np.asarray(got.res), np.asarray(want.res))
    np.testing.assert_allclose(np.asarray(got.res.sum(axis=0)), 1.0,
                               atol=1e-4)


# ------------------------------------------------------------- mappings

@pytest.mark.parametrize("key", [1, 2, 3])
def test_mappings_against_plain_numpy_per_token(key):
    x = _streams(key)
    cfg = WIDE
    maps, p, sown = _maps(x, cfg, key=key + 10,
                          alpha=jnp.asarray([0.3, 0.2, 0.4]))
    pre, post, res = _plain_maps(p, x, cfg)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(maps.pre), 0, -1), pre, rtol=2e-5)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(maps.post), 0, -1), post, rtol=2e-5)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(maps.res), (0, 1), (-2, -1)), res,
        rtol=2e-4, atol=1e-6)
    err = max(np.abs(res.sum(-1) - 1).max(), np.abs(res.sum(-2) - 1).max())
    assert float(sown["hc_res_err_max"]) == pytest.approx(err, abs=2e-6)


def test_the_init_reads_the_mean_and_keeps_the_streams():
    """The library's start, ``phi`` set to zero: H_pre 1/n, H_post 1,
    H_res near the identity (diagonal 0.87 at ``RES_DIAGONAL`` 3); with
    its near-zero ``phi`` within a hundredth of that."""
    x = _streams()
    assert (HyperConfig().phi_std, HyperConfig().bias_std) == (0.02, 0.0)
    assert (hyperconn.ALPHA_INIT, hyperconn.RES_DIAGONAL) == (0.01, 3.0)
    start, _, _ = _maps(x, HyperConfig())
    np.testing.assert_allclose(np.asarray(start.pre), 1 / N, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(start.post), 1.0, rtol=1e-2)
    cfg = HyperConfig(phi_std=0.0)
    maps, p, _ = _maps(x, cfg)
    assert p["phi"].shape == (N, D, 2 * N + N * N) and cfg.maps == 24
    np.testing.assert_allclose(np.asarray(p["alpha"]), 0.01)
    np.testing.assert_allclose(np.asarray(maps.pre), 1 / N, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(maps.post), 1.0, rtol=1e-6)
    diag = np.asarray(maps.res)[np.arange(N), np.arange(N)]
    np.testing.assert_allclose(diag, np.e ** 3 / (np.e ** 3 + 3), rtol=1e-4)
    # With the draw the streams are read unevenly.
    drawn, _, _ = _maps(x, WIDE)
    assert float(jnp.std(drawn.pre[:, 0, 0])) > 0.02


def test_a_wrong_stream_count_is_refused():
    with pytest.raises(ValueError, match="streams"):
        _maps(jnp.zeros((B, 3, S, D)))


# --------------------------------------------------------------- mixing

def test_read_and_write_against_plain_einsums():
    x, y = _streams(3), jax.random.normal(jax.random.PRNGKey(4), (B, S, D))
    maps, _, _ = _maps(x)
    pre = np.moveaxis(np.asarray(maps.pre, np.float64), 0, -1)     # [B,S,n]
    post = np.moveaxis(np.asarray(maps.post, np.float64), 0, -1)
    res = np.moveaxis(np.asarray(maps.res, np.float64), (0, 1), (-2, -1))
    xs = np.moveaxis(np.asarray(x, np.float64), 1, 2)              # [B,S,n,D]
    h = np.einsum("bsn,bsnd->bsd", pre, xs)
    np.testing.assert_allclose(np.asarray(hyperconn.read(x, maps)), h,
                               rtol=1e-5, atol=1e-6)
    out = np.einsum("bsij,bsjd->bsid", res, xs) + (
        post[..., None] * np.asarray(y, np.float64)[:, :, None])
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(hyperconn.write(x, y, maps)), 1, 2), out,
        rtol=1e-5, atol=1e-6)


def test_a_doubly_stochastic_write_keeps_the_streams_sum():
    x = _streams(5)
    maps, _, _ = _maps(x)
    kept = hyperconn.write(x, jnp.zeros((B, S, D)), maps)
    np.testing.assert_allclose(
        np.asarray(kept.sum(axis=1)), np.asarray(x.sum(axis=1)), atol=2e-4)


def test_identity_mappings_are_the_plain_residual():
    x, y = _streams(6), jax.random.normal(jax.random.PRNGKey(7), (B, S, D))
    ones = jnp.ones((N, B, S))
    eye = jnp.broadcast_to(jnp.eye(N)[:, :, None, None], (N, N, B, S))
    maps = Maps(ones / N, ones, eye)
    np.testing.assert_allclose(
        np.asarray(hyperconn.read(x, maps)), np.asarray(x.mean(axis=1)),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(hyperconn.write(x, y, maps)),
        np.asarray(x + y[:, None]), rtol=1e-6)


def test_streams_start_repeated_and_end_summed():
    e = jax.random.normal(jax.random.PRNGKey(8), (B, S, D))
    x = hyperconn.expand(e, N)
    assert x.shape == (B, N, S, D)
    for i in range(N):
        np.testing.assert_array_equal(np.asarray(x[:, i]), np.asarray(e))
    np.testing.assert_allclose(np.asarray(hyperconn.reduce(x)),
                               N * np.asarray(e), rtol=1e-6)


@pytest.mark.parametrize("fn", ["read", "write"])
def test_a_bfloat16_carrier_computes_in_float32(fn):
    x = _streams(9)
    y = jax.random.normal(jax.random.PRNGKey(10), (B, S, D))
    maps, _, _ = _maps(x)
    low = x.astype(jnp.bfloat16)
    args = (low, maps) if fn == "read" else (low, y.astype(jnp.bfloat16), maps)
    full = (low.astype(jnp.float32), maps) if fn == "read" else (
        low.astype(jnp.float32), y.astype(jnp.bfloat16).astype(jnp.float32),
        maps)
    got = getattr(hyperconn, fn)(*args)
    assert got.dtype == jnp.bfloat16
    want = getattr(hyperconn, fn)(*full)
    # One rounding of the float32 result, nothing more.
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want.astype(jnp.bfloat16)))


def test_mappings_of_bfloat16_streams_are_those_of_their_values():
    x = _streams(11).astype(jnp.bfloat16)
    low, p, _ = _maps(x)
    full, _, _ = _maps(x.astype(jnp.float32))
    assert low.res.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(low.res), np.asarray(full.res),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(low.pre), np.asarray(full.pre),
                               rtol=1e-6)


# ---------------------------------------------------------- in the stack

def test_a_hyper_connected_block_carries_streams():
    cfg = xing4_0(
        vocab_size=64, d_model=D, n_heads=2, n_layers=1, dense_layers=1,
        d_ff=32, max_len=32, dtype=jnp.float32, attention_impl="dense",
        latent=LatentConfig(
            q_rank=8, kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8),
    )
    block = TransformerBlock(cfg, "latent", "swiglu")
    x = _streams(12)
    variables = nn.unbox(block.init(jax.random.PRNGKey(0), x))
    out = block.apply(variables, x)
    assert out.shape == x.shape
    assert set(variables["params"]) == {
        "hc_attn", "hc_ffn", "ln_attn", "ln_mlp", "attn", "mlp_in", "mlp_out"}


@pytest.mark.parametrize("factory", [bert_base, olmoe, granite_h_micro])
def test_the_older_families_have_one_stream(factory):
    cfg = factory()
    assert cfg.hyper is None and cfg.latent is None
    assert "latent" not in cfg.kinds


def test_report_sets_the_gauges():
    from raydp_tpu.utils.profiling import metrics

    hyperconn.report(xing4_0(n_layers=5))
    assert metrics.gauge_value("hc/streams") == 4
    assert metrics.gauge_value("hc/sinkhorn_iters") == 20
    assert metrics.gauge_value("hc/sublayers") == 10
    hyperconn.report(olmoe())
    assert metrics.gauge_value("hc/streams") == 0
    assert metrics.gauge_value("hc/sublayers") == 0
    hyperconn.report_epoch({"hc_res_err_max": np.float32(0.25)})
    assert metrics.gauge_value("hc/res_row_sum_err_max") == 0.25
    hyperconn.report_epoch({"expert_tokens": np.ones(4)})
    assert metrics.gauge_value("hc/res_row_sum_err_max") == 0.25


# ------------------------------------------------------ step statistics

def test_each_sower_declares_how_its_statistic_merges():
    """``models/stats.py``: the residual path's error merges by the
    larger, the routed layers' counts by their sum, whatever the names."""
    a = {"hc_res_err_max": jnp.float32(0.1), "expert_tokens": jnp.ones(4)}
    b = {"hc_res_err_max": jnp.float32(0.3), "expert_tokens": jnp.ones(4)}
    merged = stats.merge(a, b)
    assert float(merged["hc_res_err_max"]) == pytest.approx(0.3)
    np.testing.assert_array_equal(np.asarray(merged["expert_tokens"]), 2.0)
    assert stats.declare("hc_res_err_max", jnp.maximum) == "hc_res_err_max"
    with pytest.raises(ValueError, match="another reduction"):
        stats.declare("hc_res_err_max", jnp.add)


def test_a_dense_stack_with_streams_reports_no_auxiliary_loss():
    """Two sublayers' errors merge into one value, and nothing of the
    routed layers' rides along."""
    from raydp_tpu.models import moe

    cfg = xing4_0(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, dense_layers=1,
        d_ff=32, max_len=16, attention_impl="dense",
        dtype=jnp.float32, hyper=WIDE,
        latent=LatentConfig(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=4,
                            v_dim=8),
    )
    model = CausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    _, sown = model.apply(
        {"params": variables["params"]}, ids, mutable=["losses", stats.STATS])
    step = moe.with_aux_loss(stats.step_stats(sown), sown)
    assert set(step) == {"hc_res_err_max"}
    assert step["hc_res_err_max"].shape == ()
    moe.report_epoch(step, 1)          # nothing of its own: no error
