"""Manifold-constrained hyper-connections (``models/hyperconn.py``) at tiny
sizes on the CPU, float32: Sinkhorn-Knopp's result is doubly stochastic
and the clamp holds, the mappings and both mixings against plain numpy
written per token, the streams' start and end, a bfloat16 carrier against
float32, the gauges, and the older families' blocks untouched; the one-pass
kernels of both mixings (``ops/stream_mix.py``, in the Pallas interpreter)
against the plain formula and its gradients, and a compiled step that holds
no float32 array of a stream's size under the mixings' scopes."""
import re

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
from raydp_tpu.ops import stream_mix

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


# ------------------------------------------------------- one-pass mixing

def _operands(n, tokens, width, dtype, key=20):
    """Streams, a sublayer's output, random mappings and two cotangents."""
    k = jax.random.split(jax.random.PRNGKey(key), 7)
    x = jax.random.normal(k[0], (B, n, tokens, width), dtype)
    y = jax.random.normal(k[1], (B, tokens, width), dtype)
    maps = Maps(
        jax.random.uniform(k[2], (n, B, tokens)),
        2 * jax.random.uniform(k[3], (n, B, tokens)),
        jax.random.uniform(k[4], (n, n, B, tokens)),
    )
    gh = jax.random.normal(k[5], (B, tokens, width))
    gx = jax.random.normal(k[6], (B, n, tokens, width))
    return x, y, maps, gh, gx


def _close(got, want, dtype):
    """Float32: the sums' order only. bfloat16: one rounding of an output
    (2^-8 of its size) and of the cotangent that went in."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("tokens", [64, 40])     # 40: a ragged last block
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_read_and_its_gradients(dtype, n, tokens):
    x, _, maps, gh, _ = _operands(n, tokens, 128, dtype)
    assert hyperconn.one_pass(dtype, 128)
    assert tokens % stream_mix.token_tile(tokens, 128, dtype) == tokens % 32
    got = hyperconn.read(x, maps)
    assert got.dtype == dtype
    x32 = x.astype(jnp.float32)
    _close(got, hyperconn.plain_read(x32, maps), dtype)

    def loss(fn, x, pre):
        h = fn(x, Maps(pre, maps.post, maps.res))
        return jnp.sum(h.astype(jnp.float32) * gh)

    gx, gpre = jax.grad(loss, (1, 2))(hyperconn.read, x, maps.pre)
    wx, wpre = jax.grad(loss, (1, 2))(hyperconn.plain_read, x32, maps.pre)
    assert gx.dtype == dtype and gpre.dtype == jnp.float32
    _close(gx, wx, dtype)
    _close(gpre, wpre, dtype)


@pytest.mark.parametrize("tokens", [64, 40])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_write_and_its_gradients(dtype, n, tokens):
    x, y, maps, _, g = _operands(n, tokens, 128, dtype)
    got = hyperconn.write(x, y, maps)
    assert got.dtype == dtype and got.shape == x.shape
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    _close(got, hyperconn.plain_write(x32, y32, maps), dtype)

    def loss(fn, x, y, post, res):
        out = fn(x, y, Maps(maps.pre, post, res))
        return jnp.sum(out.astype(jnp.float32) * g)

    got = jax.grad(loss, (1, 2, 3, 4))(
        hyperconn.write, x, y, maps.post, maps.res)
    want = jax.grad(loss, (1, 2, 3, 4))(
        hyperconn.plain_write, x32, y32, maps.post, maps.res)
    assert [a.dtype for a in got] == [dtype, dtype, jnp.float32, jnp.float32]
    assert [a.shape for a in got] == [a.shape for a in want]
    for a, b in zip(got, want):
        _close(a, b, dtype)


@pytest.mark.parametrize("fn", ["read", "write"])
def test_a_bfloat16_block_is_rounded_once(fn):
    """The kernels' forward is the float32 result rounded once, as the
    plain formula's: bit for bit but where the float32 sums, added in
    another order, fall on the other side of a rounding boundary."""
    x, y, maps, _, _ = _operands(4, 48, 256, jnp.bfloat16, key=21)
    args = (x, maps) if fn == "read" else (x, y, maps)
    got = np.asarray(getattr(hyperconn, fn)(*args), np.float32)
    want = np.asarray(getattr(hyperconn, "plain_" + fn)(*args), np.float32)
    assert np.mean(got == want) > 0.999
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7)


def test_the_mixings_differentiate_under_a_checkpoint():
    """As the blocks run them: both mixings around a sublayer inside
    ``jax.checkpoint``, every gradient that of the plain formula."""
    x, _, maps, _, g = _operands(4, 40, 128, jnp.float32, key=22)

    def sublayer(read, write, x, pre, post, res):
        m = Maps(pre, post, res)
        return jnp.sum(write(x, jnp.tanh(read(x, m)), m) * g)

    args = (x, maps.pre, maps.post, maps.res)
    got = jax.grad(jax.checkpoint(
        lambda *a: sublayer(hyperconn.read, hyperconn.write, *a)
    ), (0, 1, 2, 3))(*args)
    want = jax.grad(
        lambda *a: sublayer(hyperconn.plain_read, hyperconn.plain_write, *a),
        (0, 1, 2, 3))(*args)
    for a, b in zip(got, want):
        _close(a, b, jnp.float32)


@pytest.mark.parametrize("width,kernels", [(128, True), (96, False)])
def test_the_plain_formula_is_taken_for_a_width_no_kernel_tiles(
        width, kernels):
    x, y, maps, _, _ = _operands(4, 32, width, jnp.bfloat16, key=23)
    assert hyperconn.one_pass(x.dtype, width) is kernels
    assert not hyperconn.one_pass(jnp.int32, 128)
    text = str(jax.make_jaxpr(
        lambda x, y: hyperconn.write(x, y + hyperconn.read(x, maps), maps)
    )(x, y))
    assert ("pallas_call" in text) is kernels
    got, want = hyperconn.read(x, maps), hyperconn.plain_read(x, maps)
    if kernels:
        _close(got, want, x.dtype)
    else:
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32))


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


def _tiny_stack(width, n_layers=2):
    return xing4_0(
        vocab_size=64, d_model=width, n_heads=2, n_layers=n_layers,
        dense_layers=n_layers, d_ff=64, max_len=256, attention_impl="dense",
        hyper=WIDE,
        latent=LatentConfig(
            q_rank=8, kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8),
    )


def _float32_under_the_mixings(cfg, tokens=256):
    """Element counts of every float32 tensor an operation under a
    ``hc_*/pre|post`` scope reads or writes in the lowered gradient step of
    ``CausalLM(cfg)`` (bfloat16 carrier; StableHLO with its locations),
    and how many operations carry such a scope."""
    model = CausalLM(cfg)
    ids = jnp.zeros((1, tokens), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]

    def loss(params):
        logits, _ = model.apply(
            {"params": params}, ids, mutable=["losses", stats.STATS])
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def name(ref, depth=0):
        body = locs.get(ref, "")
        quoted = re.match(r'"([^"]*)"', body)
        if quoted:
            return quoted.group(1)
        inner = re.findall(r"#loc\d+", body)
        return name(inner[0], depth + 1) if inner and depth < 8 else ""

    scope = re.compile(r"block_\d+/hc_(attn|ffn)/(pre|post)/")
    sizes, scoped = [], 0
    for line in text.splitlines():
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if ref is None or not scope.search(name(ref.group(1))):
            continue
        scoped += 1
        sizes += [
            int(np.prod([int(v) for v in dims.split("x") if v] or [1]))
            for dims in re.findall(r"tensor<([\dx]*)xf32>", line)
        ]
    return sizes, scoped


def test_a_step_holds_no_float32_stream_under_the_mixings():
    """What the one-pass path is for: in the gradient step of a small
    stack at a width the kernels tile, no operation under ``hc_*/pre`` or
    ``hc_*/post`` touches a float32 tensor of a stream's size, forward,
    recomputed or backward (the largest is a block's coefficient columns).
    At a width they do not tile the same scan finds the plain formula's
    float32 streams, so it can tell."""
    tokens = 256
    sizes, scoped = _float32_under_the_mixings(_tiny_stack(128), tokens)
    assert scoped > 100 and sizes
    assert max(sizes) <= tokens * (N + N * N) < tokens * 128
    plain, _ = _float32_under_the_mixings(_tiny_stack(96), tokens)
    assert max(plain) == N * tokens * 96


def test_report_sets_the_gauges():
    from raydp_tpu.utils.profiling import metrics

    hyperconn.report(xing4_0(n_layers=5))
    assert metrics.gauge_value("hc/streams") == 4
    assert metrics.gauge_value("hc/sinkhorn_iters") == 20
    assert metrics.gauge_value("hc/sublayers") == 10
    assert metrics.gauge_value("hc/one_pass_sublayers") == 10
    hyperconn.report(olmoe())
    assert metrics.gauge_value("hc/streams") == 0
    assert metrics.gauge_value("hc/sublayers") == 0
    assert metrics.gauge_value("hc/one_pass_sublayers") == 0
    hyperconn.report_epoch({"hc_res_err_max": np.float32(0.25)})
    assert metrics.gauge_value("hc/res_row_sum_err_max") == 0.25
    hyperconn.report_epoch({"expert_tokens": np.ones(4)})
    assert metrics.gauge_value("hc/res_row_sum_err_max") == 0.25


@pytest.mark.parametrize("cfg,sublayers,one_pass", [
    (_tiny_stack(128, n_layers=3), 6, 6),
    (_tiny_stack(96), 4, 0),             # a width no kernel tiles
    (olmoe(), 0, 0), (bert_base(), 0, 0),
], ids=["tileable", "odd_width", "olmoe", "bert_base"])
def test_the_gauge_counts_the_sublayers_the_kernels_take(
        cfg, sublayers, one_pass, caplog):
    from raydp_tpu.utils.profiling import metrics

    with caplog.at_level("INFO", logger="raydp_tpu.models.hyperconn"):
        hyperconn.report(cfg)
    assert metrics.gauge_value("hc/sublayers") == sublayers
    assert metrics.gauge_value("hc/one_pass_sublayers") == one_pass
    if sublayers:
        path = "in one pass over the bfloat16" if one_pass else "plain formula"
        assert path in caplog.text
    else:
        assert "residual path" not in caplog.text


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
