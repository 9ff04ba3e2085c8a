"""``ops/kda.py``: the chunked gated delta rule with a decay per channel
against the token-by-token scan — output and the gradients of ``q``,
``k``, ``v``, ``g`` and ``beta`` — over chunk sizes, head widths and a
decay strong enough to overflow a factored form; the shapes it refuses;
the recurrence's two limits against closed forms (``beta -> 0``: a gated
linear attention; ``g = 0`` and ``beta = 1``: the plain delta rule); the
triangular inverse against ``numpy``; and the residuals a checkpoint's
policy keeps. The chunk-local step runs by both of its paths, the
``jax.numpy`` form and the Pallas kernels (in the interpreter here), and
so does the recurrence over chunk states (``_across`` in ``jax.numpy``,
the two state kernels); the shapes decide which, for both at once. Under
the kernels' rule the model's [b, s, h · d] arrays are read and written
in place: against the same kernels on slices of one segment at a time,
and nothing of the sequence's size moved in the traced gradient. Tiny
sizes, float32, the CPU. Last, what feeds the rule
(``models/kda.QKVConv``): on a host made to look like one TPU chip, at
heads of 128, q and k leave their convolutions' kernels normalised in
every layer and v's stays the plain one."""
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import kda as kda_model
from raydp_tpu.models.mamba import CausalConv1d
from raydp_tpu.ops import kda as kda_ops
from raydp_tpu.ops.causal_conv import Unit
from raydp_tpu.ops.kda import kda_chunked, kda_recurrent, unit_lower_inverse
from tests.test_causal_conv_kernel import (  # noqa: F401  (a fixture)
    _surveyed_and_traced,
    _tiny,
    as_on_a_tpu,
)
from tests.test_checkpoint_keeps import _eqns
from tests.test_gdn import CHEAPLY, _run_once

NAMES = ("q", "k", "v", "g", "beta")


def _inputs(seed=0, b=2, s=64, h=2, d_k=16, d_v=8, strength=1.0):
    """Unit ``q`` and ``k``, log-decays log-uniform in [-3, -1e-3] a token
    times ``strength``, ``beta`` in (0.05, 0.95)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, s, h, d_k))
    k = rng.standard_normal((b, s, h, d_k))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, s, h, d_v))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (b, s, h, d_k)))
    beta = rng.uniform(0.05, 0.95, (b, s, h))
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (q, k, v, g * strength, beta))


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _value_and_grads(fn, args):
    """``fn``'s value and the gradients of its sum under fixed weights, in
    ONE compiled program (op by op the interpreter's kernels take longer)."""
    weights = jnp.cos(jnp.arange(args[2].shape[-1], dtype=jnp.float32))

    def run(*a):
        out, vjp = jax.vjp(fn, *a)
        return out, vjp(jnp.broadcast_to(weights, out.shape).astype(out.dtype))

    return _run_once(run, *args)


@functools.lru_cache(maxsize=None)
def _by_the_scan(**sizes):
    """``_inputs(**sizes)``, and the token-by-token scan's value and
    gradients on them: one run for the two paths' cases."""
    args = _inputs(**sizes)
    return args, _value_and_grads(kda_recurrent, args)


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "kernels"])
@pytest.mark.parametrize("chunk,s,d_k,d_v,strength,tol", [
    (16, 16, 16, 8, 1.0, 5e-6),      # one chunk
    (16, 48, 16, 8, 1.0, 5e-6),      # several: three, not a power of two
    (64, 128, 8, 16, 1.0, 5e-6),     # d_k != d_v, the cell's chunk
    # A chunk's cumulative log-decay passes -1000: exp(-G) of a factored
    # form is infinite in float32 from -88 on. The cumulative sums' own
    # rounding is what is left.
    (64, 128, 16, 8, 40.0, 2e-4),
])
def test_chunked_is_the_token_by_token_scan(chunk, s, d_k, d_v, strength,
                                            tol, kernels):
    args, (want, d_want) = _by_the_scan(
        s=s, d_k=d_k, d_v=d_v, strength=strength)
    if strength > 1:
        per_chunk = args[3].reshape(2, s // chunk, chunk, 2, d_k).sum(2)
        assert float(per_chunk.min()) < -1000
    # The path asked for, whatever the shapes.
    got, d_got = _value_and_grads(
        lambda *x: kda_ops.kda_chunked(*x, chunk, kernels=kernels), args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, want) < tol
    for name, a, b in zip(NAMES, d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < 3 * tol, name


def _chunked(args, chunk):
    """[b, s, h, ...] arrays as the chunk-local step takes them:
    [b, chunks, h, chunk, ...]."""
    return tuple(
        jnp.moveaxis(a.reshape(a.shape[0], -1, chunk, *a.shape[2:]), 2, 3)
        for a in args
    )


def _in_place(args, chunk):
    """``args`` as the kernels' calls read them (``q``, ``k``, ``v``,
    ``g`` [b, s, h · d] and ``beta``'s rows), and the one segment that
    holds every chunk of them."""
    inputs, _, n, count = kda_ops._in_place(chunk, args)
    assert count == 1
    return inputs, kda_ops.Segment(jnp.zeros((), jnp.int32), n, chunk)


def _as_the_model(a):
    """[b, n, h, c, d] → [b, n · c, h · d], and rows [b, n, h, c] as they
    are."""
    if a.ndim == 4:
        return a
    return jnp.moveaxis(a, 3, 2).reshape(
        a.shape[0], -1, a.shape[2] * a.shape[4])


def test_the_kernels_vjp_is_the_jnp_chunk_local_forms():
    """One chunk of the cell's tile, [64, 128] float32, two heads: the six
    results, and the five gradients by the backward kernel against
    ``jax.vjp`` of the plain form under the same cotangents. The kernels
    read the chunks' ``T`` as the forward pass keeps it: the inverse of
    ``I + A``, two row blocks of 32 side by side in 128 lanes."""
    args = _inputs(b=1, s=64, d_k=128, d_v=128)
    chunked = _chunked(args, 64)
    rng = np.random.default_rng(1)
    want = jax.eval_shape(kda_ops._chunk_local_jnp, *chunked)
    cotangents = tuple(
        jnp.asarray(rng.standard_normal(a.shape), a.dtype) for a in want)

    def plain(*a):
        out, vjp = jax.vjp(kda_ops._chunk_local_jnp, *a)
        return out, vjp(cotangents)

    want, d_want = jax.jit(plain)(*chunked)
    inputs, where = _in_place(args, 64)

    @jax.jit
    def by_the_kernels(*inputs):
        *six, inverses = kda_ops._forward_call(
            where, *inputs, True,
            jnp.zeros(kda_ops.inverses_shape((1, 1, 2), 64), jnp.float32))
        again = kda_ops._forward_call(where, *inputs, True, inverses=inverses)
        grads = kda_ops._backward_call(
            where, inputs, inverses, cotangents,
            tuple(jnp.zeros_like(a) for a in inputs), True)
        return six, again, inverses, grads

    got, again, inverses, d_got = by_the_kernels(*inputs)
    assert inverses.shape == (1, 1, 2, 32, 128)
    _, own = kda_ops._pairwise(
        *chunked[:2], jnp.cumsum(chunked[3], -2), jnp.float32)
    system = jnp.eye(64) + chunked[4][..., None] * own
    T = jnp.concatenate([inverses[..., :64], inverses[..., 64:]], axis=-2)
    np.testing.assert_allclose(
        jnp.einsum("...ij,...jk->...ik", T, system, precision="highest"),
        jnp.broadcast_to(jnp.eye(64), T.shape), atol=2e-6)
    for a, b, c in zip(got, want, again):
        assert a.shape == b.shape == c.shape and a.dtype == b.dtype == c.dtype
        assert _rel(a, b) < 5e-6 and _rel(c, b) < 5e-6
    for name, a, b in zip(NAMES, d_got, d_want):
        b = _as_the_model(b)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 1e-5, name


@functools.lru_cache(maxsize=None)
def _bfloat16_by(kernels):
    q, k, v, g, beta = _inputs(b=1, s=128, d_k=128, d_v=128)
    args = (*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta)
    return _value_and_grads(
        lambda *a: kda_chunked(*a, 64, kernels).astype(jnp.float32), args)


def test_the_kernels_take_bfloat16_as_the_jnp_form_does():
    """``q``, ``k``, ``v`` in bfloat16 (the cell's), decays and ``beta``
    float32: output and gradients by the kernels against the plain form,
    both rounding their products' operands to bfloat16."""
    (got, d_got), (want, d_want) = _bfloat16_by(True), _bfloat16_by(False)
    assert got.dtype == want.dtype
    assert _rel(got, want) < 2e-2
    for name, a, b in zip(NAMES, d_got, d_want):
        assert a.dtype == b.dtype, name
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < 3e-2, name


@pytest.mark.parametrize("d_k,d_v,chunk,kernels", [
    (128, 128, 64, True),            # the cell's: [1, 16384, 32, 128]
    (256, 128, 128, True),
    (16, 128, 64, False),
    (128, 16, 64, False),
    (128, 128, 16, False),
    (192, 128, 64, False),           # half a register left over
])
def test_the_shapes_choose_the_path(d_k, d_v, chunk, kernels, monkeypatch):
    """The predicate alone, and that ``kda_chunked`` asks it: nothing
    runs. The one answer decides both halves: the chunk-local step and
    the recurrence over chunk states are the kernels' or ``jax.numpy``'s
    together, forward and backward."""
    assert kda_ops.uses_kernels(d_k, d_v, chunk) is kernels
    like = jax.ShapeDtypeStruct

    def shapes(s):
        return (like((1, s, 32, d_k), jnp.bfloat16),
                like((1, s, 32, d_k), jnp.bfloat16),
                like((1, s, 32, d_v), jnp.bfloat16),
                like((1, s, 32, d_k), jnp.float32),
                like((1, s, 32), jnp.float32))

    seen = []
    with monkeypatch.context() as patch:
        patch.setattr(
            kda_ops, "_kda", lambda *a: seen.append(a[-1]) or a[2]
        )
        jax.eval_shape(lambda *a: kda_chunked(*a, chunk), *shapes(16384))
    assert seen == [kernels]
    halves = []
    for name in ("_chunk_local_jnp", "_across", "_forward_call",
                 "_backward_call", "_state_forward_call",
                 "_state_backward_call"):
        def recorded(*a, _name=name, _fn=getattr(kda_ops, name), **kw):
            halves.append(_name)
            return _fn(*a, **kw)
        monkeypatch.setattr(kda_ops, name, recorded)
    jax.eval_shape(
        jax.grad(lambda *a: jnp.sum(
            kda_chunked(*a, chunk).astype(jnp.float32)), argnums=(0, 1, 2)),
        *shapes(2 * chunk))
    assert set(halves) == (
        {"_forward_call", "_backward_call", "_state_forward_call",
         "_state_backward_call"} if kernels
        else {"_chunk_local_jnp", "_across"})


def test_a_sequence_runs_in_segments(monkeypatch):
    """Four segments of two chunks give what one segment of eight gives,
    up to float32 rounding, and the gradients handed back from one to the
    one before it are the scan's."""
    args, (_, d_want) = _by_the_scan(s=64)
    whole = jax.jit(lambda *x: kda_chunked(*x, 8))(*args)
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 2)
    cut, d_cut = _value_and_grads(lambda *x: kda_chunked(*x, 8), args)
    assert _rel(cut, whole) < 2e-6
    for a, b in zip(d_cut, d_want):
        assert _rel(a, b) < 2e-5


@pytest.mark.parametrize("s,chunk,message", [
    (40, 16, "sequence 40 is not a multiple of chunk 16"),
    (48, 12, "chunk 12 is not a power of two"),
])
def test_shapes_it_cannot_cut_are_refused(s, chunk, message):
    with pytest.raises(ValueError, match=message):
        kda_chunked(*_inputs(s=s), chunk)


def _pairwise_decay(g):
    """``exp(G_t - G_j)`` for j <= t, 0 above: [b, h, t, j, d] float64."""
    G = np.cumsum(np.asarray(g, np.float64), axis=1).transpose(0, 2, 1, 3)
    diff = G[:, :, :, None] - G[:, :, None, :]
    s = G.shape[2]
    return np.where(np.tril(np.ones((s, s), bool))[..., None],
                    np.exp(np.minimum(diff, 0.0)), 0.0)


def test_beta_zero_writes_nothing_and_its_limit_is_gated_linear_attention():
    q, k, v, g, beta = _inputs(s=32)
    assert float(jnp.abs(kda_chunked(q, k, v, g, 0 * beta, 16)).max()) == 0.0
    # To first order in beta the correction -beta k k^T S drops out:
    # o_t = sum_{j<=t} beta_j (sum_d q_td k_jd e^{G_td - G_jd}) v_j.
    small = 1e-4 * beta
    got = np.asarray(kda_chunked(q, k, v, g, small, 16), np.float64)
    weights = np.einsum(
        "bthd,bjhd,bhtjd->bhtj", *(np.asarray(a, np.float64) for a in (q, k)),
        _pairwise_decay(g),
    )
    want = np.einsum(
        "bhtj,bjh,bjhv->bthv", weights, np.asarray(small, np.float64),
        np.asarray(v, np.float64),
    )
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-3


def test_no_decay_and_beta_one_is_the_plain_delta_rule():
    """``S_t = (I - k k^T) S_{t-1} + k v^T`` by a loop in float64; a key
    met a second time reads back the value last written for it."""
    q, k, v, g, beta = _inputs(s=32, b=1, h=1)
    k = k.at[0, 20, 0].set(k[0, 3, 0])
    got = np.asarray(kda_chunked(q, k, v, 0 * g, 0 * beta + 1, 16))
    K, V, Q = (np.asarray(a[0, :, 0], np.float64) for a in (k, v, q))
    state, want = np.zeros((K.shape[1], V.shape[1])), []
    for k_t, v_t, q_t in zip(K, V, Q):
        state = state - np.outer(k_t, k_t @ state) + np.outer(k_t, v_t)
        want.append(q_t @ state)
        np.testing.assert_allclose(k_t @ state, v_t, atol=1e-6)
    np.testing.assert_allclose(got[0, :, 0], np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n", [16, 64])
def test_the_triangular_inverse_and_its_backward(n):
    rng = np.random.default_rng(n)
    a = jnp.asarray(np.tril(rng.standard_normal((3, n, n)), -1) * 0.5,
                    jnp.float32)
    got = unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() < 1e-4
    # What lies on or above the diagonal is not read.
    noisy = a + jnp.triu(jnp.ones((n, n)))
    np.testing.assert_array_equal(unit_lower_inverse(noisy), got)
    weights = jnp.asarray(rng.standard_normal((3, n, n)), jnp.float32)
    d_got = jax.grad(lambda m: jnp.sum(unit_lower_inverse(m) * weights))(a)
    d_want = jax.grad(lambda m: jnp.sum(jnp.linalg.inv(
        jnp.eye(n) + jnp.tril(m, -1)) * weights))(a)
    assert _rel(d_got, d_want) < 1e-4


def test_the_forward_names_what_a_checkpoint_keeps(monkeypatch):
    """Under a checkpoint whose policy keeps ``KEPT`` the gradient runs
    the chunk quantities as often as without a checkpoint (forward, and
    the backward's own rebuild); a bare checkpoint runs them once more.
    Each run of a segment holds one cumulative sum of ``g``."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 1)
    args = _inputs(s=32)

    def loss(*a):
        return jnp.sum(kda_chunked(*a, 16))

    assert kda_ops.KEPT == (
        "kda_out", "kda_segment_states", "kda_chunk_inverses")
    policy = jax.checkpoint_policies.save_only_these_names(*kda_ops.KEPT)
    kept = jax.checkpoint(loss, policy=policy)

    def sums(fn):
        return str(jax.make_jaxpr(jax.grad(fn))(*args)).count("cumsum")

    assert sums(kept) == sums(loss) < sums(jax.checkpoint(loss))


def _kernel_calls(jaxpr):
    """{kernel's name: (operand shapes, result shapes)} of a jaxpr's
    Pallas calls."""
    return {
        e.params["name"]: ([tuple(v.aval.shape) for v in e.invars],
                           [tuple(v.aval.shape) for v in e.outvars])
        for e in _eqns(jaxpr, "pallas_call")
    }


def test_under_the_policy_the_kernels_inverses_are_kept_with_no_padded_lane(
        monkeypatch):
    """The kernel path at the cell's chunk, two segments of one chunk and
    two heads: what enters the checkpoint's backward holds the chunks'
    ``T`` as [b, chunks, h, 32, 128] float32, every segment's in the one
    array the forward kernel wrote them into (a [64, 64] float32 array's
    last dimension is padded to 128 lanes in HBM), nothing float32
    [64, 64], and the backward runs the rebuild and the gradient kernels
    of the chunk-local step, the two state kernels, and no forward chunk
    kernel."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 1)
    args = _inputs(b=1, s=128, h=2, d_k=8, d_v=8)

    def loss(*a):
        return jnp.sum(kda_chunked(*a, 64, kernels=True))

    policy = jax.checkpoint_policies.save_only_these_names(*kda_ops.KEPT)
    grad = jax.make_jaxpr(jax.grad(jax.checkpoint(loss, policy=policy)))(
        *args).jaxpr
    (backward,) = [
        e for e in _eqns(grad, "remat") + _eqns(grad, "checkpoint")
        if "kda_chunk_backward" in _kernel_calls(e.params["jaxpr"])
    ]
    assert set(_kernel_calls(backward.params["jaxpr"])) == {
        "kda_chunk_rebuild", "kda_chunk_backward",
        "kda_state_forward", "kda_state_backward", "kda_unwritten"}
    kept = [tuple(v.aval.shape) for v in backward.invars
            if v.aval.dtype == jnp.float32]
    assert (1, 2, 2, 32, 128) in kept and (2, 1, 2, 8, 8) in kept
    assert not [shape for shape in kept if shape[-1:] == (64,)]
    assert not [shape for shape in kept if shape[-2:] == (64, 64)]
    assert kda_ops.inverses_shape((1, 256, 32), 64) == (1, 256, 32, 32, 128)
    assert kda_ops.inverses_shape((3,), 128) == (3, 128, 128)


@pytest.mark.parametrize("case,h,chunks,segment,count", [
    ("one segment, an even count a grid step", 2, 2, 32, 4),
    ("one segment, an odd count", 3, 1, 32, 3),
    ("several segments, an even count", 2, 4, 2, 4),
    ("several segments, an odd count", 1, 3, 32, 1),
])
def test_the_backward_by_the_kernels_inverts_nothing(case, h, chunks, segment,
                                                     count, monkeypatch):
    """Under ``jax.grad`` of ``kda_chunked`` on the kernel path the
    triangular inverse is traced ONCE, in the forward pass's kernel, which
    writes every chunk's ``T`` into the one array it is handed; the
    backward's rebuild of a segment has that array among its operands and
    not among its results, the gradient kernel reads the same array, every
    call is told its segment by a scalar, and at an odd count of heads (a
    group that is no eight) the gradients are the ``jax.numpy`` path's."""
    chunk, d = 16, 16
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    traced = []
    inverse = kda_ops._inverse_tile
    monkeypatch.setattr(
        kda_ops, "_inverse_tile", lambda a: traced.append(a) or inverse(a))
    args = _inputs(b=1, s=chunk * chunks, h=h, d_k=d, d_v=d)

    def grads(kernels):
        return jax.grad(
            lambda *a: jnp.sum(jnp.sin(kda_chunked(*a, chunk, kernels))),
            argnums=(0, 1, 2, 3, 4))

    traced_once = jax.jit(grads(True)).trace(*args)
    calls = _kernel_calls(traced_once.jaxpr.jaxpr)
    assert len(traced) == 1
    assert count == h * math.gcd(chunks, segment)    # chunk-heads a segment
    # ``kda_unwritten`` does nothing: the arrays a loop writes into.
    assert set(calls) == {
        "kda_chunk_forward", "kda_chunk_rebuild", "kda_chunk_backward",
        "kda_state_forward", "kda_state_backward", "kda_unwritten"}
    kept = kda_ops.inverses_shape((1, chunks, h), chunk)
    assert kept[-1] == 128
    wide, rows = (1, chunk * chunks, h * d), (1, chunks, h, chunk)
    # The segment's index first; the arrays a call writes into last.
    for name, n_in, n_out, reads in [
        ("kda_chunk_forward", 1 + 5 + 1, 7, False),
        ("kda_chunk_rebuild", 1 + 6, 6, True),
        ("kda_chunk_backward", 1 + 12 + 5, 5, True),
    ]:
        operands, results = calls[name]
        assert (len(operands), len(results)) == (n_in, n_out), name
        assert operands[:6] == [(1,), wide, wide, wide, wide, rows], name
        assert (operands[6] == kept) if reads else (results[6] == kept), name
        assert kept not in (results if reads else operands[:6]), name
    assert calls["kda_chunk_backward"][1] == [wide] * 4 + [rows]
    if h % 2 == 0:
        # Two heads against the ``jax.numpy`` path, value and gradients:
        # ``test_chunked_is_the_token_by_token_scan``.
        return
    for name, a, b in zip(
            NAMES,
            traced_once.lower().compile(compiler_options=CHEAPLY)(*args),
            _run_once(grads(False), *args)):
        assert _rel(a, b) < 2e-5, name


# --------------------------------------------------------------------------
# The recurrence over chunk states by its two kernels.

LOCAL = ("U", "W", "P", "q_decayed", "to_end", "end", "state")


def _local(dtype, h=2, chunks=3, d_k=128, d_v=128, seed=0):
    """A segment's six chunk-local results, [1, chunks, h, ...], from the
    ``jax.numpy`` form at the cell's tile, and a state to enter it with
    that is not zero."""
    q, k, v, g, beta = _inputs(seed, b=1, s=64 * chunks, h=h, d_k=d_k,
                               d_v=d_v)
    args = _chunked((*(a.astype(dtype) for a in (q, k, v)), g, beta), 64)
    rng = np.random.default_rng(seed + 1)
    state = jnp.asarray(rng.standard_normal((1, h, d_k, d_v)), jnp.float32)
    return jax.jit(kda_ops._chunk_local_jnp)(*args), state


@pytest.mark.parametrize("dtype,tol,d_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 2e-2, 3e-2),
], ids=["float32", "bfloat16"])
def test_the_state_kernels_are_the_jnp_recurrence(dtype, tol, d_tol):
    """The two state kernels against ``_across`` on the same six
    chunk-local results and a non-zero entering state: ``o`` (written in
    the model's layout by the forward pass's call, chunk-major by the
    rebuild's), the state left and all seven cotangents, the bfloat16 case
    at the tolerance ``test_the_kernels_take_bfloat16_as_the_jnp_form_does``
    uses."""
    local, state = _local(dtype)
    (_, n, h, c, d_v), where = local[0].shape, None
    where = kda_ops.Segment(jnp.zeros((), jnp.int32), n, c)
    rng = np.random.default_rng(2)
    cotangents = (
        jnp.asarray(rng.standard_normal((1, n * c, h, d_v)), dtype),
        jnp.asarray(rng.standard_normal(state.shape), jnp.float32))

    @jax.jit
    def plain(*a):
        want, vjp = jax.vjp(
            lambda *a: kda_ops._across(a[:6], a[6], dtype), *a)
        return want, vjp(cotangents)

    @jax.jit
    def kernels(*a):
        out, left = kda_ops._state_forward_call(
            where, a[:6], a[6], True,
            jnp.zeros((1, n * c, h * d_v), dtype))
        again, states, w, _ = kda_ops._state_forward_call(
            where, a[:6], a[6], True)
        d_all = kda_ops._state_backward_call(
            where, (*a[1:6], states, w),
            cotangents[0].reshape(out.shape), cotangents[1], True)
        return (out.reshape(1, n * c, h, d_v), left), again, d_all

    want, d_want = plain(*local, state)
    got, again, d_got = kernels(*local, state)
    np.testing.assert_array_equal(
        _as_the_model(again).reshape(got[0].shape), got[0])
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < tol
    for name, a, b in zip(LOCAL, d_got, d_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < d_tol, name


@pytest.mark.parametrize("strength,tol", [(1.0, 5e-6), (40.0, 2e-4)],
                         ids=["weak decay", "strong decay"])
def test_the_kernels_carry_a_state_from_segment_to_segment(strength, tol,
                                                           monkeypatch):
    """Four segments of two chunks, three heads (no whole group of eight):
    every segment but the first is entered with the state the kernel
    before it left, and the state's cotangent comes back the same way;
    output and gradients against the token-by-token scan."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 2)
    args, (want, d_want) = _by_the_scan(
        b=2, s=128, h=3, d_k=16, d_v=8, strength=strength)
    weights = jnp.cos(jnp.arange(8, dtype=jnp.float32))

    def run(*a):
        out, vjp = jax.vjp(lambda *a: kda_chunked(*a, 16, kernels=True), *a)
        return out, vjp(jnp.broadcast_to(weights, out.shape))

    traced = jax.jit(run).trace(*args)
    calls = _kernel_calls(traced.jaxpr.jaxpr)
    assert calls["kda_state_backward"][0][-1] == (2, 3, 16, 8)
    got, d_got = traced.lower().compile(compiler_options=CHEAPLY)(*args)
    assert _rel(got, want) < tol
    for name, a, b in zip(NAMES, d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < 3 * tol, name


@pytest.mark.parametrize("h,heads", [
    (32, 8), (64, 8), (16, 8), (24, 8),
    # A group's decay rows are a [heads, d_k] block: whole tiles of eight
    # sublanes or the whole array.
    (12, 12), (4, 4), (3, 3),
])
def test_heads_a_grid_step_divide_the_head_count(h, heads):
    """``HEADS_A_STEP`` or a divisor of the head count, and the grid the
    kernels run on says so: (sequences, groups of heads, chunks), the
    state's block a group's, ``o``'s a chunk's rows of the group's lanes
    in the model's own array."""
    assert kda_ops.HEADS_A_STEP == 8
    assert kda_ops.step_heads(h, kda_ops.HEADS_A_STEP) == heads
    assert h % heads == 0
    like = jax.ShapeDtypeStruct
    b, n, c, d = 2, 5, 16, 8
    local = (like((b, n, h, c, d), jnp.float32),) * 2 + (
        like((b, n, h, c, c), jnp.float32), like((b, n, h, c, d), jnp.float32),
        like((b, n, h, c, d), jnp.float32), like((b, n, h, d), jnp.float32))
    where = kda_ops.Segment(jnp.zeros((), jnp.int32), n, c)
    jaxpr = jax.make_jaxpr(
        lambda *a: kda_ops._state_forward_call(where, a[:6], a[6], True, a[7])
    )(*local, like((b, h, d, d), jnp.float32),
      like((b, 3 * n * c, h * d), jnp.float32)).jaxpr
    (call,) = _eqns(jaxpr, "pallas_call")
    assert call.params["grid_mapping"].grid == (b, h // heads, n)
    blocks = [tuple(size if isinstance(size, int)
                    else getattr(size, "block_size", None)
                    for size in m.block_shape)
              for m in call.params["grid_mapping"].block_mappings]
    # The six and the state; the array written into, whole and nowhere in
    # VMEM; ``o``'s block and the state left.
    assert [len(shape) for shape in blocks] == [5, 5, 5, 5, 5, 4, 4, 3, 3, 4]
    assert blocks[7] == (b, 3 * n * c, h * d)
    assert blocks[8] == (None, c, heads * d)
    assert all(heads in shape for shape in blocks[:7] + blocks[9:])


def _state_walks(jaxpr):
    """The result shapes of a jaxpr's ``kda_state_forward`` calls."""
    return [[tuple(v.aval.shape) for v in e.outvars]
            for e in _eqns(jaxpr, "pallas_call")
            if e.params["name"] == "kda_state_forward"]


def test_only_the_rebuild_keeps_a_segments_states():
    """The forward pass's call of ``kda_state_forward`` writes ``o`` into
    the sequence's array and the state left; under differentiation (the
    backward's rebuild of ONE segment) it also writes that segment's
    entering states, transposed, and ``w``, float32: at the cell's shape
    67 MB and 34 MB a segment."""
    like = jax.ShapeDtypeStruct
    b, n, h, c, d = 1, 32, 32, 64, 128
    tile = like((b, n, h, c, d), jnp.float32)
    local = (tile, tile, like((b, n, h, c, c), jnp.bfloat16),
             like((b, n, h, c, d), jnp.bfloat16), tile,
             like((b, n, h, d), jnp.float32))
    state = like((b, h, d, d), jnp.float32)
    where = kda_ops.Segment(jnp.zeros((), jnp.int32), n, c)
    sequence = like((b, 8 * n * c, h * d), jnp.bfloat16)
    shapes = {
        keeps: [(a.shape, a.dtype) for a in jax.eval_shape(
            lambda *a: kda_ops._state_forward_call(
                where, a[:6], a[6], True, *a[7:]),
            *local, state, *(() if keeps else (sequence,)))]
        for keeps in (False, True)
    }
    out, left = ((b, n, h, c, d), jnp.bfloat16), ((b, h, d, d), jnp.float32)
    assert shapes[False] == [(sequence.shape, jnp.bfloat16), left]
    assert shapes[True] == [
        out, ((b, n, h, d, d), jnp.float32), ((b, n, h, c, d), jnp.float32),
        left]
    assert 4 * b * n * h * d * d == 67_108_864
    args = _inputs(b=1, s=32, h=2, d_k=8, d_v=8)
    forward, grad = (
        _state_walks(jax.make_jaxpr(fn)(*args).jaxpr)
        for fn in (lambda *a: kda_chunked(*a, 16, kernels=True),
                   jax.grad(lambda *a: jnp.sum(
                       kda_chunked(*a, 16, kernels=True))))
    )
    assert [len(results) for results in forward] == [2]
    assert [len(results) for results in grad] == [2, 4]


@pytest.mark.parametrize("kernel,float32_dots,rounded_dots", [
    ("kda_state_forward", 2, 2), ("kda_state_backward", 4, 4),
])
def test_the_states_products_are_float32_at_the_highest_precision(
        kernel, float32_dots, rounded_dots):
    """In the kernels' jaxprs, ``q``, ``k``, ``v`` bfloat16: every product
    that touches the state's float32 values (W·S, the state's update, and
    their four transposes) is float32 × float32 at ``Precision.HIGHEST``;
    the output products and their transposes multiply bfloat16 operands
    and accumulate in float32."""
    q, k, v, g, beta = _inputs(b=1, s=64, d_k=128, d_v=128)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        kda_chunked(*a, 64, kernels=True).astype(jnp.float32))))(
        *(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta).jaxpr
    call = [e for e in _eqns(jaxpr, "pallas_call")
            if e.params["name"] == kernel][-1]
    dots = _eqns(call.params["jaxpr"], "dot_general")
    heads = kda_ops.step_heads(2, kda_ops.HEADS_A_STEP)
    per_head = len(dots) // heads
    assert per_head == float32_dots + rounded_dots
    kinds = []
    for e in dots:
        dtypes = {v.aval.dtype for v in e.invars}
        assert len(dtypes) == 1 and e.outvars[0].aval.dtype == jnp.float32
        if dtypes == {jnp.dtype(jnp.float32)}:
            assert e.params["precision"] == (
                jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        else:
            assert dtypes == {jnp.dtype(jnp.bfloat16)}
            assert e.params["preferred_element_type"] == jnp.float32
        kinds.append(dtypes.pop())
    assert kinds.count(jnp.float32) == float32_dots * heads


# ---------------------------------- the model's own layout, read in place

def _a_segment_at_a_time(chunk, d_out, *args):
    """What the kernels' index maps and in-place writes are held to, the
    walk as it was before them: every segment SLICED out of the sequence
    by a ``lax.scan`` (a copy) and run as a sequence of its own — each
    call told segment 0 and handed fresh arrays to write — the state
    handed on, the results stacked and put together after the loop. The
    same five kernels on the same tiles in the same order as
    ``kda_chunked`` under ``KERNELS``. ``o`` and the five gradients under
    ``o``'s cotangent ``d_out``."""
    (b, s, h, d_k), v, beta = args[1].shape, args[2], args[4]
    n = math.gcd(s // chunk, kda_ops.SEGMENT_CHUNKS)
    where = kda_ops.Segment(jnp.zeros((), jnp.int32), n, chunk)

    def forward(state, xs):
        inputs = kda_ops._in_place(chunk, xs)[0]
        *six, T = kda_ops._forward_call(
            where, *inputs, True,
            jnp.zeros(kda_ops.inverses_shape((b, n, h), chunk), jnp.float32))
        out, left = kda_ops._state_forward_call(
            where, six, state, True,
            jnp.zeros((b, n * chunk, h * v.shape[-1]), v.dtype))
        return left, (out, state, T)

    def backward(d_state, xs):
        *xs, d_o, state, T = xs
        inputs, (d_o,), _, _ = kda_ops._in_place(chunk, xs, d_o)
        six = kda_ops._forward_call(where, *inputs, True, inverses=T)
        _, states, w, _ = kda_ops._state_forward_call(where, six, state, True)
        *d_six, d_state = kda_ops._state_backward_call(
            where, (*six[1:], states, w), d_o, d_state, True)
        return d_state, kda_ops._backward_call(
            where, inputs, T, d_six,
            tuple(jnp.zeros_like(a) for a in inputs), True)

    state = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
    _, (out, entered, kept) = jax.lax.scan(
        forward, state, kda_ops._segments(chunk, *args))
    _, grads = jax.lax.scan(
        backward, state,
        (*kda_ops._segments(chunk, *args, d_out), entered, kept),
        reverse=True)
    *wide, d_rows = map(kda_ops._whole, grads)
    return kda_ops._whole(out).reshape(v.shape), (
        *(a.reshape(x.shape) for a, x in zip(wide, args)),
        jnp.moveaxis(d_rows, 3, 2).reshape(beta.shape))


# float32 arrays: a few ulps of the largest entry (XLA's CPU backend may
# contract a product and a sum differently by what surrounds them, as
# ``ops/sparse_attention.py`` records); bfloat16 arrays: one rounding.
ULPS = {jnp.dtype(jnp.float32): 4 * 2.0 ** -23,
        jnp.dtype(jnp.bfloat16): 2.0 ** -8}


@pytest.mark.parametrize("dtype,h,segment", [
    (jnp.float32, 8, 1), (jnp.bfloat16, 16, 32),
], ids=["float32-8 heads-two segments", "bfloat16-16 heads-one segment"])
def test_in_place_is_a_segment_sliced_out_and_put_back(dtype, h, segment,
                                                       monkeypatch):
    """``kda_chunked`` under ``KERNELS`` — blocks of [b, s, h · d] found
    by the segment's index, ``o``, ``T`` and the gradients written into
    arrays the loops carry — against the same kernels on copies of one
    segment at a time."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    q, k, v, g, beta = _inputs(b=1, s=32, h=h, d_k=16, d_v=16)
    args = (*(a.astype(dtype) for a in (q, k, v)), g, beta)
    d_out = jnp.asarray(np.random.default_rng(3).standard_normal(v.shape),
                        dtype)

    def in_place(*a):
        out, vjp = jax.vjp(lambda *a: kda_chunked(*a, 16, kernels=True), *a)
        return out, vjp(d_out)

    got, d_got = _run_once(in_place, *args)
    want, d_want = _run_once(
        lambda *a: _a_segment_at_a_time(16, d_out, *a), *args)
    for name, a, b in zip(("o", *NAMES), (got, *d_got), (want, *d_want)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) <= ULPS[jnp.dtype(d_want[
            NAMES.index(name)].dtype if name != "o" else dtype)] * float(
                jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("segment", [32, 2], ids=["one segment", "four"])
def test_under_the_kernels_nothing_of_the_sequences_size_is_moved(
        segment, monkeypatch):
    """The traced gradient of ``kda_chunked`` under ``KERNELS``, eight
    chunks of 32 heads: outside the kernels' bodies no ``transpose``,
    ``copy``, ``concatenate``, ``dynamic_slice``, ``dynamic_update_slice``
    or ``gather`` reads or writes an array as large as ``q`` (``beta``'s
    rows, 1/128 of it at the cell's width, are turned once each way), and
    the loops over the segments scan their indices and the states they
    were entered with, nothing else."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    like = jax.ShapeDtypeStruct
    wide = like((1, 128, 32, 128), jnp.bfloat16)
    args = (wide, wide, wide, like(wide.shape, jnp.float32),
            like((1, 128, 32), jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(
            kda_chunked(*a, 16, kernels=True).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    moved = [
        e for e in _eqns(jaxpr, "")
        if e.primitive.name in (
            "transpose", "copy", "copy_p", "concatenate", "dynamic_slice",
            "dynamic_update_slice", "gather", "scatter")
        and any(math.prod(x.aval.shape) >= math.prod(wide.shape)
                for x in (*e.invars, *e.outvars) if hasattr(x, "aval"))
    ]
    assert not moved, moved
    # (A kernel's loop over a group's heads is a scan too, in its body.)
    loops = [e for e in _eqns(jaxpr, "scan")
             if _eqns(e.params["jaxpr"].jaxpr, "pallas_call")]
    assert len(loops) == 2
    count = 128 // 16 // min(segment, 8)
    for loop in loops:
        scanned = loop.invars[
            loop.params["num_consts"] + loop.params["num_carry"]:]
        assert sorted(tuple(x.aval.shape) for x in scanned) == sorted(
            [(count,)] + [(count, 1, 32, 128, 128)] * loop.params["reverse"])


# ------------------------------------------- what feeds the rule: QKVConv

def _norms_by_site(model):
    """``{(block, convolution): (unit, scale, output dtype)}`` of every
    ``CausalConv1d`` call an abstract apply of ``model`` makes."""
    sites = {}

    def note(next_fun, args, kwargs, context):
        module = context.module
        if isinstance(module, CausalConv1d) and (
                context.method_name == "__call__"):
            block = next(p for p in module.path if p.startswith("block_"))
            sites[block, module.name] = (
                module.unit, module.scale, jnp.dtype(module.dtype))
        return next_fun(*args, **kwargs)

    ids = jnp.zeros((1, 128), jnp.int32)
    with nn.intercept_methods(note):
        jax.eval_shape(
            lambda: model.apply(model.init(jax.random.PRNGKey(0), ids), ids))
    return sites


def test_on_a_tpu_q_and_k_leave_their_kernels_normalised_in_every_layer(
        as_on_a_tpu):
    model = _tiny(("kda", "attention", "kda"), key_dim=128)
    unit, compute = Unit(128, kda_model.L2_EPS), jnp.dtype(model.cfg.dtype)
    assert compute == jnp.bfloat16
    assert _norms_by_site(model) == {
        (block, name): norm
        for block in ("block_0", "block_2")
        for name, norm in (
            ("q", (unit, 128 ** -0.5, compute)), ("k", (unit, 1.0, compute)),
            ("v", (None, 1.0, jnp.dtype(jnp.float32))))
    }
    # The census and the traced gradient say the same: six call sites each
    # way, four of them q's and k's, two kernel bodies (the scale is an
    # operand: q and k share theirs).
    gauges, forward, backward, bodies = _surveyed_and_traced(model)
    assert gauges == (6, 0, 4)
    assert (forward, backward, bodies) == (6, 6, 2)


@pytest.mark.parametrize("key_dim", [64, 96, 192])
def test_a_head_of_no_whole_registers_keeps_its_norm_outside(
        as_on_a_tpu, key_dim):
    model = _tiny(("kda",), key_dim=key_dim)
    assert {norm[0] for norm in _norms_by_site(model).values()} == {None}
    assert _surveyed_and_traced(model)[0][2] == 0


def _qkv(dtype):
    """Heads of 128 (q, k) and 256 (v) over two sequences of 64 tokens."""
    module = kda_model.QKVConv(
        kda_model.KDAConfig(heads=2, key_dim=128, value_dim=256),
        dtype, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    inputs = [
        jax.random.normal(key, (2, 64, 2 * width), jnp.float32).astype(dtype)
        for key, width in zip(keys[:3], (128, 128, 256))
    ]
    cotangents = [
        jax.random.normal(key, (2, 64, 2, width), jnp.float32).astype(dtype)
        for key, width in zip(keys[3:6], (128, 128, 256))
    ]
    return module, module.init(keys[6], *inputs), inputs, cotangents


# At a bfloat16 ``x`` the ``jax.numpy`` path's ``dx`` rounds each tap's
# term and adds them in bfloat16: two roundings' worth.
@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 2e-6), (jnp.bfloat16, 2.0 ** -7)], ids=["f32", "bf16"])
def test_the_norm_inside_the_kernels_is_the_norm_after_them(
        as_on_a_tpu, monkeypatch, dtype, tol):
    """Values and every gradient of ``QKVConv`` by the kernels that hold
    the norm against the ``jax.numpy`` path, which a host without a TPU
    takes: the same float32 arithmetic, one rounding."""
    module, variables, inputs, cotangents = _qkv(dtype)

    def run():
        def apply(variables, *inputs):
            return module.apply(variables, *inputs)

        traced = jax.jit(
            lambda *a: jax.vjp(apply, *a)[1](tuple(cotangents))
        ).trace(variables, *inputs)
        out = jax.jit(apply)(variables, *inputs)
        return str(traced.jaxpr), out, traced.lower().compile()(
            variables, *inputs)

    program, out, grads = run()
    assert program.count("name=_backward_call") == 3
    monkeypatch.undo()
    program, want_out, want_grads = run()
    assert "pallas_call" not in program
    for got, want in zip(jax.tree_util.tree_leaves((out, grads)),
                         jax.tree_util.tree_leaves((want_out, want_grads))):
        assert got.dtype == want.dtype and got.shape == want.shape
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
