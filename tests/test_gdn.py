"""``ops/gdn.py``: the gated delta rule with one scalar decay a head,
chunked, against its token-by-token form and against the benchmark
reference's own scan (values and every gradient), at heads whose keys and
values differ in width (96 and 192) and a chunk of 64, at a sequence that
is no multiple of the chunk, with beta near 2 and a strong decay; what it
takes from ``ops/kda.py`` by import; what its forward names for a
checkpoint's policy; and that what feeds it (``models/kda.QKVConv``, Kimi
Linear's too) is at heads of 96 and 192 the program it was before the
convolution's kernels could hold the norm."""
import dataclasses
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.models import CausalLM
from raydp_tpu.models import step as model_step
from raydp_tpu.models.gdn import GDNConfig
from raydp_tpu.models.kda import L2_EPS, QKVConv
from raydp_tpu.models.mamba import CausalConv1d, conv_takes_kernel
from raydp_tpu.models.transformer import olmo_hybrid_7b
from raydp_tpu.ops import gdn as gdn_ops
from raydp_tpu.ops import kda as kda_ops
from raydp_tpu.ops.causal_conv import Unit
from raydp_tpu.ops.gdn import gdn_chunked, gdn_recurrent
from raydp_tpu.utils.profiling import metrics
from tests.test_causal_conv_kernel import as_on_a_tpu  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def builder():
    """The benchmark's builder file: the plain reference lives there."""
    path = os.path.join(REPO, "benchmark", "configs", "olmo_hybrid_lm.py")
    spec = importlib.util.spec_from_file_location("olmo_builder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _inputs(b=1, s=128, h=2, d_k=96, d_v=192, strength=1.0, beta_bias=0.0,
            seed=0, dtype=jnp.float32):
    """q, k L2-normalised a head (q times d_k^-1/2), v, log-decays whose
    size ``strength`` scales, beta in (0, 2) pushed toward 2 by
    ``beta_bias``."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((b, s, h, d_k))) * d_k ** -0.5
    k = unit(rng.standard_normal((b, s, h, d_k)))
    v = rng.standard_normal((b, s, h, d_v))
    g = -strength * np.exp(rng.standard_normal((b, s, h)))
    beta = 2.0 / (1.0 + np.exp(-(rng.standard_normal((b, s, h)) + beta_bias)))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32))


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _value_and_grads(fn, args):
    weights = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(
        args[2].shape)

    def loss(*a):
        out = fn(*a).astype(jnp.float32)
        return jnp.sum(out * weights), out

    (_, out), grads = _run_once(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True), *args)
    return out, grads


def _run_once(fn, *args):
    """``fn(*args)`` as ONE compiled program (op by op the interpreter's
    kernels take twice as long) that LLVM's expensive passes are not spent
    on: it runs once, on a few hundred tokens, and compiling it is most of
    a case's time."""
    return jax.jit(fn).lower(*args).compile(compiler_options=CHEAPLY)(*args)


CHEAPLY = {"xla_llvm_disable_expensive_passes": True}


CASES = [
    # chunk, s, d_k, d_v, strength, beta_bias, segment chunks
    (64, 256, 96, 192, 1.0, 0.0, 2),       # the published tiles
    (64, 128, 96, 192, 30.0, 4.0, 1),      # strong decay, beta near 2
    (32, 160, 96, 192, 1.0, 4.0, 2),       # 160 = 5 x gcd(64, 160)
    (16, 64, 12, 24, 0.05, 0.0, 32),       # hardly any decay, one segment
    (8, 64, 16, 8, 8.0, 2.0, 4),           # values narrower than keys
    (1, 8, 4, 4, 1.0, 0.0, 32),            # one token a chunk
]


@pytest.mark.parametrize(
    "chunk,s,d_k,d_v,strength,beta_bias,segment", CASES,
    ids=[f"c{c[0]}-s{c[1]}-k{c[2]}-v{c[3]}-g{c[4]}-b{c[5]}" for c in CASES])
def test_chunked_is_the_token_by_token_scan(chunk, s, d_k, d_v, strength,
                                            beta_bias, segment, monkeypatch):
    """Values and all five gradients, float32, over several segments."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    args = _inputs(s=s, d_k=d_k, d_v=d_v, strength=strength,
                   beta_bias=beta_bias)
    if beta_bias >= 4.0:
        assert float(args[4].max()) > 1.98
    want, want_grads = _value_and_grads(gdn_recurrent, args)
    got, grads = _value_and_grads(lambda *a: gdn_chunked(*a, chunk), args)
    assert got.shape == (1, s, 2, d_v)
    assert _rel(got, want) < 2e-5
    for name, g, w in zip("q k v g beta".split(), grads, want_grads):
        assert _rel(g, w) < 2e-4, name


def test_a_strong_decay_passes_float32s_smallest_inside_a_chunk():
    """A chunk's cumulative log-decay far under -88: every pairwise factor
    is e^(G_i - G_j) with i >= j, so nothing overflows and nothing is
    clamped; the result is the token-by-token scan's."""
    args = _inputs(s=128, strength=40.0, beta_bias=4.0, seed=3)
    assert float(args[3].reshape(1, 2, 64, 2).sum(2).min()) < -1000
    got = gdn_chunked(*args, 64)
    assert bool(jnp.isfinite(got).all())
    assert _rel(got, gdn_recurrent(*args)) < 2e-5


@pytest.mark.parametrize("depart", [None, "state_bfloat16"])
def test_chunked_is_the_references_scan(builder, depart):
    """The benchmark reference's own token-by-token recurrence (one
    sequence, no batch axis) gives the same values and gradients; with its
    state products in bfloat16 it does not."""
    args = _inputs(s=128, strength=2.0, beta_bias=2.0, seed=1)

    def reference(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return builder._delta_rule(
                q[0], k[0], v[0], g[0], beta[0], depart)[None]

    want, want_grads = _value_and_grads(reference, args)
    got, grads = _value_and_grads(lambda *a: gdn_chunked(*a, 64), args)
    if depart is None:
        assert _rel(got, want) < 2e-5
        for g, w in zip(grads, want_grads):
            assert _rel(g, w) < 2e-4
    else:
        assert _rel(got, want) > 1e-3


def test_bfloat16_inputs_give_bfloat16_and_stay_near_float32():
    args = _inputs(s=128, seed=2)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got = gdn_chunked(*low, 64)
    assert got.dtype == jnp.bfloat16 and got.shape == args[2].shape
    want = gdn_recurrent(*low)
    assert want.dtype == jnp.float32
    assert _rel(got.astype(jnp.float32), want) < 3e-2


def test_a_scalar_decay_is_a_channel_decay_with_every_channel_alike():
    """``ops/kda.py``'s chunked rule, given the scalar broadcast over the
    key dimension, agrees: the two rules share the walk, the inverse and
    the recurrence over chunk states, and differ in the pairwise factor."""
    args = _inputs(s=64, d_k=16, d_v=16, strength=3.0, seed=4)
    q, k, v, g, beta = args
    wide = jnp.broadcast_to(g[..., None], k.shape)
    assert _rel(gdn_chunked(*args, 16),
                kda_ops.kda_chunked(q, k, v, wide, beta, 16)) < 2e-5


def test_beta_two_reflects_the_state_along_k():
    """With no decay and beta = 2 a token's transition is the Householder
    reflection I - 2 k k^T (an eigenvalue of -1): writing the same key
    twice with v = 0 gives the first state back."""
    rng = np.random.default_rng(5)
    k = rng.standard_normal((1, 3, 1, 8))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k[0, 2] = k[0, 1]
    v = np.zeros((1, 3, 1, 4))
    v[0, 0] = rng.standard_normal(4)
    q = np.broadcast_to(k[:, :1], k.shape)
    beta = np.asarray([[[1.0], [2.0], [2.0]]])
    args = tuple(jnp.asarray(a, jnp.float32) for a in (
        q, k, v, np.zeros((1, 3, 1)), beta))
    out = gdn_chunked(*args, 1)
    np.testing.assert_allclose(out[0, 2], out[0, 0], atol=1e-6)
    assert float(jnp.max(jnp.abs(out[0, 1] - out[0, 0]))) > 1e-3


def test_it_takes_the_inverse_the_recurrence_and_the_walk_by_import():
    assert gdn_ops.unit_lower_inverse is kda_ops.unit_lower_inverse
    assert gdn_ops.segment_walk is kda_ops.segment_walk
    assert gdn_ops._across is kda_ops._across
    for rule in (gdn_ops.RULE, gdn_ops.KERNELS):
        assert isinstance(rule, kda_ops.Rule)
        # Both get their segments copied chunk-major by that module's loops.
        assert rule.forward.__qualname__.startswith("chunk_major.")
        assert rule.forward.__module__ == kda_ops.__name__
        assert rule.names == gdn_ops.KEPT == (
            "gdn_out", "gdn_segment_states", "gdn_chunk_inverses")
    assert not set(gdn_ops.KEPT) & set(kda_ops.KEPT)
    # The kernels' rule takes the tile's inverse and the walk from there,
    # and the recurrence over chunk states from neither: its own kernels.
    assert gdn_ops._inverse_tile is kda_ops._inverse_tile
    assert gdn_ops.across is not kda_ops._across


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_a_sequence_runs_in_segments(kernels, monkeypatch):
    """Four segments of two chunks give what one segment of eight gives;
    the forward keeps a state a segment and, by the kernels, every chunk's
    triangular inverse with its rows side by side in 128 lanes."""
    args = _inputs(s=128, d_k=12, d_v=24)
    whole = gdn_chunked(*args, 16, kernels=kernels)
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 2)
    cut = gdn_chunked(*args, 16, kernels=kernels)
    assert _rel(cut, whole) < 1e-5
    rule = gdn_ops.KERNELS if kernels else gdn_ops.RULE
    states = jax.eval_shape(
        lambda *a: kda_ops._forward(*a, 16, rule, keep=True), *args)
    assert states[1].shape == (4, 1, 2, 12, 24)       # a state a segment
    if kernels:
        assert [a.shape for a in states[2:]] == [(4, 1, 2, 2, 2, 128)]
    else:
        assert len(states) == 2                        # and nothing else


@pytest.mark.parametrize("s,chunk,message", [
    (48, 32, "multiple"), (64, 24, "power of two"),
])
def test_shapes_it_cannot_cut_are_refused(s, chunk, message):
    with pytest.raises(ValueError, match=message):
        gdn_chunked(*_inputs(s=s, d_k=8, d_v=8), chunk)


@pytest.mark.parametrize("kernels,mark", [
    (False, "cumsum"), (True, "name=gdn_chunk_forward"),
], ids=["plain", "kernels"])
def test_the_forward_names_what_a_checkpoint_keeps(kernels, mark, monkeypatch):
    """Under a checkpoint whose policy keeps ``KEPT`` the gradient runs
    the chunk quantities as often as without a checkpoint (forward, and
    the backward's own rebuild; by the kernels the inverting forward
    kernel once); a bare checkpoint runs them once more."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 1)
    args = _inputs(s=32, d_k=8, d_v=16)

    def loss(*a):
        return jnp.sum(gdn_chunked(*a, 16, kernels=kernels))

    policy = jax.checkpoint_policies.save_only_these_names(*gdn_ops.KEPT)
    kept = jax.checkpoint(loss, policy=policy)

    def runs(fn):
        return str(jax.make_jaxpr(jax.grad(fn))(*args)).count(mark)

    assert 0 < runs(kept) == runs(loss) < runs(jax.checkpoint(loss))


def test_the_states_products_are_float32_at_the_highest_precision():
    """Every product that touches the triangular inverse or a chunk state
    has float32 operands and ``Precision.HIGHEST``; the pairwise products
    and the two that make ``o`` take bfloat16 operands."""
    args = _inputs(s=64, d_k=8, d_v=16)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    jaxpr = jax.make_jaxpr(lambda *a: gdn_chunked(*a, 16))(*low)

    def dots(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from dots(inner)

    found = list(dots(jaxpr.jaxpr))
    by_dtype = {}
    for eqn in found:
        dtype = eqn.invars[0].aval.dtype
        by_dtype.setdefault(str(dtype), []).append(eqn.params["precision"])
    assert set(by_dtype) == {"float32", "bfloat16"}
    assert all(
        p is not None and "HIGHEST" in str(p) for p in by_dtype["float32"])
    assert len(by_dtype["bfloat16"]) == 3      # [q; k] k^T, q e^G S, P w


@pytest.mark.parametrize("sequence,chunk,segments", [
    (4096, 64, 2), (8192, 64, 4), (160, 32, 5), (2048, 64, 1), (48, 16, 3),
])
def test_the_configs_chunk_and_kept_bytes_follow_the_sequence(
        sequence, chunk, segments):
    gdn = GDNConfig()
    assert gdn.scan_chunk(sequence) == chunk
    assert gdn.state_bytes(3) == 3 * 30 * 96 * 192 * 4
    assert gdn.kept_bytes(3, sequence) == 3 * 30 * 192 * (
        2 * sequence + 4 * segments * 96)


# ------------------------------------------- what feeds the rule: QKVConv

class _QKVConvBeforeTheNormCouldBeInside(nn.Module):
    """``models/kda.QKVConv`` as it stood (PR 65): float32 out of every
    convolution, the norm and the scale as ``jax.numpy`` after it."""

    gdn: GDNConfig
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, q, k, v):
        gdn = self.gdn

        def conv(x, name, width):
            y = CausalConv1d(
                gdn.conv_taps, jnp.float32, jnp.float32, use_bias=False,
                name=name,
            )(x)
            return y.reshape(*y.shape[:-1], gdn.heads, width)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS
            )

        q = unit(conv(q, "q", gdn.key_dim)) * gdn.key_dim ** -0.5
        k = unit(conv(k, "k", gdn.key_dim))
        v = conv(v, "v", gdn.value_dim)
        return q.astype(self.dtype), k.astype(self.dtype), v.astype(self.dtype)


@pytest.mark.parametrize("heads", [4, 5], ids=["kernels", "jnp"])
def test_on_a_tpu_the_convolutions_at_heads_of_96_trace_to_what_they_did(
        as_on_a_tpu, heads):
    """Four heads: 384 and 768 channels, which the plain kernels tile (as
    the published v's 5,760); five: 480 and 960, which they do not (as
    the published q's and k's 2,880). Either way a head of 96 or 192 lanes
    is no whole register, the norm stays outside, and the gradient's
    jaxpr is the parent's to the letter."""
    gdn = GDNConfig(heads=heads, key_dim=96, value_dim=192)
    inputs = [
        jax.ShapeDtypeStruct((1, 128, heads * width), jnp.bfloat16)
        for width in (96, 96, 192)
    ]

    def gradient(module):
        variables = jax.eval_shape(
            lambda: module.init(jax.random.PRNGKey(0), *[
                jnp.zeros(x.shape, x.dtype) for x in inputs]))

        def loss(variables, *inputs):
            return sum(
                (a.astype(jnp.float32) ** 2).sum()
                for a in module.apply(variables, *inputs))

        return str(jax.make_jaxpr(jax.grad(loss))(variables, *inputs))

    got = gradient(QKVConv(gdn, jnp.bfloat16, jnp.float32))
    assert got == gradient(
        _QKVConvBeforeTheNormCouldBeInside(gdn, jnp.bfloat16))
    assert got.count("name=_forward_call") == (3 if heads == 4 else 0)


@pytest.mark.parametrize("channels,width", [(2880, 96), (5760, 192)])
def test_the_published_widths_keep_the_norm_outside(
        as_on_a_tpu, channels, width):
    shape = (4096, channels, 4, jnp.bfloat16, jnp.bfloat16)
    assert not conv_takes_kernel(*shape, unit=Unit(width, L2_EPS))
    assert conv_takes_kernel(*shape) is (channels == 5760)


def test_on_a_tpu_a_gated_delta_stack_counts_no_norm_inside(as_on_a_tpu):
    """Four heads of 96 and 192 at 128 tokens: all three convolutions of
    each delta-rule layer by the plain kernels, none with the norm."""
    model = CausalLM(dataclasses.replace(
        olmo_hybrid_7b(
            n_layers=2, layer_types=("gdn:swiglu", "attention:swiglu"),
            d_model=64, n_heads=2, d_ff=128, vocab_size=128, max_len=128),
        gdn=GDNConfig(heads=4, key_dim=96, value_dim=192, chunk=16),
    ))
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    params = jax.eval_shape(
        lambda: model_step.parameters(nn.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))))
    model_step.report(model, params, ids)
    assert metrics.gauge_value("conv/kernel_calls") == 3
    assert metrics.gauge_value("conv/jnp_calls") == 0
    assert metrics.gauge_value("conv/unit_kernel_calls") == 0
