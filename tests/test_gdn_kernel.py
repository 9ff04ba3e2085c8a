"""The scalar-decay delta rule's Pallas kernels (``ops/gdn.py``:
``gdn_chunk_forward`` / ``gdn_chunk_rebuild`` / ``gdn_chunk_backward``,
``gdn_state_forward`` / ``gdn_state_backward``) in the Pallas interpreter
at the published tiles (keys of 96, values of 192, chunks of 64) cut to
two or three heads: values and every gradient against the plain rule of
the same file and against the recurrence itself, a step a token; float32
and bfloat16; a decay that passes float32's smallest inside a chunk;
``beta`` = 2; a state carried over three segments; what the forward keeps
and what the backward therefore never inverts; the precision of every
product in the kernels' bodies; which shapes and which meshes take the
kernels; and what a step that holds them costs to LOWER for a TPU: one
kernel body a shape however many layers, a text whose size does not
follow the sequence, and no Mosaic call left for XLA to partition."""
import functools
import hashlib
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from raydp_tpu.models import CausalLM
from raydp_tpu.models import gdn as gdn_module
from raydp_tpu.models.gdn import GDNConfig
from raydp_tpu.models.transformer import olmo_hybrid_7b
from raydp_tpu.ops import gdn as gdn_ops
from raydp_tpu.ops import kda as kda_ops
from raydp_tpu.ops.gdn import gdn_chunked, gdn_recurrent
from raydp_tpu.utils.profiling import metrics
from tests.test_causal_conv_kernel import _mesh
from tests.test_checkpoint_keeps import _eqns
from tests.test_gdn import CHEAPLY, _inputs, _rel, _value_and_grads
from tests.test_kda import _kernel_calls
from tests.test_ssd_kernel import _abstract_parameters, as_on_a_tpu  # noqa: F401

BF16, F32 = jnp.bfloat16, jnp.float32
NAMES = ("o", "q", "k", "v", "g", "beta")
KERNELS = ("gdn_chunk_forward", "gdn_chunk_rebuild", "gdn_chunk_backward",
           "gdn_state_forward", "gdn_state_backward")
# What makes a case: ``_inputs``' arguments, and how far the kernels'
# values and gradients may lie from the plain rule's (a float32 sum in
# another order; one rounding of a bfloat16 product's operands through a
# chunk's sums; where a chunk's cumulative log-decay is in the thousands a
# difference of two of them is good to 1e-4, by either rule).
CASES = {
    "float32": (dict(b=2, s=128, h=3), 2e-5, 2e-5),
    "bfloat16": (dict(s=128, h=2, dtype=BF16, seed=1), 2e-2, 2e-2),
    "strong_decay": (
        dict(s=128, strength=40.0, beta_bias=4.0, seed=3), 2e-5, 5e-4),
    # ``strong_decay``'s shapes: its three compiled programs run this too.
    "beta_two": (dict(s=128, seed=4), 2e-5, 2e-5),
}


@functools.lru_cache(maxsize=None)
def _operands(case):
    args = _inputs(**CASES[case][0])
    if case == "beta_two":
        args = (*args[:4], jnp.full_like(args[4], 2.0))
    return args


SCANS = (
    lambda *a: gdn_chunked(*a, 64, kernels=True),
    lambda *a: gdn_chunked(*a, 64, kernels=False),
    lambda *a: gdn_recurrent(*a),
)


@functools.lru_cache(maxsize=None)
def _program(scan, shapes):
    """``_value_and_grads`` of ``scan`` compiled once for ``shapes``
    ((shape, dtype) an operand): two cases of one shape share it."""
    like = [jax.ShapeDtypeStruct(*each) for each in shapes]
    weights = jnp.cos(jnp.arange(
        np.prod(like[2].shape), dtype=F32)).reshape(like[2].shape)

    def loss(*a):
        out = scan(*a).astype(F32)
        return jnp.sum(out * weights), out

    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(*like).compile(
            compiler_options=CHEAPLY)


@functools.lru_cache(maxsize=None)
def both(case):
    """``(o, gradients)`` by the kernels, by the plain rule and by the
    recurrence (``q``, ``k``, ``v`` in float32 for it)."""
    args = _operands(case)
    shapes = tuple((a.shape, a.dtype) for a in args)
    found = []
    # No case compares bfloat16 inputs with the float32 recurrence.
    for scan in SCANS[:2 if case == "bfloat16" else 3]:
        (_, out), grads = _program(scan, shapes)(*args)
        DTYPES.setdefault(case, [a.dtype for a in grads])
        found.append(tuple(a.astype(F32) for a in (out, *grads)))
    return (*found, None)[:3]


DTYPES = {}      # the kernels' gradients' own dtypes, a case of ``both``


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_plain_rule(case, name):
    kernels, plain, _ = both(case)
    at = NAMES.index(name)
    assert kernels[at].shape == plain[at].shape
    assert bool(jnp.isfinite(kernels[at]).all())
    assert _rel(kernels[at], plain[at]) < CASES[case][min(at, 1) + 1], name


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", ["float32", "strong_decay", "beta_two"])
def test_the_kernels_are_the_token_by_token_scan(case, name):
    kernels, _, recurrence = both(case)
    at = NAMES.index(name)
    assert _rel(kernels[at], recurrence[at]) < (
        2e-5 if at == 0 else max(2e-4, CASES[case][2]))


def test_the_strong_decay_passes_float32s_smallest_inside_a_chunk():
    g = _operands("strong_decay")[3]
    assert float(g.reshape(1, 2, 64, 2).sum(2).min()) < -1000
    assert float(_operands("strong_decay")[4].max()) > 1.98


def test_bfloat16_in_gives_bfloat16_out_and_float32_decay_gradients():
    both("bfloat16")
    assert DTYPES["bfloat16"] == [BF16, BF16, BF16, F32, F32]
    assert jax.eval_shape(
        lambda *a: gdn_chunked(*a, 64, kernels=True),
        *_operands("bfloat16")).dtype == BF16


@pytest.mark.parametrize("segment,chunks,segments", [(1, 3, 3), (2, 4, 2)])
def test_a_state_is_carried_from_segment_to_segment(segment, chunks, segments,
                                                    monkeypatch):
    """Three chunks in three segments, and four in two, give what one
    segment gives, values and gradients: the state the kernels leave is
    the state they are entered with (and one segment's are the plain
    rule's: ``test_the_kernels_are_the_plain_rule``)."""
    args = _inputs(s=64 * chunks, strength=0.3, beta_bias=1.0, seed=6)
    whole = _value_and_grads(
        lambda *a: gdn_chunked(*a, 64, kernels=True), args)
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    states = jax.eval_shape(
        lambda *a: kda_ops._forward(*a, 64, gdn_ops.KERNELS, keep=True),
        *args)
    assert states[1].shape == (segments, 1, 2, 96, 192)
    cut = _value_and_grads(lambda *a: gdn_chunked(*a, 64, kernels=True), args)
    assert _rel(cut[0], whole[0]) < 1e-5
    for name, a, b in zip(NAMES[1:], cut[1], whole[1]):
        assert _rel(a, b) < 2e-5, name


# ---------------------------------------------- what is kept, and inverted

def _grad_jaxpr(args, wrap=lambda f: f):
    def loss(*a):
        return jnp.sum(jnp.sin(gdn_chunked(*a, 64, kernels=True)))

    return jax.make_jaxpr(
        jax.grad(wrap(loss), argnums=(0, 1, 2, 3, 4)))(*args).jaxpr


@pytest.mark.parametrize("case,h,chunks,segment,count", [
    ("one segment, an even count a grid step", 2, 2, 32, 4),
    ("one segment, an odd count", 3, 1, 32, 3),
    ("several segments", 2, 4, 2, 4),
])
def test_the_backward_inverts_nothing(case, h, chunks, segment, count,
                                      monkeypatch):
    """Under ``jax.grad`` the triangular inverse is traced ONCE, in the
    forward pass's kernel, which writes every chunk's ``T``; a segment's
    rebuild has ``T`` among its operands and not among its results, and
    the gradient kernel reads the same array."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", segment)
    traced = []
    inverse = gdn_ops._inverse_tile
    monkeypatch.setattr(
        gdn_ops, "_inverse_tile", lambda a: traced.append(a) or inverse(a))
    # A width no other test lowers: the calls' jits hold no earlier trace.
    args = _inputs(s=64 * chunks, h=h, d_k=32 * h, d_v=32 * (chunks + 1))
    calls = _kernel_calls(_grad_jaxpr(args))
    assert len(traced) == 1
    assert set(calls) == set(KERNELS)
    kept = (count, *kda_ops.inverses_shape((), 64))
    assert kept == (count, 32, 128)
    steps = count // np.gcd(count, kda_ops.CHUNKS_A_STEP)
    for name, n_in, n_out, reads in [
        ("gdn_chunk_forward", 5, 6, False),
        ("gdn_chunk_rebuild", 6, 5, True),
        ("gdn_chunk_backward", 11, 5, True),
    ]:
        operands, results = calls[name]
        assert (len(operands), len(results)) == (n_in, n_out), name
        assert (operands[5] == kept) if reads else (results[5] == kept), name
        assert kept not in (results if reads else operands), name
        # ``g`` and ``beta`` a chunk on the lanes, and their gradients.
        assert operands[3] == operands[4] == (steps, count // steps, 64)
    assert calls["gdn_chunk_backward"][1][3:] == [
        (steps, count // steps, 64)] * 2


def test_under_the_policy_the_inverses_are_kept_with_no_padded_lane(
        monkeypatch):
    """Two segments of one chunk and two heads at the published tiles:
    what enters the checkpoint's backward holds the chunks' ``T`` as
    [segments, b, chunks, h, 32, 128] float32 (a [64, 64] float32 array's
    rows are padded to 128 lanes in HBM), nothing float32 [64, 64], the
    segments' states as [96, 192], and the backward runs the rebuild and
    the gradient kernels, the two state kernels, and no forward kernel."""
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 1)
    policy = jax.checkpoint_policies.save_only_these_names(*gdn_ops.KEPT)
    grad = _grad_jaxpr(
        _inputs(s=128), lambda f: jax.checkpoint(f, policy=policy))
    (backward,) = [
        e for e in _eqns(grad, "remat") + _eqns(grad, "checkpoint")
        if "gdn_chunk_backward" in _kernel_calls(e.params["jaxpr"])
    ]
    assert set(_kernel_calls(backward.params["jaxpr"])) == set(KERNELS[1:])
    kept = [tuple(v.aval.shape) for v in backward.invars
            if v.aval.dtype == F32]
    assert (2, 1, 1, 2, 32, 128) in kept and (2, 1, 2, 96, 192) in kept
    assert not [shape for shape in kept if shape[-2:] == (64, 64)]
    assert gdn_ops.KEPT == (
        "gdn_out", "gdn_segment_states", "gdn_chunk_inverses")
    assert not set(gdn_ops.KEPT) & set(kda_ops.KEPT)


def test_the_plain_rule_keeps_no_inverse_and_inverts_again(monkeypatch):
    monkeypatch.setattr(kda_ops, "SEGMENT_CHUNKS", 1)
    args = _inputs(s=128)
    kept = jax.eval_shape(
        lambda *a: kda_ops._forward(*a, 64, gdn_ops.RULE, keep=True), *args)
    assert len(kept) == 2
    kept = jax.eval_shape(
        lambda *a: kda_ops._forward(*a, 64, gdn_ops.KERNELS, keep=True),
        *args)
    assert [a.shape for a in kept] == [
        (1, 128, 2, 192), (2, 1, 2, 96, 192), (2, 1, 1, 2, 32, 128)]


def test_only_the_rebuild_keeps_a_segments_states():
    """The forward pass's ``gdn_state_forward`` writes ``o`` and the state
    left; under differentiation (the backward's rebuild of ONE segment) it
    also writes that segment's entering states and ``w``, float32: at the
    cell's shape 71 MB and 47 MB a segment."""
    like = jax.ShapeDtypeStruct
    b, n, h, c, d_k, d_v = 1, 32, 30, 64, 96, 192
    local = (like((b, n, h, c, d_v), F32), like((b, n, h, c, d_k), F32),
             like((b, n, h, c, c), BF16), like((b, n, h, c, d_k), BF16),
             like((b, n, h, c, d_k), F32), like((b, n, h, 1), F32))
    state = like((b, h, d_k, d_v), F32)
    forward, vjp = (
        _kernel_calls(jax.make_jaxpr(fn)(*local, state).jaxpr)
        for fn in (lambda *a: gdn_ops.across(a[:6], a[6]),
                   lambda *a: jax.vjp(gdn_ops.across, a[:6], a[6])[0])
    )
    assert forward["gdn_state_forward"][1] == [
        (b, n, h, c, d_v), (b, h, d_k, d_v)]
    assert vjp["gdn_state_forward"][1] == [
        (b, n, h, c, d_v), (b, n, h, d_k, d_v), (b, n, h, c, d_v),
        (b, h, d_k, d_v)]
    assert 4 * b * n * h * d_k * d_v == 70_778_880


# ------------------------------------------------------------- precision

def _dots(call):
    kinds = []
    for e in _eqns(call.params["jaxpr"], "dot_general"):
        dtypes = {v.aval.dtype for v in e.invars}
        assert len(dtypes) == 1 and e.outvars[0].aval.dtype == F32
        if dtypes == {jnp.dtype(F32)}:
            assert e.params["precision"] == (
                jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        else:
            assert dtypes == {jnp.dtype(BF16)}
            assert e.params["preferred_element_type"] == F32
        kinds.append(dtypes.pop())
    return kinds.count(F32), kinds.count(BF16)


@pytest.mark.parametrize("kernel,float32_dots,rounded_dots", [
    # The inverse's ten products, T(βv) and T(β e^G k); [q; k] kᵀ.
    ("gdn_chunk_forward", 12, 1),
    # T is read: U and W; q kᵀ alone.
    ("gdn_chunk_rebuild", 2, 1),
    # W·S and the state's update; (q e^G) S and P w.
    ("gdn_state_forward", 2, 2),
    ("gdn_state_backward", 4, 4),
])
def test_the_kernels_products_are_float32_wherever_the_plain_rules_are(
        kernel, float32_dots, rounded_dots):
    """``q``, ``k``, ``v`` bfloat16: every product that touches ``T`` or a
    chunk state multiplies float32 by float32 at ``Precision.HIGHEST``;
    only ``[q; k] kᵀ`` and the two products that make ``o`` (and their
    transposes) take bfloat16 operands, accumulated in float32."""
    jaxpr = _grad_jaxpr(_operands("bfloat16"))
    calls = [e for e in _eqns(jaxpr, "pallas_call")
             if e.params["name"] == kernel]
    heads = gdn_ops.state_heads(2) if "state" in kernel else 1
    # The state's forward is there twice: the pass itself, and a rebuild.
    assert len(calls) == 1 + (kernel == "gdn_state_forward")
    for call in calls:
        assert _dots(call) == (float32_dots * heads, rounded_dots * heads)


def test_the_gradient_kernels_products_are_float32_but_the_pairs():
    jaxpr = _grad_jaxpr(_operands("bfloat16"))
    (call,) = [e for e in _eqns(jaxpr, "pallas_call")
               if e.params["name"] == "gdn_chunk_backward"]
    float32_dots, rounded_dots = _dots(call)
    # [q; k] kᵀ forward and its two transposes; everything else float32.
    assert rounded_dots == 3 and float32_dots >= 6
    assert "cumsum" not in str(call.params["jaxpr"])


# ------------------------------------------------- which shapes, which mesh

@pytest.mark.parametrize("d_k,d_v,chunk,takes", [
    (96, 192, 64, True),              # the published tiles
    (128, 128, 64, True),
    (128, 256, 128, True),
    (32, 32, 64, True),
    (96, 192, 32, False),             # a sequence of 160 tokens
    (96, 192, 1, False),              # ``model.init``'s sample
    (12, 24, 64, False),              # a test's heads
    (96, 200, 64, False),
    (512, 192, 64, False),            # six states would not fit VMEM
])
def test_the_predicate_reads_the_shapes_alone(d_k, d_v, chunk, takes):
    assert gdn_ops.uses_kernels(d_k, d_v, chunk) is takes


@pytest.mark.parametrize("d_k,d_v,chunk", [(96, 192, 64), (12, 24, 16)])
def test_the_shapes_choose_the_rule(d_k, d_v, chunk, monkeypatch):
    walked = []
    walk = kda_ops.segment_walk
    monkeypatch.setattr(
        gdn_ops, "segment_walk",
        lambda *a: walked.append(a[-1]) or walk(*a))
    args = _inputs(s=64, d_k=d_k, d_v=d_v)
    jax.eval_shape(lambda *a: gdn_chunked(*a, chunk), *args)
    jax.eval_shape(lambda *a: gdn_chunked(*a, chunk, kernels=True), *args)
    jax.eval_shape(lambda *a: gdn_chunked(*a, chunk, kernels=False), *args)
    takes = gdn_ops.uses_kernels(d_k, d_v, chunk)
    assert walked == [gdn_ops.KERNELS if takes else gdn_ops.RULE,
                      gdn_ops.KERNELS, gdn_ops.RULE]


@pytest.mark.parametrize("h,heads", [
    (30, 6), (2, 2), (3, 3), (32, 4), (7, 1), (12, 6), (5, 5), (1, 1),
])
def test_heads_a_grid_step_divide_the_head_count(h, heads):
    assert gdn_ops.state_heads(h) == heads
    assert h % heads == 0 and heads <= gdn_ops.HEADS_A_STEP


@pytest.mark.parametrize("mesh,takes", [
    (None, False),                    # eight devices here, no mesh told
    (dict(dp=1), True),
    (dict(dp=2), True),
    (dict(dp=2, sp=2), False),        # a sequence split over chips
    (dict(dp=2, tp=2), False),        # the heads split over chips
], ids=["not_told", "dp1", "dp2", "sp2", "tp2"])
def test_the_predicate_leaves_no_mosaic_call_for_the_compiler_to_partition(
        mesh, takes, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    mesh = mesh and _mesh(**mesh)
    assert gdn_module.scan_takes_kernels(96, 192, 64, mesh) is takes
    assert not gdn_module.scan_takes_kernels(96, 192, 1, mesh)
    if mesh is None:
        monkeypatch.setattr(jax, "device_count", lambda: 1)
        assert gdn_module.scan_takes_kernels(96, 192, 64)


def test_off_the_tpu_the_mixer_keeps_the_plain_rule():
    if jax.default_backend() != "cpu":
        pytest.skip("for a host without a TPU")
    assert not gdn_module.scan_takes_kernels(96, 192, 64)
    assert not gdn_module.scan_takes_kernels(96, 192, 64, _mesh(dp=1))


def test_on_a_mesh_each_chip_walks_its_own_sequences():
    """dp = 2: the rows over dp in a ``shard_map``, the same values and
    gradients as one device's."""
    mesh, args = _mesh(dp=2), _inputs(b=2, s=64, d_k=32, d_v=64, seed=7)
    rows = NamedSharding(mesh, P("dp"))
    got = _value_and_grads(
        lambda *a: gdn_chunked(*a, 64, kernels=True, mesh=mesh),
        tuple(jax.device_put(a, rows) for a in args))
    want = _value_and_grads(
        lambda *a: gdn_chunked(*a, 64, kernels=True), args)
    assert _rel(got[0], want[0]) < 1e-6
    for a, b in zip(got[1], want[1]):
        assert _rel(a, b) < 1e-6


# ------------------------------------------------------------ the gauges

GAUGES = ("gdn/scan_kernel_layers", "gdn/state_kernel_layers",
          "gdn/kept_inverse_mib")


def _reported(cfg, caplog, tokens=4096):
    with caplog.at_level(logging.INFO, logger="raydp_tpu.models.gdn"):
        gdn_module.report(cfg, tokens_per_step=tokens)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "raydp_tpu.models.gdn"]
    return tuple(metrics.gauge_value(g) for g in GAUGES), lines


def test_the_gauges_say_the_kernels_run_in_the_published_stage(
        as_on_a_tpu, caplog):
    gauges, (line,) = _reported(olmo_hybrid_7b(n_layers=4), caplog)
    # 3 layers × 30 heads × 4,096 tokens × 64 × 4 bytes.
    assert gauges == (3, 3, 90)
    assert gdn_ops.IMPLEMENTATION[True] in line
    assert "gdn_state_forward" in line and "90 MiB of chunk inverses" in line
    # The count ISSUE 63 pinned is of the output and the segments' states.
    assert metrics.gauge_value("gdn/kept_bytes_per_sequence") == (
        3 * 30 * 192 * (2 * 4096 + 4 * 2 * 96))


@pytest.mark.parametrize("case", ["off_the_tpu", "a_short_chunk", "no_gdn"])
def test_the_gauges_read_zero_where_the_plain_rule_runs(
        case, caplog, request):
    if case != "off_the_tpu":
        request.getfixturevalue("as_on_a_tpu")
    cfg = olmo_hybrid_7b(n_layers=4, **(
        dict(layer_types=("attention:swiglu",) * 4) if case == "no_gdn"
        else {}))
    gauges, lines = _reported(
        cfg, caplog, tokens=160 if case == "a_short_chunk" else 4096)
    assert gauges == (0, 0, 0)
    if case != "no_gdn":
        assert gdn_ops.IMPLEMENTATION[False] in lines[0]
        assert "gdn_state_forward" not in lines[0]


# ------------------------------------------------------ on a device mesh

def _tiny(mesh=None, layers=("gdn:swiglu", "attention:swiglu")):
    """A stack of width 64 whose scan the kernels take at 256 tokens: two
    heads with keys of 32 and values of 64, chunks of 64."""
    return CausalLM(olmo_hybrid_7b(
        vocab_size=128, d_model=64, n_heads=2, n_layers=len(layers), d_ff=128,
        max_len=256, layer_types=layers, mesh=mesh,
        gdn=GDNConfig(heads=2, key_dim=32, value_dim=64, chunk=64)))


def _gradient_lowered_for_a_tpu(mesh, told: bool) -> str:
    """A tiny stack's gradient over ``mesh`` (rows over dp, the
    parameters whole), lowered for a TPU: nothing is compiled."""
    model = _tiny(mesh if told else None)
    ids = jax.ShapeDtypeStruct(
        (2, 256), jnp.int32, sharding=NamedSharding(mesh, P("dp")))
    params = _abstract_parameters(model, NamedSharding(mesh, P()))

    def loss(params, ids):
        return model.apply(params, ids).astype(F32).sum()

    return jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_gradient_lowers_for_two_chips(monkeypatch):
    """What XLA refuses ("Mosaic kernels cannot be automatically
    partitioned") is never asked of it: with the mesh told the scan's
    kernels sit in a ``shard_map``, without it the step keeps the plain
    rule."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = _mesh(dp=2)
    text = _gradient_lowered_for_a_tpu(mesh, told=True)
    assert all(name in text for name in KERNELS)
    text = _gradient_lowered_for_a_tpu(mesh, told=False)
    assert "tpu_custom_call" not in text
    # Heads split over tp: the scan in the form XLA partitions over them.
    text = _gradient_lowered_for_a_tpu(_mesh(dp=2, tp=2), told=True)
    assert not any(name in text for name in KERNELS)
    # The call as one chip makes it, in a step laid over two.
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    with pytest.raises(NotImplementedError, match="partitioned"):
        _gradient_lowered_for_a_tpu(mesh, told=False)


# ------------------------------------------- what lowering a step costs

def _lowered(s, h, sites=3, d_k=96, d_v=192):
    """The TPU lowering (nothing is compiled) of a gradient through
    ``sites`` scans of one shape, one after the other as a stage's layers
    are, as text."""
    like = jax.ShapeDtypeStruct
    site = (like((1, s, h, d_k), BF16), like((1, s, h, d_k), BF16),
            like((1, s, h), F32), like((1, s, h), F32))

    def loss(v, sites):
        for at, (q, k, g, beta) in enumerate(sites):
            with jax.named_scope(f"site_{at}"):
                v = gdn_chunked(q, k, v, g, beta, 64, kernels=True)
        return (v.astype(F32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).trace(
        like((1, s, h, d_v), BF16), [site] * sites
    ).lower(lowering_platforms=("tpu",)).as_text()


def test_the_stages_three_layers_share_one_kernel_body_a_shape(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _lowered(4096, 30)
    # Forward, rebuild and gradient of a chunk; the state forward with and
    # without what the backward reads, and the state backward: six Mosaic
    # bodies for the three layers' fifteen call sites.
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 6
    for name, sites in (("_forward_call", 6), ("_backward_call", 3),
                        ("_state_forward_call", 6),
                        ("_state_backward_call", 3)):
        assert text.count(f"call @{name}") == sites, name


def test_the_lowered_text_does_not_grow_with_the_sequence(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    short, long = (_lowered(s, 30) for s in (4096, 16384))
    assert abs(len(long) - len(short)) <= 0.03 * len(short)
    # Nor with the heads: a group is six of them, however many there are.
    wide = _lowered(4096, 60)
    assert abs(len(wide) - len(short)) <= 0.05 * len(short)
    # The plain rule's text for the same three layers is 334 kB.
    assert len(short) < 250_000


# ------------------------------------- the walk, as it was before PR 68

# sha256 of the traced gradient's text at the cell's sequence (4,096 tokens:
# two segments of 32 chunks) and a group's six heads of 96 / 192, by either
# rule, at the parent of the PR that handed a rule its layout (PR 68:
# ``ops/kda.KERNELS`` reads the model's arrays in place). A jaxpr's text
# holds the kernels' bodies and no source location. Both of this module's
# rules are ``chunk_major`` ones, whose loops scan the segments' slices as
# ``segment_walk`` did: what Olmo-Hybrid's cell lowers is what it was.
PINNED = {
    "plain": "3e0797324323213cfee236433abd08c8"
             "ebd2685b623f700d0d619c46617750fb",
    "kernels": "e3e6eea440f94943a5a010c878520826"
               "816838a172f8a7d35d59f5f0a2667e77",
}


@pytest.mark.parametrize("rule", list(PINNED))
def test_the_walks_traced_program_is_the_pinned_one(rule):
    like = jax.ShapeDtypeStruct
    keys = like((1, 4096, 6, 96), BF16)
    args = (keys, keys, like((1, 4096, 6, 192), BF16),
            like((1, 4096, 6), F32), like((1, 4096, 6), F32))

    def loss(*a):
        return jnp.sum(gdn_chunked(
            *a, 64, kernels=rule == "kernels").astype(F32) ** 2)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(5))))(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[rule]


# --------------------------------------------------- the script for the chip

def test_the_chips_script_measures_every_form():
    """``scripts/gdn_on_chip.py`` at a tiny shape: it cannot rot unseen
    (its times mean something on a TPU only)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts"))
    try:
        import gdn_on_chip
    finally:
        sys.path.pop(0)
    found = gdn_on_chip.measure(
        (64, 2, 96, 192, 64), repeats=1, dtype=F32, heads_a_step=(2,))
    assert set(found) == {
        "kernels", "keys_128", "jnp", "apart", "alone", "heads_a_step"}
    assert set(found["alone"]) == set(KERNELS)
    assert set(found["heads_a_step"]) == {"2"}
    for form in ("kernels", "keys_128"):
        assert max(found["apart"][form].values()) < 2e-5
    assert gdn_on_chip.least_bytes(gdn_on_chip.OLMO) == (
        3 * 4096 * 30 * (2 * 96 * 2 + 2 * 192 * 2 + 8))
    assert gdn_ops.HEADS_A_STEP == 6
    if jax.default_backend() != "tpu":
        # Off the chip it measures nothing under a chip's name.
        assert gdn_on_chip.main([]) == 3
