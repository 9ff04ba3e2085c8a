"""The Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060) as the
``transformers`` ``GraniteMoeHybrid``/``Bamba`` modelling code has it: the
state-space layer a hybrid stack puts where most of its attention was.

    [z, xBC, dt] = W_in y
    xBC          = silu(conv1d_causal_depthwise(xBC, k) + b)       (``conv``)
    x, B, C      = split(xBC)
    dt           = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t          = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t;  y_t = h_t C_t + D x_t
    out          = W_out (rms(y · silu(z)) · w)

The gate goes in BEFORE the norm, which runs over each of the
``ssm_groups`` groups of ``heads × head_dim / ssm_groups`` features on its
own (the released code's ``group_size``; all the features where the B and
C of every head are one group). Module paths:
``mamba/{in_proj,conv,ssd,gate_norm,out_proj}``; the scan itself is
``ops/ssd.py``.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.ops import causal_conv, ssd

logger = logging.getLogger(__name__)

SCAN_IMPLEMENTATION = (
    "chunked state-space dual form (ops/ssd.py): two Pallas kernels, "
    "forward and backward, that hold a chunk's decay matrix and the states "
    "in VMEM and read x, B and C out of the convolution's one result, on a "
    "TPU where a head's channels are a multiple of 64, the state and the "
    "chunk multiples of 128 and a group's heads go in blocks of eight; the "
    "same form in jax.numpy otherwise"
)
CONV_IMPLEMENTATION = "shifted multiply-adds"


def _replicated(init):
    return nn.with_logical_partitioning(init, (None,))


def _decay_rate_init(key, shape, dtype):
    """``A_log`` with ``A = exp(A_log)`` uniform in [1, 16], as published."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _step_bias_init(key, shape, dtype, lo=1e-3, hi=1e-1):
    """``dt_bias`` with ``softplus(dt_bias)`` log-uniform in [lo, hi] (the
    inverse softplus of the draw), as published: decays neither 0 nor 1."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(lo), math.log(hi)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(taps: int):
    """``torch.nn.Conv1d``'s default for a depthwise kernel of ``taps``
    taps, weight and bias alike: uniform in ±1/√taps."""
    bound = 1.0 / math.sqrt(taps)

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def causal_depthwise_conv(x, kernel, bias=None):
    """``out_t = bias + Σ_j kernel[j] · x_{t-(k-1)+j}`` over the sequence
    axis of ``x`` [B, S, channels], zeros before the first token: ``k``
    shifted multiply-adds in float32 (``kernel`` [k, channels]), the form
    measurement pinned for 4 taps (PERF.md §6, PR 30) and for the 3 taps of
    ``models/shortconv.py`` (PR 32). Returns float32."""
    taps, s = kernel.shape[0], x.shape[-2]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = None if bias is None else bias.astype(jnp.float32)
    for j in range(taps):
        term = kernel[j].astype(jnp.float32) * padded[
            :, j:j + s
        ].astype(jnp.float32)
        out = term if out is None else out + term
    return out


def _kernels_may_run(mesh) -> bool:
    """Whether a Mosaic kernel may stand in this program: on a TPU, and
    where the compiler is not left to partition the call (it cannot). The
    program has to be one device's (no ``mesh`` told and one device here)
    or the model's ``mesh`` has to be told, over whose ``dp`` the call is
    then laid by a ``shard_map``; a mesh that splits the sequence (``sp``
    > 1) would gather it whole for the kernel."""
    if jax.default_backend() != "tpu":
        return False
    if mesh is None:
        return jax.device_count() == 1
    return mesh.shape.get("sp", 1) == 1


def conv_takes_kernel(sequence: int, channels: int, taps: int, x_dtype,
                      out_dtype, sequence_minor: bool = False,
                      mesh=None, unit=None) -> bool:
    """Whether a :class:`CausalConv1d` call of these shapes runs as the
    Pallas kernels of ``ops/causal_conv.py``: where a Mosaic kernel may
    stand at all (:func:`_kernels_may_run`; with a ``mesh`` the call is
    ``causal_conv_silu(mesh=)``'s ``shard_map``) and
    ``causal_conv.uses_kernel`` takes the shapes. Everywhere else, and at
    a shape the kernels decline (a
    decode step's single token among them), the call is
    :func:`causal_depthwise_conv` and ``jax.nn.silu``, which the compiler
    partitions as it did. ``unit`` (``causal_conv.Unit``) asks for the
    kernels with each head's L2 norm inside."""
    return _kernels_may_run(mesh) and causal_conv.uses_kernel(
        sequence, channels, taps, x_dtype, out_dtype, sequence_minor, unit
    )


class CausalConv1d(nn.Module):
    """Depthwise causal convolution over the sequence with a bias (none
    with ``use_bias`` off), and the SiLU that follows it: ``silu(b + Σ_j
    w_j · in_{t-(k-1)+j})``, zeros before the first token (``kernel``
    [k, channels]). One Pallas kernel forward and one backward where
    :func:`takes_kernel` says so, the ``jax.numpy`` form elsewhere: the
    same float32 arithmetic either way. ``sequence_minor`` tells the
    kernels which layout the compiler gives the arrays around the call
    (``ops/causal_conv.py``): the caller's knowledge, not a choice of
    result. ``mesh`` is the model's (``cfg.mesh``), for a step compiled
    for more than one device. ``unit`` (``causal_conv.Unit``) has the
    kernels write each head L2-normalised and times ``scale``: theirs
    alone, so the caller sets it only where :func:`conv_takes_kernel` took
    the call with it (``models/kda.QKVConv``)."""

    taps: int
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    use_bias: bool = True
    sequence_minor: bool = False
    mesh: Any = None
    unit: Any = None
    scale: float = 1.0

    @nn.compact
    def __call__(self, x):
        channels = x.shape[-1]
        init = _conv_init(self.taps)
        kernel = self.param(
            "kernel", nn.with_logical_partitioning(init, (None, None)),
            (self.taps, channels), self.param_dtype,
        )
        bias = self.param(
            "bias", _replicated(init), (channels,), self.param_dtype,
        ) if self.use_bias else None
        if takes_kernel(self, x):
            return causal_conv.causal_conv_silu(
                x, kernel, bias, dtype=self.dtype,
                sequence_minor=self.sequence_minor, mesh=self.mesh,
                unit=self.unit, scale=self.scale,
            )
        if self.unit is not None:
            raise ValueError(
                f"no kernel holds the norm {self.unit} of x {x.shape} "
                f"{x.dtype}: ask conv_takes_kernel first"
            )
        return jax.nn.silu(
            causal_depthwise_conv(x, kernel, bias)
        ).astype(self.dtype)


def takes_kernel(conv: CausalConv1d, x) -> bool:
    """The form ``conv`` runs as on ``x``: :func:`conv_takes_kernel` of
    the call's shapes. The module's own decision and the census's
    (:func:`counting_convs`) are this one function."""
    return x.ndim == 3 and conv_takes_kernel(
        x.shape[1], x.shape[-1], conv.taps, x.dtype, conv.dtype,
        conv.sequence_minor, conv.mesh, conv.unit,
    )


def _counting(kind, takes, *marks):
    """``(interceptor, read)``: a flax method interceptor that counts the
    calls of modules of ``kind`` made under it by the form each takes
    (``takes(module, *operands)``), and the function that reads ``(kernel
    calls, jax.numpy calls)`` afterwards (as ``models/dropout.counting``);
    after those two, the kernel calls each of ``marks(module)`` holds
    for."""
    found = [0, 0] + [0] * len(marks)

    def count(next_fun, args, kwargs, context):
        if (isinstance(context.module, kind)
                and context.method_name == "__call__"):
            taken = takes(context.module, *args, *kwargs.values())
            found[0 if taken else 1] += 1
            for at, mark in enumerate(marks, 2):
                found[at] += bool(taken and mark(context.module))
        return next_fun(*args, **kwargs)

    return count, lambda: tuple(found)


def counting_convs():
    """The census of the :class:`CausalConv1d` calls, a Mamba-2 mixer's
    and a delta-rule layer's alike (:func:`_counting`): ``(kernel calls,
    jax.numpy calls, the kernel calls that hold the L2 norm)``."""
    return _counting(
        CausalConv1d, takes_kernel, lambda conv: conv.unit is not None)


def scan_takes_kernels(sequence: int, heads: int, head_dim: int, groups: int,
                       state: int, chunk: int, mesh=None) -> bool:
    """Whether a :class:`SelectiveScan` call of these shapes runs as the
    Pallas kernels of ``ops/ssd.py``: where a Mosaic kernel may stand at
    all (:func:`_kernels_may_run`), the mesh does not split the heads,
    ``ssd.uses_kernels`` takes the shapes and the sequence is whole
    chunks. The kernels' ``shard_map`` lays the rows over ``dp`` and
    nothing else: with ``tp`` > 1 every chip of a ``tp`` group would
    gather ``in_proj``'s product whole and scan all the heads, where XLA
    partitions the ``jax.numpy`` form over them, so such a mesh keeps
    that form (not measured either way: no cell runs a Mamba stack on
    four chips). Everywhere else (``model.init``'s one-chunk sample among
    them) the call is ``ssd.ssd_chunked``, which the compiler partitions
    as it did."""
    return (
        _kernels_may_run(mesh)
        and (mesh is None or mesh.shape.get("tp", 1) == 1)
        and sequence % chunk == 0
        and ssd.uses_kernels(heads, groups, head_dim, state, chunk)
    )


class SelectiveScan(nn.Module):
    """The scan's own parameters (``A_log``, ``dt_bias``, ``D``, one a
    head, float32) and the call of ``ops/ssd.py`` on a mixer's convolved
    ``xbc`` [b, s, heads · p + 2 · groups · state] (``x``, ``B`` and ``C``
    side by side) and ``dt`` [b, s, heads], in the form :func:`scan_form`
    reads from the call: the kernels take ``xbc`` as it stands, the
    ``jax.numpy`` form its three parts. Returns ``y`` [b, s, heads · p].
    ``mesh`` is the model's (``cfg.mesh``), for a step compiled for more
    than one device."""

    chunk: int
    param_dtype: jnp.dtype
    groups: int
    state: int
    mesh: Any = None

    @nn.compact
    def __call__(self, xbc, dt):
        heads, (g, n) = dt.shape[-1], (self.groups, self.state)
        a_log = self.param(
            "A_log", _replicated(_decay_rate_init), (heads,), self.param_dtype
        )
        dt_bias = self.param(
            "dt_bias", _replicated(_step_bias_init), (heads,),
            self.param_dtype,
        )
        skip = self.param(
            "D", _replicated(nn.initializers.ones), (heads,), self.param_dtype
        )
        chunk, kernels = scan_form(self, xbc, dt)
        if not kernels:
            # Split before the softplus, as the mixer did before the
            # kernels: the traced step stays the program it was
            # (tests/test_granite_hybrid.py pins it).
            inner, lead = xbc.shape[-1] - 2 * g * n, xbc.shape[:-1]
            x, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            x, B, C = (x.reshape(*lead, heads, -1), B.reshape(*lead, g, n),
                       C.reshape(*lead, g, n))
        dt = jax.nn.softplus(
            dt.astype(jnp.float32) + dt_bias.astype(jnp.float32)
        )
        A = -jnp.exp(a_log.astype(jnp.float32))
        if kernels:
            return ssd.ssd_scan_packed(
                xbc, dt, A, skip, chunk, g, n, mesh=self.mesh)
        return ssd.ssd_chunked(x, dt, A, B, C, skip, chunk).reshape(
            *lead, inner)


def scan_form(scan: SelectiveScan, xbc, dt):
    """``(chunk, whether the kernels run)`` of ``scan`` on ``xbc`` and
    ``dt``. The module's own decision and the census's
    (:func:`counting_scans`) are this one function. A sequence shorter
    than a chunk (the batch-1 sample of ``model.init``, a test) is one
    chunk."""
    tokens, heads = dt.shape[-2:]
    chunk = min(scan.chunk, tokens)
    inner = xbc.shape[-1] - 2 * scan.groups * scan.state
    return chunk, inner % heads == 0 and scan_takes_kernels(
        tokens, heads, inner // heads, scan.groups, scan.state, chunk,
        scan.mesh,
    )


def counting_scans():
    """The census of the :class:`SelectiveScan` calls (:func:`_counting`)."""
    return _counting(
        SelectiveScan, lambda scan, xbc, dt: scan_form(scan, xbc, dt)[1])


class GatedRMSNorm(nn.Module):
    """``rms(y · silu(z)) · w``, float32 inside: the gate enters before
    the norm, whose mean square is taken over each of ``groups`` equal
    groups of the feature axis on its own (the whole axis with one); one
    learned weight a feature either way."""

    epsilon: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    groups: int = 1

    @nn.compact
    def __call__(self, y, z):
        features = y.shape[-1]
        if features % self.groups:
            raise ValueError(
                f"{features} features do not divide into {self.groups} groups"
            )
        scale = self.param(
            "scale", _replicated(nn.initializers.ones), (features,),
            self.param_dtype,
        )
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        if self.groups > 1:
            y = y.reshape(*y.shape[:-1], self.groups, -1)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + self.epsilon
        )
        if self.groups > 1:
            y = y.reshape(*y.shape[:-2], features)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    """``cfg`` is a ``TransformerConfig`` with the ``ssm_*`` sizes set.
    Input ``[B, S, d_model]`` → output ``[B, S, d_model]``."""

    cfg: object

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        heads, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                          cfg.ssm_groups)
        inner = heads * p
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        init = nn.initializers.xavier_uniform()
        zxbcdt = dense(
            2 * inner + 2 * g * n + heads, name="in_proj",
            kernel_init=nn.with_logical_partitioning(init, ("embed", None)),
        )(x)
        z, xbc, dt = jnp.split(
            zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1
        )
        # The compiler lays this mixer's arrays out with the sequence on
        # the lanes, in_proj's product and the convolution's result alike:
        # the chunked scan that consumes them contracts over a chunk's
        # positions, and its kernels read and write blocks with the
        # sequence on the lanes for the same reason. Read in the compiled
        # mixer at heads of 64 and of 128, one group and eight, the scan in
        # either form (PERF.md §6, PR 59 and PR 62; pinned by
        # tests/test_olmoe.py): the convolution's kernels take that.
        xbc = CausalConv1d(
            cfg.ssm_conv, cfg.dtype, cfg.param_dtype, sequence_minor=True,
            mesh=cfg.mesh, name="conv",
        )(xbc)
        y = SelectiveScan(
            cfg.ssm_chunk, cfg.param_dtype, groups=g, state=n, mesh=cfg.mesh,
            name="ssd",
        )(xbc, dt)
        y = GatedRMSNorm(
            cfg.norm_eps, cfg.dtype, cfg.param_dtype, groups=g,
            name="gate_norm",
        )(y, z)
        return dense(
            cfg.d_model, name="out_proj",
            kernel_init=nn.with_logical_partitioning(init, (None, "embed")),
        )(y)


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "mamba")


def report(cfg, tokens_per_step: int, convs=(0, 0, 0),
           scans=(0, 0)) -> None:
    """Static for a compiled step: the ``ssm/*`` gauges, the three
    ``conv/*_calls`` gauges and one log line where the step is built
    (as ``models/dropout.report``), nothing per step. All zero for a
    stack without state-space layers. ``convs`` is what
    :func:`counting_convs` read off the step's abstract apply
    (``models/step.survey``): the :class:`CausalConv1d` calls of the whole
    model, the delta-rule layers' among them, by the form each took, and
    of the kernel calls those with the L2 norm inside
    (``conv/unit_kernel_calls``: q's and k's of a delta-rule layer with
    heads of whole registers; ``models/kda.report`` logs them); ``scans``
    what :func:`counting_scans` read of the
    :class:`SelectiveScan` calls."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    kernel_calls, jnp_calls, unit_kernel_calls = convs
    metrics.gauge_set("conv/kernel_calls", kernel_calls)
    metrics.gauge_set("conv/jnp_calls", jnp_calls)
    metrics.gauge_set("conv/unit_kernel_calls", unit_kernel_calls)
    metrics.gauge_set("ssm/scan_kernel_calls", scans[0])
    metrics.gauge_set("ssm/scan_jnp_calls", scans[1])
    chunks = state = 0
    if layers:
        chunks = layers * -(-tokens_per_step // cfg.ssm_chunk)
        state = 4 * layers * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
    metrics.gauge_set("ssm/layers", layers)
    metrics.gauge_set("ssm/chunks_per_step", chunks)
    metrics.gauge_set("ssm/state_bytes_per_sequence", state)
    # The groups of B and C, and the features one mean square of the gated
    # norm runs over (all of a layer's where the group is one).
    groups = cfg.ssm_groups if layers else 0
    metrics.gauge_set("ssm/groups", groups)
    metrics.gauge_set(
        "ssm/gate_norm_group_size",
        cfg.ssm_heads * cfg.ssm_head_dim // groups if layers else 0,
    )
    if layers:
        logger.info(
            "hybrid stack: %d mamba and %d attention layers; attention %d "
            "query / %d key-value heads of %d; scan %d heads of %d in %d "
            "group(s), state %d, chunk %d (%d chunks a step); scan: %s; "
            "the stack's scans: %d by the kernels, %d in jax.numpy; "
            "convolution: %d taps; the stack's causal convolutions: %d as "
            "one Pallas kernel each way (ops/causal_conv.py), %d as %s in "
            "jax.numpy",
            layers, cfg.kinds.count("attention"), cfg.n_heads,
            cfg.kv_heads, cfg.head_dim, cfg.ssm_heads, cfg.ssm_head_dim,
            cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk, chunks,
            SCAN_IMPLEMENTATION, *scans, cfg.ssm_conv, kernel_calls, jnp_calls,
            CONV_IMPLEMENTATION,
        )
