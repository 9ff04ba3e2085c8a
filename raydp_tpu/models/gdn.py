"""The Gated DeltaNet mixer (arXiv:2412.06464, as its public ``fla``
layer has it under the ``linear_`` keys of a hybrid's config): the linear
attention a hybrid stack puts where three of four attention layers were,
and the layer that carries the stack's positions.

    q̃, k̃, v = silu(conv4(x W_q)), silu(conv4(x W_k)), silu(conv4(x W_v))
    q = q̃ / ‖q̃‖₂ · d_k^-1/2,   k = k̃ / ‖k̃‖₂              (a head's d_k values)
    g = −exp(A_log_h) · softplus(x W_a + dt_bias)_h           (ONE scalar a head, ≤ 0)
    β = σ(x W_b)_h,  doubled with ``neg_eigval``              (in (0, 2))
    S̄ = e^{g_t} S_{t−1};  S_t = S̄ + β_t k_t (v_t − S̄ᵀ k_t)ᵀ;  o_t = S_tᵀ q_t
    out = W_o concat_h( rms(o_h) · w ⊙ silu((x W_g)_h) )

A head's keys (``key_dim``) and values (``value_dim``) differ in width;
the state is ``key_dim × value_dim`` float32 a head. Beside
``models/kda.py`` (a decay a CHANNEL through a low-rank pair, a sigmoid
gate through another): the convolutions with their L2 norms
(``QKVConv``) and the gated norm a head (``HeadGatedRMSNorm``) are that
module's. Module paths (``gdn`` in a block): ``gdn/{q_proj, k_proj,
v_proj, conv, decay, beta, scan, g_proj, gate_norm, out}``; the
recurrence itself is ``ops/gdn.py``.
"""
from __future__ import annotations

import dataclasses
import logging
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.models.kda import HeadGatedRMSNorm, QKVConv
from raydp_tpu.models.mamba import (
    _decay_rate_init,
    _kernels_may_run,
    _replicated,
    _step_bias_init,
    conv_takes_kernel,
)
from raydp_tpu.ops import gdn as gdn_ops
from raydp_tpu.ops import kda as kda_ops

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    """The "gdn" mixer's own sizes (the norm's epsilon is the stack's)."""

    heads: int = 30
    key_dim: int = 96                # a head's q and k
    value_dim: int = 192             # a head's v and o
    conv_taps: int = 4
    chunk: int = 64
    neg_eigval: bool = True          # β in (0, 2): ``2 σ(·)``

    def state_bytes(self, layers: int) -> int:
        """What a sequence's float32 states hold, all layers."""
        return 4 * layers * self.heads * self.key_dim * self.value_dim

    def scan_chunk(self, sequence: int) -> int:
        """The chunk a sequence runs in: one that is no multiple of
        ``chunk`` (a test's) takes the largest power of two that divides
        both."""
        return math.gcd(self.chunk, sequence)

    def kept_bytes(self, layers: int, sequence: int) -> int:
        """What the scans of one sequence keep for a checkpointed block's
        backward besides their inputs (``ops/gdn.KEPT``), all layers: the
        output in the compute dtype's two bytes and the float32 state
        each segment was entered with."""
        chunks = sequence // self.scan_chunk(sequence)
        segments = chunks // math.gcd(chunks, kda_ops.SEGMENT_CHUNKS)
        return layers * self.heads * self.value_dim * (
            2 * sequence + 4 * segments * self.key_dim
        )

    def kept_inverse_bytes(self, layers: int, sequence: int) -> int:
        """What the chunks' float32 triangular inverses of one sequence
        hold, all layers, where the scan's kernels keep them (the third
        of ``ops/gdn.KEPT``); the ``jax.numpy`` form keeps none."""
        return 4 * layers * self.heads * sequence * self.scan_chunk(sequence)


def scan_takes_kernels(key_dim: int, value_dim: int, chunk: int,
                       mesh=None) -> bool:
    """Whether a mixer's scan of these shapes runs as the Pallas kernels
    of ``ops/gdn.py``: where a Mosaic kernel may stand at all
    (``models/mamba._kernels_may_run``: on a TPU, the program one device's
    or the model's ``mesh`` told), the mesh does not split the heads and
    ``ops/gdn.uses_kernels`` takes the shapes. With a ``mesh`` the call is
    ``gdn_chunked(mesh=)``'s ``shard_map`` over ``dp``; with ``tp`` > 1
    every chip of a group would gather the projections whole and scan all
    the heads, where XLA partitions the ``jax.numpy`` form over them, so
    such a mesh keeps that form (as ``mamba.scan_takes_kernels``; no cell
    runs this stack on four chips). Everywhere else (``model.init``'s
    sample, whose chunk is its one token, among them) the plain rule
    runs, which the compiler partitions as it did."""
    return (
        _kernels_may_run(mesh)
        and (mesh is None or mesh.shape.get("tp", 1) == 1)
        and gdn_ops.uses_kernels(key_dim, value_dim, chunk)
    )


class ScalarDecay(nn.Module):
    """``g = −exp(A_log_h) · softplus(x W_a + dt_bias)_h``: one projection
    to a value a head, ``A_log`` and ``dt_bias`` one scalar a head,
    float32 after the product. Returns [B, S, H]."""

    gdn: GDNConfig
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        heads = self.gdn.heads
        a = nn.Dense(
            heads, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="proj",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("embed", "heads")),
        )(x)
        a_log = self.param(
            "A_log", _replicated(_decay_rate_init), (heads,),
            self.param_dtype,
        )
        dt_bias = self.param(
            "dt_bias", _replicated(_step_bias_init), (heads,),
            self.param_dtype,
        )
        step = jax.nn.softplus(
            a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
        )
        return -jnp.exp(a_log.astype(jnp.float32)) * step


class GatedDeltaMixer(nn.Module):
    """``cfg`` is a ``TransformerConfig`` with ``gdn`` set. Input
    ``[B, S, d_model]`` → output ``[B, S, d_model]``."""

    cfg: object

    @nn.compact
    def __call__(self, x):
        cfg, gdn = self.cfg, self.cfg.gdn
        if not cfg.causal:
            raise ValueError("a delta-rule state runs over earlier tokens")
        keys, values = gdn.heads * gdn.key_dim, gdn.heads * gdn.value_dim
        init = nn.initializers.xavier_uniform()

        def dense(features, name, axes=("embed", "heads")):
            return nn.Dense(
                features, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name,
                kernel_init=nn.with_logical_partitioning(init, axes),
            )

        q, k, v = QKVConv(
            gdn, cfg.dtype, cfg.param_dtype, mesh=cfg.mesh, name="conv",
        )(
            dense(keys, "q_proj")(x), dense(keys, "k_proj")(x),
            dense(values, "v_proj")(x),
        )
        g = ScalarDecay(gdn, cfg.dtype, cfg.param_dtype, name="decay")(x)
        beta = dense(gdn.heads, "beta")(x)
        with jax.named_scope("beta"):
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
            if gdn.neg_eigval:
                beta = 2.0 * beta
        chunk = gdn.scan_chunk(x.shape[-2])
        with jax.named_scope("scan"):
            o = gdn_ops.gdn_chunked(
                q, k, v, g, beta, chunk, mesh=cfg.mesh,
                kernels=scan_takes_kernels(
                    gdn.key_dim, gdn.value_dim, chunk, cfg.mesh),
            )
        o = HeadGatedRMSNorm(
            cfg.norm_eps, cfg.dtype, cfg.param_dtype,
            activation=jax.nn.silu, name="gate_norm",
        )(o, dense(values, "g_proj")(x))
        return dense(cfg.d_model, "out", ("heads", "embed"))(
            o.reshape(*o.shape[:-2], values)
        )


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "gdn")


def report(cfg, tokens_per_step: int, sequence: int = 0) -> None:
    """Static for a compiled step: nine gauges and one log line where the
    step is built (as ``models/kda.report``). All zero for a stack
    without such layers, and the three of the scan's kernels where the
    plain rule runs. ``sequence`` is a sequence's tokens (all of a step's
    where left out)."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    gdn = cfg.gdn if layers else None
    sequence = sequence or tokens_per_step
    chunks = layers * -(-tokens_per_step // gdn.chunk) if gdn else 0
    kept = gdn.kept_bytes(layers, sequence) if gdn else 0
    kernels = bool(gdn) and scan_takes_kernels(
        gdn.key_dim, gdn.value_dim, gdn.scan_chunk(sequence), cfg.mesh)
    inverses = gdn.kept_inverse_bytes(layers, sequence) if kernels else 0
    metrics.gauge_set("gdn/scan_kernel_layers", layers if kernels else 0)
    metrics.gauge_set("gdn/state_kernel_layers", layers if kernels else 0)
    metrics.gauge_set("gdn/kept_inverse_mib", inverses >> 20)
    metrics.gauge_set("gdn/layers", layers)
    metrics.gauge_set("gdn/heads", gdn.heads if gdn else 0)
    metrics.gauge_set("gdn/chunk", gdn.chunk if gdn else 0)
    metrics.gauge_set("gdn/chunks_per_step", chunks)
    metrics.gauge_set(
        "gdn/state_bytes_per_sequence", gdn.state_bytes(layers) if gdn else 0
    )
    metrics.gauge_set("gdn/kept_bytes_per_sequence", kept)
    if gdn:
        convs = {
            name: "kernel" if conv_takes_kernel(
                sequence, gdn.heads * width, gdn.conv_taps, cfg.dtype,
                jnp.float32, mesh=cfg.mesh,
            ) else "jax.numpy"
            for name, width in (("q", gdn.key_dim), ("k", gdn.key_dim),
                                ("v", gdn.value_dim))
        }
        logger.info(
            "gated delta-rule stack: layers %s; %d heads of %d (q, k) and "
            "%d (v), one decay a head, beta in (0, %d), %d-tap convolutions "
            "(%s); chunk %d (%d chunks a step); scan: %s; a checkpointed "
            "block keeps %d MiB of outputs and segment states and %d MiB of "
            "chunk inverses a sequence",
            " ".join(cfg.kinds), gdn.heads, gdn.key_dim, gdn.value_dim,
            2 if gdn.neg_eigval else 1, gdn.conv_taps,
            ", ".join(f"{name}: {path}" for name, path in convs.items()),
            gdn.chunk, chunks, gdn_ops.IMPLEMENTATION[kernels], kept >> 20,
            inverses >> 20,
        )
