"""The "sparse" mixer's index branch, its record and its reports.

A "sparse" layer is ``models/transformer.MultiHeadAttention`` (module
``attn``: the stack's grouped heads, norms a head, rotation and output
projection) whose attention runs over a LEARNED selection of keys
(DeepSeek-V3.2-Exp's sparse attention, the ``sa_config`` of a published
``config.json``). Beside the attention's own projections the layer has an
INDEX BRANCH (:class:`IndexBranch`, module ``attn/index``), a small second
attention whose only output is a ranking:

    hd = stop_gradient(h)                       the layer's normed input
    qI = rotate(hd · WqI)                       [S, Hi, Di]
    kI = rotate(LayerNorm(hd · WkI))            [S, Di]   ONE index key head
    w  = hd · Ww · Hi^-0.5 · Di^-0.5            [S, Hi]   float32
    I[t, s] = Σ_j w[t, j] · ReLU(qI[t, j] · kI[s])         (s ≤ t)

Query ``t`` keeps its ``topk`` best-scored causal keys (all of them where
there are fewer; all tied keys under a tie at the threshold), one set for
all heads; attention runs over that set and the branch is trained to
predict the attention it thinned: the layer's INDEX LOSS is the mean over
queries of ``KL(p ‖ r)`` over the selection, ``p`` the heads' mean
attention probability (detached) and ``r`` the softmax of ``I``. It is
sown into the ``losses`` collection that ``JAXEstimator(aux_losses=True)``
adds to the step's loss. By the two detachments the language-model loss
reaches every parameter but the branch's and the index loss the branch's
alone (the sparse stage of the published training rule; the dense warm-up
stage is ROADMAP R13).

Scopes: ``attn/index`` (the three projections, the norm, the rotation and
the score kernel), ``attn/select`` (the threshold), ``attn/sparse``
(attention over the selection, forward and backward, the index branch's
backward among the latter), ``attn/index_loss``. The kernels are
``ops/sparse_attention.py``'s.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.models import stats

logger = logging.getLogger(__name__)

# One step's statistics, summed over the layers and an epoch's steps: the
# selected and the causal pairs, the queries whose selection passed
# ``topk`` by ties, the layers' index losses, and the layers counted.
SELECTED = stats.declare("index_selected_pairs")
CAUSAL = stats.declare("index_causal_pairs")
OVERFULL = stats.declare("index_overfull_queries")
KL = stats.declare("index_kl")
LAYER_STEPS = stats.declare("index_layer_steps")


@dataclasses.dataclass(frozen=True)
class SparseIndexConfig:
    """The "sparse" mixer's own sizes (``sa_config``'s six)."""

    index_heads: int = 16
    index_head_dim: int = 64
    index_kv_heads: int = 1
    topk: int = 2048
    # The sizes in which the released code makes scores and selections
    # piecewise; they change no equation (the kernels have their tiles).
    q_chunk: int = 512
    kv_chunk: int = 512

    def __post_init__(self):
        if self.index_kv_heads != 1:
            raise NotImplementedError(
                "the index branch has ONE key head all its query heads read"
            )


class IndexBranch(nn.Module):
    """``(qI [B, S, Hi, Di], kI [B, S, Di], w [B, S, Hi] float32)`` from
    the layer's normed input: no gradient reaches that input from here.
    The index head is rotated whole by ``positions`` ([B or 1, S]: the
    temporal id of a token) at the model's theta."""

    cfg: Any
    sparse: SparseIndexConfig

    @nn.compact
    def __call__(self, h, positions):
        from raydp_tpu.models.transformer import _dense_init, rotary

        cfg, sp = self.cfg, self.sparse
        hd = jax.lax.stop_gradient(h)
        q = nn.DenseGeneral(
            features=(sp.index_heads, sp.index_head_dim), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=_dense_init("embed", "heads", "kv"), name="wq",
        )(hd)
        k = nn.DenseGeneral(
            features=sp.index_head_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=_dense_init("embed", "kv"), name="wk",
        )(hd)
        k = nn.LayerNorm(
            epsilon=cfg.norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="k_norm",
        )(k)
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        # The head weights in float32 from operands of the compute dtype.
        kernel = self.param(
            "weights", _dense_init("embed", "heads"),
            (h.shape[-1], sp.index_heads), cfg.param_dtype,
        )
        w = jnp.einsum(
            "bsd,dh->bsh", hd.astype(cfg.dtype), kernel.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ) * (sp.index_heads ** -0.5 * sp.index_head_dim ** -0.5)
        return q, k, w


def attend(module: nn.Module, sparse: SparseIndexConfig, q, k, v, q_idx,
           k_idx, w, scale):
    """Attention of ``module``'s rotated ``q``, ``k``, ``v`` over the
    index branch's selection; sows the layer's index loss and statistics
    from inside ``module``. The scopes ``index`` (the score kernel),
    ``select`` and ``sparse`` are the operation's own."""
    from raydp_tpu.ops.sparse_attention import sparse_attention

    out, kl, count = sparse_attention(
        q, k, v, q_idx, k_idx, w, sparse.topk, scale
    )
    with jax.named_scope("index_loss"):
        loss = jnp.mean(kl)
        module.sow(
            "losses", "index_kl", loss,
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        count = jax.lax.stop_gradient(count)
        b, s = count.shape
        stats.sow(module, SELECTED, count.sum())
        stats.sow(module, CAUSAL, jnp.float32(b * s * (s + 1) / 2))
        stats.sow(
            module, OVERFULL, (count > sparse.topk).sum().astype(jnp.float32)
        )
        stats.sow(module, KL, jax.lax.stop_gradient(loss))
        stats.sow(module, LAYER_STEPS, jnp.float32(1.0))
    return out


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "sparse")


def report(cfg, seq_len: Optional[int] = None) -> None:
    """Static for a compiled step: gauges and one log line where the step
    is built (as ``models/window.report``): the layers, the selection's
    size, the index heads; and, given the step's sequence length (the
    estimator gives it), the layers whose backward is the one kernel
    (``ops/sparse_attention.backward_is_fused``, the rule the call itself
    takes) and what that kernel keeps resident. Without a length the rule
    cannot be asked and those two gauges are left as they are. Zero for a
    stack without sparse layers, which never imports the kernels."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    sp = cfg.sparse if layers else None
    # (the one kernel?, its resident MiB); None where the rule is not asked.
    rule = None if sp else (False, 0)
    backward = "chosen by the call's length"
    if sp and seq_len is not None:
        from raydp_tpu.ops import sparse_attention as op

        call = (seq_len, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                sp.index_heads, sp.index_head_dim, cfg.dtype)
        fused = op.backward_is_fused(*call)
        rule = fused, fused * op.fused_backward_vmem(*call)[0] / 2 ** 20
        backward = (
            f"one kernel (a tile's mask, P and dP feed all six gradients; "
            f"dk, dv and dkI of the {seq_len} positions resident, "
            f"{rule[1]:.0f} MiB)" if fused else
            "the dq and dk/dv kernels (the one kernel's resident gradients "
            "do not fit VMEM)")
    metrics.gauge_set("attention/sparse_layers", layers)
    metrics.gauge_set("attention/index_topk", sp.topk if sp else 0)
    metrics.gauge_set("attention/index_heads", sp.index_heads if sp else 0)
    if rule is not None:
        metrics.gauge_set(
            "attention/sparse_fused_bwd_layers", layers * rule[0])
        metrics.gauge_set("attention/sparse_bwd_resident_mib", rule[1])
    if sp:
        logger.info(
            "sparse attention: %d layers, %d index heads of %d over %d index "
            "key head score every causal pair, a query keeps its %d best "
            "keys (all tied ones at the threshold), %d query heads over %d "
            "key-value heads of %d attend over them; the layers' index "
            "losses go into the step's loss; positions %s; the backward is "
            "%s",
            layers, sp.index_heads, sp.index_head_dim, sp.index_kv_heads,
            sp.topk, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.positions if cfg.positions != "mrope" else
            f"mrope {tuple(cfg.mrope_section)} (text ids where none given)",
            backward,
        )


def report_epoch(sown: dict) -> None:
    """Gauges from an epoch's statistics (summed on the device, fetched
    with the epoch's loss): ``attn/selected_share`` (selected over causal
    pairs), ``attn/select_overfull_queries`` (queries of the epoch whose
    selection passed ``topk`` by ties) and ``attn/index_kl`` (the mean
    index loss a layer and step). Zero for a model without a sparse layer
    that sows other statistics; untouched (zero) for one that sows none."""
    from raydp_tpu.utils.profiling import metrics

    steps = float(sown.get(LAYER_STEPS, 0.0))
    metrics.gauge_set(
        "attn/selected_share",
        float(sown[SELECTED]) / float(sown[CAUSAL]) if steps else 0.0,
    )
    metrics.gauge_set(
        "attn/select_overfull_queries",
        float(sown[OVERFULL]) if steps else 0.0,
    )
    metrics.gauge_set(
        "attn/index_kl", float(sown[KL]) / steps if steps else 0.0
    )
