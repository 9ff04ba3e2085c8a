"""Mixture-of-Experts FFN: top-k routing that drops no token.

New capability relative to the reference (SURVEY §2.4 "Expert parallel"
row: absent — the reference has no model code at all). The layer follows
the published OLMoE block (Muennighoff et al. 2024, arXiv:2409.02060):

* Router logits and softmax in float32 from float32 inputs, whatever the
  trunk's dtype: near-equal experts must not flip on a bf16 rounding.
* ``lax.top_k`` picks ``top_k`` of ``n_experts``; their probabilities
  weight the experts' outputs as they are (no renormalisation).
* No capacity: the ``T·k`` (token, expert) pairs are sorted by expert,
  the rows gathered into expert order, run through the SwiGLU experts
  ``down(silu(gate(x)) * up(x))`` as grouped matmuls over the stacked
  ``[E, D, F]`` weights (``ops/grouped_matmul.py``; group sizes from a
  count per expert), and brought back by a gather with the inverse permutation.
  A permutation's transpose is its inverse, so :func:`take_rows` gives the
  row gather a backward that is a gather too: neither direction is a
  scatter of ``T·k`` rows (XLA's TPU scatter is a serial loop over its
  updates, 95 ns a row: PERF.md §6, PR 25).
* Expert weights carry the logical axes ``('expert', 'embed', 'mlp')``
  (experts over ``dp``, an expert's FFN over ``tp``); no biases.
* The load-balancing loss (``E · Σ_e f_e · p_e``, weight 0.01) and the
  router z-loss (``mean(logsumexp(logits)²)``, weight 0.001) are sown into
  the ``'losses'`` collection as ``moe_aux``; pull them with
  :func:`moe_aux_loss`. The tokens each expert received are sown into
  :data:`STATS`; ``JAXEstimator`` sums them over an epoch on the device
  (:func:`step_stats`, :func:`report_epoch`).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn

from raydp_tpu.ops.grouped_matmul import IMPLEMENTATION, grouped_matmul

__all__ = [
    "MoEConfig",
    "MoELayer",
    "MoEClassifier",
    "moe_aux_loss",
    "take_rows",
    "combine_rows",
    "tiny_moe",
]

logger = logging.getLogger(__name__)

STATS = "moe_stats"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 768
    d_ff: int = 3072                 # width of one expert
    n_experts: int = 8
    top_k: int = 2
    aux_loss_weight: float = 1e-2    # load balancing
    z_loss_weight: float = 1e-3      # router z-loss
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32


def _expert_init(*logical_axes: str):
    return nn.with_logical_partitioning(
        nn.initializers.xavier_uniform(), logical_axes
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(x, perm, inverse, fan: int = 1):
    """``x[perm // fan]`` for a permutation ``perm`` of ``range(len(x) *
    fan)`` with inverse ``inverse``: every row of ``x`` goes to ``fan``
    places. The transpose of a permutation is its inverse, so the
    cotangent is ``g[inverse]`` summed over each row's ``fan`` copies — a
    gather, where ``jax.grad`` of the plain gather is a scatter-add."""
    return x[perm // fan] if fan > 1 else x[perm]


def _take_rows_fwd(x, perm, inverse, fan):
    return take_rows(x, perm, inverse, fan), inverse


def _take_rows_bwd(fan, inverse, g):
    gx = g[inverse]
    if fan > 1:
        gx = gx.reshape(-1, fan, gx.shape[-1]).sum(axis=1)
    return gx, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def combine_rows(rows, gate, order, inverse):
    """Expert-ordered ``rows`` ``[T·k, D]`` back to their tokens: token
    t's output is the sum over its k pairs of ``gate[t, j]`` times the
    pair's row, float32 inside. The cotangents need no row of a ``T·k``
    array out of order: a pair's is its token's times its gate, read from
    the ``[T, D]`` cotangent in expert order."""
    t, k = gate.shape
    pairs = rows[inverse].reshape(t, k, rows.shape[-1])
    return jnp.sum(
        pairs.astype(jnp.float32) * gate[..., None], axis=1
    ).astype(rows.dtype)


def _combine_rows_fwd(rows, gate, order, inverse):
    return combine_rows(rows, gate, order, inverse), (
        rows, gate, order, inverse
    )


def _combine_rows_bwd(res, g):
    rows, gate, order, inverse = res
    k = gate.shape[1]
    g_rows = g[order // k].astype(jnp.float32)            # [T·k, D]
    d_rows = g_rows * gate.reshape(-1)[order][:, None]
    d_gate = jnp.sum(rows.astype(jnp.float32) * g_rows, axis=-1)
    return (
        d_rows.astype(rows.dtype),
        d_gate[inverse].reshape(gate.shape).astype(gate.dtype),
        None, None,
    )


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@functools.lru_cache(maxsize=None)
def _log_once(n_experts: int, top_k: int, d_ff: int) -> None:
    logger.info(
        "MoE layer: %d experts of width %d, top-%d, no capacity; grouped "
        "matmul: %s", n_experts, d_ff, top_k, IMPLEMENTATION,
    )


class MoELayer(nn.Module):
    """Top-k routed SwiGLU experts over the trailing feature axis.

    Input ``[..., D]`` → output ``[..., D]`` in the compute dtype; tokens
    are the flattened leading axes, and every token reaches all ``top_k``
    of its experts. A float32 input reaches the router as it is.
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        lead_shape = x.shape[:-1]
        d = x.shape[-1]
        if d != cfg.d_model:
            raise ValueError(f"feature dim {d} != cfg.d_model {cfg.d_model}")
        tokens = x.reshape(-1, d)
        n_tokens = tokens.shape[0]
        e, k = cfg.n_experts, cfg.top_k
        if self.is_initializing():
            _log_once(e, k, cfg.d_ff)

        # Router in f32 regardless of trunk dtype.
        logits = nn.Dense(
            e,
            kernel_init=_expert_init("embed", None),
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            precision=jax.lax.Precision.HIGHEST,
            name="router",
        )(tokens.astype(jnp.float32))
        with jax.named_scope("router"):
            probs = jax.nn.softmax(logits, axis=-1)            # [T, E]
            _, expert = jax.lax.top_k(probs, k)                # [T, k]
            chosen = jax.nn.one_hot(expert, e, dtype=jnp.float32)
            # The chosen probabilities as a product with the one-hot
            # choice: top_k's own values would give the router's
            # gradient as a scatter of T·k updates.
            gate = jnp.einsum("te,tke->tk", probs, chosen)
            counts = chosen.sum(axis=(0, 1))                   # [E]
            # E · Σ_e f_e · p_e with f the share of the T·k pairs that
            # went to e (k at uniform routing), and the z-loss.
            balance = e * jnp.sum(
                counts / n_tokens * probs.mean(axis=0)
            )
            z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
            self.sow(
                "losses", "moe_aux",
                cfg.aux_loss_weight * balance + cfg.z_loss_weight * z,
                reduce_fn=lambda a, b: a + b,
                init_fn=lambda: jnp.zeros((), jnp.float32),
            )
            self.sow(
                STATS, "expert_tokens", counts,
                reduce_fn=lambda a, b: a + b,
                init_fn=lambda: jnp.zeros((e,), jnp.float32),
            )

        w_gate = self.param(
            "w_gate", _expert_init("expert", "embed", "mlp"),
            (e, d, cfg.d_ff), cfg.param_dtype,
        )
        w_up = self.param(
            "w_up", _expert_init("expert", "embed", "mlp"),
            (e, d, cfg.d_ff), cfg.param_dtype,
        )
        w_down = self.param(
            "w_down", _expert_init("expert", "mlp", "embed"),
            (e, cfg.d_ff, d), cfg.param_dtype,
        )

        with jax.named_scope("permute"):
            # Pair p = t·k + j is token t's j-th expert. ``order`` lists
            # the pairs by expert, ``inverse`` is each pair's place in
            # that list: two sorts, no scatter.
            pairs = jnp.arange(n_tokens * k, dtype=jnp.int32)
            _, order = jax.lax.sort_key_val(
                expert.reshape(-1).astype(jnp.int32), pairs
            )
            _, inverse = jax.lax.sort_key_val(order, pairs)
            group_sizes = counts.astype(jnp.int32)
            rows = take_rows(tokens.astype(cfg.dtype), order, inverse, k)
        with jax.named_scope("experts"):
            w_gate, w_up, w_down = (
                w.astype(cfg.dtype) for w in (w_gate, w_up, w_down)
            )
            h = jax.nn.silu(
                grouped_matmul(rows, w_gate, group_sizes)
            ) * grouped_matmul(rows, w_up, group_sizes)
            rows = grouped_matmul(h, w_down, group_sizes)
        with jax.named_scope("unpermute"):
            out = combine_rows(rows, gate, order, inverse)
        return out.reshape(*lead_shape, d)


class MoEClassifier(nn.Module):
    """Sequence classifier whose FFNs are routed MoE layers — the
    expert-parallel model family reachable straight through
    ``JAXEstimator.fit`` (pass ``aux_losses=True`` so the router's
    regularizers join the objective). The blocks are
    ``TransformerBlock``s whose FFN kind is ``moe``."""

    cfg: Any          # TransformerConfig (attention/embedding side)
    moe: MoEConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, ids, deterministic: bool = True):
        from raydp_tpu.models.transformer import (
            TransformerEncoder,
            _dense_init,
        )

        cfg = dataclasses.replace(
            self.cfg, ffn="moe", n_experts=self.moe.n_experts,
            top_k=self.moe.top_k, d_expert=self.moe.d_ff,
        )
        h = TransformerEncoder(cfg, name="encoder")(ids, None, deterministic)
        return nn.Dense(
            self.num_classes,
            kernel_init=_dense_init("embed", None),
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            name="head",
        )(h[:, 0].astype(jnp.float32))


def moe_aux_loss(variables) -> jnp.ndarray:
    """Sum every sown MoE aux loss out of ``mutable=['losses']`` state."""
    losses = variables.get("losses", {}) if isinstance(variables, dict) else {}
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(losses):
        total = total + jnp.sum(leaf)
    return total


def step_stats(variables) -> dict:
    """What one step's ``mutable=['losses', STATS]`` state says about its
    routing, as device values: ``{}`` for a model with no routed layer,
    else the auxiliary loss and the tokens each expert received, summed
    over the layers."""
    sown = jax.tree_util.tree_leaves(variables.get(STATS, {}))
    if not sown:
        return {}
    return {"aux_loss": moe_aux_loss(variables), "expert_tokens": sum(sown)}


def report_epoch(stats: dict, n_steps: int) -> None:
    """Gauges from an epoch's summed :func:`step_stats`, fetched with the
    epoch's loss: the mean auxiliary loss a step, and the fullest expert's
    tokens over the mean expert's."""
    import numpy as np

    from raydp_tpu.utils.profiling import metrics

    tokens = np.asarray(stats["expert_tokens"], np.float64)
    metrics.gauge_set("moe/aux_loss", float(stats["aux_loss"]) / n_steps)
    metrics.gauge_set("moe/load_max_over_mean", tokens.max() / tokens.mean())
    metrics.gauge_set("moe/expert_tokens_per_step", tokens.sum() / n_steps)


def tiny_moe(**overrides) -> MoEConfig:
    defaults = dict(
        d_model=32, d_ff=64, n_experts=4, top_k=2, dtype=jnp.float32,
    )
    defaults.update(overrides)
    return MoEConfig(**defaults)
