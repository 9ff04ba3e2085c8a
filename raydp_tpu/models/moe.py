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
  ``expert_form`` ``relu2`` makes every expert, routed and shared, the
  ungated ``down(relu(up(x))²)``: two stacked matrices and no ``w_gate``
  anywhere (:data:`EXPERT_FORMS`; stated once, read by the routed path,
  a share's guard, the mesh path and the shared expert).
  A permutation's transpose is its inverse, so :func:`take_rows` gives the
  row gather a backward that is a gather too: neither direction is a
  scatter of ``T·k`` rows (XLA's TPU scatter is a serial loop over its
  updates, 95 ns a row: PERF.md §6, PR 25).
* Expert weights carry the logical axes ``('expert', 'embed', 'mlp')``
  (at rest: experts over ``dp``, an expert's FFN over ``tp``); no biases.
  Sharded weights alone make no expert parallelism: XLA cannot partition
  the Mosaic grouped matmul or the two sorts, and gathers every expert
  onto every chip around them. The exchange below is what computes on the
  shards.
* What the router does is configuration of the one layer: ``scoring``
  ``softmax`` (OLMoE) or ``sigmoid``; a ``selection_bias`` added to the
  scores for the selection only (the buffer ``expert_bias`` in collection
  :data:`BUFFERS`, drawn at init by :func:`balancing_bias`: no gradient,
  and the estimator's step leaves it as it was); ``normalize_gates`` divides the selected scores by their sum +
  1e-6; ``gate_scale`` multiplies them.
* The share of an expert-parallel deployment: the router keeps all
  ``n_experts`` outputs and ``top_k`` experts a token, the layer HOLDS
  experts ``[first_expert, first_expert + held_experts)`` — their weights
  are the only ones it has — sorts the pairs on absent experts behind the
  held ones, runs the grouped matmuls over the held rows alone (the group
  sizes sum to fewer than ``T·k`` rows and the kernel's grid ends there)
  and returns the part of the sum its own experts give. The held pairs
  are the first places of the sorted order, so a share's expert path —
  the row gather, the grouped matmuls, the SwiGLU, the way back to tokens
  and all their cotangents — runs over the first ``C`` rows, not ``T·k``:
  ``C`` is one and a half times the pairs uniform routing sends here,
  ``1.5 · T·k · held / n_experts``, rounded up to the grouped matmul's row
  tile and never more than ``T·k`` (:func:`compact_rows`; nothing sets
  it). No pair on a held expert is dropped whatever the routing: a
  layer-step whose held pairs exceed ``C`` takes the ``T·k``-row path
  instead, forward and backward, inside a ``lax.cond`` that no other
  layer-step enters (:func:`_hand_in`; counted in ``overflow``). A pair
  sorted behind the ``C`` rows reads the last of them and is masked where
  rows go back to tokens. The compact path's two token-side sums — the
  way back to tokens, forward, and the cotangent of the way there,
  backward — do not gather ``T·k`` rows for the share that is live: the
  sort is stable, so a held expert's rows of one token tile are one
  contiguous run of the ``C`` rows, and ``ops/rows_to_tokens.py`` sums
  the runs where they lie (the same float32 products in another order;
  up to three held experts for each of a token's k, by the kernel's own
  rule: its time goes with the experts held, the gather's with the
  pairs). A share on one chip runs without its
  exchange: nothing stands in for the absent chips.
* The exchange, where the whole group of chips is here
  (``expert_axis`` names the axis of ``mesh`` the experts lie along, ``n``
  chips, ``n_experts / n`` experts a chip, tokens sharded over the same
  axis as the batch): inside a ``shard_map`` over that axis a chip
  all-gathers the axis's tokens with their gates and choices
  (``exchange/gather``), runs the share layer above over its own experts
  (``first_expert`` from its place on the axis; the compact ``C``-row
  path and its ``T·k``-row guard PER CHIP, so no pair is dropped under
  any imbalance), and the parts are reduce-scattered to the chips that
  own the tokens (``exchange/scatter``): :func:`_exchanged`, which also
  says why a gather and not an all-to-all of pairs. The router and its
  statistics stay outside, on the batch's own shards. Without an axis the
  layer is the one-chip program, to the bit.
* The load-balancing loss (``E · Σ_e f_e · p_e``, weight 0.01) and the
  router z-loss (``mean(logsumexp(logits)²)``, weight 0.001) are sown into
  the ``'losses'`` collection as ``moe_aux`` (nothing where both weights
  are 0); pull them with :func:`moe_aux_loss`. The tokens each expert
  received (and, for a share, each held expert, and whether the held
  pairs exceeded ``C``) are sown into the step's statistics
  (``models/stats.py``); ``JAXEstimator`` sums them over an epoch on the
  device, the step's auxiliary loss beside them (:func:`with_aux_loss`),
  and :func:`report_epoch` reads them.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from raydp_tpu.models import stats
from raydp_tpu.models.stats import STATS  # noqa: F401  (callers' mutable=)
from raydp_tpu.ops.grouped_matmul import (
    IMPLEMENTATION,
    TILING,
    grouped_matmul,
)
from raydp_tpu.ops.rows_to_tokens import pays, rows_to_tokens

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

# What the routed layers sow about a step, each summed over layers and
# steps (``models/stats.py``).
for _name in ("aux_loss", "expert_tokens", "held_tokens", "chip_tokens",
              "overflow"):
    stats.declare(_name)
# Collection of what a layer reads and no gradient step may change: the
# router's selection bias. ``JAXEstimator``'s step hands it on as it was.
BUFFERS = "buffers"


BALANCING_ROUNDS = 4
# An expert's form -> its stacked matrices; the last one goes back to
# ``d_model``, the others come from it.
EXPERT_FORMS = {
    "swiglu": ("w_gate", "w_up", "w_down"),
    "relu2": ("w_up", "w_down"),
}


def balancing_bias(scores, top_k: int):
    """``expert_bias`` as drawn at init: the values under which every
    expert is among the ``top_k`` of ``scores + bias`` for the same share
    of the tokens ``model.init`` is given (``scores`` ``[T, E]``). A token
    picks expert e when e's biased score beats its rival, the ``top_k``-th
    largest of the others; each round moves ``b_e`` so that ``top_k / E``
    of the tokens do, all experts at once, and a few rounds settle what
    the experts' moves do to each other's rivals. It stands for what the
    buffer is in a trained checkpoint, the bias that balances the experts'
    loads: a router of random weights sends a Zipf-distributed corpus's
    few frequent tokens to a few experts, and which ones differs by seed
    (PERF.md §6, PR 32: with a bias drawn at random the pairs on 8 of 32
    experts were 24% of all at one seed and 37% at another, 4.4% apart in
    step time). The published checkpoints' values are loaded over it; the
    public config gives no update rule, so nothing updates it after init."""
    e = scores.shape[-1]
    bias = jnp.zeros((e,), scores.dtype)
    for _ in range(BALANCING_ROUNDS):
        ranked = scores + bias
        top, _ = jax.lax.top_k(ranked, top_k + 1)
        kth, below = top[:, top_k - 1:top_k], top[:, top_k:]
        rival = jnp.where(ranked >= kth, below, kth)           # [T, E]
        bias = bias - jnp.quantile(ranked - rival, 1.0 - top_k / e, axis=0)
    return bias - jnp.mean(bias)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 768
    d_ff: int = 3072                 # width of one expert
    n_experts: int = 8               # the router's outputs
    top_k: int = 2
    aux_loss_weight: float = 1e-2    # load balancing
    z_loss_weight: float = 1e-3      # router z-loss
    scoring: str = "softmax"         # softmax | sigmoid
    selection_bias: bool = False     # scores + expert_bias pick the top k
    normalize_gates: bool = False    # selected scores / (their sum + 1e-6)
    gate_scale: float = 1.0
    # The share held here: experts [first_expert, first_expert + held).
    first_expert: int = 0
    held_experts: Optional[int] = None   # None = all n_experts
    # Experts every token goes through, beside the routed ones: one dense
    # expert of width ``shared_experts * d_ff``, no router gate on it, whole
    # on every share of an expert-parallel deployment.
    shared_experts: int = 0
    # What an expert computes, routed and shared alike (:data:`EXPERT_FORMS`):
    # swiglu ``down(silu(gate x) * up x)``, three matrices | relu2
    # ``down(relu(up x)²)``, two: no ``w_gate`` exists, in the parameters,
    # the optimizer's state or the cotangents.
    expert_form: str = "swiglu"
    # Expert parallelism on a mesh: the axis of ``mesh`` (a
    # ``jax.sharding.Mesh``) the experts lie along. Each of the axis's n
    # chips holds ``n_experts / n`` experts and the layer runs its exchange
    # (:func:`_exchanged`). None = every held expert on every chip, no
    # collective: the layer as it was.
    expert_axis: Optional[str] = None
    mesh: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def exchange_chips(self) -> int:
        """Chips the experts lie over (1 without an axis)."""
        if self.expert_axis is None or self.mesh is None:
            return 1
        n = int(self.mesh.shape[self.expert_axis])
        if n > 1 and (self.held != self.n_experts or self.n_experts % n):
            raise ValueError(
                f"{self.n_experts} experts ({self.held} held) over "
                f"{n} chips of axis {self.expert_axis!r}: the exchange runs "
                "over all the experts, the same number a chip"
            )
        return n

    @property
    def expert_weights(self) -> tuple:
        """The names of an expert's stacked matrices, in the order the
        expert path takes them."""
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(f"unknown expert_form {self.expert_form!r}")
        return EXPERT_FORMS[self.expert_form]

    @property
    def held(self) -> int:
        held = self.n_experts if self.held_experts is None else (
            self.held_experts
        )
        if not (0 < held and 0 <= self.first_expert
                and self.first_expert + held <= self.n_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + held}) "
                f"are not among the {self.n_experts} routed over"
            )
        return held


def _expert_init(*logical_axes: str):
    return nn.with_logical_partitioning(
        nn.initializers.xavier_uniform(), logical_axes
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(x, perm, inverse, fan: int = 1, live=None, runs=None):
    """``x[perm // fan]`` for a permutation ``perm`` of ``range(len(x) *
    fan)`` with inverse ``inverse``: every row of ``x`` goes to ``fan``
    places. The transpose of a permutation is its inverse, so the
    cotangent is ``g[inverse]`` summed over each row's ``fan`` copies — a
    gather, where ``jax.grad`` of the plain gather is a scatter-add.
    ``live`` ``[len(x), fan]`` (a share's layer) says which copies anyone
    computed on: the cotangent of the others is not read. A share's
    ``perm`` is the first ``C`` places of the permutation and ``inverse``
    never points behind them: ``C`` rows come out, and ``g`` has ``C``.
    ``runs`` (:func:`_experts`, a share's compact path) has the
    cotangent summed from ``g``'s own ``C`` rows, float32 inside, with no
    gather of ``len(x) * fan`` rows."""
    return x[perm // fan] if fan > 1 else x[perm]


def _take_rows_fwd(x, perm, inverse, fan, live, runs):
    return take_rows(x, perm, inverse, fan, live, runs), (inverse, live, runs)


def _take_rows_bwd(fan, res, g):
    inverse, live, runs = res
    if runs is not None:
        return rows_to_tokens(g, None, *runs), None, None, None, None
    gx = g[inverse]
    if fan > 1 or live is not None:
        gx = gx.reshape(-1, fan, gx.shape[-1])
        if live is not None:
            gx = jnp.where(live[..., None], gx, 0)
        gx = gx.sum(axis=1)
    return gx, None, None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def combine_rows(rows, gate, order, inverse, live=None, runs=None):
    """Expert-ordered ``rows`` ``[T·k, D]`` back to their tokens: token
    t's output is the sum over its k pairs of ``gate[t, j]`` times the
    pair's row, float32 inside. The cotangents need no row of a ``T·k``
    array out of order: a pair's is its token's times its gate, read from
    the ``[T, D]`` cotangent in expert order. ``live`` ``[T, k]`` (a
    share's layer) says which pairs have a row that was computed: the
    others add nothing and their gates get no cotangent. A share hands
    ``rows`` ``[C, D]`` with the first ``C`` places of ``order`` and an
    ``inverse`` that never points behind them; with ``runs``
    (:func:`_experts`, a share's compact path) the same products are
    summed from the ``C`` rows as they lie, and no ``[T·k, D]`` array is
    formed."""
    if runs is not None:
        return rows_to_tokens(rows, gate, *runs)
    t, k = gate.shape
    pairs = rows[inverse].reshape(t, k, rows.shape[-1])
    if live is not None:
        pairs = jnp.where(live[..., None], pairs, 0)
    return jnp.sum(
        pairs.astype(jnp.float32) * gate[..., None], axis=1
    ).astype(rows.dtype)


def _combine_rows_fwd(rows, gate, order, inverse, live, runs):
    return combine_rows(rows, gate, order, inverse, live, runs), (
        rows, gate, order, inverse, live
    )


def _combine_rows_bwd(res, g):
    rows, gate, order, inverse, live = res
    k = gate.shape[1]
    g_rows = g[order // k].astype(jnp.float32)            # [T·k, D]
    d_rows = g_rows * gate.reshape(-1)[order][:, None]
    d_gate = jnp.sum(rows.astype(jnp.float32) * g_rows, axis=-1)
    d_gate = d_gate[inverse].reshape(gate.shape)
    if live is not None:
        d_gate = jnp.where(live, d_gate, 0)
    return (
        d_rows.astype(rows.dtype), d_gate.astype(gate.dtype),
        None, None, None, None,
    )


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def compact_rows(cfg: "MoEConfig", n_tokens: int) -> int:
    """``C``, the rows the expert path of a layer over ``n_tokens`` tokens
    runs over: all ``T·k`` where every expert is held; for a share one
    and a half times the pairs uniform routing sends to its experts,
    rounded up to the grouped matmul's row tile. One and a half: balanced
    loads bring 25.0-26.1% of the pairs to a quarter of the experts and
    the most lopsided routing a chip has shown 36.7% (PERF.md §6, PR 32);
    37.5% holds both, and what it does not hold takes all the rows."""
    pairs = n_tokens * cfg.top_k
    if cfg.held == cfg.n_experts:
        return pairs
    rows = -(-3 * pairs * cfg.held // (2 * cfg.n_experts))
    return min(pairs, -(-rows // TILING[0]) * TILING[0])


def _hidden(form: str, x, weights, group_sizes):
    """What an expert of ``form`` hands its last matrix, for rows ``x`` in
    expert order: ``silu(x·w_gate) * (x·w_up)`` | ``relu(x·w_up)²``."""
    if form == "swiglu":
        w_gate, w_up = weights
        return jax.nn.silu(
            grouped_matmul(x, w_gate, group_sizes)
        ) * grouped_matmul(x, w_up, group_sizes)
    (w_up,) = weights
    return jnp.square(jax.nn.relu(grouped_matmul(x, w_up, group_sizes)))


def _experts(form: str, operands, routing, rows: Optional[int] = None):
    """The expert path over the first ``rows`` places of the sorted pairs
    (all ``T·k`` unless given): ``operands`` = tokens ``[T, D]``, gates
    ``[T, k]`` and the stacked weights of an expert of ``form`` (three,
    or two: ``MoEConfig.expert_weights``), ``routing`` = ``order``,
    ``inverse``, the held experts' ``group_sizes`` and ``live``. With fewer
    rows than pairs every array between the two token-side gathers has
    ``rows`` rows, and group sizes that sum to more are cut there."""
    tokens, gate, *weights = operands
    order, inverse, group_sizes, live = routing
    runs = None
    with jax.named_scope("permute"):
        if rows is not None and rows < order.shape[0]:
            place = inverse
            order = order[:rows]
            inverse = jnp.minimum(inverse, rows - 1)
            ends = jnp.minimum(jnp.cumsum(group_sizes), rows)
            group_sizes = jnp.diff(ends, prepend=0)
            if live is not None and pays(len(group_sizes), gate.shape[1]):
                # The two token-side sums read the ``rows`` rows as they
                # lie (``ops/rows_to_tokens.py``): each live pair's place,
                # and where the held experts' groups end.
                runs = (jnp.where(live, place.reshape(live.shape), -1), ends)
        x = take_rows(tokens, order, inverse, gate.shape[1], live, runs)
    with jax.named_scope("experts"):
        *w_in, w_down = (w.astype(tokens.dtype) for w in weights)
        h = _hidden(form, x, w_in, group_sizes)
        y = grouped_matmul(h, w_down, group_sizes)
    with jax.named_scope("unpermute"):
        return combine_rows(y, gate, order, inverse, live, runs)


# A share's guard. The compact path ``_experts(..., rows=C)`` stands in the
# program as it would without a guard, under plain autodiff; around it:
#
#     operands, wire = _hand_in(form, operands, routing, overflow)
#     out = _hand_out(form, _experts(form, operands, routing, C), wire, ...)
#
# ``_hand_out`` gives the compact result unless the layer-step's held pairs
# exceed ``C``: then, inside a ``lax.cond``, the ``T·k``-row path's. Its
# cotangent goes to the compact path and, over ``wire`` (zeros nobody
# reads, there to carry it), to ``_hand_in``'s backward, which by then also
# holds the compact path's cotangents (tokens, gates and an expert's stacked
# weights: five, four for an ungated expert) and hands them on through a
# second ``cond`` — or, on overflow, the ``T·k``-row path's in their place.
# A branch not taken computes nothing and writes nothing: no residual and
# no zero gradient of a ``T·k``-row array or of a stacked weight exists for
# the sake of the guard.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hand_in(form, operands, routing, overflow):
    return operands, jnp.zeros_like(operands[0])


def _hand_in_fwd(form, operands, routing, overflow):
    return _hand_in(form, operands, routing, overflow), (
        operands, routing, overflow
    )


def _hand_in_bwd(form, res, cotangents):
    operands, routing, overflow = res
    compact, g = cotangents

    def whole(_):
        _, pull = jax.vjp(lambda *o: _experts(form, o, routing), *operands)
        return pull(g)

    # Between barriers, or XLA moves the compact path's last ops and the
    # gradients' first users (their casts, so the optimizer's fusions read
    # float32) into the branches.
    compact = jax.lax.optimization_barrier(compact)
    grads = jax.lax.cond(overflow, whole, lambda c: c, compact)
    return jax.lax.optimization_barrier(grads), None, None


_hand_in.defvjp(_hand_in_fwd, _hand_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hand_out(form, out, wire, operands, routing, overflow):
    return jax.lax.optimization_barrier(jax.lax.cond(
        overflow, lambda: _experts(form, operands, routing), lambda: out
    ))


def _hand_out_fwd(form, out, wire, operands, routing, overflow):
    return _hand_out(form, out, wire, operands, routing, overflow), None


def _hand_out_bwd(form, _, g):
    return g, g, None, None, None


_hand_out.defvjp(_hand_out_fwd, _hand_out_bwd)


def _sorted_experts(form: str, tokens, gate, expert, counts, weights, first,
                    held: int, share: bool, rows: int, dtype, note_overflow):
    """``sum_j gate_j E_j(token)`` over the experts ``[first, first +
    held)`` of ``form`` (``weights``: their stacked matrices) for
    ``tokens`` ``[T, D]`` with their ``gate`` and ``expert``
    ``[T, k]``: the pairs sorted by expert, the expert path over the first
    ``rows`` places, the guard around it where those are fewer than all
    ``T·k`` (``note_overflow`` is handed the layer-step's flag, float32).
    ``counts`` ``[held]`` are the pairs on each held expert, ``first`` a
    number or a traced value (a chip's place on the experts' axis);
    ``share`` says that some of the routed experts are not held."""
    n_tokens, k = gate.shape
    with jax.named_scope("permute"):
        # Pair p = t·k + j is token t's j-th expert. ``order`` lists
        # the pairs by expert, ``inverse`` is each pair's place in
        # that list: two sorts, no scatter.
        pairs = jnp.arange(n_tokens * k, dtype=jnp.int32)
        key, live = expert.reshape(-1).astype(jnp.int32), None
        if share:
            # Held experts are groups 0..held-1; a pair on an absent
            # expert sorts behind them all, where no group reaches.
            key = key - first
            live = (key >= 0) & (key < held)
            key = jnp.where(live, key, held)
            live = live.reshape(n_tokens, k)
        _, order = jax.lax.sort_key_val(key, pairs)
        _, inverse = jax.lax.sort_key_val(order, pairs)
        routing = (order, inverse, counts.astype(jnp.int32), live)
        operands = (tokens.astype(dtype), gate) + tuple(weights)
    if rows == n_tokens * k:
        return _experts(form, operands, routing)
    with jax.named_scope("permute"):
        overflow = counts.sum() > rows
        note_overflow(overflow.astype(jnp.float32))
    with jax.named_scope("experts"):
        # In the compute dtype before the guard, so that what the
        # guard hands on is the kernels' own weight gradient.
        operands = operands[:2] + tuple(
            w.astype(dtype) for w in operands[2:]
        )
    operands, wire = _hand_in(form, operands, routing, overflow)
    return _hand_out(
        form, _experts(form, operands, routing, rows), wire, operands,
        routing, overflow,
    )


def _exchanged(cfg: "MoEConfig", tokens, gate, expert, counts, weights):
    """The routed experts' sum for ``tokens`` ``[T, D]`` (compute dtype)
    whose rows, like ``gate``'s and ``expert``'s ``[T, k]``, lie over the
    ``n`` chips of ``cfg.expert_axis`` as the batch does, with experts
    ``c·E/n … (c+1)·E/n - 1`` of the stacked ``weights`` on chip ``c``.
    Inside a ``shard_map`` over that axis a chip all-gathers the tokens
    with their gates and choices (scope ``exchange/gather``), runs the
    share layer over its own experts — the sort, the compact ``C``-row
    expert path and its guard, per chip: no pair is dropped — and the
    parts go back to the chips that own the tokens summed on the way
    (``exchange/scatter``, a reduce-scatter in the compute dtype). Under
    autodiff the two collectives are each other's transposes, so a
    backward pass moves the same rows.

    The gather form and not an all-to-all of pairs: with k = 8 experts a
    token over 4 chips a token misses a given chip with probability about
    0.75⁸ = 10%, so the gather and the reduce-scatter move 3·T/n rows a
    chip each way where an all-to-all of (token, expert) pairs moves about
    6·T/n and one of de-duplicated (token, chip) rows about 2.7·T/n; and
    every shape here is static, where an all-to-all needs a capacity a
    chip and a second guard for what exceeds it (PERF.md §6, PR 53).
    Returns the sum ``[T, D]`` laid out as ``tokens`` was and each chip's
    overflow flag ``[n]``. ``counts`` ``[E]`` int32 are the pairs on every
    expert, the same on every chip."""
    from jax.sharding import PartitionSpec as P

    mesh, axis, n = cfg.mesh, cfg.expert_axis, cfg.exchange_chips
    held = cfg.n_experts // n
    n_tokens = tokens.shape[0]
    if n_tokens % n:
        raise ValueError(
            f"{n_tokens} tokens over the {n} chips of axis {axis!r}"
        )
    rows = compact_rows(
        dataclasses.replace(cfg, held_experts=held, expert_axis=None), n_tokens
    )

    def chip(tokens, gate, expert, counts, *weights):
        with jax.named_scope("exchange"), jax.named_scope("gather"):
            tokens, gate, expert = (
                jax.lax.all_gather(a, axis, axis=0, tiled=True)
                for a in (tokens, gate, expert)
            )
        first = jax.lax.axis_index(axis) * held
        mine = jax.lax.dynamic_slice(counts, (first,), (held,))
        flags = [jnp.zeros((), jnp.float32)]
        out = _sorted_experts(
            cfg.expert_form, tokens, gate, expert, mine, weights, first,
            held, True, rows, cfg.dtype, flags.append,
        )
        with jax.named_scope("exchange"), jax.named_scope("scatter"):
            out = jax.lax.psum_scatter(
                out, axis, scatter_dimension=0, tiled=True
            )
        return out, flags[-1][None]

    along = P(axis)
    return jax.shard_map(
        chip, mesh=mesh,
        in_specs=(along, along, along, P()) + (along,) * len(weights),
        out_specs=(along, along),
        # pallas_call's out_shape carries no varying-axes annotation.
        check_vma=False,
    )(tokens, gate, expert, counts, *weights)


@functools.lru_cache(maxsize=None)
def _log_once(cfg: "MoEConfig") -> None:
    logger.info(
        "MoE layer: %d experts of width %d, top-%d of %s scores%s%s, no "
        "capacity; holds experts [%d, %d); grouped matmul: %s",
        cfg.n_experts, cfg.d_ff, cfg.top_k, cfg.scoring,
        " + selection bias" if cfg.selection_bias else "",
        ", gates normalised" if cfg.normalize_gates else "",
        cfg.first_expert, cfg.first_expert + cfg.held, IMPLEMENTATION,
    )


def _add(a, b):
    return a + b


class SharedExpert(nn.Module):
    """The experts no router chooses, of the routed experts' form:
    ``down(silu(gate(x)) * up(x))`` for every token, gate and up as one
    fused projection ``in``, or ``down(relu(up(x))²)`` with ``in`` the up
    projection alone (scope ``moe/shared``)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        width = cfg.shared_experts * cfg.d_ff
        gated = cfg.expert_form == "swiglu"
        h = dense(
            (2 if gated else 1) * width,
            kernel_init=_expert_init("embed", "mlp"), name="in",
        )(tokens)
        if gated:
            gate, up = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(gate) * up
        else:
            h = jnp.square(jax.nn.relu(h))
        return dense(
            cfg.d_model, kernel_init=_expert_init("mlp", "embed"),
            name="out",
        )(h)


class MoELayer(nn.Module):
    """Top-k routed experts (SwiGLU, or ungated relu²: ``cfg.expert_form``)
    over the trailing feature axis.

    Input ``[..., D]`` → output ``[..., D]`` in the compute dtype; tokens
    are the flattened leading axes, and every token reaches all of its
    ``top_k`` experts that this layer holds (all of them unless the
    configuration names a share). A float32 input reaches the router as it
    is.
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
        e, k, held = cfg.n_experts, cfg.top_k, cfg.held
        first, share = cfg.first_expert, held < cfg.n_experts
        if self.is_initializing():
            _log_once(cfg)

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
        if cfg.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {cfg.scoring!r}")
        with jax.named_scope("router"):
            probs = (jax.nn.softmax(logits, axis=-1)           # [T, E]
                     if cfg.scoring == "softmax" else jax.nn.sigmoid(logits))
            ranked = probs
            if cfg.selection_bias:
                # The bias enters the selection only: the gates are scores.
                ranked = probs + self.variable(
                    BUFFERS, "expert_bias", balancing_bias, probs, k
                ).value
            _, expert = jax.lax.top_k(ranked, k)               # [T, k]
            chosen = jax.nn.one_hot(expert, e, dtype=jnp.float32)
            # The chosen probabilities as a product with the one-hot
            # choice: top_k's own values would give the router's
            # gradient as a scatter of T·k updates.
            gate = jnp.einsum("te,tke->tk", probs, chosen)
            if cfg.normalize_gates:
                gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-6)
            if cfg.gate_scale != 1.0:
                gate = gate * cfg.gate_scale
            counts = chosen.sum(axis=(0, 1))                   # [E]
            if cfg.aux_loss_weight or cfg.z_loss_weight:
                # E · Σ_e f_e · p_e with f the share of the T·k pairs
                # that went to e (k at uniform routing), and the z-loss.
                balance = e * jnp.sum(
                    counts / n_tokens * probs.mean(axis=0)
                )
                z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
                self.sow(
                    "losses", "moe_aux",
                    cfg.aux_loss_weight * balance + cfg.z_loss_weight * z,
                    reduce_fn=_add,
                    init_fn=lambda: jnp.zeros((), jnp.float32),
                )
            stats.sow(self, "expert_tokens", counts)
            if share:
                counts = counts[first:first + held]
                stats.sow(self, "held_tokens", counts)

        *names_in, name_down = cfg.expert_weights
        weights = tuple(
            self.param(
                name, _expert_init("expert", "embed", "mlp"),
                (held, d, cfg.d_ff), cfg.param_dtype,
            ) for name in names_in
        ) + (self.param(
            name_down, _expert_init("expert", "mlp", "embed"),
            (held, cfg.d_ff, d), cfg.param_dtype,
        ),)
        if cfg.exchange_chips > 1 and not self.is_initializing():
            out, overflow = _exchanged(
                cfg, tokens.astype(cfg.dtype), gate, expert,
                counts.astype(jnp.int32), weights,
            )
            with jax.named_scope("permute"):
                # The pairs each chip's experts received, and the
                # (chip, layer-step)s that took all the rows.
                stats.sow(self, "chip_tokens", counts.reshape(
                    cfg.exchange_chips, -1
                ).sum(axis=1))
                stats.sow(self, "overflow", overflow.sum())
        else:
            # Nothing reads what ``init`` computes: no guard and no
            # exchange to compile there.
            rows = n_tokens * k if self.is_initializing() else (
                compact_rows(cfg, n_tokens)
            )
            out = _sorted_experts(
                cfg.expert_form, tokens, gate, expert, counts, weights,
                first, held, share, rows, cfg.dtype,
                lambda over: stats.sow(self, "overflow", over),
            )
        if cfg.shared_experts:
            # The same on every share: when the shares' parts are summed
            # it counts once.
            out = out + SharedExpert(cfg, name="shared")(
                tokens.astype(cfg.dtype)
            )
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


def with_aux_loss(sown: dict, variables) -> dict:
    """One step's statistics (``models/stats.step_stats``) with the
    auxiliary loss of its ``'losses'`` state beside the routed layers'
    counts; as they are for a model without a routed layer."""
    if "expert_tokens" not in sown:
        return sown
    return {**sown, "aux_loss": moe_aux_loss(variables)}


def report_epoch(sown: dict, n_steps: int) -> None:
    """Gauges from the routed layers' part of an epoch's statistics
    (``models/stats.step_stats`` summed over the steps), fetched with the
    epoch's loss; nothing for a model without a routed layer: the mean auxiliary loss a step, the (token, expert) pairs
    a step routes and how many of them landed on experts held here (all of
    them unless the layers are a share: a quarter at uniform routing over
    four shares), the fullest held expert's tokens over the mean held
    expert's, and ``moe/overflow_layer_steps``: the layer-steps of the
    epoch whose held pairs exceeded :func:`compact_rows` and took all
    ``T·k`` rows inside the guard (0 where loads are balanced, and always
    where every expert is held; a reader who sees it rise knows why the
    step slowed); over a mesh axis the fullest chip's pairs over the mean
    chip's and the pairs a step that reached the axis's FIRST chip
    (``moe/first_chip_pairs_per_step``: the rows its grouped matmuls
    ran)."""
    import numpy as np

    from raydp_tpu.utils.profiling import metrics

    if "expert_tokens" not in sown:
        return
    tokens = np.asarray(sown["expert_tokens"], np.float64)
    held = np.asarray(sown.get("held_tokens", tokens), np.float64)
    metrics.gauge_set("moe/aux_loss", float(sown["aux_loss"]) / n_steps)
    metrics.gauge_set("moe/load_max_over_mean", held.max() / held.mean())
    metrics.gauge_set("moe/expert_tokens_per_step", tokens.sum() / n_steps)
    metrics.gauge_set("moe/held_pairs_per_step", held.sum() / n_steps)
    metrics.gauge_set("moe/held_pair_share", held.sum() / tokens.sum())
    metrics.gauge_set(
        "moe/overflow_layer_steps", float(sown.get("overflow", 0.0))
    )
    if "chip_tokens" in sown:
        # The step waits for the fullest chip of the experts' axis; the
        # axis's first chip is the mesh's first device, whose kernels a
        # device trace of "chip 0" shows.
        chips = np.asarray(sown["chip_tokens"], np.float64)
        metrics.gauge_set(
            "moe/chip_load_max_over_mean", chips.max() / chips.mean()
        )
        metrics.gauge_set("moe/first_chip_pairs_per_step", chips[0] / n_steps)


def exchange_bytes(cfg: MoEConfig, n_tokens: int) -> int:
    """Bytes one chip sends plus receives in ONE pass of one layer's
    exchange over ``n_tokens`` tokens of the whole axis, from the shapes:
    the gather brings the other chips' ``(n-1)/n · T`` rows in (a token's
    ``D`` features in the compute dtype, its k gates and k choices, 4
    bytes each) and sends this chip's ``T/n`` rows to ``n-1`` chips, the
    reduce-scatter moves as many rows of ``D`` features the other way. A
    backward pass transposes the two collectives and moves the same. 0
    without an axis."""
    n = cfg.exchange_chips
    if n == 1:
        return 0
    rows = 2 * (n - 1) * n_tokens // n            # sent + received
    row = cfg.d_model * jnp.dtype(cfg.dtype).itemsize
    return rows * (2 * row + 8 * cfg.top_k)


def report(model, tokens_per_step: int) -> None:
    """Static for a compiled step: gauges where the step is built
    (as ``models/mamba.report``): the experts the routed layers route over,
    how many of them a chip holds, ``moe/compact_rows``, the
    rows a layer's expert path runs over (:func:`compact_rows`: all
    ``T·k`` pairs of the step's tokens unless the layers are a share or lie
    over a mesh axis), ``moe/exchange_chips`` (the chips of that axis, 1
    without one), ``moe/token_sum_rows``, the rows of the expert-ordered
    array that a layer-pass's two token-side sums read (the way back to
    tokens, forward; the cotangent of the way there, backward: the ``C``
    compact rows where ``ops/rows_to_tokens.py`` runs them, all ``T·k``
    pairs where a gather does), ``moe/token_sum_layers``, the routed layers
    in which the kernel runs them (0 where every expert is held, and where
    a chip holds more than three experts for each of a token's: the
    kernel's time goes with the experts held,
    ``ops/rows_to_tokens.pays``), and
    ``moe/exchange_bytes_per_step``
    (:func:`exchange_bytes` over the routed layers and a step's passes:
    forward, backward, and the forward again in the blocks that are
    checkpointed), and the expert's form as ``moe/expert_matrices`` (3
    for SwiGLU, 2 for the ungated relu²). Zero for a model without a
    routed layer."""
    from raydp_tpu.utils.profiling import metrics

    cfg, moe = getattr(model, "cfg", None), getattr(model, "moe", None)
    layers = getattr(cfg, "ffn_kinds", ()).count("moe")
    if moe is None and layers:
        moe = cfg.moe_config()
    routed, held, chips, rows, moved, summed, sum_rows = 0, 0, 1, 0, 0, 0, 0
    if moe is not None:
        routed, chips = moe.n_experts, moe.exchange_chips
        held = moe.held // chips
        # What ONE chip's expert path is: the share it holds.
        share = dataclasses.replace(moe, held_experts=held, expert_axis=None)
        rows = compact_rows(share, tokens_per_step)
        # Forward and backward a layer, and the forward again in a block
        # that is checkpointed.
        again = sum(
            1 for ffn, held in zip(
                getattr(cfg, "ffn_kinds", ()), getattr(cfg, "checkpointed", ())
            ) if held and ffn == "moe"
        )
        moved = (2 * layers + again) * exchange_bytes(moe, tokens_per_step)
        # Where :func:`_experts` hands both sums ``runs``.
        pairs = tokens_per_step * moe.top_k
        summed, sum_rows = (layers, rows) if (
            rows < pairs and pays(held, moe.top_k)
        ) else (0, pairs)
    metrics.gauge_set("moe/experts_routed", routed)
    metrics.gauge_set("moe/experts_held", held)
    metrics.gauge_set("moe/compact_rows", rows)
    metrics.gauge_set("moe/token_sum_rows", sum_rows)
    metrics.gauge_set("moe/token_sum_layers", summed)
    metrics.gauge_set(
        "moe/shared_experts", moe.shared_experts if moe is not None else 0
    )
    # An expert's form, as its stacked matrices: 3 gated (SwiGLU), 2 not.
    metrics.gauge_set(
        "moe/expert_matrices",
        len(moe.expert_weights) if moe is not None else 0,
    )
    metrics.gauge_set("moe/exchange_chips", chips)
    metrics.gauge_set("moe/exchange_bytes_per_step", moved)
    if chips > 1:
        logger.info(
            "routed layers: %d experts over the %d chips of mesh axis %r, "
            "%d a chip, top-%d; a chip gathers the axis's %d tokens, runs "
            "its experts over %d of their %d pairs (all of them in a "
            "layer-step with more on its experts) and the parts are "
            "reduce-scattered to the tokens' chips",
            routed, chips, moe.expert_axis, held, moe.top_k,
            tokens_per_step, rows, tokens_per_step * moe.top_k,
        )
    elif held < routed:
        logger.info(
            "routed layers: a share of an expert-parallel deployment, "
            "experts [%d, %d) of %d held here, top-%d over all %d; pairs on "
            "absent experts cost no matmul row and nothing stands in for "
            "their exchange; the expert path runs over %d of a step's %d "
            "pairs, and a layer-step with more on held experts over all of "
            "them", moe.first_expert, moe.first_expert + held,
            routed, moe.top_k, routed, rows, tokens_per_step * moe.top_k,
        )


def tiny_moe(**overrides) -> MoEConfig:
    defaults = dict(
        d_model=32, d_ff=64, n_experts=4, top_k=2, dtype=jnp.float32,
    )
    defaults.update(overrides)
    return MoEConfig(**defaults)
