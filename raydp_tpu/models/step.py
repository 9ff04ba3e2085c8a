"""What a model contributes to a train step besides its loss: the one
module that knows which mixers, collections and layouts the families
under ``models/`` have. ``JAXEstimator`` runs any flax module and imports
this and nothing else of ``models/`` and ``ops/``; a module that is none
of these families (no ``cfg``, nothing sown) gets zeros and silence from
every function here. A new mixer adds one line to :func:`report` (and to
:func:`report_epoch` where it sows a statistic), not to the runner.
"""
from __future__ import annotations

import dataclasses
import inspect
import logging
import math
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import numpy as np
from jax.extend.core import Literal

from raydp_tpu.models import (
    blockdiff, dropout, hyperconn, kda, latent, mamba, moe, shortconv,
    sparse_index, stats, window,
)
from raydp_tpu.models.stats import merge  # noqa: F401  (two steps' statistics as one)
from raydp_tpu.models.transformer import (
    LOGICAL_RULES, TransformerBlock, kept_names,
    report as report_stack, vocab_rules,
)
from raydp_tpu.ops.flash_attention import report as report_flash_tiles

logger = logging.getLogger(__name__)

#: The collections a training apply is asked for (``mutable=``): the
#: regularisers the layers sow and the statistics of ``models/stats.py``.
SOWN = ("losses", stats.STATS)


def logical_rules(model, rules=None) -> list:
    """The state's layout: ``rules`` (the transformer family's where
    None). A model that computes with its vocabulary tables over a mesh
    axis says so in its own configuration, and they lie there at rest:
    the one place the layout is stated in."""
    rules = LOGICAL_RULES if rules is None else rules
    state_axis = getattr(getattr(model, "cfg", None), "state_axis", None)
    if state_axis is not None:
        rules = vocab_rules(state_axis, rules)
    return list(rules)


def parameters(variables):
    """``model.init``'s variables without the output collections sown
    during init (MoE aux losses, intermediates, statistics): they are
    NOT parameters, and keeping them would feed them to the optimizer as
    trainables."""
    if not isinstance(variables, dict):
        return variables
    sown = SOWN + ("intermediates",)
    return {k: v for k, v in variables.items() if k not in sown}


def step_rngs(model) -> tuple:
    """The rng collections, beside ``dropout``, that the model draws
    from in a training step (its ``step_rngs``; none for most)."""
    return tuple(getattr(model, "step_rngs", ()))


def _takes_deterministic(model) -> bool:
    try:
        sig = inspect.signature(type(model).__call__)
        return "deterministic" in sig.parameters
    except (TypeError, ValueError):
        return False


def apply_kwargs(model, rng) -> dict:
    """The keyword arguments of one training apply. ``rng`` is the
    step's key of the threefry chain; the masks come from the chip's bit
    generator (``models/dropout.py``). A model that draws more than
    dropout's masks in its step names the collections (``step_rngs``:
    block diffusion's ``noise``) and gets a key each, a function of
    ``rng`` too."""
    if not _takes_deterministic(model):
        return {}
    rngs = {"dropout": dropout.key_for(rng)}
    for i, name in enumerate(step_rngs(model)):
        rngs[name] = dropout.key_for(jax.random.fold_in(rng, i + 1))
    return dict(deterministic=False, rngs=rngs)


#: The sum of the regularisers in one apply's :data:`SOWN` state.
aux_loss = moe.moe_aux_loss


def step_stats(sown) -> dict:
    """What one apply's :data:`SOWN` state says about the step besides
    its loss (``models/stats.py``); ``{}`` for most models."""
    return moe.with_aux_loss(stats.step_stats(sown), sown)


def frozen(params) -> dict:
    """What the model reads and no step may change (the router's
    selection bias): no gradient reaches it, and the optimizer's weight
    decay does not either. ``{}`` for a model with no such collection."""
    if isinstance(params, dict) and moe.BUFFERS in params:
        return {moe.BUFFERS: params[moe.BUFFERS]}
    return {}


class Survey(NamedTuple):
    """What ONE abstract training-mode apply says about a step: the
    dropout census ``(sites, mask words)``, the model's outputs, the
    input of every ``TransformerBlock`` by its name (a block returns what
    it is given, so its result's shape is its input's) and the causal
    convolutions ``(kernel calls, jax.numpy calls)`` by the form each
    takes (``models/mamba.counting_convs``)."""
    dropout: Tuple[int, int] = (0, 0)
    out: Any = None
    blocks: dict = {}
    convs: Tuple[int, int] = (0, 0)


def survey(model, params, sample_batch) -> Survey:
    """:class:`Survey` of ``model`` at this batch; nothing runs. Empty for
    a model whose ``__call__`` takes no ``deterministic`` (no dropout to
    count, no stack of blocks)."""
    if not _takes_deterministic(model):
        return Survey()
    count, read = dropout.counting()
    count_convs, read_convs = mamba.counting_convs()

    def apply(variables, x):
        out, sown = model.apply(
            variables, x, mutable=SOWN + ("intermediates",),
            capture_intermediates=lambda module, _: isinstance(
                module, TransformerBlock),
            **apply_kwargs(model, jax.random.PRNGKey(0))
        )
        return out, sown.get("intermediates", {})

    with nn.intercept_methods(count), nn.intercept_methods(count_convs):
        out, captured = jax.eval_shape(apply, params, sample_batch)
    blocks = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(captured):
        keys = [getattr(key, "key", None) for key in path]
        if "__call__" in keys:      # <module path>/block_i/__call__/0
            blocks[keys[keys.index("__call__") - 1]] = leaf
    return Survey(read(), out, blocks, read_convs())


def report(model, params, sample_batch, surveyed=None) -> None:
    """Where the step is built: every family's static gauges and log
    line for this model at this batch, zero and silent for a family the
    model has nothing of. One abstract training-mode apply
    (:func:`survey`, made here unless the caller has made it); the rest
    reads the configuration."""
    surveyed = surveyed or survey(model, params, sample_batch)
    dropout.report(*surveyed.dropout)
    cfg = getattr(model, "cfg", None)
    batch, seq_len = sample_batch.shape[0], int(sample_batch.shape[-1])
    # The rows a step sends through every layer: the batch's tokens,
    # or more of them where the model lays copies side by side (block
    # diffusion's pair).
    tokens_per_step = int(np.prod(sample_batch.shape)) * getattr(
        model, "positions_per_token", 1
    )
    report_stack(cfg)
    mamba.report(
        cfg, tokens_per_step=tokens_per_step, convs=surveyed.convs
    )
    kda.report(cfg, tokens_per_step=tokens_per_step, sequence=seq_len)
    shortconv.report(cfg)
    latent.report(cfg)
    window.report(cfg)
    sparse_index.report(cfg, seq_len=seq_len)
    blockdiff.report(model, batch=batch, seq_len=seq_len)
    hyperconn.report(cfg)
    report_flash_tiles(cfg, seq_len=seq_len, batch=batch)
    moe.report(model, tokens_per_step=tokens_per_step)


def report_epoch(stats_sum: dict, n_batches: int) -> None:
    """The gauges of an epoch's statistics (:func:`step_stats` merged
    over its steps and fetched with its loss)."""
    moe.report_epoch(stats_sum, n_batches)
    hyperconn.report_epoch(stats_sum)
    blockdiff.report_epoch(stats_sum)
    sparse_index.report_epoch(stats_sum)


# ------------------------------------------------- the block checkpoint
#
# How many blocks of a ``remat`` stack are checkpointed follows from the
# shapes and the device's memory: :func:`fit_checkpoint` reads both where
# the step is built and releases the blocks whose residuals fit.

#: What a compiled step holds of the blocks over :func:`kept_bytes`'
#: count of them (the operands XLA chooses to write where the count takes
#: them to fuse, HBM tile padding), and the share of the device's limit
#: the estimate leaves free. Both from one table (PR 56; PERF.md section 6
#: has it whole): the step compiled for a TPU v5e, ``memory_analysis()``
#: arguments + temporaries, and the chip's own peak, in GiB of 15.75 —
#:
#:   cell (blocks)     all checkpointed      all released       as run
#:                     estimate  compiled    estimate compiled  released peak
#:   Granite (6)         11.80    10.53       14.58    13.44     6   13.51
#:   LFM2 (7)            10.37    10.29       14.00    13.53     7   13.69
#:   Xing4.0 (5)         12.49    10.83       15.94    13.90     2   12.12
#:   Laguna (5)          15.24    12.29       19.90    15.03     0   12.47
#:   Kimi Linear (5)     16.85    13.07       22.16    17.92     0   12.77
#:   SDAR (6)            13.12    11.91       18.62    15.81     1   12.31
#:   Keye (5)            12.74    10.24         -        -       2   12.10
#:
#: 1.6 is the least slack (in tenths) at which the estimate is no lower
#: than the compiled figure in any row (LFM2, all checkpointed, binds);
#: 5% of 15.75 GiB leaves the estimate 14.96, which admits the two stacks
#: measured whole (Granite 13.51 and LFM2 13.69 GiB on the chip) and keeps
#: every peak 1.2 GiB or more under the limit. Constants, not parameters.
SLACK = 1.6
MARGIN = 0.05

# Primitives whose result XLA computes inside the fusion that reads it
# (elementwise, shape and layout ops): a residual made of these alone is
# made again from the arrays under it, which are what the step holds.
_FUSED = frozenset("""
abs add add_any and atan2 broadcast_in_dim cbrt ceil clamp concatenate
convert_element_type copy copy_p cos div dynamic_slice eq erf erf_inv erfc
exp exp2 expand_dims expm1 floor ge gt imag integer_pow iota is_finite le
log log1p logistic lt max mesh_cast min mul name ne neg nextafter not or
pad pow pvary real reduce_precision rem reshape rev round rsqrt select_n
sharding_constraint shift_left shift_right_arithmetic
shift_right_logical sign sin slice split sqrt square squeeze
stop_gradient sub tan tanh transpose xor
""".split())
# Calls whose body is read through: the callee's arrays are the caller's.
_INLINED = frozenset((
    "pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
))


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * np.dtype(aval.dtype).itemsize


def _held(jaxpr, entering, scale, named):
    """For each of ``jaxpr``'s results, the arrays it is made of that a
    program holds: ``{var: bytes}`` of results of primitives that write
    (products, reductions, kernels, scans, collectives) and of the arrays
    ``entering`` (one such dict an input). A ``shard_map``'s body is read
    with its per-chip shapes scaled to the whole mesh. ``named`` gathers
    ``{name: {var: bytes}}`` of every array given a name
    (``checkpoint_name``) on the way."""
    env = dict(zip(jaxpr.invars, entering))

    def read(var):
        # Literals (unhashable) and constants: nothing held.
        return {} if isinstance(var, Literal) else env.get(var, {})

    for eqn in jaxpr.eqns:
        name, params = eqn.primitive.name, eqn.params
        inputs = [read(v) for v in eqn.invars]
        inner = params.get("jaxpr", params.get("call_jaxpr"))
        if name in _INLINED and inner is not None:
            outs = _held(getattr(inner, "jaxpr", inner), inputs, scale, named)
        elif name == "shard_map":
            chips = math.prod(params["mesh"].shape.values())
            outs = _held(params["jaxpr"], inputs, scale * chips, named)
        elif name in _FUSED:
            if name == "name":
                var = eqn.outvars[0]
                named.setdefault(params["name"], {})[var] = (
                    scale * _nbytes(var.aval)
                )
            # The dicts are never written after they are made: one
            # operand's is handed on as it is.
            held = [h for h in inputs if h]
            merged = held[0] if len(held) == 1 else {
                var: size for h in held for var, size in h.items()
            }
            outs = [merged] * len(eqn.outvars)
        else:
            outs = [{v: scale * _nbytes(v.aval)} for v in eqn.outvars]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def kept_bytes(fun, state, *inputs, names=()) -> Tuple[int, int]:
    """``(held, under a checkpoint)``. The first: bytes a program holds
    between the forward and the backward of ``fun(state, *inputs)``, the
    residuals of ``jax.vjp`` from ONE abstract trace (nothing runs,
    nothing compiles), less ``state`` itself (the parameters are the
    step's state, counted there) and with every residual that elementwise
    and shape ops make counted as the arrays it is made of, once
    (:data:`_FUSED`: XLA makes such a residual again inside the fusion
    that reads it; JAX's own list, unfused, reads five times what a
    compiled Mamba-2 block holds). ``inputs`` that a residual reaches are
    held, and counted. The second: what the same call holds under a
    checkpoint whose policy keeps ``names`` — its inputs and the arrays
    the forward gives one of those names."""
    def residuals(state, *inputs):
        return jax.tree_util.tree_leaves(jax.vjp(fun, state, *inputs)[1])

    jaxpr = jax.make_jaxpr(residuals)(state, *inputs).jaxpr
    n_state = len(jax.tree_util.tree_leaves(state))
    entering = [{} for _ in jaxpr.invars[:n_state]] + [
        {v: _nbytes(v.aval)} for v in jaxpr.invars[n_state:]
    ]
    held, named = {}, {}
    for result in _held(jaxpr, entering, 1, named):
        held.update(result)
    under = sum(sum(e.values()) for e in entering) + sum(
        sum(named.get(name, {}).values()) for name in names
    )
    return sum(held.values()), under


def released_blocks(
    released: Sequence[int], checkpointed: Sequence[int], fixed: int,
    limit: Optional[int],
) -> Tuple[int, ...]:
    """WHICH blocks of a stack that may be checkpointed are not: as many
    as fit. ``released[i]`` is what block i holds for its backward as the
    plain block, ``checkpointed[i]`` what it holds under the checkpoint
    (its input and the kernels' named results), ``fixed`` what the step
    holds whatever the blocks do, ``limit`` the device's memory (None
    where the backend reports none: nothing is released). A choice's
    estimate (:func:`estimated_bytes`) has to stay under
    ``limit · (1 - MARGIN)``.

    Blocks are tried LAST FIRST, each released if the estimate with it
    still fits: the backward walks the stack from its end, so a released
    last block's arrays are the first to be freed and are gone when an
    earlier, checkpointed block makes its own again (the estimate adds
    them up as if they were not, which errs to the safe side most for the
    first blocks); and the order is a function of the bytes alone, so two
    runs of one shape on one device make one program. Forward time saved
    per byte kept would be the better order where kinds differ much; the
    stacks measured so far either release everything or have one kind of
    block in all layers but one (PERF.md section 6, PR 56)."""
    if limit is None:
        return ()
    out = ()
    for i in reversed(range(len(released))):
        if estimated_bytes(
            released, checkpointed, fixed, out + (i,)
        ) <= limit * (1.0 - MARGIN):
            out += (i,)
    return tuple(sorted(out))


def estimated_bytes(released, checkpointed, fixed, out) -> int:
    """What a step holds with the blocks ``out`` released: ``fixed`` +
    :data:`SLACK` x (every block's share as it is run + the largest
    checkpointed block's ``released`` bytes, since its forward runs again
    inside the backward and what it makes is live then + the largest
    block's ``released`` bytes once more, the cotangents and temporaries
    of the backward at work in it), all of it taken to be live at once."""
    stay = [i for i in range(len(released)) if i not in out]
    blocks = sum(released[i] for i in out) + sum(
        checkpointed[i] for i in stay
    ) + max((released[i] for i in stay), default=0) + max(released, default=0)
    return fixed + int(SLACK * blocks)


def device_limit(mesh) -> Optional[int]:
    """One chip's memory as its backend reports it; None where it reports
    none (the CPU), and every block of a ``remat`` model then stays
    checkpointed."""
    stats = mesh.devices.flat[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _chip_bytes(tree) -> int:
    """What ONE chip holds of a tree of arrays laid over a mesh."""
    return sum(
        leaf.addressable_shards[0].data.nbytes
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _under(tree, name: str):
    """The subtree of a nested dict under the first key ``name``."""
    if not isinstance(tree, dict):
        return None
    if name in tree:
        return tree[name]
    for value in tree.values():
        found = _under(value, name)
        if found is not None:
            return found
    return None


def block_bytes(cfg, mixer: str, ffn: str, variables, x) -> Tuple[int, int]:
    """``(released, checkpointed)``: what one block of this kind, with
    these variables, holds for its backward at the input ``x`` as the
    plain block and under the checkpoint. One abstract trace."""
    rngs = {"dropout": dropout.key_for(jax.random.PRNGKey(0))}
    block = TransformerBlock(cfg, mixer, ffn)
    return kept_bytes(
        lambda v, x: block.apply(v, x, False, rngs=rngs, mutable=SOWN),
        variables, x, names=kept_names(),
    )


def _report_checkpoint(blocks, checkpointed, estimate, limit, fell_back):
    from raydp_tpu.utils.profiling import metrics

    metrics.gauge_set("checkpoint/blocks", blocks)
    metrics.gauge_set("checkpoint/blocks_checkpointed", checkpointed)
    metrics.gauge_set("checkpoint/estimated_bytes", estimate)
    metrics.gauge_set("checkpoint/limit_bytes", limit or 0)
    metrics.gauge_set("checkpoint/fell_back", fell_back)


def fit_checkpoint(model, state, sample_batch, mesh, surveyed=None):
    """The model a TRAIN step runs: ``model`` with as many blocks of a
    ``remat`` stack released from the checkpoint as the device's memory
    holds (:func:`released_blocks`), ``model`` itself where it has no such
    stack, where nothing fits, and where the backend reports no limit.
    Every byte is one chip's: ``state`` (the ``TrainState`` on the mesh,
    parameters and moments) as it is laid out — and no more for the
    gradients, which the compiled step hands to the update where they are
    made — the batch, the head's output and its gradient, and the blocks
    at the chip's share of the batch (a ``shard_map``'s gathered rows at
    their gathered size). The gauges ``checkpoint/*`` say what was decided
    from what."""
    cfg = getattr(model, "cfg", None)
    if not getattr(cfg, "remat", False):
        _report_checkpoint(0, 0, 0, None, 0)
        return model
    n = cfg.n_layers
    limit = device_limit(mesh)
    if limit is None or cfg.released:
        _report_checkpoint(n, sum(cfg.checkpointed), 0, limit, 0)
        return model
    batch_chips = mesh.shape.get("dp", 1)
    surveyed = surveyed or survey(model, state.params, sample_batch)
    inputs = [surveyed.blocks[f"block_{i}"] for i in range(n)]
    # One trace a KIND of block: mixer, FFN and the input's shape.
    kinds = [(*layer, x.shape) for layer, x in zip(cfg.layers, inputs)]
    sizes = {}
    for i, (kind, x) in enumerate(zip(kinds, inputs)):
        if kind not in sizes:
            # Layer i's own variables, a collection each, out of the
            # model's: the block's scope is its name.
            variables = {
                name: found for name, tree in state.params.items()
                if (found := _under(tree, f"block_{i}")) is not None
            }
            sizes[kind] = block_bytes(cfg, *kind[:2], variables, x)
    released = [sizes[kind][0] // batch_chips for kind in kinds]
    checkpointed = [sizes[kind][1] // batch_chips for kind in kinds]
    head = sum(map(_nbytes, jax.tree_util.tree_leaves(surveyed.out)))
    fixed = _chip_bytes(state) + (
        2 * head + _nbytes(sample_batch)
    ) // batch_chips
    free = released_blocks(released, checkpointed, fixed, limit)
    estimate = estimated_bytes(released, checkpointed, fixed, free)
    _report_checkpoint(n, n - len(free), estimate, limit, 0)
    logger.info(
        "block checkpoint: %d of %d blocks released %s; the step is "
        "estimated to hold %.2f GiB of the chip's %.2f (%.2f whatever the "
        "blocks do; a block released holds %s MiB, checkpointed %s)",
        len(free), n, list(free), estimate / 2 ** 30, limit / 2 ** 30,
        fixed / 2 ** 30, [a >> 20 for a in released],
        [b >> 20 for b in checkpointed],
    )
    if not free:
        return model
    return model.clone(cfg=dataclasses.replace(cfg, released=free))


def checkpoint_all(model):
    """The way back: ``model`` as its configuration wrote it, every block
    of the stack checkpointed, counted in ``checkpoint/fell_back``."""
    from raydp_tpu.utils.profiling import metrics

    cfg = dataclasses.replace(model.cfg, released=())
    metrics.gauge_set("checkpoint/blocks_checkpointed", sum(cfg.checkpointed))
    metrics.gauge_set("checkpoint/fell_back", 1)
    return model.clone(cfg=cfg)
