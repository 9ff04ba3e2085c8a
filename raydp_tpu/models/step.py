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
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Jaxpr, Literal

from raydp_tpu.models import (
    blockdiff, dropout, gdn, hyperconn, kda, latent, loop, mamba, moe, mtp,
    shortconv, sparse_index, stats, window,
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
    ``rng`` too. A step's trace begins here: what a loss notes about it
    (``stats.note``) is this step's from now on."""
    stats.begin_step()
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
    it is given, so its result's shape is its input's; ONE input a name,
    which is right for a stack run ``cfg.passes`` times too: every
    application of a block has the same shapes) and the causal
    convolutions ``(kernel calls, jax.numpy calls, kernel calls with the
    L2 norm inside)`` by the form each takes
    (``models/mamba.counting_convs``), and the state-space scans ``(kernel
    calls, jax.numpy calls)`` (``models/mamba.counting_scans``)."""
    dropout: Tuple[int, int] = (0, 0)
    out: Any = None
    blocks: dict = {}
    convs: Tuple[int, int, int] = (0, 0, 0)
    scans: Tuple[int, int] = (0, 0)


def survey(model, params, sample_batch) -> Survey:
    """:class:`Survey` of ``model`` at this batch; nothing runs. Empty for
    a model whose ``__call__`` takes no ``deterministic`` (no dropout to
    count, no stack of blocks)."""
    if not _takes_deterministic(model):
        return Survey()
    count, read = dropout.counting()
    count_convs, read_convs = mamba.counting_convs()
    count_scans, read_scans = mamba.counting_scans()

    def apply(variables, x):
        out, sown = model.apply(
            variables, x, mutable=SOWN + ("intermediates",),
            capture_intermediates=lambda module, _: isinstance(
                module, TransformerBlock),
            **apply_kwargs(model, jax.random.PRNGKey(0))
        )
        return out, sown.get("intermediates", {})

    with nn.intercept_methods(count), nn.intercept_methods(count_convs), \
            nn.intercept_methods(count_scans):
        out, captured = jax.eval_shape(apply, params, sample_batch)
    blocks = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(captured):
        keys = [getattr(key, "key", None) for key in path]
        if "__call__" in keys:      # <module path>/block_i/__call__/0
            blocks[keys[keys.index("__call__") - 1]] = leaf
    return Survey(read(), out, blocks, read_convs(), read_scans())


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
        cfg, tokens_per_step=tokens_per_step, convs=surveyed.convs,
        scans=surveyed.scans,
    )
    kda.report(
        cfg, tokens_per_step=tokens_per_step, sequence=seq_len,
        convs=surveyed.convs,
    )
    gdn.report(cfg, tokens_per_step=tokens_per_step, sequence=seq_len)
    shortconv.report(cfg)
    latent.report(cfg)
    window.report(cfg)
    sparse_index.report(cfg, seq_len=seq_len)
    blockdiff.report(model, batch=batch, seq_len=seq_len)
    hyperconn.report(cfg)
    loop.report(model)
    mtp.report(model, params)
    report_flash_tiles(cfg, seq_len=seq_len, batch=batch)
    moe.report(model, tokens_per_step=tokens_per_step)


def report_epoch(stats_sum: dict, n_batches: int) -> None:
    """The gauges of an epoch's statistics (:func:`step_stats` merged
    over its steps and fetched with its loss)."""
    moe.report_epoch(stats_sum, n_batches)
    hyperconn.report_epoch(stats_sum)
    blockdiff.report_epoch(stats_sum)
    sparse_index.report_epoch(stats_sum)
    loop.report_epoch(stats_sum)
    mtp.report_epoch(stats_sum, n_batches)


# ------------------------------------------------- the block checkpoint
#
# How many blocks of a ``remat`` stack are checkpointed follows from the
# shapes and the device's memory: :func:`fit_checkpoint` reads both where
# the step is built and releases the blocks whose residuals fit.

#: What a compiled step holds over :func:`estimated_bytes`' count of it
#: (HBM tile padding, the copies XLA writes to change a layout), and the
#: share of the device's limit the estimate leaves free. The count is a
#: walk: what is HELD for the backward (the blocks before the one at work,
#: released or checkpointed; the gradients of those after it; the logits'
#: gradient where the head shares the embedding's table) beside the
#: WORKING set of the one block whose forward or backward runs, the most
#: bytes live at once in program order. Both from one table (PR 60;
#: PERF.md section 6 has every row): the step compiled for a TPU v5e,
#: ``memory_analysis()`` arguments + temporaries, in GiB of 15.75 —
#:
#:   cell (blocks)    all checkpointed    as run                 one more
#:                    estimate compiled   released  est.  comp.   est.  comp.
#:   Granite (6)        11.04   10.57     6 of 6   14.02  13.45     -     -
#:   LFM2 (7)           10.92   10.29     7 of 7   14.19  13.53     -     -
#:   Xing4.0 (5)        11.51   10.83     5 of 5   14.59  13.90     -     -
#:   Laguna (5)         13.70   12.29     3, 4     14.95  13.52   16.27 13.69
#:   Kimi Linear (5)    14.34   13.18     4        14.34  14.29   15.58 14.43
#:   SDAR (6)           12.95   11.91     4, 5     14.30  13.49   15.65 13.81
#:   Keye (5)           11.68   10.24     2, 3, 4  14.15  13.07   15.38 13.82
#:   Nemotron (9)       12.45   12.10     5, 7, 8  14.57  13.89   15.33 13.97
#:   Mellum2 (4, a chip) 12.42   9.91     1, 2, 3  14.40  12.43   15.70 13.45
#:   Ouro (6 x 4 passes)  11.56  12.15     4, 5     14.71  14.56   16.29   -
#:
#: (Ouro, PR 61: a stack run four times, the walk over its 24 applications.
#: Its all-checkpointed row is the one row where the estimate reads UNDER
#: the compiler, which starts the last pass's second forward before the
#: exits and holds it; the rule never stops there at a v5e's limit, and
#: the choice it makes and its neighbour, block 5 alone 13.14 against
#: 13.06, are bounded.)
#: 1.2 is the least slack (in tenths) at which the estimate is no lower
#: than the compiled figure in any row of the table (Kimi Linear
#: with its last block released binds: 14.34 against 14.29 since PR 66
#: took two float32 arrays out of a KDA block, 14.65 against 14.41 before;
#: it was 1.6 on a count that took everything to be live at once); 5% of
#: 15.75 GiB leaves the estimate 14.96, which admits the two stacks whole
#: (Granite 13.45 and LFM2 13.53 GiB compiled) and leaves every compiled
#: choice 1.3 GiB or more under the limit. Where sequences are long the
#: count still reads 1-2 GiB over the compiler, which orders the inside
#: of a routed layer's conditional better than its program order.
#: Constants, not parameters.
SLACK = 1.2
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
# Of those, the ones whose result is its operand's bytes in place.
_VIEWS = frozenset((
    "copy", "copy_p", "expand_dims", "mesh_cast", "name", "pvary", "reshape",
    "sharding_constraint", "squeeze", "stop_gradient",
))
# What a literal or a constant is made of: no array, and it stands.
_NOTHING = (frozenset(), True)
# Calls whose body is read through: the callee's arrays are the caller's.
_INLINED = frozenset((
    "pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
))


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * np.dtype(aval.dtype).itemsize


def _held(jaxpr, entering, scale, named, sizes, order):
    """For each of ``jaxpr``'s results, ``(held, whole)``: the set of
    arrays it is made of that a program holds, results of primitives that
    write (products, reductions, kernels, scans, collectives) and the
    arrays ``entering`` (one such pair an input), and whether it IS one
    array as it stands (written so, or a view of one). An array is its
    index in ``sizes``, its bytes, which gains every array written on the
    way; ``order`` gains, for each primitive that writes,
    in program order, ``(the arrays it reads, the arrays it writes, what
    it has live inside)``. A primitive that runs programs of its own (a
    kernel, a conditional, a loop) reads arrays: an operand that is not
    whole is written for it, and is that array from there on. A
    ``shard_map``'s body is read with its per-chip shapes scaled to the
    whole mesh. ``named`` gathers ``{name: {var: bytes}}`` of every value
    given a name (``checkpoint_name``): a checkpoint's policy writes it
    whatever it is made of."""
    env = dict(zip(jaxpr.invars, entering))

    def read(var):
        # Literals (unhashable) and constants: nothing held.
        return _NOTHING if isinstance(var, Literal) else env.get(
            var, _NOTHING)

    def written(var):
        sizes.append(scale * _nbytes(var.aval))
        return frozenset((len(sizes) - 1,)), True

    for eqn in jaxpr.eqns:
        name, params = eqn.primitive.name, eqn.params
        inputs = [read(v) for v in eqn.invars]
        inner = params.get("jaxpr", params.get("call_jaxpr"))
        if name in _INLINED and inner is not None:
            outs = _held(getattr(inner, "jaxpr", inner), inputs, scale,
                         named, sizes, order)
        elif name == "shard_map":
            chips = math.prod(params["mesh"].shape.values())
            outs = _held(params["jaxpr"], inputs, scale * chips, named,
                         sizes, order)
        elif name in _FUSED:
            merged = frozenset().union(*(held for held, _ in inputs))
            if name == "name":
                var = eqn.outvars[0]
                named.setdefault(params["name"], {})[var] = (
                    scale * _nbytes(var.aval)
                )
            whole = name in _VIEWS and inputs[0][1]
            outs = [(merged, whole)] * len(eqn.outvars)
        else:
            programs = _programs(eqn)
            for var in eqn.invars if programs else ():
                if not read(var)[1]:
                    made_of = read(var)[0]
                    env[var] = written(var)
                    order.append((made_of, env[var][0], 0))
            outs = [written(v) for v in eqn.outvars]
            order.append((
                frozenset().union(*(read(v)[0] for v in eqn.invars)),
                frozenset().union(*(held for held, _ in outs)),
                # A kernel's own program works in VMEM.
                0 if name == "pallas_call" else _inside(programs, scale),
            ))
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def _programs(eqn) -> list:
    """The programs a primitive runs of its own: the jaxprs among its
    parameters (a conditional's branches, a loop's body, a kernel's)."""
    inner = [
        getattr(each, "jaxpr", each) for value in eqn.params.values()
        for each in (value if isinstance(value, (tuple, list)) else (value,))
    ]
    return [each for each in inner if isinstance(each, Jaxpr)]


def _inside(programs, scale) -> int:
    """The most that ``programs``, which one primitive runs (a
    conditional's branches, the greatest; a loop's body, one turn), have
    live at once of what they write themselves, beyond their results,
    which the caller counts as the primitive's."""
    most = 0
    for inner in programs:
        sizes, order = [], []
        results = frozenset().union(*(held for held, _ in _held(
            inner, [_NOTHING] * len(inner.invars), scale, {}, sizes, order
        )))
        order.append((results, (), 0))
        most = max(most, _most_live(sizes, order) - sum(
            sizes[array] for array in results
        ))
    return most


class Counted(NamedTuple):
    """What one abstract trace of a block's forward and backward counts
    (:func:`kept_bytes`), in bytes."""
    released: int       # held between the forward and the backward
    checkpointed: int   # the same under a checkpoint
    working: int        # the most live at once while either runs
    gradients: int      # what the parameters' gradients hold afterwards


def kept_bytes(fun, state, *inputs, names=()) -> Counted:
    """:class:`Counted` of ``fun(state, *inputs)`` from ONE abstract trace
    of ``jax.vjp`` and its pullback (nothing runs, nothing compiles).

    ``released``: bytes a program holds between the forward and the
    backward, the residuals of ``jax.vjp`` less ``state`` itself (the
    parameters are the step's state, counted there) and with every
    residual that elementwise and shape ops make counted as the arrays it
    is made of, once (:data:`_FUSED`: XLA makes such a residual again
    inside the fusion that reads it; JAX's own list, unfused, reads five
    times what a compiled Mamba-2 block holds). ``inputs`` that a residual
    reaches are held, and counted.

    ``checkpointed``: what the same call holds under a checkpoint whose
    policy keeps ``names`` — its inputs and the arrays the forward gives
    one of those names.

    ``working``: the greatest number of bytes live at once while the
    forward and then the backward run, read in program order with the
    same fusing: an array lives from the primitive that writes it to the
    last primitive that writes from it (an input from the start, the
    result's cotangent from the backward's first read of it, a gradient
    to the end); a conditional or a loop adds, while it runs, the most
    its own programs have live (:func:`_inside`); a kernel's temporaries
    are in VMEM and not counted.

    ``gradients``: what the gradients of ``state`` are made of, held
    from this call's backward to the update."""
    n_state = len(jax.tree_util.tree_leaves(state))
    shape = {}

    def both(state, inputs, one):
        out, pullback = jax.vjp(fun, state, *inputs)
        residuals = jax.tree_util.tree_leaves(pullback)
        cotangent = jax.tree_util.tree_map(
            lambda leaf: jnp.full_like(leaf, one)
            if jnp.issubdtype(leaf.dtype, jnp.inexact)
            else np.zeros(leaf.shape, jax.dtypes.float0), out,
        )
        shape.update(
            residuals=len(residuals),
            cotangent=sum(map(_nbytes, jax.tree_util.tree_leaves(out))),
        )
        return residuals, pullback(cotangent)

    jaxpr = jax.make_jaxpr(both)(
        state, inputs, jax.ShapeDtypeStruct((), np.float32)
    ).jaxpr
    sizes = [_nbytes(v.aval) for v in jaxpr.invars[n_state:-1]]
    # The result's cotangent: one array, the next block's to write.
    cotangent = len(sizes)
    sizes.append(shape["cotangent"])
    entering = [_NOTHING] * n_state + [
        (frozenset((array,)), True) for array in range(len(sizes))
    ]
    named, order = {}, []
    results = [
        held for held, _ in _held(jaxpr, entering, 1, named, sizes, order)
    ]
    kept = frozenset().union(*results[:shape["residuals"]]) - {cotangent}
    under = sum(sizes[:cotangent]) + sum(
        sum(named.get(name, {}).values()) for name in names
    )
    gradients = results[shape["residuals"]:]
    order.append((frozenset().union(*gradients), (), 0))
    return Counted(
        sum(sizes[array] for array in kept), under,
        _most_live(sizes, order, cotangent),
        sum(sizes[array] for array in frozenset().union(
            *gradients[:n_state])),
    )


def _most_live(sizes, order, cotangent=None) -> int:
    """The greatest sum of ``sizes`` live at once over ``order``'s steps:
    an array is live from the step that writes it (``cotangent`` from the
    step that first reads it, any other that no step writes from the
    start) to the last step that reads it, and a step's third entry is
    live during that step alone."""
    born, last = {}, {}
    for at, (reads, writes, _) in enumerate(order):
        for array in writes:
            born[array] = last[array] = at
        for array in reads:
            if array == cotangent:
                born.setdefault(array, at)
            last[array] = at
    change = [0] * (len(order) + 1)
    for array, at in last.items():
        change[born.get(array, 0)] += sizes[array]
        change[at + 1] -= sizes[array]
    most = live = 0
    for delta, (_, _, inside) in zip(change, order):
        live += delta
        most = max(most, live + inside)
    return most


class Stack(NamedTuple):
    """What :func:`estimated_bytes` reads of a step, in one chip's bytes:
    a :class:`Counted` each block (``released[i]``, ``checkpointed[i]``,
    ``working[i]``, ``gradients[i]``), ``fixed`` what the step holds from
    end to end (the state, the batch), ``head`` the logits (their
    gradient is as much again) and ``head_stays`` what of the head is
    still held while the blocks' backward runs: the logits' gradient
    where the head shares the embedding's table, whose update waits for
    the lookup's gradient at the very end (the compiled Granite and LFM2
    steps hold it until then), nothing otherwise.

    A stack run ``passes`` times over one set of weights (a looped LM)
    holds a block's kept arrays once an APPLICATION and its parameters'
    gradients once a block; ``exits`` of its passes end in a head
    (``head`` is ONE exit's logits: each exit's are made and freed before
    the next's), and with more than one ``head_stays`` is the head's own
    gradient, which the exits add up and hold until the update."""
    released: Sequence[int]
    checkpointed: Sequence[int]
    working: Sequence[int]
    gradients: Sequence[int]
    fixed: int
    head: int
    head_stays: int
    passes: int = 1
    exits: int = 1
    # A block's gradients in the dtype of the parameters they are for,
    # which is what a compiled step holds them in (a product in the
    # compute dtype writes a gradient, and the compiler leaves the rounding
    # out); empty = not known (taken as ``gradients``).
    parameters: Sequence[int] = ()


class Estimate(NamedTuple):
    """:func:`estimated_bytes`' result: ``total`` and its two parts where
    it is greatest, before the slack."""
    total: int
    held: int
    working: int


def released_blocks(stack: Stack, limit: Optional[int]) -> Tuple[int, ...]:
    """WHICH blocks of a stack that may be checkpointed are not: as many
    as fit ``limit``, the device's memory (None where the backend reports
    none: nothing is released). A choice's estimate
    (:func:`estimated_bytes`) has to stay under ``limit · (1 - MARGIN)``.

    Blocks are tried LAST FIRST, each released if the estimate with it
    still fits: the backward walks the stack from its end, so a released
    last block's arrays are the first to be freed and are gone when an
    earlier block's backward runs; and the order is a function of the
    bytes alone, so two runs of one shape on one device make one program.
    Forward time saved per byte kept would be the better order where
    kinds differ much (PERF.md section 7).

    Nothing is released where the estimate is known to be short by more
    than the margin (:func:`uncounted_bytes`): what the configuration
    wrote, every block checkpointed, is the least a step can hold."""
    if limit is None:
        return ()
    out = ()
    for i in reversed(range(len(stack.released))):
        if estimated_bytes(stack, out + (i,)).total <= limit * (1.0 - MARGIN):
            out += (i,)
    if uncounted_bytes(stack, out) > limit * MARGIN:
        return ()
    return tuple(sorted(out))


def _walk(stack: Stack, out):
    """``(held, working, uncounted)`` at every step of the walk that
    :func:`estimated_bytes` describes; ``uncounted`` is what the
    gradients held or written at that step are in their parameters' dtype
    over what the count has them as."""
    held = [
        stack.released[i] if i in out else stack.checkpointed[i]
        for i in range(len(stack.released))
    ]
    n, gradients = len(held), sum(stack.gradients)
    short = [
        whole - counted for whole, counted in zip(
            stack.parameters or stack.gradients, stack.gradients)
    ]
    walk = [(
        stack.passes * sum(held) + (stack.head_stays if stack.exits > 1 else 0),
        2 * stack.head, 0,
    )]
    for t in range(stack.passes):
        last = t + 1 == stack.passes
        walk += [(
            t * sum(held) + sum(held[:i]) + stack.head_stays + (
                sum(stack.gradients[i + 1:]) if last
                else gradients - stack.gradients[i]
            ),
            stack.working[i],
            sum(short[i:]) if last else sum(short),
        ) for i in range(n)]
    return walk


def uncounted_bytes(stack: Stack, out) -> int:
    """How far the estimate is KNOWN to be short with the blocks ``out``
    released: the gradients' bytes that the count leaves out (it has a
    gradient as the product that writes it, in the compute dtype; the
    compiled steps hold it in its parameter's, PR 63's rows of the table)
    where the walk holds most with them, if that is more than the slack
    covers there; 0 where the slack covers it, which it does in every row
    of the table that was fitted (activations are most of those steps).
    A stack whose STATE is most of the chip (13.8 GiB of 15.75) is short
    by 1.5 GiB where the slack is 0.5: :func:`released_blocks` releases
    nothing where this passes the margin it leaves free."""
    held, working, uncounted = max(_walk(stack, out), key=sum)
    covered = int((SLACK - 1.0) * (held + working))
    return uncounted if uncounted > covered else 0


def estimated_bytes(stack: Stack, out) -> Estimate:
    """What a step holds with the blocks ``out`` released, as the
    greatest total over the walk a step makes. Block i's forward and,
    from the last block to the first, its backward run beside what blocks
    0..i-1 hold for theirs (``released`` or ``checkpointed`` bytes each):
    later blocks' arrays are not yet made in the forward and are freed in
    the backward, where their parameters' ``gradients`` stay in their
    place until the update (a kernel's and a conditional's are written
    whole; the compiled steps keep them to the end), and ``working[i]``
    covers both passes of block i, so a checkpointed block's second
    forward too. Between the two walks the head runs, logits and their
    gradient, beside what EVERY block holds. Each total is ``fixed`` +
    :data:`SLACK` x (held + working).

    With ``stack.passes`` > 1 the walk is over APPLICATIONS: pass t's
    block i runs beside what every application of passes 0..t-1 and
    blocks 0..i-1 of pass t hold (a block is released in all its
    applications or in none), and beside the gradients of the blocks whose
    backward has run in ANY pass: once a block, so all the others' in
    every pass but the last. The exits run between the two walks, one
    after the other, one exit's logits and their gradient at a time beside
    what EVERY application holds; their gradients are made there
    (``train/losses._exits_ce``), so where there is more than one the
    head's own (``head_stays``) is held from then on."""
    parts = max((step[:2] for step in _walk(stack, out)), key=sum)
    return Estimate(stack.fixed + int(SLACK * sum(parts)), *parts)


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


def _under_path(tree, scope):
    """:func:`_under` one name of ``scope`` after the other."""
    for name in scope:
        tree = _under(tree, name)
    return tree


def block_bytes(cfg, mixer: str, ffn: str, variables, x) -> Counted:
    """:class:`Counted` of one block of this kind, with these variables,
    at the input ``x``: what it holds for its backward as the plain block
    and under the checkpoint, and the most its two passes have live at
    once. One abstract trace."""
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
    metrics.gauge_set("checkpoint/estimated_bytes", estimate.total)
    metrics.gauge_set("checkpoint/held_bytes", estimate.held)
    metrics.gauge_set("checkpoint/working_bytes", estimate.working)
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
    nothing = Estimate(0, 0, 0)
    if not getattr(cfg, "remat", False):
        _report_checkpoint(0, 0, nothing, None, 0)
        return model
    # The stack's blocks by their scopes and, behind them, the block of a
    # multi-token-prediction module (``models/mtp.py``: ``mtp/block``, a
    # layer of the stack's last kind that runs after ``ln_final`` and
    # before the heads; in ``cfg.released`` it is ``cfg.n_layers``).
    scopes = [(f"block_{i}",) for i in range(cfg.n_layers)]
    layers = list(cfg.layers)
    if isinstance(model, mtp.MTPLM):
        scopes.append(("mtp", "block"))
        layers.append(cfg.layers[-1])
    n = len(scopes)
    limit = device_limit(mesh)
    if limit is None or cfg.released:
        _report_checkpoint(
            n, sum(cfg.remat and i not in cfg.released for i in range(n)),
            nothing, limit, 0,
        )
        return model
    batch_chips = mesh.shape.get("dp", 1)
    surveyed = surveyed or survey(model, state.params, sample_batch)
    inputs = [surveyed.blocks[scope[-1]] for scope in scopes]
    # One trace a KIND of block: mixer, FFN and the input's shape.
    kinds = [(*layer, x.shape) for layer, x in zip(layers, inputs)]
    counted, whole = {}, {}
    for scope, kind, x in zip(scopes, kinds, inputs):
        if kind not in counted:
            # The layer's own variables, a collection each, out of the
            # model's: the block's scope is its name.
            variables = {
                name: found for name, tree in state.params.items()
                if (found := _under_path(tree, scope)) is not None
            }
            counted[kind] = block_bytes(cfg, *kind[:2], variables, x)
            whole[kind] = sum(
                _nbytes(leaf) for leaf in jax.tree_util.tree_leaves(variables)
                if jnp.issubdtype(leaf.dtype, jnp.inexact)
            )
    exits = loop.exit_bytes(model, surveyed.out) or mtp.exit_bytes(
        model, surveyed.out)
    if exits is None:       # one head: the model's output is its logits
        head = sum(map(_nbytes, jax.tree_util.tree_leaves(surveyed.out)))
        exits = (1, head, head if cfg.tie_head else 0)
    stack = Stack(
        *([size // batch_chips for size in sizes]
          for sizes in zip(*(counted[kind] for kind in kinds))),
        parameters=[whole[kind] // batch_chips for kind in kinds],
        fixed=_chip_bytes(state) + _nbytes(sample_batch) // batch_chips,
        head=exits[1] // batch_chips,
        head_stays=exits[2] // batch_chips,
        passes=cfg.passes, exits=exits[0],
    )
    free = released_blocks(stack, limit)
    estimate = estimated_bytes(stack, free)
    _report_checkpoint(n, n - len(free), estimate, limit, 0)
    short = uncounted_bytes(stack, free)
    if short:
        logger.info(
            "block checkpoint: the gradients are %d MiB more in their "
            "parameters' dtype than the estimate counts where the step "
            "holds most, more than its slack covers: nothing is released "
            "where that passes the margin (%d MiB)",
            short >> 20, int(limit * MARGIN) >> 20,
        )
    logger.info(
        "block checkpoint: %d of %d blocks released %s; the step is "
        "estimated to hold %.2f GiB of the chip's %.2f: %.2f from end to "
        "end, and where it holds most %d MiB kept for the backward beside "
        "%d MiB at work (a block released keeps %s MiB, checkpointed %s, "
        "and has %s at work; the head %d; gradients %s)",
        len(free), n, list(free), estimate.total / 2 ** 30, limit / 2 ** 30,
        stack.fixed / 2 ** 30, estimate.held >> 20, estimate.working >> 20,
        *([size >> 20 for size in sizes] for sizes in stack[:3]),
        stack.head >> 20, [size >> 20 for size in stack.gradients],
    )
    if not free:
        return model
    return model.clone(cfg=dataclasses.replace(cfg, released=free))


def checkpoint_all(model):
    """The way back: ``model`` as its configuration wrote it, every block
    of the stack checkpointed, counted in ``checkpoint/fell_back``."""
    from raydp_tpu.utils.profiling import metrics

    cfg = dataclasses.replace(model.cfg, released=())
    metrics.gauge_set(
        "checkpoint/blocks_checkpointed",
        sum(cfg.checkpointed) + isinstance(model, mtp.MTPLM),
    )
    metrics.gauge_set("checkpoint/fell_back", 1)
    return model.clone(cfg=cfg)
