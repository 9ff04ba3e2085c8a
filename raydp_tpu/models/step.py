"""What a model contributes to a train step besides its loss: the one
module that knows which mixers, collections and layouts the families
under ``models/`` have. ``JAXEstimator`` runs any flax module and imports
this and nothing else of ``models/`` and ``ops/``; a module that is none
of these families (no ``cfg``, nothing sown) gets zeros and silence from
every function here. A new mixer adds one line to :func:`report` (and to
:func:`report_epoch` where it sows a statistic), not to the runner.
"""
from __future__ import annotations

import inspect

import jax
import numpy as np

from raydp_tpu.models import (
    blockdiff, dropout, hyperconn, kda, latent, mamba, moe, shortconv,
    sparse_index, stats, window,
)
from raydp_tpu.models.stats import merge  # noqa: F401  (two steps' statistics as one)
from raydp_tpu.models.transformer import LOGICAL_RULES, vocab_rules
from raydp_tpu.ops.flash_attention import report as report_flash_tiles

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


def report(model, params, sample_batch) -> None:
    """Where the step is built: every family's static gauges and log
    line for this model at this batch, zero and silent for a family the
    model has nothing of. One abstract training-mode apply (the dropout
    census); the rest reads the configuration."""
    sites, words = dropout.census(
        model.apply, params, sample_batch, also=step_rngs(model)
    ) if _takes_deterministic(model) else (0, 0)
    dropout.report(sites, words)
    cfg = getattr(model, "cfg", None)
    batch, seq_len = sample_batch.shape[0], int(sample_batch.shape[-1])
    # The rows a step sends through every layer: the batch's tokens,
    # or more of them where the model lays copies side by side (block
    # diffusion's pair).
    tokens_per_step = int(np.prod(sample_batch.shape)) * getattr(
        model, "positions_per_token", 1
    )
    mamba.report(cfg, tokens_per_step=tokens_per_step)
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
