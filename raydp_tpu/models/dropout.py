"""Dropout whose mask is made once a step, by the chip's bit generator.

A model states a dropout probability, not a bit stream. Two things decide
what a mask costs on a TPU (PERF.md §6, PR 27; BERT-base, 25 sites of
12.6M elements, 94.3 ms a step before):

* **The generator.** A threefry mask is an elementwise chain from an iota,
  some 60 integer operations a word on the vector unit (0.28 ms a site),
  and being elementwise XLA fuses a copy of it into every consumer of the
  mask, forward and backward (23.7 ms a step of replay). An ``rbg`` key
  draws the mask's words with ONE ``RngBitGenerator`` op, 0.02 ms a site.
  So the train step hands the ``dropout`` collection :func:`key_for` of
  its step key. The step's own chain (split per step, folded per scan
  step), ``model.init`` and the shuffle stay on jax's default threefry
  keys: parameters, epoch order and resume do not move. ``rbg`` and not
  ``unsafe_rbg``: split and fold_in stay threefry. No XLA flag:
  ``xla_tpu_spmd_rng_bit_generator_unsafe`` would make the masks depend
  on the sharding (without it every chip of a mesh draws the whole mask
  and keeps its slice).
* **What the backward reads.** Left alone, XLA saves the generator's
  32-bit words and redoes the comparison in each consumer (4 bytes an
  element, read three times). :class:`Dropout` pins the one-byte mask as
  the saved residual with an ``optimization_barrier``: 3.2 ms a step and
  1.0 GiB of BERT-base's 6.2 less.
"""
from __future__ import annotations

import logging
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

GENERATOR = "rbg"


class Dropout(nn.Module):
    """``flax.linen.Dropout`` (same rng collection, same automatic name,
    the whole input masked) with the mask saved for the backward."""

    rate: float

    @nn.compact
    def __call__(self, x, deterministic: bool):
        if self.rate == 0.0 or deterministic:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(self.make_rng("dropout"), keep, x.shape)
        mask = jax.lax.optimization_barrier(mask)
        return jax.lax.select(mask, x / keep, jnp.zeros_like(x))


def key_for(step_key):
    """The ``dropout`` collection's key for one step: a deterministic
    function of the step's key (typed or raw), so of ``(seed, step)``."""
    return jax.random.wrap_key_data(
        jax.random.bits(step_key, (4,), jnp.uint32), impl=GENERATOR
    )


def counting():
    """``(interceptor, read)``: a flax method interceptor that counts the
    dropout modules (this one or flax's) that draw a mask under it and the
    32-bit words those masks are drawn from, and the function that reads
    ``(sites, mask words)`` afterwards."""
    found = [0, 0]

    def count(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        module = context.module
        if (isinstance(module, (Dropout, nn.Dropout))
                and context.method_name == "__call__"):
            inputs = args[0] if args else next(iter(kwargs.values()))
            # Rate 0 or deterministic: the module returns its input.
            if out is not inputs:
                shape = list(inputs.shape)
                for dim in getattr(module, "broadcast_dims", ()):
                    shape[dim] = 1
                found[0] += 1
                found[1] += math.prod(shape)
        return out

    return count, lambda: tuple(found)


def census(apply_fn, variables, sample_batch, also=()):
    """``(sites, mask words)`` of one training-mode step: the dropout
    modules that draw a mask when the model is applied to a batch, and
    the 32-bit words those masks are drawn from (:func:`counting`). From
    one abstract apply; nothing runs. ``also`` names the further rng
    collections the model's step draws from (the apply needs a key for
    each)."""
    count, read = counting()
    with nn.intercept_methods(count):
        jax.eval_shape(
            lambda v, x: apply_fn(
                v, x, deterministic=False,
                rngs={name: key_for(jax.random.PRNGKey(0))
                      for name in ("dropout",) + tuple(also)},
            ),
            variables, sample_batch,
        )
    return read()


def report(sites: int, words: int) -> None:
    """Static for a compiled step: two gauges and one log line where the
    step is built, nothing per step."""
    from raydp_tpu.utils.profiling import metrics

    metrics.gauge_set("train/dropout_sites", sites)
    metrics.gauge_set("train/dropout_mask_words_per_step", words)
    if sites:
        logger.info(
            "train step dropout: %d sites, %d mask words a step, from the "
            "%s generator (one RngBitGenerator op a site)",
            sites, words, GENERATOR,
        )
