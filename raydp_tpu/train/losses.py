"""Loss and metric functions (name-addressable, like the reference's
string-configured losses — reference: tf/estimator.py:87-132 serializes
keras losses by name; torch estimator takes loss instances)."""
from __future__ import annotations

import functools
from typing import Callable, Dict, Union

import jax
import jax.numpy as jnp
import optax


def mse(preds, targets):
    preds = preds.squeeze(-1) if preds.ndim == targets.ndim + 1 else preds
    return jnp.mean((preds - targets) ** 2)


def mae(preds, targets):
    preds = preds.squeeze(-1) if preds.ndim == targets.ndim + 1 else preds
    return jnp.mean(jnp.abs(preds - targets))


def smooth_l1(preds, targets, beta: float = 1.0):
    """Huber/SmoothL1 (the reference's taxi example trains with
    nn.SmoothL1Loss, examples/pytorch_nyctaxi.py)."""
    preds = preds.squeeze(-1) if preds.ndim == targets.ndim + 1 else preds
    diff = jnp.abs(preds - targets)
    return jnp.mean(
        jnp.where(diff < beta, 0.5 * diff**2 / beta, diff - 0.5 * beta)
    )


def binary_crossentropy(logits, targets):
    logits = (
        logits.squeeze(-1) if logits.ndim == targets.ndim + 1 else logits
    )
    return jnp.mean(
        optax.sigmoid_binary_cross_entropy(logits, targets.astype(jnp.float32))
    )


def softmax_crossentropy(logits, targets):
    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits, targets.astype(jnp.int32)
        )
    )


def lm_crossentropy(logits, tokens):
    """Next-token language-modeling loss: ``logits`` are the model's
    outputs on the full sequence ``tokens`` — position t predicts token
    t+1 (the self-supervised objective; targets are the inputs shifted).

    The TARGETS are shifted, never the logits: the last position gets a
    weight of 0 and the mean runs over the same ``B * (S - 1)`` terms as
    ``logits[:, :-1]`` against ``tokens[:, 1:]`` would give. Do not
    "simplify" it back to that slice: an array of ``[B, S-1, V]`` (4,095
    rows) does not tile on the chip, and XLA then copies the logits four
    times around the loss, 17-22 ms of a 203 ms step at
    ``[1, 4096, 100352]`` float32 (PERF.md §6, PR 31). The backward pass
    is written out (``jax.custom_vjp``) as ONE elementwise expression
    over the logits as the head wrote them; autodiff of the log-softmax
    saves a second copy of the logits and scatter-adds the label's
    gradient into a zero array. float32 throughout."""
    targets = jnp.roll(tokens.astype(jnp.int32), -1, axis=1)
    b, s = logits.shape[:2]
    # ``[1, S]``: every position but the last.
    weights = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
    return _weighted_ce(b * (s - 1), logits, targets, weights)


def weighted_crossentropy(logits, targets, weights):
    """``Σ_i w_i · CE(logits_i, targets_i) / (B·S)`` over ``logits`` [B, S,
    V], ``targets`` and ``weights`` [B, S]: position i predicts
    ``targets_i`` (NO shift) and the mean runs over every position, the
    unweighted ones too. :func:`lm_crossentropy`'s written-out backward
    pass (it is this loss with a ``[1, S]`` weight); no gradient reaches
    the weights. float32 throughout."""
    return _weighted_ce(
        logits.shape[0] * logits.shape[1], logits, targets.astype(jnp.int32),
        jax.lax.stop_gradient(weights.astype(jnp.float32)),
    )


def blockdiff_crossentropy(preds, tokens):
    """Block diffusion's loss (``models/blockdiff.py``): ``preds`` is what
    a ``BlockDiffusionLM`` returns in training, the logits of the noised
    copy and a weight a token (``1/t`` of its block where the token was
    masked, 0 elsewhere); ``tokens`` are the clean ids, each position's own
    target."""
    logits, weights = preds
    return weighted_crossentropy(logits, tokens, weights)


def _is_target(logits, targets):
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return vocab == targets[..., None]


def _weighted_ce_fwd(count, logits, targets, weights):
    x = logits.astype(jnp.float32)
    top = jnp.max(x, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
    label = jnp.sum(jnp.where(_is_target(x, targets), x, 0.0), axis=-1)
    loss = jnp.sum((lse - label) * weights) / count
    return loss, (logits, lse, targets, weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_ce(count, logits, targets, weights):
    """``Σ w · CE / count``: ``weights`` broadcasts against ``[B, S]``."""
    return _weighted_ce_fwd(count, logits, targets, weights)[0]


def _weighted_ce_bwd(count, residuals, g):
    logits, lse, targets, weights = residuals
    x = logits.astype(jnp.float32)
    scale = (weights * (g / count))[..., None]
    grad = (
        jnp.exp(x - lse[..., None])
        - _is_target(x, targets).astype(jnp.float32)
    ) * scale
    # Written once, then read by the head's two backward products. Left
    # to itself XLA fuses the expression into the operand of both, and
    # each computes it again for every tile of its output: 7.5 ms of the
    # step above, 4.7 at ``[2, 4096, 50304]`` (PERF.md §6, PR 31).
    grad = jax.lax.optimization_barrier(grad.astype(logits.dtype))
    return grad, None, jnp.zeros_like(weights)


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


LOSSES: Dict[str, Callable] = {
    "mse": mse,
    "mae": mae,
    "smooth_l1": smooth_l1,
    "huber": smooth_l1,
    "bce": binary_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "softmax_ce": softmax_crossentropy,
    "sparse_categorical_crossentropy": softmax_crossentropy,
    "lm_ce": lm_crossentropy,
    "blockdiff_ce": blockdiff_crossentropy,
}


def resolve_loss(loss: Union[str, Callable]) -> Callable:
    if callable(loss):
        return loss
    if loss in LOSSES:
        return LOSSES[loss]
    raise ValueError(f"unknown loss {loss!r}; known: {sorted(LOSSES)}")


# -- metrics ---------------------------------------------------------------
def binary_accuracy(logits, targets):
    logits = (
        logits.squeeze(-1) if logits.ndim == targets.ndim + 1 else logits
    )
    return jnp.mean(((logits > 0).astype(jnp.int32) == targets.astype(jnp.int32)
                     ).astype(jnp.float32))


def categorical_accuracy(logits, targets):
    return jnp.mean(
        (jnp.argmax(logits, -1) == targets.astype(jnp.int32)).astype(
            jnp.float32
        )
    )


METRICS: Dict[str, Callable] = {
    "mse": mse,
    "mae": mae,
    "accuracy": binary_accuracy,
    "binary_accuracy": binary_accuracy,
    "categorical_accuracy": categorical_accuracy,
}


def resolve_metric(metric: Union[str, Callable]) -> Callable:
    if callable(metric):
        return metric
    if metric in METRICS:
        return METRICS[metric]
    raise ValueError(f"unknown metric {metric!r}; known: {sorted(METRICS)}")
