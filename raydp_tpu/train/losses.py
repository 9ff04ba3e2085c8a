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
    return _weighted_ce((b * (s - 1), False), logits, targets, weights)


def weighted_crossentropy(logits, targets, weights):
    """``Σ_i w_i · CE(logits_i, targets_i) / (B·S)`` over ``logits`` [B, S,
    V], ``targets`` and ``weights`` [B, S]: position i predicts
    ``targets_i`` (NO shift) and the mean runs over every position, the
    unweighted ones too. :func:`lm_crossentropy`'s written-out backward
    pass (it is this loss with a ``[1, S]`` weight); no gradient reaches
    the weights. float32 throughout."""
    return _weighted_ce(
        (logits.shape[0] * logits.shape[1], False), logits,
        targets.astype(jnp.int32),
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


def loop_exit_crossentropy(preds, tokens):
    """A looped LM's training loss (``models/loop.py``): ``preds`` is what
    a ``LoopLM`` returns in training (``LoopExits``: the T passes' normed
    states, the log of a token's exit distribution ``p`` [T, B, S], the
    head's [D, V] matrix, β), ``tokens`` the ids, position s predicting
    token s + 1 as :func:`lm_crossentropy` has it:

        1/(B(S-1)) Σ_{b, s<S-1} [ Σ_t p_t · CE(head(h_t), token s+1)
                                  - β · H(p) ],   H(p) = -Σ_t p_t log p_t

    float32 throughout. The head runs HERE, one exit at a time
    (:func:`_exits_ce`): an exit's logits [B, S, V] and their gradient
    live while that exit runs and no longer. The gates that define ``p``
    learn through the weights of the cross-entropies and the entropy.
    Given an array (what the model returns in ``evaluate``: the last
    exit's logits) this is :func:`lm_crossentropy` of it."""
    if not isinstance(preds, tuple):
        return lm_crossentropy(preds, tokens)
    states, log_probs, head, beta = preds
    targets = jnp.roll(tokens.astype(jnp.int32), -1, axis=1)
    b, s = targets.shape
    count = b * (s - 1)
    valid = (jnp.arange(s) < s - 1).astype(jnp.float32)[None, :]
    probs = jnp.exp(log_probs)
    exits = tuple((count, f"exit_{t}") for t in range(len(states)))
    expected, _ = _exits_ce(
        exits, tuple(states), head, (targets,) * len(states), probs * valid
    )
    entropy = -jnp.sum(probs * log_probs, axis=0)
    return expected - beta * (jnp.sum(entropy * valid) / count)


def mtp_crossentropy(preds, tokens):
    """The loss of a model with a multi-token-prediction module
    (``models/mtp.py``): ``preds`` is what an ``MTPLM`` returns in training
    (``MTPHeads``: the main model's normed state and the module's, the
    head's [D, V] matrix they share, lambda), ``tokens`` the ids; head k
    at position s predicts token s + 1 + k:

        L_main + lambda * L_mtp,
        L_main = 1/(B(S-1)) Σ_{s<S-1} CE(head(h_s),  token s+1)
        L_mtp  = 1/(B(S-2)) Σ_{s<S-2} CE(head(h1_s), token s+2)

    float32 throughout. The head runs HERE, over one state at a time
    (:func:`_exits_ce`, each head with its own targets, count and weight):
    the main logits [B, S, V] and their gradient are made, used and freed
    before the module's, which run under the scope ``mtp_head``. The two
    losses are noted for the epoch's gauges (``models/mtp.note_losses``).
    Given an array (what the model returns in ``evaluate``: the main
    logits) this is :func:`lm_crossentropy` of it."""
    if not isinstance(preds, tuple):
        return lm_crossentropy(preds, tokens)
    from raydp_tpu.models import mtp

    states, head, weight = preds
    tokens = tokens.astype(jnp.int32)
    b, s = tokens.shape
    ahead = range(1, len(states) + 1)
    exits = tuple(
        (b * (s - k), "mtp_head" if k > 1 else "main_head") for k in ahead
    )
    weights = jnp.stack([
        (jnp.arange(s) < s - k).astype(jnp.float32)[None, :]
        * (weight if k > 1 else 1.0) for k in ahead
    ])
    total, parts = _exits_ce(
        exits, tuple(states), head,
        tuple(jnp.roll(tokens, -k, axis=1) for k in ahead),
        jax.lax.stop_gradient(weights),
    )
    mtp.note_losses(parts, weight)
    return total


def _head_product(h, head):
    """``nn.Dense(dtype=float32)``'s product, under its scope."""
    with jax.named_scope("lm_head"):
        return jax.lax.dot_general(
            h.astype(jnp.float32), head.astype(jnp.float32),
            (((h.ndim - 1,), (0,)), ((), ())),
        )


def _exits_ce_fwd(exits, states, head, targets, weights):
    """The forward pass makes every gradient too. An exit's loss is linear
    in what comes back to it, so its logits' gradient is made WHERE THE
    LOGITS ARE MADE, by :func:`_weighted_ce`'s own backward expression at
    a cotangent of 1, and goes at once through the head's two backward
    products: what is kept for the backward pass are the states'
    gradients ([B, S, D] each), ONE head-sized sum and the weights'
    cotangents, and no exit's head runs a second time. An
    ``optimization_barrier`` between exits holds exit t + 1's state back
    until everything exit t's logits' gradient feeds is written: one
    exit's logits and their gradient live at a time, by the program's
    order and not by the scheduler's choice."""
    one = jnp.ones((), jnp.float32)
    total = d_head = d_state = None
    d_states, d_weights, parts = [], [], []
    for t, (h, (count, scope)) in enumerate(zip(states, exits)):
        how = (count, True)
        with jax.named_scope(scope):
            if t:
                h, d_head, d_state = jax.lax.optimization_barrier(
                    (h, d_head, d_state)
                )
                d_states[-1] = d_state
            logits, back = jax.vjp(_head_product, h, head)
            loss, kept = _weighted_ce_fwd(
                how, logits, targets[t], weights[t]
            )
            d_logits, _, d_weight = _weighted_ce_bwd(how, kept, one)
            d_state, d_matrix = back(d_logits)
        total = loss if total is None else total + loss
        d_head = d_matrix if d_head is None else d_head + d_matrix
        d_states.append(d_state)
        d_weights.append(d_weight)
        parts.append(loss)
    return (total, tuple(parts)), (
        tuple(d_states), d_head, jnp.stack(d_weights)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exits_ce(exits, states, head, targets, weights):
    """``Σ_t Σ w_t · CE(h_t @ head, targets_t) / count_t`` over the T
    exits of one head (a looped LM's passes; the main model and its
    multi-token-prediction module), and the T terms of that sum (a
    statistic: no gradient goes back through them). ``exits`` is a
    ``(count_t, scope_t)`` an exit, ``states`` a tuple of T arrays
    [B, S, D], ``head`` [D, V], ``targets`` a tuple of T arrays [B, S] (an
    exit's own: a looped LM's are one array T times), ``weights``
    [T, B or 1, S] (they learn: their cotangent is a token's cross-entropy
    at that exit)."""
    return _exits_ce_fwd(exits, states, head, targets, weights)[0]


def _exits_ce_bwd(exits, gradients, g):
    d_states, d_head, d_weights = gradients
    g, _ = g
    return (
        tuple(d * g.astype(d.dtype) for d in d_states), d_head * g, None,
        d_weights * g,
    )


_exits_ce.defvjp(_exits_ce_fwd, _exits_ce_bwd)


def _is_target(logits, targets):
    vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return vocab == targets[..., None]


def _weighted_ce_fwd(how, logits, targets, weights):
    count, learn = how
    x = logits.astype(jnp.float32)
    top = jnp.max(x, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
    label = jnp.sum(jnp.where(_is_target(x, targets), x, 0.0), axis=-1)
    loss = jnp.sum((lse - label) * weights) / count
    # Weights that learn get the cross-entropy a token as their cotangent:
    # kept from here ([B, S]), not made again from the logits.
    return loss, (logits, lse, targets, weights) + (
        (lse - label,) if learn else ()
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_ce(how, logits, targets, weights):
    """``Σ w · CE / count``: ``weights`` broadcasts against ``[B, S]``.
    ``how`` is ``(count, learn)``: with ``learn`` the weights get their
    cotangent, a token's cross-entropy scaled as the forward is (a looped
    LM's exit probabilities are such weights); without it none reaches
    them and the program is the one it was."""
    return _weighted_ce_fwd(how, logits, targets, weights)[0]


def _weighted_ce_bwd(how, residuals, g):
    count, learn = how
    logits, lse, targets, weights, *nll = residuals
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
    if not learn:
        return grad, None, jnp.zeros_like(weights)
    to_weights = nll[0] * (g / count)
    broadcast = tuple(
        axis for axis, size in enumerate(weights.shape)
        if size == 1 and to_weights.shape[axis] != 1
    )
    return grad, None, jnp.sum(to_weights, axis=broadcast, keepdims=True)


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
    "loop_exit_ce": loop_exit_crossentropy,
    "mtp_ce": mtp_crossentropy,
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
