"""A looped language model's exits (Zhu et al. 2025, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741, as Ouro is
trained in its stage I).

The stack runs ``T = cfg.passes`` times over one set of weights
(``TransformerEncoder``, which closes every pass with ``ln_final`` and
hands back the T normed states ``h_t``). After every pass the model has an
EXIT: a gate ``λ_t = sigmoid(exit_gate(h_t))`` a token and the output head
``z_t = lm_head(h_t)``. The gates define the exit distribution of a token,

    S_0 = 1,  S_t = S_{t-1} (1 - λ_t),
    p_t = λ_t S_{t-1}  (t < T),      p_T = S_{T-1}  (the rest),

and training minimises the expected next-token loss under it, less an
entropy term that keeps the gates from collapsing onto one exit:

    L = 1/(B(S-1)) Σ_{b, s<S-1} [ Σ_t p_t c_t  -  β H(p) ],
    c_t = CE(z_t, next token),   H(p) = -Σ_t p_t log p_t

(``train/losses.loop_exit_crossentropy``). The gates learn ONLY through
the weights ``p_t`` of the cross-entropies (and the entropy).

:class:`LoopLM` is that model on ``CausalLM``'s parameter tree
(``encoder``, ``lm_head``) plus ``exit_gate``, a float32 ``Dense(1)`` with
bias. One exit's logits at 8,192 x 49,152 float32 are 1.6 GB and a loss's
residual IS its logits, so T exits held for the backward do not fit beside
the state: in TRAINING the model returns :class:`LoopExits` — the T normed
states, ``log p``, and the head's matrix — and the loss takes the head
over ONE exit at a time from the pass's state (33.5 MB) and makes that
exit's gradient where its logits are made (``train/losses._exits_ce``,
which takes an exit's own targets, count and scope: here the one shifted
target array T times, ``B(S-1)`` and ``exit_<t>``; a multi-token-prediction
module's two heads, ``models/mtp.py``, bring two of each):
an exit's logits and their gradient are made, used and freed before the
next exit's, and no head runs twice. DETERMINISTIC calls
(``predict``, ``evaluate``) run all T passes and return the LAST exit's
logits ``[B, S, V]``: the cumulative exit probability reaches 1 only
there, which is what an exit threshold of 1 says. An exit threshold under
1 at serving, and the gates trained alone on a frozen model (the paper's
stage II), are not here: ROADMAP, Reach.
"""
from __future__ import annotations

import logging
import math
from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.models import stats
from raydp_tpu.models.transformer import (
    TransformerConfig,
    TransformerEncoder,
    _dense_init,
    _logits,
)

logger = logging.getLogger(__name__)

# Sown about a step (``models/stats.py``), summed over its tokens: the
# exit distribution's mass an exit ([T]), its entropy, and the tokens.
EXIT_MASS = stats.declare("loop_exit_mass")
ENTROPY = stats.declare("loop_exit_entropy")
TOKENS = stats.declare("loop_tokens")


class LoopExits(NamedTuple):
    """What a :class:`LoopLM` returns in training, for
    ``train/losses.loop_exit_crossentropy``: ``states`` the T normed
    states [B, S, D]; ``log_probs`` [T, B, S] float32, the log of a
    token's exit distribution (``exp`` of it sums to 1 over T);
    ``head`` the output head's [D, V] matrix, which every exit shares;
    ``entropy_weight`` β."""
    states: Tuple[Any, ...]
    log_probs: Any
    head: Any
    entropy_weight: Any


def exit_log_probs(gate_logits):
    """``log p`` [T, B, S] from the T-1 gates' logits [T-1, B, S], in log
    space (a saturated sigmoid is an exact 0 or 1 in float32, and the
    entropy takes ``p log p``): ``log λ = log_sigmoid(g)``, ``log (1 - λ)
    = log_sigmoid(-g)``, ``log p_t = log λ_t + Σ_{j<t} log (1 - λ_j)`` and
    the last exit takes what is left, ``Σ_{j<T} log (1 - λ_j)``."""
    stay = jnp.concatenate([
        jnp.zeros((1,) + gate_logits.shape[1:], gate_logits.dtype),
        jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0),
    ], axis=0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits) + stay[:-1], stay[-1:]], axis=0
    )


class LoopLM(nn.Module):
    """A decoder stack run ``cfg.passes`` times with an exit after every
    pass (the module docstring). ``entropy_weight`` is β."""

    cfg: TransformerConfig
    entropy_weight: float = 0.05

    def setup(self):
        cfg = self.cfg
        if not cfg.causal:
            raise ValueError("LoopLM requires cfg.causal=True")
        if (cfg.tie_head or cfg.use_bias or cfg.logits_scaling != 1.0
                or cfg.chips_along(cfg.state_axis) > 1):
            raise NotImplementedError(
                "LoopLM's training loss takes the head's own [D, V] matrix "
                "over one exit at a time: no tied, biased, scaled or "
                "column-sharded head"
            )
        # ``CausalLM``'s parameter tree, and the gate.
        self.encoder = TransformerEncoder(cfg)
        self.lm_head = nn.Dense(
            cfg.vocab_size, kernel_init=_dense_init("embed", "vocab"),
            use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
        )
        if cfg.passes > 1:
            self.exit_gate = nn.Dense(
                1, kernel_init=_dense_init("embed", None), use_bias=True,
                dtype=jnp.float32, param_dtype=cfg.param_dtype,
            )

    def __call__(self, input_ids, deterministic: bool = True):
        train = not deterministic
        states = self.encoder(input_ids, None, deterministic,
                              every_pass=True)
        # As in ``CausalLM``: the final norm's output is written once (the
        # encoder has done so for the states that enter a next pass).
        states = states[:-1] + (jax.lax.optimization_barrier(states[-1]),)
        # ``model.init`` runs the gates too: every parameter is made.
        if train or self.is_initializing():
            log_probs = _exit_distribution(self, states)
        if not train:
            return _logits(self, states[-1])
        probs = jnp.exp(log_probs)
        stats.sow(self, EXIT_MASS, probs.sum(axis=(1, 2)))
        stats.sow(self, ENTROPY, -(probs * log_probs).sum())
        stats.sow(self, TOKENS, jnp.float32(probs[0].size))
        head = nn.unbox(self.get_variable("params", "lm_head"))["kernel"]
        return LoopExits(
            states, log_probs, head, jnp.float32(self.entropy_weight)
        )


def _exit_distribution(lm: LoopLM, states):
    """``log p`` [T, B, S] of ``states``: gate t under the scope
    ``exit_<t>`` (its ops ``exit_<t>/exit_gate/...``); the last pass has
    no gate, it takes what is left. A function, not a method, as
    ``transformer._logits`` is."""
    logits = []
    for t, h in enumerate(states[:-1]):
        with jax.named_scope(f"exit_{t}"):
            logits.append(lm.exit_gate(h)[..., 0])
    shape = (0,) + states[0].shape[:-1]
    return exit_log_probs(
        jnp.stack(logits) if logits else jnp.zeros(shape, jnp.float32)
    )


def exit_bytes(model, out):
    """``(exits, one exit's logits, the head's gradient)`` in bytes, for
    the block checkpoint's walk (``models/step.estimated_bytes``), from a
    training apply's abstract output; None for a model that is no
    :class:`LoopLM` (its output IS its one head's logits)."""
    return heads_bytes(out) if isinstance(model, LoopLM) else None


def heads_bytes(out):
    """``(states, one state's float32 logits, the head's gradient)`` in
    bytes of a training output that carries ``states`` and the ``head``
    they share (:class:`LoopExits`; ``models/mtp.MTPHeads``)."""
    tokens, (_, vocab) = math.prod(out.states[0].shape[:-1]), out.head.shape
    return len(out.states), 4 * tokens * vocab, 4 * math.prod(out.head.shape)


def report(model) -> None:
    """Static for a compiled step: three gauges and one log line where the
    step is built. Zero for every model but a :class:`LoopLM` (a stack run
    several times under another head still reports its passes)."""
    from raydp_tpu.utils.profiling import metrics

    cfg = getattr(model, "cfg", None)
    passes = getattr(cfg, "passes", 1)
    looped = passes > 1 or isinstance(model, LoopLM)
    metrics.gauge_set("loop/passes", passes if looped else 0)
    metrics.gauge_set(
        "loop/applications", passes * cfg.n_layers if looped else 0
    )
    metrics.gauge_set(
        "loop/exits_live", 1 if isinstance(model, LoopLM) else 0
    )
    if isinstance(model, LoopLM):
        logger.info(
            "looped LM: %d layers run %d times over one set of weights "
            "(%d applications a step), the final norm closes every pass; "
            "%d exits (a gate a token and the %d-word head), the loss "
            "takes the head over one exit at a time and makes each exit's "
            "gradient where its logits are made (entropy weight %g)",
            cfg.n_layers, passes, passes * cfg.n_layers, passes,
            cfg.vocab_size, model.entropy_weight,
        )


def report_epoch(sown: dict) -> None:
    """The gauges ``loop/exit_share_<t>`` (the mean of ``p_t`` over an
    epoch's tokens, t from 0 as the scopes ``exit_<t>`` count),
    ``loop/exit_entropy`` (the mean of ``H(p)``) and ``loop/expected_pass``
    (the mean number of passes a token's exit distribution runs, ``Σ (t +
    1) p_t``, 1 to T); nothing for a model that has no exits."""
    from raydp_tpu.utils.profiling import metrics

    if EXIT_MASS not in sown:
        return
    tokens = max(float(sown[TOKENS]), 1.0)
    mass = [float(m) / tokens for m in sown[EXIT_MASS]]
    for t, share in enumerate(mass):
        metrics.gauge_set(f"loop/exit_share_{t}", share)
    metrics.gauge_set("loop/exit_entropy", float(sown[ENTROPY]) / tokens)
    metrics.gauge_set(
        "loop/expected_pass", sum((t + 1) * m for t, m in enumerate(mass))
    )
