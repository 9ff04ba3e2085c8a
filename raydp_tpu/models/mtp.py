"""A multi-token-prediction module behind a decoder stack (DeepSeek-V3,
arXiv:2412.19437, section 2.2 at depth D = 1, as the GLM-4.5 report,
arXiv:2508.06471, takes it over; ``num_nextn_predict_layers`` 1 in the
public configurations).

The main model predicts token ``s + 1`` at position ``s``. The module is
one more block BEHIND the stack that predicts token ``s + 2`` there, from
the stack's own state and the NEXT token's embedding:

    hbar_s = the last layer's output (before ``ln_final``)
    e_s    = Emb(t_{s+1})                      the main model's table, SHARED
    z_s    = W_eh [ rms_e(e_s) ; rms_h(hbar_s) ]     W_eh [2D, D]
    z'     = Block(z)                 a layer of the stack's last kind with
                                      weights of its own, positions 0..S-1
    logits1_s = rms_m(z'_s) W_head             the main model's head, SHARED

    L = L_main + lambda * L_mtp
    L_main = 1/(B(S-1)) sum_{s < S-1} CE(logits_s,  t_{s+1})
    L_mtp  = 1/(B(S-2)) sum_{s < S-2} CE(logits1_s, t_{s+2})

No gradient is stopped: ``L_mtp`` reaches the stack through ``hbar``, the
table through both of its uses and the head through both of its uses. The
last position's input ``e_{S-1}`` is the rolled-in ``t_0``: under a causal
mixer only that position sees it, and the loss masks positions S-2 and
S-1.

:class:`MTPLM` is that model on ``CausalLM``'s parameter tree (``encoder``,
``lm_head``) plus ``mtp`` (``enorm``, ``hnorm``, ``eh_proj``, ``block``,
``norm``), the way ``models/loop.LoopLM`` is that tree plus ``exit_gate``:
ONE table and ONE head in the tree, the optimizer's state and the
cotangents. In TRAINING it returns :class:`MTPHeads` — the two normed
states, the head's matrix and lambda — and the loss takes the head over one
of them at a time (``train/losses.mtp_crossentropy``): the module's logits
and their gradient are made, used and freed after the main head's, under
the scope ``mtp_head``, and no ``[B, S, V]`` array is made in the model.
DETERMINISTIC calls (``predict``, ``evaluate``) return the main logits,
``CausalLM``'s on the same ``encoder`` and ``lm_head`` bit for bit: the
module never changes what the model predicts. :meth:`MTPLM.both_logits`
returns both sets for a check. The module USED at serving (a decode round
that drafts with it and verifies in the next step) and a chain of more
than one module are not here: ROADMAP R10.

Scopes: ``mtp/embed``, ``mtp/enorm``, ``mtp/hnorm``, ``mtp/eh_proj``,
``mtp/block/{ln_attn,attn/...,ln_mlp,moe/...}`` (the sub-scopes a block of
the stack has), ``mtp/norm``.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.models import stats
from raydp_tpu.models.transformer import (
    TransformerBlock,
    TransformerConfig,
    TransformerEncoder,
    _dense_init,
    _logits,
    _norm,
    checkpointed_block,
)

logger = logging.getLogger(__name__)

# Noted about a step by the loss (``stats.note``: the heads run there,
# outside the model's apply), summed over an epoch's steps.
LOSS_MAIN = stats.declare("loss/main")
LOSS_MTP = stats.declare("loss/mtp")


@dataclasses.dataclass(frozen=True)
class MTPConfig:
    """``depth`` modules behind the stack (1: a chain of more is ROADMAP
    R10) and lambda, the weight of the second loss."""

    depth: int = 1
    loss_weight: float = 0.3


class MTPHeads(NamedTuple):
    """What an :class:`MTPLM` returns in training, for
    ``train/losses.mtp_crossentropy``: ``states`` the normed states
    [B, S, D] the shared head reads, the main model's and then the
    module's (head k predicts token ``s + 1 + k``); ``head`` the head's
    [D, V] matrix; ``loss_weight`` lambda."""
    states: Tuple[Any, ...]
    head: Any
    loss_weight: Any


class MTPModule(nn.Module):
    """``rms_m(Block(W_eh [rms_e(e); rms_h(hbar)]))``: two norms and a
    projection of its own, ONE block of the stack's last kind (mixer, FFN,
    router, selection bias and experts of its own) and a closing norm.
    ``checkpointed``: whether the block runs under the block checkpoint."""

    cfg: TransformerConfig
    checkpointed: bool = False

    @nn.compact
    def __call__(self, hbar, next_embed, deterministic: bool = True):
        cfg = self.cfg
        z = nn.Dense(
            cfg.d_model, use_bias=False,
            kernel_init=_dense_init(None, "embed"), dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="eh_proj",
        )(jnp.concatenate(
            [_norm(cfg, "enorm")(next_embed), _norm(cfg, "hnorm")(hbar)], -1
        ))
        block = checkpointed_block() if self.checkpointed else TransformerBlock
        z = block(cfg, *cfg.layers[-1], name="block")(z, deterministic)
        return _norm(cfg, "norm")(z)


class MTPLM(nn.Module):
    """A causal LM with a multi-token-prediction module behind its stack
    (the module docstring). ``cfg.released`` may name ``cfg.n_layers``:
    the module's block, which ``models/step.fit_checkpoint`` walks as the
    stack's last."""

    cfg: TransformerConfig
    mtp_config: MTPConfig = MTPConfig()

    def setup(self):
        cfg = self.cfg
        if not cfg.causal:
            raise ValueError("MTPLM requires cfg.causal=True")
        if self.mtp_config.depth != 1:
            raise NotImplementedError(
                "one module behind the stack; a chain of more is not built"
            )
        if (cfg.tie_head or cfg.use_bias or cfg.logits_scaling != 1.0
                or cfg.embedding_multiplier != 1.0 or cfg.passes != 1
                or cfg.hyper is not None or cfg.diffusion is not None
                or cfg.chips_along(cfg.state_axis) > 1):
            raise NotImplementedError(
                "MTPLM's loss takes the head's own [D, V] matrix over one "
                "state at a time, and the module reads the table as it "
                "is: no tied, biased, scaled or sharded table or head, "
                "one pass, one residual stream"
            )
        # ``CausalLM``'s parameter tree, and the module.
        self.encoder = TransformerEncoder(cfg)
        self.lm_head = nn.Dense(
            cfg.vocab_size, kernel_init=_dense_init("embed", "vocab"),
            use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
        )
        self.mtp = MTPModule(
            cfg, cfg.remat and cfg.n_layers not in cfg.released
        )

    def __call__(self, input_ids, deterministic: bool = True):
        if deterministic and not self.is_initializing():
            # ``CausalLM.__call__``, op for op.
            h = self.encoder(input_ids, None, deterministic)
            return _logits(self, jax.lax.optimization_barrier(h))
        states = _states(self, input_ids, deterministic)
        if deterministic:       # ``model.init``: every parameter is made
            return _logits(self, states[0])
        head = nn.unbox(self.get_variable("params", "lm_head"))["kernel"]
        return MTPHeads(states, head, jnp.float32(self.mtp_config.loss_weight))

    def both_logits(self, input_ids):
        """``(logits [B, S, V], logits1 [B, S, V])`` of a deterministic
        call, float32: the main model's and the module's (position s of
        the second predicts token s + 2; its last two positions carry no
        loss)."""
        main, module = _states(self, input_ids, True)
        return _logits(self, main), _logits(self, module)


def _states(lm: MTPLM, input_ids, deterministic: bool):
    """The two normed states the head reads, each written once (as
    ``CausalLM`` writes the one its head reads). A function, not a method,
    as ``transformer._logits`` is."""
    cfg = lm.cfg
    hbar, h = lm.encoder(input_ids, None, deterministic, with_prenorm=True)
    with jax.named_scope("mtp"), jax.named_scope("embed"):
        # ``nn.Embed``'s lookup of the NEXT token in the encoder's table:
        # one parameter, two uses.
        table = nn.unbox(
            lm.encoder.get_variable("params", "tok_embed")
        )["embedding"]
        next_embed = jnp.take(
            table.astype(cfg.dtype), jnp.roll(input_ids, -1, axis=-1), axis=0
        )
    module = lm.mtp(hbar, next_embed, deterministic)
    return jax.lax.optimization_barrier((h, module))


def note_losses(parts, loss_weight) -> None:
    """``L_main`` and ``L_mtp`` of a step from the weighted parts the loss
    has made (``L_main``, ``lambda * L_mtp``), for :func:`report_epoch`."""
    stats.note(LOSS_MAIN, parts[0])
    stats.note(LOSS_MTP, jnp.where(
        loss_weight > 0, parts[1] / jnp.maximum(loss_weight, 1e-30), 0.0
    ))


def exit_bytes(model, out):
    """``(heads, one head's logits, the head's gradient)`` in bytes, for
    the block checkpoint's walk (``models/step.estimated_bytes``), from a
    training apply's abstract output; None for a model that is no
    :class:`MTPLM`."""
    from raydp_tpu.models.loop import heads_bytes

    return heads_bytes(out) if isinstance(model, MTPLM) else None


def n_params(variables) -> int:
    """The trained parameters under ``mtp`` in a model's variables."""
    tree = variables.get("params", {}).get("mtp", {})
    return sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree))


def report(model, params=None) -> None:
    """Static for a compiled step: three gauges and one log line where the
    step is built. Zero for every model but an :class:`MTPLM`."""
    from raydp_tpu.utils.profiling import metrics

    is_mtp = isinstance(model, MTPLM)
    held = n_params(params) if is_mtp and isinstance(params, dict) else 0
    metrics.gauge_set("mtp/depth", model.mtp_config.depth if is_mtp else 0)
    metrics.gauge_set("mtp/params", held)
    metrics.gauge_set("mtp/loss_weight", model.mtp_config.loss_weight if is_mtp else 0)
    if is_mtp:
        cfg = model.cfg
        logger.info(
            "multi-token prediction: %d module behind the %d-layer stack "
            "(%d parameters: two norms, a [%d, %d] projection, one %s "
            "block, a closing norm) reads the stack's state before its "
            "final norm beside the next token's embedding, shares the "
            "table and the %d-word head, and adds %g x the loss two "
            "tokens ahead; the loss takes the head over one state at a "
            "time",
            model.mtp_config.depth, cfg.n_layers, held, 2 * cfg.d_model,
            cfg.d_model, ":".join(cfg.layers[-1]), cfg.vocab_size,
            model.mtp_config.loss_weight,
        )


def report_epoch(sown: dict, n_steps: int) -> None:
    """The gauges ``train/loss_main`` and ``train/loss_mtp``: the means
    over an epoch's steps of the two losses (``train_loss`` is ``main +
    lambda * mtp``); nothing for a model without the module."""
    from raydp_tpu.utils.profiling import metrics

    if LOSS_MAIN not in sown:
        return
    metrics.gauge_set("train/loss_main", float(sown[LOSS_MAIN]) / n_steps)
    metrics.gauge_set("train/loss_mtp", float(sown[LOSS_MTP]) / n_steps)
