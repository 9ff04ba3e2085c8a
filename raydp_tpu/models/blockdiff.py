"""Block diffusion's TRAINING objective on a decoder stack (Arriola et al.
2025, arXiv:2503.09573, as SDAR uses it, arXiv:2510.06303).

A sequence ``x⁰`` of S tokens lies in blocks of ``L`` consecutive ones.
One step draws a noise level a block, ``t_b ~ U[t_min, 1]``, and a mask a
token, ``m_i ~ Bernoulli(t_{b(i)})``, and replaces the masked tokens by
the mask id: ``xᵗ``. The model runs ONE pass over the 2·S ids ``[xᵗ ;
x⁰]``, the two copies sharing the positions ``0 … S-1``, under the pair
mask (``ops/attention.pair_mask``): a noised token sees its own block's
noised tokens and the CLEAN tokens of the blocks before, which is what
the sampler will have when it denoises that block over a cache of
finished ones. The head reads the noised half only, position ``i``
predicts ITS OWN token ``x⁰_i`` (no shift), and the loss is

    (1 / (B·S)) Σ_i (m_i / t_{b(i)}) · CE(ℓ_i, x⁰_i)

(``train/losses.blockdiff_crossentropy``): the masked positions only,
each weighted by its block's ``1/t`` (the linear schedule's bound).

:class:`BlockDiffusionLM` is that step on ``CausalLM``'s parameter tree
(``encoder``, ``lm_head``). In TRAINING (``deterministic=False``) it takes
the S clean ids, draws the noise on the device from the ``noise`` rng
collection (``JAXEstimator`` hands it a key that is a function of (seed,
step): :data:`BlockDiffusionLM.step_rngs`) and returns ``(logits,
weights)``. In EVALUATION it takes the pair ``[B, 2·S]`` as the caller
noised it (:func:`make_pair`), draws nothing and returns the logits of
the noised half, so that a reference can be given the same noise. One
function (:func:`_run_pair`) runs the pair in both.

The decode loop (several denoising steps a block over a cache of finished
blocks) is not here: ROADMAP R10.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from raydp_tpu.models import stats
from raydp_tpu.models.transformer import (
    TransformerConfig,
    TransformerEncoder,
    _dense_init,
    _logits,
    _TiedHead,
)

logger = logging.getLogger(__name__)

# Sown about a step (``models/stats.py``): the tokens the step masked, and
# the tokens it could have.
MASKED = stats.declare("diffusion_masked_tokens")
TOKENS = stats.declare("diffusion_tokens")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """The objective's own sizes: the block length ``L``, the id that
    stands for a masked token, and the smallest noise level (``1/t`` is a
    token's weight, so ``t_min`` bounds it)."""

    block_length: int = 4
    mask_id: int = 0
    t_min: float = 1e-3


def draw_noise(key, batch: int, seq_len: int,
               cfg: BlockDiffusionConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(masked [B, S] bool, t [B, S/L] float32)`` from ONE draw of
    ``B · S/L · (L + 1)`` uniforms: a block's first is its noise level,
    ``t = t_min + u (1 - t_min)``, the other ``L`` its tokens', ``m_i = u_i
    < t``. With an ``rbg`` key that is one ``RngBitGenerator`` op."""
    length = cfg.block_length
    if seq_len % length:
        raise ValueError(
            f"{seq_len} tokens do not divide into blocks of {length}"
        )
    u = jax.random.uniform(
        key, (batch, seq_len // length, length + 1), jnp.float32
    )
    t = cfg.t_min + u[..., 0] * (1.0 - cfg.t_min)
    masked = (u[..., 1:] < t[..., None]).reshape(batch, seq_len)
    return masked, t


def make_pair(ids, masked, mask_id: int):
    """``[xᵗ ; x⁰]`` [B, 2·S] from the clean ids and a token mask (numpy
    or jax arrays: a caller of the evaluation mode noises on the host)."""
    xp = np if isinstance(ids, np.ndarray) else jnp
    return xp.concatenate([xp.where(masked, mask_id, ids), ids], axis=1)


def pair_positions(seq_len: int):
    """[1, 2·S]: both copies sit at positions ``0 … S-1``."""
    return jnp.tile(jnp.arange(seq_len), 2)[None, :]


class BlockDiffusionLM(nn.Module):
    """The block-diffusion training step of a decoder stack (the module
    docstring). ``cfg.diffusion`` is its :class:`BlockDiffusionConfig`."""

    cfg: TransformerConfig

    # The rng collections ``JAXEstimator``'s step hands the model a key
    # for, beside dropout's.
    step_rngs = ("noise",)
    # Every token runs the layers twice, noised and clean.
    positions_per_token = 2

    def setup(self):
        cfg = self.cfg
        if cfg.diffusion is None or not cfg.causal:
            raise ValueError(
                "BlockDiffusionLM needs cfg.diffusion (a "
                "BlockDiffusionConfig) and cfg.causal"
            )
        # ``CausalLM``'s parameter tree.
        self.encoder = TransformerEncoder(cfg)
        if cfg.tie_head:
            self.lm_head = _TiedHead()
        else:
            self.lm_head = nn.Dense(
                cfg.vocab_size, kernel_init=_dense_init("embed", "vocab"),
                use_bias=cfg.use_bias, dtype=jnp.float32,
                param_dtype=cfg.param_dtype,
            )

    def __call__(self, input_ids, deterministic: bool = True):
        diff = self.cfg.diffusion
        # ``model.init`` is given the S clean ids a step is given and runs
        # the training mode on them under the ``params`` key: what a layer
        # draws from its first batch (the router's balancing bias,
        # ``models/moe.balancing_bias``) then sees the masked tokens, a
        # quarter of a step's positions, that every step will hold.
        if deterministic and not self.is_initializing():
            if input_ids.shape[-1] % (2 * diff.block_length):
                raise ValueError(
                    f"evaluation takes the pair [B, 2·S] as the caller "
                    f"noised it (blockdiff.make_pair), S a whole number of "
                    f"blocks of {diff.block_length}; got "
                    f"{input_ids.shape[-1]} ids"
                )
            return _run_pair(self, input_ids, True)
        batch, seq_len = input_ids.shape
        with jax.named_scope("noise"):
            masked, t = draw_noise(
                self.make_rng(
                    "params" if self.is_initializing() else "noise"
                ), batch, seq_len, diff,
            )
            pair = make_pair(input_ids, masked, diff.mask_id)
            weights = jnp.where(
                masked, jnp.repeat(1.0 / t, diff.block_length, axis=1), 0.0
            )
            stats.sow(self, MASKED, masked.sum().astype(jnp.float32))
            stats.sow(self, TOKENS, jnp.float32(batch * seq_len))
            # For a caller that asks (``mutable=["intermediates"]``): the
            # noise this step drew.
            self.sow("intermediates", "noise", (masked, t))
        return _run_pair(self, pair, deterministic), weights


def _run_pair(lm: BlockDiffusionLM, pair, deterministic: bool):
    """Logits [B, S, V] of the noised half of ``pair`` [B, 2·S]. The clean
    half's rows run every layer (they are the keys and values the noised
    half reads) and feed no logit. A function, not a method, as
    ``transformer._logits`` is: the ops keep the paths ``encoder/...`` and
    ``lm_head/...`` directly under the model's name."""
    seq_len = pair.shape[-1] // 2
    with jax.named_scope("noise"):
        positions = pair_positions(seq_len)
    h = lm.encoder(pair, None, deterministic, positions=positions)
    # As in ``CausalLM``: the final norm's output is written once.
    h = jax.lax.optimization_barrier(h[:, :seq_len])
    return _logits(lm, h)


def report(model, batch: int, seq_len: int) -> None:
    """Static for a compiled step: three gauges and one log line where the
    step is built (as ``models/window.report``). Zero for every model but
    a :class:`BlockDiffusionLM`."""
    from raydp_tpu.utils.profiling import metrics

    cfg = getattr(model, "cfg", None)
    diff = getattr(cfg, "diffusion", None) if isinstance(
        model, BlockDiffusionLM
    ) else None
    length = diff.block_length if diff else 0
    metrics.gauge_set("diffusion/block_length", length)
    metrics.gauge_set(
        "diffusion/blocks_per_sequence", seq_len // length if diff else 0
    )
    metrics.gauge_set(
        "diffusion/pair_positions_per_step", 2 * batch * seq_len if diff else 0
    )
    if diff:
        own = (
            "beside the kernels on [S/L, L, L] blocks, merged through the "
            "rows' logsumexp (ops/flash_attention.flash_pair_attention)"
            if cfg.attention_impl == "flash"
            else "inside the dense masked softmax"
        )
        logger.info(
            "block diffusion: blocks of %d tokens, %d a sequence, mask id "
            "%d, noise t ~ U[%g, 1] a block and a mask a token drawn on the "
            "device from the step's key, weight 1/t; a step runs %d pair "
            "positions a layer and takes the head over %d of them; the "
            "noised copy's own-block term is computed %s",
            length, seq_len // length, diff.mask_id, diff.t_min,
            2 * batch * seq_len, batch * seq_len, own,
        )


def report_epoch(sown: dict) -> None:
    """The counter ``diffusion/masked_tokens`` and the gauge
    ``diffusion/masked_share`` from an epoch's statistics (summed on the
    device, fetched with the epoch's loss); nothing for a model that
    draws no noise."""
    from raydp_tpu.utils.profiling import metrics

    if MASKED not in sown:
        return
    masked, tokens = float(sown[MASKED]), float(sown[TOKENS])
    metrics.counter_add("diffusion/masked_tokens", masked)
    metrics.gauge_set("diffusion/masked_share", masked / max(tokens, 1.0))
