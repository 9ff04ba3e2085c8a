"""Transformer model family: BERT-style encoder, GLUE classifier, causal LM.

New capability relative to the reference (SURVEY §2.4, §5.7: the
reference ships no attention code at all — models live in user examples);
this is the BERT-GLUE benchmark config of BASELINE.md and the flagship
for tensor/sequence parallelism.

TPU-first design:

* Every kernel carries flax *logical axis* metadata
  (``nn.with_logical_partitioning``); :data:`LOGICAL_RULES` maps logical
  axes onto the ``dp/tp/sp`` mesh — megatron-style TP (QKV and MLP
  up-projection column-sharded over ``tp``, output projections
  row-sharded) with XLA inserting the psums, not hand-written NCCL.
* Widths are MXU-friendly (d_model, d_ff multiples of 128); compute
  dtype defaults to bfloat16 with float32 params.
* Attention is pluggable: ``dense`` (XLA softmax attention), ``ring``
  (sequence-parallel K/V rotation over the ``sp`` ICI ring), ``ulysses``
  (head-sharded all_to_all), ``flash`` (Pallas kernel) — see
  raydp_tpu.ops.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np

from raydp_tpu.models.dropout import Dropout
from raydp_tpu.models.window import WindowConfig
from raydp_tpu.ops.attention import (
    cached_decode_attention,
    reference_attention,
    ring_attention,
    ulysses_attention,
)

# Logical axis → mesh axis. None keeps the axis replicated.
LOGICAL_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "dp"),
    ("seq", "sp"),
    ("vocab", None),
    ("embed", None),
    ("heads", "tp"),
    ("kv", None),
    ("mlp", "tp"),
    ("pooled", None),
    ("stage", "pp"),  # stacked pipeline-stage axis (models/pipelined.py)
    ("expert", "dp"),  # MoE expert axis shards over dp (models/moe.py)
)


MIXERS = frozenset(
    {"attention", "window", "mamba", "conv", "latent", "kda", "gdn", "sparse"}
)
FFNS = frozenset({"gelu", "swiglu", "moe"})
# The sublayer a layer of ONE sublayer does not have: ``"<mixer>:none"`` is
# ``x + mixer(norm(x))`` alone, ``"none:<ffn>"`` ``x + FFN(norm(x))`` alone.
NONE = "none"


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN (Peng et al. 2023, arXiv:2309.00071) as the published configs'
    ``rope_scaling`` of ``type`` yarn gives it."""

    factor: float = 1.0
    original_max_len: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    # What the rotation is scaled by where the config states it outright
    # (``attention_factor``); None = m(mscale) / m(mscale_all_dim).
    attention_factor: Optional[float] = None

    @property
    def stretch(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return yarn_mscale(self.factor, self.mscale) / yarn_mscale(
            self.factor, self.mscale_all_dim
        )


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(half: int, theta: float, yarn: YarnScaling) -> np.ndarray:
    """The ``half`` rotary frequencies under YaRN: frequency i is
    ``theta^(-i/half)`` where it turns more than ``beta_fast`` times over
    the original context, that over ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between the two in between."""
    dim = 2 * half
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)

    def index_of(turns: float) -> float:
        return dim * math.log(
            yarn.original_max_len / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(index_of(yarn.beta_fast)), 0)
    high = min(math.ceil(index_of(yarn.beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low)
        / (high - low if high > low else 0.001), 0.0, 1.0,
    )
    return (plain / yarn.factor * ramp + plain * (1.0 - ramp)).astype(
        np.float32
    )


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522          # BERT wordpiece vocab
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 512
    n_segments: int = 2
    dropout_rate: float = 0.1
    causal: bool = False
    # What describes an architecture (the defaults are BERT's block).
    norm: str = "layernorm"          # layernorm | rmsnorm
    norm_eps: float = 1e-6
    # learned (a table) | rotary | mrope (rotary by three ids a token,
    # ``mrope_section`` frequencies each) | none
    positions: str = "learned"
    rope_theta: float = 10000.0
    mrope_section: Optional[Tuple[int, ...]] = None
    # Norm of q and k before the positions: False | True or "projection"
    # (over the whole projection) | "head" (over each head's values, one
    # learned weight of head_dim).
    qk_norm: Any = False
    use_bias: bool = True            # biases of the block's projections
    ffn: str = "gelu"                # gelu | swiglu (dense) | moe (routed)
    n_experts: int = 0               # the router's outputs
    top_k: int = 0
    d_expert: int = 0
    # What the router of a routed layer does, and the share of the experts
    # held here (``models/moe.py``; the defaults are OLMoE's layer).
    router_scoring: str = "softmax"  # softmax | sigmoid
    router_bias: bool = False        # expert_bias, for the selection only
    norm_top_k: bool = False         # gates = selected scores / their sum
    routed_scaling: float = 1.0
    first_expert: int = 0            # experts [first, first + held) are here
    experts_held: Optional[int] = None      # None = all n_experts
    moe_loss_weights: Tuple[float, float] = (1e-2, 1e-3)   # balance, z
    shared_experts: int = 0          # of d_expert each, beside the routed
    # An expert's form, routed and shared alike (``models/moe.py``):
    # swiglu ``down(silu(gate x) * up x)`` | relu2 ``down(relu(up x)²)``.
    expert_form: str = "swiglu"
    # Layer i as "<mixer>" or "<mixer>:<ffn>": the mixer is "attention" |
    # "window" | "mamba" | "conv" | "latent" | "kda" | "gdn" | "sparse", the FFN kind one of
    # ``ffn``'s and ``ffn`` itself where the entry names none. A layer of
    # ONE sublayer (one norm, one residual add) names "none" for the other:
    # "<mixer>:none" or "none:<ffn>". None = attention and ``ffn``
    # everywhere.
    layer_types: Optional[Tuple[str, ...]] = None
    # A mixer's or the residual path's own sizes, a record each:
    # ``models/latent.LatentConfig`` for the "latent" mixer,
    # ``models/kda.KDAConfig`` for the "kda" mixer,
    # ``models/gdn.GDNConfig`` for the "gdn" mixer,
    # ``models/hyperconn.HyperConfig`` for more than one residual stream
    # (None = the plain ``x + F(norm(x))``);
    # ``models/blockdiff.BlockDiffusionConfig`` for a stack that runs on
    # block diffusion's training PAIRS (a noised and a clean copy of every
    # sequence side by side, the "attention" mixer under the pair mask);
    # ``models/sparse_index.SparseIndexConfig`` for the "sparse" mixer
    # (the "attention" mixer over the keys a learned index branch selects).
    latent: Any = None
    kda: Any = None
    gdn: Any = None
    hyper: Any = None
    diffusion: Any = None
    sparse: Any = None
    # :class:`WindowConfig` for the "window" mixer; the four fields after
    # it describe the "attention" mixer beside it (and a window layer's
    # head size and gate): a head of its own size (None = d_model //
    # n_heads), how many of its leading features the positions rotate
    # (None = all), YaRN for them, a per-head sigmoid gate on the output.
    window: Any = None
    head_size: Optional[int] = None
    rotary_dim: Optional[int] = None
    rope_yarn: Any = None
    head_gate: bool = False
    conv_taps: int = 3               # the "conv" mixer (models/shortconv.py)
    n_kv_heads: Optional[int] = None       # None = n_heads (no grouping)
    attention_scale: Optional[float] = None    # None = head_dim ** -0.5
    tie_head: bool = False           # CausalLM's logits from tok_embed
    embed_init_std: float = 0.02     # of tok_embed's table at init
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0      # logits are DIVIDED by it
    # The Mamba-2 mixer's sizes (models/mamba.py).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # How to run it.
    attention_impl: str = "dense"    # dense | ring | ulysses | flash
    # The blocks MAY be checkpointed (memory-bound fits): a checkpointed
    # block's backward starts from its input, and from the flash kernels'
    # output and dense lse where it calls them
    # (``ops/flash_attention.KEPT``), and runs the rest of its forward
    # again. Which blocks are is ``checkpointed`` below: all of them as a
    # configuration is written, fewer once ``models/step.fit_checkpoint``
    # has read the shapes and the device's memory and RELEASED the blocks
    # whose residuals fit (``released``: the program's to set where the
    # step is built, not a configuration's).
    remat: bool = False
    released: Tuple[int, ...] = ()
    # How many times the WHOLE stack runs over its one set of weights (a
    # looped LM): pass t's blocks are pass 0's modules, the final norm
    # closes every pass and its output enters the next, and the encoder
    # can hand back every pass's normed state (``every_pass``; ``LoopLM``
    # in ``models/loop.py`` puts an exit after each). 1 = the stack as it
    # was, op for op.
    passes: int = 1
    # Where a sublayer's norms sit, stated once for every kind of layer:
    # False  ``x + F(norm_in(x))``            the input alone (pre-norm);
    # True   ``x + norm_out(F(norm_in(x)))``  input and output;
    # "only" ``x + norm_out(F(x))``           the output alone.
    # The input norm has the name it always had (``ln_attn``, ``ln_mlp``,
    # ...), the output norm that name + ``_out``; a norm that does not sit
    # is not in the parameter tree.
    branch_norm: Any = False
    dtype: Any = jnp.bfloat16        # compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32
    mesh: Any = None                 # ring/ulysses; flash on >1 device
    # The axis of ``mesh`` that the model's STATE lies along beside the
    # batch, ``1 / n`` of it a chip: the routed layers' experts, with the
    # expert exchange around them (``models/moe.py``), and the rows of
    # ``tok_embed`` and the columns of an untied ``lm_head``
    # (:func:`embed_over`, :func:`_logits`). ``JAXEstimator`` reads it
    # here and puts the logical axis ``vocab`` on it (:func:`vocab_rules`),
    # which is what keeps the tables and their moments a share a chip at
    # rest: the layout has no second place to be stated in.
    # None = whole on every chip, no collective: the program as it was.
    state_axis: Optional[str] = None

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def layers(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, FFN kind) of every layer, ``n_layers`` long; ``NONE``
        for the sublayer a layer of one sublayer does not have."""
        entries = self.layer_types or ("attention",) * self.n_layers
        layers = tuple(
            tuple((entry.split(":", 1) + [self.ffn])[:2]) for entry in entries
        )
        if (len(layers) != self.n_layers
                or {m for m, _ in layers} - MIXERS - {NONE}
                or {f for _, f in layers} - FFNS - {NONE}
                or (NONE, NONE) in layers):
            raise ValueError(
                f"layer_types {entries!r} does not name the mixers (and FFN "
                f"kinds) of {self.n_layers} layers"
            )
        return layers

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of every layer (``NONE`` where a layer is an FFN
        alone)."""
        return tuple(mixer for mixer, _ in self.layers)

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        """The FFN kind of every layer (``NONE`` where a layer is a mixer
        alone)."""
        return tuple(ffn for _, ffn in self.layers)

    @property
    def checkpointed(self) -> Tuple[bool, ...]:
        """Whether layer i's block is checkpointed in a gradient's
        program: every block of a ``remat`` stack but the ``released``."""
        return tuple(
            self.remat and i not in self.released
            for i in range(self.n_layers)
        )

    @property
    def serves_from_kv_cache(self) -> bool:
        """Whether every layer's state is the per-slot K/V cache that
        ``CausalLM.prefill``/``decode_step`` keep."""
        return set(self.kinds) == {"attention"} and (
            self.kv_heads == self.n_heads
        )

    def moe_config(self):
        from raydp_tpu.models.moe import MoEConfig

        return MoEConfig(
            d_model=self.d_model, d_ff=self.d_expert,
            n_experts=self.n_experts, top_k=self.top_k,
            aux_loss_weight=self.moe_loss_weights[0],
            z_loss_weight=self.moe_loss_weights[1],
            scoring=self.router_scoring, selection_bias=self.router_bias,
            normalize_gates=self.norm_top_k, gate_scale=self.routed_scaling,
            first_expert=self.first_expert, held_experts=self.experts_held,
            shared_experts=self.shared_experts,
            expert_form=self.expert_form,
            expert_axis=self.state_axis,
            mesh=self.mesh if self.state_axis is not None else None,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def chips_along(self, axis: Optional[str]) -> int:
        """Chips of ``mesh`` along ``axis``; 1 without either."""
        if axis is None or self.mesh is None:
            return 1
        return int(self.mesh.shape[axis])


def _dense_init(*logical_axes: str):
    return nn.with_logical_partitioning(
        nn.initializers.xavier_uniform(), logical_axes
    )


def _embed_init(*logical_axes: str, std: float = 0.02):
    return nn.with_logical_partitioning(
        nn.initializers.normal(stddev=std), logical_axes
    )


def _norm(cfg: TransformerConfig, name: str, dtype=None) -> nn.Module:
    """The configuration's norm over the feature axis; its output in
    ``dtype`` (the compute dtype unless given)."""
    cls = {"layernorm": nn.LayerNorm, "rmsnorm": nn.RMSNorm}[cfg.norm]
    return cls(
        epsilon=cfg.norm_eps, dtype=dtype or cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones, ("embed",)
        ),
    )


def rotary_angles(positions, half: int, theta: float,
                  yarn: Optional[YarnScaling] = None,
                  sections: Optional[Tuple[int, ...]] = None):
    """Ids into angles, [B or 1, S, half] float32: frequency i is
    ``theta^(-i/half)`` (YaRN's blend with ``yarn``,
    :func:`yarn_inv_freq`) and turns by a token's id. ``positions`` is [B
    or 1, S], one id a token; with ``sections`` (M-RoPE, ``mrope_section``
    of the published configs: contiguous bands that sum to ``half``) it is
    [len(sections), B or 1, S] and band b's frequencies turn by
    ``positions[b]``. Equal ids make the bands one rotation."""
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(half, theta, yarn))
    if sections is None:
        return positions.astype(jnp.float32)[..., None] * inv_freq
    if sum(sections) != half or positions.shape[0] != len(sections):
        raise ValueError(
            f"sections {sections!r} over {half} frequencies and ids of "
            f"shape {positions.shape}"
        )
    band = np.repeat(np.arange(len(sections)), sections)
    return jnp.moveaxis(
        positions.astype(jnp.float32)[band], 0, -1
    ) * inv_freq


def rotary(x, positions, theta: float, yarn: Optional[YarnScaling] = None,
           dims: Optional[int] = None,
           sections: Optional[Tuple[int, ...]] = None):
    """Rotary position embedding (Su et al. 2021) in the half-split form
    of the published OLMoE/NeoX code: feature i pairs with i + D/2.
    ``x`` [B, S, H, D], ``positions`` [B or 1, S]; float32 inside. With
    ``yarn`` the frequencies are YaRN's blend (:func:`yarn_inv_freq`) and
    the rotation is scaled by ``yarn.stretch``. ``dims`` rotates the
    first ``dims`` features of a head (pairs i, i + dims/2) and passes
    the others through. With ``sections`` the ids are three a token
    (:func:`rotary_angles`, which every form goes through)."""
    if dims is not None and dims != x.shape[-1]:
        return jnp.concatenate([
            rotary(x[..., :dims], positions, theta, yarn, None, sections),
            x[..., dims:],
        ], axis=-1)
    half = x.shape[-1] // 2
    angle = rotary_angles(positions, half, theta, yarn, sections)
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    if yarn is not None and yarn.stretch != 1.0:
        cos, sin = cos * yarn.stretch, sin * yarn.stretch
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32
    )
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def embed_over(table, ids, mesh, axis: str):
    """``table[ids]`` for a ``table`` ``[V, D]`` whose rows lie over the
    ``n`` chips of ``axis``, ``V / n`` a chip, and ``ids`` ``[B, ...]``
    whose rows lie over the same chips: inside a ``shard_map`` a chip
    gathers the ids (4 bytes a token), looks its own rows up for all of
    them — zeros where a token's row is another chip's — and the parts
    are reduce-scattered to the chips that own the tokens (scope
    ``exchange``). The table is never whole on a chip; its gradient is a
    scatter-add into the chip's own rows."""
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[axis])
    if table.shape[0] % n or ids.shape[0] % n:
        raise ValueError(
            f"{table.shape[0]} rows and {ids.shape[0]} sequences over the "
            f"{n} chips of axis {axis!r}"
        )
    held = table.shape[0] // n

    def chip(table, ids):
        with jax.named_scope("exchange"):
            ids = jax.lax.all_gather(ids, axis, axis=0, tiled=True)
        local = ids - jax.lax.axis_index(axis) * held
        mine = (local >= 0) & (local < held)
        rows = jnp.where(
            mine[..., None], table[jnp.where(mine, local, 0)], 0
        )
        with jax.named_scope("exchange"):
            return jax.lax.psum_scatter(
                rows, axis, scatter_dimension=0, tiled=True
            )

    return jax.shard_map(
        chip, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis),
        check_vma=False,
    )(table, ids)


class TokenEmbed(nn.Embed):
    """``nn.Embed`` (same parameter, same scope) whose table may lie over
    a mesh axis: with ``mesh`` the lookup is :func:`embed_over`. Nothing
    reads what ``init`` computes, so the plain lookup stands there (its
    one sample row does not divide over the chips)."""

    mesh: Any = None
    axis: Optional[str] = None

    def __call__(self, inputs):
        if self.mesh is None or self.is_initializing():
            return super().__call__(inputs)
        return embed_over(
            self.embedding.astype(self.dtype), inputs, self.mesh, self.axis
        )


class MultiHeadAttention(nn.Module):
    """Softmax attention over all earlier positions (or all positions
    without ``cfg.causal``) from the configuration's own sizes; with
    ``window`` (a :class:`WindowConfig`, the "window" mixer) over the last
    ``window.window`` of them, with that record's head count and rotary
    positions. ``positions`` ([B or 1, S]) are what rotary positions
    rotate by where they are not ``0 … S-1``. With ``cfg.diffusion`` the S
    positions are a noised and a clean copy of a sequence side by side and
    the mask is the pair's (``ops/attention.pair_mask``), in blocks of
    that record's length. With ``sparse`` (a ``SparseIndexConfig``, the
    "sparse" mixer) attention runs over the keys the layer's index branch
    selects (``models/sparse_index.py``). Under ``positions="mrope"``
    ``positions`` may be [3, B or 1, S]; a call without them is a text's,
    whose three ids are equal."""

    cfg: TransformerConfig
    window: Any = None
    sparse: Any = None

    @nn.compact
    def __call__(
        self,
        x,
        deterministic: bool = True,
        *,
        cache_mode: Optional[str] = None,
        cache_positions=None,
        kv_len: Optional[int] = None,
        positions=None,
    ):
        cfg, win, sparse = self.cfg, self.window, self.sparse
        scale = cfg.attention_scale
        if sparse is not None and (
                win is not None or cache_mode is not None or not cfg.causal
                or cfg.diffusion is not None):
            raise NotImplementedError(
                "a sparse layer trains and evaluates causally over whole "
                "sequences; an index-key cache and the selection inside "
                "decode attention are ROADMAP R13"
            )
        pair = cfg.diffusion.block_length if cfg.diffusion else None
        if pair is not None and (
                win is not None or cache_mode is not None or not cfg.causal
                or cfg.attention_impl not in ("dense", "flash")):
            raise NotImplementedError(
                "the pair mask runs in the causal 'attention' mixer, dense "
                "or flash, in training and evaluation; a decode loop that "
                "finishes a block a step is ROADMAP R10"
            )
        n_heads, theta, rotary_dim, yarn, span = (
            cfg.n_heads, cfg.rope_theta, cfg.rotary_dim, cfg.rope_yarn, None
        ) if win is None else (
            win.n_heads, win.rope_theta, win.rotary_dim, None, win.window
        )
        if span is not None and (not cfg.causal or cache_mode is not None):
            raise NotImplementedError(
                "a window layer trains causally; no decode cache holds a "
                "window's positions (ROADMAP R2)"
            )
        project = functools.partial(
            nn.DenseGeneral, axis=-1, use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        if cfg.kv_heads == n_heads:
            # One fused projection where the head counts are equal (the
            # layout every checkpoint so far was written with).
            qkv = project(
                features=(3, n_heads, cfg.head_dim),
                kernel_init=_dense_init("embed", "qkv", "heads", "kv"),
                name="qkv",
            )(x)
            q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        else:
            # Grouped-query heads: each key-value head serves
            # n_heads / n_kv_heads query heads.
            if cache_mode is not None:
                raise NotImplementedError(
                    "the decode cache holds one key-value head a query head"
                )
            q = project(
                features=(n_heads, cfg.head_dim),
                kernel_init=_dense_init("embed", "heads", "kv"), name="q",
            )(x)
            kv = project(
                features=(2, cfg.kv_heads, cfg.head_dim),
                kernel_init=_dense_init("embed", "qkv", "heads", "kv"),
                name="kv",
            )(x)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        if cfg.qk_norm == "head":
            # Over each head's values, one weight of head_dim for all heads.
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        elif cfg.qk_norm in (True, "projection"):
            # Over the whole projection, before the split into heads.
            q = _norm(cfg, "q_norm")(
                q.reshape(q.shape[:-2] + (-1,))
            ).reshape(q.shape)
            k = _norm(cfg, "k_norm")(
                k.reshape(k.shape[:-2] + (-1,))
            ).reshape(k.shape)
        elif cfg.qk_norm:
            raise ValueError(f"unknown qk_norm {cfg.qk_norm!r}")
        pos = None
        if cfg.positions in ("rotary", "mrope"):
            if cache_mode == "step":
                pos = cache_positions[:, None]
            elif positions is not None:
                pos = positions
            else:
                pos = jnp.arange(x.shape[-2])[None, :]
            # Three ids a token only where they were given: a text's are
            # equal, and equal ids make the bands one rotation.
            bands = cfg.mrope_section if pos.ndim == 3 else None
            if (cfg.positions == "mrope") != (cfg.mrope_section is not None):
                raise ValueError("positions='mrope' takes mrope_section")
            q = rotary(q, pos, theta, yarn, rotary_dim, bands)
            k = rotary(k, pos, theta, yarn, rotary_dim, bands)

        if cache_mode is not None:
            # Per-slot KV cache rows (serve-plane autoregressive decode).
            # Row b belongs to whichever request currently owns slot b;
            # the pool in serve/decode.py recycles rows without zeroing —
            # masking by cache length in cached_decode_attention is what
            # keeps stale pages invisible.
            b = x.shape[0]
            cache_shape = (b, cfg.max_len, n_heads, cfg.head_dim)
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(cache_shape, cfg.dtype),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(cache_shape, cfg.dtype),
            )
            if cache_mode == "prefill":
                # Whole (padded) prompt lands in rows [0, S); positions
                # past the true prompt length hold junk until decode
                # overwrites them one step at a time — always before the
                # length mask admits them.
                ck.value = jax.lax.dynamic_update_slice_in_dim(
                    ck.value, k.astype(cfg.dtype), 0, axis=1
                )
                cv.value = jax.lax.dynamic_update_slice_in_dim(
                    cv.value, v.astype(cfg.dtype), 0, axis=1
                )
                out = reference_attention(q, k, v, causal=True, scale=scale)
            elif cache_mode == "step":
                # One token per slot: scatter K/V at each slot's current
                # cache length, then attend over a static kv_len-bucket
                # slice (static slice = one XLA program per bucket, and
                # no gather of max_len when the batch is young).
                rows = jnp.arange(b)
                ck.value = ck.value.at[rows, cache_positions].set(
                    k[:, 0].astype(cfg.dtype)
                )
                cv.value = cv.value.at[rows, cache_positions].set(
                    v[:, 0].astype(cfg.dtype)
                )
                out = cached_decode_attention(
                    q,
                    ck.value[:, :kv_len],
                    cv.value[:, :kv_len],
                    cache_positions + 1,
                    scale=scale,
                )
            else:
                raise ValueError(f"unknown cache_mode {cache_mode!r}")
        elif sparse is not None:
            from raydp_tpu.models import sparse_index

            if pos is None:
                raise ValueError("a sparse layer's index head is rotated")
            # The index head turns by a token's temporal id alone.
            q_idx, k_idx, w = sparse_index.IndexBranch(
                cfg, sparse, name="index"
            )(x, pos[0] if pos.ndim == 3 else pos)
            out = sparse_index.attend(
                self, sparse, q, k, v, q_idx, k_idx, w, scale
            )
        elif pair is not None:
            # Everything between the rotated q, k, v and the attention's
            # output under the pair mask carries the scope ``attn/pair``
            # or, the kernels and the layout moves around them,
            # ``attn/jit(flash_attention)``.
            if cfg.attention_impl == "dense":
                with jax.named_scope("pair"):
                    out = reference_attention(
                        q, k, v, causal=True, scale=scale, pair=pair
                    )
            elif cfg.mesh is not None:
                raise NotImplementedError(
                    "the pair mask through the flash kernels on a mesh"
                )
            else:
                from raydp_tpu.ops.flash_attention import (
                    flash_pair_attention,
                )

                out = flash_pair_attention(q, k, v, pair, scale=scale)
        elif cfg.attention_impl == "dense":
            out = reference_attention(
                q, k, v, causal=cfg.causal, scale=scale, window=span
            )
        elif cfg.attention_impl in ("ring", "ulysses"):
            if span is not None:
                raise NotImplementedError(
                    f"a window layer through {cfg.attention_impl!r}"
                )
            # Both move K and V a query head at a time: grouped heads are
            # repeated first.
            group = n_heads // cfg.kv_heads
            if group > 1:
                k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
            attend = (ring_attention if cfg.attention_impl == "ring"
                      else ulysses_attention)
            out = attend(
                q, k, v, mesh=cfg.mesh, causal=cfg.causal, scale=scale
            )
        elif cfg.attention_impl == "flash":
            from raydp_tpu.ops.flash_attention import (
                flash_attention,
                sharded_flash_attention,
            )

            # Mosaic-compiled, TPU only: off the chip this raises
            # instead of quietly running the Pallas interpreter. On
            # more than one device the config has to carry the mesh.
            if cfg.mesh is not None:
                out = sharded_flash_attention(
                    q, k, v, mesh=cfg.mesh, causal=cfg.causal, scale=scale,
                    window=span, scope=self.name,
                )
            else:
                out = flash_attention(
                    q, k, v, causal=cfg.causal, scale=scale, window=span
                )
        else:
            raise ValueError(
                f"unknown attention_impl {cfg.attention_impl!r}"
            )

        if cfg.head_gate:
            # One sigmoid gate a head and token, from the layer's input.
            gate = nn.sigmoid(project(
                features=n_heads, kernel_init=_dense_init("embed", "heads"),
                name="gate",
            )(x).astype(jnp.float32))
            out = out * gate[..., None].astype(out.dtype)
        out = nn.DenseGeneral(
            features=cfg.d_model,
            axis=(-2, -1),
            kernel_init=_dense_init("heads", "kv", "embed"),
            use_bias=cfg.use_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="out",
        )(out)
        if cfg.dropout_rate > 0:
            out = Dropout(cfg.dropout_rate)(out, deterministic)
        return out


class TransformerBlock(nn.Module):
    """Pre-norm block (trains stably in bf16 without warmup tricks). Norm,
    positions, QK-norm and biases come from the configuration, the mixer
    and the kind of FFN from the stack's per-layer pattern: BERT's encoder
    block, a routed decoder block, both layers of a hybrid state-space
    stack, the dense and routed layers of a short-convolution hybrid and
    those of a latent-attention stack are the same code. With
    ``cfg.hyper`` the block carries ``[B, n, S, D]`` streams and each of
    its two sublayers reads and writes them through its own mappings
    (``models/hyperconn.py``; scopes ``hc_attn`` and ``hc_ffn``). A layer
    of one sublayer (``mixer`` or ``ffn`` is ``NONE``) is ``x + F(norm(x))``
    with the one norm, the one module and the one add of the sublayer it
    has, under the names they have in a block of two."""

    cfg: TransformerConfig
    mixer: str = "attention"         # one of ``MIXERS``, or ``NONE``
    ffn: Optional[str] = None        # None = cfg.ffn; ``NONE`` = no FFN

    @nn.compact
    def __call__(
        self,
        x,
        deterministic: bool = True,
        *,
        cache_mode: Optional[str] = None,
        cache_positions=None,
        kv_len: Optional[int] = None,
        positions=None,
    ):
        cfg = self.cfg
        if cfg.diffusion is not None and self.mixer not in (
                "attention", NONE):
            raise NotImplementedError(
                f"a {self.mixer!r} layer over a pair of copies: only the "
                "'attention' mixer knows the pair mask"
            )

        def scaled(branch):
            if cfg.residual_multiplier == 1.0:
                return branch
            return branch * cfg.residual_multiplier

        # The mixer's input norm, by the name it has always had.
        mixer_norm = {"mamba": "ln_mamba", "conv": "ln_conv",
                      "kda": "ln_kda", "gdn": "ln_gdn"}.get(
                          self.mixer, "ln_attn")
        if cfg.branch_norm not in (False, True, "only"):
            raise ValueError(f"unknown branch_norm {cfg.branch_norm!r}")

        def entering(name, dtype=None):
            """A sublayer's norm on its INPUT; nothing where the stack
            norms outputs only."""
            if cfg.branch_norm == "only":
                return lambda h: h
            return _norm(cfg, name, dtype)

        def mix(h):
            """The layer's mixer on its own norm of ``h``."""
            if self.mixer in ("attention", "window", "sparse"):
                # A window layer's module has a name of its own, so that a
                # trace tells the two kinds of layer apart.
                attend = MultiHeadAttention(
                    cfg, cfg.window, name="attn_window"
                ) if self.mixer == "window" else MultiHeadAttention(
                    cfg, sparse=cfg.sparse if self.mixer == "sparse" else None,
                    name="attn",
                )
                return attend(
                    entering(mixer_norm)(h),
                    deterministic,
                    cache_mode=cache_mode,
                    cache_positions=cache_positions,
                    kv_len=kv_len,
                    positions=positions,
                )
            if cache_mode is not None:
                raise NotImplementedError({
                    "mamba": "no decode cache for a state-space layer's state",
                    "conv": "no decode cache for a short convolution's last "
                            "tokens",
                    "latent": "no decode cache for the key-value latent "
                              "(ROADMAP R3)",
                    "kda": "no decode cache for a delta-rule layer's state "
                           "(ROADMAP R11)",
                    "gdn": "no decode cache for a delta-rule layer's state "
                           "(ROADMAP R11)",
                }[self.mixer])
            if self.mixer == "mamba":
                from raydp_tpu.models.mamba import Mamba2Mixer

                return Mamba2Mixer(cfg, name="mamba")(
                    entering(mixer_norm)(h)
                )
            if self.mixer == "conv":
                from raydp_tpu.models.shortconv import ShortConv

                return ShortConv(cfg, name="conv")(entering(mixer_norm)(h))
            if self.mixer == "kda":
                from raydp_tpu.models.kda import KimiDeltaMixer

                return KimiDeltaMixer(cfg, name="kda")(
                    entering(mixer_norm)(h)
                )
            if self.mixer == "gdn":
                from raydp_tpu.models.gdn import GatedDeltaMixer

                return GatedDeltaMixer(cfg, name="gdn")(
                    entering(mixer_norm)(h)
                )
            from raydp_tpu.models.latent import LatentAttention

            return LatentAttention(cfg, name="attn")(entering(mixer_norm)(h))

        def feed(h):
            """The layer's FFN on its own norm of ``h``."""
            dense = functools.partial(
                nn.Dense, use_bias=cfg.use_bias, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
            )
            ffn = self.ffn or cfg.ffn
            if ffn == "moe":
                from raydp_tpu.models.moe import MoELayer

                # The norm's output stays float32 for the router: one bf16
                # rounding less between near-equal experts. The experts get
                # it in the compute dtype.
                y = entering("ln_mlp", jnp.float32)(h)
                y = MoELayer(cfg.moe_config(), name="moe")(y)
            elif ffn == "gelu":
                y = entering("ln_mlp")(h)
                y = dense(
                    cfg.d_ff, kernel_init=_dense_init("embed", "mlp"),
                    name="mlp_up",
                )(y)
                y = nn.gelu(y)
                y = dense(
                    cfg.d_model, kernel_init=_dense_init("mlp", "embed"),
                    name="mlp_down",
                )(y)
            elif ffn == "swiglu":
                # Dense gated MLP, one fused input projection: [gate, up].
                y = entering("ln_mlp")(h)
                gate, up = jnp.split(dense(
                    2 * cfg.d_ff, kernel_init=_dense_init("embed", "mlp"),
                    name="mlp_in",
                )(y), 2, axis=-1)
                y = dense(
                    cfg.d_model, kernel_init=_dense_init("mlp", "embed"),
                    name="mlp_out",
                )(nn.silu(gate) * up)
            else:
                raise ValueError(f"unknown ffn {ffn!r}")
            if cfg.dropout_rate > 0:
                y = Dropout(cfg.dropout_rate)(y, deterministic)
            return y

        def normed(sublayer, name):
            """``sublayer`` with the configuration's norm on its output
            (``cfg.branch_norm`` true or "only"), under the input norm's
            name + ``_out``."""
            if not cfg.branch_norm:
                return sublayer
            return lambda h: _norm(cfg, name)(sublayer(h))

        # The sublayers the layer has, in order: two unless it names one.
        sublayers = [
            (name, normed(sublayer, norm + "_out"))
            for (name, sublayer, norm), kind in (
                (("hc_attn", mix, mixer_norm), self.mixer),
                (("hc_ffn", feed, "ln_mlp"), self.ffn or cfg.ffn),
            ) if kind != NONE
        ]
        if cfg.hyper is None:
            for _, sublayer in sublayers:
                x = x + scaled(sublayer(x))
            return nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        from raydp_tpu.models import hyperconn

        for name, sublayer in sublayers:
            maps = hyperconn.HyperMaps(
                cfg.hyper, cfg.norm_eps, cfg.param_dtype, name=name
            )(x)
            with jax.named_scope(name), jax.named_scope("pre"):
                h = hyperconn.read(x, maps)
            y = scaled(sublayer(h))
            with jax.named_scope(name), jax.named_scope("post"):
                x = hyperconn.write(x, y, maps)
        return nn.with_logical_constraint(
            x, ("batch", None, "seq", "embed")
        )


def checkpointed_block():
    """``TransformerBlock`` under the block checkpoint. What such a block
    keeps besides its input are the residuals only a kernel's forward can
    make, named where they are made: the flash forward kernel's output
    ([B, H, S, D_v], the size of one q projection's result) and lse.
    Recomputing them is a second run of the whole kernel, the dearest item
    of a block per byte kept; lse is kept as a dense [B, H, S] array
    because the kernel's [B, H, S, 1] columns are 128 times their bytes in
    HBM's tiling. A block that calls no flash kernel (dense, ring,
    ulysses) holds no such name and keeps its input alone. A delta-rule
    layer's scan names its output and the few states its segments were
    entered with for the same reason (``ops/kda.KEPT``, ``ops/gdn.KEPT``), the attention
    over a learned selection its kernels' (``ops/sparse_attention.KEPT``)."""
    return nn.remat(
        TransformerBlock, static_argnums=(2,),
        policy=jax.checkpoint_policies.save_only_these_names(*kept_names()),
    )


def kept_names() -> Tuple[str, ...]:
    """The names a checkpointed block keeps besides its input."""
    from raydp_tpu.ops.flash_attention import KEPT
    from raydp_tpu.ops.gdn import KEPT as GDN_KEPT
    from raydp_tpu.ops.kda import KEPT as KDA_KEPT
    from raydp_tpu.ops.sparse_attention import KEPT as SPARSE_KEPT

    return (*KEPT, *KDA_KEPT, *SPARSE_KEPT, *GDN_KEPT)


class TransformerEncoder(nn.Module):
    """Token + position (+ optional segment) embeddings, N blocks (layer
    i's mixer and FFN kind from ``cfg.layer_types``), final LN.

    Input: int32 token ids [B, S] (+ optional segment ids; ``positions``
    [B or 1, S] where rotary positions are not ``0 … S-1``). Output:
    [B, S, d_model] hidden states.

    With ``cfg.passes`` = T > 1 the blocks run T times, the SAME modules
    (one set of parameters, whose gradient is the sum over the
    applications), ``ln_final`` closes every pass and its output enters
    the next; pass t's ops lie under the scope ``pass_<t>``. The output is
    the last pass's normed state, or with ``every_pass`` the tuple of all
    T. Every application of a block has the same shapes. With
    ``with_prenorm`` (one pass) the output is the pair ``(the last block's
    output before ln_final, the normed state)``.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        segment_ids=None,
        deterministic: bool = True,
        *,
        cache_mode: Optional[str] = None,
        cache_positions=None,
        kv_len: Optional[int] = None,
        positions=None,
        every_pass: bool = False,
        with_prenorm: bool = False,
    ):
        cfg = self.cfg
        if with_prenorm and (cfg.passes > 1 or every_pass):
            raise NotImplementedError(
                "the state before the final norm is the ONE pass's"
            )
        if cfg.passes > 1 and (
                cache_mode is not None or cfg.hyper is not None):
            raise NotImplementedError(
                "a stack run several times keeps no decode cache (one a "
                "pass: ROADMAP Reach) and carries one residual stream"
            )
        if positions is not None and cfg.positions not in ("rotary", "mrope"):
            raise NotImplementedError(
                f"given positions with cfg.positions={cfg.positions!r}"
            )
        x = TokenEmbed(
            cfg.vocab_size, cfg.d_model,
            embedding_init=_embed_init(
                "vocab", "embed", std=cfg.embed_init_std
            ),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="tok_embed",
            mesh=cfg.mesh if cfg.chips_along(cfg.state_axis) > 1 else None,
            axis=cfg.state_axis,
        )(input_ids)
        if cfg.embedding_multiplier != 1.0:
            # Under the embedding's scope, for the same reason as the
            # logit scaling under ``lm_head``.
            with jax.named_scope("tok_embed"):
                x = x * cfg.embedding_multiplier
        if cfg.positions == "learned":
            if cache_mode == "step":
                # Each slot's token sits at its own absolute position —
                # the slot's current cache length, not a shared arange.
                pos = jnp.minimum(cache_positions, cfg.max_len - 1)[:, None]
            else:
                pos = jnp.arange(input_ids.shape[-1])[None, :]
            x = x + nn.Embed(
                cfg.max_len, cfg.d_model,
                embedding_init=_embed_init("seq", "embed"),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="pos_embed",
            )(pos)
        if segment_ids is not None:
            x = x + nn.Embed(
                cfg.n_segments, cfg.d_model,
                embedding_init=_embed_init(None, "embed"),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="seg_embed",
            )(segment_ids)
        if cfg.dropout_rate > 0:
            x = Dropout(cfg.dropout_rate)(x, deterministic)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        # A checkpointed block's activations are recomputed in the backward
        # instead of stored: the FLOPs-for-HBM trade that fits a bigger
        # batch or sequence where training is memory-bound, and work the
        # result does not need where the memory is there. So the unit is
        # the block: ``cfg.checkpointed`` says which blocks of a ``remat``
        # stack are (all of them until ``models/step.fit_checkpoint`` has
        # released those whose residuals fit the device), and a released
        # block is the plain ``TransformerBlock`` under the same name, with
        # the same parameters.
        if cache_mode is not None and cfg.remat:
            raise ValueError("decode cache is incompatible with remat")
        checkpointed = checkpointed_block() if any(cfg.checkpointed) else None
        if cfg.hyper is not None:
            from raydp_tpu.models import hyperconn

            with jax.named_scope("hc_expand"):
                x = hyperconn.expand(x, cfg.hyper.streams)
        # Given positions go to the blocks that rotate by them; a call
        # without them is the call it was.
        given = {} if positions is None else {"positions": positions}
        # The modules are made once and called ``passes`` times: a Python
        # loop, so that a trace tells the passes apart and the compiler
        # orders 24 applications as it orders 6 (PERF.md section 6, PR 61).
        # One pass adds no scope: the program it was.
        blocks = [
            (checkpointed if cfg.checkpointed[i] else TransformerBlock)(
                cfg, mixer, ffn, name=f"block_{i}"
            ) for i, (mixer, ffn) in enumerate(cfg.layers)
        ]
        ln_final = _norm(cfg, "ln_final")
        pass_scope = (lambda t: jax.named_scope(f"pass_{t}")) if (
            cfg.passes > 1) else (lambda t: contextlib.nullcontext())
        states = []
        for t in range(cfg.passes):
            with pass_scope(t):
                for block in blocks:
                    x = block(
                        x,
                        deterministic,
                        cache_mode=cache_mode,
                        cache_positions=cache_positions,
                        kv_len=kv_len,
                        **given,
                    )
                if cfg.hyper is not None:
                    with jax.named_scope("hc_reduce"):
                        x = hyperconn.reduce(x)
                prenorm = x
                x = ln_final(x)
            if t + 1 < cfg.passes:
                # Written once: the next pass and an exit both read it
                # (as ``CausalLM`` writes the state its head reads).
                x = jax.lax.optimization_barrier(x)
            states.append(x)
        if with_prenorm:
            # What a module BEHIND the stack reads (``models/mtp.py``
            # norms it itself); a call without it is the call it was.
            return prenorm, x
        return tuple(states) if every_pass else x


class SequenceClassifier(nn.Module):
    """Encoder + first-token pooler + classification head — the BERT-GLUE
    fine-tune model (BASELINE.md config matrix, last row)."""

    cfg: TransformerConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, segment_ids=None, deterministic: bool = True):
        h = TransformerEncoder(self.cfg, name="encoder")(
            input_ids, segment_ids, deterministic
        )
        pooled = nn.tanh(
            nn.Dense(
                self.cfg.d_model,
                kernel_init=_dense_init("embed", "pooled"),
                dtype=self.cfg.dtype,
                param_dtype=self.cfg.param_dtype,
                name="pooler",
            )(h[:, 0])
        )
        # Logits in float32: bf16 is fine through the trunk but softmax/
        # cross-entropy want full precision.
        return nn.Dense(
            self.num_classes,
            kernel_init=_dense_init("embed", None),
            dtype=jnp.float32,
            param_dtype=self.cfg.param_dtype,
            name="head",
        )(pooled)


class _TiedHead(nn.Module):
    """Logits from the embedding table, in float32; no parameter of its
    own, a module so that its ops carry the scope ``lm_head``."""

    @nn.compact
    def __call__(self, h, table):
        return jnp.einsum(
            "...d,vd->...v", h.astype(jnp.float32),
            table.astype(jnp.float32),
        )


def _logits(lm: "CausalLM", h):
    """The head's logits. A function, not a method: flax would put a
    method's name into the scope of every op under it, and ``lm_head``'s
    ops keep the path they had."""
    cfg = lm.cfg
    if cfg.tie_head:
        table = nn.unbox(
            lm.encoder.get_variable("params", "tok_embed")
        )["embedding"]
        logits = lm.lm_head(h, table)
    elif cfg.chips_along(cfg.state_axis) > 1 and not lm.is_initializing():
        # The head's columns lie over ``state_axis`` as the batch does: a
        # chip gathers the axis's tokens ([B, S, D], under ``lm_head/
        # gather``) and computes its own columns of every token's logits,
        # which stay where they were computed. A cross-entropy that
        # reduces over the vocabulary by max and sum
        # (``train/losses.lm_crossentropy``) then needs two numbers a
        # token from the other chips, the head's weight gradient none,
        # and the tokens' gradient is reduce-scattered to their chips.
        # Gathering the head instead would move ``D·V`` floats a step
        # three times (forward, backward, the gradient's reduce-scatter)
        # and hold a whole table and a whole gradient beside the logits.
        from jax.sharding import NamedSharding, PartitionSpec as P

        with jax.named_scope("lm_head"), jax.named_scope("gather"):
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(cfg.mesh, P())
            )
        logits = jax.lax.with_sharding_constraint(
            lm.lm_head(h),
            NamedSharding(cfg.mesh, P(None, None, cfg.state_axis)),
        )
    else:
        logits = lm.lm_head(h)
    if cfg.logits_scaling != 1.0:
        # Under the head's scope: a pass over the logits belongs to the
        # head's share of a device trace, not to no part at all.
        with jax.named_scope("lm_head"):
            logits = logits / cfg.logits_scaling
    return logits


def _require_kv_cache(cfg: TransformerConfig) -> None:
    if not cfg.serves_from_kv_cache:
        raise NotImplementedError(
            "prefill/decode_step keep one K/V row a query head and layer; "
            "a stack with state-space or convolution layers or grouped "
            "key-value heads "
            "needs a cache of its own (ROADMAP R4)"
        )


class CausalLM(nn.Module):
    """Decoder-only LM: the long-context flagship — pair with
    ``attention_impl='ring'`` to scale sequence length over the sp axis.
    The output head is a matrix of its own unless ``cfg.tie_head``: then
    the logits are the final hidden states times ``tok_embed``'s table, in
    float32 (the scope is ``lm_head`` either way).

    Besides the teacher-forced ``__call__``, exposes the serve-plane
    decode pair: :meth:`prefill` runs the prompt once, writing per-slot
    KV-cache rows (flax ``"cache"`` collection) and returning the first
    greedy token's logits; :meth:`decode_step` extends every live slot by
    one token against that cache. The round loop in serve/decode.py jits
    both with the cache buffers donated, so steady-state decode never
    reallocates HBM.
    """

    cfg: TransformerConfig

    def setup(self):
        assert self.cfg.causal, "CausalLM requires cfg.causal=True"
        # Attribute names double as scope names, keeping the param tree
        # ("encoder", "lm_head") identical to the old nn.compact layout.
        self.encoder = TransformerEncoder(self.cfg)
        if self.cfg.tie_head:
            self.lm_head = _TiedHead()
        else:
            self.lm_head = nn.Dense(
                self.cfg.vocab_size,
                kernel_init=_dense_init("embed", "vocab"),
                use_bias=self.cfg.use_bias,
                dtype=jnp.float32,
                param_dtype=self.cfg.param_dtype,
            )

    def __call__(self, input_ids, deterministic: bool = True, *,
                 positions=None):
        given = {} if positions is None else {"positions": positions}
        h = self.encoder(input_ids, None, deterministic, **given)
        # The final norm's output is written once. Fused into the head's
        # products instead, the norm is computed again inside the weight
        # gradient, whose tiling gets worse for it (PERF.md §6, PR 31:
        # 10.9 -> 11.9 ms at [2, 4096, 2048] x [2048, 50304]).
        h = jax.lax.optimization_barrier(h)
        return _logits(self, h)

    def prefill(self, input_ids, lengths):
        """Prompt pass that populates the KV cache.

        ``input_ids`` [B, S] right-padded prompts, ``lengths`` [B] true
        prompt lengths. Apply with ``mutable=["cache"]`` to receive the
        freshly written cache rows. Returns logits at each prompt's last
        real position — argmax of which is the sequence's first generated
        token (so TTFT costs exactly one forward pass).
        """
        _require_kv_cache(self.cfg)
        h = self.encoder(input_ids, None, True, cache_mode="prefill")
        last = jnp.take_along_axis(
            h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )
        return _logits(self, last)[:, 0]

    def decode_step(self, tokens, cache_positions, kv_len: int):
        """One decode iteration over the whole slot batch.

        ``tokens`` [B, 1] last generated token per slot, ``cache_positions``
        [B] current cache length per slot (the position the new token is
        written to), ``kv_len`` static cache-length bucket. Apply with the
        ``"cache"`` collection mutable; returns next-token logits [B, V].
        """
        _require_kv_cache(self.cfg)
        h = self.encoder(
            tokens,
            None,
            True,
            cache_mode="step",
            cache_positions=cache_positions,
            kv_len=kv_len,
        )
        return _logits(self, h)[:, 0]

    def init_cache(self, batch: int):
        """Shape-only helper: an all-zeros cache pytree for ``batch``
        slots (what one jitted prefill would create, without running it)."""
        cfg = self.cfg
        _require_kv_cache(self.cfg)
        shape = (batch, cfg.max_len, cfg.n_heads, cfg.head_dim)

        def zeros(_):
            return jnp.zeros(shape, cfg.dtype)

        names = [f"block_{i}" for i in range(cfg.n_layers)]
        return {
            "encoder": {
                name: {
                    "attn": {
                        "cached_key": zeros(None),
                        "cached_value": zeros(None),
                    }
                }
                for name in names
            }
        }


# ---------------------------------------------------------------- factories

def bert_base(**overrides) -> TransformerConfig:
    """BERT-base (the GLUE fine-tune target)."""
    return TransformerConfig(**overrides)


def olmoe(**overrides) -> TransformerConfig:
    """OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060; the
    ``config.json`` of allenai/OLMoE-1B-7B-0125-Instruct): 16 pre-norm
    decoder layers of width 2048, 16 heads of 128 with rotary positions
    and an RMSNorm over the whole q and k projections, no biases, 64
    SwiGLU experts of width 1024 with the 8 largest router probabilities
    used as they are, vocabulary 50304, context 4096."""
    defaults = dict(
        vocab_size=50304, d_model=2048, n_heads=16, n_layers=16,
        max_len=4096, dropout_rate=0.0, causal=True,
        norm="rmsnorm", norm_eps=1e-5, positions="rotary",
        rope_theta=10000.0, qk_norm=True, use_bias=False,
        ffn="moe", n_experts=64, top_k=8, d_expert=1024,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def granite_h_micro(**overrides) -> TransformerConfig:
    """IBM Granite 4.0-H Micro (3B, dense; ``config.json`` of
    ibm-granite/granite-4.0-h-micro, ``model_type`` granitemoehybrid): 40
    pre-norm layers of width 2048, a Mamba-2 mixer (64 heads of 64, state
    128, one group, 4-tap convolution, chunks of 256) in 36 of them and
    grouped-query attention (32 query / 8 key-value heads of 64, no
    positions, softmax scale 1/64) in layers 5, 15, 25 and 35; a dense
    SwiGLU MLP of width 8192 after either; RMSNorm; embedding × 12, every
    residual branch × 0.22, logits ÷ 8; vocabulary 100352, tied head."""
    n_layers = overrides.get("n_layers", 40)
    defaults = dict(
        vocab_size=100352, d_model=2048, n_heads=32, n_kv_heads=8,
        n_layers=n_layers, d_ff=8192, max_len=131072, dropout_rate=0.0,
        causal=True, norm="rmsnorm", norm_eps=1e-5, positions="none",
        use_bias=False, ffn="swiglu", attention_scale=0.015625,
        layer_types=tuple(
            "attention" if i % 10 == 5 else "mamba" for i in range(n_layers)
        ),
        tie_head=True, embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        ssm_groups=1, ssm_conv=4, ssm_chunk=256,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def lfm2_8b_a1b(**overrides) -> TransformerConfig:
    """Liquid AI LFM2-8B-A1B (8.3B parameters, 1.5B active; ``config.json``
    of LiquidAI/LFM2-8B-A1B, ``model_type`` lfm2_moe): 24 pre-norm layers
    of width 2048, a gated 3-tap short convolution in 18 of them and
    grouped-query attention (32 query / 8 key-value heads of 64, an RMSNorm
    over each head's q and k, rotary positions at theta 1e6) in layers 2,
    6, 10, 14, 18 and 21; a dense SwiGLU FFN of width 7168 in the first
    two layers and 32 SwiGLU experts of width 1792 in the others, 4 a
    token by sigmoid score + ``expert_bias``, their scores divided by
    their sum; RMSNorm, no biases, no auxiliary loss; vocabulary 65536,
    tied head. ``experts_held``/``first_expert`` give a layer the share of
    an expert-parallel deployment; ``n_layers`` keeps the model's own first
    layers."""
    attention = (2, 6, 10, 14, 18, 21)
    n_layers = overrides.get("n_layers", 24)
    defaults = dict(
        vocab_size=65536, d_model=2048, n_heads=32, n_kv_heads=8,
        n_layers=n_layers, d_ff=7168, max_len=128000, dropout_rate=0.0,
        causal=True, norm="rmsnorm", norm_eps=1e-5, positions="rotary",
        rope_theta=1e6, qk_norm="head", use_bias=False, ffn="moe",
        n_experts=32, top_k=4, d_expert=1792, router_scoring="sigmoid",
        router_bias=True, norm_top_k=True, routed_scaling=1.0,
        moe_loss_weights=(0.0, 0.0), conv_taps=3, tie_head=True,
        layer_types=tuple(
            ("attention" if i in attention else "conv")
            + (":swiglu" if i < 2 else ":moe") for i in range(n_layers)
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def xing4_0(**overrides) -> TransformerConfig:
    """Xing4.0-29B-A4B (29.5B parameters, about 4B active; ``config.json``
    of XingChen-AGI/Xing4.0-29B-A4B, ``model_type`` xing4_0): 40 pre-norm
    layers of width 3584 whose residual path is four streams mixed by
    Sinkhorn-projected mappings (``hc_mult`` 4, 20 rounds); latent
    attention in every layer (32 heads of 128 + 64 for q and k and 128 for
    v from latents of 768 and 512, one shared rotary key, YaRN x 64 over
    4,096); a dense SwiGLU FFN of width 9216 in the first two layers and
    64 SwiGLU experts of width 1024 beside one shared expert in the
    others, 4 a token by sigmoid score + ``e_score_correction_bias``,
    their scores divided by their sum and times 2; RMSNorm, no biases, no
    auxiliary loss; vocabulary 131072, untied head. The multi-token
    prediction module is ``models/mtp.MTPLM``'s (this preset's benchmark
    cut leaves it out for memory). ``experts_held`` /
    ``first_expert`` give a layer the share of an expert-parallel
    deployment; ``n_layers`` and ``dense_layers`` keep the model's own
    first layers."""
    from raydp_tpu.models.hyperconn import HyperConfig
    from raydp_tpu.models.latent import LatentConfig

    overrides = dict(overrides)
    n_layers = overrides.get("n_layers", 40)
    dense = overrides.pop("dense_layers", 2)
    defaults = dict(
        vocab_size=131072, d_model=3584, n_heads=32, n_layers=n_layers,
        d_ff=9216, max_len=262144, dropout_rate=0.0, causal=True,
        norm="rmsnorm", norm_eps=1e-6, positions="rotary",
        rope_theta=10000.0, use_bias=False, ffn="moe", n_experts=64,
        top_k=4, d_expert=1024, shared_experts=1, router_scoring="sigmoid",
        router_bias=True, norm_top_k=True, routed_scaling=2.0,
        moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=tuple(
            "latent" + (":swiglu" if i < dense else ":moe")
            for i in range(n_layers)
        ),
        latent=LatentConfig(
            q_rank=768, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
            yarn=YarnScaling(
                factor=64.0, original_max_len=4096, beta_fast=32.0,
                beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0,
            ),
        ),
        hyper=HyperConfig(streams=4, sinkhorn_iters=20, eps=1e-6,
                          clamp=(-30.0, 30.0)),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def glm_4_7_flash(**overrides) -> TransformerConfig:
    """GLM-4.7-Flash (30B parameters, about 3.6B active; ``config.json``
    of zai-org/GLM-4.7-Flash, ``model_type`` glm4_moe_lite): 47 pre-norm
    layers of width 2048 on ONE residual stream; latent attention in every
    layer (20 heads of 192 + 64 for q and k and 256 for v from latents of
    768 and 512, one shared rotary key, theta 1e6, no scaling); a dense
    SwiGLU FFN of width 10240 in the first layer and 64 SwiGLU experts of
    width 1536 beside one shared expert in the others, 4 a token by
    sigmoid score + ``e_score_correction_bias``, their scores divided by
    their sum and times 1.8; RMSNorm at 1e-5, no biases, no auxiliary
    loss; vocabulary 154880, untied head. Its multi-token prediction
    module (``num_nextn_predict_layers`` 1) is ``models/mtp.MTPLM`` over
    this configuration. ``experts_held`` / ``first_expert`` give a layer
    the share of an expert-parallel deployment; ``n_layers`` and
    ``dense_layers`` keep the model's own first layers."""
    from raydp_tpu.models.latent import LatentConfig

    overrides = dict(overrides)
    n_layers = overrides.get("n_layers", 47)
    dense = overrides.pop("dense_layers", 1)
    defaults = dict(
        vocab_size=154880, d_model=2048, n_heads=20, n_layers=n_layers,
        d_ff=10240, max_len=202752, dropout_rate=0.0, causal=True,
        norm="rmsnorm", norm_eps=1e-5, positions="rotary",
        rope_theta=1e6, use_bias=False, ffn="moe", n_experts=64,
        top_k=4, d_expert=1536, shared_experts=1, router_scoring="sigmoid",
        router_bias=True, norm_top_k=True, routed_scaling=1.8,
        moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=tuple(
            "latent" + (":swiglu" if i < dense else ":moe")
            for i in range(n_layers)
        ),
        latent=LatentConfig(
            q_rank=768, kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256,
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def laguna_xs_2(**overrides) -> TransformerConfig:
    """poolside Laguna-XS.2 (33.4B parameters, about 3B active;
    ``config.json`` of poolside/Laguna-XS.2, ``model_type`` laguna): 40
    pre-norm layers of width 2048 with heads of 128 over 8 key-value
    heads; every fourth layer from layer 0 attends over all earlier
    positions with 48 query heads, half of each head rotated at theta
    500,000 under YaRN x 64 over 4,096 (beta 64 / 1, the rotation times
    1.41589), the other three over the last 512 with 64 query heads, the
    whole head rotated at theta 10,000; a sigmoid gate a head on
    attention's output; a dense SwiGLU FFN of width 8192 in layer 0 and
    256 SwiGLU experts of width 512 beside one shared expert in the
    others, 8 a token by sigmoid score, their scores divided by their sum
    and times 2.5; RMSNorm 1e-6, no biases, no auxiliary loss; vocabulary
    100352, untied head. ``experts_held`` / ``first_expert`` give a layer
    the share of an expert-parallel deployment; ``n_layers`` and
    ``dense_layers`` keep the model's own first layers."""
    overrides = dict(overrides)
    n_layers = overrides.get("n_layers", 40)
    dense = overrides.pop("dense_layers", 1)
    defaults = dict(
        vocab_size=100352, d_model=2048, n_heads=48, n_kv_heads=8,
        head_size=128, n_layers=n_layers, d_ff=8192, max_len=262144,
        dropout_rate=0.0, causal=True, norm="rmsnorm", norm_eps=1e-6,
        positions="rotary", rope_theta=500000.0, rotary_dim=64,
        rope_yarn=YarnScaling(
            factor=64.0, original_max_len=4096, beta_fast=64.0,
            beta_slow=1.0, attention_factor=1.4158883083359672,
        ),
        head_gate=True,
        window=WindowConfig(window=512, n_heads=64, rope_theta=10000.0),
        use_bias=False, ffn="moe", n_experts=256, top_k=8, d_expert=512,
        shared_experts=1, router_scoring="sigmoid", norm_top_k=True,
        routed_scaling=2.5, moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=tuple(
            ("attention" if i % 4 == 0 else "window")
            + (":swiglu" if i < dense else ":moe") for i in range(n_layers)
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def kimi_linear_48b_a3b(**overrides) -> TransformerConfig:
    """Moonshot AI Kimi-Linear-48B-A3B (49.1B parameters, about 3B active;
    ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct,
    ``model_type`` kimi_linear; arXiv:2510.26692): 27 pre-norm layers of
    width 2304 and NO positions anywhere; Kimi Delta Attention (32 heads
    with keys and values of 128, 4-tap convolutions, a decay per channel;
    ``models/kda.py``) in three layers of four and latent attention
    without a query latent or a rotation (32 heads of 128 + 64 for q and
    k and 128 for v from a key-value latent of 512) in layers 4, 8, …, 24
    and 27 (1-indexed); a dense SwiGLU FFN of width 9216 in the first
    layer and 256 SwiGLU experts of width 1024 beside one shared expert in
    the others, 8 a token by sigmoid score + a selection bias, their
    scores divided by their sum and times 2.446; RMSNorm 1e-5, no biases,
    no auxiliary loss; vocabulary 163840, untied head. ``experts_held`` /
    ``first_expert`` give a layer the share of an expert-parallel
    deployment; ``n_layers`` and ``dense_layers`` keep the model's own
    first layers."""
    from raydp_tpu.models.kda import KDAConfig
    from raydp_tpu.models.latent import LatentConfig

    overrides = dict(overrides)
    n_layers = overrides.get("n_layers", 27)
    dense = overrides.pop("dense_layers", 1)
    latent_layers = {3, 7, 11, 15, 19, 23, 26}
    defaults = dict(
        vocab_size=163840, d_model=2304, n_heads=32, n_layers=n_layers,
        d_ff=9216, max_len=1048576, dropout_rate=0.0, causal=True,
        norm="rmsnorm", norm_eps=1e-5, positions="none", use_bias=False,
        ffn="moe", n_experts=256, top_k=8, d_expert=1024, shared_experts=1,
        router_scoring="sigmoid", router_bias=True, norm_top_k=True,
        routed_scaling=2.446, moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=tuple(
            ("latent" if i in latent_layers else "kda")
            + (":swiglu" if i < dense else ":moe") for i in range(n_layers)
        ),
        latent=LatentConfig(
            q_rank=None, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        ),
        kda=KDAConfig(heads=32, key_dim=128, value_dim=128, conv_taps=4,
                      gate_rank=128, chunk=64),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def olmo_hybrid_7b(**overrides) -> TransformerConfig:
    """Olmo-Hybrid-7B (Allen AI; ``config.json`` of allenai/Olmo-Hybrid-7B,
    ``model_type`` olmo_hybrid): 32 decoder layers of width 3840, Gated
    DeltaNet (arXiv:2412.06464; 30 heads with keys of 96 and values of
    192, one decay a head, beta in (0, 2): ``models/gdn.py``) in three
    layers of four and full attention (30 heads of 128, a norm over the
    whole q and k projections, no positions: the delta-rule layers carry
    them) in the fourth, a dense SwiGLU FFN of 11008 in every layer, and
    the OLMo 2 stack's norms: RMSNorm (eps 1e-6) on each sublayer's OUTPUT
    and none on its input; no biases, vocabulary 100,352, untied head."""
    from raydp_tpu.models.gdn import GDNConfig

    n_layers = overrides.get("n_layers", 32)
    defaults = dict(
        vocab_size=100352, d_model=3840, n_heads=30, n_layers=n_layers,
        d_ff=11008, max_len=65536, dropout_rate=0.0, causal=True,
        norm="rmsnorm", norm_eps=1e-6, positions="none",
        qk_norm="projection", use_bias=False, ffn="swiglu", tie_head=False,
        branch_norm="only",
        layer_types=tuple(
            ("attention" if i % 4 == 3 else "gdn") + ":swiglu"
            for i in range(n_layers)
        ),
        gdn=GDNConfig(heads=30, key_dim=96, value_dim=192, conv_taps=4,
                      chunk=64, neg_eigval=True),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def sdar_30b_a3b(**overrides) -> TransformerConfig:
    """JetLM SDAR-30B-A3B-Chat (30.5B parameters, about 3B active;
    ``config.json`` of JetLM/SDAR-30B-A3B-Chat, ``model_type`` sdar_moe;
    arXiv:2510.06303): 48 identical pre-norm layers of width 2048;
    grouped-query attention, 32 query heads over 4 key-value heads of 128
    (a head size of its own: 32 x 128 = 4096 over a hidden of 2048), an
    RMSNorm over each head's q and k, the whole head rotated at theta
    1e6; 128 SwiGLU experts of width 768, 8 a token by softmax
    probability, renormalised, no shared expert, no auxiliary loss;
    RMSNorm 1e-6, no biases; vocabulary 151936, untied head. It is
    trained and sampled by DIFFUSION OVER BLOCKS: ``diffusion`` is the
    objective's record (``models/blockdiff.py``; the config gives neither
    the block length nor the noise schedule: 4, the released chat
    model's generation block, and the linear schedule of arXiv:2503.09573
    are assumed), and the model class is ``BlockDiffusionLM``.
    ``experts_held`` / ``first_expert`` give a layer the share of an
    expert-parallel deployment; ``n_layers`` keeps the model's own first
    layers. The published block has no selection bias and neither has
    this one."""
    from raydp_tpu.models.blockdiff import BlockDiffusionConfig

    defaults = dict(
        vocab_size=151936, d_model=2048, n_heads=32, n_kv_heads=4,
        head_size=128, n_layers=48, max_len=32768, dropout_rate=0.0,
        causal=True, norm="rmsnorm", norm_eps=1e-6, positions="rotary",
        rope_theta=1e6, qk_norm="head", use_bias=False, ffn="moe",
        n_experts=128, top_k=8, d_expert=768, router_scoring="softmax",
        norm_top_k=True, moe_loss_weights=(0.0, 0.0), tie_head=False,
        diffusion=BlockDiffusionConfig(
            block_length=4, mask_id=151669, t_min=1e-3
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def keye_vl_2_0_30b_a3b(**overrides) -> TransformerConfig:
    """Kwai Keye-VL-2.0-30B-A3B's LANGUAGE MODEL (30.6B parameters, about
    3B active; ``config.json`` of Kwai-Keye/Keye-VL-2.0-30B-A3B,
    ``model_type`` KeyeVL2): 48 identical pre-norm layers of width 2048;
    grouped-query attention, 32 query heads over 4 key-value heads of 128,
    an RMSNorm over each head's q and k, the head rotated by three ids a
    token (M-RoPE, bands of 16, 24 and 24 frequencies at theta 1e7; a
    text's ids are equal), OVER THE 2,048 KEYS A LEARNED INDEX BRANCH
    SELECTS for each query (``sa_config``: 16 index heads of 64 over one
    index key head; ``models/sparse_index.py``); 128 SwiGLU experts of
    width 768, 8 a token by softmax probability, renormalised, no shared
    expert; RMSNorm 1e-6, no biases; vocabulary 151936, untied head. The
    vision tower is not built (the config gives none of its widths).
    ``experts_held`` / ``first_expert`` give a layer the share of an
    expert-parallel deployment; ``n_layers`` keeps the model's own first
    layers."""
    from raydp_tpu.models.sparse_index import SparseIndexConfig

    n_layers = overrides.get("n_layers", 48)
    defaults = dict(
        vocab_size=151936, d_model=2048, n_heads=32, n_kv_heads=4,
        head_size=128, n_layers=n_layers, max_len=262144, dropout_rate=0.0,
        causal=True, norm="rmsnorm", norm_eps=1e-6, positions="mrope",
        mrope_section=(16, 24, 24), rope_theta=1e7, qk_norm="head",
        use_bias=False, ffn="moe", n_experts=128, top_k=8, d_expert=768,
        router_scoring="softmax", norm_top_k=True,
        moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=("sparse",) * n_layers,
        sparse=SparseIndexConfig(
            index_heads=16, index_head_dim=64, index_kv_heads=1, topk=2048,
            q_chunk=512, kv_chunk=512,
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def mellum2_12b_a2_5b(**overrides) -> TransformerConfig:
    """JetBrains Mellum2-12B-A2.5B-Instruct (12.15B parameters, about 2.5B
    active; ``config.json`` of JetBrains/Mellum2-12B-A2.5B-Instruct,
    ``model_type`` mellum): 28 pre-norm layers of width 2304; grouped-query
    attention, 32 query heads over 4 key-value heads of 128, the whole head
    rotated at theta 500,000, no QK-norm; every fourth layer from layer 3
    attends over all earlier positions under YaRN x 16 over 8,192 (beta 32
    / 1, the rotation times 1.27726), the other three over the last 1,024
    positions with the plain frequencies; 64 SwiGLU experts of width 896
    in every layer, 8 a token by softmax probability, renormalised, no
    shared expert, no selection bias, no auxiliary loss; RMSNorm 1e-6, no
    biases; vocabulary 98304, untied head. The model's stated deployment
    shares a layer among four chips: ``mesh`` with ``state_axis`` lays the
    64 experts over that axis, 16 a chip, with the exchange around them,
    and the two vocabulary tables, a quarter a chip (``JAXEstimator``
    keeps them there at rest); ``n_layers`` keeps the model's own first
    layers."""
    n_layers = overrides.get("n_layers", 28)
    defaults = dict(
        vocab_size=98304, d_model=2304, n_heads=32, n_kv_heads=4,
        head_size=128, n_layers=n_layers, d_ff=7168, max_len=131072,
        dropout_rate=0.0, causal=True, norm="rmsnorm", norm_eps=1e-6,
        positions="rotary", rope_theta=500000.0,
        rope_yarn=YarnScaling(
            factor=16.0, original_max_len=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782,
        ),
        window=WindowConfig(window=1024, n_heads=32, rope_theta=500000.0),
        qk_norm=False, use_bias=False, ffn="moe", n_experts=64, top_k=8,
        d_expert=896, router_scoring="softmax", norm_top_k=True,
        moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=tuple(
            "attention" if i % 4 == 3 else "window" for i in range(n_layers)
        ),
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


NEMOTRON_H_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def hybrid_pattern_layers(pattern: str) -> Tuple[str, ...]:
    """``layer_types`` of a ``nemotron_h`` ``hybrid_override_pattern``:
    one character a layer and ONE sublayer a layer, ``M`` a Mamba-2
    mixer, ``*`` attention, ``E`` the routed feed-forward part (``-``, the
    family's dense relu² part, is in no published pattern this stack
    runs and is not built)."""
    kinds = {"M": "mamba:none", "*": "attention:none", "E": "none:moe"}
    if set(pattern) - set(kinds):
        raise ValueError(f"hybrid pattern {pattern!r}: M, * or E a layer")
    return tuple(kinds[c] for c in pattern)


def nemotron_3_nano_30b_a3b(**overrides) -> TransformerConfig:
    """NVIDIA Nemotron 3 Nano 30B-A3B (31.6B parameters, about 3.2B active;
    ``config.json`` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
    ``model_type`` nemotron_h): 52 pre-norm layers of width 2688 in the
    pattern ``MEMEM*E…`` where EVERY LAYER IS ONE SUBLAYER, ``x +
    F(rms(x))``: a Mamba-2 mixer in 23 (64 heads of 64, state 128, EIGHT
    groups of B and C, 4-tap convolution with bias, chunks of 128, the
    gated norm over each group's 512 features), the routed feed-forward
    part in 23 (128 UNGATED relu² experts of width 1856,
    ``down(relu(up x)²)``, 6 a token by sigmoid score + a selection bias,
    their scores divided by their sum and times 2.5, beside one shared
    expert of the same form at width 3712) and grouped-query attention in
    6 (32 query heads over 2 key-value heads of 128, no bias, no
    positions); RMSNorm 1e-5, no auxiliary loss; vocabulary 131072,
    untied head. ``pattern`` keeps the model's own first layers;
    ``experts_held`` / ``first_expert`` give a layer the share of an
    expert-parallel deployment."""
    overrides = dict(overrides)
    pattern = overrides.pop("pattern", NEMOTRON_H_PATTERN)
    defaults = dict(
        vocab_size=131072, d_model=2688, n_heads=32, n_kv_heads=2,
        head_size=128, n_layers=len(pattern), max_len=262144,
        dropout_rate=0.0, causal=True, norm="rmsnorm", norm_eps=1e-5,
        positions="none", use_bias=False, ffn="moe", n_experts=128,
        top_k=6, d_expert=1856, shared_experts=2, expert_form="relu2",
        router_scoring="sigmoid", router_bias=True, norm_top_k=True,
        routed_scaling=2.5, moe_loss_weights=(0.0, 0.0), tie_head=False,
        layer_types=hybrid_pattern_layers(pattern),
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv=4, ssm_chunk=128,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def ouro_2_6b(**overrides) -> TransformerConfig:
    """Ouro-2.6B (ByteDance; ``config.json`` of ByteDance/Ouro-2.6B,
    ``model_type`` ouro; "Scaling Latent Reasoning via Looped Language
    Models", arXiv:2510.25741): 48 decoder layers of width 2048, 16 heads
    of 128 (no grouping) with rotary positions at theta 1e6, a SwiGLU FFN
    of width 5632, RMSNorm (eps 1e-6) on every sublayer's input AND
    output, no biases; the whole stack run ``total_ut_steps`` = 4 times
    over its one set of weights, the final norm closing every pass;
    vocabulary 49,152, untied head. ``models/loop.LoopLM`` puts the exit
    gate and the head after every pass."""
    defaults = dict(
        vocab_size=49152, d_model=2048, n_heads=16, n_layers=48, d_ff=5632,
        max_len=65536, dropout_rate=0.0, causal=True, norm="rmsnorm",
        norm_eps=1e-6, positions="rotary", rope_theta=1e6, use_bias=False,
        ffn="swiglu", tie_head=False, passes=4, branch_norm=True,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def tiny_transformer(**overrides) -> TransformerConfig:
    """Small MXU-aligned config for tests/dry runs (widths still /128)."""
    defaults = dict(
        vocab_size=1024, d_model=128, n_heads=8, n_layers=2, d_ff=256,
        max_len=128, dropout_rate=0.0,
    )
    defaults.update(overrides)
    return TransformerConfig(**defaults)


def report(cfg) -> None:
    """Static for a compiled step: the stack's layers by what they hold,
    gauges where the step is built (as ``models/mamba.report``):
    ``stack/layers``, ``stack/sublayers`` (two a layer unless it names
    one), ``stack/mixer_only_layers`` and ``stack/ffn_only_layers``. Zero
    for a model that is no such stack."""
    from raydp_tpu.utils.profiling import metrics

    layers = getattr(cfg, "layers", ())
    mixer_only = sum(1 for _, ffn in layers if ffn == NONE)
    ffn_only = sum(1 for mixer, _ in layers if mixer == NONE)
    metrics.gauge_set("stack/layers", len(layers))
    metrics.gauge_set(
        "stack/sublayers", 2 * len(layers) - mixer_only - ffn_only
    )
    metrics.gauge_set("stack/mixer_only_layers", mixer_only)
    metrics.gauge_set("stack/ffn_only_layers", ffn_only)


# ------------------------------------------------------------- shardings

def param_shardings(model: nn.Module, mesh, *example_args, rules=LOGICAL_RULES):
    """Mesh shardings for every parameter, derived from the logical axis
    metadata — the pjit weight-sharding story (SURVEY §2.4 "TP" row).

    Returns (abstract_variables, shardings). Typical use::

        _, shardings = param_shardings(model, mesh, ids)
        params = jax.jit(lambda: nn.unbox(model.init(key, ids)),
                         out_shardings=shardings)()

    (``nn.unbox`` strips the logical-partitioning metadata boxes so the
    tree is plain arrays for optax/checkpointing.)
    """
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), *example_args)
    )
    logical = nn.get_partition_spec(abstract)
    return abstract, nn.logical_to_mesh_sharding(
        logical, mesh, effective_rules(mesh, rules)
    )


def effective_rules(mesh, rules=LOGICAL_RULES):
    """Logical rules restricted to the axes this mesh actually has —
    a dp×tp mesh simply replicates the seq axis rather than erroring on
    the absent ``sp``."""
    return [
        (logical, axis if axis in mesh.axis_names else None)
        for logical, axis in rules
    ]


def vocab_rules(axis: Optional[str], rules=LOGICAL_RULES):
    """``rules`` with the logical axis ``vocab`` on mesh axis ``axis``, so
    that ``tok_embed``'s rows, an untied ``lm_head``'s columns and their
    optimizer moments lie over that axis at rest. ``JAXEstimator`` applies
    it to its rules for a model whose configuration names a
    ``state_axis`` (the model computes with the tables there)."""
    return tuple(
        (logical, axis if logical == "vocab" else mesh_axis)
        for logical, mesh_axis in rules
    )
