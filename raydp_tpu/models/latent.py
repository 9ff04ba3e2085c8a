"""Multi-head latent attention in its expanded (training) form.

The DeepSeek-V2/V3 layer (arXiv:2405.04434) the key names ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim`` and
``v_head_dim`` belong to: queries and key-values go through a narrow
latent, positions ride on a separate rotary part, and the key's rotary
part is ONE head shared by all query heads.

    c_q = rms(h W_dq)                         [q_rank]
    q   = c_q W_uq                            H x (nope + rope)
    [c_kv, k_r] = h W_dkv                     [kv_rank + rope]
    [k_nope, v] = rms(c_kv) W_ukv             H x (nope + v_dim)
    q_r, k_r rotated (YaRN frequencies); k_r the same for every head
    scores = (q_nope k_nope^T + q_r k_r^T) * (nope + rope)^-1/2 * m^2
    out    = softmax_causal(scores) v W_o

``m = 0.1 * mscale_all_dim * ln(factor) + 1`` is YaRN's attention factor
(:func:`raydp_tpu.models.transformer.yarn_mscale`); the rotation itself is
scaled by ``m(mscale) / m(mscale_all_dim)``, 1 where the two are equal.

The score is ONE ``(nope + rope)``-wide product: the shared rotary key is
repeated across the heads and concatenated to ``k_nope`` (50 MB in
bfloat16 at 4,096 tokens and 32 heads), so the kernels see ``q`` and ``k``
of one width and ``v`` of another (``ops/flash_attention.py``). The
absorbed form, in which the up-projections fold into ``q`` and the output
and the cache holds ``c_kv`` and ``k_r`` alone, is the serving plane's
(ROADMAP R3); nothing here keeps a cache.

With ``q_rank`` None the queries come from ONE full-rank projection of
``h`` (no ``q_down``, no ``q_norm``; the projection keeps the name
``q_up``), and in a stack without positions (``positions="none"``: another
layer kind carries them) the ``rope`` features of ``q`` and the shared key
stay and nothing is rotated.

Scopes under the module (``attn`` in a block): ``q_down``, ``q_norm``,
``q_up``, ``kv_down``, ``kv_norm``, ``kv_up``, ``rope``, the kernel's, ``out``.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """The latent mixer's own sizes (the head count, ``rope_theta`` and
    the norm's epsilon are the stack's)."""

    q_rank: Optional[int] = 768      # None = one full-rank q projection
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    yarn: Any = None                 # transformer.YarnScaling | None

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        from raydp_tpu.models.transformer import yarn_mscale

        scale = self.qk_dim ** -0.5
        if self.yarn is not None:
            scale *= yarn_mscale(
                self.yarn.factor, self.yarn.mscale_all_dim
            ) ** 2
        return scale

    def cache_bytes_per_token(self, layers: int, itemsize: int = 2) -> int:
        """What the absorbed decode path's cache will hold a token: the
        key-value latent and the shared rotary key of every layer."""
        return (self.kv_rank + self.rope_dim) * itemsize * layers


class LatentAttention(nn.Module):
    cfg: Any                         # TransformerConfig with ``latent`` set

    @nn.compact
    def __call__(self, x):
        from raydp_tpu.models.transformer import (
            _dense_init,
            _norm,
            rotary,
        )
        from raydp_tpu.ops.attention import reference_attention

        cfg, lat = self.cfg, self.cfg.latent
        if not cfg.causal or cfg.positions not in ("rotary", "none"):
            raise ValueError(
                "latent attention: a causal stack, rotary or without "
                "positions"
            )
        h, nope = cfg.n_heads, lat.nope_dim
        project = functools.partial(
            nn.DenseGeneral, axis=-1, use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )
        if lat.q_rank is None:
            q = project(
                features=(h, lat.qk_dim),
                kernel_init=_dense_init("embed", "heads", "kv"), name="q_up",
            )(x)
        else:
            c_q = project(
                features=lat.q_rank, kernel_init=_dense_init("embed", None),
                name="q_down",
            )(x)
            q = project(
                features=(h, lat.qk_dim),
                kernel_init=_dense_init(None, "heads", "kv"), name="q_up",
            )(_norm(cfg, "q_norm")(c_q))
        down = project(
            features=lat.kv_rank + lat.rope_dim,
            kernel_init=_dense_init("embed", None), name="kv_down",
        )(x)
        c_kv, k_rope = down[..., :lat.kv_rank], down[..., lat.kv_rank:]
        kv = project(
            features=(h, nope + lat.v_dim),
            kernel_init=_dense_init(None, "heads", "kv"), name="kv_up",
        )(_norm(cfg, "kv_norm")(c_kv))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        with jax.named_scope("rope"):
            if cfg.positions == "rotary":
                pos = jnp.arange(x.shape[-2])[None, :]
                turn = functools.partial(
                    rotary, positions=pos, theta=cfg.rope_theta,
                    yarn=lat.yarn,
                )
                q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
                k_rope = turn(k_rope[..., None, :])
            else:
                k_rope = k_rope[..., None, :]
            # One such key head, the same for every query head.
            k = jnp.concatenate([
                k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (
                    lat.rope_dim,
                )),
            ], -1)
        scale = lat.softmax_scale
        if cfg.attention_impl == "dense":
            out = reference_attention(q, k, v, causal=True, scale=scale)
        elif cfg.attention_impl == "flash":
            from raydp_tpu.ops.flash_attention import (
                flash_attention,
                sharded_flash_attention,
            )

            if cfg.mesh is not None:
                out = sharded_flash_attention(
                    q, k, v, mesh=cfg.mesh, causal=True, scale=scale
                )
            else:
                out = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            raise NotImplementedError(
                f"latent attention through {cfg.attention_impl!r}"
            )
        return nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1),
            kernel_init=_dense_init("heads", "kv", "embed"),
            use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="out",
        )(out)


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "latent")


def report(cfg) -> None:
    """Static for a compiled step: four gauges and one log line where the
    step is built (as ``models/mamba.report``). Zero for a stack without
    latent layers; ``latent/rotary_dims`` also where nothing is rotated."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    lat: Optional[LatentConfig] = cfg.latent if layers else None
    metrics.gauge_set("attention/latent_layers", layers)
    metrics.gauge_set("attention/kv_latent_rank", lat.kv_rank if lat else 0)
    metrics.gauge_set(
        "attention/latent_cache_bytes_per_token",
        lat.cache_bytes_per_token(layers) if lat else 0,
    )
    rotated = lat.rope_dim if lat and cfg.positions == "rotary" else 0
    metrics.gauge_set("latent/rotary_dims", rotated)
    if lat:
        logger.info(
            "latent attention: %d layers, %d heads of %d + %d (q, k) and %d "
            "(v) from latents of %s (q) and %d (kv); one shared key of %d, "
            "%d of them rotated; softmax scale %g; expanded form, %d B a "
            "token in a latent cache",
            layers, cfg.n_heads, lat.nope_dim, lat.rope_dim, lat.v_dim,
            "no rank" if lat.q_rank is None else lat.q_rank, lat.kv_rank,
            lat.rope_dim, rotated, lat.softmax_scale,
            lat.cache_bytes_per_token(layers),
        )
