"""The "window" mixer's record and its build-time report.

A stack may mix two kinds of softmax attention layer (``layer_types``):
"attention" over all earlier positions, from ``TransformerConfig``'s own
sizes, and "window" over the last ``window`` positions, a query's own
among them, with the head count and rotary positions of
:class:`WindowConfig`. Both are ``models/transformer.MultiHeadAttention``
(module names ``attn`` and ``attn_window``, so a trace tells them apart);
the head size, the key-value heads, the output gate and the norms are the
stack's. The kernels skip and never fetch what a window excludes
(``ops/flash_attention.py``).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class WindowConfig:
    """The "window" mixer's own sizes."""

    window: int = 512
    n_heads: int = 64
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None     # None = the whole head


def layers_of(cfg) -> int:
    return sum(1 for kind in getattr(cfg, "kinds", ()) if kind == "window")


def report(cfg) -> None:
    """Static for a compiled step: two gauges and one log line where the
    step is built (as ``models/latent.report``). Zero for a stack without
    window layers."""
    from raydp_tpu.utils.profiling import metrics

    layers = layers_of(cfg)
    win: Optional[WindowConfig] = cfg.window if layers else None
    metrics.gauge_set("attention/window_layers", layers)
    metrics.gauge_set("attention/window", win.window if win else 0)
    if win:
        head = cfg.head_dim
        logger.info(
            "window attention: %d layers over the last %d positions with "
            "%d query heads over %d key-value heads of %d, %d of %d "
            "features rotated at theta %g; %d layers over all positions "
            "with %d query heads, %d of %d rotated at theta %g%s; output "
            "gate a head: %s",
            layers, win.window, win.n_heads, cfg.kv_heads, head,
            win.rotary_dim or head, head, win.rope_theta,
            cfg.kinds.count("attention"), cfg.n_heads,
            cfg.rotary_dim or head, head, cfg.rope_theta,
            "" if cfg.rope_yarn is None else (
                f" under YaRN x {cfg.rope_yarn.factor:g} (rotation x "
                f"{cfg.rope_yarn.stretch:g})"
            ),
            cfg.head_gate,
        )
