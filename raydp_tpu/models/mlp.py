"""Flax MLP models for tabular regression/classification.

Model-family parity with the reference's example models (reference:
examples/pytorch_nyctaxi.py NYC_Model — a dense stack with per-layer
batch-norm-free ReLU; examples/tensorflow_titanic.ipynb — a small sigmoid
classifier). bfloat16-friendly: matmuls run in the param dtype, and layer
widths default to MXU-friendly multiples of 128.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from raydp_tpu.models.dropout import Dropout


class MLP(nn.Module):
    """Dense stack: hidden layers + linear head."""

    hidden: Sequence[int] = (256, 128, 64)
    out_dim: int = 1
    activation: Callable = nn.relu
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.Dense(width, dtype=self.dtype)(x)
            x = self.activation(x)
            if self.dropout_rate > 0:
                x = Dropout(self.dropout_rate)(x, deterministic)
        x = nn.Dense(self.out_dim, dtype=self.dtype)(x)
        return x


def taxi_fare_regressor(dtype=jnp.float32) -> MLP:
    """NYC-taxi fare MLP (capability parity with reference
    examples/pytorch_nyctaxi.py NYC_Model)."""
    return MLP(hidden=(256, 128, 64, 32), out_dim=1, dtype=dtype)


def binary_classifier(hidden: Sequence[int] = (128, 64), dtype=jnp.float32) -> MLP:
    """Titanic-style binary classifier emitting ONE logit (reference:
    examples/tensorflow_titanic.ipynb)."""
    return MLP(hidden=tuple(hidden), out_dim=1, dtype=dtype)
