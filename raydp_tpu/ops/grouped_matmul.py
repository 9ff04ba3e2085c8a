"""Grouped matrix multiplication for routed experts.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies the rows of ``lhs``
``[M, K]``, which are sorted by group, by their group's matrix in ``rhs``
``[G, K, N]``: rows ``sum(group_sizes[:g])`` up to ``sum(group_sizes[:g+1])``
meet ``rhs[g]``. It is the Pallas kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for the
input gradient, ``tgmm`` for the weight gradient, tied by its
``custom_vjp``), at a tiling measured on a TPU v5 lite. Why not
``jax.lax.ragged_dot``: at ``[65536, 2048] x [64, 2048, 1024]`` in bf16 a
SwiGLU expert layer's three products, forward and backward, took 30.0 ms
with it and 21.1 ms with this kernel, against 12.6 ms at the chip's peak
(PERF.md §6, PR 26).

The kernel is compiled by Mosaic for a TPU. On any other backend the same
kernel body runs in the Pallas interpreter, so that the models and their
tests run on the CPU; that is a matter of the platform, not a second
implementation. XLA cannot partition a Mosaic kernel: on a mesh of TPU
chips the call needs a ``shard_map`` over the experts (not written yet).
"""
from __future__ import annotations

import math

import jax
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

# (rows, contraction, columns) of one tile. 512 x 1024 x 1024 was the
# fastest of six for all three products of both expert shapes
# (2.25-2.49 ms each; 128^3 took 26-34 ms).
TILING = (512, 1024, 1024)
IMPLEMENTATION = "megablox gmm (Pallas), tiling %dx%dx%d" % TILING


def grouped_matmul(lhs, rhs, group_sizes):
    """``[M, K] x [G, K, N] -> [M, N]`` in ``lhs``'s dtype, float32
    accumulation. ``group_sizes`` is int32 ``[G]`` and sums to ``M``."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    # The row tile has to divide M: the largest power of two that does.
    tiling = (math.gcd(m, TILING[0]), min(k, TILING[1]), min(n, TILING[2]))
    return _gmm(
        lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False,
        jax.default_backend() != "tpu",
    )
