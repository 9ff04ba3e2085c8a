"""Attention ops: reference, ring (sequence-parallel), Ulysses.

Long-context capability the reference lacks entirely (SURVEY §5.7 — the
reference scales rows, never sequence length). Design is TPU-first:

  * ``ring_attention`` — q stays put, K/V blocks rotate around the ``sp``
    mesh axis via ``lax.ppermute`` (ICI neighbor hops), merged with an
    online-softmax accumulator. Memory per chip is O(S/sp); comm is
    overlap-friendly neighbor traffic, never an all-gather of the
    sequence.
  * ``ulysses_attention`` — all_to_all flips sequence-sharding into
    head-sharding, local full attention, flips back. Cheaper compute
    bookkeeping when heads >= sp, at the cost of all_to_all volume.

Both are numerically checked against ``reference_attention`` in tests on
a real 8-device mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _scale(scale: Optional[float], head_dim: int) -> float:
    """The softmax scale: ``head_dim ** -0.5`` unless the architecture
    publishes another."""
    return 1.0 / math.sqrt(head_dim) if scale is None else scale


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    pair: Optional[int] = None,
) -> jnp.ndarray:
    """Plain softmax attention. Shapes: q [B, S, H, D], k and v [B, S,
    Hkv, D] with H a multiple of Hkv (each key-value head serves H / Hkv
    consecutive query heads) → [B, S, H, D]. ``window`` (causal only)
    keeps the last ``window`` keys of each query, its own among them.
    ``pair`` (a block length, causal only, no window) reads the S
    positions as a noised and a clean copy of S/2 each under
    :func:`pair_mask`."""
    scale = _scale(scale, q.shape[-1])
    if (window is not None or pair is not None) and not causal:
        raise ValueError("a window, or a pair's blocks, are over the keys "
                         "before a query")
    if pair is not None and window is not None:
        raise ValueError("no window under the pair mask")
    h, h_kv = q.shape[2], k.shape[2]
    if h != h_kv:
        b, s_q, _, d = q.shape
        out = _grouped_attention(
            q.reshape(b, s_q, h_kv, h // h_kv, d), k, v, causal, scale,
            window, pair,
        )
        return out.reshape(b, s_q, h, v.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        scores = jnp.where(_mask(scores, window, pair), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _causal_mask(scores, window: Optional[int] = None):
    s_q, s_k = scores.shape[-2], scores.shape[-1]
    mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
    if window is not None:
        mask = jnp.triu(mask, k=s_k - s_q - window + 1)
    return mask


def pair_mask(s: int, block_length: int) -> np.ndarray:
    """The attention mask of block diffusion's training pair, [2s, 2s]
    bool, query down the rows: positions ``[0, s)`` are a NOISED copy of a
    sequence and ``[s, 2s)`` its CLEAN copy, in blocks of ``block_length``
    (``b(i) = (i mod s) // block_length``). A noised query sees the noised
    keys of its own block (both directions) and the clean keys of the
    blocks strictly before it; a clean query sees the clean keys of its
    own and earlier blocks; no clean query sees a noised key."""
    if s % block_length:
        raise ValueError(
            f"{s} positions do not divide into blocks of {block_length}"
        )
    block = np.arange(2 * s) % s // block_length
    clean = np.arange(2 * s) >= s
    q_b, k_b = block[:, None], block[None, :]
    q_c, k_c = clean[:, None], clean[None, :]
    return np.where(
        k_c, np.where(q_c, k_b <= q_b, k_b < q_b), ~q_c & (k_b == q_b)
    )


def _mask(scores, window: Optional[int], pair: Optional[int]):
    if pair is None:
        return _causal_mask(scores, window)
    s_q, s_k = scores.shape[-2], scores.shape[-1]
    if s_q != s_k or s_q % 2:
        raise ValueError(f"a pair's scores are square, not [{s_q}, {s_k}]")
    return jnp.asarray(pair_mask(s_q // 2, pair))


def _grouped_attention(q, k, v, causal: bool, scale: float,
                       window: Optional[int] = None,
                       pair: Optional[int] = None):
    """``q`` [B, S, Hkv, G, D] against ``k``, ``v`` [B, S, Hkv, D]: K and
    V are read once a group, not repeated."""
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    if causal:
        scores = jnp.where(_mask(scores, window, pair), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)


NEG_INF = -1e30


def cached_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-step decode attention over a per-slot KV cache.

    ``q`` is one new query per slot — shape [B, 1, H, D] — attending over
    the first ``lengths[b]`` positions of its cache row ([B, T, H, D]).
    Positions at and beyond ``lengths[b]`` are masked, so stale pages from
    a previous occupant of the slot can never leak into a live sequence.
    T is the *cache-length bucket* chosen by the round loop, not the
    model's max_len — slicing the cache before calling keeps the score
    matrix O(B·T) per step.
    """
    scale = _scale(scale, q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * scale
    valid = jnp.arange(k_cache.shape[1])[None, :] < lengths[:, None]  # [B, T]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v_cache)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          scale: Optional[float] = None):
    """Per-device ring step. q/k/v local: [B, S_l, H, D]."""
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, s_l, h, d = q.shape
    scale = _scale(scale, d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = rank * s_l + jnp.arange(s_l)  # global query positions

    def scores_for(t, k_t):
        # After t rotations this device holds the block that started at
        # rank - t (mod n).
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_t) * scale
        if causal:
            src = jnp.mod(rank - t, n)
            k_pos = src * s_l + jnp.arange(s_l)
            mask = q_pos[:, None] >= k_pos[None, :]  # [S_l, S_kv]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        return scores

    # t=0: the device's own (diagonal) block seeds the accumulators —
    # this also makes every scan carry derive from varying inputs, which
    # shard_map's typed carries require.
    scores0 = scores_for(0, k)
    m = scores0.max(axis=-1)
    p0 = jnp.exp(scores0 - m[..., None])
    l = p0.sum(axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p0, v)
    k_t = jax.lax.ppermute(k, axis_name, perm)
    v_t = jax.lax.ppermute(v, axis_name, perm)

    def step(t, carry):
        k_t, v_t, m, l, o = carry
        scores = scores_for(t, k_t)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * correction + p.sum(axis=-1)
        o_new = o * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_t
        )
        k_next = jax.lax.ppermute(k_t, axis_name, perm)
        v_next = jax.lax.ppermute(v_t, axis_name, perm)
        return k_next, v_next, m_new, l_new, o_new

    _, _, m, l, o = jax.lax.fori_loop(1, n, step, (k_t, v_t, m, l, o))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axis: Optional[str] = "dp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention over an ICI ring.

    Inputs are globally shaped [B, S, H, D]; S must divide evenly by the
    ``axis_name`` mesh size. Returns the same global shape, sequence-
    sharded like the inputs.
    """
    batch = batch_axis if batch_axis and mesh.shape.get(batch_axis, 1) > 1 else None
    spec = P(batch, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, causal=causal,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool,
                   scale: Optional[float] = None):
    """all_to_all: [B, S/n, H, D] → [B, S, H/n, D], full attention, back."""
    # axis 1 (local seq) gathers; axis 2 (heads) scatters.
    def swap_in(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def swap_out(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    q_h, k_h, v_h = swap_in(q), swap_in(k), swap_in(v)
    out = reference_attention(q_h, k_h, v_h, causal=causal, scale=scale)
    return swap_out(out)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    batch_axis: Optional[str] = "dp",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Head-sharded (DeepSpeed-Ulysses-style) sequence parallelism: heads
    must divide by the sp mesh size."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by sp ({n})"
        )
    batch = batch_axis if batch_axis and mesh.shape.get(batch_axis, 1) > 1 else None
    spec = P(batch, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, causal=causal, scale=scale
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
