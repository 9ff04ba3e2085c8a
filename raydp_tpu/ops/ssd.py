"""The Mamba-2 state-space scan in its chunked ("state-space dual") form
(Dao & Gu 2024, arXiv:2405.21060, listing 1), plain ``jax.numpy``.

Per head the recurrence is

    h_t = exp(dt_t · A) · h_{t-1} + dt_t · x_t ⊗ B_t        (P × N state)
    y_t = h_t · C_t + D · x_t

A sequence is cut into chunks of ``chunk`` tokens. Inside a chunk the
outputs are a masked matmul, ``(C Bᵀ ∘ L) X`` over the causal pairs with
``L[i, j] = exp(Σ_{j<k≤i} dt_k A)``; each chunk leaves a final state; a
short recurrence carries the states across the chunks of a sequence; and
the state a chunk was entered with reaches its outputs as
``C · state · decay``. The matmul operands are in the input's dtype with
float32 accumulation; ``dt``, ``A``, the cumulative log-decays, ``L`` and
the chunk states are float32. JAX differentiates it as written.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, B, C, D, chunk: int):
    """``x`` [b, s, h, p] head inputs, ``dt`` [b, s, h] positive step
    sizes (after the softplus), ``A`` [h] negative decay rates, ``B`` and
    ``C`` [b, s, g, n] with ``h`` a multiple of the groups ``g``, ``D``
    [h] the skip weight; ``s`` a multiple of ``chunk``. Returns ``y``
    [b, s, h, p] in ``x``'s dtype. The state before the first token is
    zero."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of chunk {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    c, r, f32, dtype = s // chunk, h // g, jnp.float32, x.dtype

    dt = dt.astype(f32)
    # Cumulative log-decay inside each chunk, inclusive: [b, c, g, r, q].
    a = (dt * A.astype(f32)).reshape(b, c, chunk, g, r)
    a = jnp.cumsum(jnp.moveaxis(a, 2, -1), axis=-1)
    xf = x.astype(f32).reshape(b, c, chunk, g, r, p)
    xd = xf * dt.reshape(b, c, chunk, g, r, 1)        # dt_t · x_t
    Bc = B.reshape(b, c, chunk, g, n)
    Cc = C.reshape(b, c, chunk, g, n)

    # Within a chunk: (C Bᵀ ∘ L) X over the causal pairs.
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.exp(jnp.where(
        causal, a[..., :, None] - a[..., None, :], -jnp.inf
    ))                                                # [b, c, g, r, q, q]
    scores = jnp.einsum(
        "bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32
    )
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcigrp",
        (scores[:, :, :, None] * L).astype(dtype), xd.astype(dtype),
        preferred_element_type=f32,
    )

    # Each chunk's final state, had it been entered with zero.
    to_end = jnp.exp(a[..., -1:] - a)                 # [b, c, g, r, q]
    states = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", Bc,
        (xd * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype),
        preferred_element_type=f32,
    )

    # The recurrence over the chunks of a sequence, in float32 on the
    # vector unit: the state each chunk is entered with.
    def carry(state, chunk_in):
        final, decay = chunk_in
        return decay[..., None, None] * state + final, state

    _, entered = jax.lax.scan(
        carry, jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(jnp.exp(a[..., -1]), 1, 0)),
    )
    y = y + jnp.einsum(
        "bcign,cbgrpn->bcigrp", Cc, entered.astype(dtype),
        preferred_element_type=f32,
    ) * jnp.moveaxis(jnp.exp(a), -1, 2)[..., None]

    y = y + D.astype(f32).reshape(g, r, 1) * xf
    return y.reshape(b, s, h, p).astype(dtype)
