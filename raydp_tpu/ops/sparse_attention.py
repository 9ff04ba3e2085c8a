"""Attention over a LEARNED selection of keys, as Pallas TPU kernels.

A small second attention, the INDEX BRANCH, scores every causal pair

    I[t, s] = Σ_j w[t, j] · ReLU(qI[t, j] · kI[s])          (s ≤ t)

(``qI`` [B, S, Hi, Di] index queries, ``kI`` [B, S, Di] ONE index key
head, ``w`` [B, S, Hi] float32 head weights); each query keeps the
``topk`` best-scored of its causal keys, ``S_t = {s ≤ t : I[t, s] ≥ τ_t}``
with ``τ_t`` the ``topk``-th largest of its row (every causal key where
there are fewer; under a tie at ``τ_t`` all tied keys), one set for all
heads; softmax attention runs over that set, and the index branch learns
from the attention it thinned:

    P_a[t, ·] = softmax over S_t of q_a[t]·k_g(a)[·]·scale,  o_a = P_a · v
    p = stop_gradient(mean_a P_a),   r[t, ·] = softmax over S_t of I[t, ·]
    kl[t] = Σ_{s ∈ S_t} p[t, s] · (log p[t, s] − log r[t, s])

(DeepSeek-V3.2-Exp's sparse attention and the sparse stage of its training
rule). :func:`sparse_attention` returns ``(o, kl, count)``; no gradient
passes through the selection, ``q``, ``k``, ``v`` take the output's
gradient alone and ``qI``, ``kI``, ``w`` that of ``kl`` alone
(``d kl / d I = r − p`` on the selected pairs).

THE MASK IS DATA, so no kernel can tell a dead tile from grid indices
beyond causality, and it is never an array in HBM: every kernel rebuilds a
tile's scores ``I`` from ``qI``, ``kI``, ``w`` (one product of ``Hi · Di``
a pair where the tile's own are ``2 · H · D``) and compares them with the
kept ``τ``. ALL query heads of a tile run in one grid step, so the mask is
made once a tile and the head mean ``p`` is a sum in registers. The key at
a query's threshold EQUALS ``τ``, so every kernel makes ``I`` by the one
:func:`_index_tile`, in ONE tile shape. On a TPU its bits are then the
selection's in every kernel (bf16 products are exact in float32, a pair's
64 of them are summed in one pass of the MXU and the heads in one order;
measured, PERF.md §6, and checked again by
``scripts/sparse_attention_on_chip.py``); the interpreter's float32
products do not promise that between two programs (XLA's CPU backend emits
a small product by what surrounds it), so off the chip the loops over
heads run one head a trip (:func:`_heads_a_trip`).

The kernels, all in [tq, tk] tiles with float32 accumulation and the
operands in their input dtype:

* ``index`` (:func:`_index_kernel`): ``I`` for one block of
  :data:`SELECT_ROWS` query rows against all keys, ``-inf`` outside the
  causal triangle, [rows, S] float32. Never [S, S]: the blocks of a
  sequence run one after the other (``lax.map``).
* ``select`` (:func:`_select_kernel`): a row's ``τ`` EXACTLY, by a
  bisection over the float's bits (32 counts of ``I ≥ candidate`` over
  the row in VMEM, no sort), the logsumexp of ``I`` over the selection
  and the selection's size.
* forward (:func:`_forward_kernel`): two passes over a query tile's key
  tiles, the rows' logsumexp first, then ``P`` normalised as it is made:
  ``o``, ``p`` and ``kl`` need no rescaling and no third pass.
* backward, ONE kernel (:func:`_backward_kernel`) where its resident
  gradients fit the chip's VMEM (:func:`backward_is_fused`, a rule on the
  call's own shapes: 16,384 tokens over 4 key-value heads of 128 and an
  index key of 64 keep 68 MiB of 128): a tile's index scores, mask, ``P``,
  ``dP`` and the index branch's ``dI`` are built once and feed all six
  gradients; ``dq``, ``dqI`` and ``dw`` accumulate over a query tile's key
  tiles, ``dk``, ``dv`` and ``dkI`` stay in VMEM for a batch row's whole
  walk and leave once. A longer call (32,768 tokens: 136 MiB) runs the
  PAIR: ``dq`` with ``dqI`` and ``dw`` (:func:`_dq_kernel`, key tiles
  innermost) and ``dk``, ``dv`` with ``dkI`` (:func:`_dkv_kernel`, query
  tiles innermost, tiles transposed as ``ops/flash_attention.py``'s), each
  rebuilding mask and probabilities for itself.

RESIDUALS (:data:`KEPT`, named for a checkpoint's policy as
``ops/flash_attention.KEPT`` are): the output in [B, H, S, D], the rows'
logsumexp as a dense [B, H, S], and THE SELECTION as ``τ`` [B, S] with the
selection's logsumexp of ``I`` [B, S]: the backward never ranks again, and
the one kernel takes all four as lane-dense rows (no padded ``[.., S, 1]``
column is built).

Compiled by Mosaic on a TPU; on any other backend the same kernel bodies
run in the Pallas interpreter (as ``ops/kda.py``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raydp_tpu.ops.flash_attention import vmem_bytes

NEG_INF = -1e30
_F32 = jnp.float32
_INT_MIN = np.int32(-2 ** 31)

KEPT = (
    "sparse_attention_out", "sparse_attention_lse", "sparse_attention_tau",
    "sparse_attention_index_lse",
)

# The [query rows, keys] tile of every kernel, and how many heads of a
# key-value group run in one trip of the attention kernels' loops over
# them: what measured fastest on a TPU v5e at 16,384 tokens, 32 heads over 4
# of 128, 16 index heads of 64 (PERF.md §6, PR 52).
BLOCK_Q, BLOCK_KV = 256, 1024
HEAD_UNROLL = 8
# The query rows whose scores are in HBM at a time, and the rows and columns
# the selection walks at a time in VMEM.
SELECT_ROWS = 512
SELECT_TILE_ROWS = 128
SELECT_CHUNK = 1024
_VMEM_LIMIT = 100 * 2 ** 20

# a [m, d] x b [n, d] -> [m, n], contracted on the operands' own last axes.
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    # bfloat16 operands go through the MXU as they are, whatever matmul
    # precision the caller's context asks of float32 products.
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=_F32
    )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block(block: int, s: int) -> int:
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence of {s} not in tiles of {block}")
    return block


def _params(*semantics: str):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT
    )


# ------------------------------------------------------------ the tile's mask

def _index_tile(qi, ki, w, relu_ref=None):
    """``I`` [tq, tk] of one tile from ``qi`` (a ref whose first axis is
    the index head, [Hi, tq, Di]), ``ki`` [tk, Di] and ``w`` [tq, Hi]. ONE
    definition for every kernel: the heads are summed in the same order
    over the same products, so a tile's mask is the selection's to the
    bit. ``relu_ref`` [Hi, tq, tk], where given, keeps every head's
    ``ReLU(qI_j · kI)`` for the branch's backward."""
    total = None
    for j in range(qi.shape[0]):
        relu = jnp.maximum(_dot(qi[j], ki, _NT), 0.0)
        if relu_ref is not None:
            relu_ref[j] = relu
        term = w[:, j:j + 1] * relu
        total = term if total is None else total + term
    return total


def _heads_a_trip(group: int) -> int:
    """How many of a group's heads run straight-line in one trip of the
    loop over them: :data:`HEAD_UNROLL` on a TPU, ONE in the interpreter.
    There XLA's CPU backend emits the float32 score product by what
    surrounds it, and with a second head's work beside it the last bit of
    ``I`` is no longer the selection's: a key AT its query's threshold
    drops out of a kernel's mask. The MXU's pass does not depend on its
    neighbours (module docstring)."""
    return 1 if _interpret() else math.gcd(group, HEAD_UNROLL)


def _over_heads(group: int, body, carry):
    """``body(i, carry)`` over a group's heads ``i``,
    :func:`_heads_a_trip` of them a trip of the loop (Mosaic unrolls a
    ``fori_loop`` wholly or not at all): within a trip one head's vector
    work runs beside the next one's products."""
    unroll = _heads_a_trip(group)

    def trip(n, carry):
        for u in range(unroll):
            carry = body(n * unroll + u, carry)
        return carry

    return jax.lax.fori_loop(0, group // unroll, trip, carry)


class _Heads:
    """A [1, Hi, tq, Di] block read a head at a time (a view of the block
    without its first axis would slice the padded last one)."""

    def __init__(self, ref):
        self.ref, self.shape = ref, ref.shape[1:]

    def __getitem__(self, j):
        return self.ref[0, j]


def _positions(q0, k0, shape, transposed: bool = False):
    q_axis = 1 if transposed else 0
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos, k_pos


def _selected(qi, ki, w, tau, q0, k0):
    """``(I, keep)`` of one tile: ``keep`` where the pair is causal and
    ``I ≥ τ`` of its query (``tau`` a [tq, 1] column)."""
    scores = _index_tile(qi, ki, w)
    q_pos, k_pos = _positions(q0, k0, scores.shape)
    return scores, jnp.logical_and(q_pos >= k_pos, scores >= tau)


def _selected_turned(qi, ki, w, tau, q0, k0, relu_ref=None):
    """:func:`_selected` with the keys down the rows ([tk, tq]; ``tau`` a
    [1, tq] row). The scores are made as every other kernel makes them and
    then TURNED: the key at the threshold EQUALS it, and the other
    contraction's last bit is not promised to."""
    scores = _index_tile(qi, ki, w, relu_ref).T
    q_pos, k_pos = _positions(q0, k0, scores.shape, transposed=True)
    return scores, jnp.logical_and(q_pos >= k_pos, scores >= tau)


# ------------------------------------------------------- index and selection

def _index_kernel(row0_ref, qi_ref, ki_ref, w_ref, o_ref, *, tq: int,
                  tk: int):
    """Grid (query tiles of the block, key tiles). ``row0_ref`` holds the
    block's first query position."""
    q0 = row0_ref[0] + pl.program_id(0) * tq
    k0 = pl.program_id(1) * tk
    live = k0 <= q0 + tq - 1

    @pl.when(live)
    def _scores():
        scores = _index_tile(qi_ref, ki_ref[...], w_ref[...])
        q_pos, k_pos = _positions(q0, k0, scores.shape)
        o_ref[...] = jnp.where(q_pos >= k_pos, scores, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)


def _ordered(bits):
    """The float's bits as an int32 that orders as the float does; its own
    inverse."""
    return bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF))


def _select_kernel(i_ref, tau_ref, lse_ref, count_ref, key_ref, *,
                   topk: int, chunk: int):
    """One tile of rows of ``I`` [rows, S] (``-inf`` where the pair is not
    causal): ``τ`` = the ``topk``-th largest of the row, ``-inf`` where
    the row has fewer; the logsumexp of ``I`` over ``I ≥ τ``; how many
    that are. The threshold is built bit by bit from the top, in the order
    of the floats' bits read as unsigned numbers: a bit stays set where at
    least ``topk`` of the row are at or above the candidate."""
    rows, s = i_ref.shape
    chunks = [(c, min(chunk, s - c)) for c in range(0, s, chunk)]
    lanes = min(128, chunk)

    def folded(x):
        # [rows, n] summed into [rows, lanes]: whole registers added, the
        # one reduction across lanes is left to the caller.
        parts = [x[:, c:c + lanes] for c in range(0, x.shape[1], lanes)]
        return functools.reduce(jnp.add, parts)

    peak = jnp.full((rows, 1), -jnp.inf, _F32)
    for c, n in chunks:
        x = i_ref[:, c:c + n]
        # -0.0 orders under +0.0 by its bits and equals it as a float.
        x = jnp.where(x == 0.0, 0.0, x)
        key_ref[:, c:c + n] = _ordered(
            jax.lax.bitcast_convert_type(x, jnp.int32)
        )
        peak = jnp.maximum(peak, x.max(axis=1, keepdims=True))

    def narrow(b, t_u):
        cand_u = t_u | jnp.left_shift(np.int32(1), 31 - b)
        cand = cand_u ^ _INT_MIN
        count = jnp.zeros((rows, lanes), _F32)
        for c, n in chunks:
            count = count + folded(
                jnp.where(key_ref[:, c:c + n] >= cand, 1.0, 0.0)
            )
        enough = count.sum(axis=1, keepdims=True) >= topk
        return jnp.where(enough, cand_u, t_u)

    t_u = jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros((rows, 1), jnp.int32)
    )
    tau = jax.lax.bitcast_convert_type(_ordered(t_u ^ _INT_MIN), _F32)
    # No bit set: the row (dead pairs and all) is shorter than topk.
    tau = jnp.where(t_u == 0, -jnp.inf, tau)
    total = jnp.zeros((rows, lanes), _F32)
    count = jnp.zeros((rows, lanes), _F32)
    for c, n in chunks:
        x = i_ref[:, c:c + n]
        keep = jnp.logical_and(x >= tau, x > -jnp.inf)
        total = total + folded(jnp.where(keep, jnp.exp(x - peak), 0.0))
        count = count + folded(jnp.where(keep, 1.0, 0.0))
    tau_ref[...] = tau
    lse_ref[...] = peak + jnp.log(total.sum(axis=1, keepdims=True))
    count_ref[...] = count.sum(axis=1, keepdims=True)


def index_scores(q_idx, k_idx, w, row0):
    """``I`` of the query rows ``row0 … row0 + R − 1`` against all keys:
    ``q_idx`` [Hi, R, Di], ``k_idx`` [S, Di], ``w`` [R, Hi]; [R, S]
    float32, ``-inf`` outside the causal triangle."""
    heads, rows, d = q_idx.shape
    s = k_idx.shape[0]
    tq, tk = _block(BLOCK_Q, rows), _block(BLOCK_KV, s)

    def last_kv(i, row0_ref):
        return (row0_ref[0] + i * tq + tq - 1) // tk

    return pl.pallas_call(
        functools.partial(_index_kernel, tq=tq, tk=tk),
        out_shape=jax.ShapeDtypeStruct((rows, s), _F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tq, s // tk),
            in_specs=[
                pl.BlockSpec((heads, tq, d), lambda i, j, r: (0, i, 0)),
                pl.BlockSpec(
                    (tk, d),
                    lambda i, j, r: (jnp.minimum(j, last_kv(i, r)), 0),
                ),
                pl.BlockSpec((tq, heads), lambda i, j, r: (i, 0)),
            ],
            out_specs=pl.BlockSpec((tq, tk), lambda i, j, r: (i, j)),
        ),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_index_scores",
    )(jnp.asarray(row0, jnp.int32).reshape(1), q_idx, k_idx, w)


def select_threshold(scores, topk: int):
    """``(τ, logsumexp of the selection, its size)``, each [R], of rows of
    scores [R, S] as :func:`index_scores` makes them."""
    rows, s = scores.shape
    tile = _block(SELECT_TILE_ROWS, rows)
    column = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    like = jax.ShapeDtypeStruct((rows, 1), _F32)
    tau, lse, count = pl.pallas_call(
        functools.partial(
            _select_kernel, topk=topk, chunk=min(SELECT_CHUNK, s)
        ),
        out_shape=(like, like, like),
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((tile, s), lambda i: (i, 0))],
        out_specs=(column, column, column),
        scratch_shapes=[pltpu.VMEM((tile, s), jnp.int32)],
        compiler_params=_params("parallel"),
        interpret=_interpret(),
        name="sparse_select",
    )(scores)
    return tau[:, 0], lse[:, 0], count[:, 0]


def index_select(qi_t, k_idx, w, topk: int):
    """``(τ, index_lse, count)``, each [B, S] float32, from ``qi_t`` [B,
    Hi, S, Di], ``k_idx`` [B, S, Di], ``w`` [B, S, Hi]: a block of
    :data:`SELECT_ROWS` query rows at a time, its scores under the scope
    ``index`` and its threshold under ``select``."""
    b, heads, s, d = qi_t.shape
    rows = _block(SELECT_ROWS, s)
    blocks = s // rows

    def one(n):
        at, row0 = n // blocks, (n % blocks) * rows
        with jax.named_scope("index"):
            scores = index_scores(
                jax.lax.dynamic_slice(
                    qi_t, (at, 0, row0, 0), (1, heads, rows, d)
                )[0],
                jax.lax.dynamic_index_in_dim(k_idx, at, 0, keepdims=False),
                jax.lax.dynamic_slice(
                    w, (at, row0, 0), (1, rows, heads)
                )[0],
                row0,
            )
        with jax.named_scope("select"):
            return select_threshold(scores, topk)

    tau, lse, count = jax.lax.map(one, jnp.arange(b * blocks))
    return tuple(a.reshape(b, s) for a in (tau, lse, count))


# ------------------------------------------------------- attention's kernels

def _forward_kernel(q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, tau_ref,
                    ilse_ref, o_ref, lse_ref, kl_ref, m_ref, l_ref, acc_ref,
                    kl_acc, *, tq: int, tk: int, scale: float):
    """Grid (b, query tiles, 2 passes, key tiles). Pass 0 walks the key
    tiles for every head's running maximum and sum; pass 1 walks them
    again with the rows' logsumexp known, so ``P`` is final as it is made:
    ``o += P·v``, ``p`` = the heads' mean, ``kl += Σ p (log p − log r)``."""
    qi, phase, kj = (pl.program_id(i) for i in (1, 2, 3))
    last = pl.num_programs(3) - 1
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads
    q0, k0 = qi * tq, kj * tk
    live = k0 <= q0 + tq - 1

    @pl.when(jnp.logical_and(phase == 0, kj == 0))
    def _start():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_and(phase == 1, kj == 0))
    def _between():
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        acc_ref[...] = jnp.zeros_like(acc_ref)
        kl_acc[...] = jnp.zeros_like(kl_acc)

    def tile():
        return _selected(
            _Heads(qi_ref), ki_ref[0], w_ref[0], tau_ref[0], q0, k0
        )

    @pl.when(jnp.logical_and(phase == 0, live))
    def _sums():
        _, keep = tile()

        def of_group(g, carry):
            k = k_ref[0, g]

            def head(i, carry):
                h = g * group + i
                s = jnp.where(
                    keep, _dot(q_ref[0, h], k, _NT) * scale, NEG_INF
                )
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                l_ref[h] = l_ref[h] * jnp.exp(m_prev - m_new) + jnp.exp(
                    s - m_new
                ).sum(axis=1, keepdims=True)
                m_ref[h] = m_new
                return carry

            return _over_heads(group, head, carry)

        jax.lax.fori_loop(0, kv_heads, of_group, 0)

    @pl.when(jnp.logical_and(phase == 1, live))
    def _attend():
        scores, keep = tile()

        def of_group(g, mean):
            k, v = k_ref[0, g], v_ref[0, g]

            def head(i, mean):
                h = g * group + i
                s = _dot(q_ref[0, h], k, _NT) * scale - lse_ref[0, h]
                p = jnp.exp(jnp.where(keep, s, NEG_INF))
                acc_ref[h] += _dot(p.astype(v.dtype), v)
                return mean + p

            return _over_heads(group, head, mean)

        mean = jax.lax.fori_loop(
            0, kv_heads, of_group, jnp.zeros(scores.shape, _F32)
        ) * (1.0 / heads)
        log_r = scores - ilse_ref[0]
        kl_acc[...] += jnp.where(
            mean > 0.0,
            mean * (jnp.log(jnp.maximum(mean, 1e-37)) - log_r), 0.0,
        ).sum(axis=1, keepdims=True)

    @pl.when(jnp.logical_and(phase == 1, kj == last))
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)
        kl_ref[0] = kl_acc[...]


def _index_backward(scores, keep, mean, ilse, g_kl, qi, ki, w, transposed):
    """The index branch's backward on one tile, given the heads' mean
    probability: ``dI = g_kl · (r − p)`` on the selected pairs, then
    through ``I = Σ_j w_j ReLU(z_j)``, ``z_j = qI_j · kI``. Yields per
    index head ``(dw_j as a column or row, dz_j)``."""
    r = jnp.exp(jnp.where(keep, scores - ilse, NEG_INF))
    d_scores = (r - mean) * g_kl
    for j in range(qi.shape[0]):
        if transposed:
            z, w_j = _dot(ki, qi[j], _NT), w[j:j + 1, :]
        else:
            z, w_j = _dot(qi[j], ki, _NT), w[:, j:j + 1]
        on = z > 0.0
        yield (jnp.where(on, d_scores * z, 0.0),
               jnp.where(on, d_scores * w_j, 0.0))


def _dq_kernel(q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, tau_ref, ilse_ref,
               g_ref, lse_ref, delta_ref, gkl_ref, dq_ref, dqi_ref, dw_ref,
               dq_acc, dqi_acc, dw_acc, *, tq: int, tk: int, scale: float):
    """Grid (b, query tiles, key tiles): ``dq`` of every head, and the
    index branch's ``dqI`` and ``dw``, accumulated over the key tiles."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads
    q0, k0 = qi * tq, kj * tk

    @pl.when(kj == 0)
    def _start():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(k0 <= q0 + tq - 1)
    def _accumulate():
        ki, w = ki_ref[0], w_ref[0]
        scores, keep = _selected(_Heads(qi_ref), ki, w, tau_ref[0], q0, k0)
        mean = jnp.zeros(scores.shape, _F32)
        for g in range(kv_heads):
            k, v = k_ref[0, g], v_ref[0, g]

            def head(i, mean):
                h = g * group + i
                s = _dot(q_ref[0, h], k, _NT) * scale - lse_ref[0, h]
                p = jnp.exp(jnp.where(keep, s, NEG_INF))
                dp = _dot(g_ref[0, h], v, _NT)
                ds = p * (dp - delta_ref[0, h])
                dq_acc[h] += _dot(ds.astype(k.dtype), k) * scale
                return mean + p

            mean = jax.lax.fori_loop(0, group, head, mean)
        mean = mean * (1.0 / heads)
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)
        d_w = jnp.zeros(dw_acc.shape, _F32)
        for j, (dw_j, dz) in enumerate(_index_backward(
                scores, keep, mean, ilse_ref[0], gkl_ref[0], _Heads(qi_ref), ki,
                w, False)):
            d_w = d_w + jnp.where(
                lane == j, dw_j.sum(axis=1, keepdims=True), 0.0
            )
            dqi_acc[j] += _dot(dz.astype(ki.dtype), ki)
        dw_acc[...] += d_w

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[:, :dw_ref.shape[2]]


def _dkv_kernel(q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref, wt_ref, tau_ref,
                ilse_ref, g_ref, lse_ref, delta_ref, gkl_ref, dk_ref, dv_ref,
                dki_ref,
                dk_acc, dv_acc, dki_acc, *, tq: int, tk: int, scale: float):
    """Grid (b, key tiles, query tiles), the tiles with the keys down the
    rows (``w``, ``τ``, the logsumexps, ``delta`` and ``g_kl`` arrive as
    rows): ``dk`` and ``dv`` of every key-value head and the index
    branch's ``dkI``, accumulated over the query tiles."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads
    q0, k0 = qi * tq, kj * tk

    @pl.when(qi == 0)
    def _start():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when(k0 <= q0 + tq - 1)
    def _accumulate():
        ki, w = ki_ref[0], wt_ref[0]
        scores, keep = _selected_turned(
            _Heads(qi_ref), ki, w_ref[0], tau_ref[0], q0, k0
        )
        mean = jnp.zeros(scores.shape, _F32)
        for g in range(kv_heads):
            k, v = k_ref[0, g], v_ref[0, g]

            def head(i, carry):
                mean, dk, dv = carry
                h = g * group + i
                q, go = q_ref[0, h], g_ref[0, h]
                s = _dot(k, q, _NT) * scale - lse_ref[0, h]
                p = jnp.exp(jnp.where(keep, s, NEG_INF))
                dv = dv + _dot(p.astype(go.dtype), go)
                ds = p * (_dot(v, go, _NT) - delta_ref[0, h])
                dk = dk + _dot(ds.astype(q.dtype), q) * scale
                return mean + p, dk, dv

            mean, dk, dv = jax.lax.fori_loop(
                0, group, head, (mean, dk_acc[g], dv_acc[g])
            )
            dk_acc[g], dv_acc[g] = dk, dv
        mean = mean * (1.0 / heads)
        d_ki = jnp.zeros(dki_acc.shape, _F32)
        for j, (_, dz) in enumerate(_index_backward(
                scores, keep, mean, ilse_ref[0], gkl_ref[0], _Heads(qi_ref), ki,
                w, True)):
            q_j = qi_ref[0, j]
            d_ki = d_ki + _dot(dz.astype(q_j.dtype), q_j)
        dki_acc[...] += d_ki

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        dki_ref[0] = dki_acc[...].astype(dki_ref.dtype)


def _backward_kernel(q_ref, k_ref, v_ref, kt_ref, qi_ref, qit_ref, ki_ref,
                     w_ref, tau_ref, ilse_ref, g_ref, lse_ref, delta_ref,
                     gkl_ref, dq_ref, dqi_ref, dw_ref, dk_ref, dv_ref,
                     dki_ref, dq_acc, dqi_acc, dw_acc, relu_ref, *, tq: int,
                     tk: int, scale: float):
    """All six gradients from ONE pass over a batch row's live tiles.

    Grid (b, query tiles, key tiles). A tile's quantities are built once:
    the index heads' ``ReLU(z_j)`` (kept in ``relu_ref`` for the branch's
    backward) and ``I`` by :func:`_index_tile`, queries down the rows as
    the selection made them; ``I`` turned, and from there on the keys down
    the rows ([tk, tq]: ``τ``, the logsumexps, ``delta`` and ``g_kl``
    arrive as rows): the mask, and per head ``p`` and ``ds``, which feed

    dv += p · g;  dk += scale · ds · q;  dqᵀ += scale · kᵀ · ds

    (``kt_ref`` the key tile laid out [d, tk], so dq's product is a plain
    one and comes out TRANSPOSED, [d, tq]); then ``dI`` from the heads'
    mean, turned back, and per index head

    dw_j += Σ_s dI · ReLU(z_j);  dqI_j += dz_j · kI;  dkIᵀ += qI_jᵀ · dz_j

    (``qit_ref`` the index queries laid out [Di, tq]). ``dq``, ``dqI`` and
    ``dw`` accumulate in scratch over the key tiles; ``dk``, ``dv`` and
    ``dkIᵀ`` ARE their float32 output blocks, a batch row's whole
    sequence resident in VMEM in ONE buffer: zeroed at the row's first
    step, written to HBM once after its last."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads
    q0, k0 = qi * tq, kj * tk

    @pl.when(jnp.logical_and(qi == 0, kj == 0))
    def _start_row():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(kj == 0)
    def _start():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(k0 <= q0 + tq - 1)
    def _accumulate():
        ki, w = ki_ref[0], w_ref[0]
        scores, keep = _selected_turned(
            _Heads(qi_ref), ki, w, tau_ref[0], q0, k0, relu_ref
        )

        def of_group(g, mean):
            k, v, kt = k_ref[0, g], v_ref[0, g], kt_ref[0, g]

            def head(i, carry):
                mean, dk, dv = carry
                h = g * group + i
                q, go = q_ref[0, h], g_ref[0, h]
                s = _dot(k, q, _NT) * scale - lse_ref[0, h]
                p = jnp.exp(jnp.where(keep, s, NEG_INF))
                dv = dv + _dot(p.astype(go.dtype), go)
                ds = (p * (_dot(v, go, _NT) - delta_ref[0, h])).astype(q.dtype)
                dk = dk + _dot(ds, q)
                dq_acc[h] += _dot(kt, ds)
                return mean + p, dk, dv

            zero = jnp.zeros(k.shape, _F32)
            mean, dk, dv = _over_heads(group, head, (mean, zero, zero))
            dk_ref[0, g, kj] += dk * scale
            dv_ref[0, g, kj] += dv
            return mean

        mean = jax.lax.fori_loop(
            0, kv_heads, of_group, jnp.zeros(scores.shape, _F32)
        ) * (1.0 / heads)
        r = jnp.exp(jnp.where(keep, scores - ilse_ref[0], NEG_INF))
        d_scores = ((r - mean) * gkl_ref[0]).T
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)
        d_w = jnp.zeros(dw_acc.shape, _F32)
        d_ki = jnp.zeros(dki_ref.shape[2:], _F32)
        for j in range(relu_ref.shape[0]):
            relu = relu_ref[j]
            d_w = d_w + jnp.where(
                lane == j, (d_scores * relu).sum(axis=1, keepdims=True), 0.0
            )
            dz = jnp.where(
                relu > 0.0, d_scores * w[:, j:j + 1], 0.0
            ).astype(ki.dtype)
            dqi_acc[j] += _dot(dz, ki)
            d_ki = d_ki + _dot(qit_ref[0, j], dz)
        dw_acc[...] += d_w
        dki_ref[0, kj] += d_ki

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0, :, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[:, :dw_ref.shape[2]]


# ------------------------------------------------------------------ the calls

def _specs(shapes, tq: int, tk: int, q_at, k_at, q_buffers=None):
    """Block specs of the kernels' shared operands, the query side's
    placed by ``q_at(*grid) -> (b, query tile)`` and the key side's by
    ``k_at``; ``q_buffers`` is the query side's ``pipeline_mode``."""
    (b, h, s, d), h_kv, h_i, d_i = shapes

    def q_side(block, form):
        return pl.BlockSpec(
            block, lambda *grid: form(*q_at(*grid)), pipeline_mode=q_buffers
        )

    def k_side(block, form):
        return pl.BlockSpec(block, lambda *grid: form(*k_at(*grid)))

    return {
        "q": q_side((1, h, tq, d), lambda b, i: (b, 0, i, 0)),
        "kv": k_side((1, h_kv, tk, d), lambda b, j: (b, 0, j, 0)),
        "qi": q_side((1, h_i, tq, d_i), lambda b, i: (b, 0, i, 0)),
        "ki": k_side((1, tk, d_i), lambda b, j: (b, j, 0)),
        "w": q_side((1, tq, h_i), lambda b, i: (b, i, 0)),
        "column": q_side((1, tq, 1), lambda b, i: (b, i, 0)),
        "columns": q_side((1, h, tq, 1), lambda b, i: (b, 0, i, 0)),
        "w_t": q_side((1, h_i, tq), lambda b, i: (b, 0, i)),
        "kv_t": k_side((1, h_kv, d, tk), lambda b, j: (b, 0, 0, j)),
        "qi_t": q_side((1, h_i, d_i, tq), lambda b, i: (b, 0, 0, i)),
        "row": q_side((1, 1, tq), lambda b, i: (b, 0, i)),
        "rows": q_side((1, h, 1, tq), lambda b, i: (b, 0, 0, i)),
    }


def _forward(qt, kt, vt, qi_t, k_idx, w, tau, ilse, scale: float):
    """``(o [B, H, S, D], lse [B, H, S], kl [B, S])`` of the kernels'
    layouts."""
    b, h, s, d = qt.shape
    tq, tk = _block(BLOCK_Q, s), _block(BLOCK_KV, s)
    spec = _specs(
        (qt.shape, kt.shape[1], qi_t.shape[1], qi_t.shape[3]), tq, tk,
        lambda b, i, p, j: (b, i),
        lambda b, i, p, j: (b, jnp.minimum(j, (i * tq + tq - 1) // tk)),
    )
    out, lse, kl = pl.pallas_call(
        functools.partial(_forward_kernel, tq=tq, tk=tk, scale=scale),
        out_shape=(
            jax.ShapeDtypeStruct(qt.shape, qt.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), _F32),
            jax.ShapeDtypeStruct((b, s, 1), _F32),
        ),
        grid=(b, s // tq, 2, s // tk),
        in_specs=[spec[n] for n in (
            "q", "kv", "kv", "qi", "ki", "w", "column", "column")],
        out_specs=(spec["q"], spec["columns"], spec["column"]),
        scratch_shapes=[
            pltpu.VMEM((h, tq, 1), _F32), pltpu.VMEM((h, tq, 1), _F32),
            pltpu.VMEM((h, tq, d), _F32), pltpu.VMEM((tq, 1), _F32),
        ],
        compiler_params=_params(
            "parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="sparse_attention_forward",
    )(qt, kt, vt, qi_t, k_idx, w, tau[..., None], ilse[..., None])
    return out, lse[..., 0], kl[..., 0]


def _fused_vmem_limit() -> int:
    # What the one-kernel backward may take: Mosaic sizes its own scratch
    # by what it is allowed, so the call is given all but a sixteenth of the
    # chip's VMEM, and the rule below compares with what the call is given.
    return 15 * vmem_bytes() // 16


# Mosaic's own temporaries are in no spec: room for this many [tk, tq]
# float32 tiles beside the blocks (a trip's scores, p, dp, ds, the heads'
# mean, I, r, dI and their turned copies). An allowance, not a count: the
# cell's call compiles and runs within it (PERF.md §6, PR 52).
_TILE_TEMPORARIES = 12


def _fused_layout(shapes, dtype, index_dtype):
    """The one-kernel backward's blocks at a call's shapes, the ONE place
    that knows them: ``((tq, tk), inputs, tiled, resident, scratch)``, an
    input ``(spec, dtype)`` in the order :func:`_backward_kernel` takes
    them, an output ``(spec, array)`` (the ``tiled`` ones, then the
    ``resident``), ``scratch`` arrays of VMEM. The call is built from it
    and :func:`fused_backward_vmem` adds it up.

    One buffer for what changes with the query tile alone (its fetch and
    write-back are a query row's, not a step's) and for ``dk``, ``dv``
    and ``dkIᵀ``, whose blocks are a batch row's whole sequence and ARE
    the accumulators; the pipeline's two for the keys."""
    (b, h, s, d), h_kv, h_i, d_i = shapes
    tq, tk = _block(BLOCK_Q, s), _block(BLOCK_KV, s)
    n_q, n_k = s // tq, s // tk
    like = jax.ShapeDtypeStruct
    once = pl.Buffered(1)
    spec = _specs(
        shapes, tq, tk, lambda b, i, j: (b, i),
        lambda b, i, j: (b, jnp.minimum(j, (i * tq + tq - 1) // tk)), once,
    )

    def whole_row(*block):
        return pl.BlockSpec(
            (1,) + block, lambda b, i, j: (b,) + (0,) * len(block),
            pipeline_mode=once,
        ), like((b,) + block, _F32)

    inputs = [(spec[name], of) for name, of in (
        ("q", dtype), ("kv", dtype), ("kv", dtype), ("kv_t", dtype),
        ("qi", index_dtype), ("qi_t", index_dtype), ("ki", index_dtype),
        ("w", _F32), ("row", _F32), ("row", _F32), ("q", dtype),
        ("rows", _F32), ("rows", _F32), ("row", _F32))]
    tiled = [
        (pl.BlockSpec((1, h, 1, d, tq), lambda b, i, j: (b, 0, i, 0, 0),
                      pipeline_mode=once), like((b, h, n_q, d, tq), dtype)),
        (spec["qi"], like((b, h_i, s, d_i), index_dtype)),
        (spec["w"], like((b, s, h_i), _F32)),
    ]
    resident = [
        whole_row(h_kv, n_k, tk, d), whole_row(h_kv, n_k, tk, d),
        whole_row(n_k, d_i, tk),
    ]
    scratch = [
        like((h, d, tq), _F32), like((h_i, tq, d_i), _F32),
        like((tq, max(128, h_i)), _F32), like((h_i, tq, tk), _F32),
    ]
    return (tq, tk), inputs, tiled, resident, scratch


def _padded(shape, dtype) -> int:
    """Bytes in VMEM of an array whose last two axes lie in (8 · 4 /
    itemsize, 128) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    *leading, rows, lanes = shape
    sublanes = 32 // itemsize
    return math.prod(leading) * (-(-rows // sublanes) * sublanes) * (
        -(-lanes // 128) * 128) * itemsize


def fused_backward_vmem(s: int, h: int, h_kv: int, d: int, h_i: int,
                        d_i: int, dtype) -> tuple:
    """(resident, needed) bytes of the one-kernel backward at a call's
    shapes, from the blocks the call is built with (:func:`_fused_layout`):
    ``resident`` a batch row's float32 ``dk``, ``dv`` [Hkv, S, d] and
    ``dkIᵀ`` [Di, S], which stay over all of the row's tiles; ``needed``
    every block in VMEM's tiles times its buffers, the scratch, and
    :data:`_TILE_TEMPORARIES`."""
    (tq, tk), inputs, tiled, resident, scratch = _fused_layout(
        ((1, h, s, d), h_kv, h_i, d_i), dtype, dtype
    )

    def held(spec, of):
        mode = spec.pipeline_mode
        return (2 if mode is None else mode.buffer_count) * _padded(
            spec.block_shape, of)

    kept = sum(math.prod(a.shape) * a.dtype.itemsize for _, a in resident)
    blocks = sum(held(spec, of) for spec, of in inputs) + sum(
        held(spec, a.dtype) for spec, a in tiled + resident)
    work = sum(_padded(a.shape, a.dtype) for a in scratch) + (
        _TILE_TEMPORARIES * _padded((tk, tq), _F32))
    return kept, blocks + work


def backward_is_fused(s: int, h: int, h_kv: int, d: int, h_i: int, d_i: int,
                      dtype) -> bool:
    """Whether a call's backward is the one kernel: the resident
    gradients and a step's work within what the call is given of the
    chip's VMEM (the cell's S = 16,384, Hkv = 4, d = 128, Di = 64 in bf16:
    68 MiB resident; S = 32,768: 136). What does not fit runs the dq and
    dk/dv kernels."""
    needed = fused_backward_vmem(s, h, h_kv, d, h_i, d_i, dtype)[1]
    return needed <= _fused_vmem_limit()


def _backward(qt, kt, vt, qi_t, k_idx, w, tau, ilse, out_t, lse, g_out,
              g_kl, scale: float):
    """``(dq, dk, dv, dqI, dkI, dw)`` in the operation's layouts, by the
    one kernel where :func:`backward_is_fused` says its resident blocks
    fit, else by the pair."""
    _, h, s, d = qt.shape
    h_kv, h_i, d_i = kt.shape[1], qi_t.shape[1], qi_t.shape[3]
    delta = jnp.einsum(
        "bhsd,bhsd->bhs", g_out.astype(_F32), out_t.astype(_F32)
    )
    rule = _backward_fused if backward_is_fused(
        s, h, h_kv, d, h_i, d_i, qt.dtype
    ) else _backward_pair
    return rule((qt.shape, h_kv, h_i, d_i), qt, kt, vt, qi_t, k_idx, w, tau,
                ilse, lse, g_out, delta, g_kl.astype(_F32), scale)


def _backward_fused(shapes, qt, kt, vt, qi_t, k_idx, w, tau, ilse, lse,
                    g_out, delta, g_kl, scale: float):
    """The backward as ONE kernel (:func:`_backward_kernel`). The keys
    and the index queries go in a second time with the sequence last;
    ``dq`` comes out a query tile at a time as [d, tq] and ``dk``,
    ``dv``, ``dkIᵀ`` in float32, a key tile at a time: each takes its way
    to the operation's layout and dtype in the one einsum every gradient
    ends with."""
    (b, h, s, d), h_kv, h_i, d_i = shapes
    (tq, tk), inputs, tiled, resident, scratch = _fused_layout(
        shapes, qt.dtype, qi_t.dtype
    )
    outputs = tiled + resident
    dq, dqi, dw, dk, dv, dki = pl.pallas_call(
        functools.partial(_backward_kernel, tq=tq, tk=tk, scale=scale),
        out_shape=tuple(array for _, array in outputs),
        grid=(b, s // tq, s // tk),
        in_specs=[spec for spec, _ in inputs],
        out_specs=tuple(spec for spec, _ in outputs),
        scratch_shapes=[pltpu.VMEM(a.shape, a.dtype) for a in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_fused_vmem_limit(),
        ),
        interpret=_interpret(),
        name="sparse_attention_backward",
    )(qt, kt, vt, jnp.swapaxes(kt, 2, 3), qi_t, jnp.swapaxes(qi_t, 2, 3),
      k_idx, w, tau[:, None, :], ilse[:, None, :], g_out,
      lse[:, :, None, :], delta[:, :, None, :], g_kl[:, None, :])
    heads_last = lambda x: jnp.einsum(  # noqa: E731
        "bhnkd->bnkhd", x).reshape(b, s, h_kv, d).astype(kt.dtype)
    return (
        jnp.einsum("bhndq->bnqhd", dq).reshape(b, s, h, d), heads_last(dk),
        heads_last(dv), jnp.einsum("bhsd->bshd", dqi),
        jnp.einsum("bndk->bnkd", dki).reshape(b, s, d_i).astype(k_idx.dtype),
        dw,
    )


def _backward_pair(shapes, qt, kt, vt, qi_t, k_idx, w, tau, ilse, lse, g_out,
                   delta, g_kl, scale: float):
    """The backward as two kernels, ``dq`` with ``dqI`` and ``dw`` and
    ``dk``, ``dv`` with ``dkI``, each building a tile's mask and
    probabilities for itself: what a call too long for
    :func:`_backward_fused`'s resident blocks runs."""
    (b, h, s, d), h_kv, h_i, d_i = shapes
    tq, tk = _block(BLOCK_Q, s), _block(BLOCK_KV, s)
    like = jax.ShapeDtypeStruct

    spec = _specs(
        shapes, tq, tk, lambda b, i, j: (b, i),
        lambda b, i, j: (b, jnp.minimum(j, (i * tq + tq - 1) // tk)),
    )
    dq, dqi, dw = pl.pallas_call(
        functools.partial(_dq_kernel, tq=tq, tk=tk, scale=scale),
        out_shape=(like(qt.shape, qt.dtype), like(qi_t.shape, qi_t.dtype),
                   like(w.shape, _F32)),
        grid=(b, s // tq, s // tk),
        in_specs=[spec[n] for n in (
            "q", "kv", "kv", "qi", "ki", "w", "column", "column", "q",
            "columns", "columns", "column")],
        out_specs=(spec["q"], spec["qi"], spec["w"]),
        scratch_shapes=[
            pltpu.VMEM((h, tq, d), _F32), pltpu.VMEM((h_i, tq, d_i), _F32),
            pltpu.VMEM((tq, max(128, h_i)), _F32),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_attention_dq",
    )(qt, kt, vt, qi_t, k_idx, w, tau[..., None], ilse[..., None], g_out,
      lse[..., None], delta[..., None], g_kl[..., None])

    # A query tile before the key tile's first live one names that tile.
    spec = _specs(
        shapes, tq, tk,
        lambda b, j, i: (b, jnp.maximum(i, (j * tk) // tq)),
        lambda b, j, i: (b, j),
    )
    dk, dv, dki = pl.pallas_call(
        functools.partial(_dkv_kernel, tq=tq, tk=tk, scale=scale),
        out_shape=(like(kt.shape, kt.dtype), like(vt.shape, vt.dtype),
                   like(k_idx.shape, k_idx.dtype)),
        grid=(b, s // tk, s // tq),
        in_specs=[spec[n] for n in (
            "q", "kv", "kv", "qi", "ki", "w", "w_t", "row", "row", "q",
            "rows", "rows", "row")],
        out_specs=(spec["kv"], spec["kv"], spec["ki"]),
        scratch_shapes=[
            pltpu.VMEM((h_kv, tk, d), _F32), pltpu.VMEM((h_kv, tk, d), _F32),
            pltpu.VMEM((tk, d_i), _F32),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_attention_dkv",
    )(qt, kt, vt, qi_t, k_idx, w, jnp.swapaxes(w, 1, 2), tau[:, None, :],
      ilse[:, None, :], g_out, lse[:, :, None, :], delta[:, :, None, :],
      g_kl[:, None, :])
    back = lambda x: jnp.einsum("bhsd->bshd", x)  # noqa: E731
    return back(dq), back(dk), back(dv), back(dqi), dki, dw


# ----------------------------------------------------------- the operation

def _heads_first(x):
    return jnp.einsum("bshd->bhsd", x)


def _run(q, k, v, q_idx, k_idx, w, topk: int, scale: float):
    qt, kt, vt, qi_t = (_heads_first(a) for a in (q, k, v, q_idx))
    tau, ilse, count = index_select(qi_t, k_idx, w, topk)
    with jax.named_scope("sparse"):
        out_t, lse, kl = _forward(
            qt, kt, vt, qi_t, k_idx, w, tau, ilse, scale
        )
    return (qt, kt, vt, qi_t, w), (out_t, lse, tau, ilse), kl, count


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sparse_attention(q, k, v, q_idx, k_idx, w, topk, scale):
    _, (out_t, *_), kl, count = _run(q, k, v, q_idx, k_idx, w, topk, scale)
    with jax.named_scope("sparse"):
        return jnp.einsum("bhsd->bshd", out_t), kl, count


def _sparse_attention_fwd(q, k, v, q_idx, k_idx, w, topk, scale):
    laid_out, kept, kl, count = _run(q, k, v, q_idx, k_idx, w, topk, scale)
    kept = tuple(checkpoint_name(a, n) for a, n in zip(kept, KEPT))
    with jax.named_scope("sparse"):
        out = jnp.einsum("bhsd->bshd", kept[0])
    return (out, kl, count), (laid_out, k_idx, kept)


def _sparse_attention_bwd(topk, scale, residuals, cotangents):
    (qt, kt, vt, qi_t, w), k_idx, (out_t, lse, tau, ilse) = residuals
    g_out, g_kl, _ = cotangents
    with jax.named_scope("sparse"):
        return _backward(
            qt, kt, vt, qi_t, k_idx, w, tau, ilse, out_t, lse,
            _heads_first(g_out), g_kl, scale,
        )


_sparse_attention.defvjp(_sparse_attention_fwd, _sparse_attention_bwd)


def sparse_attention(q, k, v, q_idx, k_idx, w, topk: int,
                     scale: Optional[float] = None):
    """Attention of ``q`` [B, S, H, D] over the keys the index branch
    selects (module docstring): ``k``, ``v`` [B, S, Hkv, D] (grouped
    heads), ``q_idx`` [B, S, Hi, Di], ``k_idx`` [B, S, Di], ``w`` [B, S,
    Hi]. Returns ``(o [B, S, H, D], kl [B, S] float32, count [B, S]
    float32)``: the attention, every query's divergence of the index
    branch's distribution from the heads' mean probability over its
    selection, and the selection's size (no gradient)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _sparse_attention(
        q, k, v, q_idx, k_idx, w.astype(_F32), int(topk), float(scale)
    )


def reference_sparse_attention(q, k, v, q_idx, k_idx, w, topk: int,
                               scale: Optional[float] = None):
    """The same function as dense ``jax.numpy`` over [S, S] arrays,
    float32, differentiable by autodiff with the two detachments written
    out: what the kernels are tested against at small sizes."""
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    f = lambda a: a.astype(_F32)  # noqa: E731
    group = h // k.shape[2]
    causal = jnp.tril(jnp.ones((s, s), bool))
    z = jnp.einsum("bthd,bsd->bhts", f(q_idx), f(k_idx))
    scores = jnp.einsum("bth,bhts->bts", f(w), jnp.maximum(z, 0.0))
    ranked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    tau = jnp.sort(ranked, axis=-1)[..., ::-1][..., min(topk, s) - 1]
    if topk > s:
        tau = jnp.full_like(tau, -jnp.inf)
    keep = jnp.logical_and(causal, ranked >= tau[..., None])
    logits = jnp.einsum(
        "bthd,bshd->bhts", f(q), jnp.repeat(f(k), group, axis=2)
    ) * scale
    probs = jax.nn.softmax(
        jnp.where(keep[:, None], logits, -jnp.inf), axis=-1
    )
    out = jnp.einsum(
        "bhts,bshd->bthd", probs, jnp.repeat(f(v), group, axis=2)
    )
    mean = jax.lax.stop_gradient(probs.mean(axis=1))
    log_r = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    kl = jnp.where(
        mean > 0.0,
        mean * (jnp.log(jnp.maximum(mean, 1e-37)) - log_r), 0.0,
    ).sum(axis=-1)
    return out.astype(q.dtype), kl, keep.sum(axis=-1).astype(_F32)
