"""The flash kernels under block diffusion's PAIR mask, in the Pallas
interpreter at one or two tiles a quadrant, against dense masked softmax:
the output and the gradients of q, k and v over block lengths 1, 4, a
whole tile and S, grouped and equal head counts, through the one-kernel
backward and through the dq and dk/dv kernels; two closed forms; the tile
counts and the index maps' values (no dead tile is fetched, no noised key
is read); and, with ``causal`` and ``window`` arguments, the kernels' grids
and outputs bit-equal to the parent commit's."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.ops.attention import pair_mask, reference_attention

import importlib

fa = importlib.import_module("raydp_tpu.ops.flash_attention")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, TILE = 64, 32


def _inputs(s, h, h_kv, d=16, b=1, seed=47):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, 2 * s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, 2 * s, h_kv, d)).astype(np.float32)
    v = rng.standard_normal((b, 2 * s, h_kv, d)).astype(np.float32)
    w = rng.standard_normal((b, 2 * s, h, d)).astype(np.float32)
    return q, k, v, w


def _pair(length, tile=TILE):
    return functools.partial(
        fa.flash_pair_attention, block_length=length, interpret=True,
        block_q=tile, block_kv=tile)


def _out_and_grads(attend, q, k, v, w):
    """Output and the gradients of ``sum(out * w)`` from ONE jitted
    program (an interpreted kernel's compile is what a test here costs)."""
    def loss(q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


# ------------------------------------------------------- the mask itself

def test_the_mask_is_the_four_lines_of_its_definition():
    s, length = 12, 3
    mask = pair_mask(s, length)
    assert mask.shape == (24, 24) and mask.dtype == bool
    for i in range(24):
        for j in range(24):
            b_i, b_j = i % s // length, j % s // length
            if i < s and j < s:
                want = b_i == b_j
            elif i < s:
                want = b_j < b_i
            elif j < s:
                want = False
            else:
                want = b_j <= b_i
            assert mask[i, j] == want, (i, j)
    # S² + S·L pairs: S·L own block, S(S−L)/2 and S(S+L)/2.
    assert mask.sum() == s * s + s * length
    assert mask[:s, :s].sum() == s * length
    assert mask[:s, s:].sum() == s * (s - length) // 2
    assert mask[s:, s:].sum() == s * (s + length) // 2
    assert 8192 * 8192 + 8192 * 4 == 67_141_632     # the cell's, a head


def test_a_sequence_that_is_no_whole_number_of_blocks_is_refused():
    q, k, v, _ = _inputs(SEQ, 2, 2)
    with pytest.raises(ValueError, match="blocks of 5"):
        pair_mask(SEQ, 5)
    with pytest.raises(ValueError, match="blocks of 5"):
        _pair(5)(q, k, v)
    with pytest.raises(ValueError, match="blocks of 5"):
        reference_attention(q, k, v, causal=True, pair=5)
    with pytest.raises(ValueError, match="pair"):
        fa.flash_attention(q, k, v, causal=True, pair=4, interpret=True)
    two = [np.concatenate([t, t]) for t in (q, k, v)]
    with pytest.raises(ValueError, match="pair"):
        fa.flash_attention(*two, causal=False, pair=4, interpret=True)
    with pytest.raises(ValueError, match="pair"):
        fa.flash_attention(*two, causal=True, window=8, pair=4,
                           interpret=True)
    with pytest.raises(ValueError):
        reference_attention(q, k, v, causal=True, window=8, pair=4)


# ------------------------------------- kernels against dense masked softmax

@pytest.mark.parametrize("heads", [(4, 2), (2, 2)],
                         ids=["grouped", "equal_heads"])
@pytest.mark.parametrize("length", [1, 4, TILE, SEQ],
                         ids=["L1", "L4", "L_tile", "L_S"])
def test_output_and_gradients_are_dense_masked_softmaxs(length, heads):
    q, k, v, w = _inputs(SEQ, *heads)
    want, wants = _out_and_grads(functools.partial(
        reference_attention, causal=True, pair=length), q, k, v, w)
    got, grads = _out_and_grads(_pair(length), q, k, v, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    for g, want_g, name in zip(grads, wants, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(want_g), rtol=2e-4, atol=2e-5,
            err_msg=name)


@pytest.mark.parametrize("length", [4, 6], ids=["L4", "L6"])
def test_the_two_kernel_backward_gives_the_same_gradients(length):
    """What a call too long for the resident accumulators runs (S = 32,768
    a copy): the dq and dk/dv kernels under the same mask, with the
    cotangent of ``lse`` riding in ``delta``. L = 6 is no power of two:
    the block's start by division, not by a mask of bits."""
    s = 96
    q, k, v, w = _inputs(s, 4, 2, b=2)
    fold = lambda t: t.reshape((4, s) + t.shape[2:])  # noqa: E731
    scale = 16 ** -0.5
    w_lse = np.random.default_rng(1).standard_normal((4, 4, s)).astype(
        np.float32)
    @jax.jit
    def both_rules(q, k, v, g, g_lse):
        (_, lse), res = fa._flash_pair_fwd_rule(
            q, k, v, TILE, TILE, True, scale, length)
        rules = [rule(True, TILE, TILE, True, scale, None, res, g,
                      pair=length, g_lse=g_lse)
                 for rule in (fa._flash_bwd_fused, fa._flash_bwd_pair)]
        return lse, rules

    lse, (fused, two) = both_rules(fold(q), fold(k), fold(v), fold(w), w_lse)
    for a, b, name in zip(fused, two, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
    # Against autodiff of the dense form of the same call: clean keys
    # only, every row reading its pair's clean row, lse beside the output.
    def dense(q, k, v):
        copy = (jnp.arange(4) % 2)[:, None, None]
        pos = jnp.arange(s)
        limit = (pos // length + copy) * length              # [4, s, 1]
        see = pos[None, None, :] < limit[..., None].reshape(4, s, 1)
        kc, vc = k[1::2].repeat(2, 0), v[1::2].repeat(2, 0)
        sc = jnp.einsum("bqkgd,bskd->bkgqs",
                        q.reshape(4, s, 2, 2, 16), kc) * scale
        sc = jnp.where(see[:, None, None], sc, -1e30)
        lse = jax.nn.logsumexp(sc, -1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", jnp.exp(sc - lse[..., None]),
                         vc)
        return out.reshape(4, s, 4, 16), lse.reshape(4, 4, s)

    live = np.asarray(lse) > -1e29          # rows that see a clean key
    def loss(q, k, v):
        out, lse = dense(q, k, v)
        return jnp.sum(out * fold(w) * live.transpose(0, 2, 1)[..., None]
                       ) + jnp.sum(jnp.where(live, lse * w_lse, 0.0))
    masked_w = fold(w) * live.transpose(0, 2, 1)[..., None]
    _, (got, _) = both_rules(
        fold(q), fold(k), fold(v), masked_w, w_lse * live)
    want = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
        fold(q), fold(k), fold(v))
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    # No gradient reaches a noised key from the kernels.
    assert not np.asarray(got[1])[0::2].any()
    assert not np.asarray(got[2])[0::2].any()


# ------------------------------------------------------ two closed forms

def test_blocks_of_one_with_nothing_masked_are_causal_attention():
    """L = 1 and xᵗ = x⁰ (the two copies' q, k, v alike): a noised query
    sees itself and the clean tokens before it, so the noised half is the
    plain causal attention of the clean sequence, and the clean half too."""
    q, k, v, _ = _inputs(SEQ, 4, 2)
    half = lambda t: np.concatenate([t[:, SEQ:], t[:, SEQ:]], axis=1)  # noqa: E731
    q, k, v = half(q), half(k), half(v)
    want = reference_attention(
        q[:, SEQ:], k[:, SEQ:], v[:, SEQ:], causal=True)
    for attend in (_pair(1), functools.partial(
            reference_attention, causal=True, pair=1)):
        got = attend(q, k, v)
        np.testing.assert_allclose(np.asarray(got[:, :SEQ]),
                                   np.asarray(want), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(got[:, SEQ:]),
                                   np.asarray(want), rtol=2e-5, atol=2e-6)


def test_one_block_is_bidirectional_attention_over_the_noised_copy():
    """L = S: no block lies before another, so a noised query sees the
    whole noised copy and nothing else, and a clean query the whole clean
    copy."""
    q, k, v, _ = _inputs(SEQ, 4, 2)
    for attend in (_pair(SEQ), functools.partial(
            reference_attention, causal=True, pair=SEQ)):
        got = attend(q, k, v)
        for rows in (slice(0, SEQ), slice(SEQ, None)):
            want = reference_attention(q[:, rows], k[:, rows], v[:, rows])
            np.testing.assert_allclose(
                np.asarray(got[:, rows]), np.asarray(want), rtol=2e-5,
                atol=2e-6)


def test_the_own_block_merge_alone():
    """``merge_own_block`` with nothing to merge into (lse = -1e30) is a
    softmax over the block's own L keys."""
    rng = np.random.default_rng(3)
    q = rng.standard_normal((1, 5, 4, 2, 3, 8)).astype(np.float32)
    k = rng.standard_normal((1, 5, 4, 2, 8)).astype(np.float32)
    v = rng.standard_normal((1, 5, 4, 2, 8)).astype(np.float32)
    out = rng.standard_normal((1, 5, 4, 2, 3, 8)).astype(np.float32)
    lse = np.full((1, 5, 4, 2, 3), -1e30, np.float32)
    got = fa.merge_own_block(q, k, v, out, lse, 0.5)
    scores = np.einsum("bnlkgd,bnmkd->bnlkgm", q, k) * 0.5
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bnlkgm,bnmkd->bnlkgd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(jax.grad(
        lambda lse: jnp.sum(fa.merge_own_block(q, k, v, out, lse, 0.5))
    )(jnp.asarray(lse)))).all()


# ------------------------------------------- tile counts and index maps

def test_tile_counts_of_the_cells_shape():
    """S = 8,192 in 1024² tiles, L = 4: 36 live tiles over the clean keys
    a copy (8 crossed by the staircase each), where a causal call over the
    16,384 positions would compute 136; the noised copy's own-block term
    takes no tile (it runs beside the kernels)."""
    assert fa.pair_tile_counts(8192, 4) == (72, 16, 0)
    assert fa.tile_counts(16384) == (136, 16)
    # A whole tile a block: the clean copy's diagonal tiles are whole, the
    # noised copy's are dead, nothing is crossed.
    assert fa.pair_tile_counts(8192, 1024) == (36 + 28, 0, 0)
    # One block: the clean copy sees every tile, the noised copy none.
    assert fa.pair_tile_counts(8192, 8192) == (64, 0, 0)
    # Blocks of 1: the clean copy is plain causal attention.
    # Blocks of 1: the clean copy is plain causal attention, the noised
    # copy that less its diagonal entries (its diagonal tiles stay live).
    assert fa.pair_tile_counts(8192, 1) == (72, 16, 0)


@pytest.mark.parametrize("length", [1, 4, TILE, 2 * TILE, SEQ])
def test_no_dead_tile_is_fetched_and_no_noised_key_is_read(length):
    """The index maps' values over every grid step, on plain ints: a q
    tile's kv steps name its live tiles and then hold the last of them;
    a kv tile's q steps hold its first live q tile and then name the live
    ones; every key and value block comes from the pair's CLEAN row."""
    tiles = SEQ // TILE
    mask = pair_mask(SEQ, length)

    def live(copy, qi, ki):
        rows = slice(qi * TILE, (qi + 1) * TILE)
        cols = slice(SEQ + ki * TILE, SEQ + (ki + 1) * TILE)
        block = mask[SEQ:, :][rows, cols] if copy else mask[:SEQ][rows, cols]
        return bool(block.any())

    kv_at = fa._kv_block_of(None, TILE, TILE, lambda hi: hi // 2, length)
    for bi in range(4):     # two pairs
        copy = bi % 2
        for qi in range(tiles):
            assert all(
                bool(fa._pair_live(qi, ki, TILE, TILE, length, copy))
                == live(copy, qi, ki) for ki in range(tiles))
            fetched = []
            for step in range(tiles):
                row, head, tile, _ = kv_at(bi, 3, qi, step)
                assert (int(row), head) == (bi | 1, 1)
                fetched.append(int(tile))
            wanted = [ki for ki in range(tiles) if live(copy, qi, ki)]
            assert sorted(set(fetched)) == (wanted or [0])
            # Held, not re-fetched: once past its last live tile the map
            # names that tile again.
            assert fetched == sorted(fetched)
        for ki in range(tiles):
            first = int(fa._pair_first_q(ki, TILE, TILE, length, copy, tiles))
            seers = [qi for qi in range(tiles) if live(copy, qi, ki)]
            assert first == (seers[0] if seers else tiles - 1)
            assert seers == list(range(first, tiles))[:len(seers)]


def test_the_pair_call_is_two_pallas_calls_a_gradient():
    """Forward and the one-kernel backward; the batch dimension of both
    grids is the 2B folded rows and no grid spans 2·S."""
    q, k, v, w = _inputs(SEQ, 4, 2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(_pair(4)(*a) * w), argnums=(0, 1, 2)))(q, k, v)
    grids = _grids(jaxpr.jaxpr, [])
    tiles = SEQ // TILE
    assert grids == [(2, 4, tiles, tiles), (2, 2, 2, tiles, tiles)]


def _grids(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(int(g) for g in eqn.params["grid_mapping"].grid))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _grids(inner if hasattr(inner, "eqns") else inner.jaxpr,
                           out)
    return out


# ------------------------- causal and window calls are the calls they were

PARENT_CASES = {
    "causal_grouped": (16, 16, 4, 2, dict(causal=True)),
    "window": (16, 16, 4, 2, dict(causal=True, window=24)),
    "two_widths": (24, 16, 2, 2, dict(causal=True)),
    "full": (16, 16, 2, 1, dict(causal=False)),
}


@pytest.mark.parametrize("case", sorted(PARENT_CASES))
def test_without_the_pair_mask_the_kernels_give_the_parents_bits(case):
    """``tests/data/flash_attention_parent_pr46.npz``: output, the three
    gradients (through the one kernel and through the dq and dk/dv
    kernels) and the grids of these calls at PR 47's parent commit
    (5cf50fb). The pair mask went into the predicates every call passes
    through; with ``causal`` and ``window`` arguments every grid, index
    map and body is the one it was."""
    recorded = np.load(os.path.join(
        REPO, "tests", "data", "flash_attention_parent_pr46.npz"))
    d, d_v, h, h_kv, kw = PARENT_CASES[case]
    rng = np.random.default_rng(47)
    q = rng.standard_normal((2, 64, h, d)).astype(np.float32)
    k = rng.standard_normal((2, 64, h_kv, d)).astype(np.float32)
    v = rng.standard_normal((2, 64, h_kv, d_v)).astype(np.float32)
    w = rng.standard_normal((2, 64, h, d_v)).astype(np.float32)
    call = functools.partial(fa.flash_attention, block_q=32, block_kv=32,
                             interpret=True, **kw)
    out, grads = _out_and_grads(call, q, k, v, w)
    np.testing.assert_array_equal(np.asarray(out), recorded[f"{case}.out"])
    for g, name in zip(grads, "qkv"):
        np.testing.assert_array_equal(
            np.asarray(g), recorded[f"{case}.d{name}"], err_msg=name)
    window = kw.get("window")

    @jax.jit
    def two_kernels(q, k, v, w):
        _, res = fa._flash_fwd_rule(
            q, k, v, kw["causal"], 32, 32, True, d ** -0.5, window)
        return fa._flash_bwd_pair(
            kw["causal"], 32, 32, True, d ** -0.5, window, res, w)

    loss = lambda *a: jnp.sum(call(*a) * w)  # noqa: E731
    for g, name in zip(two_kernels(q, k, v, w), "qkv"):
        np.testing.assert_array_equal(
            np.asarray(g), recorded[f"{case}.two_kernels.d{name}"],
            err_msg=name)
    grids = _grids(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr, [])
    want = [tuple(int(x) for x in row[:4 + (i > 0)])
            for i, row in enumerate(recorded[f"{case}.grids"])]
    assert grids == want
