"""The Pallas flash attention kernels under a WINDOW and with q/k tiles of
one WIDTH and v tiles of another (interpret mode on the CPU), and the
window's cases of the both-backward-paths check. Split out of
``tests/test_attention.py`` (PR 53), cases unchanged."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_cases import _banded, check_both_backward_paths
from raydp_tpu.ops import flash_attention, reference_attention
from raydp_tpu.ops.flash_attention import (
    _flash_bwd_pair,
    _flash_fwd_rule,
    tile_counts,
)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("widths", [(192, 128), (24, 16), (16, 48)],
                         ids=lambda w: f"qk{w[0]}_v{w[1]}")
def test_flash_with_two_head_widths_matches_reference(widths, group, causal):
    """``q`` and ``k`` ``d_qk`` wide, ``v`` and the output ``d_v``: forward
    and all three gradients against dense attention, with dead, whole and
    crossed tiles (S = 128 in 32-wide tiles)."""
    d_qk, d_v = widths
    rng = np.random.default_rng(36)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 128, h, d)), jnp.float32)
    q, k, v, w = mk(2, d_qk), mk(2 // group, d_qk), mk(2 // group, d_v), mk(
        2, d_v)
    scale = d_qk ** -0.5 * 1.4159 ** 2

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=32, block_kv=32, interpret=True)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    out = flash(q, k, v)
    assert out.shape == (1, 128, 2, d_v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(plain(q, k, v)), rtol=1e-4, atol=1e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


def test_flash_with_equal_widths_is_the_call_it_was():
    """``d_qk = d_v``: the jaxpr of forward and backward is the one a call
    with no notion of a second width traces (every tile ``d`` wide)."""
    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                        interpret=True)), argnums=(0, 1, 2)))(q, q, q))
    assert "f32[1,2,128,16]" in text and ",24]" not in text
    assert text.count("pallas_call") == 2       # forward, backward


@pytest.mark.parametrize("group", [1, 6, 8])
@pytest.mark.parametrize("window", [16, 32, 48, 100],
                         ids=lambda w: f"w{w}")
def test_windowed_flash_matches_a_dense_banded_softmax(window, group):
    """Forward and all three gradients in 32-wide tiles at S = 256: a
    window smaller than a tile, equal to one, one and a half and three
    tiles wide; groups of 1, 6 and 8 query heads a key-value head."""
    rng = np.random.default_rng(38)
    heads = 8 if group == 8 else 6
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 256, h, 16)), jnp.float32)
    q, k, v, w = mk(heads), mk(heads // group), mk(heads // group), mk(heads)
    live, masked = tile_counts(256, 32, 32, window=window)
    assert 0 < masked <= live < tile_counts(256, 32, 32)[0]

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=32, block_kv=32, interpret=True)

    plain = lambda q, k, v: _banded(q, k, v, window)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference_attention(q, k, v, causal=True, window=window)),
        np.asarray(plain(q, k, v)), rtol=1e-5, atol=1e-6)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("blocks", [(32, 64), (64, 32)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_windowed_flash_in_unequal_tiles(blocks):
    """Bands whose first tile is not a whole number of the other kind's
    tiles away: forward and gradients at a window of 40."""
    rng = np.random.default_rng(39)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 256, h, 16)), jnp.float32)
    q, k, v, w = mk(4), mk(2), mk(2), mk(4)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=40,
                               block_q=blocks[0], block_kv=blocks[1],
                               interpret=True)

    plain = lambda q, k, v: _banded(q, k, v, 40)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(plain(q, k, v)),
        rtol=1e-4, atol=1e-5)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(grads(flash), grads(plain), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4,
            err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("window", [128, 4096])
def test_a_window_of_the_whole_sequence_is_plain_causal_bit_for_bit(window):
    """``window >= S`` excludes nothing: the call IS the causal call (the
    same jaxpr, the same bits), forward and backward."""
    rng = np.random.default_rng(40)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, 128, h, 16)), jnp.float32)
    q, k, v = mk(4), mk(2), mk(2)

    def run(**kw):
        fn = lambda q, k, v: jnp.sum(flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=32, block_kv=32, interpret=True,
            **kw) ** 2)
        return fn, jax.value_and_grad(fn, argnums=(0, 1, 2))(q, k, v)

    fn_w, (out_w, grads_w) = run(window=window)
    fn_c, (out_c, grads_c) = run()
    assert np.asarray(out_w) == np.asarray(out_c)
    for a, b in zip(grads_w, grads_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(jax.grad(fn_w))(q, k, v)) == str(
        jax.make_jaxpr(jax.grad(fn_c))(q, k, v))


@pytest.mark.parametrize("s,blocks,window", [
    (256, (32, 32), 16), (256, (32, 32), 32), (256, (32, 32), 33),
    (256, (32, 64), 100), (256, (64, 32), 48), (512, (128, 128), 128),
    (256, (32, 32), 255), (16384, (512, 512), 512),
])
def test_tile_counts_under_a_window_against_brute_force(s, blocks, window):
    """A tile is live where any of its (query, key) pairs is inside the
    band, masked where some but not all are: counted pair by pair on the
    tile's corners."""
    bq, bkv = blocks
    live = masked = 0
    for qi in range(s // bq):
        for ki in range(s // bkv):
            i = np.arange(qi * bq, (qi + 1) * bq)[:, None]
            j = np.arange(ki * bkv, (ki + 1) * bkv)[None, :]
            if s > 1024:      # corners are enough; the band is convex
                i, j = i[[0, -1]], j[:, [0, -1]]
            inside = (j <= i) & (j > i - window)
            live += bool(inside.any())
            masked += bool(inside.any() and not inside.all())
    assert tile_counts(s, bq, bkv, window=window) == (live, masked)


def test_tile_counts_of_the_sliding_layers_at_the_cells_shape():
    """S = 16,384 under a window of 512: the tiles are 512 wide by
    default, every q tile but the first has two live tiles, both crossed
    by an edge; the band's grid is 2 steps wide, not 32."""
    from raydp_tpu.ops.flash_attention import band_tiles

    assert tile_counts(16384, window=512) == (63, 63)
    assert band_tiles(16384, 512, 512, 512) == (2, 2)
    assert tile_counts(16384) == (136, 16)            # all positions: 1024²
    assert tile_counts(16384, window=16384) == tile_counts(16384)


def test_window_without_causal_is_refused():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        reference_attention(q, q, q, causal=False, window=8)


def test_windowed_kernels_fetch_only_the_band():
    """The grids' innermost dimensions span the band: the forward's grid is
    (1, h, 8, 2) and the one backward kernel's (1, h_kv, group, 8, 2) at
    S = 256, 32-wide tiles, a window of 32; the pair's are (1, h, 8, 2)
    for dq and (1, h_kv, 8, group x 2) for dk/dv; without a window the
    same shapes take 8 steps."""
    q = jnp.zeros((1, 256, 4, 16), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 16), jnp.float32)

    def found(fn, *args):
        return sorted(re.findall(
            r"grid=\(([\d, ]+)\)", str(jax.make_jaxpr(fn)(*args))))

    def grids(**kw):
        return found(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                            interpret=True, **kw)), argnums=(0, 1, 2)),
                     q, kv, kv)

    def pair(window):
        def both(q, k, v):
            _, res = _flash_fwd_rule(q, k, v, True, 32, 32, True, 0.25,
                                     window)
            return _flash_bwd_pair(True, 32, 32, True, 0.25, window, res, q)
        return found(both, q, kv, kv)

    assert grids(window=32) == sorted(["1, 4, 8, 2", "1, 2, 2, 8, 2"])
    assert grids() == sorted(["1, 4, 8, 8", "1, 2, 2, 8, 8"])
    assert pair(32) == sorted(["1, 4, 8, 2", "1, 4, 8, 2", "1, 2, 8, 4"])
    assert pair(None) == sorted(["1, 4, 8, 8", "1, 4, 8, 8", "1, 2, 8, 16"])


@pytest.mark.parametrize("scale", [2.0 ** -3, 128 ** -0.5],
                         ids=["scale_on_q", "scale_on_scores"])
@pytest.mark.parametrize("mask", [(True, 48)],
                         ids=["window48"])
@pytest.mark.parametrize("blocks", [(32, 32), (32, 64), (64, 32)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=lambda w: f"qk{w[0]}_v{w[1]}")
@pytest.mark.parametrize("group", [1, 4])
def test_both_backward_paths_match_reference_and_each_other(
        group, widths, blocks, mask, scale):
    """The one kernel through ``flash_attention`` (these shapes fit any
    VMEM) and the dq + dk/dv pair by its rule function, on the same
    residuals: each against dense attention's gradients, and the two
    against each other (dk and dv accumulate in the same order over the
    same tiles; dq's tile product is asked of the MXU the other way
    round)."""
    check_both_backward_paths(group, widths, blocks, mask, scale)
