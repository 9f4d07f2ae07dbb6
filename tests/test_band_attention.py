"""The flash kernels under a band (ops/flash_attention.py `Band`): the
description against its two-line definition by brute force, the tile kinds
and the grids' decode against the mask itself, the kernels in interpret
mode against the dense oracle, and a window that covers the sequence as
the causal mask's own bits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops.flash_attention import (
    Band,
    band_scores,
    flash_attention,
    reference_attention,
)
from test_block_diffusion_attention import _qkv, walk_the_decode


def brute_force_mask(s, window):
    """Row r may attend to column c: HF's `kv_idx <= q_idx` and `kv_idx >
    q_idx - sliding_window`, pair by pair."""
    seen = np.zeros((s, s), bool)
    for r in range(s):
        for c in range(s):
            seen[r, c] = c <= r and c > r - window
    return seen


def needed_scores(s, window):
    w = min(s, window)
    return w * (w + 1) // 2 + (s - w) * w


@pytest.mark.parametrize("s,window", [(16, 1), (16, 4), (24, 8), (32, 32),
                                      (32, 48), (40, 13)])
def test_dense_mask_is_the_definition(s, window):
    got = np.asarray(fa.dense_mask(Band(window), s, s))
    want = brute_force_mask(s, window)
    np.testing.assert_array_equal(got, want)
    assert want.diagonal().all()  # a row sees itself: no softmax row empty
    assert want.sum() == needed_scores(s, window)
    assert want.sum(axis=1).max() == min(s, window)


# s, window, tile
SHAPES = [(64, 8, 8), (64, 16, 8), (64, 24, 8), (96, 32, 16), (64, 32, 32),
          (128, 16, 16), (48, 24, 12)]


@pytest.mark.parametrize("s,window,tile", SHAPES)
def test_tile_kinds_against_the_mask_itself(s, window, tile):
    """A tile runs if any of its scores is seen, is whole if all are, is
    crossed otherwise: by the diagonal on it, by the far edge `window`
    before it; the crossed tile's mask is the definition's."""
    mask = Band(window)
    n = s // tile
    seen = np.asarray(fa.dense_mask(mask, s, s)).reshape(
        n, tile, n, tile).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(
        seen.reshape(n, n, -1),
        brute_force_mask(s, window).reshape(n, tile, n, tile).transpose(
            0, 2, 1, 3).reshape(n, n, -1))
    some, every = seen.any(axis=(2, 3)), seen.all(axis=(2, 3))
    run, whole, crossed = fa._count_tile_kinds(mask, s, tile, tile)
    assert (run, whole, sum(crossed)) == (
        some.sum(), every.sum(), (some & ~every).sum())
    # A row's run tiles: n + 1 of them once the window has filled.
    assert run == sum(min(i, window // tile) + 1 for i in range(n))
    scores = jnp.zeros((tile, tile), jnp.float32)
    for i in range(n):
        for j in range(n):
            is_whole, hits = fa._tile_kinds(mask, i, j, tile, tile)
            assert is_whole == every[i, j]
            assert sum(hits) == int(some[i, j] and not every[i, j])
            masks = fa._way_masks(mask, i, j, tile, tile)
            for way, (hit, mask_scores) in enumerate(zip(hits, masks)):
                if hit:
                    assert way == int(j != i)
                    kept = np.asarray(mask_scores(scores)) == 0.0
                    np.testing.assert_array_equal(kept, seen[i, j])


@pytest.mark.parametrize("s,window,tile", SHAPES)
def test_the_decode_walks_the_run_tiles_alone(s, window, tile):
    walk_the_decode(Band(window), s, tile, tile, brute_force_mask(s, window))


def test_tile_kinds_and_scores_of_the_cell():
    """S 16384 under a window of 1024: over 1024 x 1024 tiles 31 of 256
    run where the causal mask runs 136, none of them whole (16 crossed by
    the diagonal, 15 by the far edge); over 512 x 512 tiles 93, 31 whole.
    The needed scores are 50.0% and 66.7% of the run tiles'."""
    assert fa.grid_steps(Band(1024), 16384, 1024, 1024) == 31
    assert fa.grid_steps(True, 16384, 1024, 1024) == 136
    assert fa._count_tile_kinds(Band(1024), 16384, 1024, 1024) == (
        31, 0, (16, 15))
    assert fa._count_tile_kinds(Band(1024), 16384, 512, 512) == (
        93, 31, (32, 30))
    needed = 1024 * 1025 // 2 + (16384 - 1024) * 1024
    assert needed == needed_scores(16384, 1024) == 16_253_440
    assert band_scores(16384, 1024) == (needed, 31 * 1024 * 1024)
    assert band_scores(16384, 1024, 512, 512) == (needed, 93 * 512 * 512)
    # A window over the sequence: the causal half, in the causal tiles.
    assert band_scores(4096, 8192) == (4096 * 4097 // 2, 10 * 1024 * 1024)
    # 8.3 times fewer needed scores than the causal mask's.
    assert 16384 * 16385 / 2 / needed == pytest.approx(8.26, abs=0.01)


def test_the_bands_masks_are_constants_of_the_trace():
    def crossed(s, i, j):
        return [m(s) for m in fa._way_masks(Band(256), i, j, 128, 128)]

    jaxpr = jax.make_jaxpr(crossed)(
        jnp.zeros((128, 128), jnp.float32), 3, 1).jaxpr
    needed = set()
    for eqn in reversed(jaxpr.eqns):
        if any(v in needed or v in jaxpr.outvars for v in eqn.outvars):
            needed.update(v for v in eqn.invars if hasattr(v, "count"))
    assert not any(v in needed for v in jaxpr.invars[1:])


def test_a_window_the_tiles_cannot_carry_raises(monkeypatch):
    q = jnp.zeros((1, 1, 512, 8))
    with pytest.raises(ValueError, match="sees no position"):
        flash_attention(q, q, q, Band(0))
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="whole number of equal tiles"):
        flash_attention(q, q, q, Band(192), 128, 128)
    with pytest.raises(ValueError, match="whole number of equal tiles"):
        flash_attention(q, q, q, Band(256), 256, 128)
    # The blocks are fitted to the window as they are to a short sequence.
    assert fa._clamp_blocks(512, 1024, 1024, Band(128)) == (128, 128)
    assert fa._clamp_blocks(16384, 1024, 1024, Band(1024)) == (1024, 1024)
    assert fa._clamp_blocks(16384, 1024, 1024, Band(1536)) == (512, 512)


@pytest.mark.parametrize(
    "s,window,tile",
    [(512, 128, 128), (512, 256, 128), (768, 384, 128), (512, 256, 256)],
    ids=["window_is_tile", "two_tiles", "three_tiles", "one_tile_of_two"],
)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_against_the_dense_oracle(
    monkeypatch, s, window, tile, dtype
):
    """Forward, dq, dk, dv of the kernels (interpret mode) under the band
    against plain XLA under the dense mask."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    mask = Band(window)
    q, k, v, g = _qkv(s + window, s, 64, dtype)

    def kernel(q, k, v):
        return flash_attention(q, k, v, mask, tile, tile)

    def oracle(q, k, v):
        return reference_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), mask)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(oracle, q, k, v)
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), atol=tol, rtol=tol)
    for name, a, b in zip(
            ("dq", "dk", "dv"), vjp(g), want_vjp(g.astype(jnp.float32))):
        assert a.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol * 4, rtol=tol, err_msg=name)


@pytest.mark.parametrize("window", [512, 640, 4096])
def test_a_window_over_the_sequence_is_the_causal_mask_bit_for_bit(
    monkeypatch, window
):
    """`Band(w)` with w >= S runs the causal call itself: o, dq, dk, dv
    carry the same bits, and the call its causal name."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    q, k, v, g = _qkv(window, 512, 64, "bfloat16")
    out, vjp = jax.vjp(
        lambda *a: flash_attention(*a, Band(window), 128, 128), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: flash_attention(*a, True, 128, 128), q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for a, b in zip(vjp(g), want_vjp(g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fa._plain(Band(window), 512) is True
    assert fa._plain(Band(256), 512) == Band(256)


def test_xla_path_takes_the_description():
    """Off the chip the same call is full attention under the dense mask,
    forward and backward (`_fallback_attention`, `_bwd_xla`)."""
    mask = Band(12)
    q, k, v, g = _qkv(7, 64, 16, "float32")
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, mask), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: reference_attention(*a, mask), q, k, v)
    np.testing.assert_allclose(out, want, atol=1e-6)
    for a, b in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # A key 12 or more before a row moves nothing of it.
    moved = np.asarray(out - flash_attention(
        q, k.at[:, :, 3].add(1.0), v.at[:, :, 3].add(1.0), mask))
    assert np.abs(moved[:, :, 3:15]).max() > 1e-3
    assert np.abs(moved[:, :, 15:]).max() == 0.0
    assert np.abs(moved[:, :, :3]).max() == 0.0


def test_kernels_carry_a_name_of_their_own_under_the_band():
    assert fa._kernel_name(Band(8), "flash_fwd") == "band_flash_fwd"
    assert fa._kernel_name(Band(8), "flash_bwd") == "band_flash_bwd"
    assert fa._kernel_name(True, "flash_fwd") == "flash_fwd"
    assert fa._kernel_name(fa.BlockDiffusion(4, 8), "flash_fwd") == (
        "bd_flash_fwd")


def test_a_band_under_ring_or_ulysses_attention_raises():
    from elasticdl_tpu.parallel.ring_attention import (
        ring_attention,
        zigzag_ring_attention,
    )
    from elasticdl_tpu.parallel.ulysses import ulysses_attention

    q = jnp.zeros((1, 2, 16, 8))
    for attend in (ring_attention, zigzag_ring_attention, ulysses_attention):
        with pytest.raises(ValueError, match="not built"):
            attend(q, q, q, "seq", causal=Band(4))
