"""The flash kernels under the block-diffusion mask (ops/flash_attention.py
`BlockDiffusion`): the description against the four-line definition by
brute force, the tile kinds and the grids' decode against the mask
itself, the kernels in interpret mode against the dense oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops.flash_attention import (
    BlockDiffusion,
    block_diffusion_tile_kinds,
    flash_attention,
    reference_attention,
)


def brute_force_mask(half, block):
    """Row r may attend to column c: the definition, pair by pair."""
    seen = np.zeros((2 * half, 2 * half), bool)
    for r in range(2 * half):
        for c in range(2 * half):
            r_noised, c_noised = r >= half, c >= half
            bp, bs = (r % half) // block, (c % half) // block
            if not r_noised and not c_noised:
                seen[r, c] = bs <= bp
            elif r_noised and not c_noised:
                seen[r, c] = bs < bp
            elif r_noised and c_noised:
                seen[r, c] = bs == bp
    return seen


SHAPES = [
    # half, block, block_q, block_k
    (32, 4, 8, 8), (32, 4, 16, 8), (32, 4, 8, 16), (32, 8, 8, 8),
    (48, 4, 8, 24), (64, 1, 16, 16), (64, 16, 32, 16), (24, 3, 6, 12),
    (32, 4, 32, 32), (32, 4, 4, 4),
]


@pytest.mark.parametrize("half,block", [(8, 1), (8, 2), (12, 3), (16, 4),
                                        (16, 16), (32, 4)])
def test_dense_mask_is_the_definition(half, block):
    got = np.asarray(fa.dense_mask(BlockDiffusion(block, half),
                                   2 * half, 2 * half))
    want = brute_force_mask(half, block)
    np.testing.assert_array_equal(got, want)
    assert want.any(axis=1).all()  # no softmax row is empty
    # Needed scores: half * (half + block).
    assert want.sum() == half * (half + block)


def test_a_description_the_sequence_cannot_carry_raises():
    q = jnp.zeros((1, 1, 64, 8))
    with pytest.raises(ValueError, match="describes 48 rows"):
        flash_attention(q, q, q, BlockDiffusion(4, 24))
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention(q, q, q, BlockDiffusion(5, 32))


def test_a_tile_that_straddles_blocks_raises(monkeypatch):
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    q = jnp.zeros((1, 1, 768, 8))
    with pytest.raises(ValueError, match="not whole blocks of 192"):
        flash_attention(q, q, q, BlockDiffusion(192, 384), 128, 128)


@pytest.mark.parametrize("half,block,block_q,block_k", SHAPES)
def test_tile_kinds_against_the_mask_itself(half, block, block_q, block_k):
    """A tile runs if any of its scores is seen, is whole if all are, is
    crossed otherwise, in the way its halves say; the crossed tile's mask
    is the definition's; skipped, whole and crossed partition the grid."""
    mask = BlockDiffusion(block, half)
    num_q, num_k = 2 * half // block_q, 2 * half // block_k
    seen = brute_force_mask(half, block).reshape(
        num_q, block_q, num_k, block_k).transpose(0, 2, 1, 3)
    some, every = seen.any(axis=(2, 3)), seen.all(axis=(2, 3))
    run, whole, crossed = block_diffusion_tile_kinds(
        half, block, block_q, block_k)
    assert (run, whole, sum(crossed)) == (
        some.sum(), every.sum(), (some & ~every).sum())
    scores = jnp.zeros((block_q, block_k), jnp.float32)
    for i in range(num_q):
        for j in range(num_k):
            is_whole, hits = fa._tile_kinds(mask, i, j, block_q, block_k)
            assert is_whole == every[i, j]
            assert sum(hits) == int(some[i, j] and not every[i, j])
            # The way a tile is crossed is the pair of halves it lies in.
            want_way = (i * block_q >= half) + (j * block_k >= half)
            masks = fa._way_masks(mask, i, j, block_q, block_k)
            for way, (hit, mask_scores) in enumerate(zip(hits, masks)):
                if hit:
                    assert way == want_way
                    kept = np.asarray(mask_scores(scores)) == 0.0
                    np.testing.assert_array_equal(kept, seen[i, j])


def walk_the_decode(mask, s, block_q, block_k, seen):
    """Walk both passes' grids, every step decoded as the index maps and
    the kernel bodies decode it: the tiles of the brute-force mask `seen`
    ([S, S]) some score of which is seen come up exactly once each and no
    other tile does, rows (by columns: columns) rising and a row's tiles
    rising, a row's first and last steps are told, and the steps listed
    as crossed are those whose tile holds a score that is not seen."""
    num_q, num_k = s // block_q, s // block_k
    tiled = seen.reshape(num_q, block_q, num_k, block_k)
    some, every = tiled.any(axis=(1, 3)), tiled.all(axis=(1, 3))
    for by_column, want, whole in ((False, some, every),
                                   (True, some.T, every.T)):
        run = fa._run_tiles(mask, s, block_q, block_k, by_column)
        steps = fa.grid_steps(mask, s, block_q, block_k)
        assert len(run.major) == len(run.offset) == steps
        major, minor, first, last = (
            np.asarray(x) for x in jax.jit(jax.vmap(lambda t: (
                fa._major_at(t, run), fa._minor_at(t, run),
                *fa._ends_at(t, run))))(
                    jnp.arange(steps, dtype=jnp.int32)))
        crossed = sorted(n for way in run.ways for n in way)
        assert crossed == [
            n for n in range(steps) if not whole[major[n], minor[n]]]
        # `argwhere` lists the run tiles once each, majors rising and
        # within a major its minors rising.
        np.testing.assert_array_equal(
            np.stack([major, minor], axis=1), np.argwhere(want))
        starts = np.r_[True, major[1:] != major[:-1]]
        np.testing.assert_array_equal(first, starts)
        np.testing.assert_array_equal(last, np.r_[starts[1:], True])
        # Every row (column) has a tile to zero and to write its sums at.
        assert set(major) == set(range(len(want)))


@pytest.mark.parametrize("half,block,block_q,block_k", SHAPES)
def test_the_decode_walks_the_run_tiles_alone(half, block, block_q, block_k):
    walk_the_decode(
        BlockDiffusion(block, half), 2 * half, block_q, block_k,
        brute_force_mask(half, block))


def test_tile_kinds_of_the_cell():
    """L 8192, b 4 over 1024 x 1024 tiles: 80 of 256 run, 56 whole, 8 + 8
    + 8 crossed; the needed scores are 80.0% of the run tiles'."""
    assert block_diffusion_tile_kinds(8192, 4, 1024, 1024) == (
        80, 56, (8, 8, 8))
    assert fa.causal_tile_kinds(16384, 1024, 1024) == (136, 120, 16)
    assert 8192 * (8192 + 4) == 67_141_632
    assert 80 * 1024 * 1024 == 83_886_080


@pytest.mark.parametrize("block_k,constant", [(128, True), (256, False)])
def test_the_block_masks_of_equal_tiles_are_constants_of_the_trace(
    block_k, constant
):
    mask = BlockDiffusion(4, 512)

    def crossed(s, i, j):
        return [
            mask_scores(s)
            for mask_scores in fa._way_masks(mask, i, j, 128, block_k)]

    jaxpr = jax.make_jaxpr(crossed)(
        jnp.zeros((128, block_k), jnp.float32), 1, 1).jaxpr
    # What the masks read of the grid indices.
    needed = set()
    for eqn in reversed(jaxpr.eqns):
        if any(v in needed or v in jaxpr.outvars for v in eqn.outvars):
            needed.update(v for v in eqn.invars if hasattr(v, "count"))
    read = any(v in needed for v in jaxpr.invars[1:])
    assert read != constant


def _qkv(seed, s, head, dtype, bh=2):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(1, bh, s, head)), dtype)
        for _ in range(4))


@pytest.mark.parametrize(
    "half,block,block_q,block_k",
    [(256, 4, 128, 128), (512, 4, 128, 128), (512, 32, 256, 128),
     (512, 4, 128, 256), (256, 128, 128, 128), (512, 8, 512, 512)],
    ids=["two_tiles", "four_tiles", "unequal_q", "unequal_k",
         "block_is_tile", "one_tile_a_half"],
)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_against_the_dense_oracle(
    monkeypatch, half, block, block_q, block_k, dtype
):
    """Forward, dq, dk, dv of the kernels (interpret mode) under the
    block-diffusion mask against plain XLA under the dense mask."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    mask = BlockDiffusion(block, half)
    q, k, v, g = _qkv(half + block, 2 * half, 64, dtype)

    def kernel(q, k, v):
        return flash_attention(q, k, v, mask, block_q, block_k)

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


def test_xla_path_takes_the_description():
    """Off the chip the same call is full attention under the dense mask,
    forward and backward (`_fallback_attention`, `_bwd_xla`)."""
    mask = BlockDiffusion(4, 32)
    q, k, v, g = _qkv(7, 64, 16, "float32")
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, mask), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: reference_attention(*a, mask), q, k, v)
    np.testing.assert_allclose(out, want, atol=1e-6)
    for a, b in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_kernels_carry_a_name_of_their_own_under_the_mask(monkeypatch):
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    assert fa._kernel_name(True, "flash_fwd") == "flash_fwd"
    assert fa._kernel_name(False, "flash_bwd") == "flash_bwd"
    assert fa._kernel_name(BlockDiffusion(4, 8), "flash_fwd") == (
        "bd_flash_fwd")
