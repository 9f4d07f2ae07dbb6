"""Flash attention for TPU via Pallas — fused forward AND backward. Off the
TPU (the CPU test platform) the same API runs a plain XLA reference; on
the TPU a request the kernel cannot serve raises — nothing quietly takes
the O(S^2) path on the chip.

No reference-framework counterpart (the reference is DP-only and has no
attention ops; SURVEY.md §5 marks long-context as absent upstream) — this is
a capability extension required for long-context training.

Design: the standard blockwise online-softmax scheme over a
(batch*heads, q_blocks, k_blocks) grid. K/V stream through VMEM one
[block_k, D] tile at a time (the k index is the minormost grid axis, so
consecutive steps revisit the same q/output block while new K/V tiles DMA
in), running (max, sum, acc) live in VMEM scratch, and the S x S score
matrix never materializes — in EITHER pass:

- forward emits the per-row log-sum-exp as a residual, lane-replicated to
  [bh, S, 128] (the (8,128) tiling makes a plain 1-D row vector an illegal
  block; lane replication is the canonical TPU layout for row stats, cf.
  jax.experimental.pallas.ops.tpu.flash_attention's MIN_BLOCK_SIZE scratch).
- backward is one streaming kernel over (bh, k_blocks, q_blocks): each
  [block_q, block_k] tile of P is rebuilt once from the saved lse and
  feeds dv, dk AND dq (five products a tile). dk/dv of a k tile finish
  within its q loop; dq's sum runs over the outer k axis, so one
  batch*head's [S, D] float32 row of dq stays in VMEM for the row's grid
  steps and is written back once. Backward memory is O(S) + tiles, not
  O(S^2); the call asks for its VMEM (`_bwd_vmem_bytes`), and a row
  VMEM cannot hold raises, naming ring/Ulysses attention.
- delta = rowsum(dout * out) is precomputed in one cheap fused XLA
  elementwise pass and streamed like lse.

The mask is a hashable description handed in where the scores would be
masked: `False` (every position sees every other), `True` (causal) or
`BlockDiffusion(block, half)` (a clean and a noised copy of a record as
one sequence of 2 * half rows, see the class). One function,
`_tile_kinds`, tells a tile's kind at its grid step from the description
and `(i, j, block_q, block_k)`: skipped (pl.when; its index maps
re-address a resident tile, so no FLOPs and no DMA); whole, accumulated by
a body with no mask at all (every position is seen, and a select whose
predicate is all true returns its input); or crossed, masked. Under the
causal mask a crossed tile is crossed by the diagonal
(`causal_tile_kinds` counts the kinds from the shapes); with equal blocks
it lies on the diagonal itself and its mask is a constant of the trace,
which lets the compiler drop the score blocks above the diagonal from the
q k^T product. Under block diffusion a tile is crossed in one of three
ways (clean rows on their own tile of clean columns, noised rows on it,
noised rows on their own tile of noised columns:
`block_diffusion_tile_kinds`), each with equal blocks a constant too, and
a noised row's run set is not contiguous (`{0..i}` and `n + i`): the
index maps and the loops' ends follow the run set's segments
(`_k_segments`, `_q_segments`). Every causal kind gives the bits of
masking every tile whole, forward and backward. Under ring/Ulysses
sequence parallelism (parallel/ring_attention.py) the per-device S is the
block, so VMEM bounds the per-shard sequence, not the global one.
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Block-size sweep on TPU v5e (S=4096, bf16, causal fwd+bwd, D=64):
# 1024x1024 tiles run 5.49 ms/step vs 5.93 (512x512) and 6.76 (256x256),
# and 1.5x faster than the full-matrix XLA path (8.26 ms) — bigger tiles
# amortize grid overhead and fill the MXU; blocks auto-clamp to S for
# short sequences.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
LANES = 128  # lane replication for row statistics (lse, delta)
# What one backward call may ask of VMEM (a v5e core has 128 MiB; Mosaic's
# default scoped limit is 16 MiB, under what 1024x1024 float32 tiles need).
VMEM_BUDGET_BYTES = 96 * 2**20


def _use_pallas():
    """The kernel runs on the TPU, and on the CPU only under the
    test-only EDL_FORCE_PALLAS_INTERPRET switch. Every other backend
    gets `reference_attention` — which is what the CPU tests compare the
    kernel with, not a fallback the chip path may take."""
    if os.environ.get("EDL_FORCE_PALLAS_INTERPRET"):
        return True
    return jax.default_backend() == "tpu"


def _interpret():
    return bool(os.environ.get("EDL_FORCE_PALLAS_INTERPRET"))


def _per_batch_shard(fn):
    """`fn` (Pallas calls over [B, H, S, ...] arrays) as the SPMD
    partitioner can take it. A multi-device jit refuses a Mosaic kernel
    outright ("cannot be automatically partitioned"), so where the
    trace runs under a mesh (the trainer names it with
    `jax.sharding.use_abstract_mesh`) whose batch axes the partitioner
    still owns, each batch shard runs the kernel on its own rows inside
    a shard_map. No mesh, one device, or axes that an enclosing
    shard_map already made manual: `fn` as it is."""
    from jax.sharding import AxisType, PartitionSpec

    from elasticdl_tpu.parallel.mesh import batch_axes

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    axes = tuple(
        a
        for a in batch_axes(mesh)
        if mesh.shape[a] > 1 and types[a] != AxisType.Manual
    )
    if not axes:
        return fn
    spec = PartitionSpec(axes)
    return jax.shard_map(
        fn, in_specs=spec, out_specs=spec, axis_names=set(axes),
        check_vma=False,
    )


# ---------- the mask's description ----------


class BlockDiffusion(NamedTuple):
    """The block-diffusion training mask (Arriola et al. 2025, BD3-LM) over
    one sequence of 2 * `half` rows: rows [0, half) a clean copy of a
    record, rows [half, 2 half) its noised copy, both at positions 0 ..
    half - 1, in blocks of `block` positions (beta(p) = p // block). Row r
    at position p sees column c at position s iff
      r clean,  c clean:   beta(s) <= beta(p)   (block-causal)
      r noised, c clean:   beta(s) <  beta(p)   (the clean past)
      r noised, c noised:  beta(s) == beta(p)   (its own block, both ways)
      r clean,  c noised:  never.
    Every row sees its own block, so no softmax row is empty."""

    block: int
    half: int


def _unmasked(mask):
    return not isinstance(mask, BlockDiffusion) and not mask


def _check_mask(mask, s, block_q=None, block_k=None):
    """A description the sequence (and, where the kernel runs, its tiles)
    cannot carry raises."""
    if not isinstance(mask, BlockDiffusion):
        return
    if s != 2 * mask.half or mask.half % mask.block:
        raise ValueError(
            f"flash_attention: {mask} describes {2 * mask.half} rows in "
            f"whole blocks of {mask.block}, the sequence has {s}")
    for tile in (block_q, block_k):
        if tile is not None and (mask.half % tile or tile % mask.block):
            raise ValueError(
                f"flash_attention: a tile of {tile} rows is not whole "
                f"blocks of {mask.block} inside a half of {mask.half}")


def dense_mask(mask, s_q, s_k):
    """The [s_q, s_k] boolean mask a description stands for (None: no
    mask): what the XLA path applies and what the tests hold the tile
    kinds to."""
    if isinstance(mask, BlockDiffusion):
        _check_mask(mask, s_q)
        _check_mask(mask, s_k)
        at = jnp.arange(s_q)
        noised = at >= mask.half
        beta = (at - noised * mask.half) // mask.block
        (rn, cn), (rb, cb) = (
            (x[:, None], x[None, :]) for x in (noised, beta))
        return jnp.where(
            rn, jnp.where(cn, cb == rb, cb < rb), ~cn & (cb <= rb))
    if mask:
        return jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
    return None


# ---------- reference path (also the correctness oracle in tests) ----------


def reference_attention(q, k, v, mask=False):
    """[B, H, S, D] full attention in plain XLA."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seen = dense_mask(mask, scores.shape[-2], scores.shape[-1])
    if seen is not None:
        scores = jnp.where(seen, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


# ---------- shared tile helpers ----------


def _last_kj(i, block_q, block_k, num_k_blocks, causal):
    """Index of the last k tile the i-th q tile attends to."""
    if not causal:
        return num_k_blocks - 1
    return jnp.minimum(
        (((i + 1) * block_q - 1) // block_k), num_k_blocks - 1
    )


def _first_qi(j, block_q, block_k, causal):
    """Index of the first q tile that sees the j-th k tile."""
    if not causal:
        return 0
    return (j * block_k) // block_q


def _below_diagonal(i, j, block_q, block_k):
    """Tile (i, j) lies wholly below the causal diagonal: its last k
    position is seen by its first q row, so no score of it is masked."""
    return (j + 1) * block_k - 1 <= i * block_q


def causal_tile_kinds(s, block_q, block_k):
    """(run, below, crossed) tiles of one batch*head's causal grid: how
    many are not skipped, how many of those take the unmasked body and how
    many the masked one. A function of the shapes alone (10 / 6 / 4 at
    S 4096 and 36 / 28 / 8 at S 8192 over 1024 x 1024 tiles)."""
    run, whole, crossed = _count_tile_kinds(True, s, block_q, block_k)
    return run, whole, sum(crossed)


def block_diffusion_tile_kinds(half, block, block_q, block_k):
    """(run, whole, crossed) tiles of one batch*head's grid under
    `BlockDiffusion(block, half)`, `crossed` by kind: (clean rows on
    clean columns, noised rows on clean columns, noised rows on noised
    columns). 80 / 56 / (8, 8, 8) at half 8192, block 4 over 1024 x 1024
    tiles, of 256."""
    return _count_tile_kinds(
        BlockDiffusion(block, half), 2 * half, block_q, block_k)


def block_diffusion_scores(half, block, block_q=DEFAULT_BLOCK_Q,
                           block_k=DEFAULT_BLOCK_K):
    """(needed, run) scores of one batch*head under `BlockDiffusion(block,
    half)`: what the mask lets through, half * (half + block), and what
    the tiles that run hold, at the tiles the kernel takes for these
    blocks."""
    bq, bk = _clamp_blocks(
        2 * half, block_q, block_k, BlockDiffusion(block, half))
    run, _, _ = block_diffusion_tile_kinds(half, block, bq, bk)
    return half * (half + block), run * bq * bk


def _count_tile_kinds(mask, s, block_q, block_k):
    kinds = [
        _tile_kinds(mask, i, j, block_q, block_k)
        for i in range(s // block_q) for j in range(s // block_k)]
    whole = sum(bool(is_whole) for is_whole, _ in kinds)
    crossed = tuple(
        sum(bool(hit) for hit, _ in way)
        for way in zip(*(ways for _, ways in kinds)))
    return whole + sum(crossed), whole, crossed


def _causal_mask_scores(scores, i, j, block_q, block_k):
    """Mask the crossed score tile (i, j) above the causal diagonal. With
    equal blocks a crossed tile is i == j, its first row and first column
    the same position: the mask is then a constant of the trace, and the
    compiler drops what it makes dead (the score blocks above the
    diagonal: 0.8 us of a 1024 x 1024 tile's 5 in the forward, where the
    same mask with the offset read at run time saves nothing)."""
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    if block_q != block_k:
        row = row + (i * block_q - j * block_k)
    return jnp.where(row >= col, scores, NEG_INF)


def _block_mask_scores(scores, sees, block, p0, s0, block_q, block_k):
    """Mask a crossed score tile under block diffusion: a row sees a
    column iff `sees(column's block, row's block)`. `p0`, `s0` are the
    positions of the tile's first row and column; with equal tiles a
    crossed tile has them equal (a multiple of `block`), and the mask is
    a constant of the trace as the causal diagonal's is."""
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    if block_q != block_k:
        row, col = row + p0, col + s0
    if block & (block - 1) == 0:
        shift = block.bit_length() - 1
        row = jax.lax.shift_right_logical(row, shift)
        col = jax.lax.shift_right_logical(col, shift)
    else:
        row, col = jax.lax.div(row, block), jax.lax.div(col, block)
    return jnp.where(sees(col, row), scores, NEG_INF)


def _half_tile(mask, t, tile):
    """Tile t of `tile` rows under block diffusion: (0 clean or 1 noised,
    the positions of its first and last row, their blocks)."""
    noised = (t * tile) // mask.half
    first = t * tile - noised * mask.half
    last = first + tile - 1
    return noised, first, last, first // mask.block, last // mask.block


def _tile_kinds(mask, i, j, block_q, block_k):
    """The kind of tile (i, j) under the mask's description, as (whole,
    crossed): `whole` says that the tile runs and none of its scores is
    masked; `crossed` is a tuple of (hit, mask_scores) pairs, one a way of
    being crossed, `hit` saying that the tile runs masked that way and
    `mask_scores(scores)` masking it. A tile that is neither is skipped.
    i, j are grid indices (traced) or plain ints (the counts)."""
    if _unmasked(mask):
        return True, ()
    if not isinstance(mask, BlockDiffusion):
        # Crossed: not above the diagonal, not below it.
        hit = (j * block_k <= (i + 1) * block_q - 1) & (
            (j + 1) * block_k - 1 > i * block_q)
        return _below_diagonal(i, j, block_q, block_k), ((
            hit,
            lambda s: _causal_mask_scores(s, i, j, block_q, block_k)),)
    r_noised, p0, _, rb0, rb1 = _half_tile(mask, i, block_q)
    c_noised, s0, _, cb0, cb1 = _half_tile(mask, j, block_k)
    # (which halves, some score seen, every score seen, the relation).
    ways = (
        ((r_noised == 0) & (c_noised == 0), cb0 <= rb1, cb1 <= rb0,
         lambda c, r: c <= r),
        ((r_noised == 1) & (c_noised == 0), cb0 < rb1, cb1 < rb0,
         lambda c, r: c < r),
        ((r_noised == 1) & (c_noised == 1),
         (cb0 <= rb1) & (rb0 <= cb1), (cb0 >= rb1) & (cb1 <= rb0),
         lambda c, r: c == r),
    )
    whole = False
    crossed = []
    for halves, some, every, sees in ways:
        whole = whole | (halves & every)
        crossed.append((
            halves & some & (every == False),  # noqa: E712 (traced)
            functools.partial(
                _block_mask_scores, sees=sees, block=mask.block, p0=p0,
                s0=s0, block_q=block_q, block_k=block_k)))
    return whole, tuple(crossed)


def _k_segments(mask, i, block_q, block_k, num_k):
    """The k tiles that row tile i runs, as two segments ((first, last),
    (first, last)) in rising order; a run set of one segment gives it
    twice. Causal: 0 .. `_last_kj`. Block diffusion: a clean row runs the
    clean columns up to its own tile; a noised row the clean columns
    whose first block lies before its last one, then the noised tiles
    that hold its own positions."""
    if not isinstance(mask, BlockDiffusion):
        only = (0, _last_kj(i, block_q, block_k, num_k, mask))
        return only, only
    noised, p0, p1, _, rb1 = _half_tile(mask, i, block_q)
    n_half = mask.half // block_k
    clean = (0, jnp.where(
        noised == 1, ((rb1 - 1) * mask.block) // block_k, p1 // block_k))
    own = (n_half + p0 // block_k, n_half + p1 // block_k)
    return clean, tuple(
        jnp.where(noised == 1, a, b) for a, b in zip(own, clean))


def _q_segments(mask, j, block_q, block_k, num_q):
    """The q tiles that column tile j is run by, as `_k_segments` gives
    the k tiles of a row. Causal: `_first_qi` .. the last. Block
    diffusion: a clean column is run by the clean rows from its own
    position on and by the noised rows whose last block lies after its
    first one; a noised column by the noised tiles that hold its own
    positions."""
    if not isinstance(mask, BlockDiffusion):
        only = (_first_qi(j, block_q, block_k, mask), num_q - 1)
        return only, only
    noised, s0, s1, _, _ = _half_tile(mask, j, block_k)
    n_half = mask.half // block_q
    own = (n_half + s0 // block_q, n_half + s1 // block_q)
    clean_rows = (s0 // block_q, n_half - 1)
    noised_rows = (n_half + (s0 + mask.block) // block_q, num_q - 1)
    return (
        tuple(jnp.where(noised == 1, a, b)
              for a, b in zip(own, clean_rows)),
        tuple(jnp.where(noised == 1, a, b)
              for a, b in zip(own, noised_rows)),
    )


def _resident_k(j, segments):
    """The k tile that forward step j addresses: itself where it runs,
    else the run tile before it (already resident: no DMA), or the first
    run tile of all."""
    (f0, l0), (f1, l1) = segments
    return jnp.where(j >= f1, jnp.minimum(j, l1), jnp.clip(j, f0, l0))


def _resident_q(i, segments, num_q):
    """The q tile that backward step i addresses: itself where it runs,
    else the next run tile (fetched while the skipped steps pass), or the
    last run tile of all."""
    (f0, l0), (f1, l1) = segments
    at = jnp.where(i <= l0, jnp.maximum(i, f0), jnp.clip(i, f1, l1))
    # A clean column that no noised row runs has an empty second segment.
    return jnp.minimum(at, num_q - 1)


def _accumulate_by_kind(accumulate, kinds):
    """Call `accumulate(mask_scores)` as the tile's kind (`_tile_kinds`)
    asks: with None where the tile is whole, with the way's own mask where
    it is crossed, not at all where it is skipped."""
    from jax.experimental import pallas as pl

    whole, crossed = kinds
    if whole is True:
        accumulate(None)
        return
    pl.when(whole)(lambda: accumulate(None))
    for hit, mask_scores in crossed:
        pl.when(hit)(functools.partial(accumulate, mask_scores))


# ---------- forward kernel ----------


def _fwd_kernel(
    q_ref, k_ref, v_ref, *refs,
    block_q, block_k, num_k_blocks, mask, scale, emit_lse,
):
    from jax.experimental import pallas as pl

    if emit_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        lse_ref = None
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block (minormost: iterates fastest)
    last_j = _k_segments(mask, i, block_q, block_k, num_k_blocks)[1][1]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def accumulate(mask_scores):
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if mask_scores is not None:
            scores = mask_scores(scores)
        m_prev = m_scr[:, :1]  # [block_q, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # Tiles no row of which sees a column contribute nothing: skip. (The
    # k/v index maps follow the run set, so skipped steps re-address an
    # already-resident tile and cost no DMA either.)
    _accumulate_by_kind(
        accumulate, _tile_kinds(mask, i, j, block_q, block_k))

    @pl.when(j == last_j)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m + jnp.log(l_safe)
            lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _flash_forward(q, k, v, mask, block_q, block_k, emit_lse):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bh = b * h
    num_q, num_k = s // block_q, s // block_k
    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k,
        mask=mask,
        scale=d**-0.5,
        emit_lse=emit_lse,
    )

    def kv_index(b_, i, j):
        # Skipped steps address a run tile that is resident: an unchanged
        # block index between consecutive grid steps skips the DMA.
        if _unmasked(mask):
            return (b_, j, 0)
        at = _resident_k(j, _k_segments(mask, i, block_q, block_k, num_k))
        return (b_, jnp.maximum(at, 0), 0)

    out_specs = [
        pl.BlockSpec(
            (None, block_q, d), lambda b_, i, j: (b_, i, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    out_shape = [jax.ShapeDtypeStruct((bh, s, d), q.dtype)]
    if emit_lse:
        out_specs.append(
            pl.BlockSpec(
                (None, block_q, LANES), lambda b_, i, j: (b_, i, 0),
                memory_space=pltpu.VMEM,
            )
        )
        out_shape.append(
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32)
        )
    res = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec(
                (None, block_q, d), lambda b_, i, j: (b_, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, block_k, d), kv_index, memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (None, block_k, d), kv_index, memory_space=pltpu.VMEM
            ),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
        name=_kernel_name(mask, "flash_fwd"),
    )(
        q.reshape(bh, s, d), k.reshape(bh, s, d), v.reshape(bh, s, d)
    )
    if not emit_lse:
        return res[0].reshape(b, h, s, d), None
    out, lse = res
    # Keep the residual compact between passes: one lane is the value.
    return out.reshape(b, h, s, d), lse[:, :, 0].reshape(b, h, s)


# ---------- backward kernel ----------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, block_q, block_k, num_q_blocks, num_k_blocks, mask, scale,
):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)  # k block
    i = pl.program_id(2)  # q block (fastest)
    # dq's sum runs over j, the outer axis: the whole [S, D] row of this
    # batch*head stays in VMEM from the row's first grid step to its last.
    @pl.when((j == 0) & (i == 0))
    def _init_row():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def accumulate(mask_scores):
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:, :1]  # [block_q, 1]
        delta = delta_ref[:, :1]
        scores = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if mask_scores is not None:
            scores = mask_scores(scores)
        p = jnp.exp(scores - lse)  # [block_q, block_k]
        dv_scr[:] = dv_scr[:] + jnp.dot(
            p.T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + scale * jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_scr[rows, :] = dq_scr[rows, :] + scale * jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    # q tiles that see none of this k tile: skip. (The q-side index maps
    # follow the run set, so skipped steps cost no DMA.)
    _accumulate_by_kind(
        accumulate, _tile_kinds(mask, i, j, block_q, block_k))

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((j == num_k_blocks - 1) & (i == num_q_blocks - 1))
    def _finalize_row():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_vmem_bytes(s, d, block_q, block_k, itemsize):
    """VMEM the backward call asks for, from its shapes: the float32
    score-sized tiles (scores, p, dp, ds and the two transposes), the
    double-buffered input and output blocks, dq's row (scratch plus its
    double-buffered output block) and the dk/dv scratch."""
    tiles = 6 * block_q * block_k * 4
    blocks = 2 * (
        2 * (block_q + block_k) * d * itemsize  # q, dO; k, v
        + 2 * block_q * LANES * 4  # lse, delta
        + 2 * block_k * d * itemsize  # dk, dv
    )
    dq_row = s * d * (4 + 2 * itemsize)
    scratch = 2 * block_k * d * 4
    return tiles + blocks + dq_row + scratch


def _flash_backward(q, k, v, out, lse, g, mask, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bh = b * h
    num_q, num_k = s // block_q, s // block_k
    vmem_bytes = _bwd_vmem_bytes(s, d, block_q, block_k, q.dtype.itemsize)
    if vmem_bytes > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"flash_attention: the backward keeps one [{s}, {d}] float32 "
            f"row of dq in VMEM and would need {vmem_bytes >> 20} MiB of "
            f"it (budget {VMEM_BUDGET_BYTES >> 20} MiB); shard the "
            "sequence with ring or Ulysses attention "
            "(parallel/ring_attention.py, parallel/ulysses.py)"
        )

    q3, k3, v3 = (x.reshape(bh, s, d) for x in (q, k, v))
    g3 = g.reshape(bh, s, d)
    # delta = rowsum(dout * out): one fused elementwise+reduce XLA pass.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, s)
    lse_fat = jnp.broadcast_to(
        lse.reshape(bh, s)[:, :, None], (bh, s, LANES)
    )
    delta_fat = jnp.broadcast_to(delta[:, :, None], (bh, s, LANES))

    # Grid (bh, k, q): k-indexed tiles are major, q-indexed minor. Skipped
    # steps address the next q tile that runs.
    def q_index(b_, j, i):
        if _unmasked(mask):
            return (b_, i, 0)
        segments = _q_segments(mask, j, block_q, block_k, num_q)
        return (b_, _resident_q(i, segments, num_q), 0)

    def q_spec(width):
        return pl.BlockSpec(
            (None, block_q, width), q_index, memory_space=pltpu.VMEM
        )

    k_spec = pl.BlockSpec(
        (None, block_k, d), lambda b_, j, i: (b_, j, 0),
        memory_space=pltpu.VMEM,
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel,
            block_q=block_q,
            block_k=block_k,
            num_q_blocks=num_q,
            num_k_blocks=num_k,
            mask=mask,
            scale=d**-0.5,
        ),
        grid=(bh, num_k, num_q),
        in_specs=[
            q_spec(d), k_spec, k_spec, q_spec(d),
            q_spec(LANES), q_spec(LANES),
        ],
        out_specs=[
            # Indexed by the row alone: written back once a row.
            pl.BlockSpec(
                (None, s, d), lambda b_, j, i: (b_, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            k_spec,
            k_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=_interpret(),
        name=_kernel_name(mask, "flash_bwd"),
    )(q3, k3, v3, g3, lse_fat, delta_fat)

    return (
        dq.reshape(b, h, s, d),
        dk.reshape(b, h, s, d),
        dv.reshape(b, h, s, d),
    )


# ---------- public API with custom VJP ----------


def _kernel_name(mask, name):
    """The causal and unmasked calls keep their names; a call under block
    diffusion carries its own, so a trace tells them apart."""
    return f"bd_{name}" if isinstance(mask, BlockDiffusion) else name


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q, k, v, mask=False, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K
):
    """Attention over [B, H, S, D] under `mask`: False, True (causal) or a
    `BlockDiffusion`. Where the kernel runs, S must be a multiple of the
    (clamped) block sizes (ValueError otherwise)."""
    bq, bk = _clamp_blocks(q.shape[2], block_q, block_k, mask)
    if _pallas_ok(q.shape[2], bq, bk, mask):
        return _per_batch_shard(
            lambda q, k, v: _flash_forward(
                q, k, v, mask, bq, bk, emit_lse=False
            )[0]
        )(q, k, v)
    return _fallback_attention(q, k, v, mask)


def _fit_block(s, requested):
    """Largest block <= requested that divides S (halving down to 128), so
    raising the default block size never refuses a divisible-by-512
    sequence length."""
    b = min(requested, s)
    while b > 128 and s % b:
        b //= 2
    return b


def _clamp_blocks(s, block_q, block_k, mask=False):
    """Under block diffusion a tile lies inside one half."""
    if isinstance(mask, BlockDiffusion):
        s = mask.half
    return _fit_block(s, block_q), _fit_block(s, block_k)


def _pallas_ok(s, block_q, block_k, mask=False):
    """True: run the kernel. False: this backend has no kernel (see
    _use_pallas). A sequence the kernel cannot tile RAISES where the
    kernel is in use — on the chip nothing drops to the O(S^2) path in
    silence."""
    if not _use_pallas():
        _check_mask(mask, s)
        return False
    if s % block_q or s % block_k:
        raise ValueError(
            f"flash_attention: sequence length {s} is not a multiple of "
            f"its block sizes ({block_q}, {block_k}); pad S to a "
            "multiple of 128 (the kernel never falls back to full-matrix "
            "attention on the TPU)"
        )
    _check_mask(mask, s, block_q, block_k)
    return True


def _fwd(q, k, v, mask, block_q, block_k):
    bq, bk = _clamp_blocks(q.shape[2], block_q, block_k, mask)
    if _pallas_ok(q.shape[2], bq, bk, mask):
        out, lse = _per_batch_shard(
            lambda q, k, v: _flash_forward(
                q, k, v, mask, bq, bk, emit_lse=True
            )
        )(q, k, v)
        return out, (q, k, v, out, lse)
    out = _fallback_attention(q, k, v, mask)
    return out, (q, k, v, out, None)


def _bwd(mask, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    bq, bk = _clamp_blocks(q.shape[2], block_q, block_k, mask)
    if lse is not None:
        return _per_batch_shard(
            lambda *a: _flash_backward(*a, mask, bq, bk)
        )(q, k, v, out, lse, g)
    return _bwd_xla(q, k, v, out, g, mask)


def _fallback_attention(q, k, v, mask):
    """`reference_attention` as the kernel keeps its promise: whatever
    dtype crosses the boundary, scores and softmax run in float32 and the
    result is rounded to the operands' dtype once. (Kept below the kernels:
    jax 0.9.0 keys a compiled Mosaic call on the line numbers of its call
    stack.)"""
    out = reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), mask
    )
    return out.astype(q.dtype)


def _bwd_xla(q, k, v, out, g, mask):
    """Full-matrix XLA backward (backends without the kernel): scores recomputed,
    then dV = P^T g;  dP = g V^T;  dS = P * (dP - rowsum(g * out));
    dQ = dS K * scale;  dK = dS^T Q * scale. In float32 whatever the
    operands' dtype, each gradient rounded to its operand's once."""
    dtypes = q.dtype, k.dtype, v.dtype
    q, k, v, out, g = (x.astype(jnp.float32) for x in (q, k, v, out, g))
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seen = dense_mask(mask, scores.shape[-2], scores.shape[-1])
    if seen is not None:
        scores = jnp.where(seen, scores, NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)
    p = jnp.exp(scores - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g, v)
    delta = jnp.sum(g * out, axis=-1, keepdims=True)
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q) * scale
    return tuple(d.astype(t) for d, t in zip((dq, dk, dv), dtypes))


flash_attention.defvjp(_fwd, _bwd)
