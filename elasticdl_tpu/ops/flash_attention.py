"""Flash attention for TPU via Pallas — fused forward AND backward. Off the
TPU (the CPU test platform) the same API runs a plain XLA reference; on
the TPU a request the kernel cannot serve raises — nothing quietly takes
the O(S^2) path on the chip.

No reference-framework counterpart (the reference is DP-only and has no
attention ops; SURVEY.md §5 marks long-context as absent upstream) — this is
a capability extension required for long-context training.

Design: the standard blockwise online-softmax scheme over a
(batch*heads, run tiles) grid: the second axis enumerates the
[block_q, block_k] tiles the mask lets run and no other, by rows in the
forward. K/V stream through VMEM one [block_k, D] tile a step (within a
row consecutive steps revisit the same q/output block while new K/V tiles
DMA in), running (max, sum, acc) live in VMEM scratch, and the S x S score
matrix never materializes — in EITHER pass. q and k share one width (Dk,
which sets the scale Dk^-0.5) and v, the output and its cotangent another
(Dv): equal in every model but the latent-attention one, whose keys of 192
(128 without position + 64 rotary) meet values of 128 as one [block, 192]
operand; such calls carry `mla_` before their names:

- forward emits the per-row log-sum-exp as a residual, lane-replicated to
  [bh, S, 128] (the (8,128) tiling makes a plain 1-D row vector an illegal
  block; lane replication is the canonical TPU layout for row stats, cf.
  jax.experimental.pallas.ops.tpu.flash_attention's MIN_BLOCK_SIZE scratch).
- backward is one streaming kernel over the run tiles by columns: each
  [block_q, block_k] tile of P is rebuilt once from the saved lse and
  feeds dv, dk AND dq (five products a tile). dk/dv of a k tile finish
  within its column; dq's sum runs over the columns, so one
  batch*head's [S, D] float32 row of dq stays in VMEM for the row's grid
  steps and is written back once. Backward memory is O(S) + tiles, not
  O(S^2); the call asks for its VMEM (`_bwd_vmem_bytes`), and a row
  VMEM cannot hold raises, naming ring/Ulysses attention.
- delta = rowsum(dout * out) is precomputed in one cheap fused XLA
  elementwise pass and streamed like lse.

The mask is a hashable description handed in where the scores would be
masked: `False` (every position sees every other), `True` (causal),
`BlockDiffusion(block, half)` (a clean and a noised copy of a record as
one sequence of 2 * half rows, see the class) or `Band(window)` (causal,
and a row sees its last `window` positions alone, its own among them; a
window that covers the sequence IS `True`). One function,
`_tile_kinds`, tells a tile's kind from the description and the plain
ints `(i, j, block_q, block_k)`: not run; whole, accumulated by a body
with no mask at all (every position is seen, and a select whose predicate
is all true returns its input); or crossed, masked. At trace time
`_run_tiles` lists the tiles that run, in the order a pass takes them (by
rows forward, by columns backward: the orders the sums have always been
made in), each with its kind; the list's length is the grid's second axis
(`grid_steps`: 10 / 36 / 136 a batch*head at S 4096 / 8192 / 16384 under
the causal mask, 80 under `BlockDiffusion(4, 8192)`, 31 under `Band(1024)`
at S 16384, over 1024 x 1024 tiles), so no grid step is skipped and a
row's (column's) last run tile is followed at once by the next one's
first, its blocks fetched behind it. Index maps and kernel bodies read
the step's `(i, j)`, its kind and whether it starts or ends a row off that
one list, by arithmetic
(`_step_value`: a constant plus each change the step has reached;
`_at_any`; a division where every tile of the grid runs, as under
`False`), not through a table operand: the calls stay q, k, v -> o, lse
and q, k, v, dO, lse, delta -> dq, dk, dv. Under the causal mask a
crossed tile is crossed by the diagonal (`causal_tile_kinds` counts the
kinds from the shapes); with equal blocks it lies on the diagonal itself
and its mask is a constant of the trace, which lets the compiler drop the
score blocks above the diagonal from the q k^T product. Under block
diffusion a tile is crossed in one of three ways (clean rows on their own
tile of clean columns, noised rows on it, noised rows on their own tile
of noised columns: `block_diffusion_tile_kinds`), each with equal blocks
a constant too, and a noised row's run set is not contiguous (`{0..i}`
and `n + i`): the list holds it as it is. Under a band of n tiles
(`window = n * tile`, equal blocks: `_check_mask` refuses another where
the kernel runs) row i runs tiles i - n .. i: the last crossed by the
diagonal (the causal way mask), the first by the window's far edge, where
a row sees the columns `col_local > row_local` (a constant of the trace as
well), those between whole; at a window of one tile no run tile is whole.
Every causal kind gives the bits of masking every tile whole, forward and
backward. Under
ring/Ulysses sequence parallelism (parallel/ring_attention.py) the
per-device S is the block, so VMEM bounds the per-shard sequence, not the
global one.
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


class Band(NamedTuple):
    """The sliding-window mask: row r sees column c iff c <= r and r - c <
    `window` (HF's `kv_idx > q_idx - sliding_window`: the row's own
    position and the `window` - 1 before it)."""

    window: int


def _unmasked(mask):
    return not isinstance(mask, (BlockDiffusion, Band)) and not mask


def _plain(mask, s):
    """A band that covers the sequence is the causal mask itself."""
    if isinstance(mask, Band) and mask.window >= s:
        return True
    return mask


def _check_mask(mask, s, block_q=None, block_k=None):
    """A description the sequence (and, where the kernel runs, its tiles)
    cannot carry raises."""
    if isinstance(mask, Band):
        if mask.window < 1:
            raise ValueError(f"flash_attention: {mask} sees no position")
        tiles = {block_q, block_k} - {None}
        if len(tiles) > 1 or any(mask.window % tile for tile in tiles):
            raise ValueError(
                f"flash_attention: a window of {mask.window} is not a "
                f"whole number of equal tiles ({block_q}, {block_k})")
        return
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
    if isinstance(mask, Band):
        _check_mask(mask, s_q)
        ahead = (jnp.arange(s_q)[:, None] + (s_k - s_q)
                 - jnp.arange(s_k)[None, :])
        return (ahead >= 0) & (ahead < mask.window)
    if mask:
        return jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
    return None


# ---------- reference path (also the correctness oracle in tests) ----------


def reference_attention(q, k, v, mask=False):
    """Full attention in plain XLA: q, k [B, H, S, Dk], v [B, H, S, Dv]
    (a key width beside a value width: latent attention's 192 against
    128), scale Dk^-0.5, the result [B, H, S, Dv]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    seen = dense_mask(mask, scores.shape[-2], scores.shape[-1])
    if seen is not None:
        scores = jnp.where(seen, scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


# ---------- shared tile helpers ----------


def _below_diagonal(i, j, block_q, block_k):
    """Tile (i, j) lies wholly below the causal diagonal: its last k
    position is seen by its first q row, so no score of it is masked."""
    return (j + 1) * block_k - 1 <= i * block_q


def causal_tile_kinds(s, block_q, block_k):
    """(run, below, crossed) tiles of one batch*head under the causal
    mask: how many run, how many of those take the unmasked body and how
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


def band_scores(s, window, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """(needed, run) scores of one batch*head under `Band(window)`: what
    the mask lets through, W (W + 1) / 2 + (S - W) W at W = min(window, S),
    and what the tiles that run hold, at the tiles the kernel takes for
    these blocks."""
    mask = _plain(Band(window), s)
    bq, bk = _clamp_blocks(s, block_q, block_k, mask)
    w = min(window, s)
    return (w * (w + 1) // 2 + (s - w) * w,
            grid_steps(mask, s, bq, bk) * bq * bk)


def grid_steps(mask, s, block_q, block_k):
    """Steps a batch*head of either pass's grid: both enumerate the tiles
    that run under the mask's description, and nothing else (10 / 36 / 136
    at S 4096 / 8192 / 16384 causal, 80 under `BlockDiffusion(4, 8192)`,
    over 1024 x 1024 tiles). A function of the shapes alone."""
    return len(_run_tiles(mask, s, block_q, block_k).major)


def _count_tile_kinds(mask, s, block_q, block_k):
    run = _run_tiles(mask, s, block_q, block_k)
    crossed = tuple(len(steps) for steps in run.ways)
    return len(run.major), len(run.major) - sum(crossed), crossed


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


def _far_edge_mask_scores(scores):
    """Mask the score tile the far edge of a band crosses: with the window
    a whole number of equal tiles the tile's first column lies `window`
    before its first row, so a row sees the columns past its own place in
    the tile: a constant of the trace, as the diagonal's mask is."""
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return jnp.where(col > row, scores, NEG_INF)


def _block_mask_scores(scores, sees, mask, i, j, block_q, block_k):
    """Mask the crossed score tile (i, j) under block diffusion: a row sees
    a column iff `sees(column's block, row's block)`. With equal tiles a
    crossed tile's first row and first column are the same position (a
    multiple of `mask.block`), and the mask is a constant of the trace as
    the causal diagonal's is."""
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    if block_q != block_k:
        row = row + _half_tile(mask, i, block_q)[1]
        col = col + _half_tile(mask, j, block_k)[1]
    block = mask.block
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


# A row's block r sees a column's block c, by the halves they lie in: clean
# rows on clean columns, noised rows on clean, noised on noised.
_BLOCK_SEES = (
    lambda c, r: c <= r,
    lambda c, r: c < r,
    lambda c, r: c == r,
)


def _way_masks(mask, i, j, block_q, block_k):
    """A `mask_scores(scores)` for each way a tile can be crossed under the
    mask's description, for tile (i, j): traced (a grid step's tile, which
    the masks read with unequal blocks alone) or plain ints."""
    if _unmasked(mask):
        return ()
    if isinstance(mask, Band):
        # Equal tiles (`_check_mask`): by the diagonal, by the far edge.
        return (lambda s: _causal_mask_scores(s, i, j, block_q, block_k),
                _far_edge_mask_scores)
    if not isinstance(mask, BlockDiffusion):
        return (lambda s: _causal_mask_scores(s, i, j, block_q, block_k),)
    return tuple(
        functools.partial(
            _block_mask_scores, sees=sees, mask=mask, i=i, j=j,
            block_q=block_q, block_k=block_k)
        for sees in _BLOCK_SEES)


def _tile_kinds(mask, i, j, block_q, block_k):
    """The kind of tile (i, j), plain ints, under the mask's description,
    as (whole, crossed): `whole` says that the tile runs and none of its
    scores is masked; `crossed` holds one truth value a way of being
    crossed (`_way_masks`), saying that the tile runs masked that way. A
    tile that is neither does not run."""
    if _unmasked(mask):
        return True, ()
    if isinstance(mask, Band):
        # Equal tiles, the window n of them: row i runs tiles i - n .. i.
        n = mask.window // block_k
        return i - n < j < i, (j == i, j == i - n)
    if not isinstance(mask, BlockDiffusion):
        # Crossed: not above the diagonal, not below it.
        hit = (j * block_k <= (i + 1) * block_q - 1) and (
            (j + 1) * block_k - 1 > i * block_q)
        return _below_diagonal(i, j, block_q, block_k), (hit,)
    r_noised, _, _, rb0, rb1 = _half_tile(mask, i, block_q)
    c_noised, _, _, cb0, cb1 = _half_tile(mask, j, block_k)
    # (which halves, some score seen, every score seen), a way.
    ways = (
        (r_noised == 0 and c_noised == 0, cb0 <= rb1, cb1 <= rb0),
        (r_noised == 1 and c_noised == 0, cb0 < rb1, cb1 < rb0),
        (r_noised == 1 and c_noised == 1,
         cb0 <= rb1 and rb0 <= cb1, cb0 >= rb1 and cb1 <= rb0),
    )
    return (
        any(halves and every for halves, _, every in ways),
        tuple(halves and some and not every for halves, some, every in ways))


class _Run(NamedTuple):
    """The tiles a pass runs, one entry a grid step: plain ints of the
    trace."""

    major: tuple  # the step's row (by column: its column)
    offset: tuple  # its k tile (by column: its q tile) less the step
    ways: tuple  # for each way of being crossed, the steps crossed so
    width: int  # where every tile of the grid runs, a row's tiles; else 0


@functools.lru_cache(maxsize=None)
def _run_tiles(mask, s, block_q, block_k, by_column=False):
    """The tiles that run under the mask's description, in the order a
    pass's grid takes them. The forward goes by rows: (i, j), row i rising
    and within it its k tiles rising, the order of a row's online softmax.
    The backward goes `by_column`: (j, i), column j rising and within it
    its q tiles rising, the order dk, dv and dq's rows are summed in."""
    num_q, num_k = s // block_q, s // block_k
    tiles = []
    for i in range(num_q):
        for j in range(num_k):
            whole, crossed = _tile_kinds(mask, i, j, block_q, block_k)
            if whole or any(crossed):
                tiles.append(((j, i) if by_column else (i, j)) + (crossed,))
    tiles.sort()
    ways = tuple(zip(*(crossed for _, _, crossed in tiles)))
    return _Run(
        major=tuple(major for major, _, _ in tiles),
        offset=tuple(minor - n for n, (_, minor, _) in enumerate(tiles)),
        ways=tuple(
            tuple(n for n, hit in enumerate(way) if hit) for way in ways),
        width=(num_q if by_column else num_k)
        if len(tiles) == num_q * num_k else 0,
    )


def _step_value(t, values):
    """`values[t]` at the traced grid step t, `values` plain ints of the
    trace: the first of them plus every later change that t has reached.
    Arithmetic over constants, so that neither an index map nor a kernel
    needs a table operand (the benchmark knows the calls by their operand
    counts). In `lax` primitives: a `jnp` operation on a tracer is a
    nested jit to trace, and a step's kernels hold thousands of these."""
    at = np.int32(values[0])
    for n in range(1, len(values)):
        if values[n] != values[n - 1]:
            at = lax.add(at, lax.select(
                lax.ge(t, np.int32(n)),
                np.int32(values[n] - values[n - 1]), np.int32(0)))
    return at


def _major_at(t, run):
    """The row (by column: the column) of grid step t: it changes where a
    row's run tiles end. Where every tile runs it is a division."""
    if run.width:
        return lax.div(t, np.int32(run.width))
    return _step_value(t, run.major)


def _minor_at(t, run):
    """The k tile (by column: the q tile) of grid step t. Inside a segment
    of a run set it rises with t, so it is t plus a value that changes
    only where a segment starts."""
    if run.width:
        return lax.rem(t, np.int32(run.width))
    return lax.add(t, _step_value(t, run.offset))


def _at_any(t, steps):
    return functools.reduce(
        lax.bitwise_or, (lax.eq(t, np.int32(n)) for n in steps))


def _ends_at(t, run):
    """(first, last): whether grid step t is the first, the last run tile
    of its row (by column: its column)."""
    if run.width:
        minor = _minor_at(t, run)
        return (lax.eq(minor, np.int32(0)),
                lax.eq(minor, np.int32(run.width - 1)))
    majors = run.major
    firsts = [n for n, m in enumerate(majors) if n == 0 or majors[n - 1] != m]
    lasts = [n - 1 for n in firsts[1:]] + [len(majors) - 1]
    return _at_any(t, firsts), _at_any(t, lasts)


def _accumulate_by_kind(accumulate, t, ways, masks):
    """Call `accumulate(mask_scores)` as the kind of grid step t's tile
    asks: with the way's own mask (`_way_masks`) at the steps `ways` lists
    for it, with None at every other step: the tile is whole there."""
    from jax.experimental import pallas as pl

    hits = [
        (_at_any(t, steps), mask_scores)
        for steps, mask_scores in zip(ways, masks) if steps]
    if not hits:
        accumulate(None)
        return
    crossed = functools.reduce(lax.bitwise_or, (hit for hit, _ in hits))
    pl.when(lax.bitwise_not(crossed))(lambda: accumulate(None))
    for hit, mask_scores in hits:
        pl.when(hit)(functools.partial(accumulate, mask_scores))


# ---------- forward kernel ----------


def _fwd_kernel(
    q_ref, k_ref, v_ref, *refs,
    block_q, block_k, run, mask, scale, emit_lse,
):
    from jax.experimental import pallas as pl

    if emit_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
        lse_ref = None
    t = pl.program_id(1)  # the t-th run tile, by rows
    i, j = _major_at(t, run), _minor_at(t, run)  # q block, k block
    first, last = _ends_at(t, run)

    @pl.when(first)
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

    _accumulate_by_kind(
        accumulate, t, run.ways, _way_masks(mask, i, j, block_q, block_k))

    @pl.when(last)
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

    b, h, s, d = q.shape  # d: the key width, q's and k's
    dv = v.shape[-1]  # the value width, v's and the output's
    bh = b * h
    # The grid's second axis runs over the run tiles alone, by rows: a
    # row's q, o and lse blocks stay while its k/v tiles stream.
    run = _run_tiles(mask, s, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        run=run,
        mask=mask,
        scale=d**-0.5,
        emit_lse=emit_lse,
    )

    def q_spec(width):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b_, t: (b_, _major_at(t, run), 0),
            memory_space=pltpu.VMEM,
        )

    def k_spec(width):
        return pl.BlockSpec(
            (None, block_k, width),
            lambda b_, t: (b_, _minor_at(t, run), 0),
            memory_space=pltpu.VMEM,
        )

    out_specs = [q_spec(dv)]
    out_shape = [jax.ShapeDtypeStruct((bh, s, dv), q.dtype)]
    if emit_lse:
        out_specs.append(q_spec(LANES))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32)
        )
    res = pl.pallas_call(
        kernel,
        grid=(bh, len(run.major)),
        in_specs=[q_spec(d), k_spec(d), k_spec(dv)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=_interpret(),
        name=_kernel_name(mask, "flash_fwd", d != dv),
    )(
        q.reshape(bh, s, d), k.reshape(bh, s, d), v.reshape(bh, s, dv)
    )
    if not emit_lse:
        return res[0].reshape(b, h, s, dv), None
    out, lse = res
    # Keep the residual compact between passes: one lane is the value.
    return out.reshape(b, h, s, dv), lse[:, :, 0].reshape(b, h, s)


# ---------- backward kernel ----------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, block_q, block_k, run, mask, scale,
):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)  # the t-th run tile, by columns
    j, i = _major_at(t, run), _minor_at(t, run)  # k block, q block
    first, last = _ends_at(t, run)

    # dq's sum runs over the columns: the whole [S, D] row of this
    # batch*head stays in VMEM from the row's first grid step to its last.
    @pl.when(t == 0)
    def _init_row():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(first)
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

    _accumulate_by_kind(
        accumulate, t, run.ways, _way_masks(mask, i, j, block_q, block_k))

    @pl.when(last)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(t == len(run.major) - 1)
    def _finalize_row():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_vmem_bytes(s, d, dv, block_q, block_k, itemsize):
    """VMEM the backward call asks for, from its shapes (d the key width,
    q's, k's and their gradients'; dv the value width, v's, dO's and
    dv's): the float32 score-sized tiles (scores, p, dp, ds and the two
    transposes), the double-buffered input and output blocks, dq's row
    (scratch plus its double-buffered output block) and the dk/dv
    scratch."""
    tiles = 6 * block_q * block_k * 4
    blocks = 2 * (
        (block_q + block_k) * (d + dv) * itemsize  # q, dO; k, v
        + 2 * block_q * LANES * 4  # lse, delta
        + block_k * (d + dv) * itemsize  # dk, dv
    )
    dq_row = s * d * (4 + 2 * itemsize)
    scratch = block_k * (d + dv) * 4
    return tiles + blocks + dq_row + scratch


def _flash_backward(q, k, v, out, lse, g, mask, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape  # d: the key width, q's and k's
    dv = v.shape[-1]  # the value width: v's, the output's, its cotangent's
    bh = b * h
    vmem_bytes = _bwd_vmem_bytes(
        s, d, dv, block_q, block_k, q.dtype.itemsize)
    if vmem_bytes > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"flash_attention: the backward keeps one [{s}, {d}] float32 "
            f"row of dq in VMEM and would need {vmem_bytes >> 20} MiB of "
            f"it (budget {VMEM_BUDGET_BYTES >> 20} MiB); shard the "
            "sequence with ring or Ulysses attention "
            "(parallel/ring_attention.py, parallel/ulysses.py)"
        )

    q3, k3 = (x.reshape(bh, s, d) for x in (q, k))
    v3, g3 = (x.reshape(bh, s, dv) for x in (v, g))
    # delta = rowsum(dout * out): one fused elementwise+reduce XLA pass.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, s)
    lse_fat = jnp.broadcast_to(
        lse.reshape(bh, s)[:, :, None], (bh, s, LANES)
    )
    delta_fat = jnp.broadcast_to(delta[:, :, None], (bh, s, LANES))

    # The grid's second axis runs over the run tiles alone, by columns: a
    # column's k, v, dk and dv blocks stay while its q-indexed tiles stream.
    run = _run_tiles(mask, s, block_q, block_k, by_column=True)

    def q_spec(width):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b_, t: (b_, _minor_at(t, run), 0),
            memory_space=pltpu.VMEM,
        )

    def k_spec(width):
        return pl.BlockSpec(
            (None, block_k, width),
            lambda b_, t: (b_, _major_at(t, run), 0),
            memory_space=pltpu.VMEM,
        )

    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _bwd_kernel,
            block_q=block_q,
            block_k=block_k,
            run=run,
            mask=mask,
            scale=d**-0.5,
        ),
        grid=(bh, len(run.major)),
        in_specs=[
            q_spec(d), k_spec(d), k_spec(dv), q_spec(dv),
            q_spec(LANES), q_spec(LANES),
        ],
        out_specs=[
            # Indexed by the row alone: written back once a row.
            pl.BlockSpec(
                (None, s, d), lambda b_, t: (b_, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            k_spec(d),
            k_spec(dv),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=_interpret(),
        name=_kernel_name(mask, "flash_bwd", d != dv),
    )(q3, k3, v3, g3, lse_fat, delta_fat)

    return (
        dq.reshape(b, h, s, d),
        dk.reshape(b, h, s, d),
        dv_.reshape(b, h, s, dv),
    )


# ---------- public API with custom VJP ----------


def _kernel_name(mask, name, latent=False):
    """The causal and unmasked calls keep their names; a call under block
    diffusion or under a band carries its own, and so does one whose key
    width is not its value width (`latent`: `mla_flash_fwd`), so a trace
    tells them apart."""
    if isinstance(mask, BlockDiffusion):
        name = f"bd_{name}"
    elif isinstance(mask, Band):
        name = f"band_{name}"
    return f"mla_{name}" if latent else name


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(
    q, k, v, mask=False, block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K
):
    """Attention of q, k [B, H, S, Dk] and v [B, H, S, Dv] under `mask`:
    False, True (causal), a `BlockDiffusion` or a `Band`; scale Dk^-0.5,
    the result and its cotangent [B, H, S, Dv]. Dk is Dv in every model
    but the latent-attention one (192 = 128 without position + 64 rotary,
    against 128), whose q and k enter as one [block, 192] operand. Where
    the kernel runs, S must be a multiple of the (clamped) block sizes
    (ValueError otherwise)."""
    mask = _plain(mask, q.shape[2])
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
    """Under block diffusion a tile lies inside one half, under a band
    inside the window."""
    if isinstance(mask, BlockDiffusion):
        s = mask.half
    elif isinstance(mask, Band):
        s = min(s, mask.window)
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
    mask = _plain(mask, q.shape[2])
    bq, bk = _clamp_blocks(q.shape[2], block_q, block_k, mask)
    if _pallas_ok(q.shape[2], bq, bk, mask):
        out, lse = _per_batch_shard(
            lambda q, k, v: _flash_forward(
                q, k, v, mask, bq, bk, emit_lse=True
            )
        )(q, k, v)
        return _kept(q, k, v, out, lse)
    out = _fallback_attention(q, k, v, mask)
    return _kept(q, k, v, out, None)


def _bwd(mask, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    mask = _plain(mask, q.shape[2])
    bq, bk = _clamp_blocks(q.shape[2], block_q, block_k, mask)
    if lse is not None:
        return _per_batch_shard(
            lambda *a: _flash_backward(*a, mask, bq, bk)
        )(q, k, v, out, lse, g)
    return _bwd_xla(q, k, v, out, g, mask)


# What the kernel made, by the names a rematerialised layer's policy may
# save (`jax.checkpoint_policies.save_only_these_names(*KEPT)`): the
# output and the compact lse [B, H, S], never the kernel's lane-replicated
# one. Without such a policy the names are identities.
KEPT = ("flash_out", "flash_lse")


def _kept(q, k, v, out, lse):
    """`_fwd`'s result and residuals with `out` and `lse` under their
    names: the layer's next product and `_bwd` then read what a policy
    saved, and the recomputed call is dead code. (Kept below `_fwd` and
    `_bwd`, like `_fallback_attention`.)"""
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, KEPT[0])
    if lse is not None:  # the fallback path keeps none
        lse = checkpoint_name(lse, KEPT[1])
    return out, (q, k, v, out, lse)


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
