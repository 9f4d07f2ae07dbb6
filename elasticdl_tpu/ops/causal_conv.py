"""The Mamba-2 mixer's convolution stage, `layers/mamba2.py:conv_silu_split`
(silu(causal depthwise conv1d(xBC) + bias), cut into x, B and C), with a
hand-written backward, as two Pallas kernels in the layout the scan's
kernels (`ops/ssd_scan.py`) take: the time on the lanes, a channel a
sublane row.

    z, x, b, c, dt = causal_conv_silu(proj, widths, weight, bias)

the call of `conv_silu_split`. Off the TPU (the CPU test platform) the same
call runs `conv_silu_split` itself (as `ssd_scan` runs `ssd_chunked`), which
is what the kernels are tested against. On the TPU a width or a length the
kernels cannot tile raises.

Why a backward by hand. `jax.grad` of K shifted slices of a padded copy
writes the shifted copies out and sums over them in passes of their own:
seven fusions and copies a layer on the granite cell, 2.7 ms where the
bytes need 0.6 (PERF.md section 6, PR 49). By hand it is one pass: with
pre_t = sum_j w_j x_{t-K+1+j} + bias and y = silu(pre),

    d pre_t = dy_t * s_t * (1 + pre_t * (1 - s_t)),   s = sigmoid(pre)
    d x_t   = sum_j w_j d pre_{t+K-1-j}               (zeros past the end)
    d w_j   = sum_t d pre_t x_{t-K+1+j},   d bias = sum_t d pre_t

with pre recomputed from xBC (no residual but the in-projection's saved
product). bfloat16 or float32 operands and results, float32 inside, each
result rounded once.

What crosses the kernels' boundary. `proj` whole, as [B, W, S] (on the
granite cut the compiler keeps every [1, S, width] activation with S
minor, so the transposes round the calls move nothing): xBC is rows
`start ..` of it by block index, never a slice. weight [B, K, C] and bias
[B, 1, C] in float32 (the parameters rounded to proj's dtype first, as
the expression takes them), a copy a batch row: a data mesh's shard_map
then takes every operand with one spec, and the broadcast's own gradient
sums d weight over the rows. Forward: x [B, widths[0], S], B and C
[B, widths[1], S] as separate results, which the scan's kernels take by
bitcast. Backward: their cotangents in, d xBC [B, C, S], d weight
[B, K, C] and d bias [B, 1, C] (float32) out.

The grid is (batch, time tile, channel block) forward and (batch, channel
block, time tile) backward, a block CHANNELS rows by `tile` lanes. A
tile's first K - 1 taps reach into the tile before it: its last HALO
columns are read as a block of their own (zeros at a sequence's start),
so the forward keeps nothing from step to step. The backward walks the
time tiles from the last to the first with the first HALO columns of the
later tile's d pre in VMEM scratch (zeros past the end), and sums d
weight and d bias a lane apart in float32 scratch, folded and written
once a channel block. A step walks its block ROWS rows (one packed
bfloat16 tile) a turn, LANES lanes at a time: a turn's values are
[ROWS, LANES] float32 each (256 KiB at the full size, VMEM's and not the
registers'), and the longer the turn the less of it is its halo's
columns (the timings at TIME and LANES below). A shift along the lanes
is a rotation of [halo | lanes] and an aligned cut.

Bodies and index maps are `lax` primitives on traced values, as in
`ops/ssd_scan.py`.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.layers.mamba2 import conv_silu_split
from elasticdl_tpu.ops import flash_attention as _fa
from elasticdl_tpu.ops.ssd_scan import F32, _i32, _sum, _to, _wide, _zeros

# Rows of a block, and of a turn inside it (one packed bfloat16 tile).
CHANNELS = 128
ROWS = 16
# Columns of the neighbouring tile a step reads for the K - 1 it needs (a
# lane tile, the least a block can be).
HALO = 128
# The longest time tile ([CHANNELS, TIME] a block) and the most lanes a
# turn: on the chip the backward took 0.92 ms a call at 4096 by 512, 0.75
# at 4096 by 1024, 0.61 at 8192 by 4096 (what a turn pays a time, its
# halo's columns among it, is spread over more lanes).
TIME = 8192
LANES = 4096
# More taps than any mixer has (4): a turn holds K shifted copies.
MAX_TAPS = 7


def _taken(proj, weight, bias):
    """The parameters as the stage takes them: in proj's dtype, zeros for
    no bias."""
    if bias is None:
        bias = jnp.zeros(weight.shape[1:], weight.dtype)
    return weight.astype(proj.dtype), bias.astype(proj.dtype)


# ---------- the kernels ----------


class _Tiles(NamedTuple):
    """The call's sizes: batch, time tiles, a tile's lanes and a turn's,
    the taps, and the channel blocks: before xBC in `proj`, of x, of B
    (and of C)."""

    bsz: int
    tiles: int
    tile: int
    lanes: int
    k: int
    before: int
    nx: int
    nb: int

    @property
    def blocks(self):
        return self.nx + 2 * self.nb


def _tiles(proj_shape, start, k, widths):
    """The sizes, or ValueError with the shape for what no tile serves."""
    bsz, s, _ = proj_shape

    def refuse(why):
        raise ValueError(
            f"causal_conv_silu: cannot tile proj {tuple(proj_shape)} from "
            f"column {start} in widths {tuple(widths)} at {k} taps: {why}")

    if len(widths) != 3 or widths[1] != widths[2]:
        refuse("the widths are not x's, B's and C's, the last two equal")
    if s % HALO:
        refuse(f"sequence length {s} is not a multiple of {HALO}")
    if any(v % CHANNELS for v in (start, *widths)):
        refuse(f"a width or the first column is not a multiple of "
               f"{CHANNELS} channels")
    if not 1 <= k <= MAX_TAPS:
        refuse(f"{k} taps are not between 1 and {MAX_TAPS}")
    tile = min(TIME, s)
    while s % tile:
        tile //= 2
    # Lanes a turn: the most under LANES that the tile is a multiple of.
    lanes = min(LANES, tile)
    while tile % lanes:
        lanes -= HALO
    return _Tiles(bsz, s // tile, tile, lanes, k, start // CHANNELS,
                  widths[0] // CHANNELS, widths[1] // CHANNELS)


def _columns(w_ref, bias_ref, cols):
    """The block's taps and bias as columns: cols[:, j] = w_j, cols[:, K]
    = bias (the rows laid in `cols` and the square turned over; what its
    other rows held is moved, never read)."""
    k = w_ref.shape[0]
    cols[0:k, :] = w_ref[:]
    cols[k:k + 1, :] = bias_ref[:]
    cols[:] = lax.transpose(cols[:], (1, 0))


def _taps(cols, rows, k, lanes):
    """[w_0 .. w_{K-1}, bias] of a turn's rows, each [ROWS, lanes]."""
    held = cols[rows, :]
    return [_wide(lax.slice_in_dim(held, j, j + 1, axis=1), (ROWS, lanes))
            for j in range(k + 1)]


def _window(x_ref, halo, rows, at, lanes):
    """[ROWS, HALO + lanes] in x's dtype: the turn's lanes from `at` and
    the HALO columns before them, `halo` [ROWS, HALO] before the tile's
    first."""
    if at:
        return x_ref[rows, at - HALO:at + lanes]
    return lax.concatenate([halo, x_ref[rows, 0:lanes]], 1)


def _rolled(window, by):
    """window turned `by` lanes on. The rotation is the kernels' dearest
    operation (some two cycles a register, PERF.md section 6, PR 49), and
    takes 32-bit lanes: bfloat16 rows go two a lane, half the
    registers."""
    from jax.experimental.pallas import tpu as pltpu

    if window.dtype.itemsize == 4:
        return pltpu.roll(window, by, 1)
    packed = pltpu.roll(pltpu.bitcast(window, jnp.uint32), by, 1)
    return pltpu.bitcast(packed, window.dtype)


def _shifted(window, by, lanes, earlier=True):
    """window's `lanes` columns as seen `by` columns earlier (window =
    [halo | lanes]) or later (window = [lanes | halo]), in float32."""
    at = HALO if earlier else 0
    if by:
        window = _rolled(window, by if earlier else HALO + lanes - by)
    return _to(lax.slice_in_dim(window, at, at + lanes, axis=1), F32)


def _pre(window, taps, k, lanes):
    """The K shifted copies of a turn's lanes and their pre-activation."""
    copies = [_shifted(window, k - 1 - j, lanes) for j in range(k)]
    pre = taps[k]
    for j in range(k):
        pre = lax.add(pre, lax.mul(copies[j], taps[j]))
    return copies, pre


def _sigmoid(pre):
    """1 / (1 + exp(-pre)) as 0.5 + 0.5 tanh(pre / 2): the same function,
    one transcendental and no division (0.04 ms a forward call and 0.08 a
    backward on the chip)."""
    half = lax.full(pre.shape, 0.5, F32)
    return lax.add(lax.mul(lax.tanh(lax.mul(pre, half)), half), half)


def _halo_kept(halo_ref, rows, first):
    """The HALO columns before the tile, zeros before a sequence's first
    (there the block read is the tile's own columns: chosen away, never
    multiplied, so that nothing they hold reaches a result)."""
    held = halo_ref[rows, :]
    return lax.select(first, _zeros(held.shape, held.dtype), held)


def _each_turn(turn):
    """turn(rows) for every ROWS rows of the block."""
    from jax.experimental import pallas as pl

    def body(i, carry):
        turn(pl.ds(pl.multiple_of(lax.mul(i, _i32(ROWS)), ROWS), ROWS))
        return carry

    lax.fori_loop(0, CHANNELS // ROWS, body, 0)


def _by_block(t, block, *refs_of):
    """Runs the one of `refs_of` (x's, B's, C's) that the channel block
    `block` belongs to, each under its own `pl.when`."""
    from jax.experimental import pallas as pl

    first_b, first_c = _i32(t.nx), _i32(t.nx + t.nb)
    tests = (lax.lt(block, first_b),
             lax.bitwise_and(lax.ge(block, first_b), lax.lt(block, first_c)),
             lax.ge(block, first_c))
    for test, run in zip(tests, refs_of):
        pl.when(test)(run)


def _fwd_kernel(x_ref, halo_ref, w_ref, bias_ref, ox_ref, ob_ref, oc_ref,
                cols, *, t):
    from jax.experimental import pallas as pl

    first = lax.eq(pl.program_id(1), _i32(0))
    lanes = t.lanes
    _columns(w_ref, bias_ref, cols)

    def into(out_ref):
        def turn(rows):
            taps = _taps(cols, rows, t.k, lanes)
            halo = _halo_kept(halo_ref, rows, first)
            for at in range(0, t.tile, lanes):
                _, pre = _pre(_window(x_ref, halo, rows, at, lanes), taps,
                              t.k, lanes)
                out_ref[rows, at:at + lanes] = _to(
                    lax.mul(pre, _sigmoid(pre)), out_ref.dtype)

        return lambda: _each_turn(turn)

    _by_block(t, pl.program_id(2), into(ox_ref), into(ob_ref), into(oc_ref))


def _bwd_kernel(x_ref, halo_ref, w_ref, bias_ref, gx_ref, gb_ref, gc_ref,
                dx_ref, dw_ref, dbias_ref, cols, sums, ahead, *, t):
    from jax.experimental import pallas as pl

    # The grid's time steps run from the last tile to the first.
    step = pl.program_id(2)
    first = lax.eq(step, _i32(t.tiles - 1))
    lanes = t.lanes
    groups = lanes // HALO

    @pl.when(lax.eq(step, _i32(0)))
    def _last_tile():
        sums[:] = _zeros(sums.shape)
        ahead[:] = _zeros(ahead.shape)

    _columns(w_ref, bias_ref, cols)

    def a_lane_apart(v):
        """[ROWS, lanes] summed to [ROWS, HALO], lane by lane."""
        total = lax.slice_in_dim(v, 0, HALO, axis=1)
        for g in range(1, groups):
            total = lax.add(total, lax.slice_in_dim(
                v, g * HALO, (g + 1) * HALO, axis=1))
        return total

    def of(g_ref):
        def turn(rows):
            taps = _taps(cols, rows, t.k, lanes)
            halo = _halo_kept(halo_ref, rows, first)
            later = ahead[rows, :]
            found = [None] * (t.k + 1)
            one = lax.full((ROWS, lanes), 1, F32)
            # From the tile's last lanes to its first: each turn's d pre
            # is what the turn before it in time needs K - 1 columns of.
            for at in reversed(range(0, t.tile, lanes)):
                copies, pre = _pre(_window(x_ref, halo, rows, at, lanes),
                                   taps, t.k, lanes)
                sig = _sigmoid(pre)
                dpre = lax.mul(
                    lax.mul(_to(g_ref[rows, at:at + lanes], F32), sig),
                    lax.add(one, lax.mul(pre, lax.sub(one, sig))))
                for j, part in enumerate(
                        [lax.mul(dpre, c) for c in copies] + [dpre]):
                    part = a_lane_apart(part)
                    found[j] = part if found[j] is None else lax.add(
                        found[j], part)
                window = lax.concatenate([dpre, later], 1)
                dx = None
                for j in range(t.k):
                    term = lax.mul(taps[j], _shifted(
                        window, t.k - 1 - j, lanes, earlier=False))
                    dx = term if dx is None else lax.add(dx, term)
                dx_ref[rows, at:at + lanes] = _to(dx, dx_ref.dtype)
                later = lax.slice_in_dim(dpre, 0, HALO, axis=1)
            ahead[rows, :] = later
            for j in range(t.k + 1):
                sums[j, rows, :] = lax.add(sums[j, rows, :], found[j])

        return lambda: _each_turn(turn)

    _by_block(t, pl.program_id(1), of(gx_ref), of(gb_ref), of(gc_ref))

    @pl.when(first)
    def _write_sums():
        for j in range(t.k + 1):
            # A channel's lanes summed, the channels then on the lanes.
            row = _sum(lax.transpose(sums[j], (1, 0)), 0)
            if j < t.k:
                dw_ref[j:j + 1, :] = row
            else:
                dbias_ref[:] = row


def _specs(t, order, time_inner):
    """BlockSpecs over the grid (batch, time tile, channel block), or
    (batch, channel block, time tile) with `time_inner`; `order` of the
    grid's time step gives the tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def grid_of(index):
        if time_inner:
            return lambda b_, c_, s_: index(b_, order(s_), c_)
        return lambda b_, s_, c_: index(b_, order(s_), c_)

    def spec(shape, index):
        return pl.BlockSpec(shape, grid_of(index), memory_space=pltpu.VMEM)

    per_tile = t.tile // HALO

    def of_proj():
        """xBC's block of `proj` [B, W, S], and the HALO columns before
        it (the tile's own first where there are none: the kernel takes
        zeros there)."""
        before = _i32(t.before)
        return (
            spec((None, CHANNELS, t.tile),
                 lambda b_, s_, c_: (b_, lax.add(before, c_), s_)),
            spec((None, CHANNELS, HALO),
                 lambda b_, s_, c_: (b_, lax.add(before, c_), lax.max(
                     lax.sub(lax.mul(s_, _i32(per_tile)), _i32(1)),
                     _i32(0)))))

    def a_channel(rows):
        return spec((None, rows, CHANNELS), lambda b_, s_, c_: (b_, 0, c_))

    def xbc():
        return spec((None, CHANNELS, t.tile), lambda b_, s_, c_: (b_, c_, s_))

    def part(first, blocks):
        """A block of x, B or C [B, blocks * CHANNELS, S], whose channel
        blocks are first .. first + blocks - 1 of xBC's: held at its
        nearest while the grid walks the others' (no copy in or out
        between two steps at one index; with the time inside, at the tile
        it is next read at, or was last)."""
        def index(b_, s_, c_):
            inside = lax.sub(c_, _i32(first))
            if time_inner:
                s_ = lax.select(
                    lax.lt(inside, _i32(0)), order(_i32(0)), lax.select(
                        lax.ge(inside, _i32(blocks)),
                        order(_i32(t.tiles - 1)), s_))
            return (b_, lax.clamp(_i32(0), inside, _i32(blocks - 1)), s_)

        return spec((None, CHANNELS, t.tile), index)

    parts = (lambda: part(0, t.nx), lambda: part(t.nx, t.nb),
             lambda: part(t.nx + t.nb, t.nb))
    return of_proj, a_channel, xbc, parts


def _vmem_limit(blocks_bytes):
    """Every block double-buffered, the scratch, and room for a turn's
    values."""
    return 2 * blocks_bytes + (16 << 20)


@functools.partial(jax.jit, static_argnames=("t", "widths", "interpret"))
def _forward(proj, weight, bias, *, t, widths, interpret):
    """x [B, widths[0], S], B and C [B, widths[1], S] from proj [B, W, S].
    A jit of its own, as the scan's kernels are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    of_proj, a_channel, _, parts = _specs(
        t, lambda s_: s_, time_inner=False)
    s = proj.shape[2]
    block = CHANNELS * t.tile * proj.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t),
        grid=(t.bsz, t.tiles, t.blocks),
        in_specs=[*of_proj(), a_channel(t.k), a_channel(1)],
        out_specs=[p() for p in parts],
        out_shape=[jax.ShapeDtypeStruct((t.bsz, w, s), proj.dtype)
                   for w in widths],
        scratch_shapes=[pltpu.VMEM((CHANNELS, HALO), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(5 * block)),
        interpret=interpret,
        name="causal_conv_fwd",
    )(proj, proj, weight, bias)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _backward(proj, weight, bias, gx, gb, gc, *, t, interpret):
    """d xBC [B, C, S], d weight [B, K, C] and d bias [B, 1, C] (float32)
    from proj, the taps and the cotangents of x, B and C. A jit of its
    own."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    of_proj, a_channel, xbc, parts = _specs(
        t, lambda s_: lax.sub(_i32(t.tiles - 1), s_), time_inner=True)
    s, c = proj.shape[2], t.blocks * CHANNELS
    block = CHANNELS * t.tile * proj.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_bwd_kernel, t=t),
        grid=(t.bsz, t.blocks, t.tiles),
        in_specs=[*of_proj(), a_channel(t.k), a_channel(1),
                  *(p() for p in parts)],
        out_specs=[xbc(), a_channel(t.k), a_channel(1)],
        out_shape=[
            jax.ShapeDtypeStruct((t.bsz, c, s), proj.dtype),
            jax.ShapeDtypeStruct((t.bsz, t.k, c), F32),
            jax.ShapeDtypeStruct((t.bsz, 1, c), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((CHANNELS, HALO), F32),  # taps and bias, columns
            pltpu.VMEM((t.k + 1, CHANNELS, HALO), F32),  # d weight, d bias
            pltpu.VMEM((CHANNELS, HALO), F32),  # the later tile's d pre
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(6 * block)),
        interpret=interpret,
        name="causal_conv_bwd",
    )(proj, proj, weight, bias, gx, gb, gc)


# ---------- the layout, and the op ----------


def _time_last(v):
    return jnp.swapaxes(v, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _stage(proj, weight, bias, start, widths):
    return _stage_fwd(proj, weight, bias, start, widths)[0]


def _stage_fwd(proj, weight, bias, start, widths):
    t = _tiles(proj.shape, start, weight.shape[1], widths)
    parts = _forward(_time_last(proj), weight, bias, t=t, widths=widths,
                     interpret=_fa._interpret())
    return tuple(_time_last(p) for p in parts), (proj, weight, bias)


def _stage_bwd(start, widths, residuals, cotangents):
    proj, weight, bias = residuals
    t = _tiles(proj.shape, start, weight.shape[1], widths)
    dxbc, dw, dbias = _backward(
        _time_last(proj), weight, bias,
        *(_time_last(g) for g in cotangents),
        t=t, interpret=_fa._interpret())
    after = proj.shape[2] - start - sum(widths)
    dproj = jnp.pad(_time_last(dxbc), ((0, 0), (0, 0), (start, after)))
    return dproj, dw, dbias


_stage.defvjp(_stage_fwd, _stage_bwd)


def causal_conv_silu(proj, widths, weight, bias):
    """`layers.mamba2.conv_silu_split`, its arguments and its results: as
    the kernels where they run (the TPU, or the CPU under the test-only
    interpret switch), where a shape they cannot tile raises; elsewhere
    the expression itself, as `ssd_scan` runs `ssd_chunked`."""
    if not _fa._use_pallas():
        return conv_silu_split(proj, widths, weight, bias)
    start, widths = widths[0], tuple(widths[1:4])
    _tiles(proj.shape, start, weight.shape[0], widths)
    weight, bias = _taken(proj, weight, bias)
    # Batch first on every operand, as `ssd_scan`'s.
    bsz = proj.shape[0]
    weight = jnp.broadcast_to(weight.astype(F32), (bsz, *weight.shape))
    bias = jnp.broadcast_to(bias.astype(F32), (bsz, 1, *bias.shape))
    x, b, c = _fa._per_batch_shard(
        lambda *operands: _stage(*operands, start, widths)
    )(proj, weight, bias)
    # z and dt are the program's own slices: fused into what reads them.
    after = start + sum(widths)
    return (lax.slice_in_dim(proj, 0, start, axis=-1), x, b, c,
            lax.slice_in_dim(proj, after, proj.shape[-1], axis=-1))
