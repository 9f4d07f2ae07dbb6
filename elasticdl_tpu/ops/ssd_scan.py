"""The chunked (SSD) state-space scan of `layers/mamba2.py:ssd_chunked` as
two Pallas kernels: the same mathematics at the same chunk, with the decay
mask, C B^T and the masked [Q, Q] product made tile by tile in VMEM, never
in HBM. Off the TPU (the CPU test platform) the same call runs
`ssd_chunked` itself; on the TPU a shape the kernels cannot tile raises.

    y = ssd_scan(x, dt, a, b, c, chunk, dtype)      # ssd_chunked's call

What crosses the boundary. x [B, S, H, P] and b, c [B, S, G, N] in the
dtype they arrive in (bfloat16 on the granite cell), dt [B, S, H] and a
[H] float32; y [B, S, H, P] float32. Inside, the operands of every product
are cast to `dtype` exactly where `ssd_chunked`'s `dot` casts them (C B^T,
(C B^T * decay) xdt, B^T (xdt * to_end), C entering, and the gradients'
products of the same tensors); every accumulator, cumulative sum, decay,
exponential and the carried state is float32.

Layout: the time on the lanes. The kernels take x as [B, G, R, P, S] (R =
H / G heads a group), dt as [B, G, R, S], B and C as [B, G, N, S], and
give y as [B, G, R, P, S]: the transposes round the calls are the
program's, and where the compiler already keeps [B, S, width] activations
with S minor (it does on the granite cut: `{1,2,0}` layouts through the
whole step) they move nothing. A head is then a leading index, every
per-token scale (dt, the decays) a row that broadcasts over sublanes, and
every tile is full: [P, 128] of x, [128, 128] of the mask.

The grid is (batch, group, chunk), the chunks in order with the state
[R * P, N] in VMEM scratch (the backward walks them in reverse with the
state's cotangent there), so the states' [C, C] product and their round
trip through HBM are gone as well. A chunk's step makes the decays'
cumulative sums for all its heads with one triangular product (float32
as three exact bfloat16 passes), makes B C^T once (a group shares it) and
the state's products for all heads at once ([R * P, N] by [N, Q] and [R *
P, Q] by [Q, N]), and then walks the heads eight a turn: eight heads'
rows are one tile's sublanes, which the transpose unit turns into eight
columns for the one place a column is needed (the mask's cum_s; a head's
is then a lane known at trace time) and back (the mask's column sums).
The [Q, Q] mask of a head exists as [128, 128] tiles, of which those
above the diagonal are never made.

The backward recomputes the same per-chunk quantities from x, dt, B, C and
the entering states the forward wrote (float32, [B, C, G, R * P, N]: 67 MB
a layer on the cell, alive only inside a rematerialised layer's backward)
and gives dx, db, dc and three dt-sized pieces of ddt and da, which XLA
adds up.

Bodies and index maps are `lax` primitives on traced values, not `jnp`
operators: each of those is a nested jit to trace (PERF.md section 6,
PR 44), and a body here has some three hundred operations.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from elasticdl_tpu.layers.mamba2 import SCAN_SCOPE, ssd_chunked
from elasticdl_tpu.ops import flash_attention as _fa

F32 = jnp.float32
BF16 = jnp.bfloat16
# The [Q, Q] mask is made in [TILE, TILE] pieces; those above the
# diagonal are never made.
TILE = 128
VMEM_BUDGET_BYTES = 96 * 2**20
# A chunk's per-token rows, kept a head a sublane and a tile of the chunk
# apart ([kind, tile, R, TILE]): a head's row is read at a traced sublane,
# which Mosaic serves from lane 0 alone. _END holds exp(cum at the chunk's
# end) on every lane of its one tile.
_DT, _CUM, _DECAYED, _TO_END, _END = range(5)
# Heads a turn of the kernels' loop over a group's heads: eight rows are
# one tile's sublanes, which the transpose unit turns into eight columns
# (the mask's cum_s, a head's by a lane that is then a constant of the
# trace) and back (the mask's column sums, to rows); and a head's small
# products wait on the matrix unit while the next head's vector work runs.
HEADS_A_TURN = 8


class _Tiles(NamedTuple):
    """The call's sizes: batch, chunks, chunk, groups, heads a group,
    head width, state."""

    bsz: int
    chunks: int
    q: int
    g: int
    r: int
    p: int
    n: int

    @property
    def rows(self):
        return self.r * self.p

    @property
    def tiles(self):
        return self.q // TILE


def _tiles(x_shape, b_shape, chunk):
    """The sizes, or ValueError with the shape for what no tile serves."""
    bsz, s, h, p = x_shape
    g, n = b_shape[2], b_shape[3]

    def refuse(why):
        raise ValueError(
            f"ssd_scan: cannot tile x {tuple(x_shape)}, b / c "
            f"{tuple(b_shape)} at chunk {chunk}: {why}")

    if s % chunk:
        refuse(f"sequence length {s} is not a multiple of the chunk")
    if h % g:
        refuse(f"{h} heads do not split over {g} groups")
    if chunk % TILE:
        refuse(f"the chunk is not a multiple of {TILE}")
    if n % TILE:
        refuse(f"the state size {n} is not a multiple of {TILE}")
    if p % 16:
        refuse(f"the head width {p} is not a multiple of 16")
    if (h // g) % HEADS_A_TURN:
        refuse(f"{h // g} heads a group are not a multiple of "
               f"{HEADS_A_TURN}")
    return _Tiles(bsz, s // chunk, chunk, g, h // g, p, n)


# ---------- lax, spelled short ----------


def _i32(v):
    return np.int32(v)


def _to(x, dtype):
    return x if x.dtype == dtype else lax.convert_element_type(x, dtype)


def _wide(x, shape):
    """Broadcast over x's unit axes (or a scalar over all of them)."""
    return lax.broadcast_in_dim(x, shape, tuple(range(x.ndim)))


def _dot(lhs, rhs, contract_lhs, contract_rhs):
    return lax.dot_general(
        lhs, rhs, (((contract_lhs,), (contract_rhs,)), ((), ())),
        preferred_element_type=F32)


def _sum(x, axis):
    return lax.expand_dims(lax.reduce_sum(x, (axis,)), (axis,))


def _rows(x, i, size=TILE):
    return lax.slice_in_dim(x, i * size, (i + 1) * size, axis=0)


def _cols(x, j, size=TILE):
    return lax.slice_in_dim(x, j * size, (j + 1) * size, axis=1)


def _zeros(shape, dtype=F32):
    return lax.full(shape, 0, dtype)


def _lanes(j):
    return slice(j * TILE, (j + 1) * TILE)


def _lane_is(shape, k):
    return lax.eq(lax.broadcasted_iota(jnp.int32, shape, 1),
                  lax.full(shape, k, jnp.int32))


def _triangle(q, keep):
    """[q, q] of ones where keep(row, column) and zeros elsewhere, in
    bfloat16, which holds both exactly."""
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return _to(lax.select(keep(row, col), lax.full((q, q), 1, F32),
                          _zeros((q, q))), BF16)


def _times_01(x, ones, contract_x, contract_ones):
    """A float32 x times a matrix of zeros and ones, summed in float32
    and exact in every product: x as three bfloat16 (8 + 8 + 8 bits of
    its 24), a pass of the matrix unit each, where `precision=HIGHEST`
    would take six."""
    hi = _to(x, BF16)
    rest = lax.sub(x, _to(hi, F32))
    mid = _to(rest, BF16)
    low = _to(lax.sub(rest, _to(mid, F32)), BF16)
    return lax.add(
        lax.add(_dot(hi, ones, contract_x, contract_ones),
                _dot(mid, ones, contract_x, contract_ones)),
        _dot(low, ones, contract_x, contract_ones))


def _chunk_decays(t, dt_ref, a_ref, rows):
    """cum_l = sum_{s <= l} dt_s a for every head of the group, one
    triangular product with the time on the lanes, and what is made of it
    a token, kept by rows in `rows`."""
    dt = dt_ref[:]  # [R, Q]
    shape = (t.r, t.q)
    cum = _times_01(lax.mul(dt, _wide(a_ref[:], shape)),
                    _triangle(t.q, lax.ge), 1, 1)  # [h, l] over s <= l
    total = _cols(cum, t.q - 1, 1)  # [R, 1]
    by_kind = {
        _DT: dt, _CUM: cum, _DECAYED: lax.exp(cum),
        _TO_END: lax.exp(lax.sub(_wide(total, shape), cum)),
    }
    for kind, values in by_kind.items():
        for j in range(t.tiles):
            rows[kind, j] = _cols(values, j)
    rows[_END, 0] = lax.exp(_wide(total, (t.r, TILE)))


class _Diagonal(NamedTuple):
    """The diagonal tile's mask, made once a call: where l > s, and what
    cum_l - cum_s is taken for elsewhere (0 on the diagonal itself,
    whatever the sum's last bit; -inf above it, before the exponential,
    which would overflow there)."""

    below: object
    other: object


def _diagonal():
    shape = (TILE, TILE)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    col = lax.broadcasted_iota(jnp.int32, shape, 1)
    return _Diagonal(lax.gt(col, row), lax.select(
        lax.eq(row, col), _zeros(shape), lax.full(shape, -np.inf, F32)))


def _decay_tile(cum_col, cum_row, diagonal):
    """exp(cum_l - cum_s) as [s, l]: `cum_col` [TILE, 1] a tile of s,
    `cum_row` [1, TILE] a tile of l no earlier; `diagonal` for the same
    tile of both."""
    shape = (TILE, TILE)
    seg = lax.sub(_wide(cum_row, shape), _wide(cum_col, shape))
    if diagonal is not None:
        seg = lax.select(diagonal.below, seg, diagonal.other)
    return lax.exp(seg)


class _Head(NamedTuple):
    """What both passes make of a head before their products."""

    at: object  # its rows in a [R * P, .] ref
    row: object  # (kind, tile) -> [1, TILE] of `rows`
    cum_col: list  # [TILE, 1] a tile of the chunk
    x: list  # [P, TILE] float32 a tile of the chunk
    dt: list  # [P, TILE], the row broadcast
    xdt: list
    ending: list  # xdt * to_end: what B^T multiplies


def _over_heads(t, x_ref, rows, head, after_turn=None):
    """held = head(k, h, z, held) for every head h of the group, the
    k-th of its turn of HEADS_A_TURN (Mosaic unrolls a loop whole or not
    at all, so a turn is written out); `held` is None at a turn's start
    and goes to after_turn(its heads' sublanes in `rows`, held)."""
    from jax.experimental import pallas as pl

    shape = (t.p, TILE)

    def turn(i, carry):
        first = lax.mul(i, _i32(HEADS_A_TURN))
        heads = pl.ds(pl.multiple_of(first, HEADS_A_TURN), HEADS_A_TURN)
        # The turn's cum, the time on the sublanes: [TILE, heads] a tile.
        cum_cols = [lax.transpose(rows[_CUM, j, heads, :], (1, 0))
                    for j in range(t.tiles)]
        held = None
        for k in range(HEADS_A_TURN):
            h = lax.add(first, _i32(k))

            def row(kind, j, h=h):
                return rows[kind, j, pl.ds(h, 1), :]

            x = [_to(x_ref[h, :, _lanes(j)], F32) for j in range(t.tiles)]
            dt = [_wide(row(_DT, j), shape) for j in range(t.tiles)]
            xdt = [lax.mul(x[j], dt[j]) for j in range(t.tiles)]
            held = head(k, h, _Head(
                pl.ds(pl.multiple_of(lax.mul(h, _i32(t.p)), t.p), t.p),
                row,
                [lax.slice_in_dim(c, k, k + 1, axis=1) for c in cum_cols],
                x, dt, xdt,
                [lax.mul(xdt[j], _wide(row(_TO_END, j), shape))
                 for j in range(t.tiles)]), held)
        if after_turn is not None:
            after_turn(heads, held)
        return carry

    lax.fori_loop(0, t.r // HEADS_A_TURN, turn, 0)


def _decay_state(t, z, ref, values):
    """ref[head] = values [P, N] * exp(cum at the chunk's end)."""
    end = _wide(z.row(_END, 0), (t.p, TILE))
    for k in range(t.n // TILE):
        ref[z.at, _lanes(k)] = lax.mul(_cols(values, k), end)


# ---------- forward ----------


def _fwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, *refs, t, dtype,
                emit_states):
    from jax.experimental import pallas as pl

    if emit_states:
        y_ref, h_ref, state, cbt, rows, from_state, ending = refs
    else:
        y_ref, state, cbt, rows, from_state, ending = refs
        h_ref = None

    @pl.when(lax.eq(pl.program_id(2), _i32(0)))
    def _first_chunk():
        state[:] = _zeros(state.shape)

    _chunk_decays(t, dt_ref, a_ref, rows)
    bt_d, ct_d = _to(bt_ref[:], dtype), _to(ct_ref[:], dtype)
    cbt[:] = _dot(bt_d, ct_d, 0, 0)  # [s, l], the group's
    entering = state[:]  # [R * P, N]
    if h_ref is not None:
        h_ref[:] = entering
    # 4. What the entering state gives each token, every head at once.
    from_state[:] = _dot(_to(entering, dtype), ct_d, 1, 0)  # [R * P, Q]
    diagonal = _diagonal()
    shape = (t.p, TILE)

    def head(k, h, z, held):
        xdt_d = [_to(v, dtype) for v in z.xdt]
        for j in range(t.tiles):
            ending[z.at, _lanes(j)] = _to(z.ending[j], dtype)
        for i in range(t.tiles):
            acc = lax.mul(from_state[z.at, _lanes(i)],
                          _wide(z.row(_DECAYED, i), shape))
            # 1. Inside the chunk, a tile at a time.
            for j in range(i + 1):
                masked = lax.mul(cbt[_lanes(j), _lanes(i)], _decay_tile(
                    z.cum_col[j], z.row(_CUM, i),
                    diagonal if i == j else None))
                acc = lax.add(acc, _dot(xdt_d[j], _to(masked, dtype), 1, 0))
            y_ref[h, :, _lanes(i)] = acc
        # 3. The state the next chunk enters with: this one's, decayed,
        _decay_state(t, z, state, state[z.at, :])

    _over_heads(t, x_ref, rows, head)
    # 2. and what the chunk adds by its end, every head at once.
    state[:] = lax.add(state[:], _dot(ending[:], bt_d, 1, 1))


def _specs(t, order):
    """BlockSpecs of the operands, the chunks walked in `order` (a
    function of the grid's chunk step): a chunk of [B, G, R, P, S], of
    [B, G, rows, S], a group's [B, G, rows, cols], and a chunk's own
    [B, C, G, rows, cols]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def by_head():
        return pl.BlockSpec(
            (None, None, t.r, t.p, t.q),
            lambda b_, g_, c_: (b_, g_, 0, 0, order(c_)),
            memory_space=pltpu.VMEM)

    def by_time(rows):
        return pl.BlockSpec(
            (None, None, rows, t.q),
            lambda b_, g_, c_: (b_, g_, 0, order(c_)),
            memory_space=pltpu.VMEM)

    def grouped(rows, cols):
        return pl.BlockSpec(
            (None, None, rows, cols), lambda b_, g_, c_: (b_, g_, 0, 0),
            memory_space=pltpu.VMEM)

    def chunked(rows, cols):
        return pl.BlockSpec(
            (None, None, None, rows, cols),
            lambda b_, g_, c_: (b_, order(c_), g_, 0, 0),
            memory_space=pltpu.VMEM)

    return by_head, by_time, grouped, chunked


def _vmem_bytes(t, blocks, scratch, what):
    """What a call asks of VMEM: its blocks double-buffered, its scratch
    with B C^T and the rows, and the float32 values of a chunk (the
    state's products) and of a turn of heads; ValueError over the
    budget."""
    asked = (2 * blocks + scratch + 4 * (t.q * t.q + 7 * t.r * t.q)
             + 4 * t.rows * (t.q + 2 * t.n) + (8 << 20))
    if asked > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"ssd_scan: the {what} keeps a chunk's [{t.r}, {t.p}, {t.q}] "
            f"blocks and the state [{t.rows}, {t.n}] in VMEM and would "
            f"need {asked >> 20} MiB of it (budget "
            f"{VMEM_BUDGET_BYTES >> 20} MiB)")
    return asked


@functools.partial(
    jax.jit, static_argnames=("t", "dtype", "emit_states", "interpret"))
def _forward(x, dt, a, bt, ct, *, t, dtype, emit_states, interpret):
    """y [B, G, R, P, S] and, with `emit_states`, the state entering each
    chunk [B, C, G, R * P, N]. A jit of its own: the step calls it in
    every scanning layer, and the kernel's body is then traced and
    lowered once a process, not once a layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    by_head, by_time, grouped, chunked = _specs(t, lambda c_: c_)
    out_specs = [by_head()]
    out_shape = [jax.ShapeDtypeStruct(x.shape, F32)]
    if emit_states:
        out_specs.append(chunked(t.rows, t.n))
        out_shape.append(jax.ShapeDtypeStruct(
            (t.bsz, t.chunks, t.g, t.rows, t.n), F32))
    itemsize = jnp.dtype(dtype).itemsize
    blocks = (t.rows * t.q * (x.dtype.itemsize + 4)
              + 2 * t.n * t.q * bt.dtype.itemsize
              + emit_states * 4 * t.rows * t.n)
    scratch = 4 * t.rows * t.n + t.rows * t.q * (4 + itemsize)
    vmem = _vmem_bytes(t, blocks, scratch, "forward")
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, t=t, dtype=dtype, emit_states=emit_states),
        grid=(t.bsz, t.g, t.chunks),
        in_specs=[by_head(), by_time(t.r), grouped(t.r, 1),
                  by_time(t.n), by_time(t.n)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((t.rows, t.n), F32),  # the carried state
            pltpu.VMEM((t.q, t.q), F32),  # B C^T
            pltpu.VMEM((5, t.tiles, t.r, TILE), F32),
            pltpu.VMEM((t.rows, t.q), F32),  # C entering
            pltpu.VMEM((t.rows, t.q), dtype),  # xdt * to_end
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x, dt, a, bt, ct)


# ---------- backward ----------


def _bwd_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, h_ref, dy_ref,
                dx_ref, dbt_ref, dct_ref, dda_ref, ddtx_ref,
                dstate, cbt, dcbt, rows, drows, from_state, d_ending,
                ending, to_state, leaving_d, *, t, dtype):
    from jax.experimental import pallas as pl

    # The grid's chunk steps run from the last chunk to the first.
    @pl.when(lax.eq(pl.program_id(2), _i32(0)))
    def _last_chunk():
        dstate[:] = _zeros(dstate.shape)

    _chunk_decays(t, dt_ref, a_ref, rows)
    bt_d, ct_d = _to(bt_ref[:], dtype), _to(ct_ref[:], dtype)
    cbt[:] = _dot(bt_d, ct_d, 0, 0)
    dcbt[:] = _zeros(dcbt.shape)
    entering_d = _to(h_ref[:], dtype)  # [R * P, N]
    # The cotangent of the state this chunk leaves.
    leaving_d[:] = _to(dstate[:], dtype)
    from_state[:] = _dot(entering_d, ct_d, 1, 0)  # [R * P, Q]
    d_ending[:] = _dot(leaving_d[:], bt_d, 1, 0)
    shape = (t.p, TILE)
    diagonal = _diagonal()
    last_lane = _lane_is((1, TILE), TILE - 1)
    # What reaches cum_s by the mask's columns comes out a column a head:
    # a turn's heads side by side on the first lanes, then turned to rows.
    of_head = [_lane_is((TILE, TILE), k) for k in range(HEADS_A_TURN)]

    def head(k, h, z, d_cols):
        xdt_d = [_to(v, dtype) for v in z.xdt]
        g_d, d_xdt, d_row = [], [], []
        lost = _zeros((1, TILE))
        for j in range(t.tiles):
            g = dy_ref[h, :, _lanes(j)]  # [P, TILE] float32
            g_d.append(_to(g, dtype))
            decayed = _wide(z.row(_DECAYED, j), shape)
            # 4. y += (C entering) exp(cum)
            to_state[z.at, _lanes(j)] = _to(lax.mul(g, decayed), dtype)
            # 2. added = B^T (xdt to_end)
            ending[z.at, _lanes(j)] = _to(z.ending[j], dtype)
            d_end = d_ending[z.at, _lanes(j)]
            d_xdt.append(lax.mul(d_end, _wide(z.row(_TO_END, j), shape)))
            # What reaches cum_l by exp(cum) of 4. and by to_end of 2.
            through_end = _sum(lax.mul(d_end, z.ending[j]), 0)
            lost = lax.add(lost, through_end)
            d_row.append(lax.sub(_sum(lax.mul(
                lax.mul(g, from_state[z.at, _lanes(j)]), decayed), 0),
                through_end))
        # What reaches the chunk's total: to_end, and 3.'s decay.
        left = dstate[z.at, :]
        d_total = lax.add(
            _sum(lost, 1),
            lax.mul(_sum(_sum(lax.mul(left, h_ref[z.at, :]), 0), 1),
                    _cols(z.row(_END, 0), 0, 1)))
        d_row[-1] = lax.add(d_row[-1], lax.select(
            last_lane, _wide(d_total, (1, TILE)), _zeros((1, TILE))))
        d_col = [None] * t.tiles
        # 1. y += xdt (B C^T * decay), a tile at a time.
        for i in range(t.tiles):
            for j in range(i + 1):
                at = (_lanes(j), _lanes(i))
                decay = _decay_tile(z.cum_col[j], z.row(_CUM, i),
                                    diagonal if i == j else None)
                masked = lax.mul(cbt[at], decay)
                d_masked = _dot(xdt_d[j], g_d[i], 0, 0)  # [s, l]
                dcbt[at] = lax.add(dcbt[at], lax.mul(d_masked, decay))
                d_xdt[j] = lax.add(
                    d_xdt[j], _dot(g_d[i], _to(masked, dtype), 1, 1))
                d_seg = lax.mul(d_masked, masked)
                d_row[i] = lax.add(d_row[i], _sum(d_seg, 0))
                through_s = _sum(d_seg, 1)  # [TILE, 1]
                d_col[j] = through_s if d_col[j] is None else lax.add(
                    d_col[j], through_s)
        d_cols = d_cols or [_zeros((TILE, TILE))] * t.tiles
        d_cols = [lax.select(of_head[k], _wide(d_col[j], (TILE, TILE)),
                             d_cols[j]) for j in range(t.tiles)]
        for j in range(t.tiles):
            drows[0, j, pl.ds(h, 1), :] = d_row[j]
            drows[1, j, pl.ds(h, 1), :] = _sum(lax.mul(d_xdt[j], z.x[j]), 0)
            dx_ref[h, :, _lanes(j)] = _to(
                lax.mul(d_xdt[j], z.dt[j]), dx_ref.dtype)
        # 3. next = exp(total) entering + added: the decay's part,
        _decay_state(t, z, dstate, left)
        return d_cols

    def after_turn(heads, d_cols):
        for j in range(t.tiles):
            at = (0, j, heads, slice(None))
            drows[at] = lax.sub(drows[at], _rows(
                lax.transpose(d_cols[j], (1, 0)), 0, HEADS_A_TURN))

    _over_heads(t, x_ref, rows, head, after_turn)
    # and 4.'s, every head at once.
    dstate[:] = lax.add(dstate[:], _dot(to_state[:], ct_d, 1, 1))
    dcbt_d = _to(dcbt[:], dtype)
    dct_ref[:] = _to(lax.add(_dot(entering_d, to_state[:], 0, 0),
                             _dot(bt_d, dcbt_d, 1, 0)), dct_ref.dtype)
    dbt_ref[:] = _to(lax.add(_dot(leaving_d[:], ending[:], 0, 0),
                             _dot(ct_d, dcbt_d, 1, 1)), dbt_ref.dtype)
    # d(dt a)_t = the sum of d cum_l over l >= t.
    dda_ref[:] = _times_01(
        lax.concatenate([drows[0, j] for j in range(t.tiles)], 1),
        _triangle(t.q, lax.le), 1, 1)
    for j in range(t.tiles):
        ddtx_ref[:, _lanes(j)] = drows[1, j]


@functools.partial(jax.jit, static_argnames=("t", "dtype", "interpret"))
def _backward(x, dt, a, bt, ct, states, dy, *, t, dtype, interpret):
    """dx, dB^T, dC^T, d(dt a) and dt's part through x * dt, from the
    forward's operands, the entering states and dy. A jit of its own, as
    `_forward`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    by_head, by_time, grouped, chunked = _specs(
        t, lambda c_: lax.sub(_i32(t.chunks - 1), c_))
    itemsize = jnp.dtype(dtype).itemsize
    blocks = (t.rows * t.q * (2 * x.dtype.itemsize + 4)
              + 4 * t.rows * t.n + 4 * t.n * t.q * bt.dtype.itemsize
              + 12 * t.r * t.q)
    scratch = (t.rows * t.n * (4 + itemsize)
               + t.rows * t.q * (8 + 2 * itemsize) + 4 * t.q * t.q)
    vmem = _vmem_bytes(t, blocks, scratch, "backward")
    return pl.pallas_call(
        functools.partial(_bwd_kernel, t=t, dtype=dtype),
        grid=(t.bsz, t.g, t.chunks),
        in_specs=[by_head(), by_time(t.r), grouped(t.r, 1),
                  by_time(t.n), by_time(t.n),
                  chunked(t.rows, t.n), by_head()],
        out_specs=[by_head(), by_time(t.n), by_time(t.n),
                   by_time(t.r), by_time(t.r)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(bt.shape, bt.dtype),
            jax.ShapeDtypeStruct(ct.shape, ct.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(dt.shape, F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.rows, t.n), F32),  # the state's cotangent
            pltpu.VMEM((t.q, t.q), F32),  # B C^T
            pltpu.VMEM((t.q, t.q), F32),  # and its cotangent
            pltpu.VMEM((5, t.tiles, t.r, TILE), F32),
            pltpu.VMEM((2, t.tiles, t.r, TILE), F32),  # d cum; dt by x
            pltpu.VMEM((t.rows, t.q), F32),  # C entering
            pltpu.VMEM((t.rows, t.q), F32),  # d (xdt * to_end)
            pltpu.VMEM((t.rows, t.q), dtype),  # xdt * to_end
            pltpu.VMEM((t.rows, t.q), dtype),  # dy * exp(cum)
            pltpu.VMEM((t.rows, t.n), dtype),  # the leaving cotangent
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x, dt, a, bt, ct, states, dy)


# ---------- the layout, and the op ----------


def _time_last(v, t, per_group):
    """[B, S, G * per_group...] -> [B, G, per_group..., S]: one transpose
    and no detour (by way of [.., C, Q] the compiler copied x, y, dy and
    dx twice each on the granite cut: 30 ms a step)."""
    v = v.reshape(t.bsz, t.chunks * t.q, t.g, *per_group)
    return jnp.moveaxis(v, 1, -1)


def _time_second(v, shape):
    """`_time_last` undone, to `shape`."""
    return jnp.moveaxis(v, -1, 1).reshape(shape)


def _operands(x, dt, a, b, c, t):
    """The kernels' operands: x [B, G, R, P, S], dt [B, G, R, S], a
    [B, G, R, 1], B and C [B, G, N, S]."""
    return (_time_last(x, t, (t.r, t.p)), _time_last(dt, t, (t.r,)),
            a.reshape(t.bsz, t.g, t.r, 1),
            _time_last(b, t, (t.n,)), _time_last(c, t, (t.n,)))


def _call(kernel, t, dtype, **static):
    return functools.partial(
        kernel, t=t, dtype=dtype, interpret=_fa._interpret(), **static)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a, b, c, chunk, dtype):
    t = _tiles(x.shape, b.shape, chunk)
    with jax.named_scope(SCAN_SCOPE):
        y, = _call(_forward, t, dtype, emit_states=False)(
            *_operands(x, dt, a, b, c, t))
        return _time_second(y, x.shape)


def _scan_fwd(x, dt, a, b, c, chunk, dtype):
    t = _tiles(x.shape, b.shape, chunk)
    with jax.named_scope(SCAN_SCOPE):
        y, states = _call(_forward, t, dtype, emit_states=True)(
            *_operands(x, dt, a, b, c, t))
        return _time_second(y, x.shape), (x, dt, a, b, c, states)


def _scan_bwd(chunk, dtype, residuals, dy):
    x, dt, a, b, c, states = residuals
    t = _tiles(x.shape, b.shape, chunk)
    with jax.named_scope(SCAN_SCOPE):
        operands = _operands(x, dt, a, b, c, t)
        dx, dbt, dct, dda, ddtx = _call(_backward, t, dtype)(
            *operands, states, _time_last(dy.astype(F32), t, (t.r, t.p)))
        # dt and a from d(dt a) [B, G, R, S].
        ddt = dda * operands[2] + ddtx
        da = jnp.sum(dda * operands[1], axis=-1).reshape(a.shape)
        return (_time_second(dx, x.shape),
                _time_second(ddt, dt.shape).astype(dt.dtype),
                da.astype(a.dtype),
                _time_second(dbt, b.shape), _time_second(dct, c.shape))


_scan.defvjp(_scan_fwd, _scan_bwd)


def runs_as_kernel():
    """Does `ssd_scan` run the kernels here (the TPU, or the CPU under
    the test-only interpret switch), or `ssd_chunked`?"""
    return _fa._use_pallas()


def ssd_scan(x, dt, a, b, c, chunk, dtype=jnp.float32):
    """`layers.mamba2.ssd_chunked`, its arguments and its result, as the
    kernels where they run; a shape they cannot tile raises there."""
    if not runs_as_kernel():
        return ssd_chunked(x, dt, a, b, c, chunk, dtype=dtype)
    _tiles(x.shape, b.shape, chunk)
    # Batch first on every operand, so that a data mesh's shard_map takes
    # them with one spec.
    a = jnp.broadcast_to(a.astype(F32), (x.shape[0], x.shape[2]))
    return _fa._per_batch_shard(
        lambda *operands: _scan(*operands, chunk, jnp.dtype(dtype))
    )(x, dt.astype(F32), a, b, c)
