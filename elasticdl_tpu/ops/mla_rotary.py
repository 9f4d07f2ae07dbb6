"""What lies between the three projections of the Kanana latent attention
and the flash kernels, as one Pallas pass for q and one for k and v, each
way: the rope turn over the last third of a head, the join of the two
parts, the rope key's copy to every head, the split of k_nope and v and the
change of layout.

    cos, sin = rope_tables(S, theta, rope)                 # once a step
    q, k, v = mla_rotary(q_proj, kv_up, k_rope, cos, sin)
    # q_proj [B, S, H, nope + rope], kv_up [B, S, H, nope + dv],
    # k_rope [B, S, rope]  ->  q, k [B, H, S, nope + rope], v [B, H, S, dv]

is, with the projections as the published weights give them (a head's
columns `nope | rope`, the rope channels paired (2i, 2i + 1); `k_nope | v`),

    q_rope, k_rope = rotary(., interleave=True).astype(dtype)   # float32 turn
    q = swapaxes([q_nope | q_rope], 1, 2)
    k = swapaxes([k_nope | k_rope for every head], 1, 2), v = swapaxes(v, 1, 2)

of `models/lfm2/lfm2_moe.py:rotary`: the rope channels brought to
evens-then-odds (the order the flash kernel sums a score's 192 products
in), turned in float32 and rounded once; q_nope, k_nope and v with the bits
the projections gave them. The stage has no weight and no norm: it is
linear, its backward needs the tables and nothing of the forward, and d
k_rope is the heads' rope lanes summed in float32, turned back and rounded
once (the expression sums them in the activation dtype first). Off the TPU
the call is that expression over the same tables (`_expression`), which is
what the kernels are tested against. Where the kernels run, anything but
heads of `nope` = `dv` = 2 `rope` = 128 lanes, an even number of them,
raises; activations that are not bfloat16 (the float32 toy models of the
tests) keep the expression there too, and the log says so once a dtype.

Why kernels. As XLA's the stage was nineteen families of fusions and copies
a layer over [S, 32, w], each a pass over HBM: 15.5% of the Kanana step
(PERF.md section 6, PR 58). A forward pass here reads each projection once
and writes q, k and v once in the flash kernels' layout; a backward reads
their cotangents and writes the projections'.

Why the matrix unit moves lanes. A head of 192 lanes is not whole rows of
128: in [rows, H * 192] every odd head starts half a row in, and the
published rope pairing is a stride-2 gather along lanes. The body reads
whole rows only. A PAIR of heads is three rows, `nope_a`, `rope_a |
nope_b's first half`, `nope_b's second half | rope_b`: two selects by lane
and a rotation by half a row part them. What reorders lanes inside a row
(the pairing to evens-then-odds and back; a head's 64 rope lanes into a
row's first or second half) is a product with a 0 / 1 matrix [128, 128] on
the matrix unit, which idles here: bfloat16 in, float32 out, each output
one input times 1, so exact (but for the sign of a zero). Nothing float32
is reordered: the backward places, turns back, rounds and only then pairs.
The weights keep their published columns; a checkpoint is the layout.

Why tables a pair of heads wide. cos and sin [1, S, 2 * rope] float32 are
`rotary`'s own expression (`qk_rotary.rope_tables`) twice side by side: two
heads' ropes fill one row of lanes, the rotation by half a rope is then two
`pltpu.roll`s and a select, and every layer reads one pair of tables.

What the set-up pays (PERF.md section 6, PRs 53 and 58): as in
`ops/qk_rotary.py`, each `pallas_call` under a jit of its own with static
sizes (four bodies a process, not one a call site: a six-layer step has 34)
and bodies and index maps in `lax` primitives. Unlike it, the pair of heads
is a GRID axis, (batch, row block, pair), and not a turn of an unrolled
body: with the 16 pairs unrolled at 256 rows a block the first dispatch of
the Kanana step grew by 3.1 s, with a body one pair long at 2048 rows by
nothing, for 0.2% of the rate (128 grid steps a call). The one rope key is
turned at a row block's first pair and kept in VMEM for the others; d
k_rope's float32 sum is carried there and turned back at the last.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.ops import flash_attention as _fa
from elasticdl_tpu.ops import qk_rotary as _qk
from elasticdl_tpu.ops.qk_rotary import LANES, SUBLANES, _a_batch_row
from elasticdl_tpu.ops.ssd_scan import F32, _dot, _to, _zeros

logger = get_logger("ops.mla_rotary")

# Rows of a block. A grid step is one PAIR of heads of a block of rows (1.5
# MiB of q's projection, 2 MiB of the up-projection), so a body is one
# pair long whatever the number of heads: what a body costs to trace, lower
# and load grows with its length (PERF.md section 6, PRs 53 and 58), and
# what a grid step costs by its number, 128 a call at the cell's sizes.
ROWS = 2048
VMEM_BYTES = 48 << 20


def rope_tables(s, theta, rope):
    """(cos, sin) float32 [1, S, 2 * rope] of rows 0 .. S - 1 under
    `theta`: `rotary`'s tables of a head of `rope` channels, twice side by
    side. Made once a step, inside its jit."""
    cos, sin = _qk.rope_tables(
        jnp.arange(s), theta ** (-jnp.arange(0, rope, 2, dtype=F32) / rope))
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([sin, sin], axis=-1))


def _expression(q_proj, kv_up, k_rope, cos, sin):
    """The stage as XLA's: the splits, `rotary`'s turn over the tables, the
    rounding, the joins, the copy to the heads, the layout."""
    rope = k_rope.shape[-1]
    nope = q_proj.shape[-1] - rope
    cos, sin = (t[:, :, None, :rope] for t in (cos, sin))

    def turned(x):
        x = x.astype(F32)
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return (x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(
            q_proj.dtype)

    q_nope, q_rope = jnp.split(q_proj, [nope], axis=-1)
    k_nope, v = jnp.split(kv_up, [nope], axis=-1)
    q_rope, k_rope = turned(q_rope), turned(k_rope[:, :, None, :])
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    return tuple(jnp.swapaxes(t, 1, 2) for t in (q, k, v))


# ---------- the kernels ----------


class _Tiles(NamedTuple):
    """The call's sizes: batch, rows, heads, a rope's channels (a row of
    lanes is two of them: `nope`, `dv` and the tables' width), a block's
    rows."""

    bsz: int
    s: int
    heads: int
    rope: int
    tile: int

    @property
    def row(self):
        return 2 * self.rope

    @property
    def tiles(self):
        return self.s // self.tile


def _block_rows(s):
    """The most rows of a block, up to ROWS, that divide the sequence."""
    tile = ROWS
    while tile > 1 and s % tile:
        tile //= 2
    return tile


def _tiles(q_proj, kv_up, k_rope, cos):
    """The sizes, or ValueError with the shapes for what no tile serves."""
    bsz, s, heads, dk = q_proj.shape
    rope = k_rope.shape[-1]

    def refuse(why):
        raise ValueError(
            f"mla_rotary: cannot tile q {q_proj.shape}, kv {kv_up.shape}, "
            f"k_rope {k_rope.shape}, tables {cos.shape}: {why}")

    if (dk, kv_up.shape[-1], cos.shape[-1]) != (3 * rope, 4 * rope, 2 * rope):
        refuse("the kernels take nope = dv = 2 rope channels and tables a "
               "pair of heads wide")
    if heads % 2:
        refuse(f"{heads} heads are not pairs")
    if 2 * rope != LANES and not _fa._interpret():
        refuse(f"a rope of {rope} channels is not half a row of {LANES} "
               "lanes")
    tile = _block_rows(s)
    if tile % (2 * SUBLANES):
        refuse(f"sequence length {s} is not a multiple of "
               f"{2 * SUBLANES} rows")
    return _Tiles(bsz, s, heads, rope, tile)


def _lane_moves(rope, dtype):
    """[2, 2 rope, 2 rope] of 0 and 1 in `dtype`. [0]: x @ it brings a
    row's two ropes from the published pairing (2i, 2i + 1) to evens then
    odds (contracted over its columns, back). [1]: the identity, whose first
    and last `rope` rows place a rope in a row's first or second half."""
    lane = np.arange(2 * rope)
    within = lane % rope
    source = lane - within + 2 * (within % (rope // 2)) + within // (
        rope // 2)
    pairing = np.zeros((2 * rope, 2 * rope), np.float32)
    pairing[source, lane] = 1
    return jnp.asarray(
        np.stack([pairing, np.eye(2 * rope, dtype=np.float32)]), dtype)


def _roll(x, shift):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift, 1)


def _words(x):
    """bfloat16 rows as half as many rows of 32 bits, lane for lane: what
    Mosaic rotates (16-bit rows it does not), and a select by lane and a
    rotation along the lanes do not see which rows share a word."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(x, jnp.uint32)


def _rows(words, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.bitcast(words, dtype)


def _lane_below(shape, size, below):
    """lane % size < below."""
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return lax.lt(lax.rem(lane, lax.full(shape, size, jnp.int32)),
                  lax.full(shape, below, jnp.int32))


def _halves_changed(x, t):
    """Each rope's two halves changed over, in a row of two ropes."""
    half = t.rope // 2
    return lax.select(_lane_below(x.shape, t.rope, half),
                      _roll(x, t.row - half), _roll(x, half))


def _tables(cos_ref, sin_ref, t):
    """cos, and sin with each rope's first half negated: rotate_half(x) *
    sin is then (x's halves changed over) * this, the same products."""
    sin = sin_ref[:]
    return cos_ref[:], lax.select(
        _lane_below(sin.shape, t.rope, t.rope // 2), lax.neg(sin), sin)


def _turned(x, cos, sin, t):
    return lax.add(lax.mul(x, cos), lax.mul(_halves_changed(x, t), sin))


def _turned_back(g, cos, sin, t):
    """The turn's transpose: the change of halves is its own."""
    return lax.add(lax.mul(g, cos), _halves_changed(lax.mul(g, sin), t))


def _paired(d, moves_ref):
    """Float32 rope cotangents, evens then odds, rounded once and brought
    back to the published pairing."""
    return _to(_dot(_to(d, moves_ref.dtype), moves_ref[0], 1, 1),
               moves_ref.dtype)


def _q_fwd_kernel(x_ref, moves_ref, cos_ref, sin_ref, o_ref, *, t):
    """A pair of heads: three rows of lanes, nope_a, rope_a | nope_b's
    first half, nope_b's second half | rope_b."""
    w, rope = t.row, t.rope
    cos, sin = _tables(cos_ref, sin_ref, t)
    first = _lane_below((t.tile // 2, w), w, rope)
    mid, last = _words(x_ref[:, w:2 * w]), _words(x_ref[:, 2 * w:])
    o_ref[0, :, :w] = x_ref[:, :w]
    o_ref[1, :, :w] = _rows(
        _roll(lax.select(first, last, mid), rope), o_ref.dtype)
    ropes = _turned(
        _dot(_rows(lax.select(first, mid, last), o_ref.dtype),
             moves_ref[0], 1, 0),
        cos, sin, t)
    o_ref[0, :, w:] = _to(ropes[:, :rope], o_ref.dtype)
    o_ref[1, :, w:] = _to(_roll(ropes, rope)[:, :rope], o_ref.dtype)


def _q_bwd_kernel(g_ref, moves_ref, cos_ref, sin_ref, dx_ref, *, t):
    w, rope = t.row, t.rope
    cos, sin = _tables(cos_ref, sin_ref, t)
    first = _lane_below((t.tile // 2, w), w, rope)
    placed = lax.add(
        _dot(g_ref[0, :, w:], moves_ref[1, :rope, :], 1, 0),
        _dot(g_ref[1, :, w:], moves_ref[1, rope:, :], 1, 0))
    ropes = _words(_paired(_turned_back(placed, cos, sin, t), moves_ref))
    nope_b = _roll(_words(g_ref[1, :, :w]), rope)
    dx_ref[:, :w] = g_ref[0, :, :w]
    dx_ref[:, w:2 * w] = _rows(
        lax.select(first, ropes, nope_b), dx_ref.dtype)
    dx_ref[:, 2 * w:] = _rows(
        lax.select(first, nope_b, ropes), dx_ref.dtype)


def _at_pair(t, which, body):
    """`body()` at a row block's first (0) or last (-1) pair of heads."""
    from jax.experimental import pallas as pl

    pair = lax.full((), which % (t.heads // 2), jnp.int32)
    return pl.when(lax.eq(pl.program_id(2), pair))(body)


def _kv_fwd_kernel(kv_ref, kr_ref, moves_ref, cos_ref, sin_ref,
                   k_ref, v_ref, turned_ref, *, t):
    w, rope = t.row, t.rope

    def turn():
        # The one rope key, turned once a block of rows for all its pairs
        # of heads (a row's first half; the second is zeros).
        cos, sin = _tables(cos_ref, sin_ref, t)
        turned_ref[:] = _to(_turned(
            _dot(kr_ref[:], moves_ref[0, :rope, :], 1, 0), cos, sin, t
        )[:, :rope], turned_ref.dtype)

    _at_pair(t, 0, turn)
    for head in range(2):
        k_ref[head, :, :w] = kv_ref[:, 2 * head * w:(2 * head + 1) * w]
        k_ref[head, :, w:] = turned_ref[:]
        v_ref[head] = kv_ref[:, (2 * head + 1) * w:(2 * head + 2) * w]


def _kv_bwd_kernel(dk_ref, dv_ref, moves_ref, cos_ref, sin_ref,
                   dkv_ref, dkr_ref, summed_ref, *, t):
    w, rope = t.row, t.rope

    def clear():
        summed_ref[:] = _zeros(summed_ref.shape)

    def turn_back():
        cos, sin = _tables(cos_ref, sin_ref, t)
        dkr_ref[:] = _paired(
            _turned_back(summed_ref[:], cos, sin, t), moves_ref)[:, :rope]

    _at_pair(t, 0, clear)
    for head in range(2):
        dkv_ref[:, 2 * head * w:(2 * head + 1) * w] = dk_ref[head, :, :w]
        dkv_ref[:, (2 * head + 1) * w:(2 * head + 2) * w] = dv_ref[head]
        # The heads' rope lanes summed in float32, in a row's first half.
        summed_ref[:] = lax.add(summed_ref[:], _dot(
            dk_ref[head, :, w:], moves_ref[1, :rope, :], 1, 0))
    _at_pair(t, -1, turn_back)


def _specs(t):
    """BlockSpecs over the grid (batch, row tile, pair of heads): (a
    pair's columns of a projection [tile, 2 * width], its heads' [2, tile,
    width], both by width in ropes), the rope key's [tile, rope], the lane
    moves', a table's [tile, 2 rope]. What no pair indexes is fetched once
    a block of rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def by_row(ropes):
        return spec((None, t.tile, 2 * ropes * t.rope),
                    lambda b_, s_, p_: (b_, s_, p_))

    def by_head(ropes):
        return spec((None, 2, t.tile, ropes * t.rope),
                    lambda b_, s_, p_: (b_, p_, s_, 0))

    return (by_row, by_head,
            spec((None, t.tile, t.rope), lambda b_, s_, p_: (b_, s_, 0)),
            spec((2, t.row, t.row), lambda b_, s_, p_: (0, 0, 0)),
            spec((None, t.tile, t.row), lambda b_, s_, p_: (b_, s_, 0)))


def _grid(t):
    return (t.bsz, t.tiles, t.heads // 2)


def _params():
    """The pairs of a block of rows in turn: the rope key's turn and d
    k_rope's sum are carried from one to the next."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES)


def _scratch(t, width, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((t.tile, width), dtype)]


def _heads_shape(t, ropes, dtype):
    return jax.ShapeDtypeStruct(
        (t.bsz, t.heads, t.s, ropes * t.rope), dtype)


def _rows_shape(t, ropes, dtype):
    return jax.ShapeDtypeStruct(
        (t.bsz, t.s, t.heads * ropes * t.rope), dtype)


def _flat(x):
    """[B, S, H, width] -> [B, S, H * width]."""
    return lax.reshape(x, (*x.shape[:2], x.shape[2] * x.shape[3]))


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _q_forward(q_proj, cos, sin, *, t, interpret):
    """q [B, H, S, 3 rope] from the projection [B, S, H, 3 rope]. A jit of
    its own: the kernel's body is then traced and lowered once a process,
    not once a call site."""
    from jax.experimental import pallas as pl

    by_row, by_head, _, moves, table = _specs(t)
    return pl.pallas_call(
        functools.partial(_q_fwd_kernel, t=t),
        grid=_grid(t),
        in_specs=[by_row(3), moves, table, table],
        out_specs=by_head(3),
        out_shape=_heads_shape(t, 3, q_proj.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_rotary_q_fwd",
    )(_flat(q_proj), _lane_moves(t.rope, q_proj.dtype),
      cos, sin)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _q_backward(g, cos, sin, *, t, interpret):
    """The query projection's cotangent [B, S, H, 3 rope] from q's
    [B, H, S, 3 rope]. A jit of its own."""
    from jax.experimental import pallas as pl

    by_row, by_head, _, moves, table = _specs(t)
    dx = pl.pallas_call(
        functools.partial(_q_bwd_kernel, t=t),
        grid=_grid(t),
        in_specs=[by_head(3), moves, table, table],
        out_specs=by_row(3),
        out_shape=_rows_shape(t, 3, g.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_rotary_q_bwd",
    )(g, _lane_moves(t.rope, g.dtype), cos, sin)
    return lax.reshape(dx, (t.bsz, t.s, t.heads, 3 * t.rope))


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _kv_forward(kv_up, k_rope, cos, sin, *, t, interpret):
    """k [B, H, S, 3 rope] and v [B, H, S, 2 rope] from the up-projection
    [B, S, H, 4 rope] and the one rope key [B, S, rope]. A jit of its
    own."""
    from jax.experimental import pallas as pl

    by_row, by_head, a_rope, moves, table = _specs(t)
    return pl.pallas_call(
        functools.partial(_kv_fwd_kernel, t=t),
        grid=_grid(t),
        in_specs=[by_row(4), a_rope, moves, table, table],
        out_specs=[by_head(3), by_head(2)],
        out_shape=[_heads_shape(t, 3, kv_up.dtype),
                   _heads_shape(t, 2, kv_up.dtype)],
        scratch_shapes=_scratch(t, t.rope, kv_up.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_rotary_kv_fwd",
    )(_flat(kv_up), k_rope,
      _lane_moves(t.rope, kv_up.dtype), cos, sin)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _kv_backward(dk, dv, cos, sin, *, t, interpret):
    """The up-projection's cotangent [B, S, H, 4 rope] and the rope key's
    [B, S, rope] from k's and v's. A jit of its own."""
    from jax.experimental import pallas as pl

    by_row, by_head, a_rope, moves, table = _specs(t)
    dkv, dkr = pl.pallas_call(
        functools.partial(_kv_bwd_kernel, t=t),
        grid=_grid(t),
        in_specs=[by_head(3), by_head(2), moves, table, table],
        out_specs=[by_row(4), a_rope],
        out_shape=[_rows_shape(t, 4, dk.dtype),
                   jax.ShapeDtypeStruct((t.bsz, t.s, t.rope), dk.dtype)],
        scratch_shapes=_scratch(t, t.row, F32),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_rotary_kv_bwd",
    )(dk, dv, _lane_moves(t.rope, dk.dtype), cos, sin)
    return lax.reshape(dkv, (t.bsz, t.s, t.heads, 4 * t.rope)), dkr


# ---------- the op ----------


@jax.custom_vjp
def _joined(q_proj, kv_up, k_rope, cos, sin):
    return _joined_fwd(q_proj, kv_up, k_rope, cos, sin)[0]


def _joined_fwd(q_proj, kv_up, k_rope, cos, sin):
    t = _tiles(q_proj, kv_up, k_rope, cos)
    interpret = _fa._interpret()
    k, v = _kv_forward(kv_up, k_rope, cos, sin, t=t, interpret=interpret)
    # Linear: the backward reads the tables and nothing of the forward.
    return (_q_forward(q_proj, cos, sin, t=t, interpret=interpret), k, v), (
        cos, sin)


def _joined_bwd(tables, cotangents):
    dq, dk, dv = cotangents
    bsz, heads, s, dk_width = dq.shape
    t = _Tiles(bsz, s, heads, dk_width // 3, _block_rows(s))
    interpret = _fa._interpret()
    dkv, dkr = _kv_backward(dk, dv, *tables, t=t, interpret=interpret)
    # The tables are the step's constants: no cotangent.
    return (_q_backward(dq, *tables, t=t, interpret=interpret), dkv, dkr,
            None, None)


_joined.defvjp(_joined_fwd, _joined_bwd)


@functools.lru_cache(maxsize=None)
def _say_the_expression_runs(dtype):
    logger.warning(
        "mla_rotary: %s activations run the XLA expression where the "
        "kernels run: the matrix unit moves bfloat16 lanes exactly and no "
        "wider ones", dtype)


def mla_rotary(q_proj, kv_up, k_rope, cos, sin):
    """(q, k [B, H, S, nope + rope], v [B, H, S, dv]) in the activation
    dtype from the query projection [B, S, H, nope + rope], the latent's
    up-projection [B, S, H, nope + dv] and the one rope key [B, S, rope],
    under `rope_tables`' cos and sin [1, S, 2 rope]. As the kernels where
    they run (the TPU, or the CPU under the test-only interpret switch),
    where shapes they cannot tile raise and any dtype but bfloat16 is the
    expression and logged once; elsewhere the expression itself."""
    if not _fa._use_pallas():
        return _expression(q_proj, kv_up, k_rope, cos, sin)
    if q_proj.dtype != jnp.bfloat16:
        _say_the_expression_runs(str(q_proj.dtype))
        return _expression(q_proj, kv_up, k_rope, cos, sin)
    bsz = q_proj.shape[0]
    return _fa._per_batch_shard(_joined)(
        q_proj, kv_up, k_rope,
        *(_a_batch_row(v, bsz) for v in (cos, sin)))
