"""What lies between the q / k projections and the flash kernels in the
SDAR and the Mellum attention, as one Pallas kernel pair: each head's
RMSNorm, the rotary turn, the rounding to the activation dtype and the
change of layout.

    cos, sin = rope_tables(positions, inv_freq, scale)     # once a step
    q = qk_rotary(q_proj, q_norm_weight, eps, cos, sin)    # [B, H, S, Dh]

with `q_proj` [B, S, H, Dh] as the projection gives it, is

    swapaxes(rotary(rms_norm(q_proj, weight, eps), ...).astype(dtype), 1, 2)

of `models/lfm2/lfm2_moe.py:rotary` and `models/nemotron_h/nemotron_h.py:
rms_norm`: the norm and the turn in float32, one rounding at the end. Off
the TPU (the CPU test platform) the call is that expression over the same
tables (`_expression`), which is what the kernels are tested against. On the
TPU a head that is not whole rows of 128 lanes raises (LFM2's heads of 64
keep `rotary` and `rms_norm` at their call site).

Why kernels. As XLA's the stage is seven families of float32 fusions a
layer over [S, H, Dh], each a pass over HBM: 16.7% of the SDAR step and 10%
of the Mellum step (PERF.md section 6, PRs 52 and 53). The forward kernel
reads the bfloat16 projection once and writes the bfloat16 result once in
the flash kernels' layout; the backward reads the projection and the
cotangent [B, H, S, Dh] and writes the projection's cotangent [B, S, H *
Dh], with the norm recomputed: the residual is the projection itself and
nothing float32.

Why tables. cos and sin [1, S, Dh] in float32 are operands, built once a
step by `rope_tables` from the expression `rotary` builds them by, so their
bits are the parent's, every layer reads one pair, and the layers of a
YaRN table share the kernels' one trace with the layers of a default
table. ([1, S, Dh] and not [S, Dh]: a benchmark reader knows the routed
layers' operations by [rows, experts], which [S, Dh] is on the SDAR cut.)

What the set-up pays (PERF.md section 6, PR 53). Each `pallas_call` sits
under a jit of its own, sizes and eps static: its body is traced and
lowered once a shape a process (q's heads and k's, forward and backward:
four), not once a call site, of which a six-layer step has 24. Bodies and
index maps are `lax` primitives on traced values, as in `ops/ssd_scan.py`: a
`jnp` operator there is a nested jit to trace. What is still paid grows
with a body's size, not with its call sites: the heads are unrolled in the
body (a loop over traced rows ran the forward 1.5 to 4.5 times longer), and
an unrolled body costs by its length to trace, lower and load.

The grid is (batch, row tile); a block is ROWS rows by every head, and a
step walks it a head a turn, unrolled, the rows' cos and sin read once for
all their heads. d weight leaves as float32 partial sums a grid block, [B,
tiles, 8, Dh], summed by the caller.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.models.nemotron_h.nemotron_h import rms_norm
from elasticdl_tpu.ops import flash_attention as _fa
from elasticdl_tpu.ops.ssd_scan import F32, _sum, _to, _wide, _zeros

# A head's width is whole rows of lanes.
LANES = 128
# Rows of a block: 2 MiB of q's projection at 32 heads of 128. Every head
# of a block is a turn of the kernel's body, unrolled, so the rows are also
# what a body's trace costs: k's 4 heads at 2048 rows a block (eight turns
# of rows a head) ran 0.05 ms a call and traced as long as q's 32 (PERF.md
# section 6, PR 53).
ROWS = 256
# Sublanes of a float32 tile: d weight's partial sums keep them apart.
SUBLANES = 8


def rope_tables(positions, inv_freq, scale=None):
    """(cos, sin) float32 [1, S, Dh] of rows at `positions` [S] under the
    frequencies `inv_freq` [Dh / 2], times `scale` (YaRN's
    `attention_factor`) where there is one: `rotary`'s own expression, made
    once a step."""
    angles = positions.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None]

    def scaled(table):
        return table if scale is None else table * scale

    return scaled(jnp.cos(angles)), scaled(jnp.sin(angles))


def _expression(x, weight, eps, cos, sin):
    """The stage as XLA's: `rms_norm`, `rotary`'s turn over the tables, the
    rounding, the layout."""
    normed = rms_norm(x, weight, eps)
    x1, x2 = jnp.split(normed, 2, axis=-1)
    turned = normed * cos[:, :, None] + jnp.concatenate(
        [-x2, x1], axis=-1) * sin[:, :, None]
    return jnp.swapaxes(turned.astype(x.dtype), 1, 2)


# ---------- the kernels ----------


class _Tiles(NamedTuple):
    """The call's sizes: batch, rows, heads, a head's width, a block's
    rows."""

    bsz: int
    s: int
    heads: int
    dh: int
    tile: int

    @property
    def tiles(self):
        return self.s // self.tile


def _tiles(x_shape):
    """The sizes, or ValueError with the shape for what no tile serves."""
    bsz, s, heads, dh = x_shape

    def refuse(why):
        raise ValueError(
            f"qk_rotary: cannot tile x {tuple(x_shape)}: {why}")

    if dh % LANES:
        refuse(f"a head of {dh} channels is not whole rows of {LANES} "
               "lanes (such a head keeps rotary(rms_norm(.)) at its call "
               "site)")
    tile = ROWS
    while tile > 1 and s % tile:
        tile //= 2
    if tile % (2 * SUBLANES):
        refuse(f"sequence length {s} is not a multiple of "
               f"{2 * SUBLANES} rows")
    return _Tiles(bsz, s, heads, dh, tile)


def _half_turned(v, dh):
    """v's two halves changed over: a rotation by half the head."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(v, dh // 2, 1)


def _signed(sin):
    """sin with its first half negated: rotate_half(x) * sin is then
    (x's halves changed over) * this, the same products."""
    lane = lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    first = lax.lt(lane, lax.full(sin.shape, sin.shape[1] // 2, jnp.int32))
    return lax.select(first, lax.neg(sin), sin)


def _unit(x, eps):
    """(x / sqrt(mean(x^2) + eps), the divisor's reciprocal [rows, 1]) of
    float32 rows, as `rms_norm` makes it (the mean as the sum times 1 /
    width: the division's own bits at a width that is a power of two)."""
    mean = lax.mul(_sum(lax.mul(x, x), 1),
                   lax.full((x.shape[0], 1), 1.0 / x.shape[1], F32))
    inv = lax.rsqrt(lax.add(mean, lax.full(mean.shape, eps, F32)))
    return lax.mul(x, _wide(inv, x.shape)), inv


def _lanes(t, head):
    return slice(head * t.dh, (head + 1) * t.dh)


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, t, eps):
    weight = _wide(w_ref[:], (t.tile, t.dh))
    cos, sin = cos_ref[:], _signed(sin_ref[:])
    # A head a turn, unrolled: as a `fori_loop` over traced rows the call
    # took 1.85 ms at 32 rows a turn and 0.63 at 128, where this takes 0.41
    # (PERF.md section 6, PR 53).
    for head in range(t.heads):
        unit, _ = _unit(_to(x_ref[:, _lanes(t, head)], F32), eps)
        normed = lax.mul(unit, weight)
        o_ref[head] = _to(lax.add(
            lax.mul(normed, cos),
            lax.mul(_half_turned(normed, t.dh), sin)), o_ref.dtype)


def _bwd_kernel(x_ref, w_ref, cos_ref, sin_ref, g_ref, dx_ref, dw_ref,
                *, t, eps):
    weight = _wide(w_ref[:], (t.tile, t.dh))
    over_width = lax.full((t.tile, 1), 1.0 / t.dh, F32)
    cos, sin = cos_ref[:], _signed(sin_ref[:])
    found = _zeros((SUBLANES, t.dh))
    for head in range(t.heads):
        lanes = _lanes(t, head)
        unit, inv = _unit(_to(x_ref[:, lanes], F32), eps)
        g = _to(g_ref[head], F32)
        # The turn's transpose: the rotation by half a head is its own.
        dnormed = lax.add(
            lax.mul(g, cos), _half_turned(lax.mul(g, sin), t.dh))
        # d weight a sublane apart: rows 8 apart summed, no shuffle.
        found = lax.add(found, lax.reduce_sum(
            lax.reshape(lax.mul(dnormed, unit),
                        (t.tile // SUBLANES, SUBLANES, t.dh)), (0,)))
        dunit = lax.mul(dnormed, weight)
        along = lax.mul(_sum(lax.mul(dunit, unit), 1), over_width)
        dx_ref[:, lanes] = _to(lax.mul(
            lax.sub(dunit, lax.mul(unit, _wide(along, unit.shape))),
            _wide(inv, unit.shape)), dx_ref.dtype)
    dw_ref[:] = found


def _specs(t):
    """BlockSpecs over the grid (batch, row tile): the projection's block
    [tile, H * Dh], a table's [tile, Dh], the turned heads' [H, tile, Dh],
    the weight's [1, Dh], d weight's partial sums [8, Dh]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return (
        spec((None, t.tile, t.heads * t.dh), lambda b_, s_: (b_, s_, 0)),
        spec((None, t.tile, t.dh), lambda b_, s_: (b_, s_, 0)),
        spec((None, t.heads, t.tile, t.dh), lambda b_, s_: (b_, 0, s_, 0)),
        spec((None, 1, t.dh), lambda b_, s_: (b_, 0, 0)),
        spec((None, None, SUBLANES, t.dh), lambda b_, s_: (b_, s_, 0, 0)))


def _params(t, blocks, itemsize):
    """Every block double-buffered, and room for a turn's values."""
    from jax.experimental.pallas import tpu as pltpu

    block = t.tile * t.heads * t.dh * itemsize
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=2 * blocks * block + (16 << 20))


@functools.partial(jax.jit, static_argnames=("t", "eps", "interpret"))
def _forward(x, weight, cos, sin, *, t, eps, interpret):
    """The turned heads [B, H, S, Dh] from the projection [B, S, H, Dh].
    A jit of its own: the kernel's body is then traced and lowered once a
    process, not once a call site."""
    from jax.experimental import pallas as pl

    by_row, table, by_head, a_weight, _ = _specs(t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t, eps=eps),
        grid=(t.bsz, t.tiles),
        in_specs=[by_row, a_weight, table, table],
        out_specs=by_head,
        out_shape=jax.ShapeDtypeStruct(
            (t.bsz, t.heads, t.s, t.dh), x.dtype),
        compiler_params=_params(t, 3, x.dtype.itemsize),
        interpret=interpret,
        name="qk_rotary_fwd",
    )(lax.reshape(x, (t.bsz, t.s, t.heads * t.dh)), weight, cos, sin)


@functools.partial(jax.jit, static_argnames=("t", "eps", "interpret"))
def _backward(x, weight, cos, sin, g, *, t, eps, interpret):
    """The projection's cotangent [B, S, H, Dh] and d weight [B, 1, Dh]
    (float32) from the projection and the turned heads' cotangent
    [B, H, S, Dh]. A jit of its own."""
    from jax.experimental import pallas as pl

    by_row, table, by_head, a_weight, partial_sums = _specs(t)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, t=t, eps=eps),
        grid=(t.bsz, t.tiles),
        in_specs=[by_row, a_weight, table, table, by_head],
        out_specs=[by_row, partial_sums],
        out_shape=[
            jax.ShapeDtypeStruct((t.bsz, t.s, t.heads * t.dh), x.dtype),
            jax.ShapeDtypeStruct((t.bsz, t.tiles, SUBLANES, t.dh), F32),
        ],
        compiler_params=_params(t, 4, x.dtype.itemsize),
        interpret=interpret,
        name="qk_rotary_bwd",
    )(lax.reshape(x, (t.bsz, t.s, t.heads * t.dh)), weight, cos, sin, g)
    return (lax.reshape(dx, x.shape),
            lax.reshape(lax.reduce_sum(dw, (1, 2)), (t.bsz, 1, t.dh)))


# ---------- the op ----------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _turned(x, weight, cos, sin, eps):
    return _turned_fwd(x, weight, cos, sin, eps)[0]


def _turned_fwd(x, weight, cos, sin, eps):
    t = _tiles(x.shape)
    out = _forward(x, weight, cos, sin, t=t, eps=eps,
                   interpret=_fa._interpret())
    return out, (x, weight, cos, sin)


def _turned_bwd(eps, residuals, g):
    x, weight, cos, sin = residuals
    t = _tiles(x.shape)
    dx, dw = _backward(x, weight, cos, sin, g, t=t, eps=eps,
                       interpret=_fa._interpret())
    # The tables are the step's constants: no cotangent.
    return dx, dw, None, None


_turned.defvjp(_turned_fwd, _turned_bwd)


def _a_batch_row(v, bsz):
    """v [1, ...] as a copy a batch row: a data mesh's shard_map then
    takes every operand with one spec."""
    shape = (bsz, *v.shape[1:])
    if v.shape == shape:
        return v
    return lax.broadcast_in_dim(v, shape, tuple(range(v.ndim)))


def qk_rotary(x, weight, eps, cos, sin):
    """x [B, S, H, Dh] (a projection, in the activation dtype) -> [B, H, S,
    Dh] in that dtype: each head's RMSNorm under `weight` [Dh], turned by
    `rope_tables`' cos and sin [1, S, Dh], rounded once. As the kernels
    where they run (the TPU, or the CPU under the test-only interpret
    switch), where a shape they cannot tile raises; elsewhere the
    expression itself."""
    if not _fa._use_pallas():
        return _expression(x, weight, eps, cos, sin)
    bsz, dh = x.shape[0], x.shape[3]
    weight = lax.reshape(_to(weight, F32), (1, 1, dh))
    return _fa._per_batch_shard(
        lambda *operands: _turned(*operands, float(eps))
    )(x, *(_a_batch_row(v, bsz) for v in (weight, cos, sin)))
