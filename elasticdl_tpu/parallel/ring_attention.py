"""Ring attention: exact attention over sequences sharded across devices.

Capability extension beyond the reference (which is DP-only; SURVEY.md §5
marks long-context absent upstream). Each device holds one sequence block of
Q/K/V; K/V blocks rotate around the mesh's sequence axis with
`lax.ppermute` while every device folds each arriving block into a running
online-softmax accumulator (max, sum, acc) — the blockwise-parallel /
RingAttention scheme. Communication rides ICI; compute between hops is a
dense [S_local x S_local] attention block on the MXU, so the transfer of the
next block overlaps the math of the current one under XLA's async
collectives.

Causality across blocks uses the GLOBAL block order: device i skips blocks
j > i entirely (they're fully masked) and applies the triangular mask only
on its own diagonal block.
"""

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def seq_axis_demand(context_parallel):
    """Sequence/context parallelism's mesh-axis contribution to world
    resolution (shared by ring attention and ulysses.py — both shard the
    same "seq" axis): intra-process, like model/stage, and the first
    axis the resolver drops when the trailing product stops dividing a
    world (the plain model trains identically without SP)."""
    from elasticdl_tpu.parallel.mesh import SEQ_AXIS, AxisDemand

    return AxisDemand(SEQ_AXIS, int(context_parallel), intra_process=True)


def _block_attend(q, k, v, scale, mask=None):
    """One blockwise contribution: returns (m, l, acc) for q against this
    k/v block. q: [B,H,Sq,D]; k,v: [B,H,Sk,D]."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, acc


def _merge(m1, l1, acc1, m2, l2, acc2):
    """Merge two online-softmax partials (the associative combine)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return (
        m,
        l1 * a1 + l2 * a2,
        acc1 * a1[..., None] + acc2 * a2[..., None],
    )


def _rotate_next(blocks, t, axis_name, axis_size):
    """Ring-shift K/V blocks to the next device for step t+1. Issued
    BEFORE the step's attention math (no data dependence on it), so XLA's
    async collectives stream the transfer over ICI while the MXU chews on
    the current block. Skipped after the last fold — the rotated blocks
    would be discarded, saving one full K/V hop per attention call. All
    devices see the same t, so the cond branches uniformly and the
    collective stays legal."""

    def rotate(bs):
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        return tuple(
            jax.lax.ppermute(b, axis_name, perm) for b in bs
        )

    return jax.lax.cond(
        t + 1 < axis_size, rotate, lambda bs: bs, tuple(blocks)
    )


def refuse_mask_description(causal):
    """Sequence parallelism takes `causal` alone: a mask description of
    the flash kernels (`Band`, `BlockDiffusion`) is not built under ring or
    Ulysses attention, and is truthy, so it would run as the causal mask."""
    if isinstance(causal, tuple):
        raise ValueError(
            f"{causal} under ring / Ulysses attention is not built: they "
            "take causal=True or False")


def refuse_unequal_widths(q, v):
    """Sequence parallelism takes one head width: a key width beside a
    value width (latent attention through the flash kernels) is not built
    under ring or Ulysses attention."""
    if q.shape[-1] != v.shape[-1]:
        raise ValueError(
            f"keys of {q.shape[-1]} against values of {v.shape[-1]} under "
            "ring / Ulysses attention are not built: they take one head "
            "width")


def ring_attention(q, k, v, axis_name, causal=False):
    """Exact attention with Q/K/V sharded [B, H, S_local, D] along
    `axis_name`. Call INSIDE shard_map; returns the local output block.
    """
    refuse_mask_description(causal)
    refuse_unequal_widths(q, v)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    s_local = q.shape[2]

    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)

    # Ring: at step t this device holds the K/V block originally owned by
    # device (my_idx - t) mod N.
    def step(t, carry):
        m, l, acc, k_blk, v_blk = carry
        owner = (my_idx - t) % axis_size
        k_next, v_next = _rotate_next(
            (k_blk, v_blk), t, axis_name, axis_size
        )
        if causal:
            # Full block mask decisions by global block order.
            def masked_block():
                q_pos = my_idx * s_local + jax.lax.broadcasted_iota(
                    jnp.int32, (s_local, k_blk.shape[2]), 0
                )
                k_pos = owner * s_local + jax.lax.broadcasted_iota(
                    jnp.int32, (s_local, k_blk.shape[2]), 1
                )
                return _block_attend(
                    q, k_blk, v_blk, scale, mask=(q_pos >= k_pos)
                )

            def skip_block():
                return (
                    jnp.full(q.shape[:-1], NEG_INF, jnp.float32),
                    jnp.zeros(q.shape[:-1], jnp.float32),
                    jnp.zeros(q.shape, jnp.float32),
                )

            mb, lb, accb = jax.lax.cond(
                owner <= my_idx, masked_block, skip_block
            )
        else:
            mb, lb, accb = _block_attend(q, k_blk, v_blk, scale)
        m, l, acc = _merge(m, l, acc, mb, lb, accb)
        return m, l, acc, k_next, v_next

    m, l, acc, _, _ = jax.lax.fori_loop(
        0, axis_size, step, (m0, l0, acc0, k, v)
    )
    return (acc / l[..., None]).astype(q.dtype)


def make_ring_attention(mesh, axis_name="seq", causal=False,
                        batch_axis=None, head_axis=None):
    """shard_map-wrapped ring attention: takes GLOBAL [B, H, S, D] arrays
    sharded on S (and optionally on B along `batch_axis` for DP+SP meshes,
    and on H along `head_axis` for TP composition — heads are embarrassingly
    parallel in attention, so a head shard just runs its own ring) and
    returns the global output with the same sharding."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(batch_axis, head_axis, axis_name, None)
    return shard_map(
        functools.partial(
            ring_attention, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


# ---------- zigzag variant: balanced causal ring ----------
#
# Plain causal ring attention is load-imbalanced: with contiguous sequence
# sharding, device 0 computes 1 block while device N-1 computes N (early
# ranks idle through skipped blocks). The zigzag assignment splits the
# sequence into 2N half-chunks and gives device r chunks (r, 2N-1-r) — one
# early + one late — so EVERY device computes exactly 2 half-blocks per ring
# step (3 on its diagonal step): per-step critical path ~halves and total
# work equalizes at 2N+1 half-blocks per device.


def _zigzag_perms(axis_size):
    """Static ppermutes moving half-chunks between contiguous and zigzag
    layouts. Contiguous: device d holds chunks (2d, 2d+1). Zigzag: chunk c
    lives on device c if c < N else 2N-1-c."""
    n = axis_size

    def owner(c):
        return c if c < n else 2 * n - 1 - c

    # First/second local halves, contiguous -> zigzag.
    fwd0 = [(d, owner(2 * d)) for d in range(n)]
    fwd1 = [(d, owner(2 * d + 1)) for d in range(n)]
    inv0 = [(dst, src) for src, dst in fwd0]
    inv1 = [(dst, src) for src, dst in fwd1]
    return fwd0, fwd1, inv0, inv1


def zigzag_ring_attention(q, k, v, axis_name, causal=True):
    """Balanced causal ring attention; call INSIDE shard_map with Q/K/V
    sharded [B, H, S_local, D] contiguously along `axis_name`. The zigzag
    relayout is internal: inputs/outputs stay contiguously sharded."""
    refuse_mask_description(causal)
    refuse_unequal_widths(q, v)
    if not causal:
        return ring_attention(q, k, v, axis_name, causal=False)
    axis_size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    s_local = q.shape[2]
    s_half = s_local // 2
    fwd0, fwd1, inv0, inv1 = _zigzag_perms(axis_size)

    def to_zigzag(x):
        # Send my first half (chunk 2my) and second half (chunk 2my+1) to
        # their zigzag owners; each device receives exactly one chunk per
        # permutation. Which received piece is the EARLY chunk (id my)
        # depends on my parity: chunk my arrived via perm (my % 2).
        a = jax.lax.ppermute(x[:, :, :s_half], axis_name, fwd0)
        b = jax.lax.ppermute(x[:, :, s_half:], axis_name, fwd1)
        even = (my % 2) == 0
        early = jnp.where(even, a, b)
        late = jnp.where(even, b, a)
        return early, late  # global chunks (my, 2N-1-my)

    def from_zigzag(early, late):
        # Inverse: chunk my returns via inv(my%2); chunk 2N-1-my via the
        # other (2N-1-my has opposite parity). Each device gets its chunk
        # 2d back through inv0 and 2d+1 through inv1.
        even = (my % 2) == 0
        via0 = jnp.where(even, early, late)
        via1 = jnp.where(even, late, early)
        first = jax.lax.ppermute(via0, axis_name, inv0)
        second = jax.lax.ppermute(via1, axis_name, inv1)
        return jnp.concatenate([first, second], axis=2)

    q_e, q_l = to_zigzag(q)
    k_e, k_l = to_zigzag(k)
    v_e, v_l = to_zigzag(v)

    shape_stats = q_e.shape[:-1]

    def empty():
        return (
            jnp.full(shape_stats, NEG_INF, jnp.float32),
            jnp.zeros(shape_stats, jnp.float32),
            jnp.zeros(q_e.shape, jnp.float32),
        )

    def diag_mask(sk):
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (s_half, sk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (s_half, sk), 1)
        return q_pos >= k_pos

    def step(t, carry):
        me, le, ae, ml, ll, al, ke, kl, ve, vl = carry
        owner = (my - t) % axis_size
        ke_n, kl_n, ve_n, vl_n = _rotate_next(
            (ke, kl, ve, vl), t, axis_name, axis_size
        )

        # q early (chunk my) vs k early (chunk owner): full if owner < my,
        # diagonal if owner == my, skip if owner > my.
        def qe_ke():
            return jax.lax.cond(
                owner == my,
                lambda: _block_attend(
                    q_e, ke, ve, scale, mask=diag_mask(ke.shape[2])
                ),
                lambda: _block_attend(q_e, ke, ve, scale),
            )

        c1 = jax.lax.cond(owner <= my, qe_ke, empty)
        me, le, ae = _merge(me, le, ae, *c1)

        # q late (chunk 2N-1-my) vs k early (chunk owner < N): always full.
        c2 = _block_attend(q_l, ke, ve, scale)
        ml, ll, al = _merge(ml, ll, al, *c2)

        # q late vs k late (chunk 2N-1-owner): full if owner > my (earlier
        # chunk), diagonal if owner == my, skip if owner < my.
        def ql_kl():
            return jax.lax.cond(
                owner == my,
                lambda: _block_attend(
                    q_l, kl, vl, scale, mask=diag_mask(kl.shape[2])
                ),
                lambda: _block_attend(q_l, kl, vl, scale),
            )

        c3 = jax.lax.cond(owner >= my, ql_kl, empty)
        ml, ll, al = _merge(ml, ll, al, *c3)
        # (q early vs k late is always in the future: never computed.)
        return me, le, ae, ml, ll, al, ke_n, kl_n, ve_n, vl_n

    m0e, l0e, a0e = empty()
    m0l, l0l, a0l = empty()
    me, le, ae, ml, ll, al, *_ = jax.lax.fori_loop(
        0, axis_size, step,
        (m0e, l0e, a0e, m0l, l0l, a0l, k_e, k_l, v_e, v_l),
    )
    out_e = (ae / le[..., None]).astype(q.dtype)
    out_l = (al / ll[..., None]).astype(q.dtype)
    return from_zigzag(out_e, out_l)


def make_zigzag_ring_attention(mesh, axis_name="seq", causal=True,
                               batch_axis=None, head_axis=None):
    """shard_map-wrapped zigzag ring attention (balanced causal SP). Same
    contract as make_ring_attention; requires an even per-device sequence."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(batch_axis, head_axis, axis_name, None)
    return shard_map(
        functools.partial(
            zigzag_ring_attention, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
