"""Switch-style mixture-of-experts FFN with expert parallelism.

No reference counterpart (the reference is DP-only, SURVEY.md §2.10); this
extends the parallel story with EP. TPU-first design: top-1 routing with
FIXED capacity so every shape is static under jit — dispatch and combine
are one-hot einsums (MXU work, no scatter), and the expert weight tensors
[E, ...] shard over an "expert" mesh axis via plain PartitionSpecs, with
XLA inserting the all-to-alls. Dropped tokens (over capacity) pass through
the residual unchanged, the standard Switch behavior.
"""

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.parallel.mesh import MODEL_AXIS


class SwitchMoE(nn.Module):
    """Top-1 routed expert FFN. Returns (output [B, S, D], aux_loss) —
    aux_loss is the Switch load-balancing term, add it to the task loss
    scaled by ~1e-2."""

    num_experts: int
    d_hidden: int
    capacity_factor: float = 1.25
    dtype: str = "bfloat16"

    @nn.compact
    def __call__(self, x):
        dtype = jnp.dtype(self.dtype)
        b, s, d = x.shape
        tokens = x.reshape(-1, d)
        n_tokens = b * s
        capacity = max(
            1,
            int(self.capacity_factor * n_tokens / self.num_experts),
        )

        # Router in float32: tiny matmul, numerically sensitive.
        logits = nn.Dense(
            self.num_experts, dtype=jnp.float32, name="router"
        )(tokens.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
        expert = jnp.argmax(probs, axis=-1)  # [T]
        onehot = jax.nn.one_hot(
            expert, self.num_experts, dtype=jnp.float32
        )

        # Load-balancing aux loss (Switch eq. 4): fraction of tokens per
        # expert dotted with mean router prob per expert, scaled by E.
        density = jnp.mean(onehot, axis=0)
        density_proxy = jnp.mean(probs, axis=0)
        aux_loss = self.num_experts * jnp.sum(density * density_proxy)

        # Position of each token within its expert; beyond-capacity tokens
        # drop (contribute zero; the caller's residual carries them).
        position = jnp.cumsum(onehot, axis=0) * onehot  # 1-based
        keep = (position <= capacity).astype(jnp.float32) * onehot
        gate = jnp.sum(probs * keep, axis=-1)  # [T]
        pos_idx = jnp.sum((position - 1.0) * keep, axis=-1).astype(
            jnp.int32
        )
        # [T, E, C] one-hot dispatch mask.
        dispatch = (
            keep[:, :, None]
            * jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)[
                :, None, :
            ]
        )

        w_in = self.param(
            "w_in",
            nn.initializers.lecun_normal(),
            (self.num_experts, d, self.d_hidden),
        )
        w_out = self.param(
            "w_out",
            nn.initializers.lecun_normal(),
            (self.num_experts, self.d_hidden, d),
        )

        # Dispatch -> expert FFN -> combine, all einsums (the all-to-alls
        # appear here when w_*/expert axes are sharded).
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(dtype), tokens.astype(dtype)
        )
        h = nn.gelu(
            jnp.einsum("ecd,edh->ech", expert_in, w_in.astype(dtype))
        )
        expert_out = jnp.einsum(
            "ech,ehd->ecd", h, w_out.astype(dtype)
        )
        combined = jnp.einsum(
            "tec,ecd->td",
            (dispatch * gate[:, None, None]).astype(dtype),
            expert_out,
        )
        return combined.reshape(b, s, d).astype(x.dtype), aux_loss


def moe_param_specs(params, expert_axis=MODEL_AXIS):
    """PartitionSpecs for a SwitchMoE param subtree, built by walking the
    actual tree so structure changes can't silently diverge: expert
    weight tensors (leading dim E) shard over `expert_axis`, everything
    else (the router) replicates.

    The default is the trainer meshes' model axis: no production mesh
    declares a dedicated "expert" axis, so the old "expert" default
    produced specs that could never match the mesh they flowed into
    (the drift class the mesh-spec-consistency lint rule rejects)."""
    from elasticdl_tpu.common.pytree_utils import nest_at, walk_dict

    specs = {}
    for path, leaf in walk_dict(params):
        if path[-1] in ("w_in", "w_out"):
            specs[path] = P(
                expert_axis, *([None] * (leaf.ndim - 1))
            )
        else:
            specs[path] = P()
    return nest_at(specs)


# ---------- top-k routed experts, this chip's share ----------

SCORES = {
    "sigmoid": jax.nn.sigmoid,
    "softmax": functools.partial(jax.nn.softmax, axis=-1),
}
ROUTING_SCOPE = "moe_routing"
GROUPED_SCOPE = "moe_grouped"
SHARED_SCOPE = "moe_shared"


def pick_chosen(scores, experts):
    """scores[t, experts[t, j]] as [T, k]: `jnp.take_along_axis(scores,
    experts, axis=-1)` without the gather, and without the scatter that is
    its transpose. scores [T, E]; experts [T, k] ints, a token's k all
    different (as `top_k` gives them); one outside [0, E) reads as the
    gather read it (`_as_the_gather_reads`).

    On the chip the gather moves the T * k numbers one at a time (10 ns
    each) and the scatter likewise, into a zeroed [T, E]. Here both ways
    are a compare of `experts` with an iota over E, a select and a
    reduction: T * k * E of vector work in one fusion, never written. The
    backward is written by hand so that what is kept for it is `experts`
    alone, as before, and not the mask.

    The same bits both ways. Forward: of the E numbers under the maximum
    for (t, j) one is scores[t, experts[t, j]] and the rest are -inf, so
    the chosen score itself comes back. (A maximum and not a sum with
    zeros: the compiler folds a sum over E into the sum over k that
    normalises the weights, one reduction over [k, E], and adds the k
    weights in another order.) Backward: a token's k experts differ, so of
    the k terms summed into d_scores[t, e] at most one is not +0.0, and the
    sum is what the scatter's 0.0 + d into its zeroed array gave.

    And the caller's bits. The sums round this (over k under the weights
    and in their backward, over E in the scores' backward) add in the
    order their operand's layout gives, and the compiler lays an operand
    out by what produces and what else reads it: handed a plain [T, k] it
    summed a token's weights tokens-minor in one configuration's step
    where it had summed them k-minor round the gather, and k-minor in
    another's where it had summed them tokens-minor, and every logged loss
    moved (PERF.md section 6, PR 54). So each result is handed over as
    the gather's and the scatter's were: a flat array (behind an
    optimisation barrier, so that it stays one) reshaped, and forward
    under the gather's own select over the indices' range. From there on
    the compiler has the program it had, and every sum and product of the
    routing stage keeps its operands' layouts in the four configurations'
    compiled steps (tests/test_tpu_compile.py pins the two thinnest)."""
    return _pick(scores, experts, scores.shape[-1])


def _where_chosen(experts, values, values_on, fill, num_experts):
    """[T, k, E]: `values` ([T, E] or [T, k], spread over the axes
    `values_on`) where e is the expert experts[t, j], `fill` elsewhere. In
    `lax` primitives: each `jnp` operator here would be one more nested
    jit for the step's trace to enter, in every routed layer."""
    shape = (*experts.shape, num_experts)
    return jax.lax.select(
        jax.lax.eq(
            jax.lax.broadcast_in_dim(experts, shape, (0, 1)),
            jax.lax.broadcasted_iota(experts.dtype, shape, 2)),
        jax.lax.broadcast_in_dim(values, shape, values_on),
        jax.lax.full(shape, fill, values.dtype))


def _as_the_gather_reads(experts, num_experts):
    """(experts with one below 0 counted from the end, which of them then
    lie in [0, E)): `take_along_axis` gives NaN for the others, and their
    cotangent goes nowhere."""
    wrapped = jax.lax.select(
        jax.lax.lt(experts, jax.lax.full_like(experts, 0)),
        jax.lax.add(experts, jax.lax.full_like(experts, num_experts)),
        experts)
    return wrapped, jax.lax.bitwise_and(
        jax.lax.ge(wrapped, jax.lax.full_like(experts, 0)),
        jax.lax.le(wrapped, jax.lax.full_like(experts, num_experts - 1)))


def _handed_over_flat(x):
    shape = x.shape
    return jax.lax.reshape(
        jax.lax.optimization_barrier(jax.lax.reshape(x, (x.size,))), shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pick(scores, experts, num_experts):
    experts, within = _as_the_gather_reads(experts, num_experts)
    picked = _handed_over_flat(jax.lax.reduce_max(_where_chosen(
        experts, scores, (0, 2), -jnp.inf, num_experts), (2,)))
    return jax.lax.select(
        within, picked, jax.lax.full_like(picked, jnp.nan))


def _pick_fwd(scores, experts, num_experts):
    return _pick(scores, experts, num_experts), experts


def _pick_bwd(num_experts, experts, d_picked):
    experts, within = _as_the_gather_reads(experts, num_experts)
    d_picked = jax.lax.select(
        within, d_picked, jax.lax.full_like(d_picked, 0))
    return _handed_over_flat(jax.lax.reduce_sum(_where_chosen(
        experts, d_picked, (0, 1), 0, num_experts), (1,))), None


_pick.defvjp(_pick_fwd, _pick_bwd)


def route_top_k(scores, correction_bias, k, norm_topk_prob, scaling_factor,
                eps=1e-20):
    """scores [T, E] float32 (after the sigmoid, or the softmax over all E)
    -> (experts [T, k] int32,
    weights [T, k] float32). The k experts are the largest of scores +
    correction_bias; the weights are the scores themselves at the chosen
    (without the bias), divided by their sum (plus `eps`: HF `lfm2_moe`
    has 1e-6 there) if `norm_topk_prob`, times `scaling_factor`."""
    _, experts = jax.lax.top_k(scores + correction_bias, k)
    weights = pick_chosen(scores, experts)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scaling_factor


def plan_held_blocks(experts, first, count, block):
    """Sort the assignments that fall on the held experts [first, first +
    count) by expert, and cut each expert's run into blocks of `block`
    rows. Nothing is dropped: the number of blocks follows the load, and an
    expert without an assignment takes none.

    experts [T, k] int32. Returns a dict of small int32 arrays:
      order       [T*k + block] assignment ids (token * k + slot) sorted by
                  held expert (the others last), padded so that a block's
                  slice never runs off the end
      counts      [count] assignments on each held expert
      group_start [count] where each expert's run starts in `order`
      block_end   [count] running number of blocks up to and with each expert
      n_blocks    []      blocks in all
    """
    flat = experts.reshape(-1)
    held = (flat >= first) & (flat < first + count)
    local = jnp.where(held, flat - first, count)
    _, order = jax.lax.sort(
        (local, jnp.arange(flat.shape[0], dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    counts = jnp.sum(
        local[:, None] == jnp.arange(count, dtype=jnp.int32)[None], axis=0,
        dtype=jnp.int32)
    group_start = jnp.cumsum(counts) - counts
    block_end = jnp.cumsum((counts + block - 1) // block)
    return {
        "order": jnp.pad(order, (0, block)),
        "counts": counts, "group_start": group_start,
        "block_end": block_end, "n_blocks": block_end[-1],
    }


def _block_rows(plan, i, block, k):
    """Block i of the plan: (held expert, where its rows start in `order`,
    assignment ids [block], tokens [block], which rows are real)."""
    e = jnp.sum(i >= plan["block_end"], dtype=jnp.int32)
    first_block = jnp.where(e > 0, plan["block_end"][e - 1], 0)
    offset = (i - first_block) * block
    start = plan["group_start"][e] + offset
    ids = jax.lax.dynamic_slice(plan["order"], (start,), (block,))
    real = offset + jnp.arange(block, dtype=jnp.int32) < plan["counts"][e]
    return e, start, ids, jnp.where(real, ids // k, 0), real


def _relu2_expert(rows, w_up, w_down):
    u = jnp.dot(rows, w_up, preferred_element_type=jnp.float32)
    a = jnp.maximum(u, 0.0)
    h = (a * a).astype(rows.dtype)
    return a, h, jnp.dot(h, w_down, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def grouped_relu2_experts(x, weights, w_up, w_down, plan, block, k):
    """sum over the held assignments (t, e) of weights[t, e] *
    down_e(relu(up_e(x_t))^2), as [T, D] float32.

    x [T, D]; weights [T, k] float32; w_up [count, D, F] and w_down
    [count, F, D] float32 (cast to x's dtype for the products); plan from
    `plan_held_blocks`. One loop iteration a block of `block` sorted rows,
    all of one expert: gather the rows, two products, scatter-add. The trip
    count is the number of blocks the load needs, so no token is dropped
    and no product runs on padding beyond an expert's last block."""
    return _grouped_forward(x, weights, w_up, w_down, plan, block, k)


def _grouped_forward(x, weights, w_up, w_down, plan, block, k):
    dtype = x.dtype
    up, down = w_up.astype(dtype), w_down.astype(dtype)
    flat_w = weights.reshape(-1)

    def body(i, y):
        e, _, ids, tokens, real = _block_rows(plan, i, block, k)
        gate = jnp.where(real, flat_w[ids], 0.0)
        _, _, out = _relu2_expert(x[tokens], up[e], down[e])
        return y.at[tokens].add(gate[:, None] * out)

    with jax.named_scope(GROUPED_SCOPE):
        return jax.lax.fori_loop(
            0, plan["n_blocks"], body, jnp.zeros(x.shape, jnp.float32))


def _grouped_fwd(x, weights, w_up, w_down, plan, block, k):
    y = _grouped_forward(x, weights, w_up, w_down, plan, block, k)
    return y, (x, weights, w_up, w_down, plan)


def _grouped_bwd(block, k, residuals, dy):
    x, weights, w_up, w_down, plan = residuals
    dtype, f32 = x.dtype, jnp.float32
    up, down = w_up.astype(dtype), w_down.astype(dtype)
    flat_w = weights.reshape(-1)
    dy = dy.astype(dtype)

    def body(i, carry):
        dx, d_up, d_down, d_sorted = carry
        e, start, ids, tokens, real = _block_rows(plan, i, block, k)
        gate = jnp.where(real, flat_w[ids], 0.0)
        rows, g = x[tokens], dy[tokens]
        a, h, out = _relu2_expert(rows, up[e], down[e])
        d_gate = jnp.sum(out * g.astype(f32), axis=-1)
        d_out = (gate[:, None] * g.astype(f32)).astype(dtype)
        d_h = jnp.dot(d_out, down[e].T, preferred_element_type=f32)
        d_u = (d_h * 2.0 * a).astype(dtype)
        d_rows = jnp.dot(d_u, up[e].T, preferred_element_type=f32)
        d_down = d_down.at[e].add(
            jnp.dot(h.T, d_out, preferred_element_type=f32))
        d_up = d_up.at[e].add(
            jnp.dot(rows.T, d_u, preferred_element_type=f32))
        # A block's tail belongs to the next expert's run: keep what is
        # there.
        was = jax.lax.dynamic_slice(d_sorted, (start,), (block,))
        d_sorted = jax.lax.dynamic_update_slice(
            d_sorted, jnp.where(real, d_gate, was), (start,))
        return dx.at[tokens].add(d_rows), d_up, d_down, d_sorted

    with jax.named_scope(GROUPED_SCOPE):
        dx, d_up, d_down, d_sorted = jax.lax.fori_loop(
            0, plan["n_blocks"], body,
            (jnp.zeros(x.shape, f32), jnp.zeros(w_up.shape, f32),
             jnp.zeros(w_down.shape, f32),
             jnp.zeros(plan["order"].shape, f32)))
        # Back from sorted order to [T, k].
        n = flat_w.shape[0]
        _, d_flat = jax.lax.sort(
            (plan["order"][:n], d_sorted[:n]), num_keys=1)
    return (dx.astype(dtype), d_flat.reshape(weights.shape),
            d_up.astype(w_up.dtype), d_down.astype(w_down.dtype), None)


grouped_relu2_experts.defvjp(_grouped_fwd, _grouped_bwd)


# ---------- the gated (SwiGLU) expert form, beside the relu^2 one ----------
#
# Its own loops: the relu^2 path above is compiled as it was. What the two
# share is what decides no arithmetic: the plan and `_block_rows`.
#
# A block's rows go back to token order by a scatter-add into the loop's
# carry. On the chip a row of a [T, D] float32 array is one sublane of D /
# 128 tiles of (8, 128), each shared with seven other tokens, and a scatter
# of rows moves an eighth of what it touches. The gated loops carry y and dx
# as [T, D / 128, 128] instead: a token's row is whole tiles of its own
# (0.094 ms a block of 1152 rows of 2048 against 0.283). The same rows, the
# same float32 sums in the same order; the padded rows still add their
# zeros to token 0, and the scatter keeps no attribute: told that a block's
# rows are sorted and unique, the compiler scatters into [T, D] three times
# slower and into slabs no faster.

_LANES = 128


def _row_slabs(a):
    """[n, D] as [n, D / 128, 128], where D is whole lanes."""
    n, d = a.shape
    return a.reshape(n, d // _LANES, _LANES) if d % _LANES == 0 else a


def _token_rows(a):
    """The forward's carry back as [T, D], an array of its own: left to
    fuse the change of layout into what reads y, the compiler keeps one
    [T, D] float32 more at the step's peak."""
    return jax.lax.optimization_barrier(a.reshape(a.shape[0], -1))


def _swiglu_expert(rows, w_gate_up, w_down):
    """down(silu(gate(rows)) * up(rows)); `w_gate_up` [D, 2F] holds the
    gate's columns first, so both take one product."""
    f32 = jnp.float32
    gu = jnp.dot(rows, w_gate_up, preferred_element_type=f32)
    g, u = jnp.split(gu, 2, axis=-1)
    h = (jax.nn.silu(g) * u).astype(rows.dtype)
    return g, u, h, jnp.dot(h, w_down, preferred_element_type=f32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def grouped_swiglu_experts(x, weights, w_gate_up, w_down, plan, block, k):
    """sum over the held assignments (t, e) of weights[t, e] *
    down_e(silu(gate_e(x_t)) * up_e(x_t)), as [T, D] float32.

    As `grouped_relu2_experts`, with w_gate_up [count, D, 2F] (gate
    columns, then up) and w_down [count, F, D]: two products a block
    forward, six backward."""
    return _swiglu_forward(x, weights, w_gate_up, w_down, plan, block, k)


def _swiglu_forward(x, weights, w_gate_up, w_down, plan, block, k):
    dtype = x.dtype
    gate_up, down = w_gate_up.astype(dtype), w_down.astype(dtype)
    flat_w = weights.reshape(-1)

    def body(i, y):
        e, _, ids, tokens, real = _block_rows(plan, i, block, k)
        gate = jnp.where(real, flat_w[ids], 0.0)
        *_, out = _swiglu_expert(x[tokens], gate_up[e], down[e])
        return y.at[tokens].add(_row_slabs(gate[:, None] * out))

    with jax.named_scope(GROUPED_SCOPE):
        return _token_rows(jax.lax.fori_loop(
            0, plan["n_blocks"], body,
            _row_slabs(jnp.zeros(x.shape, jnp.float32))))


def _swiglu_fwd(x, weights, w_gate_up, w_down, plan, block, k):
    y = _swiglu_forward(x, weights, w_gate_up, w_down, plan, block, k)
    return y, (x, weights, w_gate_up, w_down, plan)


def _swiglu_bwd(block, k, residuals, dy):
    x, weights, w_gate_up, w_down, plan = residuals
    dtype, f32 = x.dtype, jnp.float32
    gate_up, down = w_gate_up.astype(dtype), w_down.astype(dtype)
    flat_w = weights.reshape(-1)
    dy = dy.astype(dtype)

    def body(i, carry):
        dx, d_gate_up, d_down, d_sorted = carry
        e, start, ids, tokens, real = _block_rows(plan, i, block, k)
        gate = jnp.where(real, flat_w[ids], 0.0)
        rows, g_out = x[tokens], dy[tokens].astype(f32)
        g, u, h, out = _swiglu_expert(rows, gate_up[e], down[e])
        d_weight = jnp.sum(out * g_out, axis=-1)
        d_out = (gate[:, None] * g_out).astype(dtype)
        d_h = jnp.dot(d_out, down[e].T, preferred_element_type=f32)
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        d_gu = jnp.concatenate(
            [d_h * u * (sig + silu * (1.0 - sig)), d_h * silu],
            axis=-1).astype(dtype)
        d_rows = jnp.dot(d_gu, gate_up[e].T, preferred_element_type=f32)
        d_down = d_down.at[e].add(
            jnp.dot(h.T, d_out, preferred_element_type=f32))
        d_gate_up = d_gate_up.at[e].add(
            jnp.dot(rows.T, d_gu, preferred_element_type=f32))
        # A block's tail belongs to the next expert's run: keep what is
        # there.
        was = jax.lax.dynamic_slice(d_sorted, (start,), (block,))
        d_sorted = jax.lax.dynamic_update_slice(
            d_sorted, jnp.where(real, d_weight, was), (start,))
        return (dx.at[tokens].add(_row_slabs(d_rows)), d_gate_up, d_down,
                d_sorted)

    with jax.named_scope(GROUPED_SCOPE):
        dx, d_gate_up, d_down, d_sorted = jax.lax.fori_loop(
            0, plan["n_blocks"], body,
            (_row_slabs(jnp.zeros(x.shape, f32)),
             jnp.zeros(w_gate_up.shape, f32), jnp.zeros(w_down.shape, f32),
             jnp.zeros(plan["order"].shape, f32)))
        # Back from sorted order to [T, k].
        n = flat_w.shape[0]
        _, d_flat = jax.lax.sort(
            (plan["order"][:n], d_sorted[:n]), num_keys=1)
    return (dx.reshape(x.shape).astype(dtype),
            d_flat.reshape(weights.shape),
            d_gate_up.astype(w_gate_up.dtype), d_down.astype(w_down.dtype),
            None)


grouped_swiglu_experts.defvjp(_swiglu_fwd, _swiglu_bwd)


class RoutedExperts(nn.Module):
    """Top-k of `num_experts` by their scores plus a correction bias:
    `score` "sigmoid" scores each expert by the sigmoid of its logit
    (DeepSeek-V3's routing as the HF `nemotron_h` and `lfm2_moe` models use
    it), "softmax" by the softmax over all `num_experts` logits, in float32,
    before the choice (HF `qwen3_moe` / `sdar_moe`). Experts
    `down(relu(up(x))^2)` without a gate, and a shared expert of the same
    form for every token. No auxiliary loss, no capacity: nothing is dropped.
    `gated` makes the experts `down(silu(gate(x)) * up(x))` (HF `lfm2_moe`;
    parameters `w_gate_up`, `w_down`) and the shared expert, where
    `d_shared` asks for one, the same form (HF `deepseek_v3`, whose
    `n_shared_experts` are one MLP of their widths' sum; `shared_gate_up`
    holds the gate's columns first, `shared_down`), and the layer then also
    counts the rows its loops multiplied.

    The layer is told which experts it holds, `held = (first, count)`: it
    routes over all `num_experts`, computes its own experts' part of the
    result and leaves out what the others would have added (expert
    parallelism without the exchange: on one chip there is none). The
    shared expert is whole on every chip. Returns (y [B, S, D], stats):
    token-expert assignments made, those that fell on held experts, the
    largest and the mean count over the held experts."""

    num_experts: int
    num_experts_per_tok: int
    d_hidden: int
    d_shared: int = 0
    held: Optional[Tuple[int, int]] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    block_rows: int = 1024
    gated: bool = False
    score: str = "sigmoid"
    # Under the sum that normalises the chosen scores.
    topk_eps: float = 1e-20
    # Not None: the router's logits are replaced, in the forward pass, by
    # uniform noise drawn from this seed for each (row, position, expert),
    # the same at every step; the gradient goes straight through to the
    # router. Every expert then takes its 1/num_experts of the assignments
    # whatever the weights: Megatron-Core's benchmark mode
    # `--moe-router-force-load-balancing` (its `RandomSTE`).
    force_balance_seed: Optional[int] = None
    dtype: str = "bfloat16"
    kernel_init: nn.initializers.Initializer = nn.initializers.normal(0.02)

    @nn.compact
    def __call__(self, x):
        dtype, f32 = jnp.dtype(self.dtype), jnp.float32
        b, s, d = x.shape
        first, count = self.held or (0, self.num_experts)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"held experts [{first}, {first + count}) are not among "
                f"the {self.num_experts}")
        if self.score not in SCORES:
            raise ValueError(
                f"score {self.score!r}: the layer scores by {sorted(SCORES)}")
        tokens = x.reshape(-1, d).astype(dtype)
        k = self.num_experts_per_tok
        with jax.named_scope(ROUTING_SCOPE):
            router = self.param(
                "router", self.kernel_init, (self.num_experts, d), f32)
            # Untrained buffer (its update speed is not in the config):
            # held at zero, it takes no gradient through top_k.
            bias = self.variable(
                "buffers", "e_score_correction_bias",
                jnp.zeros, (self.num_experts,), f32)
            logits = jnp.einsum(
                "td,ed->te", tokens.astype(f32), router,
                precision=jax.lax.Precision.HIGHEST)
            if self.force_balance_seed is not None:
                noise = jax.random.uniform(
                    jax.random.PRNGKey(self.force_balance_seed),
                    (b, s, self.num_experts), f32).reshape(logits.shape)
                logits = logits + jax.lax.stop_gradient(noise - logits)
            scores = SCORES[self.score](logits)
            experts, weights = route_top_k(
                scores, jax.lax.stop_gradient(bias.value), k,
                self.norm_topk_prob, self.routed_scaling_factor,
                self.topk_eps)
            plan = plan_held_blocks(experts, first, count, self.block_rows)
        name, grouped, width = (
            ("w_gate_up", grouped_swiglu_experts, 2 * self.d_hidden)
            if self.gated else
            ("w_up", grouped_relu2_experts, self.d_hidden))
        w_up = self.param(name, self.kernel_init, (count, d, width), f32)
        w_down = self.param(
            "w_down", self.kernel_init, (count, self.d_hidden, d), f32)
        y = grouped(tokens, weights, w_up, w_down, plan, self.block_rows, k)
        if self.d_shared:
            with jax.named_scope(SHARED_SCOPE):
                if self.gated:
                    g, u = jnp.split(nn.Dense(
                        2 * self.d_shared, use_bias=False, dtype=dtype,
                        kernel_init=self.kernel_init,
                        name="shared_gate_up")(tokens), 2, axis=-1)
                    h = jax.nn.silu(g) * u
                else:
                    h = nn.Dense(
                        self.d_shared, use_bias=False, dtype=dtype,
                        kernel_init=self.kernel_init,
                        name="shared_up")(tokens)
                    h = jnp.square(jax.nn.relu(h))
                y = y + nn.Dense(
                    d, use_bias=False, dtype=dtype,
                    kernel_init=self.kernel_init, name="shared_down",
                )(h).astype(f32)
        counts = plan["counts"].astype(f32)
        stats = {
            "moe_assignments": jnp.asarray(tokens.shape[0] * k, f32),
            "moe_assignments_held": jnp.sum(counts),
            "moe_held_load_max": jnp.max(counts),
            "moe_held_load_mean": jnp.mean(counts),
        }
        if self.gated:
            # Rows the grouped loops multiplied, and those of them that
            # were assignments: the blocks' fill.
            stats["moe_block_rows_run"] = (
                plan["n_blocks"] * self.block_rows).astype(f32)
            stats["moe_block_rows_real"] = stats["moe_assignments_held"]
        return y.reshape(b, s, d).astype(x.dtype), stats
