"""Interleaved 1F1B pipeline parallelism (virtual pipeline stages).

Megatron-style interleaving on top of the 1F1B machinery in pipeline.py:
the Block stack splits into n_stages * v CHUNKS, device d hosting chunks
{d, d+N, ...} so every chunk-to-chunk hop is still a neighbor-only
ppermute (forward to d+1, gradient to d-1). The execution order comes
from a STATIC schedule table (parallel/pipeline_schedule.py) consumed as
scan data: per tick each device runs one table-assigned fwd slot and one
bwd slot (masked when idle), messages carry a slot tag and land in small
exactly-sized mailboxes, per-chunk inputs stash in a ring for the
vjp-recompute backward, and the LM head stays vocab-parallel across the
stage axis exactly as in make_lm_pipeline_1f1b.

Same public contract as make_lm_pipeline_1f1b — (init_fn,
loss_and_grads_fn) over the {"embed", "stages", "head"} tree with
"stages" stacked in GLOBAL CHUNK ORDER [n*v, ...] (checkpoint-compatible
with a GPipe/1F1B build of n*v stages); rows are permuted into the
device-block layout internally and gradients permuted back.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.models.transformer.transformer_lm import (
    Block,
    embed_input,
)
from elasticdl_tpu.parallel.pipeline import (
    make_lm_pipeline,
    microbatch,
    vocab_parallel_head_loss,
)
from elasticdl_tpu.parallel.pipeline_schedule import (
    build_interleaved_schedule,
)


def interleaved_row_order(n_stages, v):
    """Permutation taking chunk-ordered rows [c] to device-block order:
    position d*v + r holds chunk r*n_stages + d (device d's r-th local
    chunk)."""
    order = []
    for d in range(n_stages):
        for r in range(v):
            order.append(r * n_stages + d)
    return np.asarray(order, np.int32)


def make_lm_pipeline_interleaved(cfg, mesh, n_stages, v, num_microbatches,
                                 axis_name="stage", batch_axis=None):
    total = n_stages * v
    if cfg.n_layers % total:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by "
            f"{n_stages} stages x {v} chunks"
        )
    if cfg.vocab % n_stages:
        raise ValueError(
            f"vocab {cfg.vocab} not divisible by {n_stages} stages "
            f"(the head is vocab-parallel over the stage axis)"
        )
    layers_per_chunk = cfg.n_layers // total
    v_loc = cfg.vocab // n_stages
    act_dtype = jnp.dtype(cfg.activation_dtype)
    sched = build_interleaved_schedule(n_stages, v, num_microbatches)
    order = interleaved_row_order(n_stages, v)
    inverse = np.argsort(order)

    class EmbedIn(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return embed_input(cfg, tokens)

    class Chunk(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            for _ in range(layers_per_chunk):
                x = Block(cfg)(x, training)
            return x

    embed_mod, chunk_mod = EmbedIn(), Chunk()
    head_ln = nn.LayerNorm(dtype=act_dtype)

    def init_fn(rng, sample_tokens):
        # Same tree as a GPipe/1F1B build with n*v stages (chunk order).
        gpipe_init, _ = make_lm_pipeline(
            cfg, mesh, total, num_microbatches,
            axis_name=axis_name, batch_axis=batch_axis,
        )
        return gpipe_init(rng, sample_tokens)

    def _head_loss(head_params, y, labels_m, shard):
        return vocab_parallel_head_loss(
            cfg, head_ln, v_loc, axis_name, head_params, y, labels_m,
            shard,
        )

    def _chunk_forward(chunk_params, embed_params, x_in, tokens_m,
                       is_first, rng_m):
        """Uniform slot program: chunk 0 embeds its tokens, everything
        else consumes the mailbox activation; jnp.where routes the
        gradients (the unselected branch gets a zero cotangent)."""
        emb = embed_mod.apply({"params": embed_params}, tokens_m)
        h = jnp.where(is_first, emb, x_in)
        if rng_m is None:
            return chunk_mod.apply({"params": chunk_params}, h, True)
        return chunk_mod.apply(
            {"params": chunk_params}, h, True, rngs={"dropout": rng_m}
        )

    def _pipeline(stages_dev, embed_p, head_p, tokens_mb, labels_mb,
                  tables, rng):
        n = n_stages
        shard = jax.lax.axis_index(axis_name)
        # stages_dev: local [v, ...] rows = this device's chunks r*n+d.
        chunks_local = stages_dev
        mb, s = tokens_mb.shape[1], tokens_mb.shape[2]
        act_shape = (mb, s, cfg.d_model)
        m_total = num_microbatches
        perm_fwd = [(i, (i + 1) % n) for i in range(n)]
        perm_bwd = [(i, (i - 1) % n) for i in range(n)]

        def rng_for(c, m):
            if rng is None:
                return None
            r = jax.random.fold_in(jax.random.fold_in(rng, c), m)
            if batch_axis is not None:
                r = jax.random.fold_in(
                    r, jax.lax.axis_index(batch_axis)
                )
            return r

        def chunk_params_at(r):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, r, 0, keepdims=False
                ),
                chunks_local,
            )

        zero_chunk_grads = jax.tree_util.tree_map(
            jnp.zeros_like, chunks_local
        )

        def tick(carry, xs):
            (fwd_box, bwd_box, stash, dy_box, grads, loss_sum) = carry
            d_stages, d_embed, d_head = grads
            fc, fm, bc, bm, head_m = xs
            # Tables are per-device columns already (sharded over the
            # stage axis); squeeze the length-1 device dim.
            fc, fm = fc[0], fm[0]
            bc, bm = bc[0], bm[0]
            head_m = head_m[0]

            # ---------- fwd slot ----------
            f_active = fc >= 0
            fc_s = jnp.maximum(fc, 0)
            fm_s = jnp.clip(fm, 0, m_total - 1)
            r_f = fc_s // n
            tokens_f = jax.lax.dynamic_index_in_dim(
                tokens_mb, fm_s, 0, keepdims=False
            )
            in_tag = (fc_s * m_total + fm_s) % sched.fwd_mailbox
            x_in = jax.lax.dynamic_index_in_dim(
                fwd_box, in_tag, 0, keepdims=False
            )
            y = _chunk_forward(
                chunk_params_at(r_f), embed_p, x_in, tokens_f,
                fc_s == 0, rng_for(fc_s, fm_s),
            )
            # Stash the consumed input for this slot's backward.
            st_slot = r_f * sched.stash_depth + fm_s % sched.stash_depth
            cur = jax.lax.dynamic_index_in_dim(
                stash, st_slot, 0, keepdims=False
            )
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_active, x_in, cur), st_slot, 0
            )

            # ---------- head (vocab-parallel, all devices) ----------
            h_active = head_m >= 0
            hm_s = jnp.clip(head_m, 0, m_total - 1)
            y_last = jax.lax.psum(
                jnp.where(
                    jnp.logical_and(f_active, fc == total - 1), y, 0.0
                ),
                axis_name,
            )
            labels_h = jax.lax.dynamic_index_in_dim(
                labels_mb, hm_s, 0, keepdims=False
            )
            loss_m, head_vjp = jax.vjp(
                lambda hp, yy: _head_loss(hp, yy, labels_h, shard),
                head_p,
                y_last,
            )
            d_head_c, dy = head_vjp(jnp.float32(1.0 / m_total))
            dy = jax.lax.psum(dy, axis_name) / n  # psum-transpose factor
            loss_sum = loss_sum + jnp.where(
                h_active, loss_m / m_total, 0.0
            )
            d_head = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(h_active, g, 0.0),
                d_head,
                d_head_c,
            )
            dy_slot = hm_s % sched.dy_store
            cur_dy = jax.lax.dynamic_index_in_dim(
                dy_box, dy_slot, 0, keepdims=False
            )
            dy_box = jax.lax.dynamic_update_index_in_dim(
                dy_box,
                jnp.where(h_active, dy.astype(act_dtype), cur_dy),
                dy_slot,
                0,
            )

            # ---------- bwd slot ----------
            b_active = bc >= 0
            bc_s = jnp.maximum(bc, 0)
            bm_s = jnp.clip(bm, 0, m_total - 1)
            r_b = bc_s // n
            g_tag = (bc_s * m_total + bm_s) % sched.bwd_mailbox
            g_box = jax.lax.dynamic_index_in_dim(
                bwd_box, g_tag, 0, keepdims=False
            )
            g_dy = jax.lax.dynamic_index_in_dim(
                dy_box, bm_s % sched.dy_store, 0, keepdims=False
            )
            g = jnp.where(bc == total - 1, g_dy, g_box)
            x_b = jax.lax.dynamic_index_in_dim(
                stash,
                r_b * sched.stash_depth + bm_s % sched.stash_depth,
                0,
                keepdims=False,
            )
            tokens_b = jax.lax.dynamic_index_in_dim(
                tokens_mb, bm_s, 0, keepdims=False
            )
            _, chunk_vjp = jax.vjp(
                lambda cp, ep, xx: _chunk_forward(
                    cp, ep, xx, tokens_b, bc_s == 0,
                    rng_for(bc_s, bm_s),
                ),
                chunk_params_at(r_b),
                embed_p,
                x_b,
            )
            d_chunk, d_embed_c, dx = chunk_vjp(g)
            d_stages = jax.tree_util.tree_map(
                lambda acc, gg: acc.at[r_b].add(
                    jnp.where(b_active, gg, 0.0)
                ),
                d_stages,
                d_chunk,
            )
            d_embed = jax.tree_util.tree_map(
                lambda acc, gg: acc + jnp.where(b_active, gg, 0.0),
                d_embed,
                d_embed_c,
            )

            # ---------- neighbor hops (message + tag) ----------
            send_f = jnp.logical_and(f_active, fc < total - 1)
            f_msg = jax.lax.ppermute(
                jnp.where(send_f, y, 0.0), axis_name, perm_fwd
            )
            f_tag = jax.lax.ppermute(
                jnp.where(send_f, (fc_s + 1) * m_total + fm_s, -1),
                axis_name,
                perm_fwd,
            )
            send_b = jnp.logical_and(b_active, bc > 0)
            b_msg = jax.lax.ppermute(
                jnp.where(send_b, dx, 0.0), axis_name, perm_bwd
            )
            b_tag = jax.lax.ppermute(
                jnp.where(send_b, (bc_s - 1) * m_total + bm_s, -1),
                axis_name,
                perm_bwd,
            )
            f_slot = jnp.maximum(f_tag, 0) % sched.fwd_mailbox
            cur_f = jax.lax.dynamic_index_in_dim(
                fwd_box, f_slot, 0, keepdims=False
            )
            fwd_box = jax.lax.dynamic_update_index_in_dim(
                fwd_box, jnp.where(f_tag >= 0, f_msg, cur_f), f_slot, 0
            )
            b_slot = jnp.maximum(b_tag, 0) % sched.bwd_mailbox
            cur_b = jax.lax.dynamic_index_in_dim(
                bwd_box, b_slot, 0, keepdims=False
            )
            bwd_box = jax.lax.dynamic_update_index_in_dim(
                bwd_box, jnp.where(b_tag >= 0, b_msg, cur_b), b_slot, 0
            )
            return (
                fwd_box,
                bwd_box,
                stash,
                dy_box,
                (d_stages, d_embed, d_head),
                loss_sum,
            ), None

        carry0 = (
            jnp.zeros((sched.fwd_mailbox, *act_shape), act_dtype),
            jnp.zeros((sched.bwd_mailbox, *act_shape), act_dtype),
            jnp.zeros((v * sched.stash_depth, *act_shape), act_dtype),
            jnp.zeros((sched.dy_store, *act_shape), act_dtype),
            (
                zero_chunk_grads,
                jax.tree_util.tree_map(jnp.zeros_like, embed_p),
                jax.tree_util.tree_map(jnp.zeros_like, head_p),
            ),
            jnp.float32(0.0),
        )
        (_, _, _, _, grads, loss_sum), _ = jax.lax.scan(
            tick, carry0, tables
        )
        d_stages, d_embed, d_head = grads
        d_embed = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, axis_name), d_embed
        )
        d_head = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, axis_name) / n, d_head
        )
        loss = jax.lax.pmean(loss_sum, axis_name)
        if batch_axis is not None:
            d_embed, d_head, d_stages, loss = jax.tree_util.tree_map(
                lambda gg: jax.lax.pmean(gg, batch_axis),
                (d_embed, d_head, d_stages, loss),
            )
        return loss, {
            "embed": d_embed,
            "stages": d_stages,
            "head": d_head,
        }

    def loss_and_grads_fn(params, tokens, labels, rng=None):
        if bool(cfg.dropout) and rng is None:
            raise ValueError(
                "training with cfg.dropout > 0 requires an explicit rng"
            )
        tokens_mb = microbatch(
            jnp.asarray(tokens, jnp.int32), num_microbatches
        )
        labels_mb = microbatch(
            jnp.asarray(labels, jnp.int32), num_microbatches
        )
        # Chunk-ordered stages -> device-block layout for P(axis) on
        # dim 0 (device d's contiguous block = its local chunks).
        stages_dev = jax.tree_util.tree_map(
            lambda a: jnp.take(a, order, axis=0), params["stages"]
        )
        # Schedule tables ride the scan as xs; per-device columns shard
        # over the stage axis so each device reads only its own slots.
        tables = (
            jnp.asarray(sched.fwd_chunk),
            jnp.asarray(sched.fwd_micro),
            jnp.asarray(sched.bwd_chunk),
            jnp.asarray(sched.bwd_micro),
            jnp.asarray(sched.head_micro)[:, None].repeat(
                n_stages, axis=1
            ),
        )
        stage_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), params["stages"]
        )
        repl_e = jax.tree_util.tree_map(lambda _: P(), params["embed"])
        repl_h = jax.tree_util.tree_map(lambda _: P(), params["head"])
        x_spec = P(None, batch_axis)
        table_spec = P(None, axis_name)
        in_specs = (
            stage_specs, repl_e, repl_h, x_spec, x_spec,
            (table_spec,) * 5,
        )
        out_specs = (
            P(),
            {"embed": repl_e, "stages": stage_specs, "head": repl_h},
        )
        if rng is None:
            runner = shard_map(
                lambda sp, ep, hp, tm, lm, tb: _pipeline(
                    sp, ep, hp, tm, lm, tb, None
                ),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )
            loss, grads = runner(
                stages_dev, params["embed"], params["head"],
                tokens_mb, labels_mb, tables,
            )
        else:
            runner = shard_map(
                _pipeline,
                mesh=mesh,
                in_specs=in_specs + (P(),),
                out_specs=out_specs,
                check_vma=False,
            )
            loss, grads = runner(
                stages_dev, params["embed"], params["head"],
                tokens_mb, labels_mb, tables, rng,
            )
        # Device-block grads -> chunk order (the public tree layout).
        grads["stages"] = jax.tree_util.tree_map(
            lambda a: jnp.take(a, inverse, axis=0), grads["stages"]
        )
        return loss, grads

    return init_fn, loss_and_grads_fn
