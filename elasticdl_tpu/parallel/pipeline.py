"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

Capability extension beyond the reference (which is DP-only; SURVEY.md §2.10
records no TP/PP/SP/EP anywhere upstream). TPU-first design: per-stage
parameters are STACKED along a leading axis and sharded over the "stage"
mesh axis, so each device owns exactly one stage's weights. The whole
pipeline — fills, steady state, and drain — is ONE `lax.scan` over
`num_microbatches + num_stages - 1` ticks inside `shard_map`: at every tick
each device runs its stage on the activation received from its neighbor on
the previous tick (`lax.ppermute` ring shift), stage 0 feeding fresh
microbatches and the last stage banking finished ones. Differentiating
through the scan + ppermute yields the mirrored backward schedule
automatically, and XLA compiles the full fwd+bwd pipeline (bubble included)
into a single SPMD program whose stage hops ride ICI.

Why this shape and not a Python loop of per-stage jits: under jit the scan
is traced once with static shapes, collectives are neighbor-only
ppermutes (no host round-trips between microbatches), and the bubble cost
is the schedule's only overhead — (N-1)/(M+N-1) of ticks idle per device,
amortized by raising M.

Memory: scan autodiff saves each tick's activations; with `remat=True` the
stage body is wrapped in `jax.checkpoint`, storing only the inter-stage
activations (O(M) per device) and recomputing block internals — the same
recipe the flagship LM uses for long context.

Composes with data parallelism: on a ("data", "stage") mesh the microbatch
batch dim is sharded over "data" while params shard over "stage"; every
collective here names only the stage axis.
"""

import collections

import jax
import jax.numpy as jnp

# What a model spec's `pipeline_spec(...)` hook hands the AllReduce trainer
# (worker --pipeline_stages; the stage-hook twin of the param_specs hook):
#   init_fn(rng, sample_features) -> params        (staged param tree)
#   loss_and_grads_fn(params, features, labels, rng=None) -> (loss, grads)
#       the scheduled training step; call inside jit on a mesh whose
#       "stage" axis matches the build
#   apply_fn(params, features, training=False, rngs=None) -> outputs
#       schedule-free forward over the SAME param tree, valid on any mesh
#       (no stage axis needed) — evaluation/prediction, and the trainer's
#       sequential pure-DP fallback when a world can't host the stage axis
#   param_specs_fn(params) -> PartitionSpec tree for the staged params
PipelineBuild = collections.namedtuple(
    "PipelineBuild",
    ["init_fn", "loss_and_grads_fn", "apply_fn", "param_specs_fn"],
)


def stage_axis_demand(n_stages):
    """Pipelining's mesh-axis contribution to world resolution: an
    intra-process "stage" axis (stage hops ride on-host ICI; every host
    keeps the whole staged tree addressable for regroup snapshots). The
    resolver gives the stage axis precedence and excludes model/seq —
    all three lay out the same intra-process device slice."""
    from elasticdl_tpu.parallel.mesh import STAGE_AXIS, AxisDemand

    return AxisDemand(STAGE_AXIS, int(n_stages), intra_process=True)


def pipeline_apply(stage_fn, stage_params, x_micro, axis_name="stage",
                   rng=None, batch_axis=None):
    """Run microbatches through the pipeline. Call INSIDE shard_map.

    stage_fn: (params_for_one_stage, x_microbatch) -> y_microbatch, with
      output shaped like the input (the inter-stage activation contract).
      When `rng` is given, called as (params, x, tick_rng) instead, with
      tick_rng distinct per (stage, tick, data-shard) — fold_in of the
      stage index, tick counter, and (when `batch_axis` names a DP mesh
      axis) the data-shard index — so stochastic layers (dropout) draw
      independent bits per stage, microbatch, and batch shard.
    stage_params: pytree whose leaves have a leading stage axis; sharded
      over `axis_name`, so inside shard_map the local leading dim is 1.
    x_micro: [M, mb, ...] microbatched input, replicated over `axis_name`.
    Returns [M, mb, ...] outputs, replicated over `axis_name` (the last
    stage's results are broadcast with a masked psum).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    params_local = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    num_micro = x_micro.shape[0]
    ticks = num_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # Stage 0 consumes fresh microbatch t during the fill; other
        # stages consume what arrived from their neighbor last tick.
        fresh = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, num_micro - 1), 0, keepdims=False
        )
        inp = jnp.where(stage == 0, fresh, state)
        if rng is None:
            out = stage_fn(params_local, inp)
        else:
            tick_rng = jax.random.fold_in(
                jax.random.fold_in(rng, stage), t
            )
            if batch_axis is not None:
                # rng enters shard_map replicated; without this fold the
                # same dropout mask would repeat across every DP shard.
                tick_rng = jax.random.fold_in(
                    tick_rng, jax.lax.axis_index(batch_axis)
                )
            out = stage_fn(params_local, inp, tick_rng)
        # The last stage banks microbatch t-(N-1) once the pipe is full.
        out_idx = t - (n_stages - 1)
        bank = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
        safe = jnp.clip(out_idx, 0, num_micro - 1)
        cur = jax.lax.dynamic_index_in_dim(outputs, safe, 0,
                                           keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(bank, out, cur), safe, 0
        )
        state = jax.lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(x_micro[0])
    outputs0 = jnp.zeros_like(x_micro)
    (_, outputs), _ = jax.lax.scan(
        tick, (state0, outputs0), jnp.arange(ticks)
    )
    # Broadcast the last stage's banked outputs to every stage so the
    # result is replicated over the pipeline axis.
    return jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, 0), axis_name
    )


def make_pipeline(stage_fn, mesh, axis_name="stage", batch_axis=None,
                  remat=False, remat_policy=None):
    """shard_map-wrapped pipeline: takes GLOBAL (stage_params, x_micro)
    with params stacked [n_stages, ...] (sharded over `axis_name`) and
    x_micro [M, mb, ...] (optionally sharded over `batch_axis` on mb for
    DP x PP meshes); returns [M, mb, ...] outputs with x's sharding."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if remat:
        kwargs = {}
        if remat_policy:
            kwargs["policy"] = getattr(
                jax.checkpoint_policies, remat_policy
            )
        stage_fn = jax.checkpoint(stage_fn, **kwargs)
    x_spec = P(None, batch_axis)

    def _validate(stage_params, x_micro):
        # Fail with actionable messages instead of shard_map internals.
        n_stages = mesh.shape[axis_name]
        lead = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        if lead != n_stages:
            raise ValueError(
                f"stage_params leading dim {lead} != mesh axis "
                f"{axis_name!r} size {n_stages}"
            )
        if batch_axis is not None:
            dp = mesh.shape[batch_axis]
            mb = x_micro.shape[1]
            if mb % dp:
                raise ValueError(
                    f"microbatch size {mb} not divisible by "
                    f"{batch_axis!r} axis size {dp}; adjust the batch "
                    f"size or num_microbatches"
                )

    def wrapper(stage_params, x_micro, rng=None):
        _validate(stage_params, x_micro)
        p_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), stage_params
        )
        if rng is None:
            def run(stage_params, x_micro):
                return pipeline_apply(
                    stage_fn, stage_params, x_micro, axis_name=axis_name
                )

            return shard_map(
                run,
                mesh=mesh,
                in_specs=(p_specs, x_spec),
                out_specs=x_spec,
                check_vma=False,
            )(stage_params, x_micro)

        def run_rng(stage_params, x_micro, rng):
            return pipeline_apply(
                stage_fn, stage_params, x_micro, axis_name=axis_name,
                rng=rng, batch_axis=batch_axis,
            )

        return shard_map(
            run_rng,
            mesh=mesh,
            in_specs=(p_specs, x_spec, P()),
            out_specs=x_spec,
            check_vma=False,
        )(stage_params, x_micro, rng)

    return wrapper


def microbatch(x, num_microbatches):
    """[B, ...] -> [M, B//M, ...]; B must divide evenly."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by {num_microbatches} microbatches"
        )
    return x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])


def unmicrobatch(y):
    """[M, mb, ...] -> [M*mb, ...]."""
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])


def stack_stage_params(per_stage):
    """List of per-stage param pytrees -> one pytree with a leading stage
    axis (what pipeline_apply expects, sharded P('stage', ...))."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage
    )


# ---------- pipelined transformer LM (flagship integration) ----------


def make_lm_pipeline(cfg, mesh, n_stages, num_microbatches,
                     axis_name="stage", batch_axis=None):
    """A pipelined build of the flagship transformer LM: embedding and LM
    head run replicated over the stage axis (they are a small fraction of
    the FLOPs), the Block stack is split into `n_stages` equal stages and
    pipelined. Returns (init_fn, apply_fn):

      init_fn(rng, sample_tokens) -> params
          {"embed": ..., "stages": stacked [n_stages, ...], "head": ...}
      apply_fn(params, tokens, training=False) -> [B, S, vocab] logits
    """
    import flax.linen as nn

    from elasticdl_tpu.models.transformer.transformer_lm import (
        Block,
        embed_input,
        head_output,
    )

    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by {n_stages} stages"
        )
    layers_per_stage = cfg.n_layers // n_stages

    # Thin module shells around the SAME embed/head implementations the
    # monolithic TransformerLM uses (transformer_lm.embed_input /
    # head_output) — the only pipeline-specific structure is the stage
    # grouping of Blocks.
    class EmbedIn(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return embed_input(cfg, tokens)

    class Stage(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            for _ in range(layers_per_stage):
                x = Block(cfg)(x, training)
            return x

    class HeadOut(nn.Module):
        @nn.compact
        def __call__(self, x):
            return head_output(cfg, x)

    embed_mod, stage_mod, head_mod = EmbedIn(), Stage(), HeadOut()

    def init_fn(rng, sample_tokens):
        r_embed, r_stage, r_head = jax.random.split(rng, 3)
        embed_p = embed_mod.init(r_embed, sample_tokens)["params"]
        sample_x = embed_mod.apply({"params": embed_p}, sample_tokens)
        mb = sample_x[: max(1, sample_x.shape[0] // num_microbatches)]
        stage_rngs = jax.random.split(r_stage, n_stages)
        stacked = jax.vmap(
            lambda r: stage_mod.init(r, mb, False)["params"]
        )(stage_rngs)
        head_p = head_mod.init(r_head, mb)["params"]
        return {"embed": embed_p, "stages": stacked, "head": head_p}

    def apply_fn(params, tokens, training=False, rngs=None):
        x = embed_mod.apply({"params": params["embed"]}, tokens)
        x_micro = microbatch(x, num_microbatches)
        dropout_rng = (rngs or {}).get("dropout")
        need_rng = bool(cfg.dropout) and training
        if need_rng and dropout_rng is None:
            raise ValueError(
                "training with cfg.dropout > 0 requires "
                "rngs={'dropout': key} (per-stage/tick keys are derived "
                "inside the pipeline)"
            )
        if need_rng:
            def stage_fn(p, xm, r):
                return stage_mod.apply(
                    {"params": p}, xm, training, rngs={"dropout": r}
                )
        else:
            def stage_fn(p, xm):
                return stage_mod.apply({"params": p}, xm, training)

        pipe = make_pipeline(
            stage_fn, mesh, axis_name=axis_name, batch_axis=batch_axis,
            remat=cfg.remat, remat_policy=cfg.remat_policy,
        )
        y = unmicrobatch(
            pipe(params["stages"], x_micro, dropout_rng)
            if need_rng
            else pipe(params["stages"], x_micro)
        )
        return head_mod.apply({"params": params["head"]}, y)

    return init_fn, apply_fn


def make_lm_sequential(cfg, total_rows):
    """Schedule-free forward over the pipelined LM param tree: embed ->
    lax.scan over the stacked stage rows -> head. Mathematically identical
    to the monolithic TransformerLM (the stacked rows ARE the layer stack,
    in order: gpipe/1f1b stack stages 0..N-1 and the interleaved build's
    public tree is chunk-ordered, i.e. also sequential). Needs no mesh and
    no stage axis, so it serves as (a) the evaluation/prediction forward —
    eval tasks run on ONE worker's local devices — and (b) the trainer's
    pure-DP fallback when an elastic world can't host the stage axis,
    keeping the param tree (and therefore checkpoints, broadcasts, and
    optimizer state) intact across the degradation.

    total_rows: leading dim of params["stages"] (n_stages, or
    n_stages * virtual chunks for the interleaved build)."""
    import flax.linen as nn

    from elasticdl_tpu.models.transformer.transformer_lm import (
        Block,
        embed_input,
        head_output,
    )

    if cfg.n_layers % total_rows:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by {total_rows} "
            f"stage rows"
        )
    layers_per_row = cfg.n_layers // total_rows

    class EmbedIn(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return embed_input(cfg, tokens)

    class Stage(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            for _ in range(layers_per_row):
                x = Block(cfg)(x, training)
            return x

    class HeadOut(nn.Module):
        @nn.compact
        def __call__(self, x):
            return head_output(cfg, x)

    embed_mod, stage_mod, head_mod = EmbedIn(), Stage(), HeadOut()

    def apply_fn(params, tokens, training=False, rngs=None):
        x = embed_mod.apply({"params": params["embed"]}, tokens)
        dropout_rng = (rngs or {}).get("dropout")
        if bool(cfg.dropout) and training and dropout_rng is not None:
            keys = jax.random.split(dropout_rng, total_rows)

            def body(h, xs):
                row_p, key = xs
                return (
                    stage_mod.apply(
                        {"params": row_p}, h, training,
                        rngs={"dropout": key},
                    ),
                    None,
                )

            x, _ = jax.lax.scan(body, x, (params["stages"], keys))
        else:

            def body(h, row_p):
                return stage_mod.apply({"params": row_p}, h, training), None

            x, _ = jax.lax.scan(body, x, params["stages"])
        return head_mod.apply({"params": params["head"]}, x)

    return apply_fn


# ---------- 1F1B schedule ----------


def vocab_parallel_head_loss(cfg, head_ln, v_loc, axis_name, head_params,
                             y, labels_m, shard):
    """Vocab-parallel CE for one microbatch, shared by the 1F1B and
    interleaved-1F1B schedules: each shard computes its [v_loc] logit
    slice; pmax/psum over `axis_name` assemble the full log-sum-exp and
    label logit. Returns the mean CE over this shard's tokens.

    Gradient conventions the CALLER must match: under shard_map with
    check_vma=False the internal psums TRANSPOSE TO PSUM, so each
    device's vjp cotangents (d_head, dy) come out axis-size x their true
    share — combine with psum(...)/n. The max is stop_gradient'd BEFORE
    the pmax (pmax has no differentiation rule; the max only stabilizes
    the exp)."""
    z = head_ln.apply(
        {"params": head_params["LayerNorm_0"]}, y
    ).astype(jnp.float32)
    kernel = head_params["lm_head"]["kernel"].astype(jnp.float32)
    bias = head_params["lm_head"]["bias"].astype(jnp.float32)
    k_loc = jax.lax.dynamic_slice_in_dim(
        kernel, shard * v_loc, v_loc, axis=1
    )
    b_loc = jax.lax.dynamic_slice_in_dim(bias, shard * v_loc, v_loc, 0)
    logits = z @ k_loc + b_loc  # [mb, S, v_loc]
    m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    m_glob = jax.lax.pmax(m_loc, axis_name)
    sumexp = jnp.sum(jnp.exp(logits - m_glob[..., None]), axis=-1)
    lse = m_glob + jnp.log(jax.lax.psum(sumexp, axis_name))
    rel = labels_m.astype(jnp.int32) - shard * v_loc
    in_range = (rel >= 0) & (rel < v_loc)
    gathered = jnp.take_along_axis(
        logits, jnp.clip(rel, 0, v_loc - 1)[..., None], axis=-1
    )[..., 0]
    label_logit = jax.lax.psum(
        jnp.where(in_range, gathered, 0.0), axis_name
    )
    return jnp.mean(lse - label_logit)


def make_lm_pipeline_1f1b(cfg, mesh, n_stages, num_microbatches,
                          axis_name="stage", batch_axis=None):
    """1F1B-scheduled pipelined LM training: returns (init_fn,
    loss_and_grads_fn) where loss_and_grads_fn(params, tokens, labels,
    rng=None) -> (loss, grads) with grads shaped like params.

    Same param tree as make_lm_pipeline (init functions are
    interchangeable); different schedule and memory shape:

    - GPipe above banks the inter-stage activation of EVERY tick for scan
      autodiff: O(M) residency per device. Here backward for microbatch m
      starts as soon as its forward leaves the last stage (classic 1F1B:
      bwd of m at stage i runs at tick m + 2(N-1) - i), so a stage only
      stashes the inputs of its in-flight microbatches — a 2N-deep ring,
      O(stages) residency independent of M. The stage backward re-runs its
      forward inside jax.vjp (the remat recipe), so compute matches
      remat'd GPipe.
    - SPMD uniformity: shard_map compiles ONE program for all stages, so
      per-stage special-casing must be masked, not branched. The LM head
      would be a masked hot spot (only the last stage needs it), so it is
      VOCAB-PARALLEL over the stage axis instead: every tick, every stage
      computes its V/N logit slice of the freshly-finished microbatch and
      the cross-entropy combines with pmax/psum — total head FLOPs equal
      the unsharded head, spread evenly, nothing masked out. Embedding is
      folded into stage 0's forward (a gather; uniform-cost tax is
      negligible) so its gradient rides the normal stage backward.
    - The loss (not logits) is the output: 1F1B exists to avoid
      materializing per-microbatch activations, so the training contract
      is loss_and_grads, not apply.

    Schedule: T = M + 2(N-1) ticks; stage i runs fwd of microbatch m at
    tick m + i and bwd of m at tick m + 2(N-1) - i; activations hop
    forward and gradients hop backward on neighbor-only ppermute rings.
    """
    import flax.linen as nn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.models.transformer.transformer_lm import (
        Block,
        embed_input,
    )

    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by {n_stages} stages"
        )
    if cfg.vocab % n_stages:
        raise ValueError(
            f"vocab {cfg.vocab} not divisible by {n_stages} stages "
            f"(the 1F1B head is vocab-parallel over the stage axis)"
        )
    layers_per_stage = cfg.n_layers // n_stages
    v_loc = cfg.vocab // n_stages
    act_dtype = jnp.dtype(cfg.activation_dtype)

    class EmbedIn(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            return embed_input(cfg, tokens)

    class Stage(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            for _ in range(layers_per_stage):
                x = Block(cfg)(x, training)
            return x

    embed_mod, stage_mod = EmbedIn(), Stage()
    # Head params match make_lm_pipeline's HeadOut: LayerNorm_0 + lm_head.
    head_ln = nn.LayerNorm(dtype=act_dtype, name=None)

    def init_fn(rng, sample_tokens):
        # Delegate to the GPipe factory: identical param tree by
        # construction, so checkpoints/optimizer state transfer between
        # schedules.
        gpipe_init, _ = make_lm_pipeline(
            cfg, mesh, n_stages, num_microbatches,
            axis_name=axis_name, batch_axis=batch_axis,
        )
        return gpipe_init(rng, sample_tokens)

    def _head_loss(head_params, y, labels_m, stage):
        return vocab_parallel_head_loss(
            cfg, head_ln, v_loc, axis_name, head_params, y, labels_m,
            stage,
        )

    def _stage_forward(stage_params, embed_params, x_in, tokens_m, stage,
                       training, rng_m):
        """Uniform per-tick stage program: stage 0 embeds its tokens, the
        rest consume the neighbor activation; then this stage's blocks.
        The jnp.where routes gradients correctly (the unselected branch
        gets a zero cotangent), so one vjp of this function yields
        d_stage, d_embed (nonzero only on stage 0) and dx."""
        emb = embed_mod.apply({"params": embed_params}, tokens_m)
        h = jnp.where(stage == 0, emb, x_in)
        if rng_m is None:
            return stage_mod.apply({"params": stage_params}, h, training)
        return stage_mod.apply(
            {"params": stage_params}, h, training,
            rngs={"dropout": rng_m},
        )

    def _pipeline_1f1b(stages_p, embed_p, head_p, tokens_mb, labels_mb,
                       rng):
        n = n_stages
        stage = jax.lax.axis_index(axis_name)
        params_local = jax.tree_util.tree_map(lambda a: a[0], stages_p)
        num_micro = tokens_mb.shape[0]
        ticks = num_micro + 2 * (n - 1)
        stash_depth = 2 * n
        mb, s = tokens_mb.shape[1], tokens_mb.shape[2]
        act_shape = (mb, s, cfg.d_model)
        perm_fwd = [(i, (i + 1) % n) for i in range(n)]
        perm_bwd = [(i, (i - 1) % n) for i in range(n)]
        training = True

        def rng_for(m):
            if rng is None:
                return None
            r = jax.random.fold_in(jax.random.fold_in(rng, stage), m)
            if batch_axis is not None:
                r = jax.random.fold_in(
                    r, jax.lax.axis_index(batch_axis)
                )
            return r

        zero_grads = (
            jax.tree_util.tree_map(jnp.zeros_like, params_local),
            jax.tree_util.tree_map(jnp.zeros_like, embed_p),
            jax.tree_util.tree_map(jnp.zeros_like, head_p),
        )

        def tick(carry, t):
            fwd_msg, bwd_msg, stash, grads, loss_sum = carry
            d_stage_acc, d_embed_acc, d_head_acc = grads

            # ---- forward slot: microbatch m_f = t - stage ----
            m_f = t - stage
            fwd_valid = jnp.logical_and(m_f >= 0, m_f < num_micro)
            m_f_safe = jnp.clip(m_f, 0, num_micro - 1)
            tokens_f = jax.lax.dynamic_index_in_dim(
                tokens_mb, m_f_safe, 0, keepdims=False
            )
            y = _stage_forward(
                params_local, embed_p, fwd_msg, tokens_f, stage,
                training, rng_for(m_f_safe),
            )
            # Stash the consumed input for this microbatch's backward.
            slot = m_f_safe % stash_depth
            cur = jax.lax.dynamic_index_in_dim(
                stash, slot, 0, keepdims=False
            )
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(fwd_valid, fwd_msg, cur), slot, 0
            )

            # ---- head slot: the microbatch that just left the last
            # stage (m_h = t - (N-1)), vocab-parallel on every stage ----
            m_h = t - (n - 1)
            head_valid = jnp.logical_and(m_h >= 0, m_h < num_micro)
            m_h_safe = jnp.clip(m_h, 0, num_micro - 1)
            y_last = jax.lax.psum(
                jnp.where(stage == n - 1, y, 0.0), axis_name
            )
            labels_h = jax.lax.dynamic_index_in_dim(
                labels_mb, m_h_safe, 0, keepdims=False
            )
            loss_m, head_vjp = jax.vjp(
                lambda hp, yy: _head_loss(hp, yy, labels_h, stage),
                head_p,
                y_last,
            )
            d_head_c, dy = head_vjp(jnp.float32(1.0 / num_micro))
            # Combining the per-slice vjp partials: under shard_map with
            # check_vma=False the psums inside _head_loss TRANSPOSE TO
            # PSUM, so each device's raw cotangent is already n x its true
            # share; psum-then-divide yields the exact total (verified
            # numerically against GPipe autodiff — a plain psum here reads
            # n x high on every leaf).
            dy = jax.lax.psum(dy, axis_name) / n
            loss_sum = loss_sum + jnp.where(
                head_valid, loss_m / num_micro, 0.0
            )
            d_head_acc = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(head_valid, g, 0.0),
                d_head_acc,
                d_head_c,
            )

            # ---- backward slot: microbatch m_b = t - 2(N-1) + stage ----
            m_b = t - 2 * (n - 1) + stage
            bwd_valid = jnp.logical_and(m_b >= 0, m_b < num_micro)
            m_b_safe = jnp.clip(m_b, 0, num_micro - 1)
            x_b = jax.lax.dynamic_index_in_dim(
                stash, m_b_safe % stash_depth, 0, keepdims=False
            )
            tokens_b = jax.lax.dynamic_index_in_dim(
                tokens_mb, m_b_safe, 0, keepdims=False
            )
            # The last stage's backward seed is the dy it just computed
            # (its bwd tick for m coincides with m's head tick); other
            # stages consume the gradient hopped back from their
            # successor.
            g = jnp.where(
                stage == n - 1, dy.astype(act_dtype), bwd_msg
            )
            _, stage_vjp = jax.vjp(
                lambda sp, ep, xx: _stage_forward(
                    sp, ep, xx, tokens_b, stage, training,
                    rng_for(m_b_safe),
                ),
                params_local,
                embed_p,
                x_b,
            )
            d_stage_c, d_embed_c, dx = stage_vjp(g)
            d_stage_acc = jax.tree_util.tree_map(
                lambda acc, gg: acc + jnp.where(bwd_valid, gg, 0.0),
                d_stage_acc,
                d_stage_c,
            )
            d_embed_acc = jax.tree_util.tree_map(
                lambda acc, gg: acc + jnp.where(bwd_valid, gg, 0.0),
                d_embed_acc,
                d_embed_c,
            )

            # ---- neighbor hops ----
            fwd_msg = jax.lax.ppermute(
                jnp.where(fwd_valid, y, 0.0), axis_name, perm_fwd
            )
            bwd_msg = jax.lax.ppermute(
                jnp.where(bwd_valid, dx, 0.0), axis_name, perm_bwd
            )
            return (
                fwd_msg,
                bwd_msg,
                stash,
                (d_stage_acc, d_embed_acc, d_head_acc),
                loss_sum,
            ), None

        carry0 = (
            jnp.zeros(act_shape, act_dtype),
            jnp.zeros(act_shape, act_dtype),
            jnp.zeros((stash_depth, *act_shape), act_dtype),
            zero_grads,
            jnp.float32(0.0),
        )
        (_, _, _, grads, loss_sum), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks)
        )
        d_stage_acc, d_embed_acc, d_head_acc = grads
        # Each device accumulated only its own masked share of the
        # replicated embed/head grads and loss: combine over the stage
        # axis (loss was computed replicated per tick, so mean it).
        d_embed = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, axis_name), d_embed_acc
        )
        # Head partials carry the same n x transpose factor as dy (see
        # the head slot); embed partials do not (stage_forward has no
        # internal collectives, and only stage 0's contribution is
        # nonzero).
        d_head = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, axis_name) / n, d_head_acc
        )
        loss = jax.lax.pmean(loss_sum, axis_name)
        if batch_axis is not None:
            # Data-parallel composition: every grad (and the loss) is the
            # mean over batch shards.
            d_embed, d_head, d_stage_acc, loss = jax.tree_util.tree_map(
                lambda gg: jax.lax.pmean(gg, batch_axis),
                (d_embed, d_head, d_stage_acc, loss),
            )
        # Restore the stacked leading stage dim for the out_spec.
        d_stages = jax.tree_util.tree_map(
            lambda gg: gg[None], d_stage_acc
        )
        return loss, {
            "embed": d_embed,
            "stages": d_stages,
            "head": d_head,
        }

    def loss_and_grads_fn(params, tokens, labels, rng=None):
        if bool(cfg.dropout) and rng is None:
            raise ValueError(
                "training with cfg.dropout > 0 requires an explicit rng "
                "(per-stage/microbatch keys are derived inside the "
                "pipeline)"
            )
        tokens_mb = microbatch(
            jnp.asarray(tokens, jnp.int32), num_microbatches
        )
        labels_mb = microbatch(
            jnp.asarray(labels, jnp.int32), num_microbatches
        )
        stage_specs = jax.tree_util.tree_map(
            lambda _: P(axis_name), params["stages"]
        )
        repl_specs_e = jax.tree_util.tree_map(
            lambda _: P(), params["embed"]
        )
        repl_specs_h = jax.tree_util.tree_map(
            lambda _: P(), params["head"]
        )
        x_spec = P(None, batch_axis)
        in_specs = (
            stage_specs, repl_specs_e, repl_specs_h, x_spec, x_spec,
        )
        out_specs = (
            P(),
            {
                "embed": repl_specs_e,
                "stages": stage_specs,
                "head": repl_specs_h,
            },
        )
        if rng is None:
            return shard_map(
                lambda sp, ep, hp, tm, lm: _pipeline_1f1b(
                    sp, ep, hp, tm, lm, None
                ),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            )(
                params["stages"], params["embed"], params["head"],
                tokens_mb, labels_mb,
            )
        return shard_map(
            _pipeline_1f1b,
            mesh=mesh,
            in_specs=in_specs + (P(),),
            out_specs=out_specs,
            check_vma=False,
        )(
            params["stages"], params["embed"], params["head"],
            tokens_mb, labels_mb, rng,
        )

    return init_fn, loss_and_grads_fn


def lm_pipeline_param_specs(params, axis_name="stage"):
    """PartitionSpecs for make_lm_pipeline params: stages sharded over the
    pipeline axis on their stacked leading dim, embed/head replicated —
    feed through NamedSharding for jit in_shardings."""
    from jax.sharding import PartitionSpec as P

    return {
        "embed": jax.tree_util.tree_map(lambda _: P(), params["embed"]),
        "stages": jax.tree_util.tree_map(
            lambda _: P(axis_name), params["stages"]
        ),
        "head": jax.tree_util.tree_map(lambda _: P(), params["head"]),
    }
