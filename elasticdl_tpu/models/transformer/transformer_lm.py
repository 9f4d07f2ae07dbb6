"""Decoder-only transformer LM — the long-context flagship model.

No reference counterpart (the reference zoo is CNNs/recsys; long-context is
this framework's extension). TPU-first choices: bfloat16 activations with
float32 params/softmax, flash attention (ops/flash_attention.py) on the
local path, and a pluggable attention callable so the DP+SP training step
can drop in ring attention or Ulysses (parallel/ring_attention.py,
parallel/ulysses.py) over a ("data", "seq") mesh — see
__graft_entry__.dryrun_multichip for the sharded wiring.

Model spec contract (common/model_utils.py): custom_model / loss /
optimizer / feed / eval_metrics_fn.
"""

import dataclasses
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.evaluation_utils import MeanMetric
from elasticdl_tpu.common.model_utils import Modes
from elasticdl_tpu.data.example import batch_examples
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import flash_attention

VOCAB = 256
D_MODEL = 128
N_HEADS = 4
N_LAYERS = 2
MAX_LEN = 256


@dataclasses.dataclass(frozen=True)
class LMConfig:
    vocab: int = VOCAB
    d_model: int = D_MODEL
    n_heads: int = N_HEADS
    n_layers: int = N_LAYERS
    max_len: int = MAX_LEN
    dropout: float = 0.0
    # attention(q, k, v) with causal masking baked in; None -> local flash.
    attention: Optional[Callable] = None
    # bfloat16 activations keep the MXU in its native dtype.
    activation_dtype: str = "bfloat16"
    # Rematerialize each block in the backward pass: trades ~1/3 more FLOPs
    # for O(layers) instead of O(layers x activations) memory — the standard
    # long-context recipe (jax.checkpoint).
    remat: bool = False
    # Name of a jax.checkpoint_policies policy refining WHAT remat saves
    # (None = recompute everything). "dots_with_no_batch_dims_saveable"
    # keeps matmul outputs resident so the backward pass skips re-running
    # the MXU-heavy projections — spends HBM to win step time when the
    # activations still fit.
    remat_policy: Optional[str] = None

    def __post_init__(self):
        validate_remat_policy(self.remat, self.remat_policy)


def validate_remat_policy(remat, remat_policy):
    """Config-time validation shared by the dense and MoE LM configs."""
    if remat_policy is None:
        return
    if not remat:
        raise ValueError(
            "remat_policy is set but remat=False — the policy would be "
            "silently ignored; enable remat or drop the policy"
        )
    if not hasattr(jax.checkpoint_policies, remat_policy):
        raise ValueError(
            f"unknown remat_policy {remat_policy!r} (see "
            f"jax.checkpoint_policies)"
        )


def flagship_config(max_len: int = 4096) -> "LMConfig":
    """The >=100M-param long-context config validated on a real chip
    (benchmark cell lm_flagship.steady): 151M transformer params + 34M embeddings,
    head_dim 128 (the fast Pallas flash-attention tile).

    remat is OFF by default: the round-4 sweep on one TPU v5e (16 GB)
    measured the full activation set fitting at batch 4/S=4096 AND batch
    2/S=8192, with remat=False beating the best remat policy by ~14%
    tokens/sec at both lengths (60.1k -> 68.7k @4096; 47.8k -> 54.9k
    @8192) — recompute was pure FLOP overhead, not a memory necessity, at
    single-chip flagship scale. Re-enable remat (policy
    "dots_with_no_batch_dims_saveable" measured best) for bigger batches,
    longer contexts, or shared-HBM multi-model settings where activations
    stop fitting."""
    return LMConfig(
        vocab=32768,
        d_model=1024,
        n_heads=8,
        n_layers=12,
        max_len=max_len,
        remat=False,
    )


def _default_attention(q, k, v):
    return flash_attention(q, k, v, True)


class MultiHeadAttention(nn.Module):
    config: LMConfig

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.config
        head_dim = cfg.d_model // cfg.n_heads
        dtype = jnp.dtype(cfg.activation_dtype)
        qkv = nn.DenseGeneral(
            (3, cfg.n_heads, head_dim), dtype=dtype, name="qkv"
        )(x)
        # [B, S, 3, H, Dh] -> three [B, H, S, Dh]
        q, k, v = jnp.moveaxis(qkv, 2, 0)
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.swapaxes(k, 1, 2)
        v = jnp.swapaxes(v, 1, 2)
        if cfg.attention is None:
            # q, k, v cross the kernels' boundary in the activation dtype
            # and o, dq, dk, dv come back in it: flash_attention runs its
            # scores and softmax in float32 itself, tile by tile.
            out = _default_attention(q, k, v)
        else:
            # The context-parallel callables are handed float32.
            out = cfg.attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
            ).astype(dtype)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], cfg.d_model)
        return nn.Dense(cfg.d_model, dtype=dtype, name="proj")(out)


class Block(nn.Module):
    config: LMConfig

    @nn.compact
    def __call__(self, x, training=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        h = nn.LayerNorm(dtype=dtype)(x)
        x = x + MultiHeadAttention(cfg)(h, training)
        h = nn.LayerNorm(dtype=dtype)(x)
        h = nn.Dense(4 * cfg.d_model, dtype=dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(cfg.d_model, dtype=dtype)(h)
        if cfg.dropout:
            h = nn.Dropout(cfg.dropout, deterministic=not training)(h)
        return x + h


def embed_input(cfg, tokens):
    """Token + positional embedding. A plain function (not a submodule) so
    both TransformerLM and the pipelined build (parallel/pipeline.py) share
    one implementation without changing either's param tree — flax registers
    the named submodules on whichever module's compact scope is active."""
    dtype = jnp.dtype(cfg.activation_dtype)
    s = tokens.shape[1]
    if s > cfg.max_len:
        # Without this, the positional gather would silently clamp
        # out-of-range indices under XLA and corrupt positions.
        raise ValueError(
            f"sequence length {s} exceeds max_len {cfg.max_len}"
        )
    x = nn.Embed(cfg.vocab, cfg.d_model, dtype=dtype, name="tok_emb")(
        tokens.astype(jnp.int32)
    )
    pos = nn.Embed(cfg.max_len, cfg.d_model, dtype=dtype,
                   name="pos_emb")(jnp.arange(s))
    return x + pos[None]


def head_output(cfg, x):
    """Final LayerNorm + LM head; shared with the pipelined build (see
    embed_input). Logits in float32: softmax/CE stay out of bfloat16."""
    x = nn.LayerNorm(dtype=jnp.dtype(cfg.activation_dtype))(x)
    return nn.Dense(cfg.vocab, dtype=jnp.float32, name="lm_head")(x)


class TransformerLM(nn.Module):
    config: LMConfig = LMConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        x = embed_input(cfg, tokens)
        if cfg.remat:
            kwargs = {"static_argnums": (2,)}
            if cfg.remat_policy:
                kwargs["policy"] = getattr(
                    jax.checkpoint_policies, cfg.remat_policy
                )
            block_cls = nn.remat(Block, **kwargs)
        else:
            block_cls = Block
        for _ in range(cfg.n_layers):
            x = block_cls(cfg)(x, training)
        return head_output(cfg, x)


# ---------- model spec contract ----------


def custom_model(config: LMConfig = None):
    return TransformerLM(config or LMConfig())


def loss(labels, logits):
    """Next-token CE; labels [B, S] int, logits [B, S, V]."""
    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits, labels.astype(jnp.int32)
        )
    )


def optimizer():
    return optimizers.adam(learning_rate=3e-4)


def feed(records, mode, metadata):
    batch = batch_examples(records)
    tokens = batch["tokens"].astype(np.int32)  # [B, S+1]
    features = tokens[:, :-1]
    labels = tokens[:, 1:] if mode != Modes.PREDICTION else None
    return features, labels


def param_specs(variables):
    """Model-spec hook for hybrid DP x TP (worker --model_parallel_size):
    Megatron-style PartitionSpecs over the "model" mesh axis for the param
    collection, everything else (batch stats etc.) replicated."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel.tensor_parallel import (
        transformer_param_specs,
    )

    return {
        k: (
            transformer_param_specs(v)
            if k == "params"
            else jax.tree_util.tree_map(lambda _: P(), v)
        )
        for k, v in variables.items()
    }


def context_parallel_model(mesh, axis_name="seq", batch_axis="data",
                           head_axis=None, impl="zigzag", config=None):
    """Model-spec hook for sequence/context parallelism (worker
    --context_parallel_size): rebuild the LM with its attention bound to
    `mesh`'s sequence axis — zigzag ring (balanced causal ring,
    parallel/ring_attention.py), plain ring, or Ulysses all-to-all
    (parallel/ulysses.py). The attention callable is parameterless, so
    the param tree is IDENTICAL to the plain LM's: elastic transitions
    between SP worlds and pure-DP worlds carry (params, opt_state)
    untouched, and checkpoints are interchangeable. head_axis names a
    tensor-parallel mesh axis to also shard heads over (ring only) for
    the 3-D DP x TP x SP composition."""
    cfg = config or LMConfig()
    if impl == "zigzag":
        from elasticdl_tpu.parallel.ring_attention import (
            make_zigzag_ring_attention,
        )

        attn = make_zigzag_ring_attention(
            mesh, axis_name=axis_name, causal=True,
            batch_axis=batch_axis, head_axis=head_axis,
        )
    elif impl == "ring":
        from elasticdl_tpu.parallel.ring_attention import (
            make_ring_attention,
        )

        attn = make_ring_attention(
            mesh, axis_name=axis_name, causal=True,
            batch_axis=batch_axis, head_axis=head_axis,
        )
    elif impl == "ulysses":
        if head_axis is not None:
            raise ValueError(
                "ulysses re-shards heads itself (all-to-all) and cannot "
                "also shard them over a tensor-parallel axis; use "
                "impl='zigzag' for the 3-D composition"
            )
        from elasticdl_tpu.parallel.ulysses import make_ulysses_attention

        attn = make_ulysses_attention(
            mesh, axis_name=axis_name, causal=True, batch_axis=batch_axis
        )
    else:
        raise ValueError(f"unknown context-parallel impl {impl!r}")
    return custom_model(dataclasses.replace(cfg, attention=attn))


def pipeline_spec(mesh, n_stages, num_microbatches, schedule="1f1b",
                  batch_axis=None, virtual_stages=2, config=None):
    """Model-spec stage hook for pipeline parallelism (worker
    --pipeline_stages N --pipeline_schedule {gpipe,1f1b,interleaved}), the
    staged twin of the param_specs hook: returns a
    parallel.pipeline.PipelineBuild binding this LM's Block stack to the
    requested schedule on `mesh`'s "stage" axis. All three schedules share
    one param tree ({embed, stages[rows], head}), so checkpoints and
    optimizer state transfer between them, and the schedule-free apply_fn
    (make_lm_sequential) evaluates/predicts on any mesh."""
    from elasticdl_tpu.parallel import pipeline as plib

    cfg = config or LMConfig()
    total_rows = n_stages
    if schedule == "interleaved":
        from elasticdl_tpu.parallel.pipeline_interleaved import (
            make_lm_pipeline_interleaved,
        )

        total_rows = n_stages * virtual_stages
        init_fn, lg_fn = make_lm_pipeline_interleaved(
            cfg, mesh, n_stages, virtual_stages, num_microbatches,
            batch_axis=batch_axis,
        )
    elif schedule == "1f1b":
        init_fn, lg_fn = plib.make_lm_pipeline_1f1b(
            cfg, mesh, n_stages, num_microbatches, batch_axis=batch_axis
        )
    elif schedule == "gpipe":
        init_fn, train_apply = plib.make_lm_pipeline(
            cfg, mesh, n_stages, num_microbatches, batch_axis=batch_axis
        )

        def lg_fn(params, tokens, labels, rng=None):
            def loss_of(p):
                rngs = {"dropout": rng} if rng is not None else None
                return loss(
                    labels, train_apply(p, tokens, training=True, rngs=rngs)
                )

            return jax.value_and_grad(loss_of)(params)

    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    apply_fn = plib.make_lm_sequential(cfg, total_rows)

    def param_specs_fn(params):
        return plib.lm_pipeline_param_specs(params)

    return plib.PipelineBuild(init_fn, lg_fn, apply_fn, param_specs_fn)


def token_ce(outputs, labels):
    """Per-token CE from logits (numpy; eval-metric building block, also
    reused by the MoE variant on its logits field)."""
    logits = np.asarray(outputs, np.float32)
    labels = np.asarray(labels).astype(np.int64)
    logits = logits - logits.max(-1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    return -np.take_along_axis(logp, labels[..., None], -1)


def eval_metrics_fn():
    return {"token_ce": MeanMetric(token_ce)}
