"""The Nemotron-H hybrid decoder (HF `model_type: nemotron_h`): one mixer a
layer, chosen by a pattern string, behind a pre-norm residual.

    x = x + mixer(RMSNorm(x))          one letter of `hybrid_override_pattern`
      M  Mamba-2 mixer                 layers/mamba2.py
      *  causal GQA attention          ops/flash_attention.py, no position
                                       signal (the Mamba layers carry order)
      E  top-k routed experts with a   layers/moe.py RoutedExperts
         shared expert
    (The HF model's `-` layers, a plain feed-forward of `intermediate_size`,
    are not built: no published pattern has one.)
    final RMSNorm, untied lm_head, no bias anywhere but the conv.

`NemotronHConfig` takes the keys of the public `config.json` under their own
names (`from_public`), plus `experts_held = (first, count)`: the share of
each `E` layer's experts that lives on this chip (None = all of them).
Float32 parameters, bfloat16 activations, float32 router, softmax and loss,
as the flagship LM states its precision.

What this is not: the TwoTower release's second, denoiser tower (adaLN,
bidirectional in-block attention, cross-tower conditioning, block-diffusion
decoding) has no key in `config.json` and is not built; this is the tower
that file defines, trained causally with next-token cross-entropy.

Model contract: training=True returns {"logits", "stats"} (the routed
layers' counts, summed over the `E` layers; the trainer hands them back
beside the loss); training=False returns plain logits.
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.mamba2 import Mamba2Mixer
from elasticdl_tpu.layers.moe import RoutedExperts
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import flash_attention

LAYER_TYPES = {"M": "mamba", "*": "attention", "E": "experts"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    # The public keys, under their public names.
    hybrid_override_pattern: str = "ME*E"
    hidden_size: int = 64
    vocab_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    ssm_state_size: int = 16
    chunk_size: int = 8
    conv_kernel: int = 4
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    time_step_limit: Tuple[float, Optional[float]] = (0.0, None)
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    norm_eps: float = 1e-5
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # Routing by seeded noise, every expert its even share (a benchmark
    # mode: layers/moe.py `force_balance_seed`).
    force_load_balancing: bool = False
    # This chip's share of each E layer: (first expert, how many).
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of one block of the grouped expert product.
    expert_block_rows: int = 1024
    activation_dtype: str = "bfloat16"
    # Rematerialise every block in the backward pass (memory for FLOPs).
    remat: bool = False

    def __post_init__(self):
        unknown = set(self.hybrid_override_pattern) - set(LAYER_TYPES)
        if unknown or not self.hybrid_override_pattern:
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r}: "
                f"letters are {sorted(LAYER_TYPES)}, got {sorted(unknown)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")

    @classmethod
    def from_public(cls, public, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (rope_theta, which the HF attention never applies;
        flags of the HF runtime) are left. `num_hidden_layers` cuts the
        pattern to its first layers."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        if "time_step_limit" in kept:
            kept["time_step_limit"] = tuple(kept["time_step_limit"])
        depth = public.get("num_hidden_layers")
        if depth is not None and "hybrid_override_pattern" in kept:
            kept["hybrid_override_pattern"] = \
                kept["hybrid_override_pattern"][:int(depth)]
        kept.update(overrides)
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)


def rms_norm(x, weight, eps):
    """weight * x / sqrt(mean(x^2) + eps), in float32."""
    v = x.astype(jnp.float32)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v * weight.astype(jnp.float32)


class RMSNorm(nn.Module):
    eps: float
    dtype: str

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, weight, self.eps).astype(jnp.dtype(self.dtype))


class GroupedQueryAttention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        heads, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)

        def proj(n, name):
            return nn.DenseGeneral(
                (n, dh), use_bias=False, dtype=dtype, kernel_init=cfg.init,
                name=name)(x)

        # [B, S, H, Dh] -> [B, H, S, Dh]; each key/value head serves
        # heads / kv query heads: broadcast before the kernel, so the
        # broadcast's gradient sums the group.
        q = jnp.swapaxes(proj(heads, "q_proj"), 1, 2)
        k = jnp.repeat(
            jnp.swapaxes(proj(kv, "k_proj"), 1, 2), heads // kv, axis=1)
        v = jnp.repeat(
            jnp.swapaxes(proj(kv, "v_proj"), 1, 2), heads // kv, axis=1)
        f32 = jnp.float32
        out = flash_attention(
            q.astype(f32), k.astype(f32), v.astype(f32), True).astype(dtype)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dh)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="o_proj")(out)


class Block(nn.Module):
    """One layer: x + mixer(RMSNorm(x)). Returns (x, stats or None)."""

    config: NemotronHConfig
    kind: str
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.norm_eps, cfg.activation_dtype, name="norm")(x)
        stats = None
        if self.kind == "mamba":
            out = Mamba2Mixer(
                d_model=cfg.hidden_size, num_heads=cfg.mamba_num_heads,
                head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
                state_size=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
                chunk_size=cfg.chunk_size, use_conv_bias=cfg.use_conv_bias,
                norm_eps=cfg.layer_norm_epsilon,
                time_step_min=cfg.time_step_min,
                time_step_max=cfg.time_step_max,
                time_step_floor=cfg.time_step_floor,
                time_step_limit=cfg.time_step_limit,
                dtype=cfg.activation_dtype, kernel_init=cfg.init,
                name="mixer")(h)
        elif self.kind == "attention":
            out = GroupedQueryAttention(cfg, name="mixer")(h)
        else:
            out, stats = RoutedExperts(
                num_experts=cfg.n_routed_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                d_hidden=cfg.moe_intermediate_size,
                d_shared=cfg.moe_shared_expert_intermediate_size,
                held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                block_rows=cfg.expert_block_rows,
                force_balance_seed=(
                    self.index if cfg.force_load_balancing else None),
                dtype=cfg.activation_dtype, kernel_init=cfg.init,
                name="mixer")(h)
        return x + out.astype(x.dtype), stats


class NemotronH(nn.Module):
    config: NemotronHConfig = NemotronHConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     embedding_init=cfg.init, name="embeddings")(
                         tokens.astype(jnp.int32))
        block_cls = nn.remat(Block) if cfg.remat else Block
        totals = None
        for i, letter in enumerate(cfg.hybrid_override_pattern):
            x, stats = block_cls(
                cfg, LAYER_TYPES[letter], i, name=f"layers_{i}")(x)
            if stats is not None:
                totals = stats if totals is None else jax.tree_util.tree_map(
                    jnp.add, totals, stats)
        x = RMSNorm(cfg.norm_eps, cfg.activation_dtype, name="norm_f")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32,
            kernel_init=cfg.init, name="lm_head")(x)
        if not training:
            return logits
        out = {"logits": logits}
        if totals is not None:
            out["stats"] = totals
        return out


# ---------- model spec contract ----------


def custom_model(config: NemotronHConfig = None):
    return NemotronH(config or NemotronHConfig())


def loss(labels, outputs):
    """Next-token cross-entropy (no auxiliary loss: the published routing
    balances by its correction bias, not by a loss term)."""
    return tlm.loss(labels, outputs["logits"])


def optimizer():
    return optimizers.adam(learning_rate=3e-4)


feed = tlm.feed
eval_metrics_fn = tlm.eval_metrics_fn


def param_specs(variables):
    """Everything replicated: data parallel over whole copies of this
    chip's share. (Held experts over a mesh axis need the layer's
    all-to-all, which is not built.)"""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), variables)
