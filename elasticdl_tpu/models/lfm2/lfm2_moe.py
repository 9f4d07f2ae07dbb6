"""The LFM2 expert decoder (HF `model_type: lfm2_moe`): an operator and a
feed-forward a layer, each behind its own pre-norm residual.

    h = h + operator(RMSNorm(h))       by `layer_types[i]`
      conv            gated short convolution   layers/short_conv.py
      full_attention  causal GQA, RMSNorm over each head of q and of k,
                      then rotary over the whole head (`rotate_half`);
                      ops/flash_attention.py
    h = h + ffn(RMSNorm(h))
      i < num_dense_layers    w2(silu(w1 x) * w3 x), width intermediate_size
      after them              top-k of num_experts by sigmoid scores, gated
                              experts of width moe_intermediate_size, no
                              shared expert; layers/moe.py RoutedExperts
    final RMSNorm (`embedding_norm`), logits through the embedding table
    (tied), no bias anywhere.

`Lfm2MoeConfig` takes the keys of the public `config.json` under their own
names (`from_public`), plus `experts_held = (first, count)`: the share of
each routed layer's experts that lives on this chip (None = all of them).
Float32 parameters, bfloat16 activations, float32 router, norms, rotary
angles, softmax and loss, as the other configurations state theirs.

Model contract: training=True returns {"logits", "stats"} (the routed
layers' counts, summed over them; the trainer hands them back beside the
loss); training=False returns plain logits.
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.moe import RoutedExperts
from elasticdl_tpu.layers.short_conv import GatedShortConv
from elasticdl_tpu.models.nemotron_h.nemotron_h import RMSNorm, rms_norm
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import flash_attention

OPERATORS = ("conv", "full_attention")
ROTARY_SCOPE = "rotary"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    # The public keys, under their public names.
    layer_types: Tuple[str, ...] = ("conv", "full_attention", "conv")
    hidden_size: int = 64
    vocab_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    num_dense_layers: int = 1
    num_experts: int = 8
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e6  # config.json: rope_parameters.rope_theta
    initializer_range: float = 0.02
    # Routing by seeded noise, every expert its even share (a benchmark
    # mode: layers/moe.py `force_balance_seed`).
    force_load_balancing: bool = False
    # This chip's share of each routed layer: (first expert, how many).
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of one block of the grouped expert product.
    expert_block_rows: int = 1024
    activation_dtype: str = "bfloat16"
    # Rematerialise every layer in the backward pass (memory for FLOPs).
    remat: bool = False

    def __post_init__(self):
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types!r}: operators are "
                f"{OPERATORS}, got {sorted(unknown)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.hidden_size % (2 * self.num_attention_heads):
            raise ValueError(
                f"hidden_size {self.hidden_size} gives "
                f"{self.num_attention_heads} heads no even width to rotate")

    @classmethod
    def from_public(cls, public, keep_layers=None, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (flags of the HF runtime) are left. `keep_layers`
        are the published layers that are kept, in order: their operators
        stay theirs, and as many of them are dense as lie before the
        published `num_dense_layers`."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        rope = public.get("rope_parameters") or {}
        if "rope_theta" in rope:
            kept["rope_theta"] = float(rope["rope_theta"])
        if keep_layers is not None:
            kept["layer_types"] = [
                public["layer_types"][i] for i in keep_layers]
            kept["num_dense_layers"] = sum(
                i < int(public["num_dense_layers"]) for i in keep_layers)
        kept.update(overrides)
        kept["layer_types"] = tuple(kept["layer_types"])
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)


def rotary(x, theta, positions=None, inv_freq=None, scale=None,
           interleave=False):
    """x [B, S, H, Dh] -> x turned by its position, over the whole head:
    x * cos + rotate_half(x) * sin with angles position * theta^(-2i / Dh)
    for i < Dh / 2, repeated over the two halves (the HF `default` rope).
    A caller that turns part of a head hands in that part. `interleave`:
    the channels arrive paired (2i, 2i + 1) (HF `rope_interleave`) and are
    brought to the two halves first, evens then odds, where they stay: q
    and k are permuted alike, so their products are the published ones.
    `positions` [S] are the rows' positions where they are not 0 .. S - 1
    (a sequence that holds two copies of a record). A long-context
    frequency scaling hands in its own table `inv_freq` [Dh / 2] in place
    of theta's, and the `scale` its cos and sin are multiplied by (YaRN's
    `attention_factor`). In float32."""
    s, dh = x.shape[1], x.shape[-1]
    f32 = jnp.float32
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=f32) / dh)
    if positions is None:
        positions = jnp.arange(s, dtype=f32)
    angles = positions.astype(f32)[:, None] * inv_freq[None]  # [S, Dh/2]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x = x.astype(f32)
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)

    def scaled(table):
        return table if scale is None else table * scale

    return x * scaled(jnp.cos(angles)) + jnp.concatenate(
        [-x2, x1], axis=-1) * scaled(jnp.sin(angles))


class Attention(nn.Module):
    config: Lfm2MoeConfig

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

        def head_norm(v, name):
            weight = self.param(name, nn.initializers.ones, (dh,))
            return rms_norm(v, weight, cfg.norm_eps)

        q, k = proj(heads, "q_proj"), proj(kv, "k_proj")
        with jax.named_scope(ROTARY_SCOPE):
            q = rotary(head_norm(q, "q_layernorm"), cfg.rope_theta)
            k = rotary(head_norm(k, "k_layernorm"), cfg.rope_theta)
        # [B, S, H, Dh] -> [B, H, S, Dh]; each key/value head serves
        # heads / kv query heads: broadcast before the kernel, so the
        # broadcast's gradient sums the group. The activation dtype
        # crosses the kernels' boundary, as at the flagship's call site.
        q = jnp.swapaxes(q.astype(dtype), 1, 2)
        k = jnp.repeat(
            jnp.swapaxes(k.astype(dtype), 1, 2), heads // kv, axis=1)
        v = jnp.repeat(
            jnp.swapaxes(proj(kv, "v_proj"), 1, 2), heads // kv, axis=1)
        out = flash_attention(q, k, v, True)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dh)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="out_proj")(out)


class GatedMLP(nn.Module):
    """w2(silu(w1 x) * w3 x), HF's names."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(width, name):
            return nn.Dense(
                width, use_bias=False, dtype=jnp.dtype(cfg.activation_dtype),
                kernel_init=cfg.init, name=name)

        h = jax.nn.silu(dense(cfg.intermediate_size, "w1")(x)) * dense(
            cfg.intermediate_size, "w3")(x)
        return dense(cfg.hidden_size, "w2")(h)


class Block(nn.Module):
    """One layer. Returns (h, the routed layer's stats or None)."""

    config: Lfm2MoeConfig
    index: int

    @nn.compact
    def __call__(self, h):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.activation_dtype, name=name)

        u = norm("operator_norm")(h)
        if cfg.layer_types[self.index] == "conv":
            out = GatedShortConv(
                d_model=cfg.hidden_size, conv_kernel=cfg.conv_L_cache,
                use_conv_bias=cfg.conv_bias, dtype=cfg.activation_dtype,
                kernel_init=cfg.init, name="conv")(u)
        else:
            out = Attention(cfg, name="self_attn")(u)
        h = h + out.astype(h.dtype)
        u = norm("ffn_norm")(h)
        stats = None
        if self.index < cfg.num_dense_layers:
            out = GatedMLP(cfg, name="feed_forward")(u)
        else:
            # config.json's `use_expert_bias` is the layer's bias buffer,
            # held at zero.
            out, stats = RoutedExperts(
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                d_hidden=cfg.moe_intermediate_size, gated=True,
                held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                topk_eps=1e-6, block_rows=cfg.expert_block_rows,
                force_balance_seed=(
                    self.index if cfg.force_load_balancing else None),
                dtype=cfg.activation_dtype, kernel_init=cfg.init,
                name="feed_forward")(u)
        return h + out.astype(h.dtype), stats


class Lfm2Moe(nn.Module):
    config: Lfm2MoeConfig = Lfm2MoeConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                         embedding_init=cfg.init, name="embed_tokens")
        h = embed(tokens.astype(jnp.int32))
        block_cls = nn.remat(Block) if cfg.remat else Block
        totals = None
        for i in range(len(cfg.layer_types)):
            h, stats = block_cls(cfg, i, name=f"layers_{i}")(h)
            if stats is not None:
                totals = stats if totals is None else jax.tree_util.tree_map(
                    jnp.add, totals, stats)
        h = RMSNorm(cfg.norm_eps, cfg.activation_dtype,
                    name="embedding_norm")(h)
        # Tied head: the activation dtype's product, float32 logits.
        logits = jnp.einsum(
            "bsd,vd->bsv", h, embed.embedding.astype(dtype),
            preferred_element_type=jnp.float32)
        if not training:
            return logits
        out = {"logits": logits}
        if totals is not None:
            out["stats"] = totals
        return out


# ---------- model spec contract ----------


def custom_model(config: Lfm2MoeConfig = None):
    return Lfm2Moe(config or Lfm2MoeConfig())


def loss(labels, outputs):
    """Next-token cross-entropy, no auxiliary loss (the published routing
    balances by its expert bias, not by a loss term)."""
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
