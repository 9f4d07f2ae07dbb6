"""The Granite 4.0-H dense hybrid decoder (HF `model_type:
granitemoehybrid` with `num_local_experts` 0): a mixer AND a gated MLP a
layer, each behind its own pre-norm residual, under the four Granite
multipliers.

    h = embedding[ids] * embedding_multiplier           (tied with the head)
    h = h + residual_multiplier * mixer(RMSNorm(h))     by `layer_types[i]`
      mamba      Mamba-2 mixer, one group: the gated RMSNorm runs over the
                 whole inner width; layers/mamba2.py, its scan handed in
                 as the Pallas kernels of ops/ssd_scan.py and its
                 convolution stage as those of ops/causal_conv.py
      attention  causal GQA, no bias, NO position signal (`nope`: the
                 mixers carry order), scores times `attention_multiplier`
                 (not head_dim^-0.5); ops/flash_attention.py
    h = h + residual_multiplier * output_linear(silu(g) * u),
        g, u = split(input_linear(RMSNorm(h)))          width
                                                        shared_intermediate_size
    logits = (RMSNorm(h) @ embedding^T) / logits_scaling

The family's routed siblings (`num_local_experts` > 0: a routed branch
beside this shared MLP) are not built: `GraniteHybridConfig` refuses them.

`GraniteHybridConfig` takes the keys of the public `config.json` under
their own names (`from_public`). Float32 parameters, bfloat16 activations,
float32 decays, norms, softmax and loss, as the other configurations state
theirs.

The flash kernels scale scores by head_dim^-0.5 and take no other scale,
so the call site hands them q * (attention_multiplier * head_dim^0.5):
at the published 1/64 and head 64 that is q / 8, exact in bfloat16.

Model contract: training=True returns {"logits", "stats"} (the tokens the
step scanned and, where `ops/ssd_scan.py` runs its kernels, those of them
whose scan ran as the kernels, from the shapes; the trainer hands them
back beside the loss); training=False returns plain logits.
"""

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.mamba2 import Mamba2Mixer
# The spec's other half is the hybrid block's own: next-token
# cross-entropy of `outputs["logits"]`, Adam 3e-4, token rows as features
# and labels, everything replicated.
from elasticdl_tpu.models.nemotron_h.nemotron_h import (  # noqa: F401
    RMSNorm,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)
from elasticdl_tpu.ops.causal_conv import causal_conv_silu
from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.ops.ssd_scan import ssd_scan

MIXERS = ("mamba", "attention")
MIXER_SCOPE = "granite_mixer"
ATTENTION_SCOPE = "granite_attention"
MLP_SCOPE = "granite_mlp"
# What the backward pass recomputes: nothing; every layer but for the
# results of its matrix products.
REMAT = ("none", "dots")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    # The public keys, under their public names.
    layer_types: Tuple[str, ...] = ("mamba", "attention", "mamba")
    hidden_size: int = 64
    vocab_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    shared_intermediate_size: int = 128
    mamba_n_heads: int = 8
    mamba_d_head: int = 16
    mamba_n_groups: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 8
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.0625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    activation_dtype: str = "bfloat16"
    # One of REMAT.
    remat: str = "none"

    def __post_init__(self):
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types!r}: mixers are {MIXERS}, "
                f"got {sorted(unknown)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} does not split over "
                f"{self.num_attention_heads} heads")
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r}: one of {REMAT}")
        not_built = {
            "num_local_experts": (self.num_local_experts, 0),
            "position_embedding_type": (self.position_embedding_type,
                                        "nope"),
            "tie_word_embeddings": (self.tie_word_embeddings, True),
            "mamba_proj_bias": (self.mamba_proj_bias, False),
            "attention_bias": (self.attention_bias, False),
        }
        for key, (got, built) in not_built.items():
            if got != built:
                raise ValueError(
                    f"{key} {got!r} is not built: this block is the dense "
                    f"hybrid with {key} {built!r}")

    @classmethod
    def from_public(cls, public, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (rope_theta, which a `nope` model never applies;
        flags of the HF runtime) are left. `num_hidden_layers` cuts
        `layer_types` to its first layers."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        depth = public.get("num_hidden_layers")
        if depth is not None and "layer_types" in kept:
            kept["layer_types"] = kept["layer_types"][:int(depth)]
        kept.update(overrides)
        kept["layer_types"] = tuple(kept["layer_types"])
        inner = kept.get("mamba_n_heads", 0) * kept.get("mamba_d_head", 0)
        expand = public.get("mamba_expand")
        if expand is not None and inner and "hidden_size" in kept and (
                inner != expand * kept["hidden_size"]):
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {inner} is not "
                f"mamba_expand {expand} x hidden_size {kept['hidden_size']}")
        return cls(**kept)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def scanning_layers(self):
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)


class Attention(nn.Module):
    config: GraniteHybridConfig

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

        # The kernels' own scale is head_dim^-0.5: the rest of
        # attention_multiplier goes on q (1/8 as published, a power of
        # two: no rounding in either dtype).
        q = proj(heads, "q_proj") * jnp.asarray(
            cfg.attention_multiplier * dh ** 0.5, dtype)
        # [B, S, H, Dh] -> [B, H, S, Dh]; each key/value head serves
        # heads / kv query heads: broadcast before the kernel, so the
        # broadcast's gradient sums the group. The activation dtype
        # crosses the kernels' boundary, as at the LFM2 call site.
        q = jnp.swapaxes(q, 1, 2)
        k = jnp.repeat(
            jnp.swapaxes(proj(kv, "k_proj"), 1, 2), heads // kv, axis=1)
        v = jnp.repeat(
            jnp.swapaxes(proj(kv, "v_proj"), 1, 2), heads // kv, axis=1)
        out = flash_attention(q, k, v, True)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dh)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="o_proj")(out)


class SharedMLP(nn.Module):
    """output_linear(silu(g) * u), g, u = split(input_linear(x)): HF's
    names, the two halves side by side in one matrix."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        both = nn.Dense(
            2 * cfg.shared_intermediate_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="input_linear")(x)
        gate, up = jnp.split(both, 2, axis=-1)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="output_linear")(
                jax.nn.silu(gate) * up)


def _residual(h, branch, multiplier):
    """h + multiplier * branch, summed in float32, rounded once."""
    f32 = jnp.float32
    return (h.astype(f32) + branch.astype(f32) * multiplier).astype(h.dtype)


class Block(nn.Module):
    """One layer: the mixer `layer_types[index]` names, then the MLP."""

    config: GraniteHybridConfig
    index: int

    @nn.compact
    def __call__(self, h):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name=name)

        u = norm("input_layernorm")(h)
        if cfg.layer_types[self.index] == "mamba":
            with jax.named_scope(MIXER_SCOPE):
                out = Mamba2Mixer(
                    d_model=cfg.hidden_size, num_heads=cfg.mamba_n_heads,
                    head_dim=cfg.mamba_d_head, n_groups=cfg.mamba_n_groups,
                    state_size=cfg.mamba_d_state,
                    conv_kernel=cfg.mamba_d_conv,
                    chunk_size=cfg.mamba_chunk_size,
                    use_conv_bias=cfg.mamba_conv_bias,
                    norm_eps=cfg.rms_norm_eps, dtype=cfg.activation_dtype,
                    kernel_init=cfg.init, scan=ssd_scan,
                    conv=causal_conv_silu, name="mamba")(u)
        else:
            with jax.named_scope(ATTENTION_SCOPE):
                out = Attention(cfg, name="self_attn")(u)
        h = _residual(h, out, cfg.residual_multiplier)
        with jax.named_scope(MLP_SCOPE):
            out = SharedMLP(cfg, name="shared_mlp")(
                norm("post_attention_layernorm")(h))
        return _residual(h, out, cfg.residual_multiplier)


def _block_class(remat):
    if remat == "dots":
        return nn.remat(
            Block,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return Block


class GraniteHybrid(nn.Module):
    config: GraniteHybridConfig = GraniteHybridConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        dtype, f32 = jnp.dtype(cfg.activation_dtype), jnp.float32
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                         embedding_init=cfg.init, name="embed_tokens")
        h = (embed(tokens.astype(jnp.int32)).astype(f32)
             * cfg.embedding_multiplier).astype(dtype)
        block_cls = _block_class(cfg.remat)
        for i in range(len(cfg.layer_types)):
            h = block_cls(cfg, i, name=f"layers_{i}")(h)
        h = RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name="norm")(h)
        # Tied head: the activation dtype's product, float32 logits.
        logits = jnp.einsum(
            "bsd,vd->bsv", h, embed.embedding.astype(dtype),
            preferred_element_type=f32) / cfg.logits_scaling
        if not training:
            return logits
        scanned = jnp.asarray(tokens.size * cfg.scanning_layers, f32)
        return {"logits": logits, "stats": {"ssd_scan_tokens": scanned}}


# ---------- model spec contract ----------


def custom_model(config: GraniteHybridConfig = None):
    return GraniteHybrid(config or GraniteHybridConfig())
