"""The Kanana 2 expert decoder (HF `model_type: deepseek_v3` without a
query low-rank projection): multi-head latent attention over sigmoid-routed
gated experts with shared experts, behind leading dense layers.

Layer l, each half behind its own pre-norm residual (RMSNorm, `rms_norm_eps`):

    h = h + Wo A(q, k, v),  u = RMSNorm(h)
        q = Wq u                 [S, heads, nope + rope], q_nope | q_rope
        Wkva u                   [S, kv_lora_rank + rope], c | k_rope: ONE
                                 rope key, shared by all the heads
        Wkvb RMSNorm(c)          [S, heads, nope + v], k_nope | v
        q_rope, k_rope turned by position over their `qk_rope_head_dim`
        channels alone (`rope_theta`, the published pairing (2i, 2i + 1):
        `rope_interleave`; lfm2_moe.rotary), q_nope and k_nope not at all
        k = [k_nope | k_rope for every head], q = [q_nope | q_rope]
        (the turn, the joins, the split of k_nope and v and the change to
        the kernels' layout in one op: ops/mla_rotary.py)
        A causal softmax attention at scale (nope + rope)^-0.5 inside the
        flash kernels, keys of nope + rope against values of `v_head_dim`
        (ops/flash_attention.py: `mla_flash_fwd`, `mla_flash_bwd`)
    h = h + ffn(RMSNorm(h))
      l < first_k_dense_replace   down(silu(gate x) * up x), width
                                  intermediate_size
      after them                  top `num_experts_per_tok` of
                                  `n_routed_experts` by sigmoid scores plus
                                  the correction bias (a buffer held at
                                  zero), weights the chosen scores over
                                  their sum times `routed_scaling_factor`,
                                  gated experts of `moe_intermediate_size`,
                                  and for every token one gated MLP of
                                  n_shared_experts x moe_intermediate_size
                                  (the shared experts, as HF builds them);
                                  no auxiliary loss; layers/moe.py
    final RMSNorm, logits through an untied head, next-token cross-entropy.
    Each layer and the head hand their cotangents out together in the
    backward pass (`_cotangents_together`): the same values, a schedule that
    makes every weight gradient beside the cotangent it reads.

`KananaMoeConfig` takes the keys of the public `config.json` under their own
names (`from_public`), plus `experts_held = (first, count)`: the share of
each routed layer's experts that lives on this chip (None = all of them).
Float32 parameters, bfloat16 activations, float32 router, norms, rotary
angles, softmax and loss, as the other configurations state theirs.

Model contract: training=True returns {"logits", "stats"} (the routed
layers' counts summed over them); training=False returns plain logits. Not
built: a query low-rank projection (`q_lora_rank`), group-limited routing at
`n_group` > 1, a rope scaling (YaRN's mscale on the scores' scale), the
latent attention under ring / Ulysses attention and under tensor-parallel
specs, a latent key/value cache (the repository serves nothing).
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.moe import RoutedExperts
from elasticdl_tpu.models.nemotron_h.nemotron_h import RMSNorm
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import KEPT, flash_attention
from elasticdl_tpu.ops.mla_rotary import mla_rotary, rope_tables

Q_SCOPE = "kanana_q_proj"
KV_DOWN_SCOPE = "kanana_kv_down"
KV_UP_SCOPE = "kanana_kv_up"
ROPE_SCOPE = "kanana_rope"
ATTENTION_SCOPE = "kanana_latent_attention"
O_SCOPE = "kanana_o_proj"
MOE_SCOPE = "kanana_moe"


@dataclasses.dataclass(frozen=True)
class KananaMoeConfig:
    # The public keys, under their public names.
    num_hidden_layers: int = 3
    hidden_size: int = 64
    vocab_size: int = 256
    num_attention_heads: int = 4
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    kv_lora_rank: int = 32
    q_lora_rank: Optional[int] = None
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    first_k_dense_replace: int = 1
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    n_shared_experts: int = 2
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # Routing by seeded noise, every expert its even share (a benchmark
    # mode: layers/moe.py `force_balance_seed`).
    force_load_balancing: bool = False
    # This chip's share of each routed layer: (first expert, how many).
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of one block of the grouped expert product.
    expert_block_rows: int = 1024
    activation_dtype: str = "bfloat16"
    # The layers rematerialised in the backward pass (memory for FLOPs);
    # each keeps its attention's output and lse (`KananaMoe.__call__`).
    remat_layers: Tuple[int, ...] = ()

    def __post_init__(self):
        for key, built in (("q_lora_rank", None), ("rope_scaling", None),
                           ("rope_interleave", True),
                           ("n_group", 1), ("topk_group", 1),
                           ("scoring_func", "sigmoid")):
            if getattr(self, key) != built:
                raise ValueError(
                    f"{key} {getattr(self, key)!r} is not built: this "
                    f"model takes {built!r}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} gives the rope "
                "no pairs to turn")

    @classmethod
    def from_public(cls, public, keep_layers=None, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (flags of the HF runtime) are left. `keep_layers`
        are the published layers that are kept, in order: as many of them
        are dense as lie before the published `first_k_dense_replace`."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        if int(public.get("moe_layer_freq", 1)) != 1:
            raise ValueError("every layer after the dense ones is routed")
        if keep_layers is not None:
            kept["num_hidden_layers"] = len(keep_layers)
            kept["first_k_dense_replace"] = sum(
                i < int(public["first_k_dense_replace"])
                for i in keep_layers)
        kept.update(overrides)
        kept["remat_layers"] = tuple(kept.get("remat_layers", ()))
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)

    def rope_tables(self, s):
        """(cos, sin) of rows 0 .. s - 1 for `mla_rotary`: once a step."""
        return rope_tables(s, self.rope_theta, self.qk_rope_head_dim)


class LatentAttention(nn.Module):
    config: KananaMoeConfig

    @nn.compact
    def __call__(self, x, tables):
        """`tables`: the step's (cos, sin) of `KananaMoeConfig.rope_tables`."""
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        heads, nope, rope, dv = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim)

        def heads_of(width, name, u):
            return nn.DenseGeneral(
                (heads, width), use_bias=False, dtype=dtype,
                kernel_init=cfg.init, name=name)(u)

        with jax.named_scope(Q_SCOPE):
            q_proj = heads_of(nope + rope, "q_proj", x)
        with jax.named_scope(KV_DOWN_SCOPE):
            latent, k_rope = jnp.split(nn.Dense(
                cfg.kv_lora_rank + rope, use_bias=False, dtype=dtype,
                kernel_init=cfg.init, name="kv_a_proj_with_mqa")(x),
                [cfg.kv_lora_rank], axis=-1)
            latent = RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype,
                             name="kv_a_layernorm")(latent)
        with jax.named_scope(KV_UP_SCOPE):
            kv_up = heads_of(nope + dv, "kv_b_proj", latent)
        # [B, S, H, D] -> [B, H, S, D] in the activation dtype, which
        # crosses the flash kernels' boundary: q = [q_nope | q_rope turned],
        # k = [k_nope | the one rope key turned, for every head] (its
        # gradient sums the heads), v.
        with jax.named_scope(ROPE_SCOPE):
            q, k, v = mla_rotary(q_proj, kv_up, k_rope, *tables)
        with jax.named_scope(ATTENTION_SCOPE):
            out = flash_attention(q, k, v, True)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dv)
        with jax.named_scope(O_SCOPE):
            return nn.Dense(
                cfg.hidden_size, use_bias=False, dtype=dtype,
                kernel_init=cfg.init, name="o_proj")(out)


class GatedMLP(nn.Module):
    """down_proj(silu(gate_proj x) * up_proj x), HF's names."""

    config: KananaMoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def dense(width, name):
            return nn.Dense(
                width, use_bias=False, dtype=jnp.dtype(cfg.activation_dtype),
                kernel_init=cfg.init, name=name)

        h = jax.nn.silu(dense(cfg.intermediate_size, "gate_proj")(x)) * dense(
            cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(h)


class Block(nn.Module):
    """One layer. Returns (h, the routed layer's stats or None)."""

    config: KananaMoeConfig
    index: int

    @nn.compact
    def __call__(self, h, tables):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name=name)

        h = h + LatentAttention(cfg, name="self_attn")(
            norm("input_layernorm")(h), tables).astype(h.dtype)
        u = norm("post_attention_layernorm")(h)
        if self.index < cfg.first_k_dense_replace:
            return h + GatedMLP(cfg, name="mlp")(u).astype(h.dtype), None
        with jax.named_scope(MOE_SCOPE):
            # `topk_method: noaux_tc`: the layer's bias buffer, held at
            # zero; the sum under the weights takes HF's 1e-20.
            out, stats = RoutedExperts(
                num_experts=cfg.n_routed_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                d_hidden=cfg.moe_intermediate_size, gated=True,
                d_shared=cfg.n_shared_experts * cfg.moe_intermediate_size,
                score="sigmoid", held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                block_rows=cfg.expert_block_rows,
                force_balance_seed=(
                    self.index if cfg.force_load_balancing else None),
                dtype=cfg.activation_dtype, kernel_init=cfg.init,
                name="mlp")(u)
        return h + out.astype(h.dtype), stats


def _call(module, x, *constants):
    return module(x, *constants)


def _made_together(vjp_fn, cotangents):
    parameters, x, *constants = vjp_fn(cotangents)
    return (*jax.lax.optimization_barrier((parameters, x)), *constants)


# `module(x, *constants)`, and in the backward pass neither the parameters'
# cotangents nor x's are used before all of them are made. Left to itself
# the v5e scheduler puts weight-gradient products layers later than the
# cotangents they read (the head's three layers on, with the logits'
# cotangent `bf16[S, vocab]` alive until then, 501 MiB at the cut's shapes;
# the last layer's at the step's end): 0.76 GiB of the step's resident
# bytes (PERF.md section 6, PR 58).
_cotangents_together = nn.custom_vjp(
    _call, backward_fn=_made_together,
    forward_fn=lambda module, *inputs: nn.vjp(_call, module, *inputs))


class KananaMoe(nn.Module):
    config: KananaMoeConfig = KananaMoeConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     embedding_init=cfg.init, name="embed_tokens")(
                         tokens.astype(jnp.int32))
        totals = None
        # One pair of cos and sin tables for every layer.
        tables = cfg.rope_tables(tokens.shape[1])
        # A rematerialised layer keeps what its flash kernel made (128 MiB
        # of output and 2 MiB of lse at the cut's shapes): q, k and v are
        # projections away, the kernel is a tenth of the step.
        remat_block = nn.remat(
            Block, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
        for i in range(cfg.num_hidden_layers):
            block_cls = remat_block if i in cfg.remat_layers else Block
            h, stats = _cotangents_together(
                block_cls(cfg, i, name=f"layers_{i}"), h, tables)
            if stats is not None:
                totals = stats if totals is None else jax.tree_util.tree_map(
                    jnp.add, totals, stats)
        h = RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name="norm")(h)
        logits = _cotangents_together(nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="lm_head"), h).astype(jnp.float32)
        if not training:
            return logits
        out = {"logits": logits}
        if totals is not None:
            out["stats"] = totals
        return out


# ---------- model spec contract ----------


def custom_model(config: KananaMoeConfig = None):
    return KananaMoe(config or KananaMoeConfig())


def loss(labels, outputs):
    """Next-token cross-entropy, no auxiliary loss (`noaux_tc`: the
    published routing balances by its expert bias, not by a loss term)."""
    return tlm.loss(labels, outputs["logits"])


def optimizer():
    """Adam at a constant 3e-5, where the other models take 3e-4: this
    family trains behind a long warm-up (its `config.json` has no
    schedule), and at 3e-4 from the first step the cut's loss fell 2.3 in
    16 steps and ran apart from its own float32 reference's by up to 0.57
    (one seed of six, on the chip): no run to hold anything to."""
    return optimizers.adam(learning_rate=3e-5)


feed = tlm.feed
eval_metrics_fn = tlm.eval_metrics_fn


def param_specs(variables):
    """Everything replicated: data parallel over whole copies of this
    chip's share. (Held experts over a mesh axis need the layer's
    all-to-all, which is not built.)"""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), variables)
