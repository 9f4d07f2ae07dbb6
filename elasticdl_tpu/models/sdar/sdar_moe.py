"""The SDAR expert decoder (HF `model_type: sdar_moe`) under its training
objective, masked block diffusion (Arriola et al. 2025, BD3-LM; the SDAR
report, arXiv:2510.06303).

Every layer, each behind its own pre-norm residual:

    h = h + Wo A(rope(qn(Wq u)), rope(kn(Wk u)), Wv u),  u = RMSNorm(h)
        heads of `head_dim` (a key of its own, not hidden / heads), no
        bias; qn, kn an RMSNorm over each head's channels; rope the HF
        default over the whole head, by the rows' positions; A softmax
        attention at scale head_dim^-0.5 under the block-diffusion mask,
        inside the flash kernels (ops/flash_attention.py `BlockDiffusion`)
    h = h + sum over the top k of w_e W2_e(silu(W1_e u') * W3_e u')
        p = softmax(Wr u') over all experts in float32, the k largest,
        w = p / sum(p chosen) if `norm_topk_prob`; no shared expert, no
        correction bias, no auxiliary loss; layers/moe.py RoutedExperts
    final RMSNorm, logits through an untied head.

The objective reads a record's clean copy x_0 and its noised copy x_t
(x_t[p] = MASK where the record's draw u[p] < t[beta(p)], beta(p) = p //
block_length) as ONE sequence of 2L rows, both halves at positions 0 ..
L - 1, through every layer, routed ones too; the head runs over the noised
half alone, and the loss is the cross-entropy of the masked positions
themselves (no next-token shift) weighted 1 / t[beta(p)], over B L.

`SdarMoeConfig` takes the keys of the public `config.json` under their own
names (`from_public`), plus `experts_held = (first, count)`: the share of
each layer's experts that lives on this chip (None = all of them). Float32
parameters, bfloat16 activations, float32 router, norms, rotary angles,
softmax and loss, as the other configurations state theirs.

Model contract: features {"tokens", "noised"} int32 [B, L]; training=True
returns {"logits" [B, L, V] of the noised half, "stats"}; training=False
returns the logits alone. Labels {"targets" int32 [B, L], "weights"
float32 [B, L]} (`make_feed`). The sampler (blocks denoised against a cache
of the finished ones) belongs to a serving path this system does not have.
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.common.evaluation_utils import MeanMetric
from elasticdl_tpu.common.model_utils import Modes
from elasticdl_tpu.data.example import batch_examples
from elasticdl_tpu.layers.moe import RoutedExperts
from elasticdl_tpu.models.nemotron_h.nemotron_h import RMSNorm
from elasticdl_tpu.models.transformer.transformer_lm import token_ce
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import (
    BlockDiffusion,
    block_diffusion_scores,
    flash_attention,
)
from elasticdl_tpu.ops.qk_rotary import qk_rotary, rope_tables

ATTENTION_SCOPE = "bd_attention"


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    # The public keys, under their public names.
    num_hidden_layers: int = 2
    hidden_size: int = 64
    vocab_size: int = 256
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    moe_intermediate_size: int = 32
    num_experts: int = 8
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # The objective's own sizes (`config.json` has no key for them).
    block_length: int = 4
    mask_token_id: int = 255
    # Routing by seeded noise, every expert its even share (a benchmark
    # mode: layers/moe.py `force_balance_seed`).
    force_load_balancing: bool = False
    # This chip's share of each layer's experts: (first expert, how many).
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of one block of the grouped expert product.
    expert_block_rows: int = 1024
    activation_dtype: str = "bfloat16"
    # Rematerialise every layer in the backward pass (memory for FLOPs).
    remat: bool = False

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.head_dim % 2:
            raise ValueError(
                f"head_dim {self.head_dim} gives a head no even width to "
                "rotate")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id {self.mask_token_id} is no row of a "
                f"vocabulary of {self.vocab_size}")

    @classmethod
    def from_public(cls, public, keep_layers=None, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (flags of the HF runtime) are left. `keep_layers`
        are the published layers that are kept (all alike here: their
        number is what counts)."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        if public.get("mlp_only_layers") or int(
                public.get("decoder_sparse_step", 1)) != 1:
            raise ValueError("every layer of this model is routed")
        if keep_layers is not None:
            kept["num_hidden_layers"] = len(keep_layers)
        kept.update(overrides)
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)


class Attention(nn.Module):
    config: SdarMoeConfig

    @nn.compact
    def __call__(self, x, rope, mask):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        heads, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)

        def proj(n, name):
            return nn.DenseGeneral(
                (n, dh), use_bias=False, dtype=dtype, kernel_init=cfg.init,
                name=name)(x)

        def turned(n, name, norm):
            """[B, S, n, Dh] -> [B, n, S, Dh] in the activation dtype,
            which crosses the flash kernels' boundary: the head's norm,
            the turn, the rounding and the layout in one op."""
            weight = self.param(norm, nn.initializers.ones, (dh,))
            return qk_rotary(
                proj(n, name), weight, cfg.rms_norm_eps, *rope)

        # Each key/value head serves heads / kv query heads: broadcast
        # before the kernel, so the broadcast's gradient sums the group.
        q = turned(heads, "q_proj", "q_norm")
        k = jnp.repeat(turned(kv, "k_proj", "k_norm"), heads // kv, axis=1)
        v = jnp.repeat(
            jnp.swapaxes(proj(kv, "v_proj"), 1, 2), heads // kv, axis=1)
        with jax.named_scope(ATTENTION_SCOPE):
            out = flash_attention(q, k, v, mask)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dh)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="o_proj")(out)


class Block(nn.Module):
    """One layer. Returns (h, the routed layer's stats)."""

    config: SdarMoeConfig
    index: int

    @nn.compact
    def __call__(self, h, rope, mask):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name=name)

        h = h + Attention(cfg, name="self_attn")(
            norm("input_layernorm")(h), rope, mask).astype(h.dtype)
        out, stats = RoutedExperts(
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_tok,
            d_hidden=cfg.moe_intermediate_size, gated=True, score="softmax",
            held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            topk_eps=0.0, block_rows=cfg.expert_block_rows,
            force_balance_seed=(
                self.index if cfg.force_load_balancing else None),
            dtype=cfg.activation_dtype, kernel_init=cfg.init,
            name="mlp")(norm("post_attention_layernorm")(h))
        return h + out.astype(h.dtype), stats


class SdarMoe(nn.Module):
    config: SdarMoeConfig = SdarMoeConfig()

    @nn.compact
    def __call__(self, features, training: bool = False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        clean = features["tokens"].astype(jnp.int32)
        noised = features["noised"].astype(jnp.int32)
        batch, half = clean.shape
        mask = BlockDiffusion(cfg.block_length, half)
        # One sequence of 2L rows, both halves at positions 0 .. L - 1:
        # one pair of cos and sin tables for every layer.
        rows = jnp.concatenate([clean, noised], axis=1)
        rope = rope_tables(
            jnp.tile(jnp.arange(half), 2),
            cfg.rope_theta ** (-jnp.arange(
                0, cfg.head_dim, 2, dtype=jnp.float32) / cfg.head_dim))
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     embedding_init=cfg.init, name="embed_tokens")(rows)
        block_cls = nn.remat(Block, static_argnums=(3,)) if cfg.remat else (
            Block)
        totals = None
        for i in range(cfg.num_hidden_layers):
            h, stats = block_cls(cfg, i, name=f"layers_{i}")(h, rope, mask)
            totals = stats if totals is None else jax.tree_util.tree_map(
                jnp.add, totals, stats)
        # The head over the noised half alone: the clean half predicts
        # nothing.
        h = RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name="norm")(
            h[:, half:])
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="lm_head")(h).astype(jnp.float32)
        if not training:
            return logits
        f32 = jnp.float32
        needed, run = block_diffusion_scores(half, cfg.block_length)
        calls = batch * cfg.num_attention_heads * cfg.num_hidden_layers
        totals = dict(
            totals,
            bd_positions=jnp.asarray(batch * half, f32),
            bd_positions_masked=jnp.sum(
                noised == cfg.mask_token_id, dtype=f32),
            attn_scores_needed=jnp.asarray(calls * needed, f32),
            attn_scores_run=jnp.asarray(calls * run, f32))
        return {"logits": logits, "stats": totals}


# ---------- model spec contract ----------


def custom_model(config: SdarMoeConfig = None):
    return SdarMoe(config or SdarMoeConfig())


def loss(labels, outputs):
    """1 / (B L) * sum over the masked positions of CE(logits_noised[p],
    x_0[p]) / t[beta(p)]: `labels["weights"]` is masked / t. No auxiliary
    loss in the objective."""
    ce = optax.softmax_cross_entropy_with_integer_labels(
        outputs["logits"], labels["targets"].astype(jnp.int32))
    return jnp.mean(ce * labels["weights"])


def optimizer():
    return optimizers.adam(learning_rate=3e-4)


def noise(tokens, t, u, mask_token_id):
    """(noised copy, weights) of records [B, L] under their own draw: a
    level t [B, L / b] a block, a uniform u [B, L] a position; position p is
    masked where u[p] < t[beta(p)] and then weighs 1 / t[beta(p)]."""
    block = tokens.shape[1] // t.shape[1]
    level = np.repeat(t.astype(np.float32), block, axis=1)
    masked = u.astype(np.float32) < level
    noised = np.where(masked, np.int32(mask_token_id), tokens)
    return noised.astype(np.int32), (masked / level).astype(np.float32)


def make_feed(mask_token_id):
    """The feed of records {"tokens" int32 [L], "t" float32 [L / b], "u"
    float32 [L]}: the noise was drawn when the record was written, so the
    noising is data and no random key enters the step."""

    def feed(records, mode, metadata):
        batch = batch_examples(records)
        tokens = batch["tokens"].astype(np.int32)
        noised, weights = noise(
            tokens, batch["t"], batch["u"], mask_token_id)
        features = {"tokens": tokens, "noised": noised}
        if mode == Modes.PREDICTION:
            return features, None
        return features, {"targets": tokens, "weights": weights}

    return feed


feed = make_feed(SdarMoeConfig().mask_token_id)


def masked_ce(outputs, labels):
    """The objective, a position: CE at the masked positions over their t,
    0 elsewhere (its mean over all positions is the loss)."""
    ce = token_ce(outputs, labels["targets"])[..., 0]
    return ce * np.asarray(labels["weights"], np.float64)


def masked_accuracy(outputs, labels):
    """Whether the largest logit is the token, at the masked positions
    alone."""
    masked = np.asarray(labels["weights"]) > 0
    hit = np.argmax(np.asarray(outputs), axis=-1) == np.asarray(
        labels["targets"])
    return hit[masked].astype(np.float64)


def eval_metrics_fn():
    return {"masked_ce": MeanMetric(masked_ce),
            "masked_accuracy": MeanMetric(masked_accuracy)}


def param_specs(variables):
    """Everything replicated: data parallel over whole copies of this
    chip's share. (Held experts over a mesh axis need the layer's
    all-to-all, which is not built.)"""
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(lambda _: P(), variables)
