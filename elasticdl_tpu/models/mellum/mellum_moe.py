"""The Mellum 2 expert decoder (HF `model_type: mellum`): windowed and
full attention mixed in one tower, by `layer_types[l]`, every layer routed.

Layer l, each half behind its own pre-norm residual:

    h = h + Wo A_l(rope_l(qn(Wq u)), rope_l(kn(Wk u)), Wv u),  u = RMSNorm(h)
        heads of `head_dim` (a key of its own), no bias; qn, kn an RMSNorm
        over each head's channels; softmax attention at scale
        head_dim^-0.5 inside the flash kernels (ops/flash_attention.py)
      sliding_attention  A under `Band(sliding_window)`: row r sees column
                         c iff c <= r and r - c < sliding_window; rope the
                         HF `default` table of `rope_parameters`
      full_attention     A causal; rope the YaRN table (`yarn_inv_freq`),
                         cos and sin times its `attention_factor`
    h = h + sum over the top k of w_e W2_e(silu(W1_e u') * W3_e u')
        p = softmax(Wr u') over all experts in float32, the k largest,
        w = p / sum(p chosen) if `norm_topk_prob`; no shared expert, no
        auxiliary loss; layers/moe.py RoutedExperts
    final RMSNorm, logits through an untied head, next-token cross-entropy.

`MellumMoeConfig` takes the keys of the public `config.json` under their own
names (`from_public`), `layer_types` and `rope_parameters` among them, plus
`experts_held = (first, count)`: the share of each layer's experts that
lives on this chip (None = all of them). Float32 parameters, bfloat16
activations, float32 router, norms, rotary angles, softmax and loss, as the
other configurations state theirs.

Model contract: training=True returns {"logits", "stats"} (the routed
layers' counts summed over them, and the windowed attention's scores
needed and run); training=False returns plain logits. Not built: a band whose window
is not a whole number of tiles, a band under ring / Ulysses attention,
packed documents under a band, a multi-token-prediction head (the public
`config.json` has no key for one).
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.moe import RoutedExperts
from elasticdl_tpu.models.nemotron_h.nemotron_h import RMSNorm
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.ops import optimizers
from elasticdl_tpu.ops.flash_attention import (
    Band,
    band_scores,
    flash_attention,
)
from elasticdl_tpu.ops.qk_rotary import qk_rotary, rope_tables

BAND, FULL = "sliding_attention", "full_attention"
SCOPES = {BAND: "mellum_band_attention", FULL: "mellum_full_attention"}
MOE_SCOPE = "mellum_moe"
DEFAULT_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
           "original_max_position_embeddings": 16, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.1386294361119891},
    BAND: {"rope_type": "default", "rope_theta": 10000.0},
}


def _frozen(tree):
    """A dict of dicts as sorted tuples: a configuration is hashable."""
    if isinstance(tree, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in tree.items()))
    return tree


def _own_inv_freq(theta, dim):
    """theta^(-2i / dim) for i < dim / 2: the HF `default` table."""
    return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(rope, dim):
    """HF's `_compute_yarn_parameters` (truncation on) from one entry of
    `rope_parameters`, as float64 [dim / 2]: below `low` a frequency is
    theta's own (it turns `beta_fast` times or more inside the original
    length), above `high` it is divided by `factor`, between them the two
    are mixed along a linear ramp. It does not depend on the sequence."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope.get("beta_fast", 32)))), 0)
    high = min(
        math.ceil(correction_dim(float(rope.get("beta_slow", 1)))), dim - 1)
    own = _own_inv_freq(theta, dim)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low)
        / ((high - low) or 0.001), 0, 1)
    return (1 - ramp) * own + ramp * own / factor


def rope_table(rope, dim):
    """(inv_freq float32 [dim / 2], what cos and sin are multiplied by) of
    one entry of `rope_parameters`: computed once, on the host."""
    kind = rope.get("rope_type", "default")
    if kind == "default":
        table, scale = _own_inv_freq(float(rope["rope_theta"]), dim), None
    elif kind == "yarn":
        table = yarn_inv_freq(rope, dim)
        scale = float(rope.get("attention_factor") or (
            0.1 * math.log(float(rope["factor"])) + 1.0))
    else:
        raise ValueError(f"rope_type {kind!r}: built are default and yarn")
    return table.astype(np.float32), scale


@dataclasses.dataclass(frozen=True)
class MellumMoeConfig:
    # The public keys, under their public names.
    layer_types: Tuple[str, ...] = (BAND, FULL)
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
    sliding_window: int = 16
    # {kind of layer: its rope entry}, frozen (`rope(kind)` gives the dict).
    rope_parameters: Any = _frozen(DEFAULT_ROPE)
    initializer_range: float = 0.02
    # Routing by seeded noise, every expert its even share (a benchmark
    # mode: layers/moe.py `force_balance_seed`).
    force_load_balancing: bool = False
    # This chip's share of each layer's experts: (first expert, how many).
    experts_held: Optional[Tuple[int, int]] = None
    # Rows of one block of the grouped expert product.
    expert_block_rows: int = 1024
    activation_dtype: str = "bfloat16"
    # The layers rematerialised in the backward pass (memory for FLOPs).
    remat_layers: Tuple[int, ...] = ()

    def __post_init__(self):
        unknown = set(self.layer_types) - set(SCOPES)
        if unknown or not self.layer_types:
            raise ValueError(
                f"layer_types {self.layer_types!r}: kinds are "
                f"{sorted(SCOPES)}, got {sorted(unknown)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not split over "
                f"{self.num_key_value_heads} key/value heads")
        if self.head_dim % 2:
            raise ValueError(
                f"head_dim {self.head_dim} gives a head no even width to "
                "rotate")
        for kind in set(self.layer_types):
            rope_table(self.rope(kind), self.head_dim)

    @classmethod
    def from_public(cls, public, keep_layers=None, **overrides):
        """From a `config.json`-shaped dict: the keys this model reads are
        taken, the rest (flags of the HF runtime) are left. `keep_layers`
        are the published layers that are kept, in order: their kinds stay
        theirs."""
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in public.items() if k in names}
        if set(public.get("mlp_layer_types") or ["sparse"]) != {"sparse"}:
            raise ValueError("every layer of this model is routed")
        if keep_layers is not None:
            kept["layer_types"] = [
                public["layer_types"][i] for i in keep_layers]
        kept.update(overrides)
        kept["layer_types"] = tuple(kept["layer_types"])
        kept["rope_parameters"] = _frozen(kept["rope_parameters"])
        kept["remat_layers"] = tuple(kept.get("remat_layers", ()))
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def num_hidden_layers(self):
        return len(self.layer_types)

    def rope(self, kind):
        return dict(dict(self.rope_parameters)[kind])

    @property
    def init(self):
        return nn.initializers.normal(self.initializer_range)


class Attention(nn.Module):
    config: MellumMoeConfig
    kind: str

    @nn.compact
    def __call__(self, x, rope):
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
            the turn by this kind of layer's tables, the rounding and the
            layout in one op."""
            weight = self.param(norm, nn.initializers.ones, (dh,))
            return qk_rotary(
                proj(n, name), weight, cfg.rms_norm_eps, *rope)

        # Each key/value head serves heads / kv query heads: broadcast
        # before the kernel, so the broadcast's gradient sums the group.
        q = turned(heads, "q_proj", "q_norm")
        k = jnp.repeat(turned(kv, "k_proj", "k_norm"), heads // kv, axis=1)
        v = jnp.repeat(
            jnp.swapaxes(proj(kv, "v_proj"), 1, 2), heads // kv, axis=1)
        with jax.named_scope(SCOPES[self.kind]):
            if self.kind == BAND:
                out = flash_attention(q, k, v, Band(cfg.sliding_window))
            else:
                out = flash_attention(q, k, v, True)
        out = jnp.swapaxes(out, 1, 2).reshape(*x.shape[:2], heads * dh)
        return nn.Dense(
            cfg.hidden_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="o_proj")(out)


class Block(nn.Module):
    """One layer. Returns (h, the routed layer's stats)."""

    config: MellumMoeConfig
    index: int

    @nn.compact
    def __call__(self, h, rope):
        cfg = self.config

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name=name)

        h = h + Attention(
            cfg, cfg.layer_types[self.index], name="self_attn")(
                norm("input_layernorm")(h), rope).astype(h.dtype)
        with jax.named_scope(MOE_SCOPE):
            out, stats = RoutedExperts(
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                d_hidden=cfg.moe_intermediate_size, gated=True,
                score="softmax", held=cfg.experts_held,
                norm_topk_prob=cfg.norm_topk_prob, topk_eps=0.0,
                block_rows=cfg.expert_block_rows,
                force_balance_seed=(
                    self.index if cfg.force_load_balancing else None),
                dtype=cfg.activation_dtype, kernel_init=cfg.init,
                name="mlp")(norm("post_attention_layernorm")(h))
        return h + out.astype(h.dtype), stats


def attention_scores(cfg, batch, seq):
    """The scores a step's windowed attention needs and runs, over all its
    windowed layers and batch*heads: what the mask lets through and what
    the kernels' run tiles hold (`band_scores`)."""
    needed, run = band_scores(seq, cfg.sliding_window)
    bands = batch * cfg.num_attention_heads * cfg.layer_types.count(BAND)
    return {"band_scores_needed": bands * needed,
            "band_scores_run": bands * run}


class MellumMoe(nn.Module):
    config: MellumMoeConfig = MellumMoeConfig()

    @nn.compact
    def __call__(self, tokens, training: bool = False):
        cfg = self.config
        dtype = jnp.dtype(cfg.activation_dtype)
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     embedding_init=cfg.init, name="embed_tokens")(
                         tokens.astype(jnp.int32))
        # One pair of cos and sin tables a kind of layer, for all of them.
        ropes = {
            kind: rope_tables(
                jnp.arange(tokens.shape[1]),
                *rope_table(cfg.rope(kind), cfg.head_dim))
            for kind in sorted(set(cfg.layer_types))}
        totals = None
        for i in range(cfg.num_hidden_layers):
            block_cls = nn.remat(Block) if i in cfg.remat_layers else Block
            h, stats = block_cls(cfg, i, name=f"layers_{i}")(
                h, ropes[cfg.layer_types[i]])
            totals = stats if totals is None else jax.tree_util.tree_map(
                jnp.add, totals, stats)
        h = RMSNorm(cfg.rms_norm_eps, cfg.activation_dtype, name="norm")(h)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=dtype,
            kernel_init=cfg.init, name="lm_head")(h).astype(jnp.float32)
        if not training:
            return logits
        scores = attention_scores(cfg, *tokens.shape)
        totals = dict(totals, **{
            name: jnp.asarray(count, jnp.float32)
            for name, count in scores.items()})
        return {"logits": logits, "stats": totals}


# ---------- model spec contract ----------


def custom_model(config: MellumMoeConfig = None):
    return MellumMoe(config or MellumMoeConfig())


def loss(labels, outputs):
    """Next-token cross-entropy, no auxiliary loss."""
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
