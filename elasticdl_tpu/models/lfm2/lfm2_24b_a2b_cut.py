"""One chip's share of LFM2-24B-A2B, as a model-def module.

`edl train --model_def elasticdl_tpu.models.lfm2.lfm2_24b_a2b_cut` runs
the cut that `benchmark/configs/lfm2_24b_a2b.json` states: every width of
the public `config.json`, the router's 64 outputs and its 4 experts a
token as published; layer 0 and layers 2 to 7 of the published 40 (the
leading dense layers counted once, then `full_attention conv conv conv
full_attention conv`, all routed), experts 0-7 of each routed layer (one
of the 8 chips that share a layer), the first 8,192 rows of the
vocabulary (one of 8 slices).
"""

from elasticdl_tpu.models.lfm2.lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json: the
# keys that say something of the model's shape.
PUBLIC_CONFIG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 9
        + ["full_attention", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
KEEP_LAYERS = (0, 2, 3, 4, 5, 6, 7)
EXPERT_BLOCK_ROWS = 1152


def cut_config():
    """The cut: depth, this chip's rows of the vocabulary, this chip's
    experts; remat and the block's rows as the chip chose them (the
    configuration file's `model.remat_reason`, `expert_block_rows_reason`).
    Routing is by seeded noise, every expert its even share, as
    Megatron-Core's benchmark mode has it: with 8 of 64 experts and no
    exchange a router learns from the held experts' part alone and leaves
    them (the file's `departures`)."""
    public = dict(PUBLIC_CONFIG, vocab_size=8192)
    return Lfm2MoeConfig.from_public(
        public, keep_layers=KEEP_LAYERS, experts_held=(0, 8), remat=False,
        force_load_balancing=True, expert_block_rows=EXPERT_BLOCK_ROWS)


def custom_model():
    return _custom_model(cut_config())
