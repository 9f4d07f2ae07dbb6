"""One chip's share of Mellum2-12B-A2.5B-Instruct, as a model-def module.

`edl train --model_def elasticdl_tpu.models.mellum.mellum2_12b_a2_5b_cut`
runs the cut that `benchmark/configs/mellum2_12b_a2_5b.json` states: every
width of the public `config.json`, the window of 1,024, both rope tables,
the router's 64 outputs and its 8 experts a token as published; layers 0 to
3 of the published 28 (one period: three windowed layers and a full one,
the first of seven pipeline stages), experts 0-15 of each layer (one of the
4 chips that share a layer), the first 24,576 rows of the vocabulary (one of
4 slices).
"""

from elasticdl_tpu.models.mellum.mellum_moe import (  # noqa: F401
    MellumMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/
# config.json: the keys that say something of the model's shape.
PUBLIC_CONFIG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 500000},
    },
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
KEEP_LAYERS = (0, 1, 2, 3)
VOCAB_ROWS = 98304 // 4
EXPERT_BLOCK_ROWS = 2176
REMAT_LAYERS = (0, 1)


def cut_config():
    """The cut: depth, this chip's rows of the vocabulary, this chip's
    experts; the layers rematerialised and the block's rows as the chip
    chose them (the configuration file's `model.remat_reason`,
    `expert_block_rows_reason`). Routing is by seeded noise, every expert
    its even share, as Megatron-Core's benchmark mode has it: with 16 of 64
    experts and no exchange a router learns from the held experts' part
    alone and leaves them (the file's `departures`)."""
    public = dict(PUBLIC_CONFIG, vocab_size=VOCAB_ROWS)
    return MellumMoeConfig.from_public(
        public, keep_layers=KEEP_LAYERS, experts_held=(0, 16),
        remat_layers=REMAT_LAYERS, force_load_balancing=True,
        expert_block_rows=EXPERT_BLOCK_ROWS)


def custom_model():
    return _custom_model(cut_config())
