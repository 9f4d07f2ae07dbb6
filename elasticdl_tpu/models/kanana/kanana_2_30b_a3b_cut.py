"""One chip's share of kanana-2-30b-a3b-instruct-2601, as a model-def module.

`edl train --model_def elasticdl_tpu.models.kanana.kanana_2_30b_a3b_cut`
runs the cut that `benchmark/configs/kanana_2_30b_a3b.json` states: every
width of the public `config.json` (keys of 128 without position + 64
rotary against values of 128, the latent of 512, experts of 768, the dense
layer's 6144), the router's 128 outputs and its 6 experts a token, the two
shared experts and the scaling factor as published; layers 0 to 5 of the
published 48 (the leading dense layer and five routed ones: the first of
eight pipeline stages), experts 0-15 of each routed layer (one of the 8
chips that share a layer), the first 16,032 rows of the vocabulary (one of 8
slices).
"""

from elasticdl_tpu.models.kanana.kanana_moe import (  # noqa: F401
    KananaMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

# https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/
# config.json: the keys that say something of the model's shape.
PUBLIC_CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}
KEEP_LAYERS = (0, 1, 2, 3, 4, 5)
VOCAB_ROWS = 128256 // 8
EXPERT_BLOCK_ROWS = 896
REMAT_LAYERS = (1, 2, 3, 4, 5)


def cut_config():
    """The cut: depth, this chip's rows of the vocabulary, this chip's
    experts; the layers rematerialised and the block's rows as the chip
    chose them (the configuration file's `model.remat_reason`,
    `expert_block_rows_reason`). Routing is by seeded noise, every expert
    its even share, as Megatron-Core's benchmark mode has it: with 16 of
    128 experts and no exchange a router learns from the held experts' part
    alone and leaves them (the file's `departures`)."""
    public = dict(PUBLIC_CONFIG, vocab_size=VOCAB_ROWS)
    return KananaMoeConfig.from_public(
        public, keep_layers=KEEP_LAYERS, experts_held=(0, 16),
        remat_layers=REMAT_LAYERS, force_load_balancing=True,
        expert_block_rows=EXPERT_BLOCK_ROWS)


def custom_model():
    return _custom_model(cut_config())
