"""One chip's share of SDAR-30B-A3B-Chat, as a model-def module.

`edl train --model_def elasticdl_tpu.models.sdar.sdar_30b_a3b_cut` runs
the cut that `benchmark/configs/sdar_30b_a3b.json` states: every width of
the public `config.json`, the router's 128 outputs and its 8 experts a
token as published; 6 of the published 48 layers (all alike), experts 0-15
of each layer (one of the 8 chips that share a layer), the first 18,992
rows of the vocabulary (one of 8 slices), the last of them the MASK token.
"""

from elasticdl_tpu.models.sdar.sdar_moe import (  # noqa: F401
    SdarMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    loss,
    make_feed,
    optimizer,
    param_specs,
)

# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json:
# the keys that say something of the model's shape.
PUBLIC_CONFIG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
KEEP_LAYERS = (0, 1, 2, 3, 4, 5)
VOCAB_ROWS = 151936 // 8
MASK_TOKEN_ID = VOCAB_ROWS - 1
BLOCK_LENGTH = 4
EXPERT_BLOCK_ROWS = 1152


def cut_config():
    """The cut: depth, this chip's rows of the vocabulary, this chip's
    experts; remat and the block's rows as the chip chose them (the
    configuration file's `model.remat_reason`, `expert_block_rows_reason`).
    Routing is by seeded noise, every expert its even share, as
    Megatron-Core's benchmark mode has it: with 16 of 128 experts and no
    exchange a router learns from the held experts' part alone and leaves
    them (the file's `departures`)."""
    public = dict(PUBLIC_CONFIG, vocab_size=VOCAB_ROWS)
    return SdarMoeConfig.from_public(
        public, keep_layers=KEEP_LAYERS, experts_held=(0, 16), remat=False,
        force_load_balancing=True, expert_block_rows=EXPERT_BLOCK_ROWS,
        block_length=BLOCK_LENGTH, mask_token_id=MASK_TOKEN_ID)


def custom_model():
    return _custom_model(cut_config())


feed = make_feed(MASK_TOKEN_ID)
