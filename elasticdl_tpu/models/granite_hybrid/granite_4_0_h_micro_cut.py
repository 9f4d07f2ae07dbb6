"""One pipeline stage's chip of granite-4.0-h-micro, as a model-def module.

`edl train --model_def
elasticdl_tpu.models.granite_hybrid.granite_4_0_h_micro_cut` runs the cut
that `benchmark/configs/granite_4_0_h_micro.json` states: every width of
the public `config.json` and its four multipliers as published; layers 0
to 9 of the published 40 (one whole period of the pattern: five Mamba-2
mixers, the attention layer, four mixers more; each with its MLP), the
first 12,544 rows of the vocabulary (an eighth: a cut for the chip's
memory, not a deployment's share; the table is also the output head, and
both ends of the model run on this chip).
"""

from elasticdl_tpu.models.granite_hybrid.granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

# https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main
# /config.json: the keys that say something of the model's shape.
PUBLIC_CONFIG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
LAYERS = 10
VOCAB_ROWS = 100352 // 8
REMAT = "dots"


def cut_config():
    """The cut: depth and this chip's rows of the vocabulary; remat as the
    chip chose it (the configuration file's `model.remat_reason`)."""
    public = dict(PUBLIC_CONFIG, num_hidden_layers=LAYERS,
                  vocab_size=VOCAB_ROWS)
    return GraniteHybridConfig.from_public(public, remat=REMAT)


def custom_model():
    return _custom_model(cut_config())
