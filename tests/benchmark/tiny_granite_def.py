"""A toy Granite 4.0-H dense hybrid as a model-def module, for the CPU
tests only: three mixers to one attention layer, one group, multipliers
that are none of them 1 and an attention multiplier that is not
head_dim^-0.5, sizes as tiny_granite.json states them. Its initialiser is
25 times the published 0.02: at 0.02 a model this small stays at the
uniform loss for its first 32 steps, and neither a lower precision nor a
scan without its carried state moves it (2e-4 and 2e-5); at 0.5 every
mechanism shows in the loss from the first step."""

from elasticdl_tpu.models.granite_hybrid.granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

CONFIG = GraniteHybridConfig(
    layer_types=("mamba", "mamba", "attention", "mamba"),
    hidden_size=64, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_chunk_size=8,
    attention_multiplier=0.0625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0,
    initializer_range=0.5,
)


def custom_model():
    return _custom_model(CONFIG)
