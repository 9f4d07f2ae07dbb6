"""A toy Mellum 2 expert decoder as a model-def module, for the CPU tests
only: two periods of three windowed layers to a full one, a window (8)
smaller than the records (32), both rope tables in play (the module's
`DEFAULT_ROPE`: the YaRN ramp runs from frequency 0 to 2 of 16), heads of
a width that is not hidden / heads, a share of the experts (2 to 5 of 8),
routing by seeded noise as in the cut, sizes as tiny_mellum2.json states
them."""

from elasticdl_tpu.models.mellum.mellum_moe import (  # noqa: F401
    BAND,
    DEFAULT_ROPE,
    FULL,
    MellumMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

CONFIG = MellumMoeConfig.from_public(
    {"layer_types": [BAND, BAND, BAND, FULL] * 2,
     "rope_parameters": DEFAULT_ROPE},
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, sliding_window=8,
    experts_held=(2, 4), expert_block_rows=16,
    force_load_balancing=True,
)


def custom_model():
    return _custom_model(CONFIG)
