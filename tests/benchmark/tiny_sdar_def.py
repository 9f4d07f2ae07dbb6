"""A toy SDAR expert decoder as a model-def module, for the CPU tests only:
two routed layers under the block-diffusion mask, heads of a width that is
not hidden / heads, a share of the experts (2 to 5 of 8), sizes as
tiny_sdar.json states them."""

from elasticdl_tpu.models.sdar.sdar_moe import (  # noqa: F401
    SdarMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    loss,
    make_feed,
    optimizer,
    param_specs,
)

CONFIG = SdarMoeConfig(
    num_hidden_layers=2, hidden_size=64, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    rope_theta=1e6, block_length=4, mask_token_id=255,
    experts_held=(2, 4), expert_block_rows=16,
)


def custom_model():
    return _custom_model(CONFIG)


feed = make_feed(CONFIG.mask_token_id)
