"""A toy LFM2 expert decoder as a model-def module, for the CPU tests only:
both operators, one dense layer before the routed ones, a share of the
experts (2 to 5 of 8), sizes as tiny_lfm2.json states them."""

from elasticdl_tpu.models.lfm2.lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

CONFIG = Lfm2MoeConfig(
    layer_types=("conv", "full_attention", "conv", "conv"),
    hidden_size=64, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, moe_intermediate_size=32, num_dense_layers=1,
    num_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.0,
    conv_L_cache=3, rope_theta=1e6,
    experts_held=(2, 4), expert_block_rows=16,
)


def custom_model():
    return _custom_model(CONFIG)
