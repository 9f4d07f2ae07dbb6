"""A toy Nemotron-H as a model-def module, for the CPU tests only: every
kind of layer, a share of the experts (2 to 5 of 8), sizes as
tiny_nemotron_h.json states them."""

from elasticdl_tpu.models.nemotron_h.nemotron_h import (  # noqa: F401
    NemotronHConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

CONFIG = NemotronHConfig(
    hybrid_override_pattern="ME*EM",
    hidden_size=64, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    chunk_size=8, conv_kernel=4,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, routed_scaling_factor=2.5,
    experts_held=(2, 4), expert_block_rows=16,
)


def custom_model():
    return _custom_model(CONFIG)
