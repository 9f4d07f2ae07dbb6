"""A toy Kanana 2 expert decoder as a model-def module, for the CPU tests
only: a leading dense layer and three routed ones, keys of 16 without
position + 8 rotary against values of 16 (a key width that is not the
value width), a latent of 32, two shared experts, a share of the experts (2
to 5 of 8), routing by seeded noise as in the cut, sizes as
tiny_kanana2.json states them; an
initialiser of 0.125, so that the toy's scores are of the order of 1."""

from elasticdl_tpu.models.kanana.kanana_moe import (  # noqa: F401
    KananaMoeConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    param_specs,
)
from elasticdl_tpu.ops import optimizers

CONFIG = KananaMoeConfig.from_public(
    {"num_hidden_layers": 4, "first_k_dense_replace": 1},
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    kv_lora_rank=32, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
    routed_scaling_factor=2.448, rope_theta=1000000,
    experts_held=(2, 4), expert_block_rows=16,
    force_load_balancing=True,
    # Weights large enough that the scores are of the order of 1, as the
    # cut's are at its widths: the rope and the scale then move the loss.
    initializer_range=0.125,
)


def custom_model():
    return _custom_model(CONFIG)


def optimizer():
    """The toy trains at 3e-4 (tiny_kanana2.json), where its planted
    faults show within 16 steps; the model's own rate is a tenth."""
    return optimizers.adam(learning_rate=3e-4)
