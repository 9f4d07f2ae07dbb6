"""One chip's share of the Nemotron-H tower of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, as a model-def module.

`edl train --model_def
elasticdl_tpu.models.nemotron_h.nemotron_h_twotower_cut` runs the cut that
`benchmark/configs/nemotron_twotower_30b_a3b.json` states: every width of
the public `config.json`, the router's 128 outputs and its 6 experts a
token as published; the first nine layers of the pattern (`MEMEM*EME`),
experts 0-7 of each `E` layer (one of the 16 chips that share a layer),
the first 16,384 rows of the vocabulary (one of 8 slices). The denoiser
tower and the diffusion objective of the release are not built.
"""

from elasticdl_tpu.models.nemotron_h.nemotron_h import (  # noqa: F401
    NemotronHConfig,
    custom_model as _custom_model,
    eval_metrics_fn,
    feed,
    loss,
    optimizer,
    param_specs,
)

# https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
# /blob/main/config.json: the keys that say something of the tower's shape.
PUBLIC_CONFIG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
    "rope_theta": 10000, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_limit": [0, None], "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "vocab_size": 131072,
}

def cut_config():
    """The cut: depth, this chip's rows of the vocabulary, this chip's
    experts; remat as the chip chose it (the configuration file's
    `model.remat_reason`). Routing is by seeded noise, every expert its
    even share, as Megatron-Core's benchmark mode has it: with 8 of 128
    experts and no exchange a router learns from the held experts' part
    alone and within some tens of steps sends them nothing, and routers
    left as seeded load them differently from seed to seed (the file's
    `departures`)."""
    public = dict(PUBLIC_CONFIG, num_hidden_layers=9, vocab_size=16384)
    return NemotronHConfig.from_public(
        public, experts_held=(0, 8), remat=True,
        force_load_balancing=True)


def custom_model():
    return _custom_model(cut_config())
