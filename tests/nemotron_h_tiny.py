"""A tiny Nemotron-H configuration for the CPU tests (all four kinds of
layer, the published stage's order of mixers and experts, toy widths)."""

from dynamo_tpu.models import ModelConfig


def tiny_nemotron_h(**overrides) -> ModelConfig:
    raw = dict(
        model_type="nemotron_h", vocab_size=256, hidden_size=64,
        intermediate_size=48, num_hidden_layers=6,
        hybrid_override_pattern="MEM*E-", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, attention_bias=False,
        mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        conv_kernel=4, chunk_size=8, use_conv_bias=True,
        mlp_hidden_act="relu2", moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5, n_group=1, topk_group=1, norm_eps=1e-5,
        layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=0.0001, rope_theta=10000, partial_rotary_factor=1,
        max_position_embeddings=512, eos_token_id=2,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
