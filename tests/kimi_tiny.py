"""A tiny Kimi-Linear configuration for the CPU tests (every kind of
layer the published model has, at toy widths)."""

from dynamo_tpu.models import ModelConfig


def tiny_kimi(**overrides) -> ModelConfig:
    raw = dict(
        model_type="kimi_linear", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, first_k_dense_replace=1,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, mla_use_nope=True, q_lora_rank=None,
        linear_attn_config=dict(
            kda_layers=[1, 2, 3, 5], full_attn_layers=[4], num_heads=4,
            head_dim=16, short_conv_kernel_size=4),
        moe_intermediate_size=32, num_experts=8, num_experts_per_token=2,
        num_shared_experts=1, moe_router_activation_func="sigmoid",
        moe_renormalize=True, routed_scaling_factor=2.446,
        num_expert_group=1, topk_group=1, rms_norm_eps=1e-5,
        model_max_length=512, eos_token_id=2,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
