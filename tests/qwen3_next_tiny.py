"""A tiny Qwen3-Next configuration for the CPU tests (both kinds of
mixer in the published 3:1, the expert block, at toy widths)."""

from dynamo_tpu.models import ModelConfig


def tiny_qwen3_next(**overrides) -> ModelConfig:
    raw = dict(
        model_type="qwen3_next", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, full_attention_interval=4,
        partial_rotary_factor=0.25, rope_theta=10000000,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_conv_kernel_dim=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True, decoder_sparse_step=1,
        mlp_only_layers=[], rms_norm_eps=1e-6, max_position_embeddings=512,
        eos_token_id=2,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
