"""A tiny ``deepseek_v3`` configuration for the CPU tests (every piece
the published Kanana-2 has, at toy widths): rotary latent attention in
every layer, one leading dense layer, then sigmoid-routed experts plus
two shared ones as one MLP."""

from dynamo_tpu.models import ModelConfig


def tiny_deepseek(**overrides) -> ModelConfig:
    raw = dict(
        model_type="deepseek_v3", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, first_k_dense_replace=1,
        kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
        rope_theta=10000.0, rope_interleave=True, rope_scaling=None,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=2, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, routed_scaling_factor=2.448, n_group=1,
        topk_group=1, rms_norm_eps=1e-6, max_position_embeddings=512,
        eos_token_id=2,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
