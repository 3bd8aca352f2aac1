"""A tiny ``mimo_v2_flash`` configuration for the CPU tests (every piece
the published MiMo-V2-Flash has, at toy widths): window and full layers
mixed with their own KV head counts and rotary bases, learned sinks on
the window layers, K heads wider than V heads, a rotated leading third,
one leading dense layer, then sigmoid-routed experts of which this
process holds one shard. The window (12) is no multiple of the engine
tests' page (8): window and ``block_size`` are independent."""

from dynamo_tpu.models import ModelConfig


def tiny_mimo(**overrides) -> ModelConfig:
    raw = dict(
        model_type="mimo_v2_flash", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=4, num_attention_heads=8,
        num_key_value_heads=2, head_dim=24, v_head_dim=16,
        swa_num_attention_heads=8, swa_num_key_value_heads=4,
        swa_head_dim=24, swa_v_head_dim=16,
        hybrid_layer_pattern=[0, 1, 1, 0], sliding_window=12,
        sliding_window_size=12, attention_chunk_size=12,
        add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
        attention_value_scale=0.707, partial_rotary_factor=0.334,
        rope_theta=5000000.0, swa_rope_theta=10000.0, attention_bias=False,
        moe_layer_freq=[0, 1, 1, 1], moe_intermediate_size=32,
        n_routed_experts=4, expert_shards=2, expert_shard_index=0,
        num_experts_per_tok=3, n_shared_experts=None, scoring_func="sigmoid",
        topk_method="noaux_tc", norm_topk_prob=True,
        routed_scaling_factor=None, n_group=1, topk_group=1,
        layernorm_epsilon=1e-5, max_position_embeddings=512, eos_token_id=2,
        hidden_act="silu", tie_word_embeddings=False,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
