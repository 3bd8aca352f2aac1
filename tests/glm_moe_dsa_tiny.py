"""A tiny ``glm_moe_dsa`` configuration for the CPU tests (every piece the
published GLM-5 has, at toy widths): a low-rank query, rotary latent
attention under a learned indexer (4 heads of 16, the first 8 values
rotated) whose top 12 keys a query attends — neither a page (8) nor a
context of the tests — one leading dense layer, then sigmoid-routed
experts plus one shared MLP. The rotary base sits in ``rope_parameters``,
as in the published file."""

from dynamo_tpu.models import ModelConfig


def tiny_glm(**overrides) -> ModelConfig:
    raw = dict(
        model_type="glm_moe_dsa", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, first_k_dense_replace=1,
        kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
        rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
        rope_interleave=True, index_n_heads=4, index_head_dim=16,
        index_topk=12, indexer_rope_interleave=True,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1,
        topk_group=1, rms_norm_eps=1e-5, max_position_embeddings=512,
        num_nextn_predict_layers=1, eos_token_id=2,
    )
    raw.update(overrides)
    return ModelConfig.from_dict(raw)
