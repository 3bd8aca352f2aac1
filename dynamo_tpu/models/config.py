"""Model architecture config, loaded from HF-format config.json."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class ModelConfig:
    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_id: int | list[int] = 2
    # qwen2-family: bias on q/k/v projections
    attention_bias: bool = False
    # mistral-family: attend only to the last `sliding_window` positions
    sliding_window: Optional[int] = None
    # mlp activation: "silu" (llama et al) or "gelu" (gemma)
    hidden_act: str = "silu"
    # gemma-family: x *= sqrt(hidden_size) after embedding lookup, and
    # rmsnorm weights are stored as (w - 1) so the norm multiplies (1+w)
    scale_embeddings: bool = False
    norm_bias_one: bool = False
    # MoE (Mixtral-style)
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # multimodal (filled for vision-language models)
    vision_config: Optional[dict] = None
    # token id the processor substitutes per image patch slot (LLaVA's
    # image_token_index); None = resolve via the tokenizer
    image_token_index: Optional[int] = None
    # kimi_linear (models/kimi_linear.py): layers of several kinds. The
    # 1-based layer lists of linear_attn_config say which mixer a layer
    # has (gated delta rule with per-sequence state, or latent attention
    # over paged rows); first_k_dense_replace says which feed-forward.
    linear_attn_config: Optional[dict] = None
    first_k_dense_replace: int = 0
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_use_nope: bool = False
    # expert layer: num_experts is how many THIS process holds; the
    # router scores num_experts * expert_shards and this process holds
    # the expert_shard_index-th run of them (1 / 0 = all of them)
    num_experts: int = 0
    num_experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_intermediate_size: int = 0
    moe_router_activation_func: str = "softmax"
    moe_renormalize: bool = True
    routed_scaling_factor: float = 1.0
    num_expert_group: int = 1
    topk_group: int = 1
    expert_shards: int = 1
    expert_shard_index: int = 0
    # qwen3_next (models/qwen3_next.py): every full_attention_interval-th
    # layer is gated GQA attention with a rotary part of each head, the
    # others Gated DeltaNet (linear_*); every layer has softmax-routed
    # experts (num_experts_per_tok of them) and a gated shared expert
    full_attention_interval: int = 0
    partial_rotary_factor: float = 1.0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: list = field(default_factory=list)
    # nemotron_h (models/nemotron_h.py): hybrid_override_pattern gives
    # every layer ONE part by a letter — M a Mamba-2 mixer (mamba_*,
    # ssm_state_size, n_groups B/C groups, conv_kernel taps, chunk_size
    # tokens a block of the chunked scan), * attention, E sigmoid-routed
    # relu^2 experts (n_routed_experts held here, as num_experts above)
    # plus a shared one, - a relu^2 MLP
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    use_bias: bool = False
    mamba_proj_bias: bool = False
    mlp_bias: bool = False
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_shared_expert_intermediate_size: int = 0
    n_group: int = 1
    mlp_hidden_act: str = "relu2"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    # deepseek_v3 (models/deepseek_v3.py): latent attention with a rotary
    # part in every layer (rope_interleave: the rotated pairs are the
    # adjacent ones), first_k_dense_replace dense layers then
    # n_routed_experts sigmoid-routed experts (scoring_func, topk_method:
    # top num_experts_per_tok of score + e_score_correction_bias) plus
    # n_shared_experts shared ones as one MLP; qk_head_dim is
    # qk_nope_head_dim + qk_rope_head_dim, checked where given
    rope_interleave: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    qk_head_dim: Optional[int] = None
    # mimo_v2_flash (models/mimo_v2_flash.py): hybrid_layer_pattern gives
    # every layer 0 (full attention: num_key_value_heads, rope_theta, no
    # sink unless add_full_attention_sink_bias) or 1 (window attention
    # over sliding_window keys: swa_num_key_value_heads, swa_rope_theta,
    # a learned sink a head if add_swa_attention_sink_bias); K heads are
    # head_dim wide and V heads v_head_dim (swa_* the same), V scaled by
    # attention_value_scale; the first partial_rotary_factor of a head is
    # rotated; moe_layer_freq gives every layer 0 (dense MLP) or 1
    # (n_routed_experts sigmoid-routed experts, no shared one)
    hybrid_layer_pattern: Optional[list] = None
    moe_layer_freq: Optional[list | int] = None
    swa_num_attention_heads: Optional[int] = None
    swa_num_key_value_heads: Optional[int] = None
    swa_head_dim: Optional[int] = None
    swa_v_head_dim: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    attention_value_scale: Optional[float] = None
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    sliding_window_size: Optional[int] = None
    attention_chunk_size: Optional[int] = None
    # glm_moe_dsa (models/glm_moe_dsa.py): deepseek_v3's layers with a
    # low-rank query (q_lora_rank) and a learned indexer a layer —
    # index_n_heads heads of index_head_dim, the first qk_rope_head_dim of
    # them rotated (pairs by indexer_rope_interleave) — whose index_topk
    # best keys are all a query attends; rope_theta is read from
    # rope_parameters where the top level has none;
    # num_nextn_predict_layers (a multi-token-prediction layer) is read
    # by nothing: the main model's logits do not depend on it
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    indexer_rope_interleave: bool = True
    num_nextn_predict_layers: int = 0

    def __post_init__(self) -> None:
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @property
    def is_moe(self) -> bool:
        return self.num_local_experts > 0

    @property
    def has_recurrent_state(self) -> bool:
        """Layers that keep a fixed-size state per sequence beside the
        paged rows (the engine gives every running sequence a slot): the
        configuration's family module says so (``RECURRENT_STATE``)."""
        from dynamo_tpu.models import family

        return bool(getattr(family(self), "RECURRENT_STATE", False))

    @property
    def owns_pages(self) -> bool:
        """The family lays its pages out itself (latent rows, or the K
        and V of its attention layers alone) and sizes them for the
        engine (``page_bytes_per_block``). Whether it ALSO keeps
        recurrent state is ``has_recurrent_state``'s to say: a family
        with pages of its own and no state keeps the prefix cache."""
        from dynamo_tpu.models import family

        return hasattr(family(self), "page_bytes_per_block")

    @property
    def released_window(self) -> int:
        """The window behind which a page plane of the family is
        RELEASED (0: none is): the third question asked of a family
        (``models/__init__.py``; ``released_window(cfg)`` of its module).
        The engine then keeps a second plane with its own ids and table,
        and prefix reuse is off."""
        from dynamo_tpu.models import family

        fn = getattr(family(self), "released_window", None)
        return int(fn(self)) if fn is not None else 0

    def layer_letters(self) -> list[str]:
        """``hybrid_override_pattern`` as one letter a layer (``M``,
        ``*``, ``E``, ``-``), ``num_hidden_layers`` of them."""
        letters = list(self.hybrid_override_pattern)
        bad = sorted(set(letters) - set("M*E-"))
        if bad or len(letters) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} "
                f"must give each of the {self.num_hidden_layers} layers one "
                f"of M, *, E, -" + (f" (found {bad})" if bad else ""))
        return letters

    @property
    def eos_token_ids(self) -> list[int]:
        e = self.eos_token_id
        return list(e) if isinstance(e, list) else [e]

    @classmethod
    def from_dir(cls, path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            raw = json.load(f)
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict) -> "ModelConfig":
        # VLM configs (LLaVA layout) nest the language model under
        # text_config; hoist it and keep the vision_config alongside
        # (reference: examples/multimodal serves such checkpoints)
        if "text_config" in raw:
            merged = dict(raw["text_config"])
            if raw.get("vision_config") is not None:
                merged["vision_config"] = raw["vision_config"]
            if "image_token_index" in raw:
                merged["image_token_index"] = raw["image_token_index"]
            structural = {
                "hidden_size", "num_hidden_layers",
                "num_attention_heads", "intermediate_size",
            }
            missing = structural - set(merged)
            if missing:
                # real llava-hf text_configs are often sparse and lean
                # on transformers' LlamaConfig (7B) defaults — which
                # this dataclass happens to share. Weight loading
                # validates every shape, so a wrong guess fails loudly
                # there; random-weight runs would not, hence the warning.
                import logging

                logging.getLogger("dynamo_tpu.models").warning(
                    "text_config omits %s; assuming Llama-7B-shaped "
                    "defaults (weight loading validates shapes)",
                    sorted(missing),
                )
            raw = merged
        known = {f.name for f in cls.__dataclass_fields__.values()}  # type: ignore[attr-defined]
        kwargs = {k: v for k, v in raw.items() if k in known}
        # qwen2 checkpoints always use qkv bias but don't say so in config
        if raw.get("model_type") == "qwen2" and "attention_bias" not in raw:
            kwargs["attention_bias"] = True
        # normalize HF gelu variants onto the one gelu we implement
        if kwargs.get("hidden_act") in ("gelu_pytorch_tanh", "gelu_new"):
            kwargs["hidden_act"] = "gelu"
        # gemma semantics are implied by the model_type, not config keys
        if raw.get("model_type") == "gemma":
            kwargs["scale_embeddings"] = True
            kwargs["norm_bias_one"] = True
            kwargs.setdefault("hidden_act", "gelu")
            kwargs.setdefault("tie_word_embeddings", True)
        # qwen2 configs carry sliding_window but HF defaults
        # use_sliding_window to FALSE: the window only applies when the
        # flag is explicitly true (mistral-family configs have no such
        # flag and the window always applies)
        if raw.get("model_type") == "qwen2" and not raw.get("use_sliding_window", False):
            kwargs["sliding_window"] = None
        elif raw.get("use_sliding_window") is False:
            kwargs["sliding_window"] = None
        # glm_moe_dsa keeps the rotary base in rope_parameters; a rope_type
        # other than default is a scaled rotary (refused by the family)
        rope = raw.get("rope_parameters")
        if isinstance(rope, dict):
            if "rope_theta" not in raw and "rope_theta" in rope:
                kwargs["rope_theta"] = float(rope["rope_theta"])
            if (rope.get("rope_type", "default") != "default"
                    and raw.get("rope_scaling") is None):
                kwargs["rope_scaling"] = dict(rope)
        # kimi_linear states its longest context as model_max_length
        if "max_position_embeddings" not in raw and "model_max_length" in raw:
            kwargs["max_position_embeddings"] = int(raw["model_max_length"])
        # nemotron_h names the RMSNorm epsilon norm_eps / layer_norm_epsilon
        if "rms_norm_eps" not in raw:
            for key in ("norm_eps", "layer_norm_epsilon", "layernorm_epsilon"):
                if key in raw:
                    kwargs["rms_norm_eps"] = float(raw[key])
                    break
        return cls(**kwargs)
