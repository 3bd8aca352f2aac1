"""telemetry/roofline.py: the roofline formula behind the attribution
ledger's device-split prior and `dynamo_roofline_fraction`, pinned at
the 8B int8 geometry (bytes a decode step must read, from shapes and
the datasheet bandwidth: arithmetic, not a measurement)."""

import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.telemetry.roofline import (
    RooflineModel,
    build_roofline,
    kv_bytes_per_token,
    param_bytes,
    phase_ideal_bytes,
    roofline_tok_s,
    step_bytes,
)


def _mc_8b() -> ModelConfig:
    # DeepSeek-R1-Distill-Llama-8B geometry (BASELINE.md config 1)
    return ModelConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=8192,
    )


# the pinned workload: batch 64, isl 128 / osl 128 -> avg ctx 192
B, AVG_CTX = 64, 192


def test_8b_int8_param_bytes_pin():
    # int8 weights ≈ 8.03 GB (fits a 16 GB v5e chip with KV headroom:
    # MLP+projections ~6.98 GB + 2·V·D ~1.05 GB)
    assert param_bytes(_mc_8b(), "int8") == pytest.approx(8.03e9, rel=0.01)
    assert param_bytes(_mc_8b(), None) == 2 * param_bytes(_mc_8b(), "int8")


def test_8b_kv_bytes_per_token_pin():
    mc = _mc_8b()
    # 2·L·Hk·Dh = 65536 elements/token; int8 pays +4/128 for the
    # per-(slot, head) f32 scale, fp8 is scale-free
    assert kv_bytes_per_token(mc, "bfloat16") == 131072.0
    assert kv_bytes_per_token(mc, "int8") == 65536 * (1 + 4 / 128)
    assert kv_bytes_per_token(mc, "float8_e4m3fn") == 65536.0


def test_8b_headline_roofline_pins():
    mc = _mc_8b()
    # the HBM ceiling at the pinned workload: bf16 KV -> ~5437 tok/s,
    # int8 KV -> ~5916 (half the KV bytes a step)
    assert roofline_tok_s(mc, B, AVG_CTX, "int8", "bfloat16") == pytest.approx(
        5437.0, abs=1.0
    )
    assert roofline_tok_s(mc, B, AVG_CTX, "int8", "int8") == pytest.approx(
        5915.7, abs=1.0
    )


def test_8b_phase_byte_table_pins():
    # the per-phase byte table at the pinned workload
    ph = phase_ideal_bytes(_mc_8b(), B, AVG_CTX, "int8", "int8")
    assert ph["mlp"] == pytest.approx(6.98e9, rel=0.01)
    assert ph["attention"] == pytest.approx(0.83e9, rel=0.01)
    assert ph["lm_head"] == pytest.approx(0.526e9, rel=0.01)
    assert ph["sampling"] == pytest.approx(33e6, rel=0.01)
    bf16 = phase_ideal_bytes(_mc_8b(), B, AVG_CTX, "int8", "bfloat16")
    assert bf16["attention"] == pytest.approx(1.61e9, rel=0.01)
    # phases + embedding = the step total (phase table excludes the
    # embedding read, which rides param_bytes)
    mc = _mc_8b()
    assert (
        ph["mlp"] + ph["lm_head"] + ph["attention"]
        <= step_bytes(mc, B, AVG_CTX, "int8", "int8")
    )


def test_roofline_model_matches_free_functions():
    mc = _mc_8b()
    rm = build_roofline(mc, "int8", "int8")
    assert isinstance(rm, RooflineModel)
    # ideal_step_s at the pinned geometry reproduces the tok/s pin
    # (the model adds the [B, V] sampling read — sub-0.5% at 8B)
    ideal = rm.ideal_step_s(B, B * AVG_CTX)
    assert B / ideal == pytest.approx(
        roofline_tok_s(mc, B, AVG_CTX, "int8", "int8"), rel=0.005
    )
    fr = rm.phase_fractions(B, B * AVG_CTX)
    assert sum(fr.values()) == pytest.approx(1.0)
    # weight-bound decode: MLP dominates the prior
    assert fr["mlp"] > 0.5 and fr["attention"] < 0.2


def test_roofline_model_phase_prior_matches_phase_table():
    """The ledger's device-split prior decomposes against the byte
    table `phase_ideal_bytes` gives (the embedding gather belongs to
    no phase: it reads B rows, not the table)."""
    mc = _mc_8b()
    rm = build_roofline(mc, "int8", "int8")
    ph = phase_ideal_bytes(mc, B, AVG_CTX, "int8", "int8")
    total = sum(ph.values())
    fr = rm.phase_fractions(B, B * AVG_CTX)
    for k, v in ph.items():
        assert fr[k] == pytest.approx(v / total, rel=1e-9), k
