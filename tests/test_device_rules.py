"""The rules PR 21 set for running on a real chip: ONE compile-cache
rule, no fallback that hides the device, errors instead of assumed
peaks and capacities."""

import json
import logging
import os
import re
import subprocess
import sys
import types

import jax
import pytest

from dynamo_tpu.utils import jaxtools

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls without applying them (the test
    process keeps the cache conftest configured)."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.append((k, v))
    )
    return calls


def test_cache_dir_from_environment_is_used_and_no_other_is_set(
    monkeypatch, config_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.delenv("DYN_COMPILE_CACHE", raising=False)
    assert jaxtools.enable_compile_cache() == "/some/dir"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert not [c for c in config_updates
                if c[0] == "jax_compilation_cache_dir"]


def test_cache_dir_defaults_to_the_checkout_and_is_exported(
    monkeypatch, config_updates
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.delenv("DYN_COMPILE_CACHE", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jaxtools.enable_compile_cache() == want
    # children inherit the choice through the environment
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert ("jax_compilation_cache_dir", want) in config_updates
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)  # for teardown


@pytest.mark.parametrize("knob,expect_off", [("0", True), ("/elsewhere", False)])
def test_dyn_compile_cache_only_turns_the_cache_off(
    knob, expect_off, monkeypatch, config_updates
):
    """``DYN_COMPILE_CACHE=<dir>`` no longer relocates the cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv("DYN_COMPILE_CACHE", knob)
    monkeypatch.delenv("JAX_ENABLE_COMPILATION_CACHE", raising=False)
    got = jaxtools.enable_compile_cache()
    assert got == (None if expect_off else "/some/dir")
    assert not [c for c in config_updates
                if c[0] == "jax_compilation_cache_dir"]
    # "off" holds although JAX reads JAX_COMPILATION_CACHE_DIR itself:
    # JAX is told, and so are the children
    told = ("jax_enable_compilation_cache", False) in config_updates
    assert told == expect_off
    assert (os.environ.get("JAX_ENABLE_COMPILATION_CACHE") == "0") == expect_off


def test_cache_off_really_writes_nothing(tmp_path):
    """In a fresh process with ``JAX_COMPILATION_CACHE_DIR`` set, the
    knob at 0 leaves the directory empty after a compile."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from dynamo_tpu.utils.jaxtools import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64))).block_until_ready()\n"
    )
    for knob, expect_entries in (("0", False), ("", True)):
        d = tmp_path / f"cache{knob or 'on'}"
        env = dict(
            os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
            JAX_COMPILATION_CACHE_DIR=str(d), DYN_COMPILE_CACHE=knob,
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        )
        env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == ("None" if knob == "0" else str(d))
        assert (d.exists() and any(d.iterdir())) == expect_entries


def test_one_place_in_the_tree_sets_the_cache_directory():
    hits = []
    for root in ("dynamo_tpu", "perf", "tests", "examples"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits.append(os.path.join(REPO, "chip_smoke.py"))
    setters = [
        os.path.relpath(p, REPO) for p in hits
        if re.search(r'update\(\s*"jax_compilation_cache_dir"',
                     open(p).read())
    ]
    assert setters == ["dynamo_tpu/utils/jaxtools.py"]


# ---------------------------------------------------------------------------
# peaks and capacities are read, not assumed
# ---------------------------------------------------------------------------


def _dev(platform, kind, stats="absent"):
    d = types.SimpleNamespace(platform=platform, device_kind=kind)
    d.memory_stats = lambda: None if stats == "absent" else stats
    return d


def test_device_peaks_known_kind_and_named_cpu_default():
    from dynamo_tpu.telemetry.hbm import (
        CPU_DEFAULT_KIND, DEVICE_PEAKS, device_peaks,
    )

    v5e = device_peaks(_dev("tpu", "TPU v5 lite"))
    assert (v5e.hbm_bytes_per_s, v5e.bf16_flops_per_s, v5e.hbm_bytes) == (
        819e9, 197e12, 16e9
    )
    # CPU test backends keep the v5e row as a default that is NAMED
    assert device_peaks(_dev("cpu", "cpu")) is DEVICE_PEAKS[CPU_DEFAULT_KIND]


@pytest.mark.parametrize(
    "platform,kind", [("tpu", "TPU v9 mega"), ("gpu", "NVIDIA H100")]
)
def test_device_peaks_unknown_accelerator_is_an_error(platform, kind):
    from dynamo_tpu.telemetry.hbm import device_peaks

    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks(_dev(platform, kind))


def _sizing_engine():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    stub = types.SimpleNamespace(
        model_config=ModelConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=8192,
        ),
        config=EngineConfig(block_size=128, max_model_len=4096),
    )
    return lambda devices: JaxEngine._auto_num_blocks(stub, devices)


def test_auto_num_blocks_tpu_without_memory_stats_is_an_error():
    with pytest.raises(RuntimeError, match="no memory_stats"):
        _sizing_engine()([_dev("tpu", "TPU v5 lite")])


def test_auto_num_blocks_reads_free_hbm_and_keeps_cpu_pool():
    size = _sizing_engine()
    # the attached v5e after the int8 8B weights loaded (chip run, PR 21)
    stats = {"bytes_limit": 16909336064, "bytes_in_use": 8100000000}
    n = size([_dev("tpu", "TPU v5 lite", stats)])
    block_bytes = 2 * 32 * 128 * 8 * 128 * 2  # K+V, bf16
    assert 64 < n < (stats["bytes_limit"] - stats["bytes_in_use"]) // block_bytes
    assert size([_dev("cpu", "cpu")]) == 512  # fixed pool, never sized


# ---------------------------------------------------------------------------
# nothing hides the device
# ---------------------------------------------------------------------------


def test_cpu_fallback_warns_once_unless_cpu_was_asked_for(monkeypatch, caplog):
    log = logging.getLogger("test_device_rules")
    monkeypatch.setattr(jaxtools, "_cpu_warned", False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not jaxtools.warn_if_cpu_fallback(log, "engine 'x'")
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.delenv("DYN_JAX_PLATFORM", raising=False)
    with caplog.at_level(logging.WARNING, logger="test_device_rules"):
        assert jaxtools.warn_if_cpu_fallback(log, "engine 'x'")
        assert not jaxtools.warn_if_cpu_fallback(log, "engine 'x'")  # once
    assert len(caplog.records) == 1
    assert "CPU backend" in caplog.records[0].getMessage()


async def test_engine_counts_the_mosaic_kernels_of_its_lowered_step(monkeypatch):
    """"No kernel ran interpreted" is read off the lowered first step,
    not re-derived from the platform: with Pallas forced on the CPU the
    kernels run interpreted, lower to plain HLO, and the count is 0."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    monkeypatch.setenv("DYN_MATMUL_IMPL", "pallas")
    mc = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )
    engine = await JaxEngine.launch(
        EngineConfig(
            model_path="", model_name="dev", random_weights=True,
            quantization="int8", num_blocks=32, block_size=8,
            max_batch_size=2, max_model_len=64, prefill_chunk_size=32,
            prewarm=True,
        ),
        model_config=mc,
    )
    try:
        dev = engine.device_report
        assert dev["platform"] == "cpu" and dev["matmul_pallas_active"]
        assert dev["mosaic_calls_in_step"] == 0
        assert dev["chip_nodes"] == []  # no TPU device node is held here
        assert "kernels_interpreted" not in dev
    finally:
        await engine.shutdown()
