"""CPU rehearsal of ``chip_smoke.py`` at a tiny size.

The script has no switch that relaxes what it requires of the device;
the rehearsal replaces its module-level expectations and sizes HERE, in
the test, then drives ``main()`` exactly as the command line would: the
kernel check child (Pallas interpreted), the CLI server child, the
requests, SIGTERM — and reads the last line it printed."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(
    EXPECT={
        "platform": "cpu", "attn_pallas_active": False,
        "matmul_pallas_active": False, "mosaic_in_step": False,
        "distinct_chips": False,
    },
    GEOMETRY=dict(
        vocab_size=2048, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=1024,
    ),
    ENGINE=dict(
        random_weights=True, seed=0, max_model_len=512, num_blocks=128,
        block_size=16, max_batch_size=8, prefill_chunk_size=128,
    ),
    LONG_PROMPT_TOKENS=300,
    KERNEL_SPEC=dict(
        D=64, F=128, V=768, H=4, Hk=2, Dh=128, block_size=16, m=8,
        m_large=96, ctx=[5, 33, 70], prefill=[20, 32], seed=0,
    ),
    READY_TIMEOUT_S=240.0,
    REPLICA_PROMPT_TOKENS=(192, 32, 100),
)


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


def test_rehearsal_serves_and_prints_the_contract_line(monkeypatch, capsys):
    for name, value in TINY.items():
        monkeypatch.setattr(chip_smoke, name, value)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    rc = chip_smoke.main()
    lines = _lines(capsys.readouterr().out)
    assert rc == 0, lines[-1]
    # the LAST line is the contract line and holds nothing else
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert phases["kernels_vs_reference"]["ok"]
    assert phases["kernels_vs_reference"]["interpreted"]  # CPU rehearsal
    assert len(phases["kernels_vs_reference"]["kernels"]) == 15  # 3 stacked
    up = phases["engine_up"]
    assert up["models"] == ["smoke"]
    assert up["device"]["platform"] == "cpu"
    assert up["compile_cache"]["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    reqs = phases["requests"]["requests"]
    assert len(reqs) == 13
    assert all(r["completion_tokens"] == chip_smoke.MAX_TOKENS
               for r in reqs.values())
    assert any(r["prompt_tokens"] >= 300 for r in reqs.values())
    assert phases["after_serving"]["compile_fence"]["mode"] == "record"
    assert phases["shutdown"] == {
        "phase": "shutdown", "tag": "server", "exit_code": 0,
        "killed": False, "shutdown_s": phases["shutdown"]["shutdown_s"],
    }


def test_unmodified_script_refuses_a_machine_without_a_tpu():
    """As the driver runs it where no chip is attached: non-zero exit,
    ``"ok": false`` last, and no server was ever started."""
    env = {k: v for k, v in os.environ.items() if k != "DYN_JAX_PLATFORM"}
    env["JAX_PLATFORMS"] = "cpu"  # what JAX falls back to without a chip
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    lines = _lines(proc.stdout)
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert "platform" in lines[-1]["error"]
    assert not any(ln.get("phase") == "engine_up" for ln in lines)


@pytest.mark.parametrize("n,start", [(24, 0), (1500, 1000)])
def test_prompt_words_are_exact_in_vocabulary_tokens(n, start):
    text = chip_smoke.words(n, start, 128256)
    ids = [int(w[1:]) for w in text.split()]
    assert len(ids) == n
    assert all(5 <= i < 128256 for i in ids)


def test_replicas_rehearsal_at_the_product_lease_defaults(
    monkeypatch, capsys, tmp_path
):
    """store + frontend + four workers started together, nothing about
    the lease overridden: everybody is still up after the requests and
    nobody logged a late renewal."""
    for name, value in TINY.items():
        monkeypatch.setattr(chip_smoke, name, value)
    for knob in ("DYN_LEASE_TTL_S", "DYN_LEASE_KEEPALIVE_S"):
        monkeypatch.delenv(knob, raising=False)
    chip_smoke.replicas_phase(str(tmp_path))
    lines = _lines(capsys.readouterr().out)
    rep = next(ln for ln in lines if ln.get("phase") == "replicas")
    assert rep["lease_remarks"] == []
    assert [w["device"]["visible_chips"] for w in rep["workers"]] == [
        "0", "1", "2", "3"]
    routes = rep["router_decisions"]
    assert len(routes) == 12
    assert len({r["worker"] for r in routes}) >= 2
    # the three that follow the first shared-prefix request find its blocks
    assert all(r["overlap_blocks"] > 0 for r in routes[1:4])
    assert all(r["worker"] == routes[0]["worker"] for r in routes[1:4])
    stopped = {ln["tag"]: ln for ln in lines if ln.get("phase") == "shutdown"}
    assert all(stopped[f"worker{i}"]["exit_code"] == 0 for i in range(4))
    assert stopped["frontend"]["exit_code"] == 0


@pytest.mark.parametrize("held,ok", [
    ([["/dev/vfio/0"], ["/dev/vfio/1"], ["/dev/vfio/2"], ["/dev/vfio/3"]], True),
    ([["/dev/vfio/0"], ["/dev/vfio/0"], ["/dev/vfio/2"], ["/dev/vfio/3"]], False),
    ([["/dev/vfio/0"], [], ["/dev/vfio/2"], ["/dev/vfio/3"]], False),
])
def test_workers_must_hold_chips_of_their_own(held, ok):
    devices = [{"chip_nodes": h, "visible_chips": str(i)}
               for i, h in enumerate(held)]
    if ok:
        chip_smoke.check_distinct_chips(devices)
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="share a chip"):
            chip_smoke.check_distinct_chips(devices)


def test_mosaic_check_reads_the_lowered_step_not_the_platform():
    """A TPU engine whose step holds no Mosaic kernel fails the check."""
    report = {"platform": "tpu", "attn_pallas_active": True,
              "matmul_pallas_active": True, "mosaic_calls_in_step": 0}
    with pytest.raises(chip_smoke.SmokeFailure, match="mosaic_in_step"):
        chip_smoke.check_device(report, chip_smoke.ONE_CHIP_CHECKS, "x")
    chip_smoke.check_device(
        {**report, "mosaic_calls_in_step": 7}, chip_smoke.ONE_CHIP_CHECKS, "x"
    )
