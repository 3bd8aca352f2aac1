"""CPU rehearsal of ``perf/run.py`` at a tiny preset.

The script has no CPU switch: the rehearsal replaces module attributes
HERE (the platform it requires, where cells, mixes and limits are found)
and then drives ``main()`` as the command line would — server child,
ramp, window, drain, probe requests, reference child, trace reduction —
and reads the result line."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.reference import check  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402

CELLS = {"tiny.chat": "tiny-chat", "tiny.closed": "tiny-closed",
         "tiny.sessions": "tiny-sessions"}


def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    real = {w["traffic"]: w["name"] for w in bench["workloads"]}
    stand_in = {real["chat"]: "tiny.chat", real["decode-heavy"]: "tiny.closed",
                real["sessions"]: "tiny.sessions"}
    bench["configs"] = [{"name": "tiny-llama", "file": os.path.relpath(
        os.path.join(DATA, "tiny-llama.json"), REPO)}]
    bench["workloads"] = [{"name": n, "config": "tiny-llama", "traffic": t,
                           "chips": 1} for n, t in CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [stand_in[w] for w in m["workloads"]]
    return bench


def tiny_mix(name: str) -> dict:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return dict(json.load(f), name=name)


@pytest.fixture
def rehearsal(monkeypatch):
    monkeypatch.setattr(perf_run, "REQUIRE_PLATFORM", "cpu")
    monkeypatch.setattr(perf_run, "load_benchmark", tiny_benchmark)
    monkeypatch.setattr(check, "POSITIONS", 48)
    monkeypatch.setattr(perf_run, "TRACE_AT_S", 0.5)
    monkeypatch.setattr(perf_run, "TRACE_MS", 600)
    monkeypatch.setattr(sched, "load_mix", tiny_mix)
    monkeypatch.setattr(check, "load_limits",
                        lambda cell: {"logprob_err_mean": 0.05})


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("cell,trace", [
    ("tiny.chat", 0), ("tiny.closed", 0), ("tiny.sessions", 1)])
def test_rehearsal_prints_the_contract_line(rehearsal, capsys, cell, trace):
    rc = perf_run.main(["--workload", cell, "--seed", str(2**31 + 77),
                        "--seconds", "8", "--trace", str(trace)])
    out = capsys.readouterr()
    lines = _lines(out.out)
    assert rc == 0, out.err[-3000:]
    result = lines[-1]
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    # the schedule printed is the mix file's, whatever the seed
    assert phases["schedule"]["digest"] == sched.digest(
        sched.build(tiny_mix(CELLS[cell]), 8.0))
    assert phases["window"]["failed"] == 0
    assert phases["window"]["host_stall_max_s"] >= 0
    # the probe is the cell's own: a row per client / live session / as
    # many as the open loop keeps running, every row read by the reference
    waves = check.probe_waves(tiny_mix(CELLS[cell]))
    compared = phases["outputs"]["compared"]
    assert compared["rows_sent"] == compared["rows_compared"] == len(waves[0]) \
        == {"tiny.chat": 8, "tiny.closed": 4, "tiny.sessions": 4}[cell]
    assert compared["positions"] == sum(j["out"] for w in waves for j in w)
    assert len(phases["window"]["ttft_ms_deciles"]) == 11
    bench = tiny_benchmark()
    if trace:
        assert result["metrics"]["prefix_hit_share.sessions"]["value"] > 0
        assert "ttft_p50_ms.sessions" in result["metrics"]
    else:
        want = {m["name"] for m in bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
        want.discard("ttft_p85_ms")  # too few samples at this size: left out
        assert want <= set(result["metrics"])
        assert result["metrics"]["setup_s"]["value"] > 0
    assert phases["shutdown"]["exit_code"] == 0 and not phases["shutdown"]["killed"]


def test_unmodified_script_refuses_a_machine_without_a_tpu():
    """No accelerator: non-zero exit, no result line, no server started."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perf", "run.py"), "--workload",
         "mistral-7b.chat", "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = _lines(proc.stdout)
    assert proc.returncode != 0
    assert lines[-1]["phase"] == "error"
    assert not any(ln.get("phase") == "engine_up" for ln in lines)
