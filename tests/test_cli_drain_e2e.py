"""Graceful-drain e2e (ISSUE 20 acceptance, docs/robustness.md
"Graceful drain & rolling restarts"): SIGTERM a serving worker
mid-stream with a healthy peer up — the client's SSE stream must splice
onto the peer byte-identically with zero SSE errors, the worker must
exit 0 within the drain deadline, and the request's autopsy must show
the planned handoff (reason=drain, no synthesized worker_died segment —
the commit log was exact, nothing was lost). Plus the operator path:
``dynamo-tpu drain <worker>`` retires one worker of two through the
worker-control subject and returns once discovery shows it gone."""

import asyncio
import json
import signal
import subprocess
import sys
import time
import urllib.request

from cli_harness import (
    ENV,
    MODEL_DIR,
    CliFleet,
    fetch_autopsy,
    free_port,
    wait_http,
)
from test_cli_failover_e2e import _metric_value


def _instance_keys(store_port: int, namespace: str) -> list[str]:
    """Discovery listing via a short-lived store client (what the
    ``drain`` subcommand itself polls)."""
    from dynamo_tpu.store.client import StoreClient

    async def go():
        client = await StoreClient.connect("127.0.0.1", store_port)
        try:
            entries = await client.kv_get_prefix(f"instances/{namespace}/")
            return sorted(e.key for e in entries)
        finally:
            await client.close()

    return asyncio.run(go())


def test_sigterm_mid_stream_drains_byte_identical():
    """The tentpole proof: a drain is INVISIBLE to the client. Compare
    with test_cli_failover_e2e's SIGKILL twin — there the victim's
    finish is synthesized (worker_died); here the worker hands the
    stream off at a step boundary with an exact commit log and exits 0."""
    store_port = free_port()
    http_port = free_port()
    metrics_port = free_port()
    fleet = CliFleet()
    try:
        fleet.spawn("store", "--host", "127.0.0.1", "--port", str(store_port))
        time.sleep(2)
        common = ["--store-host", "127.0.0.1", "--store-port", str(store_port)]
        # the victim steps slowly (output-neutral injected delay) so the
        # stream outlives the survivor's spawn + registration
        victim = fleet.spawn(
            "run", "--in", "dyn://gd.backend.generate", "--out", "jax",
            "--model-path", MODEL_DIR, *common,
            env={"DYN_FAULTS": "seed=1;engine.step:delay=0.5"},
        )
        fleet.spawn(
            "run", "--in", "http", "--out", "dyn://gd.backend.generate",
            "--model-path", MODEL_DIR, "--http-port", str(http_port),
            *common,
        )
        fleet.spawn(
            "metrics", "--namespace", "gd", "--component", "backend",
            "--port", str(metrics_port), *common,
        )
        wait_http(
            f"http://127.0.0.1:{http_port}/v1/models",
            lambda b: json.loads(b)["data"],
        )
        prompt = "graceful drain byte identity"
        n_tokens = 240  # ≥120 s of stream at the injected 0.5 s/step
        body = json.dumps({
            "model": "tiny_llama_model", "prompt": prompt,
            "max_tokens": n_tokens, "stream": True, "temperature": 0,
            "ext": {"ignore_eos": True},
        }).encode()
        rid = "autopsy-drain-e2e"
        req = urllib.request.Request(
            f"http://127.0.0.1:{http_port}/v1/completions", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": rid},
        )
        resp = urllib.request.urlopen(req, timeout=60)
        first = resp.readline()
        assert first.startswith(b"data:"), first
        # tokens are flowing on the slow victim: bring up the survivor
        fleet.spawn(
            "run", "--in", "dyn://gd.backend.generate", "--out", "jax",
            "--model-path", MODEL_DIR, *common,
        )
        wait_http(
            f"http://127.0.0.1:{metrics_port}/metrics",
            lambda b: b"llm_workers_reporting 2" in b.replace(b".0", b""),
            timeout=120,
        )
        # the planned departure: SIGTERM, not SIGKILL
        victim.send_signal(signal.SIGTERM)
        # drain the stream while the handoff happens underneath it
        lines = [first]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = resp.readline()
            if not line:
                break
            lines.append(line)
        text = b"".join(lines).decode()
        assert "event: error" not in text, text[-2000:]
        assert "[DONE]" in text, text[-2000:]
        # the worker drained and exited CLEANLY within the deadline
        assert victim.wait(timeout=60) == 0
        fleet.forget(victim)
        chunks = [
            json.loads(ln[len("data:"):].strip())
            for ln in text.splitlines()
            if ln.startswith("data:") and "[DONE]" not in ln
        ]
        streamed = "".join(
            c["choices"][0].get("text") or ""
            for c in chunks if c.get("choices")
        )
        finishes = [
            c["choices"][0].get("finish_reason")
            for c in chunks if c.get("choices")
        ]
        assert finishes[-1] == "length", finishes[-5:]
        # byte identity against the no-drain greedy baseline on the peer
        base_body = json.dumps({
            "model": "tiny_llama_model", "prompt": prompt,
            "max_tokens": n_tokens, "temperature": 0,
            "ext": {"ignore_eos": True},
        }).encode()
        base = json.load(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{http_port}/v1/completions", data=base_body,
            headers={"Content-Type": "application/json"},
        ), timeout=180))
        assert base["choices"][0]["finish_reason"] == "length"
        assert streamed == base["choices"][0]["text"]
        # the frontend scored a planned handoff: one ok resume, no abort
        assert _metric_value(
            http_port, "dynamo_midstream_resumes_total", result="ok"
        ) >= 1
        assert _metric_value(http_port, "dynamo_midstream_aborts_total") == 0

        # autopsy: the splice is stamped reason=drain, the handoff event
        # names the departing worker, and — unlike the SIGKILL twin —
        # NOTHING was synthesized: the victim ended its own segment at
        # the step boundary with the commit log exact
        rec = fetch_autopsy(http_port, rid)
        assert "migrated" in rec["flags"], rec["flags"]
        splices = [e for e in rec["events"]
                   if e.get("kind") == "resume_splice"]
        assert splices, rec["events"]
        assert splices[0]["reason"] == "drain"
        assert splices[0]["from_worker"] != splices[0]["to_worker"]
        assert splices[0]["delivered"] >= 1
        handoffs = [e for e in rec["events"]
                    if e.get("kind") == "drain_handoff"]
        assert handoffs, rec["events"]
        assert handoffs[0]["worker"] == splices[0]["from_worker"]
        assert handoffs[0]["delivered"] == splices[0]["delivered"]
        assert not [s for s in rec["segments"]
                    if s["source"] == "worker_died"], rec["segments"]
        # both dials recorded; the survivor's is marked as the resume
        assert len(rec["router"]) >= 2
        assert rec["router"][-1]["resume"] is True
        fleet.assert_alive()
    finally:
        fleet.teardown()


def test_drain_subcommand_retires_one_worker():
    """Operator surface: ``dynamo-tpu drain <worker>`` publishes the
    control call, the worker converges onto the SIGTERM path, drains,
    deregisters, and exits 0 — and the subcommand returns success only
    once discovery shows the instance gone."""
    store_port = free_port()
    fleet = CliFleet()
    try:
        fleet.spawn("store", "--host", "127.0.0.1", "--port", str(store_port))
        time.sleep(2)
        common = ["--store-host", "127.0.0.1", "--store-port", str(store_port)]
        workers = [
            fleet.spawn(
                "run", "--in", "dyn://dd.backend.generate", "--out", "jax",
                "--model-path", MODEL_DIR, *common,
            )
            for _ in range(2)
        ]
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            keys = _instance_keys(store_port, "dd")
            if len(keys) == 2:
                break
            time.sleep(1)
        assert len(keys) == 2, keys
        target_hex = keys[0].rpartition(":")[2]
        out = subprocess.run(
            [sys.executable, "-m", "dynamo_tpu.cli.main", "drain",
             target_hex, "--namespace", "dd", *common],
            env=ENV, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "drained and deregistered" in out.stdout
        # exactly the targeted worker exited, cleanly; its peer serves on
        remaining = _instance_keys(store_port, "dd")
        assert remaining == [k for k in keys if not k.endswith(target_hex)]
        # the subcommand returns once discovery shows the instance gone;
        # the worker's own process exit follows (runtime shutdown,
        # interpreter teardown), so give it a moment
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            exited = [w for w in workers if w.poll() is not None]
            if exited:
                break
            time.sleep(0.2)
        assert len(exited) == 1
        assert exited[0].returncode == 0
        fleet.forget(exited[0])
        fleet.assert_alive()
    finally:
        fleet.teardown()
