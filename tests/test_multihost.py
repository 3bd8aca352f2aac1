"""Multi-host engine bring-up: two real jax.distributed processes
(num_nodes=2), global tp=2 mesh spanning them, leader/follower step
protocol (reference: lib/llm/src/engines.rs:41-58 MultiNodeConfig;
design: dynamo_tpu/parallel/multihost.py)."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_pair(kv_dtype: str) -> dict:
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(rank), coord, kv_dtype],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
        assert all(p.returncode == 0 for p in procs), (
            f"rank0:\n{outs[0][-3000:]}\nrank1:\n{outs[1][-3000:]}"
        )
        result_lines = [
            ln for ln in outs[0].splitlines() if ln.startswith("RESULT ")
        ]
        assert result_lines, outs[0][-3000:]
        result = json.loads(result_lines[0][len("RESULT "):])
        assert len(result["tokens"]) == 6
        # sharded G2 offload: shards were pumped into the per-process
        # pool, and the repeat prompt (onboarding through the mirrored
        # tier after device eviction) continues identically
        assert result["offloaded"] > 0, result
        assert result["repeat_matches"], result
        # disagg KV export/import over the cross-process-sharded cache:
        # whole blocks assembled on the leader, re-imported into the
        # lockstep shard pools (engine.{_export,_import}_blocks)
        assert result["export_ok"], result
        assert result["imported"] >= 4, result
        # multimodal embed-injection prefill over the step broadcast
        # (KIND_STEP_MM): the follower mirrored the mm step variant
        assert result["mm_ok"], result
        return result
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_two_process_engine_serves_request():
    _run_pair("float32")


def test_two_process_engine_int8_kv():
    """The same 2-process protocol over an int8 (values, scales) cache:
    quantized writes inside the lockstep steps, mirrored offload /
    export / import dequantizing to the bf16 wire at the block-copy
    boundary (mirror_gather/_scatter tuple dispatch) — the combination
    the 70B ladder budget assumes (docs/multihost.md)."""
    _run_pair("int8")


def test_hash_halves_survive_broadcast_canonicalization():
    """xxh3 hashes are 64-bit; jax canonicalizes uint64 -> uint32 on the
    broadcast path (x64 off), so they travel as two uint32 halves."""
    from dynamo_tpu.parallel.multihost import _join_hashes, _split_hashes

    hashes = [0, 1, 2**32 - 1, 2**32, 2**40 + 5, 2**63 + 17, 2**64 - 1]
    halves = _split_hashes(hashes)
    assert halves.dtype == __import__("numpy").uint32
    assert halves.shape == (2, len(hashes))
    assert _join_hashes(halves) == hashes
    # and the canonicalization that motivated this: with x64 disabled
    # (this repo's default), a uint64 round trip through jnp would NOT
    # have survived
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not jax.config.jax_enable_x64:
        truncated = np.asarray(jnp.asarray(np.asarray([2**40 + 5], np.uint64)))
        assert int(truncated[0]) != 2**40 + 5  # the bug this guards against
