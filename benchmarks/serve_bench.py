"""HTTP-level serving benchmark: real frontend+worker, concurrency sweep.

The committed, reproducible version of the reference's benchmark
methodology (reference: examples/llm/benchmarks/README.md:28-100 —
genai-perf closed-loop concurrency sweep at fixed ISL/OSL, recording
output tok/s and p50 TTFT). Spawns the actual serving stack
(``dynamo-tpu run --in http --out jax --static``) as a subprocess,
drives it with benchmarks/load_gen.py's closed loop, and emits one JSON
line per concurrency plus a markdown table to stdout.

Modes:
  --mode cpu   tiny model, CPU backend: CI smoke / methodology check
  --mode tpu   flagship 8B geometry, int8 weights, real chip

Results land in benchmarks/results_<mode>.json. Only the CPU
methodology check is committed; nothing is measured on the attached
chip yet (PERF.md).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from load_gen import (  # noqa: E402
    Stats,
    ms,
    one_request,
    run_closed_loop,
    run_multiturn,
)

TINY_MODEL = os.path.join(REPO, "tests", "data", "tiny_llama_model")

SHAPES = {
    "cpu": dict(
        config=dict(
            model_type="llama", vocab_size=2048, hidden_size=128,
            intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=2048,
        ),
        engine=dict(random_weights=True, num_blocks=512, block_size=16,
                    max_batch_size=16, decode_steps=4,
                    prefill_chunk_size=256),
        isl=64, osl=32, duration=15.0, concurrency=[1, 2, 4, 8],
    ),
    "tpu": dict(
        # DeepSeek-R1-Distill-Llama-8B geometry (BASELINE.md config 1);
        # int8 weights fit the single 16 GB chip
        config=dict(
            model_type="llama", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_hidden_layers=32,
            num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192,
        ),
        engine=dict(random_weights=True, quantization="int8",
                    # max_batch_size=64 with the mid decode bucket for
                    # lower concurrencies; not measured on the attached
                    # chip
                    block_size=128, max_batch_size=64, decode_steps=32,
                    hbm_utilization=0.7, prefill_chunk_size=1024,
                    max_model_len=320),
        # isl is in WORDS (load_gen builds text); the test tokenizer
        # expands ~9 tokens/word, so 14 words ≈ 130 prompt tokens —
        # matching bench.py's 128/128 token workload under
        # max_model_len=320
        isl=14, osl=128, duration=90.0, concurrency=[1, 4, 16, 32],
    ),
    # the REFERENCE methodology (examples/llm/benchmarks/README.md:28-100
    # + perf.sh): ISL 3000 tokens / OSL 150, concurrency 1 -> 256.
    # Real block-table widths, real HBM pressure: one 16 GB chip's KV
    # budget holds only a handful of 3.2k-token contexts resident, so
    # high concurrencies measure the scheduler's admission/queueing
    # behavior under pressure — exactly what the r3 sweep (130-token
    # prompts, max_model_len 320) never exercised.
    "tpu_ref": dict(
        config=dict(
            model_type="llama", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_hidden_layers=32,
            num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192,
        ),
        # hbm_utilization stays 0.7 (headroom against memory pressure
        # shortly after startup); not retuned on the attached chip.
        engine=dict(random_weights=True, quantization="int8",
                    # int8 KV with the mid-chunk sync skip; not measured
                    # on the attached chip
                    kv_cache_dtype="int8",
                    block_size=128, max_batch_size=32, decode_steps=32,
                    hbm_utilization=0.7, prefill_chunk_size=1024,
                    max_model_len=3328),
        # ~9 tokens/word with the test tokenizer: 334 words ≈ 3000
        # prompt tokens
        isl=334, osl=150, duration=120.0, concurrency=[1, 4, 16, 64, 256],
    ),
    # KV-offload A/B on the reference's multi-turn recipe
    # (docs/architecture.md:91-96: multi-turn conversations x users,
    # system-memory KV tier measured as TTFT on RETURNING turns vs
    # prefix-caching-only). G1 is deliberately constrained
    # (num_blocks) so conversations evict between turns; variant B's
    # G2 host tier restores their blocks instead of recomputing.
    "tpu_offload": dict(
        config=dict(
            model_type="llama", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_hidden_layers=32,
            num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192,
        ),
        engine=dict(random_weights=True, quantization="int8",
                    block_size=128, max_batch_size=32, decode_steps=32,
                    prefill_chunk_size=1024, max_model_len=2304,
                    num_blocks=192),
        # overlay: the G2 tier, FORCED past the restore-vs-recompute
        # probe — this mode exists to measure the tier itself (the
        # gate would disable it on a slow host link)
        engine_b=dict(host_kv_blocks=768, kv_offload_force=True),
        # ~30 words x ~9 tok/word = ~270 prompt tokens per turn + 64
        # generated: 6 turns end near 2000 tokens of history
        workload="multiturn",
        isl=30, osl=64, users=24, turns=6, think=8.0,
        duration=0.0, concurrency=[],
    ),
    # CI smoke of the same machinery on CPU (tiny model, no pressure
    # claims — just that both variants serve and the report emits)
    "cpu_offload": dict(
        config=dict(
            model_type="llama", vocab_size=2048, hidden_size=128,
            intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=2048,
        ),
        engine=dict(random_weights=True, num_blocks=64, block_size=16,
                    max_batch_size=8, decode_steps=4,
                    prefill_chunk_size=256, max_model_len=512),
        engine_b=dict(host_kv_blocks=256, kv_offload_force=True),
        workload="multiturn",
        isl=4, osl=8, users=4, turns=3, think=0.2,
        duration=0.0, concurrency=[],
    ),
}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_model_dir(tmp: str, shape: dict) -> str:
    """Model dir = tiny test tokenizer + the benchmark shape's config
    (random weights: throughput is weight-agnostic)."""
    d = os.path.join(tmp, "model")
    os.makedirs(d, exist_ok=True)
    for f in ("tokenizer.json", "tokenizer_config.json"):
        shutil.copy(os.path.join(TINY_MODEL, f), os.path.join(d, f))
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(shape["config"], f)
    return d


def wait_ready(url: str, timeout: float, proc=None) -> None:
    """Poll /v1/models until it lists a model. Only "not listening
    yet" is polled through; a dead child or the deadline raises."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"server exited with code {proc.returncode} before ready"
            )
        try:
            with urllib.request.urlopen(f"{url}/v1/models", timeout=2) as r:
                if json.load(r).get("data"):
                    return
        except (urllib.error.URLError, OSError, ValueError):
            pass  # connection refused / reset / half-written reply
        time.sleep(1.0)
    raise RuntimeError(f"server at {url} not ready after {timeout}s")


async def drive(args, shape: dict) -> list[dict]:
    import aiohttp

    results = []
    for c in shape["concurrency"]:
        # untimed warmup at this concurrency: compiles must not land
        # inside the measured window
        warm = Stats()
        async with aiohttp.ClientSession() as session:
            await asyncio.gather(
                *[one_request(session, args, warm) for _ in range(c)]
            )
        stats = await run_closed_loop(args, c)
        if stats.completed and not stats.tokens:
            raise RuntimeError(
                f"concurrency {c}: {stats.completed} requests completed "
                "with ZERO output tokens — the server is rejecting the "
                "workload (prompt over max_model_len?); results would "
                "be garbage"
            )
        row = {
            "concurrency": c,
            "completed": stats.completed,
            "errors": stats.errors,
            "output_tok_per_s": round(stats.tokens / max(stats.elapsed, 1e-9), 2),
            "ttft_ms": ms(stats.ttft),
            "e2e_ms": ms(stats.e2e),
        }
        print(json.dumps(row), flush=True)
        results.append(row)
    return results


def launch_server(
    mode: str, engine: dict, model_dir: str, tmp: str, tag: str,
    ready_timeout: float,
):
    """Start the real serving stack for one engine config; returns
    (proc, url, log_fh). Raises with the log tail if it never comes up."""
    engine_args = os.path.join(tmp, f"engine_{tag}.json")
    with open(engine_args, "w") as f:
        json.dump(engine, f)
    port = free_port()
    # the child inherits this parent's environment — the checkout on
    # the import path, and the ONE compile-cache rule
    # (utils/jaxtools.py), so both variants' servers share one cache
    from dynamo_tpu.utils.jaxtools import compile_cache_dir

    inherited = os.environ.get("PYTHONPATH", "")
    env = dict(
        os.environ,
        PYTHONPATH=REPO + (os.pathsep + inherited if inherited else ""),
    )
    if compile_cache_dir() is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    if mode.startswith("cpu"):
        env["JAX_PLATFORMS"] = "cpu"
    server_log = os.path.join(tmp, f"server_{tag}.log")
    log_fh = open(server_log, "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dynamo_tpu.cli.main", "run",
            "--in", "http", "--out", "jax", "--static",
            "--model-path", model_dir, "--model-name", "bench",
            "--http-host", "127.0.0.1", "--http-port", str(port),
            "--extra-engine-args", engine_args,
        ],
        env=env,
        stdout=log_fh,
        stderr=subprocess.STDOUT,
    )
    url = f"http://127.0.0.1:{port}"
    try:
        wait_ready(url, ready_timeout, proc)
    except RuntimeError:
        with open(server_log) as f:
            print("--- server log tail ---\n" + f.read()[-4000:],
                  file=sys.stderr)
        stop_server(proc, log_fh)
        raise
    return proc, url, log_fh


def stop_server(proc, log_fh) -> None:
    """SIGTERM, then SIGKILL — and WAIT either way: a chip belongs to
    one process, and the next variant's server must not start while a
    killed child still holds it."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    log_fh.close()


def bench_args(url: str, shape: dict):
    class A:
        pass

    a = A()
    a.url = url
    a.model = "bench"
    a.isl = shape["isl"]
    a.osl = shape["osl"]
    a.duration = shape["duration"]
    a.request_timeout = 600.0
    return a


def drive_multiturn(cli, shape: dict, model_dir: str, tmp: str) -> list[dict]:
    """A/B the multi-turn conversation workload: variant 'prefix_only'
    (base engine) vs 'g2_host' (base + engine_b overlay, the host KV
    tier). Each variant gets its own server; the headline is the
    RETURNING-turn TTFT delta (reference: docs/architecture.md:91-96,
    +40% TTFT from the system-memory tier)."""
    variants = [
        ("prefix_only", dict(shape["engine"])),
        ("g2_host", dict(shape["engine"], **shape["engine_b"])),
    ]
    rows = []
    for tag, engine in variants:
        proc, url, log_fh = launch_server(
            cli.mode, engine, model_dir, tmp, tag, cli.ready_timeout
        )
        try:
            a = bench_args(url, shape)
            # warmup: one short conversation compiles every shape
            warm_stats = asyncio.run(
                run_multiturn(a, users=1, turns=2, think=0.0)
            )
            if warm_stats.errors:
                raise RuntimeError(f"{tag}: warmup conversation errored")
            stats = asyncio.run(
                run_multiturn(
                    a, users=shape["users"], turns=shape["turns"],
                    think=shape["think"],
                )
            )
            row = {
                "variant": tag,
                "users": shape["users"],
                "turns": shape["turns"],
                "completed": stats.completed,
                "errors": stats.errors,
                "output_tok_per_s": round(
                    stats.tokens / max(stats.elapsed, 1e-9), 2
                ),
                "ttft_first_ms": ms(stats.ttft_first),
                "ttft_later_ms": ms(stats.ttft_later),
                "e2e_ms": ms(stats.e2e),
            }
            print(json.dumps(row), flush=True)
            rows.append(row)
        finally:
            stop_server(proc, log_fh)
    return rows


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--mode",
        choices=["cpu", "tpu", "tpu_ref", "tpu_offload", "cpu_offload"],
        default="cpu",
    )
    p.add_argument("--duration", type=float, default=None)
    p.add_argument("--concurrency", default=None, help="comma list override")
    p.add_argument("--users", type=int, default=None)
    p.add_argument("--turns", type=int, default=None)
    p.add_argument("--keep-logs", default=None,
                   help="copy server logs to this directory instead of "
                        "deleting them with the tmp dir (stall forensics)")
    p.add_argument("--engine-override", default=None,
                   help="JSON dict merged over the shape's engine config "
                        "(e.g. '{\"mixed_wide_max_running\": 32}')")
    p.add_argument("--ready-timeout", type=float, default=1200.0)
    p.add_argument("--out", default=None, help="results JSON path")
    cli = p.parse_args()

    shape = SHAPES[cli.mode]
    if cli.duration:
        shape = dict(shape, duration=cli.duration)
    if cli.concurrency:
        shape = dict(
            shape, concurrency=[int(x) for x in cli.concurrency.split(",")]
        )
    if cli.users:
        shape = dict(shape, users=cli.users)
    if cli.turns:
        shape = dict(shape, turns=cli.turns)
    if cli.engine_override:
        shape = dict(
            shape,
            engine=dict(shape["engine"], **json.loads(cli.engine_override)),
        )

    tmp = tempfile.mkdtemp(prefix="dyn_serve_bench_")
    model_dir = make_model_dir(tmp, shape)
    rows: list[dict] = []
    try:
        if shape.get("workload") == "multiturn":
            rows = drive_multiturn(cli, shape, model_dir, tmp)
            out_path = cli.out or os.path.join(
                HERE, f"results_{cli.mode}.json"
            )
            with open(out_path, "w") as f:
                json.dump(
                    {
                        "mode": cli.mode,
                        "workload": "multiturn",
                        "isl": shape["isl"],
                        "osl": shape["osl"],
                        "users": shape["users"],
                        "turns": shape["turns"],
                        "think_s": shape["think"],
                        "engine": shape["engine"],
                        "engine_b": shape["engine_b"],
                        "model_geometry": shape["config"],
                        "rows": rows,
                    },
                    f,
                    indent=1,
                )
            print("\n| variant | out tok/s | turn-1 TTFT p50 | "
                  "returning-turn TTFT p50 | p99 |")
            print("|---|---|---|---|---|")
            for r in rows:
                print(
                    f"| {r['variant']} | {r['output_tok_per_s']} "
                    f"| {r['ttft_first_ms']['p50']} "
                    f"| {r['ttft_later_ms']['p50']} "
                    f"| {r['ttft_later_ms']['p99']} |"
                )
            return

        proc, url, log_fh = launch_server(
            cli.mode, shape["engine"], model_dir, tmp, "main",
            cli.ready_timeout,
        )
        try:
            rows = asyncio.run(drive(bench_args(url, shape), shape))
        finally:
            stop_server(proc, log_fh)
        out_path = cli.out or os.path.join(HERE, f"results_{cli.mode}.json")
        with open(out_path, "w") as f:
            json.dump(
                {
                    "mode": cli.mode,
                    "isl": shape["isl"],
                    "osl": shape["osl"],
                    "duration_s": shape["duration"],
                    "engine": shape["engine"],
                    "model_geometry": shape["config"],
                    "rows": rows,
                },
                f,
                indent=1,
            )
        # markdown table
        print("\n| conc | out tok/s | p50 TTFT ms | p99 TTFT ms | p50 e2e ms |")
        print("|---|---|---|---|---|")
        for r in rows:
            print(
                f"| {r['concurrency']} | {r['output_tok_per_s']} "
                f"| {r['ttft_ms']['p50']} | {r['ttft_ms']['p99']} "
                f"| {r['e2e_ms']['p50']} |"
            )
    finally:
        if cli.keep_logs:
            os.makedirs(cli.keep_logs, exist_ok=True)
            for f in os.listdir(tmp):
                if f.startswith("server") and f.endswith(".log"):
                    shutil.copy(os.path.join(tmp, f), cli.keep_logs)
        shutil.rmtree(tmp, ignore_errors=True)
    failed = sum(r.get("errors", 0) for r in rows)
    if failed:
        # the table above is still printed, but a run with failed
        # requests is not a result
        print(f"{failed} requests failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
