"""KV-aware routing vs round-robin: the recorded serving A/B.

The reference's headline routing claim is 3x TTFT / 2x avg latency from
KV-aware routing on prefix-heavy workloads (reference:
docs/architecture.md:73-87). This bench measures OUR analogue on a real
multi-worker serving fleet: coordinator store + TWO jax workers
(publishing KV events) + an HTTP frontend, once with
``--router-mode kv`` and once with ``--router-mode round-robin``,
driven by the multi-turn conversation workload (each user's history
grows turn over turn, so a returning turn's prefix is cached ONLY on
the worker that served the previous turn — KV routing sends the user
back there; round-robin sprays turns across workers and re-prefills
~half the histories from scratch).

Reported per mode: returning-turn TTFT p50/p99 (where routing pays),
first-turn TTFT (sanity: should match across modes), and the
fleet-wide average prefix-hit rate scraped from the metrics service.
Committed results (CPU): benchmarks/results_router_ab.json.

    python benchmarks/router_ab_bench.py            # full A/B (CPU)
    python benchmarks/router_ab_bench.py --users 4 --turns 3   # quicker
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
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from load_gen import Stats, ms, run_multiturn  # noqa: E402

TINY_MODEL = os.path.join(REPO, "tests", "data", "tiny_llama_model")

# big enough that re-prefilling a multi-turn history is clearly
# distinguishable from serving it out of prefix cache on a CPU worker
CONFIG = dict(
    model_type="llama", vocab_size=2048, hidden_size=256,
    intermediate_size=512, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=4, max_position_embeddings=4096,
)
ENGINE = dict(
    random_weights=True, num_blocks=1024, block_size=16, max_batch_size=8,
    decode_steps=4, prefill_chunk_size=512, max_model_len=3072,
    enable_prefix_caching=True,
)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Fleet:
    def __init__(self, tmp: str):
        self.tmp = tmp
        self.procs: list[tuple[subprocess.Popen, str]] = []

    def spawn(self, tag: str, *argv: str) -> subprocess.Popen:
        inherited = os.environ.get("PYTHONPATH", "")
        env = dict(
            os.environ,
            PYTHONPATH=REPO + (os.pathsep + inherited if inherited else ""),
            JAX_PLATFORMS="cpu",
        )
        log = os.path.join(self.tmp, f"{tag}.log")
        fh = open(log, "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli.main", *argv],
            env=env, stdout=fh, stderr=subprocess.STDOUT,
        )
        self.procs.append((proc, log))
        return proc

    def teardown(self) -> None:
        for proc, _ in self.procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc, log in self.procs:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.procs.clear()


def wait_http(url: str, ready, timeout: float = 300.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=2) as r:
                body = r.read()
                if ready(body):
                    return body
        except Exception as exc:
            last = exc
        time.sleep(0.5)
    raise RuntimeError(f"{url} never ready: {last}")


def scrape_metrics(port: int) -> dict[str, float]:
    out: dict[str, float] = {}
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as r:
        for line in r.read().decode().splitlines():
            if line and not line.startswith("#"):
                name = line.split("{")[0].split(" ")[0]
                try:
                    out[name] = float(line.rsplit(" ", 1)[1])
                except ValueError:
                    pass
    return out


def run_mode(mode: str, model_dir: str, engine_args: str,
             users: int, turns: int, think: float) -> dict:
    tmp = tempfile.mkdtemp(prefix=f"dyn_router_ab_{mode}_")
    fleet = Fleet(tmp)
    store_port = free_port()
    http_port = free_port()
    metrics_port = free_port()
    try:
        fleet.spawn("store", "store", "--host", "127.0.0.1",
                    "--port", str(store_port))
        time.sleep(2)
        common = ["--store-host", "127.0.0.1", "--store-port",
                  str(store_port)]
        for i in range(2):
            fleet.spawn(
                f"worker{i}", "run", "--in", "dyn://ab.backend.generate",
                "--out", "jax", "--model-path", model_dir,
                "--model-name", "bench",
                "--extra-engine-args", engine_args, *common,
            )
        fleet.spawn(
            "frontend", "run", "--in", "http",
            "--out", "dyn://ab.backend.generate",
            "--model-path", model_dir, "--model-name", "bench",
            "--http-host", "127.0.0.1", "--http-port", str(http_port),
            "--router-mode", mode, *common,
        )
        fleet.spawn(
            "metrics", "metrics", "--namespace", "ab", "--component",
            "backend", "--port", str(metrics_port), *common,
        )
        wait_http(
            f"http://127.0.0.1:{http_port}/v1/models",
            lambda b: json.loads(b).get("data"),
        )
        # BOTH workers must be routable or the A/B is vacuous
        wait_http(
            f"http://127.0.0.1:{metrics_port}/metrics",
            lambda b: b"llm_workers_reporting 2" in b.replace(b".0", b""),
        )

        class A:
            url = f"http://127.0.0.1:{http_port}"
            model = "bench"
            isl = 40  # words/turn; ~9 tok/word on the test tokenizer
            osl = 24
            request_timeout = 600.0

        stats: Stats = asyncio.run(run_multiturn(A, users, turns, think))
        metrics = scrape_metrics(metrics_port)
        row = {
            "mode": mode,
            "users": users,
            "turns": turns,
            "completed": stats.completed,
            "errors": stats.errors,
            "output_tok_per_s": round(
                stats.tokens / max(stats.elapsed, 1e-9), 2
            ),
            "ttft_first_ms": ms(stats.ttft_first),
            "ttft_later_ms": ms(stats.ttft_later),
            "avg_prefix_hit_rate": round(
                metrics.get("llm_kv_avg_hit_rate", 0.0), 4
            ),
        }
        print(json.dumps(row), flush=True)
        return row
    except Exception:
        for _, log in fleet.procs:
            try:
                with open(log) as f:
                    print(f"--- {log} tail ---\n{f.read()[-2000:]}",
                          file=sys.stderr)
            except OSError:
                pass
        raise
    finally:
        fleet.teardown()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--users", type=int, default=8)
    p.add_argument("--turns", type=int, default=5)
    p.add_argument("--think", type=float, default=1.0)
    p.add_argument("--out", default=os.path.join(
        HERE, "results_router_ab.json"))
    cli = p.parse_args()

    tmp = tempfile.mkdtemp(prefix="dyn_router_ab_model_")
    model_dir = os.path.join(tmp, "model")
    os.makedirs(model_dir, exist_ok=True)
    for f in ("tokenizer.json", "tokenizer_config.json"):
        shutil.copy(os.path.join(TINY_MODEL, f), os.path.join(model_dir, f))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(CONFIG, f)
    engine_args = os.path.join(tmp, "engine.json")
    with open(engine_args, "w") as f:
        json.dump(ENGINE, f)

    try:
        rows = [
            run_mode("kv", model_dir, engine_args,
                     cli.users, cli.turns, cli.think),
            run_mode("round_robin", model_dir, engine_args,
                     cli.users, cli.turns, cli.think),
        ]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    with open(cli.out, "w") as f:
        json.dump({
            "workload": "multiturn",
            "workers": 2,
            "users": cli.users,
            "turns": cli.turns,
            "rows": rows,
        }, f, indent=1)
    kv, rr = rows
    print("\n| mode | later-turn TTFT p50 | p99 | first-turn p50 | "
          "prefix hit |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['mode']} | {r['ttft_later_ms']['p50']} ms "
            f"| {r['ttft_later_ms']['p99']} ms "
            f"| {r['ttft_first_ms']['p50']} ms "
            f"| {r['avg_prefix_hit_rate']} |"
        )
    speedup = (
        rr["ttft_later_ms"]["p50"] / max(1e-9, kv["ttft_later_ms"]["p50"])
    )
    print(f"\nreturning-turn TTFT p50 speedup (kv vs rr): {speedup:.2f}x")


if __name__ == "__main__":
    main()
