"""The system under test as one child process, and what is read from it.

JAX-free: a chip belongs to one process, and that process is the server
child (``python -m dynamo_tpu.cli.main run --in http --out jax --static``,
the product's normal entry point, compile fence armed). Process handling
is copied from ``benchmarks/serve_bench.py`` / ``chip_smoke.py`` (sound
after PR 21); the model directory with the word-level tokenizer is
``chip_smoke.py``'s, with every id a plain word so that the text of an
answer names exactly the ids that were sampled.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Everything a run leaves behind lives here (git-ignored): the compile
# cache at a FIXED path (the path is part of the cache key), the model
# directory, server logs, profiler traces.
WORK = os.path.join(ROOT, ".perf_work")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

MODEL_NAME = "bench"
# keys of a configuration file that are the benchmark's, not the model's
NOT_HF_KEYS = ("source", "reduced", "assumed", "deployment", "serving")
READY_TIMEOUT_S = 1100.0  # a first run in a checkout compiles everything


def hf_config(config: dict) -> dict:
    """The published ``config.json`` keys of a configuration file."""
    return {k: v for k, v in config.items() if k not in NOT_HF_KEYS}


class BenchFailure(RuntimeError):
    """The run cannot give a result: no result line, non-zero exit."""


def accelerator_nodes() -> list[str]:
    """TPU device nodes of this machine (v5e hosts: /dev/vfio/<n>; older
    ones /dev/accel<n>) — read without touching JAX, so that a machine
    with no chip is refused before any server starts."""
    return sorted(glob.glob("/dev/vfio/[0-9]*") + glob.glob("/dev/accel[0-9]*"))


def child_env(**extra: str) -> dict:
    """Environment of every child: this checkout on the import path and
    the compile cache INSIDE the checkout, whatever the machine set."""
    env = dict(os.environ)
    inherited = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = ROOT + (os.pathsep + inherited if inherited else "")
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    env.update(extra)
    return env


def cache_entries() -> int:
    if not os.path.isdir(CACHE_DIR):
        return 0
    return sum(1 for _ in os.scandir(CACHE_DIR))


def make_model_dir(config: dict, name: str) -> str:
    """``config.json`` with the configuration's published keys plus a
    word-level tokenizer over the whole vocabulary: token i is the word
    ``w<i>``, none is special, so a prompt of n words is n tokens and an
    answer of n tokens is n words. Rewritten only when it changed."""
    d = os.path.join(WORK, "models", name)
    os.makedirs(d, exist_ok=True)
    hf = hf_config(config)
    V = hf["vocab_size"]
    files = {
        "config.json": hf,
        "tokenizer_config.json": {
            "bos_token": "w0", "eos_token": f"w{hf['eos_token_id']}",
            "tokenizer_class": "PreTrainedTokenizerFast",
        },
    }
    stamp = os.path.join(d, "stamp.json")
    want = json.dumps(files, sort_keys=True)
    if os.path.exists(stamp) and open(stamp).read() == want:
        return d
    tokenizer = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel",
                  "vocab": {f"w{i}": i for i in range(V)},
                  "unk_token": "w3"},
    }
    with open(os.path.join(d, "tokenizer.json"), "w") as f:
        json.dump(tokenizer, f)
    for fname, obj in files.items():
        with open(os.path.join(d, fname), "w") as f:
            json.dump(obj, f)
    with open(stamp, "w") as f:
        f.write(want)
    return d


class StallProbe(threading.Thread):
    """How late this idle thread wakes from a 0.1 s sleep (chip_smoke.py):
    a host freeze leaves its mark here, whatever metric it also hit."""

    def __init__(self) -> None:
        super().__init__(daemon=True)
        self.worst_s = 0.0
        self._done = threading.Event()

    def run(self) -> None:
        last = time.monotonic()
        while not self._done.wait(0.1):
            now = time.monotonic()
            self.worst_s = max(self.worst_s, now - last - 0.1)
            last = now

    def reset(self) -> float:
        worst, self.worst_s = self.worst_s, 0.0
        return worst

    def stop(self) -> float:
        self._done.set()
        return self.worst_s


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


class Server:
    """The serving child with its log; always reaped by ``stop``."""

    def __init__(self, model_dir: str, engine: dict, argv: list[str],
                 env: dict):
        os.makedirs(WORK, exist_ok=True)
        args_path = os.path.join(WORK, "engine_args.json")
        with open(args_path, "w") as f:
            json.dump(engine, f)
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(WORK, "server.log")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli.main", "run",
             "--in", "http", "--out", "jax", "--static",
             "--model-path", model_dir, "--model-name", MODEL_NAME,
             "--http-host", "127.0.0.1", "--http-port", str(self.port),
             "--extra-engine-args", args_path, *argv],
            env=env, stdout=self._log, stderr=subprocess.STDOUT, cwd=WORK,
        )

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            raise BenchFailure(
                f"server exited with code {self.proc.returncode}:\n"
                + self.log_tail()
            )

    def wait_ready(self, timeout: float) -> float:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                if get_json(f"{self.url}/v1/models", timeout=2).get("data"):
                    return time.monotonic() - self.t0
            except (urllib.error.URLError, OSError, ValueError):
                pass  # not listening yet
            time.sleep(0.25)
        raise BenchFailure(
            f"server not ready after {timeout:.0f}s:\n" + self.log_tail()
        )

    def engine_state(self, timeout: float = 10.0) -> dict:
        eng = get_json(f"{self.url}/debug/state", timeout).get("engine")
        if not isinstance(eng, dict) or "device" not in eng:
            raise BenchFailure("/debug/state names no engine")
        return eng

    def stop(self, grace_s: float = 30.0) -> dict:
        """SIGTERM, wait, SIGKILL if it must — and WAIT either way: the
        reference child needs the chip this one holds."""
        out = {"exit_code": self.proc.poll(), "killed": False}
        if out["exit_code"] is None:
            t0 = time.monotonic()
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                out["killed"] = True
            out["exit_code"] = self.proc.returncode
            out["shutdown_s"] = round(time.monotonic() - t0, 2)
        if not self._log.closed:
            self._log.close()
        return out


def counters(eng: dict) -> dict:
    """The counts of one ``/debug/state`` snapshot that readers take
    deltas or means of. A count the program no longer reports is a
    failure, never a 0: "no compiles" must not be read from a counter
    that is absent."""
    def part(name: str) -> dict:
        got = eng.get(name)
        if not isinstance(got, dict):
            raise BenchFailure(f"/debug/state engine has no {name!r}")
        return got

    def need(d: dict, where: str, key: str):
        if key not in d:
            raise BenchFailure(f"/debug/state {where} has no {key!r}")
        return d[key]

    sched, pool, fence = part("scheduler"), part("kv_pool"), part("compile_fence")
    out = {"t": time.monotonic(), "fence_mode": need(fence, "compile_fence", "mode"),
           "compile_events": need(fence, "compile_fence", "events_total")}
    for key in ("running", "prefilling", "waiting", "preemptions",
                "prefix_queries", "prefix_hits"):
        out[key] = need(sched, "scheduler", key)
    for key in ("active_blocks", "total_blocks"):
        out[key] = need(pool, "kv_pool", key)
    out["contexts"] = [
        need(r, "scheduler request", "prompt_tokens") + need(r, "scheduler request", "generated")
        for r in need(sched, "scheduler", "requests")
        if need(r, "scheduler request", "state") == "running"
    ]
    return out
