#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host: tp=4, replicas

One chip: (1) a child runs every Pallas kernel of the serving path once
at Llama-3.1-8B widths against the plain XLA path it replaces
(``dynamo_tpu.ops.selfcheck``) and exits; (2) the normal entry point

    python -m dynamo_tpu.cli.main run --in http --out jax --static \
        --model-path <dir> --quantization int8

serves the full-width, 32-layer geometry with seeded random int8 weights
(engine options at their defaults, prewarm on; ``max_model_len`` 4096
narrows the set of prewarmed shapes, no width or depth is cut) and
answers ``/v1/models`` and a few streamed / non-streamed chat and
completion requests — short and >= 1k-token prompts, several at once —
with the compile fence armed; (3) SIGTERM, clean exit.

Four chips (``--chips 4``; only this, nothing of the above): the same
geometry in bf16 behind ``--tensor-parallel-size 4`` — compared, at 8
layers, with tp=1 on the same seed and prompts — and four one-chip int8
workers behind the KV router.

This process never imports JAX: a chip belongs to one process, and
every phase that touches it is a child that has exited before the next
one starts. Every line printed is one JSON object; the LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
as the serving process reported it, or ``{"ok": false, ...}`` with a
non-zero exit code when any phase failed — among them: JAX found no
TPU. Nothing printed here is a benchmark result.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# What the run is held to. The CPU rehearsal (tests/test_chip_smoke.py)
# replaces these module attributes; the script itself has no switch that
# relaxes them.
EXPECT = {
    "platform": "tpu",
    "attn_pallas_active": True,
    "matmul_pallas_active": True,  # one-chip int8 path only
    # the lowered first step holds Mosaic kernels (tpu_custom_call): a
    # Pallas kernel that ran interpreted would lower to plain HLO
    "mosaic_in_step": True,
    # each confined worker holds a chip of its own (--chips 4)
    "distinct_chips": True,
}
# Llama-3.1-8B / DeepSeek-R1-Distill-Llama-8B
GEOMETRY = dict(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
    max_position_embeddings=8192,
)
CUT_LAYERS = 8  # depth of the tp=1 vs tp=4 comparison (--chips 4)
# Engine options beyond the CLI defaults: seeded random weights, and the
# context cap that bounds the prewarmed shape set (table width).
ENGINE = dict(random_weights=True, seed=0, max_model_len=4096)
SHORT_PROMPT_TOKENS = 24
LONG_PROMPT_TOKENS = 1500  # > prefill_chunk_size: a chunked prefill
MAX_TOKENS = 16
READY_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 300.0
KERNEL_SPEC = dict(
    D=4096, F=14336, V=128256, H=32, Hk=8, Dh=128, block_size=128, m=64,
    m_large=2048, ctx=[1, 130, 1000, 4000], prefill=[1024, 1024], seed=0,
)
LOGPROB_TOLERANCE = 0.1  # nats; tp=1 vs tp=4 chosen-token logprobs
REPLICAS = 4  # one-chip workers behind the router (--chips 4)
# their prompts: four share a prefix (shared + own tail), eight do not
REPLICA_PROMPT_TOKENS = (600, 40, 300)
# what a one-chip int8 engine's device report is held to
ONE_CHIP_CHECKS = ("platform", "attn_pallas_active", "matmul_pallas_active",
                   "mosaic_in_step")

SPECIALS = {
    "<|begin_of_text|>": 0, "<|start_header_id|>": 1,
    "<|end_header_id|>": 2, "<|eot_id|>": 4,
}


class SmokeFailure(RuntimeError):
    pass


def say(**obj) -> None:
    print(json.dumps(obj), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(**extra: str) -> dict:
    """The environment every child inherits: the checkout on the import
    path and the ONE compile-cache rule (utils/jaxtools.py)."""
    from dynamo_tpu.utils.jaxtools import compile_cache_dir

    env = dict(os.environ)
    inherited = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = HERE + (os.pathsep + inherited if inherited else "")
    cache = compile_cache_dir()
    if cache is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache
    env.update(extra)
    return env


def cache_entries() -> int:
    from dynamo_tpu.utils.jaxtools import compile_cache_dir

    cache = compile_cache_dir()
    if cache is None or not os.path.isdir(cache):
        return 0
    return sum(1 for _ in os.scandir(cache))


# ---------------------------------------------------------------------------
# Model directory: real widths, a tokenizer whose text names token ids
# ---------------------------------------------------------------------------


def make_model_dir(tmp: str, geometry: dict, name: str = "model") -> str:
    """config.json at ``geometry`` plus a word-level tokenizer over the
    WHOLE vocabulary (token i is the word ``w<i>``): prompts have an
    exact token count and the returned text names the sampled ids, so
    runs can be compared token by token over HTTP."""
    d = os.path.join(tmp, name)
    os.makedirs(d, exist_ok=True)
    V = geometry["vocab_size"]
    by_id = {i: t for t, i in SPECIALS.items()}
    vocab = {by_id.get(i, f"w{i}"): i for i in range(V)}
    tokenizer = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [
            {"id": i, "content": t, "single_word": False, "lstrip": False,
             "rstrip": False, "normalized": False, "special": True}
            for t, i in SPECIALS.items()
        ],
        "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "w3"},
    }
    with open(os.path.join(d, "tokenizer.json"), "w") as f:
        json.dump(tokenizer, f)
    with open(os.path.join(d, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "<|begin_of_text|>", "eos_token": "<|eot_id|>",
            "chat_template": (
                "{{- bos_token }}{%- for message in messages %}"
                "{{- '<|start_header_id|> ' + message['role'] + "
                "' <|end_header_id|> ' }}{{- message['content'] | trim }}"
                "{{- ' <|eot_id|> ' }}{%- endfor %}"
                "{%- if add_generation_prompt %}"
                "{{- '<|start_header_id|> assistant <|end_header_id|> ' }}"
                "{%- endif %}"
            ),
            "tokenizer_class": "PreTrainedTokenizerFast",
        }, f)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"], "model_type": "llama",
            "rms_norm_eps": 1e-5, "rope_theta": 500000.0,
            "bos_token_id": 0, "eos_token_id": 4,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16",
            **geometry,
        }, f)
    return d


def words(n: int, start: int, vocab: int) -> str:
    """``n`` in-vocabulary words (ids past the specials, deterministic)."""
    return " ".join(f"w{5 + (start + 7 * i) % (vocab - 5)}" for i in range(n))


# ---------------------------------------------------------------------------
# Children
# ---------------------------------------------------------------------------


class Server:
    """One ``dynamo_tpu.cli.main`` child with its log; always reaped."""

    def __init__(self, tag: str, argv: list[str], tmp: str, env: dict):
        self.tag = tag
        self.log_path = os.path.join(tmp, f"{tag}.log")
        self._log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli.main", *argv],
            env=env, stdout=self._log, stderr=subprocess.STDOUT, cwd=tmp,
        )

    def log_tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"{self.tag} exited with code {self.proc.returncode}:\n"
                + self.log_tail()
            )

    def stop(self, grace_s: float = 60.0) -> dict:
        """SIGTERM, wait, SIGKILL if it must; never leaves the child (and
        the chip it holds) behind."""
        out = {"tag": self.tag, "exit_code": self.proc.poll(),
               "killed": False}
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
            out["shutdown_s"] = round(time.monotonic() - t0, 1)
        self._log.close()
        return out


@contextlib.contextmanager
def reaped(servers: list[Server], require_clean: bool = True):
    """Whatever happens inside, every server in ``servers`` (the list
    may grow inside the block) is stopped, newest first. On a failure
    their log tails go to stderr first; on success each must have
    exited 0 on SIGTERM when ``require_clean``."""
    try:
        yield
    except BaseException:
        keep = os.path.join(HERE, "chiprun_out", "chip_smoke_logs")
        os.makedirs(keep, exist_ok=True)  # git-ignored; the tool returns it
        for s in servers:
            print(f"--- {s.tag} log tail ---\n" + s.log_tail(3500),
                  file=sys.stderr)
            shutil.copyfile(s.log_path, os.path.join(keep, f"{s.tag}.log"))
        for s in reversed(servers):
            say(phase="shutdown", **s.stop(grace_s=10))
        raise
    for s in reversed(servers):
        stopped = s.stop()
        say(phase="shutdown", **stopped)
        if require_clean and (stopped["killed"] or stopped["exit_code"] != 0):
            raise SmokeFailure(f"{s.tag} did not shut down cleanly: {stopped}")


class StallProbe(threading.Thread):
    """How late this idle, JAX-free process wakes from a 0.1 s sleep:
    the host's longest freeze while servers start. A four-chip host
    froze for 7.8 s while four 8B workers cold-started — which is what
    once cost the frontend its 10 s store lease (runtime/config.py)."""

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

    def stop(self) -> float:
        self._done.set()
        return round(self.worst_s, 3)


def engine_server(
    tag: str, tmp: str, model_dir: str, engine: dict, extra_argv: list[str],
    in_mode: str = "http", **env: str,
) -> tuple[Server, str]:
    args_path = os.path.join(tmp, f"engine_{tag}.json")
    with open(args_path, "w") as f:
        json.dump(engine, f)
    port = free_port()
    argv = [
        "run", "--in", in_mode, "--out", "jax",
        "--model-path", model_dir, "--model-name", "smoke",
        "--extra-engine-args", args_path, *extra_argv,
    ]
    if in_mode == "http":
        # --static: no store, no discovery — one self-contained server
        argv += ["--static", "--http-host", "127.0.0.1",
                 "--http-port", str(port)]
    server = Server(tag, argv, tmp, child_env(DYN_COMPILE_FENCE="1", **env))
    return server, f"http://127.0.0.1:{port}"


def get_json(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def wait_ready(server: Server, url: str, timeout: float) -> float:
    """Poll /v1/models until it lists a model; the child dying or the
    deadline passing is a failure (with the server log)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        server.check_alive()
        try:
            if get_json(f"{url}/v1/models", timeout=2).get("data"):
                return time.monotonic() - server.t0
        except (urllib.error.URLError, OSError, ValueError):
            pass  # not listening yet
        time.sleep(1.0)
    raise SmokeFailure(
        f"{server.tag} not ready after {timeout:.0f}s:\n" + server.log_tail()
    )


def post(url: str, path: str, body: dict, rid: str = "") -> dict:
    """One OpenAI request; returns text, token count, seconds, and for
    streams the number of SSE chunks."""
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["X-Request-Id"] = rid
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(), headers=headers,
    )
    chat = path.endswith("/chat/completions")
    t0 = time.monotonic()
    text, usage, chunks, logprobs = "", None, 0, []

    def take(choice: dict) -> str:
        lp = choice.get("logprobs") or {}
        if chat:
            logprobs.extend(e["logprob"] for e in lp.get("content") or [])
            return (choice.get("delta") or choice.get("message") or {}).get(
                "content") or ""
        logprobs.extend(lp.get("token_logprobs") or [])
        return choice.get("text") or ""

    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
        if body.get("stream"):
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    break
                ev = json.loads(data)
                chunks += 1
                usage = ev.get("usage") or usage
                for choice in ev.get("choices") or []:
                    text += take(choice)
        else:
            ev = json.load(r)
            usage = ev.get("usage")
            text = take(ev["choices"][0])
    return {
        "text": text, "words": len(text.split()), "chunks": chunks,
        "usage": usage, "logprobs": logprobs,
        "seconds": round(time.monotonic() - t0, 3),
    }


def request_body(kind: str, prompt: str, stream: bool, **extra) -> tuple[str, dict]:
    body = {
        "model": "smoke", "max_tokens": MAX_TOKENS, "temperature": 0.0,
        "stream": stream, "ext": {"ignore_eos": True, "greedy_sampling": True},
        **extra,
    }
    if stream:
        body["stream_options"] = {"include_usage": True}
    if kind == "chat":
        body["messages"] = [{"role": "user", "content": prompt}]
        return "/v1/chat/completions", body
    body["prompt"] = prompt
    return "/v1/completions", body


def check_reply(name: str, reply: dict, min_prompt_tokens: int) -> None:
    usage = reply["usage"] or {}
    if reply["words"] != MAX_TOKENS:
        raise SmokeFailure(
            f"{name}: {reply['words']} tokens in the text, wanted "
            f"{MAX_TOKENS}: {reply['text'][:200]!r}"
        )
    if usage.get("completion_tokens") != MAX_TOKENS:
        raise SmokeFailure(f"{name}: usage {usage}, wanted {MAX_TOKENS}")
    if usage.get("prompt_tokens", 0) < min_prompt_tokens:
        raise SmokeFailure(
            f"{name}: prompt_tokens {usage.get('prompt_tokens')} < "
            f"{min_prompt_tokens}"
        )


def check_device(report: dict, keys: tuple[str, ...], who: str) -> None:
    report = {**report,
              "mosaic_in_step": bool(report.get("mosaic_calls_in_step"))}
    for key in keys:
        if report.get(key) != EXPECT[key]:
            raise SmokeFailure(
                f"{who}: {key} is {report.get(key)!r}, the smoke requires "
                f"{EXPECT[key]!r} (device report: {report})"
            )


def engine_state(url: str) -> dict:
    state = get_json(f"{url}/debug/state")
    eng = state.get("engine")
    if not isinstance(eng, dict) or "device" not in eng:
        raise SmokeFailure(f"/debug/state names no engine: {list(state)}")
    return eng


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def native_tier() -> dict:
    """Build the native tier from native/src when it is not there (a
    clean checkout has no .so) and say whether it loads; the Python
    paths serve when it does not."""
    from dynamo_tpu import native

    had = os.path.exists(native._so_path())
    built = native.build()
    return {"phase": "native_tier", "so_present_before": had,
            "built": bool(built), "loaded": native.is_available()}


def kernels_phase() -> dict:
    """The kernel check, in a child that has exited before any server
    starts. Its device line is what decides whether the run goes on."""
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.ops.selfcheck",
         json.dumps({**KERNEL_SPEC, "require_platform": EXPECT["platform"]})],
        env=child_env(), capture_output=True, text=True, timeout=900,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if not lines:
        raise SmokeFailure(
            f"kernel check printed no report (exit {proc.returncode}):\n"
            + proc.stderr[-3000:]
        )
    report = json.loads(lines[-1])
    say(**report)
    check_device(report["device"], ("platform",), "kernel check")
    if report["interpreted"] != (EXPECT["platform"] != "tpu"):
        # Pallas interprets exactly when the device is not a TPU
        raise SmokeFailure(f"kernel check interpreted={report['interpreted']}")
    if proc.returncode != 0 or not report["ok"]:
        raise SmokeFailure("a kernel disagrees with its XLA reference")
    return report["device"]


def serve_phase(tmp: str) -> dict:
    vocab = GEOMETRY["vocab_size"]
    model_dir = make_model_dir(tmp, GEOMETRY)
    entries_before = cache_entries()
    say(phase="config", geometry=GEOMETRY, quantization="int8", engine=ENGINE,
        note="max_model_len narrows the prewarmed shape set; no width or "
             "depth is cut; other engine options are the CLI defaults")
    stalls = StallProbe()
    stalls.start()
    server, url = engine_server(
        "server", tmp, model_dir, ENGINE, ["--quantization", "int8"]
    )
    with reaped([server]):
        ready_s = wait_ready(server, url, READY_TIMEOUT_S)
        host_stall_max_s = stalls.stop()
        eng = engine_state(url)
        dev = eng["device"]
        cache_events = dev.get("compile_cache_events") or {}
        entries_after = cache_entries()
        say(phase="engine_up", startup_s=round(ready_s, 1),
            host_stall_max_s=host_stall_max_s,
            init_s=dev.get("init_s"), prewarm_s=dev.get("prewarm_s"),
            device=dev, models=[m["id"] for m in
                                get_json(f"{url}/v1/models")["data"]],
            compile_cache={
                "dir": dev.get("compile_cache_dir"),
                "entries_before": entries_before,
                "entries_after_prewarm": entries_after,
                "written": entries_after > entries_before,
                "read": cache_events.get("hits", 0) > 0,
                **cache_events,
            },
            memory_after_warmup=eng.get("hbm"))
        check_device(dev, ONE_CHIP_CHECKS, "serving process")

        short = words(SHORT_PROMPT_TOKENS, 0, vocab)
        long_a = words(LONG_PROMPT_TOKENS, 1000, vocab)
        long_b = words(LONG_PROMPT_TOKENS, 50000, vocab)
        # one at a time: each endpoint, streamed and not
        plan = [
            ("chat", short, False, SHORT_PROMPT_TOKENS),
            ("chat", short, True, SHORT_PROMPT_TOKENS),
            ("completions", short, False, SHORT_PROMPT_TOKENS),
            ("completions", short, True, SHORT_PROMPT_TOKENS),
            ("completions", long_a, False, LONG_PROMPT_TOKENS),
        ]
        replies = {}
        for kind, prompt, stream, n_prompt in plan:
            name = f"{kind}{'_stream' if stream else ''}_{n_prompt}"
            path, body = request_body(kind, prompt, stream)
            replies[name] = post(url, path, body)
            check_reply(name, replies[name], n_prompt)
            server.check_alive()
        # greedy on the same prompt: the stream must carry the same tokens
        for kind in ("chat", "completions"):
            a = replies[f"{kind}_{SHORT_PROMPT_TOKENS}"]["text"].split()
            b = replies[f"{kind}_stream_{SHORT_PROMPT_TOKENS}"]["text"].split()
            if a != b:
                raise SmokeFailure(f"{kind}: streamed != non-streamed: {a} {b}")
        # several at once: a batched decode step beside chunked prefills
        burst = [
            ("chat", long_b, True, LONG_PROMPT_TOKENS),
            ("completions", long_a, True, LONG_PROMPT_TOKENS),
        ] + [
            ("chat" if i % 2 else "completions",
             words(SHORT_PROMPT_TOKENS, 100 * (i + 1), vocab), bool(i % 2),
             SHORT_PROMPT_TOKENS)
            for i in range(6)
        ]
        with concurrent.futures.ThreadPoolExecutor(len(burst)) as pool:
            futs = [
                pool.submit(post, url, *request_body(kind, prompt, stream))
                for kind, prompt, stream, _ in burst
            ]
            for i, (fut, (kind, _, stream, n_prompt)) in enumerate(
                    zip(futs, burst)):
                name = f"burst{i}_{kind}{'_stream' if stream else ''}_{n_prompt}"
                replies[name] = fut.result()
                check_reply(name, replies[name], n_prompt)
        say(phase="requests", requests={
            name: {"seconds": r["seconds"], "chunks": r["chunks"],
                   "prompt_tokens": r["usage"]["prompt_tokens"],
                   "completion_tokens": r["usage"]["completion_tokens"]}
            for name, r in replies.items()
        }, sample_text=replies[f"chat_{SHORT_PROMPT_TOKENS}"]["text"])

        eng = engine_state(url)
        steps = [s.get("kind") for s in eng.get("recent_steps") or []]
        fence = eng.get("compile_fence") or {}
        say(phase="after_serving", compile_fence=fence,
            serve_phase_compiles=fence.get("events_total"),
            memory=eng.get("hbm"), kv_pool=eng.get("kv_pool"),
            tokens_generated_total=eng.get("tokens_generated_total"),
            recent_step_kinds=sorted(set(k for k in steps if k)))
        if fence.get("mode") != "record":
            raise SmokeFailure(f"compile fence not armed: {fence}")
    return dev


def one_chip(tmp: str) -> dict:
    say(**native_tier())
    kernels_phase()
    return serve_phase(tmp)


# ---------------------------------------------------------------------------
# Four chips: a sharded model, and replicas behind the router
# ---------------------------------------------------------------------------


def greedy_with_logprobs(url: str, vocab: int) -> list[dict]:
    out = []
    for i, n in enumerate((SHORT_PROMPT_TOKENS, 200, LONG_PROMPT_TOKENS)):
        path, body = request_body(
            "completions", words(n, 31 * (i + 1), vocab), False, logprobs=0
        )
        reply = post(url, path, body)
        check_reply(f"prompt{n}", reply, n)
        out.append(reply)
    return out


def tp_serve(tmp: str, tag: str, geometry: dict, tp: int) -> tuple[list, dict]:
    """Serve ``geometry`` in bf16 at tensor-parallel ``tp``; returns the
    greedy replies and the engine's state."""
    model_dir = make_model_dir(tmp, geometry, f"model_L{geometry['num_hidden_layers']}")
    server, url = engine_server(
        tag, tmp, model_dir, ENGINE, ["--tensor-parallel-size", str(tp)]
    )
    with reaped([server]):
        ready_s = wait_ready(server, url, READY_TIMEOUT_S)
        eng = {**engine_state(url), "ready_s": round(ready_s, 1)}
        replies = greedy_with_logprobs(url, geometry["vocab_size"])
    return replies, eng


def tp_phase(tmp: str) -> dict:
    cut = dict(GEOMETRY, num_hidden_layers=CUT_LAYERS)
    ref, eng1 = tp_serve(tmp, "tp1_cut", cut, 1)
    got, eng4 = tp_serve(tmp, "tp4_cut", cut, 4)
    compare = []
    for a, b in zip(ref, got):
        ta, tb = a["text"].split(), b["text"].split()
        agree = next((i for i in range(len(ta)) if ta[i] != tb[i]), len(ta))
        # up to and including the step where the streams part, both
        # sides conditioned on the same tokens: the chosen-token
        # logprobs must agree; past it they are different sequences
        upto = min(agree + 1, len(ta))
        dlp = max(
            abs(x - y) for x, y in zip(a["logprobs"][:upto], b["logprobs"][:upto])
        )
        compare.append({"prompt_tokens": a["usage"]["prompt_tokens"],
                        "tokens": len(ta), "agree_prefix": agree,
                        "max_logprob_diff": round(dlp, 4)})
    say(phase="tp4_vs_tp1", layers=CUT_LAYERS, dtype="bfloat16",
        logprob_tolerance=LOGPROB_TOLERANCE, prompts=compare,
        tp1={k: eng1["device"].get(k) for k in
             ("ids", "attn_pallas_active", "matmul_pallas_active", "init_s")},
        tp4={k: eng4["device"].get(k) for k in
             ("ids", "attn_pallas_active", "matmul_pallas_active", "init_s")})
    for row in compare:
        if row["agree_prefix"] < 1 or row["max_logprob_diff"] > LOGPROB_TOLERANCE:
            raise SmokeFailure(f"tp=4 disagrees with tp=1: {row}")

    _, eng = tp_serve(tmp, "tp4_full", GEOMETRY, 4)
    dev = eng["device"]
    per_dev = dev.get("bytes_in_use_per_device") or []
    say(phase="tp4_full_depth", layers=GEOMETRY["num_hidden_layers"],
        dtype="bfloat16", ready_s=eng["ready_s"], device=dev,
        weight_bytes=eng["hbm"].get("weight_bytes"),
        bytes_in_use_per_device=per_dev)
    check_device(dev, ("platform", "attn_pallas_active", "mosaic_in_step"),
                 "tp=4 engine")
    if dev.get("count") != 4 or len(per_dev) != 4:
        raise SmokeFailure(f"tp=4 engine is not on four devices: {dev}")
    if None not in per_dev and max(per_dev) > 1.25 * min(per_dev):
        raise SmokeFailure(f"weights are not spread evenly: {per_dev}")
    return dev


def worker_device(server: Server) -> dict | None:
    """The device report of a worker's "engine up" log line, once it
    also says it is serving its endpoint."""
    log = server.log_tail(200_000)
    if "worker serving" not in log or "engine up:" not in log:
        return None
    line = log.split("engine up:", 1)[1].split("\n", 1)[0]
    return json.loads(line.split("device=", 1)[1].split(", mesh=", 1)[0])


def check_distinct_chips(devices: list[dict]) -> None:
    """Each worker holds device nodes no other holds, as the kernel
    lists them (``chip_nodes``, utils/jaxtools.py). ``visible_chips``
    only repeats what this script asked for, and a confined device
    calls itself id 0 at (0,0,0) on every chip."""
    held = [d.get("chip_nodes") or [] for d in devices]
    nodes = [n for h in held for n in h]
    if EXPECT["distinct_chips"] and (
        not all(held) or len(set(nodes)) != len(nodes)
    ):
        raise SmokeFailure(f"workers share a chip, or hold none: {held}")


def lease_remarks(servers: list[Server]) -> list[str]:
    """Every late renewal or lost lease the children logged."""
    out = []
    for s in servers:
        for line in s.log_tail(10_000_000).splitlines():
            if any(mark in line for mark in (
                    "lease renewed", "lease lost", "lease sweep woke",
                    "store unreachable")):
                out.append(f"{s.tag}: {line.strip()[:200]}")
    return out


def replicas_phase(tmp: str) -> None:
    """store + the discovery frontend with KV routing + four one-chip
    int8 workers (each confined to its chip by ``--tpu-chips``), all
    started TOGETHER with nothing about the store lease overridden: the
    frontend has to hold its lease while four 8B workers cold-start."""
    vocab = GEOMETRY["vocab_size"]
    model_dir = make_model_dir(tmp, GEOMETRY)
    store_port = free_port()
    started: list[Server] = []
    stalls = StallProbe()
    stalls.start()
    # the store and the frontend are not held to a clean exit code
    with reaped(started, require_clean=False):
        started.append(Server(
            "store", ["store", "--host", "127.0.0.1", "--port", str(store_port)],
            tmp, child_env(DYN_JAX_PLATFORM="cpu"),
        ))
        time.sleep(2.0)
        started[0].check_alive()
        store = ["--store-host", "127.0.0.1", "--store-port", str(store_port)]
        port = free_port()
        front = Server(
            "frontend",
            ["run", "--in", "http", "--out", "auto", "--router-mode", "kv",
             "--http-host", "127.0.0.1", "--http-port", str(port), *store],
            tmp, child_env(DYN_JAX_PLATFORM="cpu"),
        )
        started.append(front)
        url = f"http://127.0.0.1:{port}"
        workers = []
        for chip in range(REPLICAS):
            w, _ = engine_server(
                f"worker{chip}", tmp, model_dir, ENGINE,
                ["--quantization", "int8", "--tpu-chips", str(chip), *store],
                in_mode="dyn://dynamo.backend.generate",
            )
            workers.append(w)
            started.append(w)
        deadline = time.monotonic() + READY_TIMEOUT_S
        devices: list = [None] * REPLICAS
        while not all(devices):
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"workers not ready: {[bool(d) for d in devices]}"
                )
            time.sleep(2.0)
            for s in started:
                s.check_alive()
            devices = [worker_device(w) for w in workers]
        workers_ready_s = round(time.monotonic() - workers[0].t0, 1)
        wait_ready(front, url, 120.0)
        time.sleep(5.0)  # let the frontend's watcher see every instance
        n_shared, n_tail, n_other = REPLICA_PROMPT_TOKENS
        shared = words(n_shared, 777, vocab)
        prompts = [shared + " " + words(n_tail, 1000 * i, vocab)
                   for i in range(4)]
        prompts += [words(n_other, 5000 * (i + 1), vocab) for i in range(8)]
        # one shared-prefix request first, so its blocks are indexed
        # before the others that share the prefix are routed
        first = post(url, *request_body("completions", prompts[0], False),
                     rid="smoke-r0")
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            replies = [first] + list(pool.map(
                lambda ip: post(
                    url, *request_body("completions", ip[1], False),
                    rid=f"smoke-r{ip[0]}"),
                list(enumerate(prompts))[1:],
            ))
        decisions = []
        for i, r in enumerate(replies):
            check_reply(f"replica_request{i}", r, n_other)
            rec = get_json(f"{url}/debug/request/smoke-r{i}")
            route = (rec.get("router") or [{}])[0]
            decisions.append({
                "request": i, "shared_prefix": i < 4,
                "worker": route.get("worker"),
                "overlap_blocks": route.get("overlap_blocks"),
                "total_blocks": route.get("total_blocks"),
                "seconds": r["seconds"],
            })
        for s in started:
            s.check_alive()  # nobody lost its lease along the way
        say(phase="replicas", lease="defaults (DYN_LEASE_TTL_S unset)",
            frontend_started="with the workers",
            workers_ready_s=workers_ready_s,
            host_stall_max_s=stalls.stop(),
            lease_remarks=lease_remarks(started),
            workers=[{"worker": i, "device": d}
                     for i, d in enumerate(devices)],
            router_decisions=decisions)
        check_distinct_chips(devices)
        for i, d in enumerate(devices):
            check_device(d, ONE_CHIP_CHECKS, f"worker {i}")
        if len({d["worker"] for d in decisions if d["worker"]}) < 2:
            raise SmokeFailure("the router used fewer than two workers")


def four_chips(tmp: str) -> dict:
    dev = tp_phase(tmp)
    replicas_phase(tmp)
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase (tp=4 + replicas)")
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            dev = (one_chip if args.chips == 1 else four_chips)(tmp)
        if dev["count"] != args.chips:
            raise SmokeFailure(f"{dev['count']} devices, wanted {args.chips}")
    except Exception as e:  # every failure ends in the "ok": false line
        print(f"chip_smoke failed: {type(e).__name__}: {e}", file=sys.stderr)
        say(ok=False, error=f"{type(e).__name__}: {str(e)[:500]}")
        return 1
    say(ok=True, device={"platform": dev["platform"], "kind": dev["kind"],
                         "count": dev["count"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
