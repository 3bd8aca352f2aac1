#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell comes from data: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``perf/configs/<config>.json`` and
``perf/traffic/<mix>.json`` hold them, and each metric of the cell is
read by ``perf/metrics/<name up to the first dot>.py``. This parent never
imports JAX: the chip belongs to the server child, then to the reference
child, one at a time.

Phases: set-up (model directory, server child up with the weights made
on the device from ``--seed``, every serving shape prewarmed by the
engine, ramp traffic) -> the window of ``--seconds`` -> drain -> probe
requests for the output check -> server stopped -> reference child ->
(traced runs) trace reduction -> the result line, last on stdout.
Every earlier line is one JSON object too.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import measure, server as srv  # noqa: E402
from perf.client import Load  # noqa: E402
from perf.reference import check  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402

# What a run is held to. The CPU rehearsal (tests/perf_harness/) replaces
# these module attributes; the script has no switch that relaxes them.
REQUIRE_PLATFORM = "tpu"
SERVER_ARGV = ["--spec-decode", ""]   # the product's default, said aloud
SAMPLE_EVERY_S = 0.5                  # /debug/state, traced runs only
TRACE_AT_S = 20.0                     # capture starts this far into the window
TRACE_MS = 2000
STATE_TIMEOUT_S = 90.0                # writing the trace blocks the server's loop
IDLE_WAIT_S = 30.0


def say(**obj) -> None:
    print(json.dumps(obj), flush=True)


class Run:
    """What the metric readers see."""

    def __init__(self) -> None:
        self.records: list = []
        self.t0 = self.end = self.seconds = self.setup_s = 0.0
        self.mix: dict = {}
        self.schedule: dict = {}
        self.config: dict = {}
        self.snap_before: dict = {}
        self.snap_after: dict = {}
        self.samples: list[dict] = []
        self.trace: dict | None = None
        self.device: dict = {}
        self.block_size = 0
        self.trace_dir = ""
        self.trace_span = (0.0, 0.0)   # monotonic start and end of the capture
        self.notes: list[dict] = []    # what readers want said beside their number


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise srv.BenchFailure(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        return cell, json.load(f)


def metrics_of(bench: dict, cell: dict, group: str) -> list[dict]:
    """The cell's metrics of ``group``: those that list it, and those that
    list no workloads at all."""
    mine = [m for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "end_to_end":
        return mine
    reported = {m["name"] for m in mine}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def read_metric(run: Run, name: str):
    stem, _, variant = name.partition(".")
    mod = importlib.import_module(f"perf.metrics.{stem}")
    return mod.read(run, variant)


async def window_side(load: Load, server: srv.Server, run: Run, trace: bool):
    """Beside the traffic: the counters at the window's two ends, and in
    a traced run the samples between them and the profiler capture."""
    loop = asyncio.get_running_loop()

    async def snap() -> dict:
        return srv.counters(await loop.run_in_executor(
            None, server.engine_state, STATE_TIMEOUT_S))

    await load.sleep_until(load.t0)
    run.snap_before = await snap()
    capture = None
    if trace:
        async def grab():
            await load.sleep_until(load.t0 + min(TRACE_AT_S, run.seconds / 4))
            ms = int(min(TRACE_MS, run.seconds * 250))
            began = time.monotonic()
            run.trace_span = (began, began + ms / 1000.0)
            return await loop.run_in_executor(
                None, srv.get_json, f"{server.url}/debug/profile?ms={ms}", 240.0)
        capture = asyncio.ensure_future(grab())
        t = load.t0 + SAMPLE_EVERY_S
        while t < load.end:
            await load.sleep_until(t)
            run.samples.append(await snap())
            # a sample that waited (the trace being written) is not made up for
            t = max(t + SAMPLE_EVERY_S, time.monotonic())
    await load.sleep_until(load.end)
    run.snap_after = await snap()
    if capture is not None:
        run.trace_dir = (await capture)["trace_dir"]


def wait_idle(server: srv.Server) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < IDLE_WAIT_S:
        c = srv.counters(server.engine_state())
        if c["running"] + c["prefilling"] + c["waiting"] == 0:
            return time.monotonic() - t0
        time.sleep(0.2)
    raise srv.BenchFailure("the server did not fall idle after the drain")


def child_json(argv: list[str], env: dict, what: str, timeout: float) -> dict:
    proc = subprocess.run([sys.executable, *argv], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or not lines:
        raise srv.BenchFailure(
            f"{what} failed (exit {proc.returncode}):\n{proc.stderr[-3000:]}")
    return json.loads(lines[-1])


def bring_up(cell: dict, config: dict, seed: int, run: Run,
             stalls: srv.StallProbe) -> srv.Server:
    """Model directory, server child up (weights made on the device from
    ``seed``, every serving shape prewarmed by the engine), and the
    checks a run is held to before any traffic."""
    model_dir = srv.make_model_dir(config, cell["config"])
    serving = config["serving"]
    engine = {"random_weights": True, "seed": seed, **serving["engine"]}
    entries_before = srv.cache_entries()
    work = os.path.join(srv.WORK, "profiles")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)
    server = srv.Server(
        model_dir, engine,
        ["--quantization", serving["quantization"], *SERVER_ARGV],
        srv.child_env(DYN_COMPILE_FENCE="1", DYN_PROFILE_DIR=work))
    try:
        ready_s = server.wait_ready(srv.READY_TIMEOUT_S)
        eng = server.engine_state()
        dev = eng["device"]
        run.device = {"platform": dev.get("platform"), "kind": dev.get("kind"),
                      "count": dev.get("count")}
        run.block_size = eng.get("block_size") or 0
        events = dev.get("compile_cache_events") or {}
        say(phase="engine_up", ready_s=round(ready_s, 2),
            init_s=dev.get("init_s"), prewarm_s=dev.get("prewarm_s"),
            device=run.device, kernels={k: dev.get(k) for k in (
                "attn_impl", "matmul_impl", "attn_pallas_active",
                "matmul_pallas_active", "mosaic_calls_in_step")},
            compile_cache={"dir": dev.get("compile_cache_dir"),
                           "entries_before": entries_before,
                           "entries_after": srv.cache_entries(), **events},
            kv_pool=eng.get("kv_pool"), block_size=run.block_size,
            hbm=eng.get("hbm"), host_stall_max_s=round(stalls.reset(), 3))
        if run.device["platform"] != REQUIRE_PLATFORM \
                or run.device["count"] != cell["chips"]:
            raise srv.BenchFailure(
                f"the server runs on {run.device}, the cell needs "
                f"{cell['chips']} x {REQUIRE_PLATFORM}")
        if (eng.get("compile_fence") or {}).get("mode") != "record":
            raise srv.BenchFailure("the compile fence is not armed")
    except BaseException:
        print("--- server log tail ---\n" + server.log_tail(), file=sys.stderr)
        server.stop(grace_s=10)
        raise
    return server


def refuse_without_chips(cell: dict) -> None:
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise srv.BenchFailure("the system under test (dynamo_tpu/) is not here")
    nodes = srv.accelerator_nodes()
    if REQUIRE_PLATFORM == "tpu" and len(nodes) < cell["chips"]:
        raise srv.BenchFailure(
            f"{len(nodes)} accelerator device nodes, the cell needs "
            f"{cell['chips']}: no server started")


def probe(load: Load, seed: int, config: dict, mix: dict) -> list[dict]:
    """The output check's requests, sent to the idle server wave by wave:
    the cell's own probe, laid out by its traffic kind from the mix file
    (perf/reference/check.py)."""
    answers: list[dict] = []
    for wave in check.probe_waves(mix):
        earlier = [a for a in answers if a["wave"] == wave[0]["wave"] - 1]
        jobs = check.wave_jobs(seed, config["vocab_size"], wave, earlier)
        answers += asyncio.run(load.check_requests(jobs))
    extra = {a["prompt_tokens_served"] - len(a["ids"]) for a in answers}
    if extra != {0}:
        raise srv.BenchFailure(
            f"the server counted {extra} tokens beyond the scheduled prompt")
    return answers


def reference_child(config: dict, runs: list[dict]) -> dict:
    """One reference child (the chip is free by now) for ``runs``
    ``[{seed, sequences, precisions}]``: its report, ``runs`` aligned."""
    os.makedirs(srv.WORK, exist_ok=True)
    job_path = os.path.join(srv.WORK, "reference_job.json")
    with open(job_path, "w") as f:
        json.dump({"config": srv.hf_config(config),
                   "require_platform": REQUIRE_PLATFORM,
                   "runs": [{"seed": r["seed"], "precisions": r["precisions"],
                             "sequences": [{k: s[k] for k in ("tokens", "at", "chosen")}
                                           for s in r["sequences"]]}
                            for r in runs]}, f)
    t_ref = time.monotonic()
    ref = child_json(["-m", "perf.reference.check", job_path],
                     srv.child_env(), "the reference child", 1500)
    ref["seconds"] = round(time.monotonic() - t_ref, 2)
    return ref


def judge(answers: list[dict], config: dict, cell_name: str,
          seed: int) -> tuple[dict, dict, dict]:
    """The comparison with the reference: the numbers compared, their
    limits, and the reference child's report."""
    seqs = check.compared(check.sequences(answers))
    ref = reference_child(config, [{"seed": seed, "sequences": seqs,
                                    "precisions": ["f32"]}])
    got = check.compare(seqs, ref["runs"][0]["f32"])
    got.update(probe_shape(answers, seqs))
    return got, check.load_limits(cell_name), ref


def probe_shape(answers: list[dict], seqs: list[dict]) -> dict:
    return {"rows_sent": len({a["row"] for a in answers}),
            "rows_compared": len(seqs),
            "longest_context": max(len(s["tokens"]) for s in seqs)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    server = None
    stalls = srv.StallProbe()
    try:
        bench = load_benchmark()
        cell, config = cell_of(bench, args.workload)
        refuse_without_chips(cell)
        run = Run()
        run.config, run.seconds = config, args.seconds
        run.mix = sched.load_mix(cell["traffic"])
        run.schedule = sched.build(run.mix, args.seconds)
        kind = sched.kind_module(run.mix["kind"])
        say(phase="schedule", workload=cell["name"], seed=args.seed,
            digest=sched.digest(run.schedule), ramp_s=run.schedule["ramp_s"],
            seconds=args.seconds, **kind.totals(run.schedule))

        stalls.start()
        server = bring_up(cell, config, args.seed, run, stalls)

        load = Load(server.url, run.schedule, run.mix, args.seed,
                    config["vocab_size"])
        timing = asyncio.run(load.run(
            kind.drive,
            lambda ld: window_side(ld, server, run, bool(args.trace))))
        server.check_alive()
        run.records, run.t0, run.end = load.records, load.t0, load.end
        run.setup_s = load.t0 - T_START
        stall_window = stalls.reset()

        win = measure.window_records(run)
        done = measure.finished(win)
        failed = [r for r in load.records if r.failed]
        late = sorted(measure.late_ms(r) for r in load.records if r.sent)
        say(phase="window", setup_s=run.setup_s, ramp_s=timing["ramp_s"],
            drain_s=round(timing["drain_s"], 3),
            drain_timed_out=timing["drain_timed_out"],
            requests_sent=len(load.records), window_requests=len(win),
            window_finished=len(done),
            cancelled_at_window_end=sum(r.cancelled for r in load.records),
            failed=len(failed), first_errors=[r.error or
                f"{r.received} of {r.out_tokens} tokens" for r in failed[:3]],
            window_prompt_tokens=sum(r.prompt_tokens for r in win),
            window_output_tokens_scheduled=sum(r.out_tokens for r in win),
            tokens_received_in_window=measure.tokens_in_window(run),
            samples={"ttft": len(done),
                     "beyond_p85": measure.beyond(len(done), 85),
                     "beyond_p50": measure.beyond(len(done), 50),
                     "gen_late": len(late),
                     "gen_late_beyond_p80": measure.beyond(len(late), 80)},
            ttft_ms_deciles=measure.deciles([measure.ttft_ms(r) for r in done]),
            gen_late_ms={"p50": late[len(late) // 2] if late else None,
                         "max": late[-1] if late else None},
            host_stall_max_s=round(stall_window, 3),
            serve_compiles=run.snap_after["compile_events"]
            - run.snap_before["compile_events"],
            preemptions=run.snap_after["preemptions"]
            - run.snap_before["preemptions"],
            prefix=[run.snap_after["prefix_hits"] - run.snap_before["prefix_hits"],
                    run.snap_after["prefix_queries"]
                    - run.snap_before["prefix_queries"]],
            running_at_ends=[run.snap_before["running"], run.snap_after["running"]],
            waiting_at_ends=[run.snap_before["waiting"], run.snap_after["waiting"]],
            blocks_at_ends=[run.snap_before["active_blocks"],
                            run.snap_after["active_blocks"],
                            run.snap_after["total_blocks"]])

        # -- outputs: probe requests, then the reference ------------------
        idle_s = wait_idle(server)
        answers = probe(load, args.seed, config, run.mix)
        eng = server.engine_state()
        peak = (eng.get("hbm") or {}).get("peak_bytes_in_use")
        stopped = server.stop()
        server = None
        say(phase="shutdown", idle_wait_s=round(idle_s, 2), **stopped)
        got, limits, ref = judge(answers, config, cell["name"], args.seed)
        correct = (got["logprob_err_mean"] <= limits["logprob_err_mean"]
                   and not failed and not timing["drain_timed_out"])
        say(phase="outputs", compared=got, limits=limits,
            reference_platform=ref["platform"], reference_s=ref["seconds"],
            probe_requests=len(answers), correct=correct)

        out_device = dict(run.device, memory_peak_bytes=peak)
        result = {"correct": correct, "attempted": len(load.records),
                  "failed": len(failed), "metrics": {}, "device": out_device}
        if args.trace:
            from perf.trace import reduce as tr

            red_path = os.path.join(srv.WORK, "trace_reduced.json")
            subprocess.run(
                [sys.executable, "-m", "perf.trace.reduce", run.trace_dir,
                 red_path], env=srv.child_env(JAX_PLATFORMS="cpu"), cwd=ROOT,
                check=True, timeout=600)
            with open(red_path) as f:
                run.trace = json.load(f)
            if REQUIRE_PLATFORM == "tpu" and run.trace["busy_s"] <= 0:
                raise srv.BenchFailure("no operation ran on the device in the trace")
            out_device.update(busy_s=run.trace["busy_s"],
                              window_s=run.trace["window_s"])
            result["breakdown"] = tr.breakdown(run.trace)
        group = "per_layer" if args.trace else "end_to_end"
        for m in metrics_of(bench, cell, group):
            value = read_metric(run, m["name"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        say(phase="host", reader_notes=run.notes,
            host_stall_max_s_after_window=round(stalls.stop(), 3),
            total_s=round(time.monotonic() - T_START, 2))
        print(json.dumps(result), flush=True)
        return 0
    except Exception as e:  # no result line, a non-zero exit
        import traceback

        traceback.print_exc()
        if server is not None:
            print("--- server log tail ---\n" + server.log_tail(), file=sys.stderr)
        say(phase="error", error=f"{type(e).__name__}: {str(e)[:600]}")
        return 1
    finally:
        if server is not None:
            server.stop(grace_s=10)


if __name__ == "__main__":
    sys.exit(main())
