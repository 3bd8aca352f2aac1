#!/usr/bin/env python3
"""Find a knee once: one server, several windows of one mix with one
parameter varied (the open loop's ``rate_rps``, the closed loop's
``clients``, the sessions' ``live_sessions``).

    python3 perf/tools/sweep.py --workload mistral-7b.chat --seed 7 \
        --param rate_rps --values 1.6 1.9 2.2 2.5 2.9 3.3 --seconds 30

Not part of a benchmark run: the benchmark offers load at the rate its
mix file fixes and never searches. Prints one JSON line per value: share
of window requests inside the mix's ``slo``, failures, TTFT median and
p85, mean token gap, output tokens/s, and the backlog (waiting, running,
active KV blocks) at the window's two ends — a knee needs >= 90% inside
the limits, no failure and no growing backlog."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import measure, run as perf_run, server as srv  # noqa: E402
from perf.client import Load  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", nargs="+", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    bench = perf_run.load_benchmark()
    cell, config = perf_run.cell_of(bench, args.workload)
    perf_run.refuse_without_chips(cell)
    base = sched.load_mix(cell["traffic"])
    slo = base.setdefault("slo", {"ttft_ms": 1000, "gap_ms": 60})
    stalls = srv.StallProbe()
    stalls.start()
    run = perf_run.Run()
    run.config = config
    server = perf_run.bring_up(cell, config, args.seed, run, stalls)
    try:
        for n, value in enumerate(args.values):
            as_int = isinstance(base.get(args.param), int)
            mix = dict(base, **{args.param: int(value) if as_int else value})
            run = perf_run.Run()
            run.config, run.seconds, run.mix = config, args.seconds, mix
            run.schedule = sched.build(mix, args.seconds)
            # the weights are the server's; each window draws its OWN token
            # ids, or a later window would find the earlier one's prompts
            # in the prefix cache
            load = Load(server.url, run.schedule, mix,
                        args.seed + 7919 * (n + 1), config["vocab_size"])
            timing = asyncio.run(load.run(
                sched.kind_module(mix["kind"]).drive,
                lambda ld: perf_run.window_side(ld, server, run, False)))
            run.records, run.t0, run.end = load.records, load.t0, load.end
            win = measure.window_records(run)
            a, b = run.snap_before, run.snap_after
            print(json.dumps({
                args.param: value, "window_requests": len(win),
                "failed": sum(r.failed for r in load.records),
                **{name: perf_run.read_metric(run, name) for name in (
                    "slo_met_share", "ttft_p50_ms", "ttft_p85_ms",
                    "tpot_mean_ms", "out_tok_s")},
                "gap_over_limit": sum(
                    (measure.tpot_ms(r) or 0) > slo["gap_ms"]
                    for r in measure.finished(win)),
                "waiting": [a["waiting"], b["waiting"]],
                "running": [a["running"], b["running"]],
                "prefilling": [a["prefilling"], b["prefilling"]],
                "active_blocks": [a["active_blocks"], b["active_blocks"],
                                  b["total_blocks"]],
                "preemptions": b["preemptions"] - a["preemptions"],
                "prefix": [b["prefix_hits"] - a["prefix_hits"],
                           b["prefix_queries"] - a["prefix_queries"]],
                "serve_compiles": b["compile_events"] - a["compile_events"],
                "drain_s": round(timing["drain_s"], 2),
                "host_stall_max_s": round(stalls.reset(), 3),
            }), flush=True)
            perf_run.wait_idle(server)
    finally:
        print(json.dumps({"shutdown": server.stop()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
