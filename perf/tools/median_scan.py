#!/usr/bin/env python3
"""How finely a mix's schedule resolves its TTFT median: one server, one
window for each value of one mix parameter (``schedule_seed``, as a rule),
and per window the median with the TTFTs that lie around it.

    python3 perf/tools/median_scan.py --workload nemotron-3-nano-30b.chat-burst \
        --seed 7 --param schedule_seed --values 20260930 20260933

Not part of a benchmark run. A fixed schedule makes ``ttft_p50_ms`` one
of the ~60 distinct values the window's requests take, and a burst puts
gaps between them: where the median sits beside a gap, a shift of a few
milliseconds moves it by the whole gap. ``chat-burst``'s
``schedule_seed`` was chosen with this tool (PERF.md, PR 37: the rule is
in the mix's ``note``). Prints one JSON line per value: the window's
requests, failures, ``ttft_p50_ms``, ``tpot_mean_ms``, ``slo_met_share``,
the 13 TTFTs around the median and ``rel_span_pm3``, the distance from
the third value below the median to the third above it, over the median."""

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


def near_median(recs) -> dict:
    xs = sorted(measure.ttft_ms(r) for r in recs)
    n = len(xs)
    p50 = measure.percentile(xs, 50)
    i = xs.index(p50)
    return {"n": n, "rank": i,
            "values": [round(x, 1) for x in xs[max(0, i - 6):min(n, i + 7)]],
            "rel_span_pm3": round(
                (xs[min(n - 1, i + 3)] - xs[max(0, i - 3)]) / p50, 4)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--param", default="schedule_seed")
    ap.add_argument("--values", nargs="+", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()
    cell, config = perf_run.cell_of(perf_run.load_benchmark(), args.workload)
    perf_run.refuse_without_chips(cell)
    base = sched.load_mix(cell["traffic"])
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
            # each window draws its own token ids (as perf/tools/sweep.py)
            load = Load(server.url, run.schedule, mix,
                        args.seed + 7919 * (n + 1), config["vocab_size"])
            asyncio.run(load.run(
                sched.kind_module(mix["kind"]).drive,
                lambda ld: perf_run.window_side(ld, server, run, False)))
            run.records, run.t0, run.end = load.records, load.t0, load.end
            win = measure.window_records(run)
            print(json.dumps({
                args.param: value, "window_requests": len(win),
                "failed": sum(r.failed for r in load.records),
                **{name: perf_run.read_metric(run, name) for name in (
                    "slo_met_share", "ttft_p50_ms", "tpot_mean_ms")},
                "host_stall_max_s": round(stalls.reset(), 3),
                "near_median": near_median(measure.finished(win)),
            }), flush=True)
            perf_run.wait_idle(server)
    finally:
        print(json.dumps({"shutdown": server.stop()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
