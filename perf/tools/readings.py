#!/usr/bin/env python3
"""Read what a limit of the output check is set from (PERF.md, section 2):
per seed the program's number on a cell's probe and, beside it, the
control's — the reference one precision step below what the configuration
states (``a8``: int8 activations), teacher-forced on the very sequences
the program answered (``perf/reference/control.py``). No window, no
traffic. Not run by the benchmark.

    python3 perf/tools/readings.py --workloads mistral-7b.chat mistral-7b.sessions \
        --seeds 31 32 33 --controls a8 [--engine kv_cache_dtype=float8_e4m3fn]

The workloads share one configuration: one server per seed answers every
workload's probe, then one reference child per workload reads all seeds.
``--engine`` switches a lower-precision path of the PROGRAM on (the
program is then itself a control, and has to read above the limit).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import run as perf_run, server as srv  # noqa: E402
from perf.client import Load  # noqa: E402
from perf.reference import check, control  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    ap.add_argument("--engine", nargs="*", default=[],
                    help="engine options as key=value (JSON values)")
    args = ap.parse_args()
    bench = perf_run.load_benchmark()
    cells = [perf_run.cell_of(bench, w) for w in args.workloads]
    cell, config = cells[0]
    if {c["config"] for c, _ in cells} != {cell["config"]}:
        raise SystemExit("the workloads of one call share one configuration")
    perf_run.refuse_without_chips(cell)
    extra = {}
    for kv in args.engine:
        k, _, v = kv.partition("=")
        try:
            extra[k] = json.loads(v)
        except ValueError:
            extra[k] = v
    config = dict(config, serving=dict(
        config["serving"], engine={**config["serving"]["engine"], **extra}))
    stalls = srv.StallProbe()
    stalls.start()
    answered: dict = {c["name"]: [] for c, _ in cells}
    for seed in args.seeds:
        server = perf_run.bring_up(cell, config, seed, perf_run.Run(), stalls)
        try:
            load = Load(server.url, {}, {}, seed, config["vocab_size"])
            for c, _ in cells:
                answers = perf_run.probe(load, seed, config,
                                         sched.load_mix(c["traffic"]))
                perf_run.wait_idle(server)
                answered[c["name"]].append((seed, answers))
        finally:
            server.stop()
    for name, per_seed in answered.items():
        runs = [{"seed": seed, "precisions": ["f32", *args.controls],
                 "sequences": check.compared(check.sequences(answers))}
                for seed, answers in per_seed]
        ref = perf_run.reference_child(config, runs)
        for (seed, answers), run, got in zip(per_seed, runs, ref["runs"]):
            seqs = run["sequences"]
            line = {"workload": name, "engine": extra, "seed": seed,
                    "program": check.compare(seqs, got["f32"]),
                    **{f"control_{p}": control.forced_error(seqs, got[p], got["f32"])
                       for p in args.controls},
                    **perf_run.probe_shape(answers, seqs),
                    "limits": check.load_limits(name),
                    "reference_platform": ref["platform"]}
            print(json.dumps(line), flush=True)
        print(json.dumps({"workload": name, "reference_s": ref["seconds"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
