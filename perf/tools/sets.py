#!/usr/bin/env python3
"""Run several benchmark runs in ONE call and summarise them.

    python3 perf/tools/sets.py --runs cell:seed[:trace[:set]] ... [--seconds S]
        [--keep DIR]

Each run is the benchmark's own command in a fresh process, exactly as
the driver starts it. Prints every run's earlier lines (shortened), its
result line, and per cell and metric the values, the median and the
spread (distance between the quartiles of ``statistics.quantiles(n=4)``
over the median). ``--keep`` copies each run's server log and (traced
runs) the reduced trace there.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values: list[float]) -> float | None:
    if len(values) < 2 or statistics.median(values) == 0:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / abs(statistics.median(values))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    table: dict = {}
    for spec in args.runs:
        cell, seed, *rest = spec.split(":")
        trace = rest[0] if rest else "0"
        tag = rest[1] if len(rest) > 1 else ""
        t0 = time.monotonic()
        proc = subprocess.run(
            [*bench["command"], "--workload", cell, "--seed", seed,
             "--seconds", str(seconds), "--trace", trace],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t0
        lines = proc.stdout.splitlines()
        for ln in lines[:-1]:
            print("   " + ln[:1800], flush=True)
        print(json.dumps({"run": spec, "exit": proc.returncode,
                          "wall_s": round(wall, 1)}), flush=True)
        if lines:
            print(lines[-1][:6000], flush=True)
        if proc.returncode != 0:
            print(proc.stderr[-6000:], flush=True)
            continue
        result = json.loads(lines[-1])
        row = table.setdefault((cell, trace + tag), {})
        for name, m in result["metrics"].items():
            row.setdefault(name, []).append(m["value"])
        row.setdefault("correct", []).append(result["correct"])
        for ln in lines[:-1]:
            obj = json.loads(ln)
            if obj.get("phase") == "outputs":
                row.setdefault("logprob_err_mean", []).append(
                    obj["compared"]["logprob_err_mean"])
                row.setdefault("logprob_err_max", []).append(
                    obj["compared"]["logprob_err_max"])
        if args.keep:
            keep = os.path.join(ROOT, args.keep)
            os.makedirs(keep, exist_ok=True)
            tag = spec.replace(":", "_")
            work = os.path.join(ROOT, ".perf_work")
            for name in ("server.log", "trace_reduced.json"):
                src = os.path.join(work, name)
                if os.path.exists(src) and (name == "server.log" or trace == "1"):
                    shutil.copyfile(src, os.path.join(keep, f"{tag}.{name}"))
    print("=== summary (values; median; spread = IQR/median) ===")
    for (cell, trace), row in table.items():
        for name, vals in row.items():
            if name == "correct":
                print(f"{cell} trace={trace} correct: {vals}")
                continue
            print(f"{cell} trace={trace} {name}: "
                  f"{[round(v, 5) for v in vals]} median="
                  f"{statistics.median(vals):.6g} spread={spread(vals)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
