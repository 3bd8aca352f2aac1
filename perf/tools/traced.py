#!/usr/bin/env python3
"""One traced benchmark run, with what a builder wants kept from it.

    python3 perf/tools/traced.py --workload <cell> --seed <n> [--seconds S]
        [--trace 0|1] --keep DIR [--cut]

Runs ``perf/run.py``'s ``main`` as the command line would, and beside it:

- after the window, before the probe, reads the server's
  ``/debug/hostplane`` and prints the event loop's lag over its rolling
  window (``phase: loop_lag``): how long the capture held the loop; and the
  flight recorder's newest decode steps, ``plan_ms`` + ``dispatch_ms``
  (``phase: flight_recorder``), to set beside ``step_host_ms_p50``;
- copies the server log, the reduced trace, ``program_spans.json`` and
  ``program_steps.json`` (perf/trace/program_spans.py) into ``--keep``;
- ``--cut``: writes ``<keep>/<cell>.<seed>.cut.json``, a cut of the
  capture from the step program before its first prefill-class program
  to the one after it, with the engine thread's ``dyn.step.*`` events: what
  ``tests/perf_harness/data/recorded_steps.json`` was made from.

The benchmark runs none of this.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import run as perf_run, server as srv  # noqa: E402


def cut(trace_dir: str, out_path: str) -> int:
    """The child half (JAX pinned to the CPU by the caller)."""
    from perf.trace import program_spans as ps, reduce as tr

    path = tr.find_xplane(trace_dir)
    events = tr.load_events(path)
    lines = ps.host_lines(path)
    print(json.dumps({"host_lines_kept_by_load_events": sorted(events["host"])[:40],
                      "lines_with_phases": {k: len(v) for k, v in lines.items()}}))
    plane = sorted(events["device"])[0]
    dev = events["device"][plane]
    ops = [(tr.op_label(n), s, d) for n, s, d in dev["XLA Ops"]]

    def has(prefix: str, s: float, d: float) -> bool:
        return any(label.startswith(prefix) and s <= at < s + d
                   for (label, _), at, _ in ops)

    # the step programs, in order: those that ran an attention kernel
    steps = [("prefill" if has("paged_attention_prefill_stacked", s, d) else "decode", s, d)
             for _, s, d in sorted(dev.get("XLA Modules", []), key=lambda e: e[1])
             if has("paged_attention_", s, d)]
    hit = min((i for i, st in enumerate(steps)
               if st[0] == "prefill" and 0 < i < len(steps) - 1),
              key=lambda i: steps[i][2], default=None)   # the shortest: a small cut
    if hit is None:
        print(json.dumps({"cut": None, "why": "no prefill program between two others"}))
        return 0
    lo, hi = steps[hit - 1][1], steps[hit + 1][1] + steps[hit + 1][2]

    def short(n: str) -> str:   # name = type[dims] opcode( : all op_label reads
        opcode = tr.op_label(n)[1]
        head = tr._HEAD.match(n)
        if not head:
            return n[:80]
        tup = "(" if "= (" in n[:n.index("[")] else ""
        return f"%{head.group(1)} = {tup}{head.group(2)}[{head.group(3)}]" \
            f"{')' if tup else ''} {opcode}("

    doc = {"note": "", "device": {plane: {
        "XLA Ops": [[short(n), s - lo, d] for n, s, d in dev["XLA Ops"]
                    if s >= lo and s + d <= hi],
        "XLA Modules": [[n, s - lo, d] for n, s, d in dev.get("XLA Modules", [])
                        if s >= lo and s + d <= hi]}},
        "host": {"/host:CPU/engine": [
            [ps.PHASE_PREFIX + n, s - lo, d] for n, s, d in ps.engine_phases(lines)
            if s + d >= lo and s <= hi]}}
    for raw, cut_ in zip((e for e in dev["XLA Ops"] if e[1] >= lo and e[1] + e[2] <= hi),
                         doc["device"][plane]["XLA Ops"]):
        assert tr.op_label(raw[0]) == tr.op_label(cut_[0]), (raw[0], cut_[0])
    with open(out_path, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print(json.dumps({"cut": out_path, "ops": len(doc["device"][plane]["XLA Ops"]),
                      "programs": len(doc["device"][plane]["XLA Modules"]),
                      "phases": len(doc["host"]["/host:CPU/engine"]),
                      "span_ms": (hi - lo) / 1e6,
                      "steps": [st[0] for st in steps[hit - 1:hit + 2]]}))
    return 0


def main() -> int:
    if len(sys.argv) == 4 and sys.argv[1] == "--cut-only":
        return cut(sys.argv[2], sys.argv[3])
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", default="1")
    ap.add_argument("--keep", required=True)
    ap.add_argument("--cut", action="store_true")
    args = ap.parse_args()
    seconds = args.seconds or str(perf_run.load_benchmark()["run_seconds"])

    wait_idle = perf_run.wait_idle

    def wait_idle_and_lag(server):
        try:
            host = srv.get_json(f"{server.url}/debug/hostplane", 30.0)
            loop = (host.get("frontend") or {}).get("loop") or {}
            perf_run.say(phase="loop_lag", **{
                k: loop.get(k) for k in ("lag", "stalls", "beats")})
            steps = [r for r in server.engine_state(30.0).get("recent_steps") or []
                     if r.get("kind") == "decode" and "dispatch_ms" in r]
            host = sorted(r.get("plan_ms", 0.0) + r["dispatch_ms"] for r in steps)
            perf_run.say(phase="flight_recorder", decode_steps=len(steps),
                         plan_plus_dispatch_ms_p50=host[len(host) // 2] if host else None)
        except Exception as e:  # a tool: say so and go on
            perf_run.say(phase="loop_lag", error=f"{type(e).__name__}: {e}")
        return wait_idle(server)

    perf_run.wait_idle = wait_idle_and_lag
    rc = perf_run.main(["--workload", args.workload, "--seed", args.seed,
                        "--seconds", seconds, "--trace", args.trace])
    keep = os.path.join(ROOT, args.keep)
    os.makedirs(keep, exist_ok=True)
    tag = f"{args.workload}.{args.seed}"
    dirs = sorted(glob.glob(os.path.join(srv.WORK, "profiles", "*")))
    found = [os.path.join(srv.WORK, n) for n in
             ("server.log", "trace_reduced.json", "program_steps.json")]
    if dirs:
        found.append(os.path.join(dirs[-1], "program_spans.json"))
    for src in found:
        if os.path.exists(src):
            shutil.copyfile(src, os.path.join(keep, f"{tag}.{os.path.basename(src)}"))
    if args.cut and dirs and args.trace == "1":
        subprocess.run([sys.executable, os.path.abspath(__file__), "--cut-only",
                        dirs[-1], os.path.join(keep, f"{tag}.cut.json")],
                       env=srv.child_env(JAX_PLATFORMS="cpu"), cwd=ROOT, timeout=600)
    return rc


if __name__ == "__main__":
    sys.exit(main())
