"""What the program itself recorded around a capture, for the readers.

Two sources, both left in ``run.trace_dir`` by the server
(``dynamo_tpu/telemetry/debug.py``), both optional: a program that writes
neither (an older commit; the CPU rehearsal's device half) makes every
function here return ``None``, and the readers then report nothing.

``program_spans.json`` — the request spans the server held in memory
(``http.request`` -> ``preprocess`` -> ``engine.queue_wait`` /
``engine.prefill`` / ``engine.decode``), each with its start on
CLOCK_MONOTONIC, the clock ``perf/client.py`` times the window on (one
host). ``requests(run)`` keeps the requests whose ``engine.queue_wait``
begins inside the window. The copy written at shutdown is preferred: it
holds every request the server finished.

the ``.xplane.pb`` again — for what ``perf/trace/reduce.py`` does not
keep (read in a child with JAX pinned to the CPU, as that reducer is
run; its functions are imported, not copied):

  phases     the ``dyn.step.*`` events (``plan``, ``pack``, ``dispatch``,
             ``harvest``, ``emit``, ``record``, ``wait``) of the engine
             thread: the host line that holds most of them. Read by
             ``host_lines``, which keeps EVERY line: the reducer's
             ``load_events`` keys host lines by name, and the python
             threads of one process share a name, so it keeps one of them
             (measured, PR 25: the engine thread survived in one capture
             of three);
  host step  the capture cut at each ``dyn.step.dispatch``'s start: one
             step's host work is what plan, pack, dispatch, emit and
             record took between two cuts (``harvest`` waits for the
             device and ``wait`` for work: neither is host work);
  idle       the device's idle gaps >= ``reduce.GAP_MIN_S`` over the
             reducer's own interval, each put down to the innermost phase
             covering its midpoint, or to ``none``;
  programs   each execution on the "XLA Modules" line, classed by what
             ran inside its interval: *prefill* if a
             ``paged_attention_prefill_stacked*`` op did, *decode* if a
             ``paged_attention_decode*`` op did, else *other* (the glue
             programs that chain and pack tokens). A step is what it
             runs: no hash and no shape is matched.

Run as a module: ``python -m perf.trace.program_spans <trace dir> <out.json>``.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import subprocess
import sys

from perf import server as srv
from perf.trace import reduce as tr

SPANS_FILE = "program_spans.json"
PHASE_PREFIX = "dyn.step."
HOST_WORK = ("plan", "pack", "dispatch", "emit", "record")
_ABSENT = object()


# -- the span file -------------------------------------------------------------
def spans_doc(run) -> dict | None:
    """The parsed ``program_spans.json`` of the run's capture (cached on
    the run), or None when the program wrote none."""
    got = getattr(run, "_program_spans_doc", _ABSENT)
    if got is _ABSENT:
        got = None
        path = os.path.join(run.trace_dir or "", SPANS_FILE)
        if run.trace_dir and os.path.isfile(path):
            with open(path) as f:
                got = json.load(f)
            run.notes.append({"program_spans": {
                "written": got.get("written"), "spans": len(got["spans"]),
                "dropped": got.get("dropped"),
                "counts_in_capture": _count_deltas(got)}})
        run._program_spans_doc = got
    return got


def _count_deltas(doc: dict) -> dict:
    """stop - start of every cumulative count the program gave."""
    def flat(d: dict, prefix: str = "") -> dict:
        out: dict = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out.update(flat(v, f"{prefix}{k}."))
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                out[prefix + k] = v
        return out

    a = flat((doc.get("start") or {}).get("counts") or {})
    b = flat((doc.get("stop") or {}).get("counts") or {})
    return {k: b[k] - a.get(k, 0) for k in b if k not in ("ts", "pid")}


def requests(run) -> list[dict] | None:
    """One ``{span name: span}`` per request whose ``engine.queue_wait``
    begins inside the window, or None without a span file."""
    doc = spans_doc(run)
    if doc is None:
        return None
    got = getattr(run, "_program_requests", None)
    if got is None:
        by_trace: dict = {}
        for s in doc["spans"]:
            by_trace.setdefault(s["trace_id"], {}).setdefault(s["name"], s)
        lo, hi = run.t0 * 1e9, run.end * 1e9
        got = [t for t in by_trace.values()
               if "engine.queue_wait" in t
               and lo <= t["engine.queue_wait"]["start_mono_ns"] <= hi]
        ttft = sorted(t["engine.decode"]["attrs"]["ttft_ms"] for t in got
                      if "ttft_ms" in (t.get("engine.decode") or {}).get("attrs", {}))
        run.notes.append({"program_requests": {
            "in_window": len(got), "traces": len(by_trace),
            "server_ttft_ms_p50": ttft[len(ttft) // 2] if ttft else None}})
        run._program_requests = got
    return got


def durations_ms(run, name: str) -> list[float] | None:
    """Durations of the window's ``name`` spans, ms."""
    reqs = requests(run)
    if reqs is None:
        return None
    return [t[name]["duration_s"] * 1e3 for t in reqs if name in t]


# -- the capture, read again ---------------------------------------------------
def host_lines(path: str) -> dict:
    """``{"<plane>/<line name>#<n>": [(name, start_ns, dur_ns)]}``: the
    ``dyn.step.*`` events of every host line of an ``.xplane.pb``, no two
    lines sharing a key."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                   for ev in line.events if ev.name.startswith(PHASE_PREFIX)]
            if evs:
                out[f"{plane.name}/{line.name}#{n}"] = evs
    return out


def engine_phases(host: dict) -> list[tuple[str, float, float]]:
    """``(phase, start_ns, dur_ns)`` of the engine thread, by start."""
    best: list = []
    for evs in host.values():
        mine = [e for e in evs if e[0].startswith(PHASE_PREFIX)]
        if len(mine) > len(best):
            best = mine
    return sorted(((e[0][len(PHASE_PREFIX):], float(e[1]), float(e[2]))
                   for e in best), key=lambda e: e[1])


def host_step_ms(phases: list) -> list[float]:
    """Host work per step, ms: the capture cut at each dispatch's start."""
    cuts = [s for name, s, _ in phases if name == "dispatch"]
    out = [0.0] * max(0, len(cuts) - 1)
    for name, start, dur in phases:
        i = bisect.bisect_right(cuts, start) - 1
        if name in HOST_WORK and 0 <= i < len(out):
            out[i] += dur / 1e6
    return out


def device_interval(events: dict):
    """``(t0, t1, ops, interval_from, modules)`` of the first device plane
    as ``reduce.reduce_events`` takes them: start_trace's return to
    stop_trace's call, an edge the trace does not show being the first /
    last op; ``ops`` are ``(label, start_ns, dur_ns)`` without container
    ops, ``modules`` the "XLA Modules" line as it is. None when no op ran
    on a device."""
    if not events["device"]:
        return None
    lines = events["device"][sorted(events["device"])[0]]
    raw = lines.get("XLA Ops")
    if raw is None:
        raw = max(lines.values(), key=len)
    ops = []
    for name, start, dur in raw:
        label, opcode = tr.op_label(name)
        if opcode not in tr.CONTAINERS:
            ops.append((label, float(start), float(dur)))
    if not ops:
        return None
    begun, ended = tr.capture_edges(events)
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    t0 = first if begun is None else begun
    t1 = last if ended is None else ended
    if t1 <= t0:
        t0, t1, begun, ended = first, last, None, None
    where = ("first op" if begun is None else "start_trace") + ".." \
        + ("last op" if ended is None else "stop_trace")
    return t0, t1, ops, where, lines.get("XLA Modules", [])


def idle_by_phase(t0: float, t1: float, ops: list, phases: list) -> tuple[float, dict]:
    """``(busy_s, {phase: idle seconds})`` over gaps >= GAP_MIN_S."""
    inside = [(max(s, t0), min(s + d, t1)) for _, s, d in ops
              if s + d > t0 and s < t1]
    busy_ns, gaps = tr._union(inside)
    if inside:
        gaps = [(t0, min(a for a, _ in inside)), *gaps,
                (max(b for _, b in inside), t1)]
    starts = [s for _, s, _ in phases]
    by: dict = {}
    for g0, g1 in gaps:
        if (g1 - g0) / 1e9 < tr.GAP_MIN_S:
            continue
        mid, who = (g0 + g1) / 2, "none"
        # phases do not nest: the one that began last before the midpoint
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and phases[i][1] + phases[i][2] >= mid:
            who = phases[i][0]
        by[who] = by.get(who, 0.0) + (g1 - g0) / 1e9
    return busy_ns / 1e9, by


def classify_programs(t0: float, t1: float, ops: list, modules: list) -> dict:
    """``{class: {calls, total_s, median_s}}`` of the executions on the
    "XLA Modules" line that overlap the interval, seconds clipped to it."""
    marks = {"prefill": sorted(s for label, s, _ in ops
                               if label.startswith("paged_attention_prefill_stacked")),
             "decode": sorted(s for label, s, _ in ops
                              if label.startswith("paged_attention_decode"))}

    def ran(kind: str, s: float, e: float) -> bool:
        i = bisect.bisect_left(marks[kind], s)
        return i < len(marks[kind]) and marks[kind][i] < e

    by: dict = {}
    for _, s, d in modules:
        s, e = float(s), float(s) + float(d)
        if e <= t0 or s >= t1:
            continue
        kind = "prefill" if ran("prefill", s, e) else \
            "decode" if ran("decode", s, e) else "other"
        by.setdefault(kind, []).append((min(e, t1) - max(s, t0), e - s))
    return {k: {"calls": len(v), "total_s": sum(c for c, _ in v) / 1e9,
                "median_s": statistics.median(d for _, d in v) / 1e9}
            for k, v in by.items()}


def reduce_steps(events: dict, phase_lines: dict | None = None) -> dict | None:
    """Everything above from one capture: ``events`` as the reducer
    loads them (the device's lines, and the host frames its interval is
    read from), ``phase_lines`` as ``host_lines`` does (default: the
    events' own host lines). None when the capture holds no device plane."""
    got = device_interval(events)
    if got is None:
        return None
    t0, t1, ops, where, modules = got
    phases = [p for p in engine_phases(
        events["host"] if phase_lines is None else phase_lines)
        if p[1] + p[2] > t0 and p[1] < t1]
    busy_s, idle = idle_by_phase(t0, t1, ops, phases)
    totals: dict = {}
    for name, _, dur in phases:
        row = totals.setdefault(name, {"calls": 0, "total_s": 0.0})
        row["calls"] += 1
        row["total_s"] += dur / 1e9
    return {"interval_from": where, "window_s": (t1 - t0) / 1e9,
            "busy_s": busy_s, "phases": totals,
            "host_step_ms": host_step_ms(phases), "idle_by_phase": idle,
            "programs": classify_programs(t0, t1, ops, modules)}


def steps(run) -> dict | None:
    """``reduce_steps`` of the run's capture (a child, once per run), or
    None: no capture, no ``dyn.step.*`` event, or no device plane."""
    got = getattr(run, "_program_steps", _ABSENT)
    if got is _ABSENT:
        got = None
        if run.trace_dir and spans_doc(run) is not None:
            out = os.path.join(srv.WORK, "program_steps.json")
            subprocess.run(
                [sys.executable, "-m", "perf.trace.program_spans",
                 run.trace_dir, out], env=srv.child_env(JAX_PLATFORMS="cpu"),
                cwd=srv.ROOT, check=True, timeout=600)
            with open(out) as f:
                got = json.load(f)
            if got is not None and not got["phases"]:
                got = None
            if got is not None:
                run.notes.append({"program_steps": {
                    k: got[k] for k in ("interval_from", "window_s", "busy_s",
                                        "phases", "idle_by_phase", "programs")}})
        run._program_steps = got
    return got


def main(argv: list[str]) -> int:
    path = tr.find_xplane(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce_steps(tr.load_events(path), host_lines(path)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
