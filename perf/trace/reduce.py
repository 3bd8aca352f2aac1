"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction.

Two halves. ``load_events`` reads the file with JAX's own
``ProfileData`` (nothing else) into plain tuples; ``reduce_events`` is
pure arithmetic over those tuples and is what the test drives with a
small recorded trace (``tests/perf_harness/data/``).

  device busy   = the union of the intervals in which an op ran on the
                  device plane's "XLA Ops" line;
  window        = the traced interval: from the return of the profiler's
                  ``start_trace`` to the call of its ``stop_trace`` (python
                  frames on the host line) — the span the program asked
                  for. Device ops are clipped to it, and a device that
                  idles at either edge of it is counted idle there.
                  While the profiler starts and stops only some of its
                  tracers record, so that time is not part of it. An
                  edge whose frame the trace does not show falls back to
                  the first op's start / the last op's end
                  (``interval_from`` says which);
  ops           = total seconds, calls and median by op, named
                  ``<hlo name>_<dtype>_<dims>__<category>`` so that two
                  fusions of different shapes never share a row;
  modules       = the same for whole programs ("XLA Modules" line);
  idle gaps     = each gap between device ops longer than ``GAP_MIN_S``,
                  attributed to the innermost python frame covering the
                  gap's midpoint, or else to the innermost TraceMe of the
                  thread that launches the device's programs.

Run as a module: ``python -m perf.trace.reduce <trace dir> <out.json>``
(a child with JAX pinned to the CPU: only the server may hold the chip).
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys

GAP_MIN_S = 0.0005
GAPS_ATTRIBUTED = 300  # the longest gaps of a trace get a name
_HEAD = re.compile(r"^%?([\w.\-]+)\s*=\s*\(?\s*([a-z]+[0-9]*)\[([0-9,]*)\]")
_OPCODE = re.compile(r"[\)\}\]]\s+([a-z][a-z\-]*)\(")
# ops that only contain other ops of the same line: their time is their
# bodies' time, listed on its own rows
CONTAINERS = ("while", "conditional", "call")


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:120]


def op_label(raw: str) -> tuple[str, str]:
    """A device event's name is its HLO text,
    ``%fusion.19 = s8[4096,14336]{1,0:T(8,128)(4,1)} fusion(...)``;
    returns (``fusion.19_s8_4096_14336__fusion``, ``fusion``): op name,
    result type (a tuple's first element) and opcode, so that two
    fusions of different shapes never share a row."""
    head = _HEAD.match(raw)
    if not head:
        return _clean(raw.split(" ")[0].lstrip("%")), ""
    code = _OPCODE.search(raw)
    opcode = code.group(1) if code else "op"
    dims = head.group(3).replace(",", "_")
    return _clean(f"{head.group(1)}_{head.group(2)}_{dims}__{opcode}"), opcode


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(path: str) -> dict:
    """``{"device": {plane: {line: [(name, start_ns, dur_ns)]}},
    "host": {thread: [(name, start_ns, dur_ns)]}}`` with names as the
    profiler wrote them (a device op's name is its HLO text)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: dict = {}
    host: dict = {}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name \
            and "SparseCore" not in plane.name
        is_host = plane.name.startswith("/host:")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            out = []
            for ev in line.events:
                out.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
            if not out:
                continue
            if is_dev:
                device.setdefault(plane.name, {})[line.name] = out
            else:
                host[f"{plane.name}/{line.name}"] = out
    return {"device": device, "host": host}


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered length and the gaps between covered stretches."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def _table(events: list) -> dict:
    by: dict = {}
    for label, _, dur in events:
        by.setdefault(label, []).append(dur)
    return {
        label: {"total_s": sum(d) / 1e9, "calls": len(d),
                "median_s": statistics.median(d) / 1e9}
        for label, d in by.items()
    }


class _HostIndex:
    """Who held the host while the device idled. The profiler writes every
    python frame into one line (``$file.py:LINE func``; built-ins as
    ``$<...>``) and the runtime's TraceMe events into the lines of the
    threads that made them. An instant goes to the innermost python frame
    of the program's own files covering it, or else to the innermost
    TraceMe of the thread that launches programs (most ``...Execute``
    events)."""

    def __init__(self, host: dict):
        import numpy as np

        def arrays(evs: list) -> tuple:
            return ([e[0] for e in evs],
                    np.array([e[1] for e in evs], dtype=np.float64),
                    np.array([e[1] + e[2] for e in evs], dtype=np.float64))

        frames = [e for evs in host.values() for e in evs
                  if e[0].startswith("$") and not e[0].startswith("$<")]
        launch = max(host.values(), default=[],
                     key=lambda evs: sum(1 for e in evs if "Execute" in e[0]))
        self.rows = [arrays(frames), arrays(launch)]

    def innermost(self, t_ns: float) -> str:
        import numpy as np

        for names, starts, ends in self.rows:
            hit = np.nonzero((starts <= t_ns) & (ends >= t_ns))[0]
            if hit.size:
                i = int(hit[int(np.argmin(ends[hit] - starts[hit]))])
                return _clean(names[i])
        return "no_host_event"


def capture_edges(events: dict) -> tuple[float | None, float | None]:
    """(ns, ns): the return of ``start_trace`` and the call of
    ``stop_trace`` (``$profiler.py:LINE start_trace`` python frames on the
    host's lines); None for an edge whose frame the trace does not show."""
    frames = [e for evs in events["host"].values() for e in evs
              if e[0].startswith("$") and "profiler.py" in e[0]]
    begun = [e[1] + e[2] for e in frames if e[0].endswith(" start_trace")]
    ended = [e[1] for e in frames if e[0].endswith(" stop_trace")]
    return (max(begun) if begun else None), (min(ended) if ended else None)


def reduce_events(events: dict) -> dict:
    planes = []
    begun, ended = capture_edges(events)
    for plane, lines in sorted(events["device"].items()):
        raw = lines.get("XLA Ops")
        if raw is None:  # fall back to the fullest line
            raw = max(lines.values(), key=len)
        ops = []
        for name, start, dur in raw:
            label, opcode = op_label(name)
            if opcode not in CONTAINERS:
                ops.append((label, start, dur))
        first = min(s for _, s, _ in ops)
        last = max(s + d for _, s, d in ops)
        t0 = first if begun is None else begun
        t1 = last if ended is None else ended
        if t1 <= t0:   # frames of another capture: not this trace's edges
            t0, t1, begun, ended = first, last, None, None
        inside = [(max(s, t0), min(s + d, t1)) for _, s, d in ops
                  if s + d > t0 and s < t1]
        busy_ns, gaps = _union(inside)
        if inside and min(a for a, _ in inside) > t0:
            gaps.insert(0, (t0, min(a for a, _ in inside)))
        if inside and max(b for _, b in inside) < t1:
            gaps.append((max(b for _, b in inside), t1))
        planes.append({
            "plane": plane, "window_s": (t1 - t0) / 1e9,
            "ops_span_s": (last - first) / 1e9,
            "busy_s": busy_ns / 1e9, "ops": _table(ops),
            "modules": _table([(_clean(n), s, d) for n, s, d
                               in lines.get("XLA Modules", [])]),
            "gaps": gaps,
        })
    if not planes:
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0, "ops_span_s": 0.0,
                "interval_from": "nothing", "ops": {},
                "modules": {}, "idle_gaps": {}, "idle_s": 0.0}
    gap_by: dict = {}
    index = _HostIndex(events["host"])
    long_gaps = sorted((g for g in planes[0]["gaps"]
                        if (g[1] - g[0]) / 1e9 >= GAP_MIN_S),
                       key=lambda g: g[0] - g[1])[:GAPS_ATTRIBUTED]
    for g0, g1 in long_gaps:
        who = index.innermost((g0 + g1) / 2)
        gap_by[who] = gap_by.get(who, 0.0) + (g1 - g0) / 1e9
    n = len(planes)
    return {
        "chips": n,
        # averaged over the chips used
        "busy_s": sum(p["busy_s"] for p in planes) / n,
        "window_s": sum(p["window_s"] for p in planes) / n,
        "ops_span_s": sum(p["ops_span_s"] for p in planes) / n,
        "interval_from": ("first op" if begun is None else "start_trace") + ".."
        + ("last op" if ended is None else "stop_trace"),
        "idle_s": sum(p["window_s"] - p["busy_s"] for p in planes) / n,
        "ops": planes[0]["ops"], "modules": planes[0]["modules"],
        "idle_gaps": gap_by,
        # what the edges were read from, for whoever doubts them
        "profiler_frames": [[e[0], e[1] / 1e9, e[2] / 1e9]
                            for evs in events["host"].values() for e in evs
                            if "profiler.py" in e[0]][:8],
    }


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1]["total_s"])[:top]
    gaps = sorted(red["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v["total_s"]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def main(argv: list[str]) -> int:
    trace_dir, out_path = argv[1], argv[2]
    events = load_events(find_xplane(trace_dir))
    red = reduce_events(events)
    with open(out_path, "w") as f:
        json.dump(red, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
