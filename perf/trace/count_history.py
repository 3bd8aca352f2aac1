"""The program's own counts over the UNTRACED window.

Since ISSUE 40 the engine thread notes its host-side cumulative counts
once a second, capture or no capture, and ``program_spans.json`` carries
them as ``"history": [{"monotonic_ns", "counts": {provider: counts}}]``
beside ``"end"``, the two clocks at ``stop_trace``'s RETURN
(``dynamo_tpu/telemetry/debug.py``; docs/observability.md "Step phases").
The stamps are on CLOCK_MONOTONIC, the clock ``perf/client.py`` times
the window on.

``growth(run)`` keeps the entries inside the window, pairs each with the
next, drops every pair that touches the capture's ``[start, end]`` (the
python tracer charges every python call there, and ``stop_trace`` then
serialises for seconds), and adds up each count's growth over the pairs
kept. A metric is a ratio of two such sums: ``per(run, numerator,
denominator)``. Fewer than ``MIN_PAIRS`` pairs, or a program that writes
no history (an older commit), read ``None``.
"""

from __future__ import annotations

from perf.trace import program_spans

MIN_PAIRS = 5
PHASES = ("plan", "pack", "dispatch", "harvest", "emit", "record", "wait")
HOST_WORK = ("plan", "pack", "dispatch", "emit", "record")
_ABSENT = object()


def flat(counts: dict, prefix: str = "") -> dict:
    """``{"a.b": n}`` of the numbers in a nested dict of counts."""
    out: dict = {}
    for k, v in counts.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[prefix + k] = v
    return out


def kept_pairs(doc: dict, t0_s: float, end_s: float) -> list[tuple[dict, dict]]:
    """Consecutive history entries of the provider that noted most, both
    inside ``[t0_s, end_s]`` (monotonic seconds) and clear of the capture."""
    by: dict = {}
    for e in doc.get("history") or ():
        for name, counts in (e.get("counts") or {}).items():
            if isinstance(counts, dict):
                by.setdefault(name, []).append(
                    (e["monotonic_ns"], flat(counts)))
    if not by:
        return []
    mine = sorted(max(by.values(), key=len), key=lambda e: e[0])
    inside = [e for e in mine if t0_s * 1e9 <= e[0] <= end_s * 1e9]
    cap0 = (doc.get("start") or {}).get("monotonic_ns")
    cap1 = (doc.get("end") or doc.get("stop") or {}).get("monotonic_ns")
    pairs = []
    for a, b in zip(inside, inside[1:]):
        if cap0 is not None and cap1 is not None and a[0] <= cap1 and b[0] >= cap0:
            continue
        pairs.append((a, b))
    return pairs


def growth(run) -> dict | None:
    """``{flat count: growth summed over the kept pairs}``, with
    ``pairs`` and ``seconds`` (what the pairs cover) beside them; None
    with fewer than ``MIN_PAIRS`` pairs or no history at all. Cached on
    the run; one reader note says what was kept."""
    got = getattr(run, "_count_history_growth", _ABSENT)
    if got is _ABSENT:
        got = None
        doc = program_spans.spans_doc(run)
        if doc is not None and doc.get("history"):
            pairs = kept_pairs(doc, run.t0, run.end)
            inside = sum(1 for e in doc["history"]
                         if run.t0 * 1e9 <= e["monotonic_ns"] <= run.end * 1e9)
            if len(pairs) >= MIN_PAIRS:
                got = {"pairs": len(pairs),
                       "seconds": sum(b[0] - a[0] for a, b in pairs) / 1e9}
                for a, b in pairs:
                    for k, v in b[1].items():
                        got[k] = got.get(k, 0) + v - a[1].get(k, 0)
            run.notes.append({"count_history": {
                "entries": len(doc["history"]), "in_window": inside,
                "pairs_kept": len(pairs),
                "seconds_kept": got["seconds"] if got else None}})
        run._count_history_growth = got
    return got


def total(g: dict, prefix: str) -> float:
    """Sum of every count under ``prefix`` (``dispatches.`` = all kinds)."""
    return sum(v for k, v in g.items() if k.startswith(prefix))


def per(run, numerator, denominator, scale: float = 1.0) -> float | None:
    """``scale * numerator(g) / denominator(g)`` over the untraced
    window's growth ``g``; None without one or with a zero denominator."""
    g = growth(run)
    if g is None:
        return None
    den = denominator(g)
    return scale * numerator(g) / den if den else None


def dispatches(g: dict) -> float:
    return total(g, "dispatches.")


def per_dispatch_ms(g: dict, names) -> dict:
    """ms a dispatch of each count in ``names`` (ns counts), rounded for
    a reader's note."""
    n = dispatches(g)
    return {k: round(g.get(k, 0) / n / 1e6, 4) for k in names} if n else {}
