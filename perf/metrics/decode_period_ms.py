"""The decode step's period in the UNTRACED window, ms: wall from one
decode dispatch's start to the next dispatch's, the engine's ``wait``
between them left out, as the engine's own clock counts it always —
``period_ns.<kind>`` over ``dispatches.<kind>`` (``program_spans.json``
``history``; perf/trace/count_history.py). The kind is ``decode``; where a
program's decode rides another kind (``window``, ``mixed``, ``spec``) the
one of those it dispatched most, and the note says which. Set it beside
``step_device_ms_p50.*`` (the device's share of it) and ``tpot_mean_ms``.
A program without the history (an older commit) gives nothing to read."""
from perf.trace import count_history as ch

KINDS = ("decode", "window", "mixed", "spec")


def read(run, variant=""):
    g = ch.growth(run)
    if g is None:
        return None
    kind = max(KINDS, key=lambda k: g.get(f"dispatches.{k}", 0))
    n = g.get(f"dispatches.{kind}", 0)
    if not n:
        return None
    run.notes.append({"decode_period_ms": {
        "kind": kind, "dispatches": n, "pairs": g["pairs"],
        "dispatches_by_kind": {k[len("dispatches."):]: v for k, v in g.items()
                               if k.startswith("dispatches.")},
        "period_ms_by_kind": {
            k[len("period_ns."):]: round(
                v / g[f"dispatches.{k[len('period_ns.'):]}"] / 1e6, 4)
            for k, v in g.items() if k.startswith("period_ns.")
            and g.get(f"dispatches.{k[len('period_ns.'):]}")}}})
    return g.get(f"period_ns.{kind}", 0) / n / 1e6
