"""Share (%) of the prefill dispatches that the decode pipeline issued IN
LINE — behind a step still in flight, without emptying itself first — in
the UNTRACED window: the growth of ``prefill_dispatches_inline`` over that
of ``dispatches.prefill`` (``program_spans.json`` ``history``;
perf/trace/count_history.py). Near 100 an arrival's prefill follows the
step in flight with no host-made gap before or after it; the rest are
prefills on an engine that was decoding nothing (the way in: no step was in
flight to go behind — most of a cell whose arrivals find the engine idle)
and those the pipeline emptied itself for (``pipeline_drains.*`` in the
same history says why). A program that keeps no such count (an older
commit) gives nothing to read, and neither does a window without a
prefill dispatch."""
from perf.trace import count_history as ch

COUNT = "prefill_dispatches_inline"


def read(run, variant=""):
    g = ch.growth(run)
    if g is None or COUNT not in g:
        return None
    n = g.get("dispatches.prefill", 0)
    if not n:
        return None
    run.notes.append({"inline_admit_share": {
        "prefill_dispatches": n, COUNT: g[COUNT],
        "finishes_inline": g.get("finishes_inline"),
        "pipeline_drains": {k[len("pipeline_drains."):]: v
                            for k, v in g.items()
                            if k.startswith("pipeline_drains.")}}})
    return 100.0 * g[COUNT] / n
