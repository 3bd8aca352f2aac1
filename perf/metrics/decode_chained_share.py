"""Share (%) of the decode pipeline's dispatches that were chained onto a
step still in flight, over the capture: the growth of
``decode_dispatches_chained`` over that of ``decode_dispatches``
(``program_spans.json`` counts; the engine counts every single-step
decode dispatch of its overlapped pipeline, and those it issued before
harvesting the step before). Near 100 the device never waits out the
host's step; near 0 the serial loop ran (somebody was waiting whom the
planner could place, or a finish at every step). A program that keeps no
such counts (an older commit, a serial or fused-window engine that
dispatched none) gives nothing to read."""
from perf.metrics import kimi_linear_costs as costs


def read(run, variant=""):
    deltas = costs.count_deltas(run)
    if not deltas:
        return None
    chained = costs.engine_count(deltas, "decode_dispatches_chained")
    total = costs.engine_count(deltas, "decode_dispatches")
    if not total or chained is None:
        return None
    return 100.0 * chained / total
