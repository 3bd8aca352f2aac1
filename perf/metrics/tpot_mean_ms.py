"""Mean over the window's requests of (last - first token time) /
(output tokens - 1)."""
from perf import measure


def read(run, variant=""):
    vals = [measure.tpot_ms(r)
            for r in measure.finished(measure.window_records(run))]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
