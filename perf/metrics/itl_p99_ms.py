"""99th percentile of the gap between consecutive chunks of a stream, ms,
over every gap that ends inside the window (client side, host clock): a
prefill chunk that stalls decode shows here directly."""
from perf import measure


def read(run, variant=""):
    gaps = [(t - before) * 1e3
            for r in run.records if not r.failed
            for (before, _), (t, _) in zip(r.chunks, r.chunks[1:])
            if run.t0 <= t < run.end]
    return measure.percentile(gaps, 99)
