"""Median over the window's requests of first token time - DUE time."""
from perf import measure


def read(run, variant=""):
    recs = measure.finished(measure.window_records(run))
    return measure.percentile([measure.ttft_ms(r) for r in recs], 50)
