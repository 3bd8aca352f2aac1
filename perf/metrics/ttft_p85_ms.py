"""85th percentile (nearest rank) of first token time - DUE time over the
window's requests; nothing when fewer than ten samples lie beyond it."""
from perf import measure


def read(run, variant=""):
    recs = measure.finished(measure.window_records(run))
    return measure.percentile([measure.ttft_ms(r) for r in recs], 85)
