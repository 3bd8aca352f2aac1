"""85th percentile (nearest rank) of the time in the scheduler's waiting
queue over the window's requests: the turns that waited behind another
turn's prefill chunk (``engine.queue_wait`` spans)."""
from perf import measure
from perf.trace import program_spans


def read(run, variant=""):
    waits = program_spans.durations_ms(run, "engine.queue_wait")
    return measure.percentile(waits, 85) if waits else None
