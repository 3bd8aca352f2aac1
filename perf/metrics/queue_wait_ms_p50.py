"""Median time in the scheduler's waiting queue (submit to first
admission) over the window's requests: ``engine.queue_wait`` spans of the
program's own span file (perf/trace/program_spans.py)."""
from perf import measure
from perf.trace import program_spans


def read(run, variant=""):
    waits = program_spans.durations_ms(run, "engine.queue_wait")
    return measure.percentile(waits, 50) if waits else None
