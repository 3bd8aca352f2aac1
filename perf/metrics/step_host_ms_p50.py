"""Median host work per engine step in the capture, ms: what
``dyn.step.plan`` + ``pack`` + ``dispatch`` + ``emit`` + ``record`` took
between one dispatch and the next (``harvest`` and ``wait`` left out:
the host waits there). perf/trace/program_spans.py."""
from perf import measure
from perf.trace import program_spans


def read(run, variant=""):
    got = program_spans.steps(run)
    if got is None:
        return None
    return measure.percentile(got["host_step_ms"], 50)
