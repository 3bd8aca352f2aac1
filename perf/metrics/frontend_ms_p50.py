"""Median time a request spends in the HTTP frontend before the engine
has it: ``http.request`` start to ``engine.queue_wait`` start of the same
trace (parse, validate, tokenize, hand over), both on CLOCK_MONOTONIC."""
from perf import measure
from perf.trace import program_spans


def read(run, variant=""):
    reqs = program_spans.requests(run)
    ms = [(t["engine.queue_wait"]["start_mono_ns"]
           - t["http.request"]["start_mono_ns"]) / 1e6
          for t in reqs or [] if "http.request" in t]
    return measure.percentile(ms, 50) if ms else None
