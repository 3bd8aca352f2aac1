"""Output tokens received inside the window / its length."""
from perf import measure


def read(run, variant=""):
    return measure.tokens_in_window(run) / run.seconds
