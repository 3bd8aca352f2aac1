"""Share (%) of the device's busy seconds in the traced interval that
prefill-class programs took (executions on the "XLA Modules" line inside
which a ``paged_attention_prefill_stacked*`` op ran); both classes' call
counts and medians are in the ``program_steps`` note."""
from perf.trace import program_spans


def read(run, variant=""):
    got = program_spans.steps(run)
    if got is None or got["busy_s"] <= 0 or not got["programs"]:
        return None
    prefill = got["programs"].get("prefill", {"total_s": 0.0})
    return 100.0 * prefill["total_s"] / got["busy_s"]
