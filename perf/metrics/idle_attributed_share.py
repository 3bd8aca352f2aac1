"""Share (%) of the device's idle seconds (gaps >= 0.5 ms over the traced
interval) that lie under a ``dyn.step.*`` phase of the engine thread; the
seconds by phase are in the ``program_steps`` note."""
from perf.trace import program_spans


def read(run, variant=""):
    got = program_spans.steps(run)
    if got is None:
        return None
    idle = got["idle_by_phase"]
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * (total - idle.get("none", 0.0)) / total
