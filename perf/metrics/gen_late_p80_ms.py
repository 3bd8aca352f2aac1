"""How late the generator sent: 80th percentile of send time - due time
over every request of ramp and window."""
from perf import measure


def read(run, variant=""):
    recs = [r for r in run.records if r.sent and r.due < run.end]
    return measure.percentile([measure.late_ms(r) for r in recs], 80)
