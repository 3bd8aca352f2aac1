"""Share (%) of the window's requests with TTFT and mean token gap inside
the mix's limits; a failed request misses."""
from perf import measure


def read(run, variant=""):
    slo = run.mix.get("slo")
    recs = measure.window_records(run)
    if not slo or not recs:
        return None
    met = 0
    for r in recs:
        gap = measure.tpot_ms(r)
        if (not r.failed and measure.ttft_ms(r) <= slo["ttft_ms"]
                and (gap is None or gap <= slo["gap_ms"])):
            met += 1
    return 100.0 * met / len(recs)
