"""Share (%) of the requests admitted in the window that found a cached
prefix: /debug/state scheduler prefix_hits / prefix_queries, delta."""


def read(run, variant=""):
    a, b = run.snap_before, run.snap_after
    q = b["prefix_queries"] - a["prefix_queries"]
    if q <= 0:
        return None
    return 100.0 * (b["prefix_hits"] - a["prefix_hits"]) / q
