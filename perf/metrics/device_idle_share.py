"""1 - union of device-op intervals / traced interval, percent. The
interval is the profiler's start_trace..stop_trace span; an edge the
trace does not show is the first / last device op, and the note says so."""


def read(run, variant=""):
    red = run.trace
    if not red or red["window_s"] <= 0:
        return None
    run.notes.append({"device_idle_share": {
        "interval_from": red["interval_from"], "window_s": red["window_s"],
        "ops_span_s": red["ops_span_s"], "busy_s": red["busy_s"]}})
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
