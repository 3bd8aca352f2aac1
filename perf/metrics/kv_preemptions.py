"""Requests preempted in the window (/debug/state delta)."""


def read(run, variant=""):
    return float(run.snap_after["preemptions"] - run.snap_before["preemptions"])
