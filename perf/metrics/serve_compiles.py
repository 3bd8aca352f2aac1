"""Compilations the fence recorded in the window (must read 0)."""


def read(run, variant=""):
    if run.snap_after.get("fence_mode") != "record":
        return None
    return float(run.snap_after["compile_events"]
                 - run.snap_before["compile_events"])
