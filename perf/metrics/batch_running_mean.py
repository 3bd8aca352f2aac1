"""Mean of the scheduler's running count over the window's samples."""


def read(run, variant=""):
    if not run.samples:
        return None
    return sum(s["running"] for s in run.samples) / len(run.samples)
