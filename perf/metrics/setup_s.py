"""Process start to the window's first instant, seconds (host clock)."""


def read(run, variant=""):
    return run.setup_s
