"""Arithmetic from request records to numbers (percentiles copied from
``benchmarks/load_gen.py``: nearest rank)."""

from __future__ import annotations

import math

MIN_BEYOND = 10  # a percentile is reported only with this many samples beyond it


def percentile(xs: list[float], p: float) -> float | None:
    """Nearest-rank percentile, or None when fewer than ``MIN_BEYOND``
    samples lie beyond it (it would be a maximum, not a percentile)."""
    n = len(xs)
    rank = math.ceil(n * p / 100.0)
    if n == 0 or n - rank < MIN_BEYOND:
        return None
    return sorted(xs)[max(0, rank - 1)]


def deciles(xs: list[float]) -> list[float]:
    """Minimum, the nine deciles (nearest rank) and maximum: the shape of
    a distribution on one earlier line, whatever its sample count."""
    xs = sorted(xs)
    if not xs:
        return []
    return [round(xs[max(0, math.ceil(len(xs) * k / 10) - 1)], 1) for k in range(11)]


def beyond(n: int, p: float) -> int:
    return n - math.ceil(n * p / 100.0)


def ttft_ms(rec) -> float:
    return (rec.first - rec.due) * 1000.0


def tpot_ms(rec) -> float | None:
    if rec.received < 2:
        return None
    return (rec.last - rec.first) * 1000.0 / (rec.received - 1)


def late_ms(rec) -> float:
    return (rec.sent - rec.due) * 1000.0


def window_records(run) -> list:
    """Requests whose DUE time falls in the window and that were not
    cancelled by the generator itself."""
    return [r for r in run.records
            if run.t0 <= r.due < run.end and not r.cancelled]


def finished(recs: list) -> list:
    return [r for r in recs if not r.failed and r.received > 0]


def tokens_in_window(run) -> int:
    return sum(n for r in run.records for t, n in r.chunks
               if run.t0 <= t < run.end)
