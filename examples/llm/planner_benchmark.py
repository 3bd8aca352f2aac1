"""Planner vs static fleets under the reference's sinusoidal workload —
the recorded analogue of the reference planner benchmark (reference:
docs/guides/planner_benchmark/benchmark_planner.md — planner vs a
static 2p2d baseline on a sin_synth.py workload: 1.5x request
throughput per resource at -7.4% GPU-hours).

Model: a sinusoidal offered token rate (sin_synth.py's shape) hits a
fleet of decode workers, each serving ``tokens_per_worker_tick``.
Unserved demand queues (the latency proxy). Three fleets run the SAME
workload:

- ``planner``   — the REAL Planner (driven mode) scales workers from
                  kv-load / queue signals, exactly as planner_sim.py;
- ``static-peak`` — fixed at the planner's peak grant (the
                  capacity-planning answer: meets demand, burns
                  worker-hours all night);
- ``static-mean`` — fixed at mean-load sizing (cheap, melts at peaks).

Outputs one JSON line per fleet: served tokens, goodput (served /
offered), worker-ticks (the resource-hours analogue), tokens per
worker-tick (efficiency), and peak backlog. tests/test_examples.py
asserts the planner's win holds.

    python -m examples.llm.planner_benchmark
"""

from __future__ import annotations

import asyncio
import json
import math
from dataclasses import dataclass, field


@dataclass
class FleetStats:
    name: str
    served: float = 0.0
    offered: float = 0.0
    worker_ticks: int = 0
    backlog_peak: float = 0.0
    workers_trace: list = field(default_factory=list)

    def summary(self) -> dict:
        return {
            "fleet": self.name,
            "offered_tokens": round(self.offered, 1),
            "served_tokens": round(self.served, 1),
            "goodput": round(self.served / max(1e-9, self.offered), 4),
            "worker_ticks": self.worker_ticks,
            "tokens_per_worker_tick": round(
                self.served / max(1, self.worker_ticks), 2
            ),
            "backlog_peak_tokens": round(self.backlog_peak, 1),
            "peak_workers": max(self.workers_trace or [0]),
        }


def _offered(t: int, period: int, peak_tokens: float) -> float:
    """sin_synth.py's request-rate shape, scaled to tokens/tick."""
    return peak_tokens * 0.5 * (1.0 - math.cos(2 * math.pi * t / period))


async def run_fleet(
    policy: str,
    n_ticks: int,
    period: int,
    peak_tokens: float = 1200.0,
    tokens_per_worker_tick: float = 300.0,
    fixed_workers: int = 0,
    name: str = "",
) -> FleetStats:
    """One fleet over the shared workload. ``policy`` is "planner" or
    "static" (with ``fixed_workers``); ``name`` labels the stats row."""
    from dynamo_tpu.planner import Planner, PlannerConfig

    planner = None
    if policy == "planner":
        class _Grant:
            async def add_component(self, component):
                return True

            async def remove_component(self, component):
                return True

        cfg = PlannerConfig(grace_cycles=2, min_decode=1, max_decode=8,
                            min_prefill=0, max_prefill=4)
        planner = Planner(store=None, component=None, connector=_Grant(),
                          config=cfg, decode_workers=1, prefill_workers=1)

    stats = FleetStats(name=name or policy)
    backlog = 0.0
    for t in range(n_ticks):
        offered = _offered(t, period, peak_tokens)
        workers = planner.decode_workers if planner else fixed_workers
        capacity = workers * tokens_per_worker_tick
        demand = backlog + offered
        served = min(demand, capacity)
        backlog = demand - served
        stats.offered += offered
        stats.served += served
        stats.worker_ticks += workers
        stats.backlog_peak = max(stats.backlog_peak, backlog)
        stats.workers_trace.append(workers)
        if planner:
            # the same driven-mode signals planner_sim.py synthesizes:
            # utilization of the granted fleet + queue pressure
            util = demand / max(1e-9, capacity)
            snap = {
                "kv_load_mean": min(1.0, util),
                "prefill_queue_depth": max(0.0, util - 1.0) * 8.0,
                "prefill_queue_per_worker": (
                    max(0.0, util - 1.0) * 8.0
                    / max(1, planner.prefill_workers)
                ),
                "decode_workers_reporting": float(planner.decode_workers),
                "tick": t,
            }
            await planner.make_adjustments(snap)
    return stats


async def compare(period: int = 60, cycles: float = 3.0) -> list[dict]:
    n_ticks = int(period * cycles)
    dyn = await run_fleet("planner", n_ticks, period)
    peak = max(dyn.workers_trace)
    mean = max(1, round(sum(dyn.workers_trace) / len(dyn.workers_trace)))
    static_peak = await run_fleet(
        "static", n_ticks, period, fixed_workers=peak, name="static-peak"
    )
    static_mean = await run_fleet(
        "static", n_ticks, period, fixed_workers=mean, name="static-mean"
    )
    return [s.summary() for s in (dyn, static_peak, static_mean)]


def main() -> None:
    rows = asyncio.run(compare())
    for row in rows:
        print(json.dumps(row))


if __name__ == "__main__":
    main()
