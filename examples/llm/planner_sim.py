"""Planner scale-up/down under sinusoidal load — the runnable analogue
of the reference's planner benchmark (reference:
docs/guides/planner_benchmark/sin_synth.py generates a sinusoidal
request rate; its README records the planner's replica trace against
it).

This drives the REAL Planner (dynamo_tpu/planner) in driven mode: a
sinusoidal offered load produces kv-cache-usage and prefill-queue
signals, scaled down by the replicas the planner has granted (adding a
worker absorbs load), and every tick is appended to a JSONL trace:

    python -m examples.llm.planner_sim --out planner_trace.jsonl

A recorded trace ships at examples/llm/planner_trace.jsonl.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
from dataclasses import dataclass, field


@dataclass
class RecordingConnector:
    """Grants every adjustment and remembers the story."""

    events: list = field(default_factory=list)

    async def add_component(self, component: str) -> bool:
        self.events.append(("add", component))
        return True

    async def remove_component(self, component: str) -> bool:
        self.events.append(("remove", component))
        return True


async def simulate(
    out_path: str,
    period_ticks: int = 60,
    cycles: float = 2.0,
    peak_kv_load: float = 3.2,
    peak_queue: float = 6.0,
) -> dict:
    """One adjustment per tick (adjustment_interval collapsed for the
    simulation); returns a summary dict."""
    from dynamo_tpu.planner import Planner, PlannerConfig

    conn = RecordingConnector()
    cfg = PlannerConfig(grace_cycles=2, min_decode=1, max_decode=6,
                        min_prefill=0, max_prefill=4)
    planner = Planner(
        store=None, component=None, connector=conn, config=cfg,
        decode_workers=1, prefill_workers=1,
    )
    n_ticks = int(period_ticks * cycles)
    trace = []
    with open(out_path, "w") as fh:
        for t in range(n_ticks):
            # offered load: sinusoid in [0, 1]
            offered = 0.5 * (1.0 - math.cos(2 * math.pi * t / period_ticks))
            # each granted worker absorbs a share of the offered load
            snap = {
                "kv_load_mean": min(
                    1.0, peak_kv_load * offered / planner.decode_workers
                ),
                "prefill_queue_depth": peak_queue * offered,
                "prefill_queue_per_worker": (
                    peak_queue * offered / max(1, planner.prefill_workers)
                ),
                "decode_workers_reporting": float(planner.decode_workers),
                "tick": t,
            }
            await planner.make_adjustments(snap)
            row = {
                **snap,
                "decode_workers": planner.decode_workers,
                "prefill_workers": planner.prefill_workers,
            }
            trace.append(row)
            fh.write(json.dumps(row) + "\n")
    ups = sum(1 for e in conn.events if e[0] == "add")
    downs = sum(1 for e in conn.events if e[0] == "remove")
    return {
        "ticks": n_ticks,
        "scale_ups": ups,
        "scale_downs": downs,
        "peak_decode_workers": max(r["decode_workers"] for r in trace),
        "final_decode_workers": trace[-1]["decode_workers"],
        "events": conn.events,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="planner_trace.jsonl")
    p.add_argument("--period-ticks", type=int, default=60)
    p.add_argument("--cycles", type=float, default=2.0)
    args = p.parse_args()
    summary = asyncio.run(
        simulate(args.out, args.period_ticks, args.cycles)
    )
    summary.pop("events")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
