"""Closed loop: ``clients`` callers that each wait for a reply before
sending the next request of their own fixed list. Each client's FIRST
request has its output cut to a scheduled phase, so completions are
spread evenly from the window's first second instead of arriving as one
wave. Entry lists: ``clients[c] = [{prompt, out}, ...]``."""

import math

from perf.traffic.schedule import uniform_int


def build(mix: dict, seconds: float, rng) -> dict:
    n = int(mix["clients"])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    span = float(mix["ramp_s"]) + seconds
    # enough requests that no client runs dry even at the fastest pace
    # the mix allows for (a client that does is a failed run, not silence)
    per_client = 2 + math.ceil(span / (o["min"] * float(mix["fastest_tpot_s"])))
    clients = []
    for c in range(n):
        reqs = [{"prompt": uniform_int(rng, p["min"], p["max"]),
                 "out": uniform_int(rng, o["min"], o["max"])}
                for _ in range(per_client)]
        reqs[0]["out"] = max(int(mix["first_out_min"]),
                             round(reqs[0]["out"] * (c + 1) / n))
        clients.append(reqs)
    return {"clients": clients}


def totals(schedule: dict) -> dict:
    reqs = [r for c in schedule["clients"] for r in c]
    return {
        "clients": len(schedule["clients"]), "requests": len(reqs),
        "listed_prompt_tokens": sum(r["prompt"] for r in reqs),
        "listed_output_tokens": sum(r["out"] for r in reqs),
    }


def probe(mix: dict, rng) -> list[list[dict]]:
    """The output check's requests, from the mix alone: one wave with a
    row per client, so that decode runs at the batch the window runs at;
    prompts from the mix's own range."""
    p = mix["prompt_tokens"]
    return [[{"row": c, "new": uniform_int(rng, p["min"], p["max"])}
             for c in range(int(mix["clients"]))]]


async def drive(load) -> None:
    """Each client sends its list in order, the next as soon as the
    previous answer ended (that instant is the request's due time). At
    the window's end the requests in flight are cancelled: their tokens
    received inside the window count, they are neither complete nor
    failed."""
    import asyncio
    import time

    ramp_start = load.t0 - float(load.schedule["ramp_s"])
    stagger = float(load.mix.get("start_stagger_s", 0.0))

    async def client(c: int, reqs: list[dict]) -> None:
        due = ramp_start + stagger * c
        await load.sleep_until(due)
        for k, r in enumerate(reqs):
            rec = await load.request(
                ("closed", c, k), due, load.ids((0, c, k), r["prompt"]), r["out"])
            if rec.failed:
                return
            due = time.monotonic()
        raise RuntimeError(f"client {c} ran out of scheduled requests")

    tasks = [asyncio.ensure_future(client(c, reqs))
             for c, reqs in enumerate(load.schedule["clients"])]
    await load.sleep_until(load.end)
    load.cancelling = True
    for t in tasks:
        t.cancel()
    for t in tasks:
        try:
            await t
        except asyncio.CancelledError:
            pass
