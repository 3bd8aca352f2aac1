"""Open loop: independent users. Requests are due at Poisson offsets at
the mix's fixed ``rate_rps``, whether or not earlier ones have finished;
lengths are clipped lognormals. Entry: ``{i, due, prompt, out}`` with
``due`` in seconds from the window's first instant (ramp entries < 0)."""

import math

from perf.traffic.schedule import clipped_lognormal


def build(mix: dict, seconds: float, rng) -> dict:
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    cap = int(mix["max_total_tokens"])
    entries, t = [], -float(mix["ramp_s"])
    while True:
        # one draw of each per entry, in a fixed order: a longer window
        # extends the same schedule
        gap = float(rng.exponential(1.0 / float(mix["rate_rps"])))
        prompt = clipped_lognormal(rng, p["median"], p["sigma"], p["min"], p["max"])
        out = clipped_lognormal(rng, o["median"], o["sigma"], o["min"], o["max"])
        t += gap
        if t >= seconds:
            break
        out = min(out, cap - prompt)
        entries.append({"i": len(entries), "due": round(t, 6),
                        "prompt": prompt, "out": out})
    return {"entries": entries}


def totals(schedule: dict) -> dict:
    win = [e for e in schedule["entries"] if e["due"] >= 0]
    return {
        "requests": len(schedule["entries"]), "window_requests": len(win),
        "window_prompt_tokens": sum(e["prompt"] for e in win),
        "window_output_tokens": sum(e["out"] for e in win),
    }


def probe(mix: dict, rng) -> list[list[dict]]:
    """The output check's requests, from the mix alone: one wave of as
    many rows as the mix keeps running at its latency limit (Little's
    law: rate x mean answer length x the limit on the token gap), prompts
    from the mix's own distribution with the first at the cap, so that
    multi-chunk prefill and the longest page lists are among them."""
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    draws = [(clipped_lognormal(rng, p["median"], p["sigma"], p["min"], p["max"]),
              clipped_lognormal(rng, o["median"], o["sigma"], o["min"], o["max"]))
             for _ in range(256)]
    mean_out = sum(d[1] for d in draws) / len(draws)
    gap_s = float((mix.get("slo") or {}).get("gap_ms", 60)) / 1000.0
    rows = max(8, math.ceil(float(mix["rate_rps"]) * mean_out * gap_s))
    prompts = [p["max"]] + [d[0] for d in draws[:rows - 1]]
    return [[{"row": r, "new": n, "room": int(mix["max_total_tokens"]) - n}
             for r, n in enumerate(prompts)]]


async def drive(load) -> None:
    """One task per entry: sleep until it is due, send, stream to the end."""
    import asyncio

    prompts = {e["i"]: load.ids((0, e["i"]), e["prompt"])
               for e in load.schedule["entries"]}

    async def one(e: dict) -> None:
        due = load.t0 + e["due"]
        await load.sleep_until(due)
        await load.request(("open", e["i"]), due, prompts[e["i"]], e["out"])

    await asyncio.gather(*(one(e) for e in load.schedule["entries"]))
