"""Open loop with bursts: independent users whose requests arrive in
clumps. As ``open_loop`` — requests are due whether or not earlier ones
have finished, lengths are clipped lognormals — but the gaps between
arrivals are drawn from a gamma distribution of shape ``arrival_shape``
and mean ``1 / rate_rps``: their coefficient of variation is
``arrival_shape ** -0.5`` (1 at shape 1, the Poisson process of
``open_loop``; 2 at shape 0.25: most gaps far below the mean, a few many
times it). Entry: ``{i, due, prompt, out}``, ``due`` in seconds from the
window's first instant (ramp entries < 0)."""

from perf.traffic.kinds import open_loop
from perf.traffic.schedule import clipped_lognormal

totals = open_loop.totals
drive = open_loop.drive


def build(mix: dict, seconds: float, rng) -> dict:
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    cap = int(mix["max_total_tokens"])
    shape = float(mix["arrival_shape"])
    scale = 1.0 / (float(mix["rate_rps"]) * shape)
    entries, t = [], -float(mix["ramp_s"])
    while True:
        # one draw of each per entry, in a fixed order: a longer window
        # extends the same schedule
        gap = float(rng.gamma(shape, scale))
        prompt = clipped_lognormal(rng, p["median"], p["sigma"], p["min"], p["max"])
        out = clipped_lognormal(rng, o["median"], o["sigma"], o["min"], o["max"])
        t += gap
        if t >= seconds:
            break
        out = min(out, cap - prompt)
        entries.append({"i": len(entries), "due": round(t, 6),
                        "prompt": prompt, "out": out})
    return {"entries": entries}


def probe(mix: dict, rng) -> list[list[dict]]:
    """``open_loop``'s probe — one wave of the rows the mix keeps running
    at its latency limit, the first prompt at the cap — with at most
    ``probe_rows_max`` rows: what the server can hold at once (a model
    with recurrent state runs no more rows than it has state slots)."""
    cap = int(mix["probe_rows_max"])
    return [wave[:cap] for wave in open_loop.probe(mix, rng)]
