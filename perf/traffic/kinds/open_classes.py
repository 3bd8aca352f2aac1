"""Open loop over length CLASSES: independent users of one endpoint, some
of whom send everyday chat turns and some pasted repositories, logs and
agent contexts — short and long requests in ONE queue. Requests are due
at Poisson offsets at the mix's fixed ``rate_rps``, whether or not
earlier ones have finished; each first draws its class by the classes'
``share`` and then its lengths from that class's clipped lognormals.
Entry: ``open_loop``'s ``{i, due, prompt, out}`` and ``class`` (the
class's name), so every reader of an open-loop schedule or record reads
this one; ``sweep.py --param rate_rps`` varies it as it stands."""

from perf.traffic.kinds.open_loop import drive, totals  # noqa: F401
from perf.traffic.schedule import clipped_lognormal


def _lengths(rng, cls: dict) -> tuple[int, int]:
    p, o = cls["prompt_tokens"], cls["output_tokens"]
    return (clipped_lognormal(rng, p["median"], p["sigma"], p["min"], p["max"]),
            clipped_lognormal(rng, o["median"], o["sigma"], o["min"], o["max"]))


def _classes(mix: dict) -> tuple[list[str], list[float]]:
    names = list(mix["classes"])
    shares = [float(mix["classes"][n]["share"]) for n in names]
    if abs(sum(shares) - 1.0) > 1e-9:
        raise ValueError(f"class shares {shares} do not add up to 1")
    return names, shares


def build(mix: dict, seconds: float, rng) -> dict:
    names, shares = _classes(mix)
    cap = int(mix["max_total_tokens"])
    entries, t = [], -float(mix["ramp_s"])
    while True:
        # one draw of each per entry, in a fixed order: a longer window
        # extends the same schedule
        gap = float(rng.exponential(1.0 / float(mix["rate_rps"])))
        u = float(rng.random())
        name = names[-1]
        for n, share in zip(names, shares):
            if u < share:
                name = n
                break
            u -= share
        prompt, out = _lengths(rng, mix["classes"][name])
        t += gap
        if t >= seconds:
            break
        entries.append({"i": len(entries), "due": round(t, 6), "prompt": prompt,
                        "out": min(out, cap - prompt), "class": name})
    return {"entries": entries}


def probe(mix: dict, rng) -> list[list[dict]]:
    """The output check's requests, from the mix alone: one wave of eight
    rows at once — the first at the LONGEST class's cap (``room`` keeps
    its answer inside ``max_total_tokens``), six drawn from the shortest
    class, and LAST in row order one of the longest class's median: the
    comparison reads the longest row and then rows in order while they
    fit, so it always holds the row whose window pages were released all
    along its prompt, the short rows, and the median long row where that
    fits too."""
    cap = int(mix["max_total_tokens"])
    by_len = sorted(mix["classes"].values(),
                    key=lambda c: c["prompt_tokens"]["median"])
    short, long_ = by_len[0], by_len[-1]
    prompts = [int(long_["prompt_tokens"]["max"])]
    prompts += [_lengths(rng, short)[0] for _ in range(6)]
    prompts.append(int(long_["prompt_tokens"]["median"]))
    return [[{"row": r, "new": n, "room": cap - n}
             for r, n in enumerate(prompts)]]
