"""Shared documents: each arriving DOCUMENT (Poisson at the mix's fixed
``doc_rate_rps``) is asked ``asks_per_doc`` short questions. Every ask
sends the whole document followed by its own fresh question and wants a
short answer; the first ask is due at the document's arrival and each
later one ``ask_gap_s`` after the DUE time of the one before, whether or
not that answer is done (open loop). A document's asks share exactly its
tokens as a prefix; no two documents share anything.

Entry (one per ask, ordered by due time): ``{i, due, prompt, out}`` as
``open_loop``'s (``prompt`` the whole prompt's tokens), and ``doc``,
``ask``, ``doc_tokens``, ``question`` beside them. ``due`` is in seconds
from the window's first instant (ramp entries < 0)."""

import math

import numpy as np

from perf.traffic.schedule import clipped_lognormal, uniform_int


def build(mix: dict, seconds: float, rng) -> dict:
    d, o = mix["doc_tokens"], mix["output_tokens"]
    q, n_asks, gap = mix["question_tokens"], mix["asks_per_doc"], mix["ask_gap_s"]
    cap = int(mix["max_total_tokens"])
    asks, t, doc = [], -float(mix["ramp_s"]), 0
    while True:
        # one draw of each per document, in a fixed order (the asks' own
        # draws from a generator of the document's, so that a longer
        # window extends the same schedule)
        t += float(rng.exponential(1.0 / float(mix["doc_rate_rps"])))
        tokens = clipped_lognormal(rng, d["median"], d["sigma"], d["min"], d["max"])
        sub = np.random.default_rng([int(mix["schedule_seed"]), 2, doc])
        if t >= seconds:
            break
        due = t
        for k in range(uniform_int(sub, n_asks["min"], n_asks["max"])):
            if k:
                due += float(sub.uniform(gap["min"], gap["max"]))
            question = uniform_int(sub, q["min"], q["max"])
            out = clipped_lognormal(sub, o["median"], o["sigma"], o["min"], o["max"])
            if due >= seconds:
                break
            asks.append({"due": round(due, 6), "doc": doc, "ask": k,
                         "doc_tokens": tokens, "question": question,
                         "prompt": tokens + question,
                         "out": min(out, cap - tokens - question)})
        doc += 1
    asks.sort(key=lambda a: (a["due"], a["doc"], a["ask"]))
    return {"entries": [dict(a, i=i) for i, a in enumerate(asks)]}


def totals(schedule: dict) -> dict:
    win = [e for e in schedule["entries"] if e["due"] >= 0]
    first = [e for e in win if e["ask"] == 0]
    prompt = sum(e["prompt"] for e in win)
    return {
        "requests": len(schedule["entries"]), "window_requests": len(win),
        "window_prompt_tokens": prompt,
        "window_output_tokens": sum(e["out"] for e in win),
        "documents": len({e["doc"] for e in schedule["entries"]}),
        "window_first_asks": len(first),
        # what the prefix cache can serve of the window's prompt tokens:
        # the document of every ask but a document's first
        "window_shared_token_share": round(
            sum(e["doc_tokens"] for e in win if e["ask"]) / max(1, prompt), 4),
    }


PROBE_DOCS = 6


def probe(mix: dict, rng) -> list[list[dict]]:
    """The output check's requests, from the mix alone. Wave 1: six
    documents at once, lengths spread over the mix (its shortest, its
    longest, four quantiles of its lognormal between), each with a
    question. Wave 2: the same six documents, each with a NEW question —
    the prefix cache serves the document's pages and the prefill starts
    past them. Rows are numbered so that of the sequences the reference
    reads (``check.compared``: the longest, then in row order while
    32 768 padded tokens hold them) the longest is the longest
    document's SECOND ask (it gets the longest question), then the
    shortest document's first and second ask and the next document's
    second: three of four are cache hits, and both ends of the context
    range are read."""
    d, q = mix["doc_tokens"], mix["question_tokens"]
    quantiles = (-1.2, -0.4, 0.4, 1.0)[:PROBE_DOCS - 2]
    lengths = sorted(
        [int(d["min"]), int(d["max"])]
        + [int(min(d["max"], max(d["min"], round(
            d["median"] * math.exp(d["sigma"] * z))))) for z in quantiles])
    lengths[1] = min(lengths[1], int(d["min"] * 1.15))   # pads like the shortest
    n = len(lengths)
    first_rows = [0] + list(range(3, n + 2))             # 0, 3, 4, 5, 6, 7
    second_rows = [1, 2] + list(range(n + 2, 2 * n))     # 1, 2, 8, 9, 10, 11
    cap = int(mix["max_total_tokens"])
    waves: list[list[dict]] = [[], []]
    for i, tokens in enumerate(lengths):
        longest = i == n - 1
        asks = (int(q["min"]) if longest else uniform_int(rng, q["min"], q["max"]),
                int(q["max"]) if longest else uniform_int(rng, q["min"], q["max"]))
        for wave, (row, new) in enumerate(zip((first_rows[i], second_rows[i]), asks)):
            waves[wave].append({"row": row, "shared": [9, i],
                                "shared_tokens": tokens, "new": max(1, new),
                                "room": cap - tokens - new})
    return waves


async def drive(load) -> None:
    """One task per ask: sleep until it is due, send the document and the
    question, stream to the end."""
    import asyncio

    docs: dict = {}

    def document(e: dict) -> list[int]:
        if e["doc"] not in docs:
            docs[e["doc"]] = load.ids((1, e["doc"]), e["doc_tokens"])
        return docs[e["doc"]]

    prompts = {e["i"]: document(e) + load.ids((2, e["doc"], e["ask"]), e["question"])
               for e in load.schedule["entries"]}

    async def one(e: dict) -> None:
        due = load.t0 + e["due"]
        await load.sleep_until(due)
        await load.request(("doc", e["doc"], e["ask"]), due,
                           prompts.pop(e["i"]), e["out"])

    await asyncio.gather(*(one(e) for e in load.schedule["entries"]))
