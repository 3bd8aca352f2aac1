"""How ``correct`` is decided: the program's chosen-token logprobs against
the plain reference's, on probe requests sent outside the window.

The probe is the CELL's own: the traffic kind lays it out from the mix
file alone (``perf/traffic/kinds/<kind>.py`` ``probe``) — as many rows at
once as the mix keeps running, prompts and contexts as long as the mix
sends them, a second wave that resends the first's prompt and answer
where the mix shares prefixes. So the programs, page lists and cache
paths the window ran are the ones that are checked. Lengths never see
``--seed``; it picks the ids. The program returns, per generated token,
the id it chose and the logprob it gave it. The reference (a child of its
own, started after the server has released the chip) runs its float32
forward pass over prompt + chosen ids and gives ITS logprob of the same
ids. Every row is sent; the reference reads as many whole rows as fit
``REFERENCE_TOKENS`` (the longest first, then in row order).

The number compared is ``logprob_err_mean``: the mean over all compared
positions of |program - reference|. ``logprob_err_max`` is printed
beside it. Limits: ``perf/reference/limits/<cell>.json``, set from
readings (PERF.md, section 2).

Run as a module it is the reference child:
``python -m perf.reference.check <job.json>`` prints one JSON line.
"""

from __future__ import annotations

import json
import math
import os
import sys

from perf.reference.family import family_of

HERE = os.path.dirname(os.path.abspath(__file__))
POSITIONS = 768           # generated positions a probe aims at, all rows and waves
REFERENCE_TOKENS = 32768  # padded tokens the reference reads in one run
LENGTHS = (512, 1024, 2048, 3072, 4096)  # a sequence is padded to one of these
PER_ROW = 64              # compared positions per sequence are padded to a multiple
ATTENTION_CELLS = 8 * 1024 * 1024  # rows x T x T the reference holds at once


def probe_waves(mix: dict) -> list[list[dict]]:
    """The cell's probe: waves of jobs ``{row, new, out[, shared,
    shared_tokens][, after]}``, a function of the mix file alone."""
    import numpy as np

    from perf.traffic.schedule import kind_module

    rng = np.random.default_rng([int(mix["schedule_seed"]), 1])
    waves = kind_module(mix["kind"]).probe(mix, rng)
    rows = sum(len(w) for w in waves)
    out = max(16, 8 * math.ceil(POSITIONS / rows / 8))
    for w, wave in enumerate(waves):
        for job in wave:
            job["wave"] = w
            job["out"] = min(out, job.pop("room", out))
    return waves


def wave_jobs(seed: int, vocab: int, wave: list[dict],
              earlier: list[dict]) -> list[dict]:
    """A wave's requests ``{row, ids, out}``: the group's shared ids or
    the whole sequence of the row it follows, then new ids from the seed."""
    from perf.traffic.schedule import token_ids

    before = {a["row"]: a for a in earlier}
    jobs = []
    for job in wave:
        if "after" in job:
            head = before[job["after"]]["ids"] + before[job["after"]]["chosen"]
        elif "shared" in job:
            head = token_ids(seed, tuple(job["shared"]), job["shared_tokens"], vocab)
        else:
            head = []
        ids = head + token_ids(seed, (20 + job["wave"], job["row"]), job["new"], vocab)
        jobs.append({"row": job["row"], "wave": job["wave"], "ids": ids,
                     "out": job["out"], "after": job.get("after")})
    return jobs


def sequences(answers: list[dict]) -> list[dict]:
    """One sequence per chain of answers: a follow-up's prompt + answer
    holds the row it follows as a prefix, so one forward pass over it
    reads every wave's positions. ``{tokens, at, chosen, logprobs}``
    with ``at[i]`` the position whose logits chose ``chosen[i]``."""
    followed = {(a["wave"] - 1, a["after"]) for a in answers
                if a.get("after") is not None}
    by_key = {(a["wave"], a["row"]): a for a in answers}
    seqs = []
    for a in answers:
        if (a["wave"], a["row"]) in followed:
            continue
        chain = [a]
        while chain[0].get("after") is not None:
            chain.insert(0, by_key[(chain[0]["wave"] - 1, chain[0]["after"])])
        seq = {"row": a["row"], "tokens": a["ids"] + a["chosen"],
               "at": [], "chosen": [], "logprobs": []}
        for link in chain:
            whole = link["ids"] + link["chosen"]
            if seq["tokens"][:len(whole)] != whole:
                raise ValueError("a follow-up does not extend the row it follows")
            n = len(link["chosen"])
            seq["at"] += [len(link["ids"]) - 1 + i for i in range(n)]
            seq["chosen"] += link["chosen"]
            seq["logprobs"] += link["logprobs"]
        seqs.append(seq)
    return seqs


def padded(n: int) -> int:
    """Few lengths, so the reference compiles few programs (each takes the
    chip's compiler ~25 s, once per checkout)."""
    for length in LENGTHS:
        if n <= length:
            return length
    return -(-n // 1024) * 1024


def compared(seqs: list[dict]) -> list[dict]:
    """The sequences the reference reads: the longest, then in row order
    while they fit ``REFERENCE_TOKENS`` padded tokens."""
    longest = max(seqs, key=lambda s: len(s["tokens"]))
    take, used = [longest], padded(len(longest["tokens"]))
    for s in sorted(seqs, key=lambda s: s["row"]):
        if s is longest:
            continue
        if used + padded(len(s["tokens"])) > REFERENCE_TOKENS:
            break
        take.append(s)
        used += padded(len(s["tokens"]))
    return take


def load_limits(cell: str) -> dict:
    """The cell's limits: ``perf/reference/limits/<cell>.json``."""
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)


def compare(seqs: list[dict], ref_logprobs: list[list[float]]) -> dict:
    diffs = [abs(a - b) for seq, ref in zip(seqs, ref_logprobs)
             for a, b in zip(seq["logprobs"], ref)]
    if not diffs:
        raise ValueError("nothing to compare")
    return {"logprob_err_mean": sum(diffs) / len(diffs),
            "logprob_err_max": max(diffs), "positions": len(diffs)}


def chosen_logprobs(logits, chosen):
    """log-softmax of ``logits [B, P, V]`` at ``chosen [B, P]``."""
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, jnp.asarray(chosen)[:, :, None], axis=-1)[:, :, 0]


def reference_logprobs(cfg: dict, seed: int, seqs: list[dict],
                       precision: str = "f32") -> list[list[float]]:
    """The logprob that the reference of the configuration's family
    (``family.family_of``) gives every chosen id (in this process), the
    sequences padded to a few lengths and read a few rows at a time."""
    import numpy as np

    fn = family_of(cfg).logits_fn(cfg, precision)
    P = -(-max(len(s["at"]) for s in seqs) // PER_ROW) * PER_ROW
    out: list = [None] * len(seqs)
    buckets: dict = {}
    for i, s in enumerate(seqs):
        buckets.setdefault(padded(len(s["tokens"])), []).append(i)
    for T, members in sorted(buckets.items()):
        B = max(1, min(8, ATTENTION_CELLS // (T * T)))
        for lo in range(0, len(members), B):
            part = members[lo:lo + B]
            tokens = np.zeros((B, T), np.int32)
            lengths = np.ones((B,), np.int32)   # rows beyond ``part`` are blanks
            at = np.zeros((B, P), np.int32)
            chosen = np.zeros((B, P), np.int32)
            for b, i in enumerate(part):
                s = seqs[i]
                tokens[b, :len(s["tokens"])] = s["tokens"]
                lengths[b] = len(s["tokens"])
                at[b, :len(s["at"])] = s["at"]
                chosen[b, :len(s["at"])] = s["chosen"]
            lp = np.asarray(chosen_logprobs(
                fn(seed, tokens, lengths, at), chosen))
            for b, i in enumerate(part):
                out[i] = lp[b, :len(seqs[i]["at"])].tolist()
    return out


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    import jax

    dev = jax.devices()[0]
    if job.get("require_platform") and dev.platform != job["require_platform"]:
        print(f"reference child: platform {dev.platform}, wanted "
              f"{job['require_platform']}", file=sys.stderr)
        return 1
    result = {"platform": dev.platform, "runs": []}
    for run in job["runs"]:   # one per seed; the programs are compiled once
        result["runs"].append({
            precision: reference_logprobs(job["config"], run["seed"],
                                          run["sequences"], precision)
            for precision in run["precisions"]})
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
