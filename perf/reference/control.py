"""The control of the output check: the reference itself, computed one
precision step below what the configuration states, put in the program's
place. It has to come out as NOT correct.

Two ways to put it there, which read alike (a test holds them together
at a tiny size):

``control_error`` — free running: the control model answers a wave of
the probe greedily, token by token, exactly as the program does over
HTTP; the float32 reference then gives its logprob of the same ids.
One forward pass per generated token: for tests.

``forced_error`` — teacher forced: the control model gives its logprob
of the ids of sequences that were already answered (by the program, in
``perf/tools/readings.py``), in one forward pass, and is compared with
the float32 reference at the same positions. This is how the control is
read on the chip at a cell's own size, beside the program's own number.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.reference import check  # noqa: E402
from perf.reference.family import family_of  # noqa: E402


def control_answers(cfg: dict, seed: int, precision: str,
                    jobs: list[dict]) -> list[dict]:
    """The control model's greedy answers to ``jobs`` (one wave):
    the jobs with ``chosen`` and ``logprobs`` as the program returns them."""
    import numpy as np

    n_out = max(j["out"] for j in jobs)
    longest = max(len(j["ids"]) for j in jobs) + n_out
    T = check.padded(longest)
    B = len(jobs)
    tokens = np.zeros((B, T), np.int32)
    lengths = np.array([len(j["ids"]) for j in jobs], np.int32)
    for b, j in enumerate(jobs):
        tokens[b, :lengths[b]] = j["ids"]
    fn = family_of(cfg).logits_fn(cfg, precision)
    chosen = np.zeros((B, n_out), np.int32)
    lps = np.zeros((B, n_out), np.float32)
    for k in range(n_out):
        at = (lengths - 1)[:, None].astype(np.int32)
        logits = fn(seed, tokens, lengths, at)
        pick = np.asarray(logits[:, 0].argmax(-1)).astype(np.int32)
        lp = np.asarray(check.chosen_logprobs(logits, pick[:, None]))[:, 0]
        chosen[:, k], lps[:, k] = pick, lp
        tokens[np.arange(B), lengths] = pick
        lengths = lengths + 1
    return [dict(j, chosen=chosen[b, :j["out"]].tolist(),
                 logprobs=lps[b, :j["out"]].tolist()) for b, j in enumerate(jobs)]


def control_error(cfg: dict, seed: int, precision: str, jobs: list[dict]) -> dict:
    seqs = check.sequences(control_answers(cfg, seed, precision, jobs))
    return check.compare(seqs, check.reference_logprobs(cfg, seed, seqs, "f32"))


def forced_error(seqs: list[dict], control_logprobs: list[list[float]],
                 ref_logprobs: list[list[float]]) -> dict:
    """The control's logprobs of ``seqs``' ids against the reference's."""
    return check.compare(
        [dict(s, logprobs=lp) for s, lp in zip(seqs, control_logprobs)],
        ref_logprobs)
