"""The one general traffic generator: a mix file in, a schedule out.

A mix is ``perf/traffic/<mix>.json``; its ``kind`` names the module in
``perf/traffic/kinds/`` that lays the schedule out. The schedule — every
arrival offset, length, think time, phase and session membership — is a
function of the mix file (which carries its own ``schedule_seed``) and of
``--seconds`` ALONE. ``--seed`` reaches traffic through one door only,
``token_ids``: it picks the ids of a prompt whose length and sharing the
schedule already fixed.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIRST_ID = 5  # ids below stay out of prompts (bos/eos/unk of the word vocabulary)


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic mix file {path}")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def kind_module(kind: str):
    return importlib.import_module(f"perf.traffic.kinds.{kind}")


def build(mix: dict, seconds: float) -> dict:
    """The schedule of ``mix`` for a window of ``seconds`` (plus the
    mix's ramp before it). Pure: same arguments, same bytes."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    sched = kind_module(mix["kind"]).build(mix, float(seconds), rng)
    sched.update(kind=mix["kind"], mix=mix["name"], seconds=float(seconds),
                 ramp_s=float(mix["ramp_s"]))
    return sched


def serialise(schedule: dict) -> bytes:
    return json.dumps(schedule, sort_keys=True, separators=(",", ":")).encode()


def digest(schedule: dict) -> str:
    return hashlib.sha256(serialise(schedule)).hexdigest()


def token_ids(seed: int, key: tuple, n: int, vocab: int) -> list[int]:
    """``n`` prompt ids for the schedule entry named by ``key`` (a tuple
    of small ints): the only thing ``--seed`` decides about traffic."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 *[int(k) for k in key]])
    return rng.integers(FIRST_ID, vocab, size=int(n)).tolist()


def words(ids: list[int]) -> str:
    return " ".join(f"w{i}" for i in ids)


def clipped_lognormal(rng, median: float, sigma: float, lo: int, hi: int) -> int:
    return int(min(hi, max(lo, round(float(rng.lognormal(np.log(median), sigma))))))


def uniform_int(rng, lo: int, hi: int) -> int:
    return int(rng.integers(int(lo), int(hi) + 1))
