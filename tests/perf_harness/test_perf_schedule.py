"""The lesson of PR 23 as tests: ``--seed`` never changes the work.

For every traffic mix of the benchmark the schedule — arrivals, lengths,
think times, phases, session membership — is byte-identical whatever the
seed (the builder never sees it), the scheduled token totals are equal,
and two seeds differ in token ids alone."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import measure  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
SECONDS = BENCH["run_seconds"]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_is_a_function_of_the_mix_file_and_seconds_alone(mix):
    a = sched.build(sched.load_mix(mix), SECONDS)
    b = sched.build(sched.load_mix(mix), SECONDS)
    assert sched.serialise(a) == sched.serialise(b)
    assert sched.digest(a) == sched.digest(b)
    # the builder's signature has no place for a seed
    import inspect
    kind = sched.kind_module(sched.load_mix(mix)["kind"])
    assert list(inspect.signature(kind.build).parameters) == [
        "mix", "seconds", "rng"]
    assert "seed" not in inspect.signature(sched.build).parameters


@pytest.mark.parametrize("mix", MIXES)
def test_scheduled_tokens_are_equal_across_seeds(mix):
    m = sched.load_mix(mix)
    kind = sched.kind_module(m["kind"])
    totals = [kind.totals(sched.build(m, SECONDS)) for _ in range(2)]
    assert totals[0] == totals[1]
    assert all(v > 0 for v in totals[0].values())


@pytest.mark.parametrize("mix", MIXES)
def test_a_longer_window_extends_the_same_schedule(mix):
    m = sched.load_mix(mix)
    short, long_ = sched.build(m, 10), sched.build(m, SECONDS)
    if m["kind"] == "open_loop":
        n = len(short["entries"])
        assert long_["entries"][:n] == short["entries"]
    elif m["kind"] == "closed_loop":
        for a, b in zip(short["clients"], long_["clients"]):
            assert a[0]["prompt"] > 0 and b[0]["prompt"] > 0
    assert sched.digest(short) != sched.digest(long_)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 12345)])
def test_seeds_differ_in_token_ids_only(seeds):
    key, n, vocab = (0, 3), 257, 32000
    a, b = (sched.token_ids(s, key, n, vocab) for s in seeds)
    assert len(a) == len(b) == n and a != b
    assert all(sched.FIRST_ID <= t < vocab for t in a + b)
    assert sched.token_ids(seeds[0], key, n, vocab) == a  # same seed, same ids
    assert len(sched.words(a).split()) == n


def test_chat_window_supports_its_tail_percentile():
    m = sched.load_mix("chat")
    win = [e for e in sched.build(m, SECONDS)["entries"] if e["due"] >= 0]
    assert measure.beyond(len(win), 85) >= measure.MIN_BEYOND
    every = sched.build(m, SECONDS)["entries"]
    assert measure.beyond(len(every), 80) >= measure.MIN_BEYOND  # gen_late_p80_ms
    assert all(e["prompt"] + e["out"] <= m["max_total_tokens"] for e in win)


def test_decode_heavy_first_requests_are_phased():
    m = sched.load_mix("decode-heavy")
    firsts = [c[0]["out"] for c in sched.build(m, SECONDS)["clients"]]
    assert len(firsts) == m["clients"]
    # first answers end spread over the range, not as one wave
    assert min(firsts) < m["output_tokens"]["min"] / 4
    assert max(firsts) >= m["output_tokens"]["min"]


def test_sessions_stay_inside_the_history_cap_and_share_prompts_by_group():
    m = sched.load_mix("sessions")
    s = sched.build(m, SECONDS)
    assert len(s["slots"]) == m["live_sessions"]
    for sl in s["slots"]:
        assert sl["group"] == sl["slot"] // m["group_size"]
        assert -m["ramp_s"] <= sl["open_at"] < 0
        for turns in sl["sessions"]:
            total = m["system_prompt_tokens"] + sum(
                t["user"] + t["out"] for t in turns)
            assert total <= m["max_history_tokens"] and len(turns) >= 4
        assert sl["start_turn"] < len(sl["sessions"][0])


@pytest.mark.parametrize("n,p,want", [
    (148, 90, True), (100, 90, True), (99, 90, False), (60, 95, False),
    (20, 50, True), (19, 50, False)])
def test_percentile_needs_ten_samples_beyond_it(n, p, want):
    xs = [float(i) for i in range(n)]
    got = measure.percentile(xs, p)
    assert (got is not None) == want
    if want:
        assert measure.beyond(n, p) >= 10
        assert got == xs[-(measure.beyond(n, p)) - 1]


def test_every_metric_of_the_benchmark_has_a_reader_found_by_name():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        stem = m["name"].partition(".")[0]
        assert os.path.exists(os.path.join(REPO, "perf", "metrics", f"{stem}.py")), m["name"]
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(REPO, "perf", "traffic", f"{w['traffic']}.json"))
