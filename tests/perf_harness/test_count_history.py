"""``perf/trace/count_history.py`` and the five readers of the UNTRACED
window (ISSUE 40) on a written-out ``program_spans.json``: the history's
entries inside the window, pairs that touch the capture dropped, a ratio
of summed growths — and nothing (no error) from a parent's document
without ``history``, or with fewer than five pairs."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.trace import count_history as ch  # noqa: E402
from perf.trace import program_spans  # noqa: E402

OPEN = ["mistral-7b.chat", "qwen3-next-80b.chat-long",
        "nemotron-3-nano-30b.chat-burst"]
VARIANTS = {"open": ("tpot_mean_ms", OPEN),
            "sessions": ("tpot_mean_ms.sessions", ["mistral-7b.sessions"]),
            "closed": ("out_tok_s", ["qwen2.5-7b.decode-heavy",
                                     "kimi-linear-48b.long-decode"])}
STEMS = {"decode_period_ms": ("ms", "engine step loop"),
         "step_host_wall_ms": ("ms", "engine step loop"),
         "step_host_offcpu_ms": ("ms", "engine step loop"),
         "loop_cpu_ms_per_step": ("ms", "HTTP frontend"),
         "dispatch_drained_share": ("%", "device")}
S = 1_000_000_000


def counts_at(steps: int, slow: int = 0) -> dict:
    """Cumulative counts after ``steps`` decode dispatches of a made-up
    engine: a 20 ms period, 1 + 0.5 + 0.25 + 2 + 0.25 = 4 ms of host work a
    step of which 1 ms off the CPU, 3 ms of event-loop CPU, one dispatch in
    four to a drained device; ``slow`` extra ns a step inflate every
    phase, as a capture's python tracer does."""
    ms = 1_000_000

    def phase(wall_ms: float, cpu_ms: float) -> dict:
        return {"wall_ns": int(steps * (wall_ms * ms + slow)),
                "cpu_ns": int(steps * cpu_ms * ms), "calls": steps}

    return {
        "steps": {"decode": steps, "prefill": steps // 10},
        "step_phases": {
            "plan": phase(1, 0.75), "pack": phase(0.5, 0.5),
            "dispatch": phase(0.25, 0.25), "harvest": phase(15, 0.1),
            "emit": phase(2, 1.25), "record": phase(0.25, 0.25),
            "wait": phase(0, 0)},
        "loop_wall_ns": steps * 20 * ms, "unphased_ns": steps * ms,
        "offcpu_ns": steps * ms,
        "dispatches": {"decode": steps, "prefill": steps // 10},
        "period_ns": {"decode": steps * 20 * ms,
                      "prefill": (steps // 10) * 50 * ms},
        "dispatches_device_drained": steps // 4,
        "cpu_ns": {"engine": steps * 3 * ms, "loop": steps * 3 * ms,
                   "process": steps * 8 * ms},
        "decode_dispatches": steps, "prefill_tokens_real": 100 * steps,
    }


def write_doc(tmp_path, history, capture=(20.0, 22.0, 25.0), **more) -> None:
    start, stop, end = (int(t * S) for t in capture)
    doc = {"written": "shutdown", "spans": [], "dropped": 0,
           "start": {"monotonic_ns": start, "counts": {}},
           "stop": {"monotonic_ns": stop, "counts": {}},
           "end": {"monotonic_ns": end, "time_ns": 0}, **more}
    if history is not None:
        doc["history"] = history
    with open(tmp_path / program_spans.SPANS_FILE, "w") as f:
        json.dump(doc, f)


def make_run(tmp_path, t0=10.0, end=60.0) -> perf_run.Run:
    run = perf_run.Run()
    run.trace_dir, run.t0, run.end = str(tmp_path), t0, end
    # the capture's own reduction is another reader's business
    run._program_steps = None
    return run


def history(seconds=range(0, 70), slow_between=(20, 25)) -> list[dict]:
    """One entry a second from the process's start: 50 steps a second
    outside the capture, 20 a second (each 30 ms slower) inside it."""
    out, steps, slow_steps = [], 0, 0
    for t in seconds:
        base = counts_at(steps)
        slow = counts_at(slow_steps, slow=30_000_000)
        merged = ch.flat(base)
        for k, v in ch.flat(slow).items():
            merged[k] = merged.get(k, 0) + v
        out.append({"monotonic_ns": t * S + 123, "counts": {"engine": _nest(merged)}})
        if slow_between[0] <= t < slow_between[1]:
            slow_steps += 20
        else:
            steps += 50
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        d = out
        *path, leaf = k.split(".")
        for p in path:
            d = d.setdefault(p, {})
        d[leaf] = v
    return out


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------
def test_pairs_inside_the_window_and_clear_of_the_capture(tmp_path):
    write_doc(tmp_path, history())
    run = make_run(tmp_path)
    with open(tmp_path / program_spans.SPANS_FILE) as f:
        pairs = ch.kept_pairs(json.load(f), run.t0, run.end)
    firsts = [a[0] // S for a, _ in pairs]
    # entries 10..59 lie in [10, 60]; the pairs starting at 19..25 touch
    # the capture's [20, 25] (entry stamps sit 123 ns after the second)
    assert firsts == [*range(10, 19), *range(25, 59)]
    g = ch.growth(run)
    assert g["pairs"] == len(firsts) == 43 and g["seconds"] == pytest.approx(43.0)
    # only untraced steps in what is kept: 50 a second
    assert g["dispatches.decode"] == 43 * 50
    assert g["step_phases.plan.wall_ns"] == 43 * 50 * 1_000_000
    note = next(n["count_history"] for n in run.notes if "count_history" in n)
    assert note == {"entries": 70, "in_window": 50, "pairs_kept": 43,
                    "seconds_kept": pytest.approx(43.0)}


def test_without_an_end_the_capture_runs_to_its_stop(tmp_path):
    """A document whose capture has no ``end`` stamp: ``stop`` closes the
    interval (fewer pairs dropped, none raised over)."""
    write_doc(tmp_path, history())
    path = tmp_path / program_spans.SPANS_FILE
    with open(path) as f:
        doc = json.load(f)
    del doc["end"]
    pairs = ch.kept_pairs(doc, 10.0, 60.0)
    assert [a[0] // S for a, _ in pairs] == [*range(10, 19), *range(22, 59)]


@pytest.mark.parametrize("stem,want", [
    ("decode_period_ms", 20.0),
    ("step_host_wall_ms", 4.0 / 1.1),      # over decode AND prefill dispatches
    ("step_host_offcpu_ms", 1.0 / 1.1),
    ("loop_cpu_ms_per_step", 3.0 / 1.1),
    ("dispatch_drained_share", 25.0 / 1.1),
])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_five_readers_read_the_untraced_window(tmp_path, stem, want, variant):
    write_doc(tmp_path, history())
    run = make_run(tmp_path)
    got = perf_run.read_metric(run, f"{stem}.{variant}")
    # the capture's 30 ms-a-step inflation is nowhere in the reading
    assert got == pytest.approx(want, rel=2e-3)


def test_the_readers_notes_say_what_fills_a_step(tmp_path):
    write_doc(tmp_path, history())
    run = make_run(tmp_path)
    run._program_steps = {"host_step_ms": [12.0] * 40}  # the capture's own
    for stem in STEMS:
        assert perf_run.read_metric(run, f"{stem}.closed") is not None
    notes = {k: v for n in run.notes for k, v in n.items()}
    period = notes["decode_period_ms"]
    assert period["kind"] == "decode" and period["dispatches"] == 43 * 50
    assert period["period_ms_by_kind"] == {"decode": 20.0, "prefill": 50.0}
    wall = notes["step_host_wall_ms"]
    per = wall["per_dispatch_ms"]
    assert per["step_phases.emit.wall_ns"] == pytest.approx(2 / 1.1, rel=1e-3)
    assert per["step_phases.record.wall_ns"] == pytest.approx(0.25 / 1.1, rel=1e-3)
    assert per["unphased_ns"] == pytest.approx(1 / 1.1, rel=1e-3)
    assert wall["unphased_share_pct"] == pytest.approx(5.0)
    # the capture's median against the untraced mean
    assert wall["captures_p50_over_this"] == pytest.approx(12.0 / (4 / 1.1), rel=1e-3)
    assert notes["step_host_offcpu_ms"]["per_dispatch_ms"]["emit"] == \
        pytest.approx(0.75 / 1.1, rel=1e-3)
    cpu = notes["loop_cpu_ms_per_step"]
    assert cpu["engine_cpu_ms"] == pytest.approx(3 / 1.1, rel=1e-3)
    assert cpu["other_threads_cpu_ms"] == pytest.approx(2 / 1.1, rel=1e-3)
    # 50 steps a second x (3 + 3) ms of CPU = 0.3 of one interpreter
    assert cpu["interpreter_fill"] == pytest.approx(0.3, rel=1e-3)


def test_a_program_whose_decode_rides_windows_is_read_by_that_kind(tmp_path):
    hist = history()
    for e in hist:
        c = e["counts"]["engine"]
        c["dispatches"] = {"window": c["dispatches"]["decode"], "prefill": 1}
        c["period_ns"] = {"window": c["period_ns"]["decode"]}
    write_doc(tmp_path, hist)
    run = make_run(tmp_path)
    assert perf_run.read_metric(run, "decode_period_ms.open") == pytest.approx(20.0)
    note = next(n["decode_period_ms"] for n in run.notes if "decode_period_ms" in n)
    assert note["kind"] == "window"


# ---------------------------------------------------------------------------
# nothing to read
# ---------------------------------------------------------------------------
def _parent_doc(tmp_path):
    write_doc(tmp_path, None)                      # an older commit: no history


def _no_file(tmp_path):
    pass


def _four_pairs(tmp_path):
    write_doc(tmp_path, history(seconds=range(10, 15)))   # 5 entries = 4 pairs


def _all_inside_the_capture(tmp_path):
    write_doc(tmp_path, history(seconds=range(19, 27)))


def _outside_the_window(tmp_path):
    write_doc(tmp_path, history(seconds=range(0, 9)))


def _no_dispatch(tmp_path):
    hist = history()
    for e in hist:
        e["counts"]["engine"]["dispatches"] = {}
    write_doc(tmp_path, hist)


@pytest.mark.parametrize("make", [
    _parent_doc, _no_file, _four_pairs, _all_inside_the_capture,
    _outside_the_window, _no_dispatch], ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("stem", sorted(STEMS))
def test_nothing_to_read_gives_none_and_no_error(tmp_path, make, stem):
    make(tmp_path)
    run = make_run(tmp_path)
    assert perf_run.read_metric(run, f"{stem}.closed") is None
    assert not any(stem in n for n in run.notes)


def test_five_pairs_are_enough(tmp_path):
    write_doc(tmp_path, history(seconds=range(10, 16)))   # 6 entries = 5 pairs
    run = make_run(tmp_path)
    assert ch.MIN_PAIRS == 5
    assert perf_run.read_metric(run, "decode_period_ms.closed") == pytest.approx(20.0)


def test_no_loop_clock_reads_nothing_for_the_loop_alone(tmp_path):
    """A platform without per-thread CPU clocks leaves ``cpu_ns.loop``
    out: that reader is silent, the others read."""
    hist = history()
    for e in hist:
        del e["counts"]["engine"]["cpu_ns"]["loop"]
    write_doc(tmp_path, hist)
    run = make_run(tmp_path)
    assert perf_run.read_metric(run, "loop_cpu_ms_per_step.open") is None
    assert perf_run.read_metric(run, "step_host_wall_ms.open") is not None


# ---------------------------------------------------------------------------
# the benchmark's entries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("stem", sorted(STEMS))
def test_benchmark_lists_each_stem_for_every_cell_by_variant(stem, variant):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    moves, cells = VARIANTS[variant]
    unit, layer = STEMS[stem]
    entry = next(m for m in bench["per_layer"] if m["name"] == f"{stem}.{variant}")
    assert entry == {
        "name": f"{stem}.{variant}", "unit": unit, "better": "lower",
        "source": "program_counter", "layer": layer, "moves": moves,
        "workloads": cells}
    e2e = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(e2e["workloads"])
    assert os.path.exists(os.path.join(REPO, "perf", "metrics", f"{stem}.py"))


def test_the_new_entries_are_appended_and_cover_all_six_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    mine = [f"{s}.{v}" for s in STEMS for v in ("open", "sessions", "closed")]
    assert names[-15:] == mine
    every = sorted(w["name"] for w in bench["workloads"])
    for stem in STEMS:
        listed = [c for m in bench["per_layer"]
                  if m["name"].partition(".")[0] == stem for c in m["workloads"]]
        assert sorted(listed) == every
