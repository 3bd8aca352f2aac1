"""The readers of what the program records around a capture
(``perf/trace/program_spans.py`` and the metrics on top of it).

``data/recorded_steps.json`` is a cut of a real capture of
``mistral-7b.sessions`` on a TPU v5e (a decode step, a prefill program, a
decode step) with the engine thread's ``dyn.step.*`` events: the classifier and the gap
attribution have to give what they gave when the cut was made. The
readers are driven by a hand-written span file, the empty and the absent
file among them, and once end to end by the CPU rehearsal."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.client import Record  # noqa: E402
from perf.trace import program_spans as ps, reduce as tr  # noqa: E402

from tests.perf_harness.test_perf_run_rehearsal import (  # noqa: E402,F401
    _lines,
    rehearsal,
)

SPAN_READERS = ["queue_wait_ms_p50", "queue_wait_ms_p85.sessions",
                "prefill_ms_p50", "frontend_ms_p50", "cached_token_share"]
STEP_READERS = ["step_host_ms_p50.open", "idle_attributed_share.open",
                "prefill_device_share.open"]


# ---------------------------------------------------------------------------
# the recorded cut
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_steps.json")) as f:
        return json.load(f)


def test_recorded_programs_are_classed_by_what_they_ran(recorded):
    red = ps.reduce_steps(recorded)
    progs = red["programs"]
    assert red["interval_from"] == "first op..last op"
    assert {k: v["calls"] for k, v in progs.items()} == RECORDED["calls"]
    assert progs["prefill"]["total_s"] == pytest.approx(RECORDED["prefill_s"], rel=1e-6)
    assert progs["decode"]["median_s"] == pytest.approx(RECORDED["decode_median_s"], rel=1e-6)
    # the interval and the busy time are the reducer's own
    whole = tr.reduce_events(recorded)
    assert red["window_s"] == pytest.approx(whole["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(whole["busy_s"], rel=1e-9)
    assert sum(red["idle_by_phase"].values()) <= whole["idle_s"] + 1e-12
    # one dispatch span per step program on the device
    steps = progs["prefill"]["calls"] + progs["decode"]["calls"]
    assert abs(red["phases"]["dispatch"]["calls"] - steps) <= 2


def test_recorded_idle_gaps_go_to_the_phase_that_covered_them(recorded):
    red = ps.reduce_steps(recorded)
    assert {k: round(v * 1e6) for k, v in red["idle_by_phase"].items()} \
        == RECORDED["idle_us_by_phase"]
    assert [round(ms, 3) for ms in red["host_step_ms"]] == RECORDED["host_step_ms"]


# what the cut gave when it was made (my chip run, PR 25): after the decode
# program the device idles 8.0 ms under the harvest's tail and `emit`; after
# the prefill program 7.6 ms, whose midpoint falls between `record` and
# `pack`, where _one_step chooses its path under no phase
RECORDED = {
    "calls": {"decode": 2, "prefill": 1, "other": 1},
    "prefill_s": 0.032173766, "decode_median_s": 0.0411423445,
    "idle_us_by_phase": {"emit": 8029, "none": 7580},
    "host_step_ms": [6.938, 6.89],
}


# ---------------------------------------------------------------------------
# the same arithmetic on a constructed capture, whose answer is known by hand
# ---------------------------------------------------------------------------
MS = 1e6


def constructed() -> dict:
    def op(name, start_ms, dur_ms):
        return [f"%{name} = bf16[8,128]{{1,0}} custom-call(", start_ms * MS, dur_ms * MS]

    return {
        "device": {"/device:TPU:0": {
            "XLA Ops": [
                op("fusion.1", 0, 1), op("paged_attention_decode_stacked.5", 1, 1),
                # 2..5: idle 3 ms, the host packing and dispatching
                op("fusion.2", 5, 1), op("paged_attention_prefill_stacked.7", 6, 3),
                # 9..9.2: 0.2 ms, too short to count
                op("fusion.3", 9.2, 0.8),
                # 10..12: idle 2 ms under no phase at all
                op("paged_attention_decode_stacked.5", 12, 1),
                op("copy.1", 13.5, 0.5),   # 13..13.5: the idle edge of harvest
            ],
            "XLA Modules": [["jit_step(1)", 0, 2 * MS], ["jit_step(2)", 5 * MS, 5 * MS],
                            ["jit_step(1)", 12 * MS, 1 * MS],
                            ["jit_chain_next(3)", 13.5 * MS, 0.5 * MS]],
        }},
        "host": {
            "/host:CPU/python3/77": [
                ["dyn.step.dispatch", 0.1 * MS, 0.4 * MS],
                ["dyn.step.harvest", 0.5 * MS, 1.6 * MS],
                ["dyn.step.emit", 2.1 * MS, 0.4 * MS],
                ["dyn.step.record", 2.5 * MS, 0.2 * MS],
                ["dyn.step.plan", 2.8 * MS, 0.3 * MS],
                ["dyn.step.pack", 3.1 * MS, 1.0 * MS],       # covers 3.5, the gap's middle
                ["dyn.step.dispatch", 4.2 * MS, 0.6 * MS],
                ["dyn.step.harvest", 4.9 * MS, 5.2 * MS],
                ["dyn.step.wait", 10.2 * MS, 0.5 * MS],      # ends before 11
                ["dyn.step.dispatch", 11.5 * MS, 0.3 * MS],
                ["dyn.step.harvest", 11.9 * MS, 1.5 * MS],   # covers 13.25
            ],
            "/host:CPU/python3/78": [["dyn.step.plan", 0.0, 14 * MS]],  # not the engine
        },
    }


def test_constructed_capture_by_hand():
    red = ps.reduce_steps(constructed())
    assert red["interval_from"] == "first op..last op"
    assert red["window_s"] == pytest.approx(0.014)
    assert red["busy_s"] == pytest.approx(0.0083)
    assert red["idle_by_phase"] == pytest.approx(
        {"pack": 0.003, "none": 0.002, "harvest": 0.0005})
    assert {k: v["calls"] for k, v in red["programs"].items()} \
        == {"decode": 2, "prefill": 1, "other": 1}
    assert red["programs"]["prefill"]["total_s"] == pytest.approx(0.005)
    assert red["programs"]["decode"]["median_s"] == pytest.approx(0.0015)
    # cut at each dispatch's start: [0.1, 4.2) holds dispatch 0.4 + emit 0.4 +
    # record 0.2 + plan 0.3 + pack 1.0; [4.2, 11.5) holds dispatch 0.6 alone
    # (harvest and wait are not host work); the last cut has no end
    assert red["host_step_ms"] == pytest.approx([2.3, 0.6])
    assert red["phases"]["dispatch"] == {"calls": 3, "total_s": pytest.approx(0.0013)}


def test_a_capture_without_a_device_plane_gives_nothing():
    events = constructed()
    events["device"] = {}
    assert ps.reduce_steps(events) is None


# ---------------------------------------------------------------------------
# the readers on a hand-written span file
# ---------------------------------------------------------------------------
def span(name, trace, start_s, dur_s, **attrs):
    return {"name": name, "trace_id": trace, "span_id": name + trace,
            "start": 1.7e9 + start_s, "start_mono_ns": int(start_s * 1e9),
            "duration_s": dur_s, "attrs": attrs}


def written_run(tmp_path, n=40, doc=None) -> perf_run.Run:
    """A run whose window is [100 s, 150 s) and whose capture directory
    holds ``doc``, or ``n`` requests written by hand: request i is
    submitted at 100 + i s, its frontend took 2 + i ms, its queue wait
    i ms, its prefill 10 + i ms over 100 prompt tokens of which i are
    cached."""
    run = perf_run.Run()
    run.t0, run.end, run.seconds = 100.0, 150.0, 50.0
    run.trace_dir = str(tmp_path)
    if doc is None:
        spans = []
        for i in range(n):
            t, at = f"t{i}", 100.0 + i
            spans += [
                span("http.request", t, at - (2 + i) / 1e3, 1.0),
                span("engine.queue_wait", t, at, i / 1e3, waiting=0),
                span("engine.prefill", t, at + i / 1e3, (10 + i) / 1e3,
                     prompt_tokens=100, cached_tokens=i, chunks=1),
                span("engine.decode", t, at + (10 + 2 * i) / 1e3, 0.5,
                     tokens=9, ttft_ms=10.0 + 2 * i),
            ]
        doc = {"written": "shutdown", "spans": spans, "dropped": 0,
               "start": {"monotonic_ns": 0, "time_ns": 0, "counts": {
                   "engine": {"steps": {"decode": 10}, "prompt_tokens": 500}}},
               "stop": {"monotonic_ns": 0, "time_ns": 0, "counts": {
                   "engine": {"steps": {"decode": 110, "prefill": 3},
                              "prompt_tokens": 900}}}}
    if doc is not False:
        with open(os.path.join(tmp_path, ps.SPANS_FILE), "w") as f:
            json.dump(doc, f)
    return run


def test_readers_on_a_written_span_file(tmp_path):
    run = written_run(tmp_path, n=70)   # requests 0..49 begin inside the window
    read = perf_run.read_metric
    # nearest rank over i = 0..50 (the window's ends are inclusive): 51 samples
    assert read(run, "queue_wait_ms_p50") == pytest.approx(25.0)
    assert read(run, "queue_wait_ms_p50.sessions") == pytest.approx(25.0)
    assert read(run, "queue_wait_ms_p85.sessions") is None  # 7 beyond: too few
    assert read(run, "prefill_ms_p50.sessions") == pytest.approx(35.0)
    assert read(run, "frontend_ms_p50") == pytest.approx(27.0)
    assert read(run, "cached_token_share.sessions") == pytest.approx(
        100.0 * sum(range(51)) / (51 * 100))
    notes = {k: v for n in run.notes for k, v in n.items()}
    assert notes["program_spans"]["written"] == "shutdown"
    assert notes["program_spans"]["counts_in_capture"] == {
        "engine.steps.decode": 100, "engine.steps.prefill": 3,
        "engine.prompt_tokens": 400}
    assert notes["program_requests"] == {
        "in_window": 51, "traces": 70, "server_ttft_ms_p50": 60.0}
    assert notes["prefill_ms_p50"]["chunks_mean"] == 1.0


def test_p85_needs_ten_samples_beyond_it(tmp_path):
    run = written_run(tmp_path, n=50)
    run.end = 200.0   # all 50: 85th percentile of i ms, 7 beyond -> nothing
    assert perf_run.read_metric(run, "queue_wait_ms_p85.sessions") is None
    big = written_run(tmp_path, n=50)
    big.end = 200.0
    big._program_requests = ps.requests(big) * 2   # 100 samples, 15 beyond
    assert perf_run.read_metric(big, "queue_wait_ms_p85.sessions") == pytest.approx(42.0)


@pytest.mark.parametrize("doc", [
    False,                                           # the program wrote no file
    {"written": "capture_end", "spans": [], "dropped": 0},   # an empty one
])
@pytest.mark.parametrize("name", SPAN_READERS + STEP_READERS)
def test_readers_say_nothing_without_spans(tmp_path, monkeypatch, doc, name):
    """None — never 0, never an exception — and no child is started for a
    capture whose program wrote no span file."""
    run = written_run(tmp_path, doc=doc)
    if doc is False:
        monkeypatch.setattr(ps.subprocess, "run", lambda *a, **kw: pytest.fail(
            "a child was started"))
    else:
        monkeypatch.setattr(ps.subprocess, "run", lambda *a, **kw: None)
        monkeypatch.setattr(ps.srv, "WORK", str(tmp_path))
        with open(os.path.join(tmp_path, "program_steps.json"), "w") as f:
            json.dump(None, f)   # what the child writes without a device plane
    assert perf_run.read_metric(run, name) is None


def test_no_capture_at_all_says_nothing():
    run = perf_run.Run()   # an untraced run: no trace_dir
    for name in SPAN_READERS + STEP_READERS:
        assert perf_run.read_metric(run, name) is None


def test_step_readers_on_a_reduced_capture(tmp_path, monkeypatch):
    run = written_run(tmp_path)
    red = ps.reduce_steps(constructed())
    red["host_step_ms"] = [float(i) for i in range(1, 42)]   # 41 steps
    monkeypatch.setattr(ps.srv, "WORK", str(tmp_path))
    calls = []

    def child(argv, **kw):
        calls.append(argv)
        with open(argv[-1], "w") as f:
            json.dump(red, f)

    monkeypatch.setattr(ps.subprocess, "run", child)
    read = perf_run.read_metric
    assert read(run, "step_host_ms_p50.closed") == pytest.approx(21.0)
    assert read(run, "idle_attributed_share.closed") == pytest.approx(100 * 3.5 / 5.5)
    assert read(run, "prefill_device_share.closed") == pytest.approx(100 * 5.0 / 8.3)
    assert len(calls) == 1 and calls[0][-2] == str(tmp_path)   # once per run
    notes = {k: v for n in run.notes for k, v in n.items()}
    assert notes["program_steps"]["idle_by_phase"]["pack"] == pytest.approx(0.003)


# ---------------------------------------------------------------------------
# the gap between chunks, client side
# ---------------------------------------------------------------------------
def test_itl_p99_is_over_the_gaps_that_end_in_the_window():
    run = perf_run.Run()
    run.t0, run.end = 10.0, 20.0
    # chunks every 4 ms from 9.95 s on; one 300 ms stall inside the window
    times = [9.95 + 0.004 * i for i in range(2000)]
    times = times[:1000] + [t + 0.3 for t in times[1000:]]
    ok = Record(key=(0,), due=9.9, prompt_tokens=8, out_tokens=2000, received=2000,
                chunks=[(t, 1) for t in times])
    failed = Record(key=(1,), due=9.9, prompt_tokens=8, out_tokens=5, received=2,
                    chunks=[(10.0, 1), (19.0, 1)])
    run.records = [ok, failed]
    got = perf_run.read_metric(run, "itl_p99_ms.watch")
    assert got == pytest.approx(4.0, abs=1e-6)       # the stall is beyond p99
    ok.chunks = [(t, 1) for t in times[:500]]        # under 1 000 gaps: too few
    assert perf_run.read_metric(run, "itl_p99_ms.sessions") is None


# ---------------------------------------------------------------------------
# end to end on the CPU: the server writes the file, the readers find it
# ---------------------------------------------------------------------------
def test_rehearsal_reads_the_programs_own_spans(rehearsal, capsys):
    rc = perf_run.main(["--workload", "tiny.sessions", "--seed", "4242",
                        "--seconds", "8", "--trace", "1"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    lines = _lines(out.out)
    metrics = lines[-1]["metrics"]
    assert 0 < metrics["cached_token_share.sessions"]["value"] < 100
    assert metrics["queue_wait_ms_p50.sessions"]["value"] >= 0
    assert metrics["prefill_ms_p50.sessions"]["value"] > 0
    assert metrics["frontend_ms_p50.sessions"]["value"] > 0
    # no device plane on the CPU: the capture's device half says nothing
    for name in ("step_host_ms_p50.sessions", "idle_attributed_share.sessions",
                 "prefill_device_share.sessions"):
        assert name not in metrics
    notes = {k: v for ln in lines if ln.get("phase") == "host"
             for n in ln["reader_notes"] for k, v in n.items()}
    # the copy the server wrote as it shut down, with the counts at the
    # capture's two edges
    assert notes["program_spans"]["written"] == "shutdown"
    assert notes["program_spans"]["dropped"] == 0
    counts = notes["program_spans"]["counts_in_capture"]
    assert counts["engine.steps.decode"] > 0
    assert counts["engine.preemptions"] == 0
    assert notes["program_requests"]["in_window"] >= 20
    assert notes["program_requests"]["server_ttft_ms_p50"] > 0
