"""``perf/metrics/inline_admit_share.py`` on a written-out
``program_spans.json``: the share of the untraced window's prefill
dispatches that the decode pipeline issued in line — and nothing (no
error) from a history without the count (the parent commit's side of the
PR that added it), without a prefill dispatch, or of fewer than five
pairs."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.trace import program_spans  # noqa: E402

S = 1_000_000_000
VARIANTS = {
    "closed": ("out_tok_s", ["qwen2.5-7b.decode-heavy",
                             "kimi-linear-48b.long-decode"]),
    "open": ("tpot_mean_ms", ["mistral-7b.chat", "qwen3-next-80b.chat-long",
                              "nemotron-3-nano-30b.chat-burst",
                              "kanana-2-30b.doc-qa"]),
    "sessions": ("tpot_mean_ms.sessions", ["mistral-7b.sessions"]),
}


def history(seconds, with_count=True, prefills_a_second=4) -> list[dict]:
    """One entry a second: 60 decode dispatches and ``prefills_a_second``
    prefill dispatches between two of them, all but one of the latter in
    line; every prefill follows a finish that left the pipeline running,
    and the one drain a second is for an offload tier's admission."""
    out = []
    for i, t in enumerate(seconds):
        counts = {
            "dispatches": {"decode": 60 * i, "prefill": prefills_a_second * i},
            "period_ns": {"decode": 60 * i * 15_000_000},
            "decode_dispatches": 60 * i, "decode_dispatches_chained": 59 * i,
        }
        if with_count:
            counts.update(
                prefill_dispatches_inline=max(0, prefills_a_second - 1) * i,
                finishes_inline=prefills_a_second * i,
                pipeline_drains={"unpredicted_finish": 0, "admission": i,
                                 "blocks": 0, "irregular": 0, "control": 0},
            )
        out.append({"monotonic_ns": t * S + 7, "counts": {"engine": counts}})
    return out


def make_run(tmp_path, hist, t0=10.0, end=60.0) -> perf_run.Run:
    doc = {"written": "shutdown", "spans": [], "dropped": 0,
           "start": {"monotonic_ns": 20 * S, "counts": {}},
           "stop": {"monotonic_ns": 22 * S, "counts": {}},
           "end": {"monotonic_ns": 25 * S, "time_ns": 0}}
    if hist is not None:
        doc["history"] = hist
    with open(tmp_path / program_spans.SPANS_FILE, "w") as f:
        json.dump(doc, f)
    run = perf_run.Run()
    run.trace_dir, run.t0, run.end = str(tmp_path), t0, end
    run._program_steps = None
    return run


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_share_is_inline_over_prefill_dispatches_of_the_untraced_window(
    tmp_path, variant
):
    run = make_run(tmp_path, history(range(0, 70)))
    got = perf_run.read_metric(run, f"inline_admit_share.{variant}")
    assert got == pytest.approx(75.0)
    note = next(n["inline_admit_share"] for n in run.notes
                if "inline_admit_share" in n)
    # entries 10..59 less the pairs that touch the capture's [20, 25]
    assert note["prefill_dispatches"] == 43 * 4
    assert note["prefill_dispatches_inline"] == 43 * 3
    assert note["finishes_inline"] == 43 * 4
    assert note["pipeline_drains"] == {
        "unpredicted_finish": 0, "admission": 43, "blocks": 0,
        "irregular": 0, "control": 0}


def test_a_serial_window_reads_zero_not_nothing(tmp_path):
    """The count is there and did not grow: every prefill ran with
    nothing in flight (doc-qa's reading, and the right one)."""
    hist = history(range(0, 70))
    for e in hist:
        e["counts"]["engine"]["prefill_dispatches_inline"] = 3
    run = make_run(tmp_path, hist)
    assert perf_run.read_metric(run, "inline_admit_share.open") == 0.0


@pytest.mark.parametrize("hist", [
    None,                                             # an older commit: no history
    history(range(0, 70), with_count=False),          # the parent: no such count
    history(range(0, 70), prefills_a_second=0),       # no prefill in the window
    history(range(10, 15)),                           # 5 entries = 4 pairs
], ids=["no_history", "no_count", "no_prefill", "four_pairs"])
def test_nothing_to_read_gives_none_and_no_error(tmp_path, hist):
    run = make_run(tmp_path, hist)
    assert perf_run.read_metric(run, "inline_admit_share.closed") is None
    assert not any("inline_admit_share" in n for n in run.notes)


def test_five_pairs_are_enough(tmp_path):
    run = make_run(tmp_path, history(range(10, 16)))  # 6 entries = 5 pairs
    assert perf_run.read_metric(
        run, "inline_admit_share.closed") == pytest.approx(75.0)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_benchmark_lists_the_metric_for_its_cells(variant):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = f"inline_admit_share.{variant}"
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    moves, cells = VARIANTS[variant]
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step loop",
        "moves": moves, "workloads": cells,
    }
    reports = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(reports["workloads"])
