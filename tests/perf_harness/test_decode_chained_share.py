"""``perf/metrics/decode_chained_share.py`` on a constructed
``program_spans.json``: the share of the counts' growth over the capture,
and nothing (no error) from a program that writes no such counts — the
parent commit's side of the PR that added them."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.trace import program_spans  # noqa: E402

NAME = "decode_chained_share.closed"


def make_run(tmp_path, counts: tuple[dict, dict] | None) -> perf_run.Run:
    run = perf_run.Run()
    run.trace_dir = str(tmp_path)
    if counts is not None:
        doc = {"written": "capture_end", "spans": [], "dropped": 0,
               "start": {"counts": {"engine": counts[0]}},
               "stop": {"counts": {"engine": counts[1]}}}
        with open(tmp_path / program_spans.SPANS_FILE, "w") as f:
            json.dump(doc, f)
    return run


def test_share_is_chained_over_dispatches_in_the_capture(tmp_path):
    start = {"decode_dispatches": 1000, "decode_dispatches_chained": 200,
             "steps": {"decode": 1000}}
    stop = {"decode_dispatches": 1000 + 140, "decode_dispatches_chained": 200 + 133,
            "steps": {"decode": 1140}}
    run = make_run(tmp_path, (start, stop))
    assert perf_run.read_metric(run, NAME) == pytest.approx(95.0)


def test_serial_capture_reads_zero_not_nothing(tmp_path):
    start = {"decode_dispatches": 50, "decode_dispatches_chained": 40}
    stop = {"decode_dispatches": 90, "decode_dispatches_chained": 40}
    assert perf_run.read_metric(make_run(tmp_path, (start, stop)), NAME) == 0.0


@pytest.mark.parametrize("counts", [
    None,                                                      # no span file
    ({"steps": {"decode": 10}, "preemptions": 0},              # the parent's counts
     {"steps": {"decode": 150}, "preemptions": 0}),
    ({"decode_dispatches": 7, "decode_dispatches_chained": 5},  # none in the capture
     {"decode_dispatches": 7, "decode_dispatches_chained": 5}),
], ids=["no_file", "no_counts", "no_dispatch"])
def test_nothing_to_read_gives_none(tmp_path, counts):
    assert perf_run.read_metric(make_run(tmp_path, counts), NAME) is None


def test_benchmark_lists_the_metric_for_the_closed_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step loop",
        "moves": "out_tok_s",
        "workloads": ["qwen2.5-7b.decode-heavy", "kimi-linear-48b.long-decode"],
    }
