"""The readers of the ``kimi_linear`` family's own kernels and counts
(``perf/metrics/kda_decode_roofline.py``, ``mla_decode_roofline.py``,
``moe_roofline.py``, ``state_slots_used_share.py``) and the operation and
byte functions beside them, on constructed captures: the arithmetic, that
a share over 100% raises and never clips, and that a program without the
kernels or the counts (the parent commit, another family) reads nothing
and raises nothing."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import roofline, run as perf_run  # noqa: E402
from perf.metrics import kimi_linear_costs as costs  # noqa: E402
from perf.trace import program_spans  # noqa: E402

READERS = ["kda_decode_roofline", "mla_decode_roofline", "moe_roofline",
           "state_slots_used_share"]
BW = 819e9


def config(name: str = "kimi-linear-48b") -> dict:
    with open(os.path.join(REPO, "perf", "configs", f"{name}.json")) as f:
        return json.load(f)


def make_run(tmp_path, ops: dict, counts: tuple[dict, dict] | None,
             contexts=((1000, 2000, 3000),), cfg=None) -> perf_run.Run:
    run = perf_run.Run()
    run.config = cfg or config()
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"ops": ops}
    run.trace_span = (10.0, 12.0)
    run.samples = [{"t": 10.5 + i, "contexts": list(c)} for i, c in enumerate(contexts)]
    run.trace_dir = str(tmp_path)
    if counts is not None:
        doc = {"written": "capture_end", "spans": [], "dropped": 0,
               "start": {"counts": {"engine": counts[0]}},
               "stop": {"counts": {"engine": counts[1]}}}
        with open(tmp_path / program_spans.SPANS_FILE, "w") as f:
            json.dump(doc, f)
    return run


def op(calls: int, total_s: float) -> dict:
    return {"calls": calls, "total_s": total_s, "median_s": total_s / calls}


def test_kda_cost_is_the_state_in_and_out():
    ops, byts = costs.kda_decode_cost(48, 32, 128)
    state = 32 * 128 * 128
    assert byts == 48 * (2 * state * 4 + 5 * 32 * 128 * 4 + 32 * 4)
    assert ops == 8.0 * 48 * state
    assert byts / BW > ops / 197e12          # bound by bytes


def test_mla_cost_reads_each_cached_row_once():
    ops, byts = costs.mla_decode_cost([1000, 3000], 32, 512, 64)
    assert byts == 4000 * 576 * 2 + 2 * 32 * (576 + 512) * 2
    assert ops == 2.0 * 32 * 4000 * (576 + 512)


def test_moe_cost_follows_the_experts_touched():
    ops, byts = costs.moe_cost(384, 100, 48, 2304, 1024)
    per_expert = 3 * 2304 * 1024 + (2 * 1024 + 2304) * 4
    assert byts == 100 * per_expert + 2 * 48 * 2304 * 2
    assert ops == 2.0 * 384 * 3 * 2304 * 1024
    assert costs.moe_cost(384, 128, 48, 2304, 1024)[1] > byts


def test_kda_share_is_least_over_measured(tmp_path):
    rows = (1000, 2000, 3000)
    least = costs.kda_decode_cost(3, 32, 128)[1] / BW
    run = make_run(tmp_path, {
        "kda_decode_update.7_f32_64_32_128__custom-call": op(70, 70 * least * 4),
        "fusion.3_f32_64_2304__fusion": op(9, 1.0)}, None, [rows])
    got = perf_run.read_metric(run, "kda_decode_roofline")
    assert got == pytest.approx(25.0)
    note = run.notes[-1]["kda_decode_roofline"]
    assert note["calls"] == 70 and note["rows_mean"] == 3 and note["bound"] == "bytes"


def test_mla_share_is_least_over_measured(tmp_path):
    ctx = [(1000, 3000), (2000, 2000)]
    least = costs.mla_decode_cost([1000, 3000], 32, 512, 64)[1] / BW
    run = make_run(tmp_path, {
        "mla_decode_attention.1_bf16_64_32_512__custom-call": op(20, 20 * least * 2)},
        None, ctx)
    assert perf_run.read_metric(run, "mla_decode_roofline") == pytest.approx(50.0)
    assert run.notes[-1]["mla_decode_roofline"]["context_mean"] == 2000


def test_moe_share_reads_the_counts_and_the_shapes(tmp_path):
    start = {"moe_layer_calls": 80, "moe_local_assignments": 1000,
             "moe_experts_touched": 500, "steps": {"decode": 10}}
    stop = {"moe_layer_calls": 80 + 240, "moe_local_assignments": 1000 + 240 * 192,
            "moe_experts_touched": 500 + 240 * 100, "steps": {"decode": 40}}
    least = roofline.least_seconds(
        *costs.moe_cost(240 * 192, 240 * 100, 240 * 24, 2304, 1024),
        roofline.peaks("TPU v5 lite"))[0]
    ops = {
        "fusion.254_f32_128_64_1024__fusion": op(240, least),
        "fusion.158_f32_64_2304__fusion": op(240, least),
        "fusion.9_f32_64_2304__fusion": op(600, 600 * 2e-6),       # the stream's own
        "ragged-dot-none.21_f32_32768_2304__ragged-dot": op(8, least / 2),
        "convert_bitcast_fusion.21_bf16_128_1024_2304__fusion": op(8, least / 2),
        "kda_decode_update.7_f32_64_32_128__custom-call": op(210, 9.0),
        "step.12_bf16_64_4096__custom-call": op(500, 9.0),
    }
    run = make_run(tmp_path, ops, (start, stop))
    got = perf_run.read_metric(run, "moe_roofline")
    assert got == pytest.approx(100.0 * least / (3 * least + 600 * 2e-6))
    note = run.notes[-1]["moe_roofline"]
    assert note["layer_calls"] == 240 and note["assignments_per_call"] == 192
    assert note["experts_touched_per_call"] == 100 and note["experts_held"] == 128
    assert note["light_s"] == pytest.approx(600 * 2e-6)


def test_state_slot_share_is_used_over_total(tmp_path):
    start = {"state_slot_steps_used": 100, "state_slot_steps_total": 640}
    stop = {"state_slot_steps_used": 100 + 48 * 50, "state_slot_steps_total": 640 + 64 * 50}
    run = make_run(tmp_path, {}, (start, stop))
    assert perf_run.read_metric(run, "state_slots_used_share") == pytest.approx(75.0)


@pytest.mark.parametrize("name,ops", [
    ("kda_decode_roofline", {"kda_decode_update.7_f32_64_32_128__custom-call": op(70, 1e-9)}),
    ("mla_decode_roofline", {"mla_decode_attention.1_bf16_64_32_512__custom-call": op(20, 1e-9)}),
    ("moe_roofline", {"fusion.254_f32_128_64_1024__fusion": op(8, 1e-9)}),
])
def test_a_share_over_100_raises(tmp_path, name, ops):
    counts = ({"moe_layer_calls": 0, "moe_local_assignments": 0, "moe_experts_touched": 0},
              {"moe_layer_calls": 8, "moe_local_assignments": 1536, "moe_experts_touched": 800})
    run = make_run(tmp_path, ops, counts)
    with pytest.raises(roofline.RooflineError):
        perf_run.read_metric(run, name)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_kernels_or_the_counts_reads_nothing(tmp_path, name):
    """What the parent commit gives: step programs and qmm kernels, the
    engine's older counts, no kernel and no count of this family."""
    ops = {"step.12_bf16_64_4096__custom-call": op(500, 0.2),
           "paged_attention_decode_stacked.3_bf16_64_28_128__custom-call": op(56, 0.5),
           "fusion.9_bf16_64_3584__fusion": op(600, 0.001)}
    old = ({"steps": {"decode": 10}, "prompt_tokens": 5},
           {"steps": {"decode": 40}, "prompt_tokens": 9})
    for counts in (old, None):
        run = make_run(tmp_path, ops, counts, cfg=config("qwen2.5-7b"))
        assert perf_run.read_metric(run, name) is None
        run = make_run(tmp_path, ops, counts)
        if name != "moe_roofline" or counts is None:
            assert perf_run.read_metric(run, name) is None


@pytest.mark.parametrize("name", READERS)
def test_no_capture_at_all_reads_nothing(name):
    run = perf_run.Run()
    run.config = config()
    run.device = {"kind": "TPU v5 lite"}
    assert perf_run.read_metric(run, name) is None
