"""The trace reduction on a small recorded trace, and the roofline count.

``data/recorded_trace.json`` is a cut of a real profiler trace of
``mistral-7b.chat`` on a TPU v5e (about two layer iterations of one decode
step): the reducer has to give the busy time, the kernel times and the
row names it gave when the cut was made. Idle gaps and their attribution
are driven by a constructed trace, whose answer is known by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import roofline  # noqa: E402
from perf.trace import reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        return tr.reduce_events(json.load(f))


def test_recorded_trace_busy_and_window(recorded):
    assert recorded["chips"] == 1
    # the cut holds no profiler frames: the interval is first op .. last op
    assert recorded["interval_from"] == "first op..last op"
    assert recorded["window_s"] == recorded["ops_span_s"] \
        == pytest.approx(0.002266896, rel=1e-9)
    assert recorded["busy_s"] == pytest.approx(0.002264659, rel=1e-9)
    assert recorded["idle_s"] == pytest.approx(2.237e-06, rel=1e-3)
    assert recorded["idle_gaps"] == {}  # no gap as long as GAP_MIN_S in the cut


@pytest.mark.parametrize("label,calls,total_s", [
    ("paged_attention_decode_stacked.5_bf16_32_32_128__custom-call", 3, 0.000955565),
    ("closed_call.37_bf16_32_14336__custom-call", 2, 0.000328113),
    ("dynamic-slice_bitcast_fusion.19_s8_4096_14336__fusion", 2, 0.000157896),
    ("closed_call.38_bf16_32_4096__custom-call", 2, 0.000147847),
])
def test_recorded_trace_kernel_times(recorded, label, calls, total_s):
    row = recorded["ops"][label]
    assert row["calls"] == calls
    assert row["total_s"] == pytest.approx(total_s, rel=1e-9)


def test_containers_are_not_rows_and_programs_are(recorded):
    assert not any("__while" in k for k in recorded["ops"])
    (name, row), = recorded["modules"].items()
    assert name.startswith("jit_step_") and row["calls"] == 1
    top = tr.breakdown(recorded)["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("paged_attention_decode")


@pytest.mark.parametrize("raw,label,opcode", [
    ("%closed_call.31 = bf16[1024,14336]{1,0:T(8,128)(2,1)} custom-call(bf16[1024,4096]{1,0} %f)",
     "closed_call.31_bf16_1024_14336__custom-call", "custom-call"),
    ("%while.3 = (s32[]{:T(128)}, bf16[32,1,4096]{2,0,1:T(8,128)(2,1)}) while((s32[]) %t)",
     "while.3_s32___while", "while"),
    ("%copy-done.3 = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16[32,4096]) %c)",
     "copy-done.3_bf16_32_4096__copy-done", "copy-done"),
])
def test_op_label(raw, label, opcode):
    assert tr.op_label(raw) == (label, opcode)


def test_idle_gap_goes_to_the_innermost_frame_of_the_launching_thread():
    ms = 1e6
    events = {
        "device": {"/device:TPU:0": {"XLA Ops": [
            ["%a = f32[8]{0} fusion(f32[8] %x)", 0.0, 2 * ms],
            ["%b = f32[8]{0} fusion(f32[8] %x)", 1 * ms, 2 * ms],   # overlaps a
            ["%a = f32[8]{0} fusion(f32[8] %x)", 5 * ms, 1 * ms],   # 2 ms gap before
            ["%b = f32[8]{0} fusion(f32[8] %x)", 6.2 * ms, 0.8 * ms],  # 0.2 ms: too short
        ]}},
        "host": {
            "/host:CPU/engine": [["PJRT_LoadedExecutable_Execute", 0.0, 0.1 * ms],
                                 ["$engine.py:10 _one_step", 2.5 * ms, 3 * ms],
                                 ["$scheduler.py:7 plan", 3.5 * ms, 1 * ms]],
            "/host:CPU/python3": [["$<unknown> recv", 3.9 * ms, 0.2 * ms],
                                  ["$<frozen x>:1 y", 2.0, 1.0]],
        },
    }
    red = tr.reduce_events(events)
    assert red["window_s"] == pytest.approx(0.007)
    assert red["busy_s"] == pytest.approx(0.003 + 0.001 + 0.0008)
    assert red["ops"]["a_f32_8__fusion"] == {
        "total_s": pytest.approx(0.003), "calls": 2, "median_s": pytest.approx(0.0015)}
    # the gap's midpoint (4 ms) lies in plan(), inside _one_step; the
    # built-in recv() that also covers it (another thread's) is not a frame
    # of the program's files
    assert red["idle_gaps"] == {"_scheduler.py_7_plan": pytest.approx(0.002)}


def test_idle_at_the_edges_of_the_capture_is_counted():
    """The program asked for 10 ms (start_trace returns at 1 ms, stop_trace
    is called at 11 ms) and the device ran for 2 ms in the middle: 80% idle,
    not 0%. The profiler's own start and stop, when only the host is
    recorded, are no part of the interval, and an op is clipped to it."""
    ms = 1e6
    events = {
        "device": {"/device:TPU:0": {"XLA Ops": [
            ["%a = f32[8]{0} fusion(f32[8] %x)", 5 * ms, 2 * ms],
            ["%b = f32[8]{0} fusion(f32[8] %x)", 10.5 * ms, 1 * ms]]}},
        "host": {"/host:CPU/python": [
            ["$profiler.py:101 start_trace", 0.0, 1 * ms],
            ["$engine.py:10 _one_step", 1 * ms, 3.9 * ms],
            ["PJRT_LoadedExecutable_Execute", 4.9 * ms, 0.1 * ms],
            ["$asyncio.py:1 _run_once", 7 * ms, 3 * ms],
            ["$profiler.py:213 stop_trace", 11 * ms, 4 * ms]]},
    }
    red = tr.reduce_events(events)
    assert red["interval_from"] == "start_trace..stop_trace"
    assert red["window_s"] == pytest.approx(0.010)
    assert red["ops_span_s"] == pytest.approx(0.0065)
    assert red["busy_s"] == pytest.approx(0.002 + 0.0005)
    assert red["idle_s"] == pytest.approx(0.0075)
    assert red["idle_gaps"] == {"_engine.py_10__one_step": pytest.approx(0.004),
                                "_asyncio.py_1__run_once": pytest.approx(0.0035)}
    # a trace that shows only one of the two frames keeps that edge
    events["host"]["/host:CPU/python"].pop()
    red = tr.reduce_events(events)
    assert red["interval_from"] == "start_trace..last op"
    assert red["window_s"] == pytest.approx(0.0105)
    assert red["busy_s"] == pytest.approx(0.003)


def test_a_trace_without_a_device_plane_reads_nothing():
    red = tr.reduce_events({"device": {}, "host": {}})
    assert red["chips"] == 0 and red["busy_s"] == 0.0


def test_roofline_share_of_a_recorded_kernel_and_the_stale_count():
    """closed_call.37 (gate and up in one call, 32 rows, 4096 -> 14336) took
    164.06 us a call in the recorded trace."""
    pk = roofline.peaks("TPU v5 lite")
    ops, byts = roofline.qmm_cost(32, 4096, 14336, weights=2)
    least, bound = roofline.least_seconds(ops, byts, pk)
    assert bound == "bytes"
    assert byts == 2 * (4096 * 14336 + 14336 * 4) + 32 * 4096 * 2 + 32 * 14336 * 2
    share = roofline.share_pct(least, 0.0001640565)
    assert 85.0 < share < 90.0
    # a stale count — the weights still counted at bf16's two bytes, as
    # before int8 — would read over 100%: refused, never clipped
    stale = byts + 2 * 4096 * 14336
    with pytest.raises(roofline.RooflineError, match="> 100%"):
        roofline.share_pct(roofline.least_seconds(ops, stale, pk)[0], 0.0001640565)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.RooflineError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(roofline.RooflineError):
        roofline.peaks("cpu")


def test_attention_decode_bytes_are_whole_pages():
    ops, byts = roofline.attn_decode_cost([1, 128, 129], 32, 8, 128, 128)
    pages = 1 + 1 + 2
    assert byts == 2 * pages * 128 * 8 * 128 * 2 + 2 * 3 * 32 * 128 * 2
    assert ops == 4 * 32 * 128 * (1 + 128 + 129)


def mistral_run(trace, model_type=None):
    """What ``qmm_roofline`` reads of ``perf/run.py``'s ``Run``."""
    with open(os.path.join(REPO, "perf", "configs", "mistral-7b.json")) as f:
        config = json.load(f)
    if model_type:
        config["model_type"] = model_type

    class Run:
        notes = []
        device = {"kind": "TPU v5 lite"}

    Run.trace, Run.config = trace, config
    return Run


def test_qmm_roofline_reader_counts_the_weight_slice_copies(recorded):
    """The recorded decode step dates from before PR 26: the kernels alone
    would read 84% of the HBM floor; with the int8 weight-slice copies that
    fed them then (where the HBM read was paid) the weight path reads 47%."""
    from perf.metrics import qmm_roofline

    Run = mistral_run(recorded)
    share = qmm_roofline.read(Run)
    note = Run.notes[-1]["qmm_roofline"]
    assert note["bound"] == "bytes" and note["row_counts"] == [32]
    assert note["weight_slices_s"] > 0.8 * note["kernels_s"]
    assert share == pytest.approx(
        100 * note["least_s"] / (note["kernels_s"] + note["weight_slices_s"]))
    assert 40.0 < share < 55.0
    assert 100 * note["least_s"] / note["kernels_s"] > 80.0


HEAD_ROW = {"calls": 1, "total_s": 0.0001876, "median_s": 0.0001876}


def with_op(recorded, label):
    return dict(recorded, ops={**recorded["ops"], label: HEAD_ROW})


def test_qmm_roofline_counts_the_head_once_at_its_own_shape(recorded):
    """The head runs outside the scan as ``step.<n>`` with N = the
    vocabulary: one ``[8, 4096] x [4096, 32000]`` call, 187.6 us in the
    PR 26 captures (85% of its HBM floor)."""
    from perf.metrics import qmm_roofline

    before = mistral_run(recorded)
    qmm_roofline.read(before)
    was = before.notes[-1]["qmm_roofline"]
    assert was["head_calls"] == 0 and was["head_s"] == 0.0

    Run = mistral_run(with_op(recorded, "step.1_bf16_8_32000__custom-call"))
    share = qmm_roofline.read(Run)
    note = Run.notes[-1]["qmm_roofline"]
    ops, byts = roofline.qmm_cost(8, 4096, 32000)
    assert byts == 4096 * 32000 + 32000 * 4 + 8 * 4096 * 2 + 8 * 32000 * 2
    least, bound = roofline.least_seconds(ops, byts, roofline.peaks("TPU v5 lite"))
    assert bound == "bytes" and 84.0 < 100 * least / HEAD_ROW["total_s"] < 86.0
    assert note["head_calls"] == 1 and note["head_s"] == HEAD_ROW["total_s"]
    assert note["least_s"] == pytest.approx(was["least_s"] + least, rel=1e-12)
    assert note["kernels_s"] == pytest.approx(
        was["kernels_s"] + HEAD_ROW["total_s"], rel=1e-12)
    assert note["row_counts"] == [8, 32]
    assert share == pytest.approx(100 * note["least_s"] / (
        note["kernels_s"] + note["weight_slices_s"]))


@pytest.mark.parametrize("label", [
    "step.3_bf16_8_4096__custom-call",      # a top-level call of another width
    "step.1_f32_8_32000__custom-call",      # not a bf16 result
    "jit_step.1_bf16_8_32000__fusion",      # no custom call
])
def test_qmm_roofline_leaves_other_top_level_calls_alone(recorded, label):
    from perf.metrics import qmm_roofline

    plain, other = mistral_run(recorded), mistral_run(with_op(recorded, label))
    assert qmm_roofline.read(plain) == qmm_roofline.read(other)
    assert other.notes[-1]["qmm_roofline"]["head_calls"] == 0


def test_qmm_roofline_says_nothing_for_a_family_that_lists_no_matmuls(
        recorded, family_files):
    """Which matmuls a layer has is the family's knowledge: a family module
    without ``layer_matmuls`` reads ``None``, never llama's count under
    another model's name; with its own it reads, by files alone."""
    from perf.metrics import qmm_roofline

    family_files("silent")
    Run = mistral_run(recorded, "silent")
    assert qmm_roofline.read(Run) is None and Run.notes == []
    family_files("telling", """
def layer_matmuls(g):
    return {14336: [(4096, 2, False)], 4096: [(4096, 1, False), (14336, 1, True)],
            1024: [(4096, 1, False)]}
""")
    Run = mistral_run(recorded, "telling")
    assert 40.0 < qmm_roofline.read(Run) < 60.0
    # a width the family does not list: nothing, not a guess
    family_files("narrow", "\ndef layer_matmuls(g):\n    return {4096: [(4096, 1, False)]}\n")
    assert qmm_roofline.read(mistral_run(recorded, "narrow")) is None
    with pytest.raises(LookupError, match="'nobody'"):
        qmm_roofline.read(mistral_run(recorded, "nobody"))


def test_attention_decode_reader_takes_its_heads_from_the_family(family_files):
    """Only ``H, Hk, Dh`` of the family's ``geometry``, times the calls the
    trace counted: right for a model in which only some layers attend."""
    from perf.metrics import attn_decode_roofline

    family_files("hybrid")   # the toy family: H 4, Hk 2, Dh 16

    class Run:
        notes, block_size, trace_span = [], 128, (10.0, 12.0)
        device = {"kind": "TPU v5 lite"}
        config = {"model_type": "hybrid", "hidden_size": 64, "vocab_size": 16}
        samples = [{"t": 11.0, "contexts": [100, 300]}]
        trace = {"ops": {"paged_attention_decode_stacked.6_bf16_2_4_16__custom-call":
                         {"calls": 6, "total_s": 6e-6}}}

    share = attn_decode_roofline.read(Run)
    least, _ = roofline.least_seconds(
        *roofline.attn_decode_cost([100, 300], 4, 2, 16, 128),
        roofline.peaks("TPU v5 lite"))
    assert share == pytest.approx(100 * 6 * least / 6e-6)
    assert Run.notes[-1]["attn_decode_roofline"]["calls"] == 6
