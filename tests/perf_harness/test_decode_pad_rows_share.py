"""``perf/metrics/decode_pad_rows_share.py`` on a written-out
``program_spans.json``: the share of the untraced window's decode rows that
were padding — and nothing (no error) from a history without the counts
(the parent commit's side of the PR that added them), without a decode
dispatch, or of fewer than five pairs."""

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
    "closed": ("out_tok_s", ["kimi-linear-48b.long-decode"]),
    "open": ("tpot_mean_ms", ["qwen3-next-80b.chat-long",
                              "nemotron-3-nano-30b.chat-burst"]),
}


def history(seconds, bucket=64, live=48, with_counts=True,
            decodes_a_second=45) -> list[dict]:
    """One entry a second: ``decodes_a_second`` decode dispatches of a
    ``bucket``-row program with ``live`` rows running."""
    out = []
    for i, t in enumerate(seconds):
        n = decodes_a_second * i
        counts = {"dispatches": {"decode": n, "prefill": i},
                  "decode_dispatches": n, "decode_dispatches_chained": n}
        if with_counts:
            counts.update(decode_rows_dispatched=n * bucket,
                          decode_rows_padded=n * (bucket - live))
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


@pytest.mark.parametrize("variant,bucket,live,want", [
    ("closed", 64, 48, 25.0),      # kimi: 48 clients in the 64-row bucket
    ("open", 32, 8, 75.0),         # qwen3-next: 8 rows in the 32-row bucket
    ("open", 4, 4, 0.0),           # a full bucket reads zero, not nothing
])
def test_share_is_padded_over_dispatched_rows_of_the_untraced_window(
    tmp_path, variant, bucket, live, want
):
    run = make_run(tmp_path, history(range(0, 70), bucket, live))
    got = perf_run.read_metric(run, f"decode_pad_rows_share.{variant}")
    assert got == pytest.approx(want)
    note = next(n["decode_pad_rows_share"] for n in run.notes
                if "decode_pad_rows_share" in n)
    # entries 10..59 less the pairs that touch the capture's [20, 25]
    assert note["decode_dispatches"] == 43 * 45
    assert note["decode_rows_dispatched"] == 43 * 45 * bucket
    assert note["decode_rows_padded"] == 43 * 45 * (bucket - live)
    assert note["rows_a_dispatch"] == bucket
    assert note["live_rows_a_dispatch"] == live


@pytest.mark.parametrize("hist", [
    None,                                             # an older commit: no history
    history(range(0, 70), with_counts=False),         # the parent: no such counts
    history(range(0, 70), decodes_a_second=0),        # no decode in the window
    history(range(10, 15)),                           # 5 entries = 4 pairs
], ids=["no_history", "no_counts", "no_decode", "four_pairs"])
def test_nothing_to_read_gives_none_and_no_error(tmp_path, hist):
    run = make_run(tmp_path, hist)
    assert perf_run.read_metric(run, "decode_pad_rows_share.closed") is None
    assert not any("decode_pad_rows_share" in n for n in run.notes)


def test_five_pairs_are_enough(tmp_path):
    run = make_run(tmp_path, history(range(10, 16)))  # 6 entries = 5 pairs
    assert perf_run.read_metric(
        run, "decode_pad_rows_share.closed") == pytest.approx(25.0)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_benchmark_lists_the_metric_for_its_cells(variant):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = f"decode_pad_rows_share.{variant}"
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    moves, cells = VARIANTS[variant]
    assert entry == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": moves, "workloads": cells,
    }
    reports = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(reports["workloads"])


# the families whose cells keep a recurrent-state plane (models/<family>.py
# RECURRENT_STATE): the cells a metric of that plane may list alone
STATE_FAMILIES = ("kimi_linear", "qwen3_next", "nemotron_h")


def test_the_nemotron_cell_keeps_its_listing():
    """``test_prefill_fill_share.test_the_nemotron_cell_keeps_its_listing``
    let a metric beyond PR 37's list that cell only if it lists every
    open-loop cell, and is skipped since ``decode_pad_rows_share.open`` lists
    the two open-loop cells with a state plane (``tests/conftest.py``). Every
    assertion it made is held here, that rule as "every open-loop cell, or
    cells of recurrent-state families only"."""
    nemotron, qwen = "nemotron-3-nano-30b.chat-burst", "qwen3-next-80b.chat-long"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}

    def listed(cell):
        return {n for n, m in metrics.items() if cell in m.get("workloads", ())}

    def keeps_state(cell):
        with open(os.path.join(REPO, configs[cells[cell]["config"]]["file"])) as f:
            return json.load(f)["model_type"] in STATE_FAMILIES

    cell = cells[nemotron]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b", "chat-burst", 1)
    assert len(cell["why"]) <= 200
    config = configs["nemotron-3-nano-30b"]
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(qwen) < names.index(nemotron)
    at_pr_37 = {
        "ttft_p50_ms", "tpot_mean_ms", "ssm_decode_roofline", "moe_updown_roofline",
        "gen_late_p80_ms", "slo_met_share", "ttft_p85_ms.watch", "itl_p99_ms.watch",
        "frontend_ms_p50", "queue_wait_ms_p50", "prefill_ms_p50",
        "batch_running_mean.open", "kv_preemptions.open", "serve_compiles.open",
        "step_device_ms_p50.open", "step_host_ms_p50.open", "prefill_device_share.open",
        "device_idle_share.open", "idle_attributed_share.open",
        "attn_decode_roofline.open", "state_slots_used_share.open", "moe_touched_share"}
    assert at_pr_37 | {"prefill_fill_share.open",
                       "decode_pad_rows_share.open"} <= listed(nemotron)
    open_cells = set(metrics["ttft_p50_ms"]["workloads"])
    for name in listed(nemotron) - at_pr_37:
        on = set(metrics[name]["workloads"])
        assert open_cells <= on or all(map(keeps_state, on)), name
    for name in listed(nemotron) & listed(qwen):
        on = metrics[name]["workloads"]
        assert on.index(qwen) < on.index(nemotron)      # appended, nothing moved
    for name in ("ssm_decode_roofline", "moe_updown_roofline"):
        m = metrics[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
            "kernels", "tpot_mean_ms", "%", "device_trace")
        assert m in bench["per_layer"] and qwen not in m["workloads"]
    with open(os.path.join(REPO, "perf", "reference", "limits", nemotron + ".json")) as f:
        assert 0 < json.load(f)["logprob_err_mean"] < 1
