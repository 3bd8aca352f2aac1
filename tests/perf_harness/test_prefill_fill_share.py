"""``perf/metrics/prefill_fill_share.py`` on a constructed
``program_spans.json``: real over padded prefill tokens by the counts as
they stand at the capture's end — ONE interval in every cell, whether the
capture held a prefill dispatch or, as the chat schedule's never does,
none — and nothing (no error) from a program that keeps no such counts:
the parent commit's side of the PR that added them."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.trace import program_spans  # noqa: E402

VARIANTS = {
    "prefill_fill_share.open": ("ttft_p50_ms", [
        "mistral-7b.chat", "qwen3-next-80b.chat-long",
        "nemotron-3-nano-30b.chat-burst"]),
    "prefill_fill_share.closed": ("out_tok_s", [
        "qwen2.5-7b.decode-heavy", "kimi-linear-48b.long-decode"]),
    "prefill_fill_share.sessions": ("tpot_mean_ms.sessions", [
        "mistral-7b.sessions"]),
}


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


def _note(run) -> dict:
    return next(n["prefill_fill_share"] for n in run.notes
                if "prefill_fill_share" in n)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_share_is_real_over_padded_up_to_the_captures_end(tmp_path, name):
    # ramp and window: 40 steps before the capture, then three 1 x 512 steps
    # of 300, 410 and 480 tokens and one 8 x 128 of 700 inside it
    start = {"prefill_tokens_real": 9_000, "prefill_tokens_padded": 20_000,
             "steps": {"prefill": 40}}
    stop = {"prefill_tokens_real": 9_000 + 1_890,
            "prefill_tokens_padded": 20_000 + 3 * 512 + 1_024,
            "steps": {"prefill": 44}}
    run = make_run(tmp_path, (start, stop))
    assert perf_run.read_metric(run, name) == pytest.approx(100 * 10_890 / 22_560)
    assert _note(run) == {"tokens_real": 10_890, "tokens_padded": 22_560}


def test_a_capture_without_a_prefill_step_reads_the_same_interval(tmp_path):
    """The chat schedule has no arrival in its capture: the reading is the
    one every other cell gets, not a second kind."""
    counts = {"prefill_tokens_real": 30_000, "prefill_tokens_padded": 40_000,
              "decode_dispatches": 100}
    run = make_run(tmp_path, (counts, dict(counts, decode_dispatches=300)))
    assert perf_run.read_metric(run, "prefill_fill_share.open") == pytest.approx(75.0)
    assert _note(run) == {"tokens_real": 30_000, "tokens_padded": 40_000}


@pytest.mark.parametrize("counts", [
    None,                                                     # no span file
    ({"steps": {"decode": 10}, "decode_dispatches": 4},       # the parent's counts
     {"steps": {"decode": 150}, "decode_dispatches": 90}),
    ({"prefill_tokens_real": 0, "prefill_tokens_padded": 0},  # nothing prefilled yet
     {"prefill_tokens_real": 0, "prefill_tokens_padded": 0}),
], ids=["no_file", "no_counts", "no_prefill"])
@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_nothing_to_read_gives_none(tmp_path, counts, name):
    run = make_run(tmp_path, counts)
    assert perf_run.read_metric(run, name) is None
    assert not any("prefill_fill_share" in n for n in run.notes)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_benchmark_lists_the_metric_for_every_cell_by_variant(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    moves, cells = VARIANTS[name]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": moves, "workloads": cells,
    }
    # each listed cell reports the end-to-end metric this one should move
    e2e = next(m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(e2e["workloads"])


def test_the_three_variants_cover_all_six_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [c for m in bench["per_layer"] if m["name"] in VARIANTS
              for c in m["workloads"]]
    assert sorted(listed) == sorted(w["name"] for w in bench["workloads"])


def test_the_nemotron_cell_keeps_its_listing():
    """``test_perf_nemotron_h.test_the_cell_is_listed_where_its_readers_read``
    holds the exact set of metrics that list that cell, and is skipped since
    ``prefill_fill_share.open`` lists it too (``tests/conftest.py``). Every
    assertion it made is held here, the set as "at least these, and beyond
    them only metrics that list every open-loop cell"."""
    nemotron, qwen = "nemotron-3-nano-30b.chat-burst", "qwen3-next-80b.chat-long"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}

    def listed(cell):
        return {n for n, m in metrics.items() if cell in m.get("workloads", ())}

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
    assert at_pr_37 | {"prefill_fill_share.open"} <= listed(nemotron)
    open_cells = set(metrics["ttft_p50_ms"]["workloads"])
    for name in listed(nemotron) - at_pr_37:
        assert open_cells <= set(metrics[name]["workloads"]), name
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

