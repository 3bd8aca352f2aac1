"""The ``nemotron_h`` family in the benchmark, at a size a test holds: its
family module's seeded draw is the program's recipe value for value, its
``a8`` control comes out as NOT correct by the limit the program meets,
the bursty kind's schedule is a function of the mix and ``--seconds`` alone
with the mean rate and the spread of gaps the mix states, the new readers
read what the program counts, and ``perf/run.py`` drives the family's cell
end to end on the CPU (server child, window, probe, reference child, result
line) in a work directory of its own."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.metrics import moe_updown_roofline, ssm_decode_roofline  # noqa: E402
from perf.metrics import nemotron_h_costs as costs  # noqa: E402
from perf.reference import check, control, nemotron_h as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402
from perf.traffic.kinds import open_burst, open_loop  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations, on the
# CPU) reads 0.0105-0.0192 at widths of 128 over four seeds, 0.0192 at the
# rehearsal's own (1e-5 with float32 activations: what is left is bf16's
# rounding of matmul operands, and a router choice it turns now and then),
# the a8 control 0.0317 / 0.0400 / 0.1673 at its three seeds: the geometric
# mean of the program's largest and the control's smallest
TINY_LIMIT = 0.025
CELL, MIX = "tiny-nemotron-h.chat-burst", "tiny-chat-burst"
BENCH_CELL = "nemotron-3-nano-30b.chat-burst"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-nemotron-h.json")) as f:
        return hf_config(json.load(f))


def published():
    with open(os.path.join(REPO, "perf", "configs", "nemotron-3-nano-30b.json")) as f:
        return json.load(f)


def jobs_for(cfg, seed, lengths=(140, 157, 133, 171), out=12):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


def test_the_configuration_names_this_family(cfg):
    assert family_of(cfg) is family
    pub = hf_config(published())
    assert family_of(pub) is family
    g = family.geometry(pub)
    want = dict(D=2688, V=131072, H=32, Hk=2, Dh=128, Hm=64, P=64, N=128, G=8,
                E=128, Fe=1856, Fs=3712, k=6, inner=4096, conv=6144)
    assert {n: g[n] for n in want} == want
    assert g["pattern"] == "MEMEM*EMEMEM*" and g["expert_form"] == "updown"
    assert {"f32", "a8"} <= set(family.PRECISIONS)
    with pytest.raises(ValueError):
        family.logits_fn(cfg, "w4")


def test_the_configuration_file_is_the_catalog_row_cut_in_depth_alone():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    mine = published()
    assert mine["source"] == row["source_url"]
    assert mine["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    for key, value in row["config"].items():
        if key in mine["reduced"]:
            continue
        assert mine[key] == value, key
    assert mine["num_hidden_layers"] == 13
    assert row["config"]["hybrid_override_pattern"].startswith(
        mine["hybrid_override_pattern"])
    assert mine["published"]["hybrid_override_pattern"] == \
        row["config"]["hybrid_override_pattern"]
    assert mine["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert mine["serving"]["engine"] == {"max_model_len": 4096, "num_blocks": 8192}
    assert "deployment" in mine and len(mine["assumed"]) >= 8


def test_reference_against_itself_reads_zero(cfg):
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-4 and got["positions"] == 48


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_a8_control_is_not_correct(cfg, seed):
    got = control.control_error(cfg, seed, "a8", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


STACKED = ["norm", "m_win", "m_wdt", "m_conv", "m_conv_bias", "m_A_log",
           "m_dt_bias", "m_D", "m_onorm", "m_wo", "attn_wq", "attn_wk", "attn_wv",
           "attn_wo", "router", "router_bias", "ws_up", "ws_down", "w_up", "w_down"]
EXPERTS = ["we_up", "we_down"]
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, nemotron_h as nh

    mc = ModelConfig.from_dict(cfg)
    return nh.init_params_quantized(mc, seed=SEED), nh.param_shapes(mc)


def served(params, name, *index):
    """A parameter's slice as the program serves it, in float32."""
    w = np.asarray(params[name][index], np.float32)
    if name + "_scale" not in params:
        return w
    s = np.asarray(params[name + "_scale"][index])
    return w * (s[:, None] if name == "embed" else s[None, :])


def assert_same_draw(mine, theirs):
    """Value for value — but for a value that sat on an int8 rounding tie
    and fell the other way in the other program's fused arithmetic: at
    most one in 10 000, and by one quantization step."""
    diff = np.abs(np.asarray(mine) - theirs)
    assert (diff > 1e-7).mean() <= 1e-4
    assert diff.max() <= max(np.abs(theirs).max(), 1e-9) / 127 * 1.01


def test_the_parameter_order_is_the_programs(cfg, program_params):
    _, shapes = program_params
    assert list(family.param_index(family.geometry(cfg))) == list(shapes)
    # and a pattern without two of the kinds leaves their parameters out
    some = dict(cfg, hybrid_override_pattern="MMEE", num_hidden_layers=4)
    names = list(family.param_index(family.geometry(some)))
    assert "m_win" in names and "we_up" in names
    assert not {"attn_wq", "w_up"} & set(names)


@pytest.mark.parametrize("name", STACKED)
def test_the_draw_of_a_stack_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    g = family.geometry(cfg)
    idx = family.param_index(g)
    layer = shapes[name][0][0] - 1          # the stack's last layer
    mine = family.draw(
        jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(SEED), idx[name]), layer),
        name, shapes[name][0][1:], g["dt_range"])
    assert_same_draw(mine, served(params, name, layer))


@pytest.mark.parametrize("name", EXPERTS)
def test_the_draw_of_an_expert_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    layer, expert = 1, 5
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(SEED), idx[name]), layer), expert)
    assert_same_draw(family.draw(k, name, shapes[name][0][2:]),
                     served(params, name, layer, expert))


@pytest.mark.parametrize("name", ["embed", "lm_head", "final_norm"])
def test_the_draw_of_a_table_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    mine = family.draw(jax.random.fold_in(
        jax.random.PRNGKey(SEED), idx[name]), name, shapes[name][0])
    assert_same_draw(mine, served(params, name))


def test_the_family_module_and_the_repos_reference_agree(cfg, program_params):
    """Two plain references written apart (this one draws its weights, the
    repo's takes the program's) give the same logits."""
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.reference import nemotron_h as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    tokens = rng.integers(5, cfg["vocab_size"], (2, 48)).astype(np.int32)
    at = np.tile(np.arange(40, 48, dtype=np.int32), (2, 1))
    mine = np.asarray(family.logits_fn(cfg)(
        SEED, tokens, np.array([48, 48], np.int32), at))
    theirs = np.asarray(repo_ref.forward(
        ModelConfig.from_dict(cfg), params, jnp.asarray(tokens)))[:, 40:48]
    np.testing.assert_allclose(mine, theirs, rtol=0, atol=2e-4)


# -- the kind and the mix ----------------------------------------------------------
def test_open_burst_schedule_is_a_function_of_the_mix_and_seconds_alone():
    mix = sched.load_mix("chat-burst")
    a, b = sched.build(mix, 50.0), sched.build(sched.load_mix("chat-burst"), 50.0)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) != sched.digest(sched.build(mix, 51.0))
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert sched.digest(sched.build(other, 50.0)) != sched.digest(a)
    seeds = {sched.load_mix(m)["schedule_seed"]
             for m in ("chat", "chat-long", "sessions", "decode-heavy", "long-decode")}
    assert mix["schedule_seed"] not in seeds
    # a longer window extends the same schedule
    longer = sched.build(mix, 60.0)["entries"]
    assert longer[:len(a["entries"])] == a["entries"]
    assert open_burst.totals is open_loop.totals and open_burst.drive is open_loop.drive


@pytest.mark.parametrize("shape", [0.25, 1.0, 4.0])
def test_open_burst_gaps_have_the_mean_and_the_spread_the_mix_states(shape):
    mix = dict(sched.load_mix("chat-burst"), arrival_shape=shape, rate_rps=8.0,
               ramp_s=0)
    entries = sched.build(mix, 4000.0)["entries"]
    gaps = np.diff([0.0] + [e["due"] for e in entries])
    assert len(entries) / 4000.0 == pytest.approx(8.0, rel=0.05)
    assert gaps.mean() == pytest.approx(1 / 8.0, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(shape ** -0.5, rel=0.08)


def test_chat_burst_is_the_issues_traffic_and_fits_the_served_context():
    mix = sched.load_mix("chat-burst")
    assert mix["kind"] == "open_burst" and mix["ramp_s"] == 8
    assert mix["arrival_shape"] == 0.25                       # CV 2
    assert mix["prompt_tokens"] == {"median": 256, "sigma": 0.9, "min": 32, "max": 3072}
    assert mix["output_tokens"] == {"median": 96, "sigma": 0.7, "min": 16, "max": 512}
    assert mix["slo"] == {"ttft_ms": 1000, "gap_ms": 60} and mix["drain_limit_s"] == 60
    assert mix["probe_rows_max"] == 64
    assert round(mix["rate_rps"] * 10) == pytest.approx(mix["rate_rps"] * 10)
    limit = published()["serving"]["engine"]["max_model_len"]
    entries = sched.build(mix, 50.0)["entries"]
    assert max(e["prompt"] + e["out"] for e in entries) <= mix["max_total_tokens"] <= limit
    window = [e for e in entries if e["due"] >= 0]
    assert len(window) == pytest.approx(50 * mix["rate_rps"], rel=0.35)
    # bursts: the busiest second of the window holds several times the mean
    per_second = np.bincount([int(e["due"]) for e in window], minlength=50)
    assert per_second.max() >= 2.5 * mix["rate_rps"]
    # the probe: the state slots' worth of rows at most, the longest prompt
    # at the cap (three prefill chunks), the same for every seed
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("chat-burst")) and len(waves) == 1
    rows = waves[0]
    assert 12 <= len(rows) <= 64 and 768 <= sum(j["out"] for j in rows) <= 1024
    assert max(j["new"] for j in rows) == rows[0]["new"] == 3072
    uncapped = open_loop.probe(dict(mix, rate_rps=40.0), np.random.default_rng(1))
    capped = open_burst.probe(dict(mix, rate_rps=40.0), np.random.default_rng(1))
    assert len(uncapped[0]) > 64 and capped[0] == uncapped[0][:64]
    a = check.wave_jobs(1, 32000, rows, [])
    b = check.wave_jobs(2**31 + 5, 32000, rows, [])
    assert [len(j["ids"]) for j in a] == [len(j["ids"]) for j in b]
    assert a[0]["ids"] != b[0]["ids"]
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


# -- the benchmark's entries -----------------------------------------------------
READ_AS_THEY_STAND = [
    "gen_late_p80_ms", "slo_met_share", "ttft_p85_ms.watch", "itl_p99_ms.watch",
    "frontend_ms_p50", "queue_wait_ms_p50", "prefill_ms_p50",
    "batch_running_mean.open", "kv_preemptions.open", "serve_compiles.open",
    "step_device_ms_p50.open", "step_host_ms_p50.open", "prefill_device_share.open",
    "device_idle_share.open", "idle_attributed_share.open",
    "attn_decode_roofline.open", "state_slots_used_share.open", "moe_touched_share"]


QWEN_CELL = "qwen3-next-80b.chat-long"


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return (bench, {w["name"]: w for w in bench["workloads"]},
            {c["name"]: c for c in bench["configs"]}, metrics)


def _listed(metrics: dict, cell: str) -> set:
    return {name for name, m in metrics.items() if cell in m.get("workloads", ())}


def _limit(cell: str) -> float:
    with open(os.path.join(REPO, "perf", "reference", "limits", cell + ".json")) as f:
        return json.load(f)["logprob_err_mean"]


def test_the_cell_is_listed_where_its_readers_read():
    """Everything is found by NAME and held by membership and order, never
    by last position: the next cell is appended behind this one."""
    bench, cells, configs, metrics = _benchmark()
    cell = cells[BENCH_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b", "chat-burst", 1)
    assert len(cell["why"]) <= 200
    config = configs["nemotron-3-nano-30b"]
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(QWEN_CELL) < names.index(BENCH_CELL)   # put behind what was there
    assert _listed(metrics, BENCH_CELL) == {
        "ttft_p50_ms", "tpot_mean_ms", "ssm_decode_roofline", "moe_updown_roofline",
        *READ_AS_THEY_STAND}
    for name in _listed(metrics, BENCH_CELL) & _listed(metrics, QWEN_CELL):
        on = metrics[name]["workloads"]
        assert on.index(QWEN_CELL) < on.index(BENCH_CELL)      # appended, nothing moved
    for name in ("ssm_decode_roofline", "moe_updown_roofline"):
        m = metrics[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"]) == (
            "kernels", "tpot_mean_ms", "%", "device_trace")
        assert m in bench["per_layer"] and QWEN_CELL not in m["workloads"]
    assert 0 < _limit(BENCH_CELL) < 1


def test_the_qwen3_next_cell_keeps_its_listing():
    """``test_perf_qwen3_next.py``'s listing test also wants that cell ALONE on
    ``moe_touched_share`` and ``state_slots_used_share.open``, where this
    PR's cell is appended, and is skipped for it (``tests/conftest.py``).
    Everything else it holds is held here, and those two lists by order."""
    _, cells, configs, metrics = _benchmark()
    cell = cells[QWEN_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b", "chat-long", 1)
    assert configs["qwen3-next-80b"]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    listed = _listed(metrics, QWEN_CELL)
    assert {"ttft_p50_ms", "tpot_mean_ms", "gdn_decode_roofline", "moe_touched_share",
            "moe_roofline.open", "state_slots_used_share.open",
            "attn_decode_roofline.open", "step_device_ms_p50.open",
            "device_idle_share.open"} <= listed
    assert not {"qmm_roofline.open", "prefix_hit_share", "cached_token_share"} & listed
    for name in ("gdn_decode_roofline", "moe_roofline.open"):
        # the delta rule's kernel and three-matrix experts: not this PR's cell's
        assert metrics[name]["moves"] == "tpot_mean_ms"
        assert metrics[name]["workloads"][0] == QWEN_CELL
        assert BENCH_CELL not in metrics[name]["workloads"]
    for name in ("moe_touched_share", "state_slots_used_share.open"):
        assert metrics[name]["moves"] == "tpot_mean_ms"
        assert metrics[name]["workloads"][:2] == [QWEN_CELL, BENCH_CELL]
    assert 0 < _limit(QWEN_CELL) < 1


# -- the new readers --------------------------------------------------------------
def test_ssm_decode_cost_counts_the_state_twice_and_the_operands_once():
    ops, byts = costs.ssm_decode_cost(3, 64, 64, 128, 8)
    state = 64 * 64 * 128
    assert ops == 5.0 * 3 * state
    assert byts == 3 * (2 * state * 4 + (2 * 64 * 64 + 2 * 64 + 2 * 8 * 128) * 4)


def test_moe_updown_cost_counts_two_matrices_an_expert():
    from perf.metrics import kimi_linear_costs

    ops, byts = costs.moe_updown_cost(600, 90, 100, 2688, 1856)
    assert ops == 2.0 * 600 * 2 * 2688 * 1856
    assert byts == 90 * (2 * 2688 * 1856 + (1856 + 2688) * 4) + 2 * 100 * 2688 * 2
    ops3, byts3 = kimi_linear_costs.moe_cost(600, 90, 100, 2688, 1856)
    assert ops3 == 1.5 * ops and byts3 > 1.45 * byts    # why moe_roofline is not listed


class FakeRun:
    def __init__(self, config, ops=None, samples=()):
        self.config, self.trace = config, {"ops": ops or {}}
        self.trace_span, self.samples = (10.0, 12.0), list(samples)
        self.device, self.notes = {"kind": "TPU v5 lite"}, []
        self.trace_dir = None


def test_ssm_decode_roofline_reads_its_kernel_and_nothing_elsewhere():
    pub = hf_config(published())
    rows = [300, 700, 150, 2048, 90]
    _, byts = costs.ssm_decode_cost(len(rows), 64, 64, 128, 8)
    least = byts / 819e9
    ops = {"ssm_decode_update.3": {"calls": 60, "total_s": 60 * least / 0.4,
                                   "median_s": least / 0.4}}
    run = FakeRun(pub, ops=ops, samples=[{"t": 11.0, "contexts": rows}])
    assert ssm_decode_roofline.read(run) == pytest.approx(40.0, rel=1e-3)
    assert run.notes[0]["ssm_decode_roofline"]["calls"] == 60
    assert ssm_decode_roofline.read(FakeRun(pub)) is None           # no such kernel
    assert ssm_decode_roofline.read(FakeRun(
        pub, ops={"kda_decode_update.1": ops["ssm_decode_update.3"]},
        samples=run.samples)) is None                               # the parent's program
    for other in ("qwen3-next-80b", "kimi-linear-48b", "mistral-7b"):
        with open(os.path.join(REPO, "perf", "configs", other + ".json")) as f:
            theirs = hf_config(json.load(f))
        assert ssm_decode_roofline.read(
            FakeRun(theirs, ops=ops, samples=run.samples)) is None


def test_moe_updown_roofline_is_least_over_the_time_of_the_experts_ops(monkeypatch):
    from perf.metrics import kimi_linear_costs

    pub = hf_config(published())
    deltas = {"engine.moe_layer_calls": 50, "engine.moe_experts_touched": 50 * 90,
              "engine.moe_local_assignments": 50 * 144}
    monkeypatch.setattr(kimi_linear_costs, "count_deltas", lambda run: deltas)
    _, byts = costs.moe_updown_cost(50 * 144, 50 * 90, 50 * 24, 2688, 1856)
    least = byts / 819e9
    ops = {
        "fusion.7_f32_128_24_1856__fusion": {"calls": 50, "total_s": least,
                                             "median_s": least / 50},
        "fusion.8_f32_24_2688__fusion": {"calls": 50, "total_s": least,
                                         "median_s": least / 50},
        "fusion.9_bf16_24_4096__fusion": {"calls": 50, "total_s": 9.0, "median_s": 1.0},
    }
    run = FakeRun(pub, ops=ops)
    assert moe_updown_roofline.read(run) == pytest.approx(50.0, rel=1e-3)
    note = run.notes[0]["moe_updown_roofline"]
    assert note["experts_touched_per_call"] == 90 and note["experts_held"] == 128
    sliced = {"fusion.23_s8_128_2688_1856__fusion":     # a layer's experts, for its blocks
              {"calls": 5, "total_s": 4 * least, "median_s": least},
              "ragged-dot.3_f32_6144_4096__custom-call":  # no program of this family's
              {"calls": 5, "total_s": 4 * least, "median_s": least}}
    assert moe_updown_roofline.read(FakeRun(pub, ops=sliced)) == pytest.approx(
        25.0, rel=1e-3)
    monkeypatch.setattr(kimi_linear_costs, "count_deltas", lambda run: None)
    assert moe_updown_roofline.read(FakeRun(pub, ops=ops)) is None   # no counts
    monkeypatch.setattr(kimi_linear_costs, "count_deltas", lambda run: deltas)
    with open(os.path.join(REPO, "perf", "configs", "qwen3-next-80b.json")) as f:
        gated = hf_config(json.load(f))
    assert moe_updown_roofline.read(FakeRun(gated, ops=ops)) is None  # three matrices


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-nemotron-h", "file": os.path.relpath(
        os.path.join(DATA, "tiny-nemotron-h.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-nemotron-h",
                           "traffic": MIX, "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL for w in m["workloads"] if w == BENCH_CELL]
    return bench


def tiny_mix(name: str) -> dict:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return dict(json.load(f), name=name)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(monkeypatch, capsys, tmp_path, trace):
    from perf import server as srv

    # a work directory of its own: the other rehearsals share
    # <checkout>/.perf_work (or hold their own) and clear its profiles
    monkeypatch.setattr(srv, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(perf_run, "REQUIRE_PLATFORM", "cpu")
    monkeypatch.setattr(perf_run, "load_benchmark", tiny_benchmark)
    monkeypatch.setattr(check, "POSITIONS", 48)
    # the capture lies past the ramp and is 2 s long: a bursty schedule has
    # whole seconds without an arrival, and a capture inside the ramp or of
    # half a second can hold no expert call (PERF.md section 7)
    monkeypatch.setattr(perf_run, "TRACE_AT_S", 2.0)
    monkeypatch.setattr(perf_run, "TRACE_MS", 2000)
    monkeypatch.setattr(sched, "load_mix", tiny_mix)
    monkeypatch.setattr(check, "load_limits",
                        lambda cell: {"logprob_err_mean": TINY_LIMIT})
    rc = perf_run.main(["--workload", CELL, "--seed", str(2**31 + 78),
                        "--seconds", "8", "--trace", str(trace)])
    out = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.out.splitlines() if ln.startswith("{")]
    assert rc == 0, out.err[-3000:]
    result = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert result["correct"] is True and result["failed"] == 0
    assert phases["window"]["prefix"][0] == 0          # reuse is a counted miss
    assert phases["window"]["prefix"][1] > 0
    assert phases["outputs"]["compared"]["rows_compared"] >= 8
    assert phases["engine_up"]["kv_pool"]["total_blocks"] == 255
    names = set(result["metrics"])
    if trace:
        assert {"state_slots_used_share.open", "moe_touched_share",
                "batch_running_mean.open"} <= names
        assert 0 < result["metrics"]["state_slots_used_share.open"]["value"] <= 100
        assert 0 < result["metrics"]["moe_touched_share"]["value"] <= 100
    else:
        assert {"ttft_p50_ms", "tpot_mean_ms", "setup_s"} <= names
        assert result["metrics"]["tpot_mean_ms"]["value"] > 0
