"""The ``qwen3_next`` family in the benchmark, at a size a test holds: its
family module's seeded draw is the program's recipe value for value, its
``a8`` control comes out as NOT correct by the limit the program meets,
the new mix's schedule is a function of the file and ``--seconds`` alone,
the new readers read what the program counts, and ``perf/run.py`` drives
the family's cell end to end on the CPU (server child, window, probe,
reference child, result line) in a work directory of its own."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.metrics import gdn_decode_roofline, moe_touched_share  # noqa: E402
from perf.metrics import qwen3_next_costs as costs  # noqa: E402
from perf.reference import check, control, qwen3_next as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations, on
# the CPU) reads 0.033 at the rehearsal's seed at widths of 128 (0.017-0.076
# over three seeds; 1e-4 with float32 activations: it is bf16's noise among
# 8 near-equal router scores), the a8 control 0.086-0.136 at its three seeds
TINY_LIMIT = 0.07
CELL, MIX = "tiny-qwen3-next.chat-long", "tiny-chat-long"
BENCH_CELL = "qwen3-next-80b.chat-long"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-qwen3-next.json")) as f:
        return hf_config(json.load(f))


def jobs_for(cfg, seed, lengths=(140, 157, 133, 171), out=12):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


def test_the_configuration_names_this_family(cfg):
    assert family_of(cfg) is family
    with open(os.path.join(REPO, "perf", "configs", "qwen3-next-80b.json")) as f:
        published = hf_config(json.load(f))
    assert family_of(published) is family
    g = family.geometry(published)
    want = dict(D=2048, V=76032, H=16, Hk=2, Dh=256, Hl=32, dl=128, E=256, Fe=512, k=10)
    assert {n: g[n] for n in want} == want
    assert g["attn"] == [3, 7] and len(g["gdn"]) == 6 and g["rot"] == 64
    assert {"f32", "a8"} <= set(family.PRECISIONS)
    with pytest.raises(ValueError):
        family.logits_fn(cfg, "w4")


def test_reference_against_itself_reads_zero(cfg):
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-4 and got["positions"] == 48


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_a8_control_is_not_correct(cfg, seed):
    got = control.control_error(cfg, seed, "a8", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


STACKED = ["gdn_wqkvz", "gdn_wba", "gdn_conv", "gdn_A_log", "gdn_dt_bias",
           "gdn_onorm", "gdn_wo", "attn_wq", "attn_wk", "attn_qnorm", "attn_wo",
           "router", "shared_gate", "ws_down", "mlp_norm"]
EXPERTS = ["we_gate", "we_up", "we_down"]
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, qwen3_next as qn

    mc = ModelConfig.from_dict(cfg)
    return qn.init_params_quantized(mc, seed=SEED), qn.param_shapes(mc)


def served(params, name, *index):
    """A parameter's slice as the program serves it, in float32; a
    ``(1 + w)`` norm as the factor it multiplies by."""
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


@pytest.mark.parametrize("name", STACKED)
def test_the_draw_of_a_stack_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    layer = shapes[name][0][0] - 1          # the stack's last layer
    mine = family.draw(
        jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(SEED), idx[name]), layer),
        name, shapes[name][0][1:])
    assert_same_draw(mine, served(params, name, layer))


@pytest.mark.parametrize("name", EXPERTS)
def test_the_draw_of_an_expert_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    layer, expert = 2, 5
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(SEED), idx[name]), layer), expert)
    assert_same_draw(family.draw(k, name, shapes[name][0][2:]),
                     served(params, name, layer, expert))


@pytest.mark.parametrize("name", ["embed", "lm_head"])
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
    from dynamo_tpu.models.reference import qwen3_next as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    tokens = rng.integers(5, cfg["vocab_size"], (2, 48)).astype(np.int32)
    at = np.tile(np.arange(40, 48, dtype=np.int32), (2, 1))
    mine = np.asarray(family.logits_fn(cfg)(
        SEED, tokens, np.array([48, 48], np.int32), at))
    theirs = np.asarray(repo_ref.forward(
        ModelConfig.from_dict(cfg), params, jnp.asarray(tokens)))[:, 40:48]
    np.testing.assert_allclose(mine, theirs, rtol=0, atol=2e-4)


# -- the mix ---------------------------------------------------------------------
def test_chat_long_schedule_is_a_function_of_the_file_and_seconds_alone():
    mix = sched.load_mix("chat-long")
    a, b = sched.build(mix, 50.0), sched.build(sched.load_mix("chat-long"), 50.0)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) != sched.digest(sched.build(mix, 51.0))
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert sched.digest(sched.build(other, 50.0)) != sched.digest(a)
    assert mix["schedule_seed"] != sched.load_mix("chat")["schedule_seed"]


def test_chat_long_is_the_issues_traffic_and_fits_the_served_context():
    mix = sched.load_mix("chat-long")
    assert mix["kind"] == "open_loop" and mix["ramp_s"] == 8
    assert mix["prompt_tokens"] == {"median": 768, "sigma": 0.9, "min": 64, "max": 3584}
    assert mix["output_tokens"] == {"median": 160, "sigma": 0.7, "min": 16, "max": 512}
    assert mix["slo"] == sched.load_mix("chat")["slo"] and mix["drain_limit_s"] == 60
    assert round(mix["rate_rps"] * 10) == mix["rate_rps"] * 10
    with open(os.path.join(REPO, "perf", "configs", "qwen3-next-80b.json")) as f:
        limit = json.load(f)["serving"]["engine"]["max_model_len"]
    entries = sched.build(mix, 50.0)["entries"]
    assert max(e["prompt"] + e["out"] for e in entries) <= mix["max_total_tokens"] <= limit
    crossing = sum(e["prompt"] > 1024 for e in entries)
    assert 0.2 < crossing / len(entries) < 0.5     # about a third cross a prefill chunk
    # the probe, held to THIS mix's cap (the benchmark's own probe test holds
    # every open-loop cell to the chat mix's 3 072: tests/conftest.py)
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("chat-long")) and len(waves) == 1
    rows = waves[0]
    assert len(rows) >= 12 and 768 <= sum(j["out"] for j in rows) <= 1024
    assert max(j["new"] for j in rows) == rows[0]["new"] == 3584   # four prefill chunks
    a = check.wave_jobs(1, 32000, rows, [])
    b = check.wave_jobs(2**31 + 5, 32000, rows, [])
    assert [len(j["ids"]) for j in a] == [len(j["ids"]) for j in b]
    assert a[0]["ids"] != b[0]["ids"]
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


# -- the benchmark's entries -----------------------------------------------------
def test_the_cell_is_listed_where_its_readers_read():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == BENCH_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b", "chat-long", 1)
    config = next(c for c in bench["configs"] if c["name"] == "qwen3-next-80b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if BENCH_CELL in m.get("workloads", ())}
    assert {"ttft_p50_ms", "tpot_mean_ms", "gdn_decode_roofline", "moe_touched_share",
            "moe_roofline.open", "state_slots_used_share.open",
            "attn_decode_roofline.open", "step_device_ms_p50.open",
            "device_idle_share.open"} <= listed
    assert not {"qmm_roofline.open", "prefix_hit_share", "cached_token_share"} & listed
    for m in bench["per_layer"]:
        if m["name"] in ("gdn_decode_roofline", "moe_touched_share",
                         "moe_roofline.open", "state_slots_used_share.open"):
            assert m["moves"] == "tpot_mean_ms" and m["workloads"] == [BENCH_CELL]
    with open(os.path.join(REPO, "perf", "reference", "limits",
                           BENCH_CELL + ".json")) as f:
        assert 0 < json.load(f)["logprob_err_mean"] < 1


# -- the new readers --------------------------------------------------------------
def test_gdn_decode_cost_counts_the_state_twice_and_the_operands_once():
    ops, byts = costs.gdn_decode_cost(3, 32, 16, 128)
    state = 32 * 128 * 128
    assert ops == 8.0 * 3 * state
    assert byts == 3 * (2 * state * 4 + (2 * 16 + 2 * 32) * 128 * 4 + 2 * 32 * 4)


class FakeRun:
    def __init__(self, config, counts=None, ops=None, samples=()):
        self.config, self.trace = config, {"ops": ops or {}}
        self.trace_span, self.samples = (10.0, 12.0), list(samples)
        self.device, self.notes = {"kind": "TPU v5 lite"}, []
        self.trace_dir = None
        self._counts = counts


def test_moe_touched_share_is_touched_over_calls_times_held(cfg, monkeypatch):
    from perf.metrics import kimi_linear_costs

    deltas = {"engine.moe_layer_calls": 80, "engine.moe_experts_touched": 80 * 2,
              "engine.moe_local_assignments": 999}
    monkeypatch.setattr(kimi_linear_costs, "count_deltas", lambda run: deltas)
    assert moe_touched_share.read(FakeRun(cfg)) == pytest.approx(100.0 * 2 / 8)
    monkeypatch.setattr(kimi_linear_costs, "count_deltas", lambda run: None)
    assert moe_touched_share.read(FakeRun(cfg)) is None     # a program without counts


def test_gdn_decode_roofline_reads_the_shared_kernel_and_nothing_elsewhere(cfg):
    with open(os.path.join(REPO, "perf", "configs", "qwen3-next-80b.json")) as f:
        published = hf_config(json.load(f))
    rows = [900, 1200, 300, 2048]
    _, byts = costs.gdn_decode_cost(len(rows), 32, 16, 128)
    least = byts / 819e9
    ops = {"kda_decode_update.3": {"calls": 60, "total_s": 60 * least / 0.5,
                                   "median_s": least / 0.5}}
    run = FakeRun(published, ops=ops, samples=[{"t": 11.0, "contexts": rows}])
    assert gdn_decode_roofline.read(run) == pytest.approx(50.0, rel=1e-3)
    assert run.notes[0]["gdn_decode_roofline"]["calls"] == 60
    assert gdn_decode_roofline.read(FakeRun(published)) is None      # no such kernel
    with open(os.path.join(REPO, "perf", "configs", "kimi-linear-48b.json")) as f:
        kimi = hf_config(json.load(f))
    assert gdn_decode_roofline.read(FakeRun(kimi, ops=ops, samples=run.samples)) is None


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-qwen3-next", "file": os.path.relpath(
        os.path.join(DATA, "tiny-qwen3-next.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-qwen3-next",
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
    # the capture lies past the ramp, where two or three prompts a second arrive,
    # and is long enough to hold expert calls when other workers load the host
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
