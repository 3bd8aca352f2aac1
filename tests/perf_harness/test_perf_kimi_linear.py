"""The ``kimi_linear`` family in the benchmark, at a size a test holds:
its family module's seeded draw is the program's recipe value for value,
its ``a8`` control comes out as NOT correct by the limit the program
meets, the new mix's schedule is a function of the file and ``--seconds``
alone, and ``perf/run.py`` drives the family's cell end to end on the CPU
(server child, window, probe, reference child, result line)."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import run as perf_run  # noqa: E402
from perf.reference import check, control, kimi_linear as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations, on
# the CPU) reads 0.087 there at widths of 128 (0.0001 with float32
# activations: it is bf16's noise), the a8 control 0.125-0.217
TINY_LIMIT = 0.105
CELL, MIX = "tiny-kimi.long-decode", "tiny-long-decode"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-kimi-linear.json")) as f:
        return hf_config(json.load(f))


def jobs_for(cfg, seed, lengths=(140, 157, 133, 171), out=12):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


def test_the_configuration_names_this_family(cfg):
    assert family_of(cfg) is family
    g = family.geometry(cfg)
    assert {"D", "V", "H", "Hk", "Dh"} <= set(g)
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


STACKED = ["kda_wq", "kda_conv", "kda_wfb", "kda_A_log", "kda_dt_bias", "kda_wb",
           "kda_wo", "mla_wkva", "mla_wkvb", "w_down", "router", "router_bias",
           "ws_up", "attn_norm", "kda_onorm"]
EXPERTS = ["we_gate", "we_up", "we_down"]


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, kimi_linear as kl

    mc = ModelConfig.from_dict(cfg)
    return kl.init_params_quantized(mc, seed=2**31 + 11), kl.param_shapes(mc)


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
    assert diff.max() <= np.abs(theirs).max() / 127 * 1.01


def test_the_parameter_order_is_the_programs(cfg, program_params):
    _, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    assert list(idx) == list(shapes)


@pytest.mark.parametrize("name", STACKED)
def test_the_draw_of_a_stack_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    key = jax.random.PRNGKey(2**31 + 11)
    layer = shapes[name][0][0] - 1          # the stack's last layer
    mine = family.draw(
        jax.random.fold_in(jax.random.fold_in(key, idx[name]), layer),
        name, shapes[name][0][1:])
    assert_same_draw(mine, served(params, name, layer))


@pytest.mark.parametrize("name", EXPERTS)
def test_the_draw_of_an_expert_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    key = jax.random.PRNGKey(2**31 + 11)
    layer, expert = 2, 5
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, idx[name]), layer), expert)
    mine = family.draw(k, name, shapes[name][0][2:])
    assert_same_draw(mine, served(params, name, layer, expert))


@pytest.mark.parametrize("name", ["embed", "lm_head"])
def test_the_draw_of_a_table_is_the_programs_recipe(cfg, program_params, name):
    import jax

    params, shapes = program_params
    idx = family.param_index(family.geometry(cfg))
    mine = family.draw(jax.random.fold_in(
        jax.random.PRNGKey(2**31 + 11), idx[name]), name, shapes[name][0])
    assert_same_draw(mine, served(params, name))


def test_the_family_module_and_the_repos_reference_agree(cfg, program_params):
    """Two plain references written apart (this one draws its weights, the
    repo's takes the program's) give the same logits."""
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.reference import kimi_linear as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    tokens = rng.integers(5, cfg["vocab_size"], (2, 48)).astype(np.int32)
    at = np.tile(np.arange(40, 48, dtype=np.int32), (2, 1))
    mine = np.asarray(family.logits_fn(cfg)(
        2**31 + 11, tokens, np.array([48, 48], np.int32), at))
    theirs = np.asarray(repo_ref.forward(
        ModelConfig.from_dict(cfg), params, jnp.asarray(tokens)))[:, 40:48]
    np.testing.assert_allclose(mine, theirs, rtol=0, atol=2e-4)


# -- the mix ---------------------------------------------------------------------
def test_long_decode_schedule_is_a_function_of_the_file_and_seconds_alone():
    mix = sched.load_mix("long-decode")
    a, b = sched.build(mix, 50.0), sched.build(sched.load_mix("long-decode"), 50.0)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) != sched.digest(sched.build(mix, 51.0))
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert sched.digest(sched.build(other, 50.0)) != sched.digest(a)


def test_long_decode_requests_fit_the_served_context():
    mix = sched.load_mix("long-decode")
    with open(os.path.join(REPO, "perf", "configs", "kimi-linear-48b.json")) as f:
        config = json.load(f)
    limit = config["serving"]["engine"]["max_model_len"]
    reqs = [r for c in sched.build(mix, 50.0)["clients"] for r in c]
    assert len(sched.build(mix, 50.0)["clients"]) == 48
    assert max(r["prompt"] + r["out"] for r in reqs) <= 3840 <= limit
    assert min(r["prompt"] for r in reqs) >= 640
    assert max(r["prompt"] for r in reqs) > 1024   # some cross a prefill chunk
    firsts = [c[0]["out"] for c in sched.build(mix, 50.0)["clients"]]
    assert sum(firsts[:16]) < sum(firsts[16:32]) < sum(firsts[32:])   # phased
    rows = check.probe_waves(mix)[0]
    assert len(rows) == 48 and all(j["out"] == 16 for j in rows)


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    real = next(w["name"] for w in bench["workloads"]
                if w["traffic"] == "long-decode")
    bench["configs"] = [{"name": "tiny-kimi", "file": os.path.relpath(
        os.path.join(DATA, "tiny-kimi-linear.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-kimi", "traffic": MIX,
                           "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL for w in m["workloads"] if w == real]
    return bench


def tiny_mix(name: str) -> dict:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return dict(json.load(f), name=name)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(monkeypatch, capsys, tmp_path, trace):
    from perf import server as srv

    # a work directory of its own: the other rehearsals share
    # <checkout>/.perf_work and clear its profiles when they start
    monkeypatch.setattr(srv, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(perf_run, "REQUIRE_PLATFORM", "cpu")
    monkeypatch.setattr(perf_run, "load_benchmark", tiny_benchmark)
    monkeypatch.setattr(check, "POSITIONS", 48)
    monkeypatch.setattr(perf_run, "TRACE_AT_S", 0.5)
    monkeypatch.setattr(perf_run, "TRACE_MS", 600)
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
    assert phases["outputs"]["compared"]["rows_compared"] == 4
    assert phases["engine_up"]["kv_pool"]["total_blocks"] == 255
    names = set(result["metrics"])
    if trace:
        assert "state_slots_used_share" in names
        assert 0 < result["metrics"]["state_slots_used_share"]["value"] <= 100
        assert "batch_running_mean.closed" in names
    else:
        assert {"out_tok_s", "setup_s"} <= names
        assert result["metrics"]["out_tok_s"]["value"] > 0
