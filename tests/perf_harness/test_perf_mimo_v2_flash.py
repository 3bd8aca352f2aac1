"""The ``mimo_v2_flash`` family (MiMo-V2-Flash) in the benchmark, at a size
a test holds: its family module's seeded draw is the program's recipe
value for value, its ``a8`` control comes out as NOT correct by the limit
the program meets, the ``open_classes`` kind's schedule is a function of
the mix and ``--seconds`` alone and holds both classes in their shares,
the probe's compared rows hold the longest row, the four new readers read
what the program counts (none over 100), and ``perf/run.py`` drives the
family's cell end to end on the CPU (server child, window, probe,
reference child, result line) in a work directory of its own."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf import roofline, run as perf_run  # noqa: E402
from perf.metrics import (  # noqa: E402
    attn_prefill_roofline,
    full_decode_roofline,
    mimo_v2_flash_costs as costs,
    window_decode_roofline,
    window_pages_per_row,
)
from perf.reference import check, control, mimo_v2_flash as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402
from perf.traffic.kinds import open_classes, open_loop  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations, on the
# CPU) reads 0.0087 / 0.0148 / 0.0168 / 0.0190 at widths of 128 over four seeds
# (0.0168 at the rehearsal's own), the a8 control 0.0321 / 0.0450 / 0.0467 at
# its three: the geometric mean of the program's largest and the control's
# smallest. Top 6 of 16 experts (8 held), as the deepseek_v3 rehearsal found:
# at top 3 of 8 one turned choice moves a token's logprob by more than the
# control's whole reading
TINY_LIMIT = 0.025
CELL, MIX = "tiny-mimo-v2-flash.short-long", "tiny-short-long"
BENCH_CELL = "mimo-v2-flash.short-long"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-mimo-v2-flash.json")) as f:
        return hf_config(json.load(f))


def published():
    with open(os.path.join(REPO, "perf", "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


def jobs_for(cfg, seed, lengths=(140, 157, 133, 171), out=12):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


# -- the configuration and the family module ----------------------------------------
def test_the_configuration_names_this_family(cfg):
    assert family_of(cfg) is family
    pub = hf_config(published())
    assert family_of(pub) is family
    g = family.geometry(pub)
    want = dict(D=4096, V=152576, H=64, Hk=4, Dh=192, Hk_full=4, Hk_window=8,
                Dk=192, Dv=128, window=128, rot=64, L=12, L_full=3, L_window=9,
                E=16, shards=16, shard=0, Fe=2048, F=16384, k=8)
    assert {n: g[n] for n in want} == want
    assert g["full"] == [0, 5, 11] and g["dense"] == [0]
    assert g["moe"] == list(range(1, 12))
    assert (g["theta_full"], g["theta_window"], g["vscale"]) == (5e6, 1e4, 0.707)
    assert g["scale"] == 1.0 and g["eps"] == 1e-5
    runs = family.layer_runs(g)
    assert [(r["kind"], r["moe"], r["layers"]) for r in runs] == [
        ("full", False, [0]), ("win", True, [1, 2, 3, 4]), ("full", True, [5]),
        ("win", True, [6, 7, 8, 9, 10]), ("full", True, [11])]
    assert [r["attn"] for r in runs] == [[0], [0, 1, 2, 3], [1], [4, 5, 6, 7, 8], [2]]
    assert [r["ffn"] for r in runs] == [[0], [0, 1, 2, 3], [4], [5, 6, 7, 8, 9], [10]]
    assert {"f32", "a8"} <= set(family.PRECISIONS)
    with pytest.raises(ValueError):
        family.logits_fn(cfg, "w4")


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("rope_scaling", {"type": "yarn"}), ("n_shared_experts", 1),
    ("attention_bias", True), ("add_full_attention_sink_bias", True),
    ("attention_chunk_size", 64)])
def test_the_reference_builds_nothing_the_program_refuses(cfg, key, value):
    with pytest.raises(ValueError, match=key):
        family.geometry(dict(cfg, **{key: value}))


def test_the_configuration_file_is_the_catalog_row_cut_in_depth_and_experts_held():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2-Flash")
    mine = published()
    assert mine["source"] == row["source_url"]
    assert mine["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                               "moe_layer_freq", "n_routed_experts"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert key in mine and mine[key] == value, key
    assert mine["num_hidden_layers"] == 12
    assert mine["hybrid_layer_pattern"] == row["config"]["hybrid_layer_pattern"][:12]
    assert mine["moe_layer_freq"] == row["config"]["moe_layer_freq"][:12]
    # a whole period of the published mix after the lead-in, >= 8 experts held
    assert mine["hybrid_layer_pattern"][6:12] == [1, 1, 1, 1, 1, 0]
    assert (mine["n_routed_experts"], mine["expert_shards"]) == (16, 16)
    assert mine["n_routed_experts"] * mine["expert_shards"] \
        == row["config"]["n_routed_experts"] == mine["published"]["n_routed_experts"]
    assert mine["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert mine["published"]["max_position_embeddings"] == 262144
    assert mine["serving"]["engine"] == {"max_model_len": 16384}
    assert "deployment" in mine and len(mine["assumed"]) >= 8
    # no width is cut: every *_dim, head count and size as published
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
                "num_attention_heads", "num_key_value_heads",
                "swa_num_key_value_heads", "num_experts_per_tok",
                "sliding_window", "vocab_size"):
        assert mine[key] == row["config"][key], key


def test_reference_against_itself_reads_zero(cfg):
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-4 and got["positions"] == 48


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_a8_control_is_not_correct(cfg, seed):
    got = control.control_error(cfg, seed, "a8", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


STACKED = ["attn_norm", "mlp_norm", "full_wq", "full_wk", "full_wv", "full_wo",
           "win_wq", "win_wk", "win_wv", "win_wo", "win_sink",
           "w_gate", "w_up", "w_down", "router", "router_bias"]
EXPERTS = ["we_gate", "we_up", "we_down"]


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, mimo_v2_flash as mm

    mc = ModelConfig.from_dict(cfg)
    return mm.init_params_quantized(mc, seed=SEED), mm.param_shapes(mc)


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
    # (a few units in the last place of the largest value: fused or not)
    assert (diff > 1e-6 * max(np.abs(theirs).max(), 0.1)).mean() <= 1e-4
    assert diff.max() <= max(np.abs(theirs).max(), 1e-9) / 127 * 1.01


def test_the_parameter_order_is_the_programs(program_params):
    assert list(family.PARAM_ORDER) == list(program_params[1])


@pytest.mark.parametrize("name", STACKED)
def test_the_draw_of_a_stack_is_the_programs_recipe(program_params, name):
    import jax

    params, shapes = program_params
    layer = shapes[name][0][0] - 1          # the stack's last layer OF ITS KIND
    mine = family.draw(
        jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(SEED), family.PARAM_ORDER.index(name)), layer),
        name, shapes[name][0][1:])
    assert_same_draw(mine, served(params, name, layer))
    if name == "win_sink":
        assert 0.4 < float(np.std(np.asarray(params[name]))) < 1.8


@pytest.mark.parametrize("name", EXPERTS)
def test_the_draw_of_an_expert_is_the_programs_recipe(program_params, name):
    import jax

    params, shapes = program_params
    layer, expert = 2, 5
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(SEED), family.PARAM_ORDER.index(name)), layer), expert)
    assert_same_draw(family.draw(k, name, shapes[name][0][2:]),
                     served(params, name, layer, expert))


@pytest.mark.parametrize("name", ["embed", "lm_head", "final_norm"])
def test_the_draw_of_a_table_is_the_programs_recipe(program_params, name):
    import jax

    params, shapes = program_params
    mine = family.draw(jax.random.fold_in(
        jax.random.PRNGKey(SEED), family.PARAM_ORDER.index(name)), name, shapes[name][0])
    assert_same_draw(mine, served(params, name))


def test_the_family_module_and_the_repos_reference_agree(cfg, program_params):
    """Two plain references written apart (this one draws its weights,
    reads the queries a block at a time and cuts a window layer's keys to
    the block's reach; the repo's takes the program's weights and a whole
    softmax) give the same logits — at a length that takes two query
    blocks, with a row shorter than the rectangle."""
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.reference import mimo_v2_flash as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    T = 2 * family.QUERY_BLOCK
    tokens = rng.integers(5, cfg["vocab_size"], (2, T)).astype(np.int32)
    lengths = np.array([T, 300], np.int32)
    at = np.stack([np.arange(T - 8, T), np.arange(292, 300)]).astype(np.int32)
    mine = np.asarray(family.logits_fn(cfg)(SEED, tokens, lengths, at))
    mc = ModelConfig.from_dict(cfg)
    for b in range(2):
        theirs = np.asarray(repo_ref.forward(
            mc, params, jnp.asarray(tokens[b:b + 1, :lengths[b]])))[0, at[b]]
        np.testing.assert_allclose(mine[b], theirs, rtol=0, atol=3e-4)


# -- the kind and the mix ----------------------------------------------------------
def test_open_classes_schedule_is_a_function_of_the_mix_and_seconds_alone():
    mix = sched.load_mix("short-long")
    a, b = sched.build(mix, 50), sched.build(sched.load_mix("short-long"), 50)
    assert sched.digest(a) == sched.digest(b)
    longer = sched.build(mix, 80)
    n = len(a["entries"])
    assert longer["entries"][:n] == a["entries"] and len(longer["entries"]) > n
    assert sched.digest(sched.build(dict(mix, rate_rps=mix["rate_rps"] * 2), 50)) \
        != sched.digest(a)
    # open_loop's fields, so that no reader is edited
    assert set(a["entries"][0]) == {"i", "due", "prompt", "out", "class"}
    assert open_classes.totals is open_loop.totals
    assert open_classes.drive is open_loop.drive
    t = open_classes.totals(a)
    assert t["window_requests"] == sum(e["due"] >= 0 for e in a["entries"])
    assert a["entries"][0]["due"] < 0 <= a["entries"][-1]["due"] < 50


def test_short_long_is_the_issues_traffic_and_holds_both_classes_in_their_shares():
    mix = sched.load_mix("short-long")
    assert mix["kind"] == "open_classes" and mix["ramp_s"] == 20
    assert mix["drain_limit_s"] == 90 and mix["max_total_tokens"] == 16384
    assert mix["slo"] == {"ttft_ms": 3000, "gap_ms": 80}
    short, long_ = mix["classes"]["short"], mix["classes"]["long"]
    assert (short["share"], long_["share"]) == (0.8, 0.2)
    assert short["prompt_tokens"] == {"median": 512, "sigma": 0.8, "min": 64, "max": 2048}
    assert short["output_tokens"] == {"median": 128, "sigma": 0.7, "min": 16, "max": 512}
    assert long_["prompt_tokens"] == {"median": 8192, "sigma": 0.4, "min": 4096,
                                      "max": 14336}
    assert long_["output_tokens"] == {"median": 512, "sigma": 0.6, "min": 64,
                                      "max": 1536}
    assert published()["serving"]["engine"]["max_model_len"] == mix["max_total_tokens"]
    entries = sched.build(dict(mix, rate_rps=20.0), 400)["entries"]
    longs = [e for e in entries if e["class"] == "long"]
    shorts = [e for e in entries if e["class"] == "short"]
    assert len(entries) > 5000 and 0.18 < len(longs) / len(entries) < 0.22
    assert all(4096 <= e["prompt"] <= 14336 and 64 <= e["out"] <= 1536 for e in longs)
    assert all(64 <= e["prompt"] <= 2048 and 16 <= e["out"] <= 512 for e in shorts)
    assert all(e["prompt"] + e["out"] <= 16384 for e in entries)
    assert 7000 < np.median([e["prompt"] for e in longs]) < 9500
    assert 430 < np.median([e["prompt"] for e in shorts]) < 600
    with pytest.raises(ValueError, match="add up"):
        open_classes.build(dict(mix, classes={"a": dict(short, share=0.5)}), 5,
                           np.random.default_rng(0))


def test_the_probes_compared_rows_hold_the_longest_row():
    mix = sched.load_mix("short-long")
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("short-long")) and len(waves) == 1
    (wave,) = waves
    assert [j["row"] for j in wave] == list(range(8))
    assert wave[0]["new"] == 14336 and wave[-1]["new"] == 8192
    assert all(64 <= j["new"] <= 2048 for j in wave[1:7])
    assert all(j["out"] == 96 for j in wave)         # check.POSITIONS / 8 rows
    answers = [dict(job, chosen=[9] * job["out"], logprobs=[0.0] * job["out"])
               for job in check.wave_jobs(7, 152576, wave, [])]
    kept = check.compared(check.sequences(answers))
    assert kept[0]["row"] == 0 and len(kept[0]["tokens"]) == 14336 + 96
    assert [s["row"] for s in kept[1:7]] == [1, 2, 3, 4, 5, 6]
    assert len(kept) in (7, 8)
    assert sum(len(s["at"]) for s in kept) >= 256
    assert sum(check.padded(len(s["tokens"])) for s in kept) <= check.REFERENCE_TOKENS
    assert check.padded(14336 + 96) == 15360
    a = check.wave_jobs(1, 32000, wave, [])
    b = check.wave_jobs(2**31 + 5, 32000, wave, [])
    assert [len(j["ids"]) for j in a] == [len(j["ids"]) for j in b]
    assert a[0]["ids"] != b[0]["ids"]
    assert 768 <= sum(j["out"] for j in wave) <= 1024
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


# -- the benchmark's entries -----------------------------------------------------
AT_LEAST = {
    "ttft_p50_ms", "tpot_mean_ms", "window_decode_roofline", "full_decode_roofline",
    "attn_prefill_roofline", "window_pages_per_row", "moe_roofline.open",
    "moe_touched_share", "slo_met_share", "serve_compiles.open",
    "batch_running_mean.open", "kv_preemptions.open", "step_device_ms_p50.open",
    "device_idle_share.open", "prefill_ms_p50", "queue_wait_ms_p50",
    "prefill_fill_share.open", "prefill_device_share.open", "decode_period_ms.open",
    "inline_admit_share.open"}


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return (bench, {w["name"]: w for w in bench["workloads"]},
            {c["name"]: c for c in bench["configs"]}, metrics)


def _listed(metrics: dict, cell: str) -> set:
    return {name for name, m in metrics.items() if cell in m.get("workloads", ())}


def test_the_cell_is_listed_where_its_readers_read():
    """Found by NAME and held as "at least these", by membership and order,
    never by last position: the next cell is appended behind this one and
    needs no skip."""
    bench, cells, configs, metrics = _benchmark()
    cell = cells[BENCH_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash", "short-long", 1)
    assert len(cell["why"]) <= 200 and "16" in cell["why"] and "host" in cell["why"]
    config = configs["mimo-v2-flash"]
    assert len(config["why"]) <= 200
    assert config["reduced"] == published()["reduced"]
    assert config["source"] == published()["source"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index("kanana-2-30b.doc-qa") < names.index(BENCH_CELL)
    listed = _listed(metrics, BENCH_CELL)
    assert AT_LEAST <= listed
    # one Hk and one Dh, a state plane, a prefix cache, latent pages: their
    # readers would find nothing or the wrong thing here
    assert not {"attn_decode_roofline.open", "state_slots_used_share.open",
                "qmm_roofline.open", "prefix_hit_share", "cached_token_share",
                "mla_decode_roofline.open", "mla_prefill_roofline"} & listed
    for name in listed:
        on = metrics[name]["workloads"]
        for earlier in ("mistral-7b.chat", "qwen3-next-80b.chat-long",
                        "nemotron-3-nano-30b.chat-burst", "kanana-2-30b.doc-qa"):
            if earlier in on:
                assert on.index(earlier) < on.index(BENCH_CELL)   # appended
    for name, layer, moves, unit, source, better in (
            ("window_decode_roofline", "kernels", "tpot_mean_ms", "%",
             "device_trace", "higher"),
            ("full_decode_roofline", "kernels", "tpot_mean_ms", "%",
             "device_trace", "higher"),
            ("attn_prefill_roofline", "kernels", "ttft_p50_ms", "%",
             "device_trace", "higher"),
            ("window_pages_per_row", "paged KV cache", "tpot_mean_ms", "pages",
             "program_counter", "lower")):
        m = metrics[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"], m["better"]) == (
            layer, moves, unit, source, better)
        assert m in bench["per_layer"] and m["workloads"] == [BENCH_CELL]
    with open(os.path.join(REPO, "perf", "reference", "limits", BENCH_CELL + ".json")) as f:
        assert 0 < json.load(f)["logprob_err_mean"] < 1
    rate = sched.load_mix("short-long")["rate_rps"]
    assert rate == round(rate, 1) and str(rate) in cell["why"]


# -- what the skipped cases of older tests held (tests/conftest.py) -------------------
OPEN = ["mistral-7b.chat", "qwen3-next-80b.chat-long", "nemotron-3-nano-30b.chat-burst",
        "kanana-2-30b.doc-qa"]


def test_the_open_variants_keep_the_four_cells_before_this_one_in_their_order():
    """PR 42's tests hold each ``.open`` list as exactly three cells and
    kanana's; a fifth open-loop cell lengthens them all: the four stay,
    in their order, and this one follows."""
    _, cells, _, metrics = _benchmark()
    stems = ("decode_period_ms", "step_host_wall_ms", "step_host_offcpu_ms",
             "loop_cpu_ms_per_step", "dispatch_drained_share", "prefill_fill_share",
             "inline_admit_share")
    for stem in stems:
        m = metrics[stem + ".open"]
        assert m["moves"] in ("tpot_mean_ms", "ttft_p50_ms")
        assert m["workloads"] == OPEN + [BENCH_CELL], stem
        listed = [c for n, mm_ in metrics.items()
                  if n.partition(".")[0] == stem for c in mm_["workloads"]]
        assert sorted(listed) == sorted(cells)       # every cell, once
    # the skipped case of test_inline_admit_share.py, entire
    entry = metrics["inline_admit_share.open"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "inline_admit_share.open", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step loop",
        "moves": "tpot_mean_ms"}
    assert set(entry["workloads"]) <= set(metrics["tpot_mean_ms"]["workloads"])


def test_a_family_nobody_serves_is_still_an_error_that_names_it():
    from perf.reference.family import FamilyError

    assert family_of({"model_type": "mimo-v2-flash"}) is family   # "-" read as "_"
    cfg = {"model_type": "mimo_v3"}
    with pytest.raises(FamilyError) as err:
        family_of(cfg)
    assert all(part in str(err.value)
               for part in ("'mimo_v3'", "perf/reference/mimo_v3.py"))


# -- the new readers -----------------------------------------------------------------
def test_the_costs_are_the_published_widths():
    ops, byts = costs.attn_prefill_cost(1000, 64, 192, 128)
    assert ops == 1000 * 64 * 640 and byts == 0.0
    ops, byts = costs.attn_decode_cost(1000, 64, 8, 192, 128)
    assert byts == 1000 * 8 * 320 * 2 and ops == 2 * 1000 * 64 * 320
    from dynamo_tpu.models import mimo_v2_flash as mm

    assert costs.PAIR_UNIT == mm.PAIR_UNIT
    assert {"attn_full_pairs", "attn_window_pairs", "attn_full_decode_keys",
            "attn_window_decode_keys", "attn_full_decode_calls",
            "attn_window_prefill_calls"} <= set(mm.COUNT_NAMES)
    # the names a trace tells the kinds apart by
    assert mm.DECODE["win"].keywords["name"] == "paged_attention_decode_stacked_window"
    assert mm.PREFILL["full"].keywords["name"] == "paged_attention_prefill_stacked_full"


class FakeRun:
    def __init__(self, config, ops=None):
        self.config, self.trace = config, {"ops": ops or {}}
        self.trace_span, self.samples = (10.0, 12.0), []
        self.device, self.notes = {"kind": "TPU v5 lite"}, []
        self.trace_dir = None


@pytest.mark.parametrize("kind,reader,hk", [
    ("window", window_decode_roofline, 8), ("full", full_decode_roofline, 4)])
def test_a_decode_roofline_is_least_bytes_over_its_kinds_time(
        monkeypatch, kind, reader, hk):
    pub = hf_config(published())
    keys, calls = 3_000_000, 90
    deltas = {f"engine.attn_{kind}_decode_keys": keys,
              f"engine.attn_{kind}_decode_calls": calls,
              "engine.attn_full_decode_keys" if kind == "window"
              else "engine.attn_window_decode_keys": 7, }
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    least = keys * hk * 320 * 2 / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    other = "full" if kind == "window" else "window"
    ops = {f"paged_attention_decode_stacked_{kind}.7": {
        "calls": calls, "total_s": least / 0.4, "median_s": least / calls},
        f"paged_attention_decode_stacked_{other}.9": {
            "calls": 30, "total_s": 9.0, "median_s": 0.3},
        f"paged_attention_prefill_stacked_{kind}.2": {
            "calls": 12, "total_s": 9.0, "median_s": 0.3}}
    run = FakeRun(pub, ops=ops)
    assert reader.read(run) == pytest.approx(40.0, rel=1e-3)
    note = run.notes[0][f"{kind}_decode_roofline"]
    assert note["calls_counted"] == note["calls_traced"] == calls
    assert note["bound"] == "bytes" and note["labels"] == [
        f"paged_attention_decode_stacked_{kind}.7"]
    # a call straddles the capture's edge: counted, not traced whole
    few = dict(ops)
    few[f"paged_attention_decode_stacked_{kind}.7"] = dict(
        ops[f"paged_attention_decode_stacked_{kind}.7"], calls=calls - 1)
    assert reader.read(FakeRun(pub, ops=few)) is None
    # too many bytes for the time raises, it is never clipped
    fast = dict(ops)
    fast[f"paged_attention_decode_stacked_{kind}.7"] = dict(
        ops[f"paged_attention_decode_stacked_{kind}.7"], total_s=least / 1.2)
    with pytest.raises(roofline.RooflineError):
        reader.read(FakeRun(pub, ops=fast))
    assert reader.read(FakeRun(pub)) is None                        # no kernel
    monkeypatch.setattr(costs, "count_deltas", lambda run: None)
    assert reader.read(FakeRun(pub, ops=ops)) is None               # no counts
    # the parent's program, or another family's: no such counts, no such name
    monkeypatch.setattr(costs, "count_deltas",
                        lambda run: {"engine.moe_layer_calls": 9})
    assert reader.read(FakeRun(pub, ops=ops)) is None
    with open(os.path.join(REPO, "perf", "configs", "kanana-2-30b.json")) as f:
        other_family = hf_config(json.load(f))
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    assert reader.read(FakeRun(other_family, ops=ops)) is None


def test_attn_prefill_roofline_is_least_flop_over_both_kinds_time(monkeypatch):
    pub = hf_config(published())
    deltas = {"engine.attn_full_pairs": 300_000, "engine.attn_window_pairs": 20_000,
              "engine.attn_full_prefill_calls": 36,
              "engine.attn_window_prefill_calls": 108}
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    peak = roofline.peaks("TPU v5 lite")["bf16_flops_per_s"]
    least = 320_000 * 1024 * 64 * 640 / peak
    ops = {"paged_attention_prefill_stacked_full.3": {
        "calls": 36, "total_s": least / 0.25 * 0.8, "median_s": 0.01},
        "paged_attention_prefill_stacked_window.4": {
            "calls": 108, "total_s": least / 0.25 * 0.2, "median_s": 0.001},
        "paged_attention_decode_stacked_full.5": {
            "calls": 99, "total_s": 5.0, "median_s": 0.05}}
    run = FakeRun(pub, ops=ops)
    assert attn_prefill_roofline.read(run) == pytest.approx(25.0, rel=1e-3)
    note = run.notes[0]["attn_prefill_roofline"]
    assert note["calls_counted"] == note["calls_traced"] == 144
    assert note["bound"] == "compute"
    assert note["pairs_window_share"] == pytest.approx(1 / 16)
    few = dict(ops)
    few["paged_attention_prefill_stacked_window.4"] = dict(
        ops["paged_attention_prefill_stacked_window.4"], calls=100)
    assert attn_prefill_roofline.read(FakeRun(pub, ops=few)) is None
    assert attn_prefill_roofline.read(FakeRun(pub)) is None
    monkeypatch.setattr(costs, "count_deltas",
                        lambda run: {"engine.mla_prefill_pairs": 9})
    assert attn_prefill_roofline.read(FakeRun(pub, ops=ops)) is None


def test_window_pages_per_row_is_the_ratio_of_two_growths(monkeypatch):
    from perf.trace import count_history as ch

    run = FakeRun(hf_config(published()))
    monkeypatch.setattr(ch, "growth", lambda run: {
        "window_page_steps": 5400, "window_row_steps": 2000,
        "window_pages_released_total": 310, "pairs": 30, "seconds": 30.0})
    assert window_pages_per_row.read(run) == pytest.approx(2.7)
    assert run.notes[0]["window_pages_per_row"]["released"] == 310
    monkeypatch.setattr(ch, "growth", lambda run: {"dispatches.decode": 5})
    assert window_pages_per_row.read(run) is None      # a program without the plane
    monkeypatch.setattr(ch, "growth", lambda run: None)
    assert window_pages_per_row.read(run) is None
    monkeypatch.setattr(ch, "growth", lambda run: {
        "window_page_steps": 0, "window_row_steps": 0})
    assert window_pages_per_row.read(run) is None


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-mimo-v2-flash", "file": os.path.relpath(
        os.path.join(DATA, "tiny-mimo-v2-flash.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-mimo-v2-flash",
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
    monkeypatch.setattr(check, "POSITIONS", 96)
    monkeypatch.setattr(check, "REFERENCE_TOKENS", 2048)
    # the capture lies past the ramp (3 s): rows of both classes decode by
    # then, so prefill and decode calls of both kinds fall inside it
    monkeypatch.setattr(perf_run, "TRACE_AT_S", 1.0)
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
    assert result["correct"] is True and result["failed"] == 0, (
        phases.get("outputs"), phases.get("window"))
    hits, queries = phases["window"]["prefix"]
    assert queries > 0 and hits == 0              # reuse is off for the family
    compared = phases["outputs"]["compared"]
    assert compared["rows_sent"] == 8 and compared["rows_compared"] >= 3
    assert phases["engine_up"]["kv_pool"]["total_blocks"] == 255
    names = set(result["metrics"])
    if trace:
        assert "batch_running_mean.open" in names
        # a 2 s capture on a loaded machine can hold no expert-layer call at
        # all and then reads nothing (PERF.md section 7 on kimi's rehearsal);
        # where it held one, the share is a share
        touched = result["metrics"].get("moe_touched_share")
        assert touched is None or 0 < touched["value"] <= 100
        # the device readers find their kernels' names only on the chip (the
        # CPU runs the XLA form): whatever they print stays under 100
        for name in ("window_decode_roofline", "full_decode_roofline",
                     "attn_prefill_roofline", "moe_roofline.open"):
            if name in names:
                assert 0 <= result["metrics"][name]["value"] <= 100
    else:
        assert {"ttft_p50_ms", "tpot_mean_ms", "setup_s"} <= names
        assert result["metrics"]["tpot_mean_ms"]["value"] > 0
        pages = result["metrics"].get("window_pages_per_row")
        # window 24 over pages of 16: a decoding row holds 3, a row in a
        # 128-token chunk 10; never a 400-token row's 25
        assert pages is None or 0 < pages["value"] < 10
