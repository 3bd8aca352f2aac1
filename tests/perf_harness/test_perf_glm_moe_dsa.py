"""The ``glm_moe_dsa`` family (GLM-5) in the benchmark, at a size a test
holds: its family module's seeded draw is the program's recipe value for
value, the two plain references written apart agree, its ``a8`` control
comes out as NOT correct by the limit the program meets, the ``long-docs``
schedule is a function of the mix and ``--seconds`` alone, the probe's
compared rows are the longest document's second ask and the shortest's
first, the four new readers read what the program counts (none over 100,
nothing on a straddled call), and ``perf/run.py`` drives the family's cell
end to end on the CPU in a work directory of its own."""

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
    dsa_decode_roofline,
    dsa_index_roofline,
    dsa_prefill_roofline,
    dsa_selected_share,
    glm_moe_dsa_costs as costs,
)
from perf.reference import check, control, glm_moe_dsa as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402
from perf.traffic.kinds import shared_docs  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations and
# pages, on the CPU) reads 0.0145-0.0417 at widths of 128 over five seeds
# (0.0417 at the rehearsal's own), the a8 control 0.0842 / 0.0861 / 0.112 /
# 0.112 at its four: the geometric mean of the program's largest and the
# control's smallest. The preset attends the top 256 of up to 420 keys: at
# a top 64 ONE turned selection is 1/64 of a query's keys, turned
# selections feed the next layer's index scores (0.3 swaps a query in layer
# 0, 4 in layer 2), and the program's readings (0.113-0.178) straddled the
# control's (0.154-0.172) — selection flips saturate the statistic at a
# toy size as router flips did at top 3 of 8 experts
TINY_LIMIT = 0.059
CELL, MIX = "tiny-glm-moe-dsa.long-docs", "tiny-long-docs"
BENCH_CELL = "glm-5.long-docs"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-glm-moe-dsa.json")) as f:
        return hf_config(json.load(f))


def published():
    with open(os.path.join(REPO, "perf", "configs", "glm-5.json")) as f:
        return json.load(f)


def jobs_for(cfg, seed, lengths=(340, 397, 333, 401), out=12):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


# -- the configuration and the family module ----------------------------------------
def test_the_configuration_names_this_family(cfg):
    assert family_of(cfg) is family
    pub = hf_config(published())
    assert family_of(pub) is family
    g = family.geometry(pub)
    want = dict(D=6144, V=19360, H=64, Hk=1, Dh=576, rank=512, rope=64, nope=192,
                vd=256, q_rank=2048, G=32, dI=128, topk=2048, E=16, shards=16,
                shard=0, Fe=2048, Fs=2048, F=12288, k=8, L=9)
    assert {n: g[n] for n in want} == want
    assert g["dense"] == [0] and g["moe"] == list(range(1, 9))
    assert g["theta"] == 1e6 and g["interleave"] and g["index_interleave"]
    assert g["scale"] == 2.5 and g["eps"] == 1e-5
    assert {"f32", "a8"} <= set(family.PRECISIONS)
    with pytest.raises(ValueError):
        family.logits_fn(cfg, "w4")


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn"}),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})])
def test_the_reference_builds_nothing_the_program_refuses(cfg, key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        family.geometry(dict(cfg, **{key: value}))


def test_the_configuration_file_is_the_catalog_row_with_the_four_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    mine = published()
    assert mine["source"] == row["source_url"]
    assert mine["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
        else:
            assert mine["published"][key] == value, key
    assert (mine["num_hidden_layers"], mine["first_k_dense_replace"],
            mine["n_routed_experts"], mine["vocab_size"]) == (9, 1, 16, 19360)
    assert mine["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (mine["expert_shards"], mine["expert_shard_index"]) == (16, 0)
    assert mine["serving"]["engine"] == {"max_model_len": 24576}
    assert "128 TPU v5e chips" in mine["deployment"] and len(mine["assumed"]) >= 8
    # no width among the cuts: every head size, rank and expert width as published
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
                "index_topk", "moe_intermediate_size", "num_experts_per_tok",
                "num_attention_heads", "intermediate_size"):
        assert mine[key] == row["config"][key], key


def test_reference_against_itself_reads_zero(cfg):
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-4 and got["positions"] == 48


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_a8_control_is_not_correct(cfg, seed):
    """0.084-0.112 over these seeds (contexts 333-413 tokens of which a
    query attends 256): over the rehearsal's limit."""
    got = control.control_error(cfg, seed, "a8", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


STACKED = ["attn_norm", "mlp_norm", "mla_wqa", "mla_qnorm", "mla_wqb", "mla_wkva",
           "mla_kvnorm", "mla_wkvb", "mla_wo", "w_gate", "w_up", "w_down", "router",
           "router_bias", "ws_gate", "ws_up", "ws_down", "idx_wq", "idx_wk",
           "idx_knorm", "idx_kbias", "idx_ww"]
EXPERTS = ["we_gate", "we_up", "we_down"]


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, glm_moe_dsa as glm

    mc = ModelConfig.from_dict(cfg)
    return glm.init_params_quantized(mc, seed=SEED), glm.param_shapes(mc)


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
    most one in 10 000 (two values of a slice smaller than that), and by
    one quantization step."""
    diff = np.abs(np.asarray(mine) - theirs)
    assert (diff > 1e-7).sum() <= max(2, 1e-4 * diff.size)
    assert diff.max() <= max(np.abs(theirs).max(), 1e-9) / 127 * 1.01


def test_the_parameter_order_is_the_programs(program_params):
    assert list(family.PARAM_ORDER) == list(program_params[1])
    assert program_params[0]["idx_ww"].dtype == np.float32
    assert program_params[0]["router"].shape[-1] == 16      # 8 held x 2 shards


@pytest.mark.parametrize("name", STACKED)
def test_the_draw_of_a_stack_is_the_programs_recipe(program_params, name):
    import jax

    params, shapes = program_params
    layer = shapes[name][0][0] - 1          # the stack's last layer
    mine = family.draw(
        jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(SEED), family.PARAM_ORDER.index(name)), layer),
        name, shapes[name][0][1:])
    assert_same_draw(mine, served(params, name, layer))


@pytest.mark.parametrize("name", EXPERTS)
def test_the_draw_of_an_expert_is_the_programs_recipe(program_params, name):
    import jax

    params, shapes = program_params
    layer, expert = 1, 5
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
    reads the queries a block at a time and selects by ``lax.top_k``; the
    repo's takes the program's weights, a whole ``[T, T]`` index score and
    a full stable sort) give the same logits — at a length that takes two
    query blocks, with a row shorter than the rectangle, both several
    past the 256 keys a query attends."""
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.reference import glm_moe_dsa as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    T = 2 * family.QUERY_BLOCK
    tokens = rng.integers(5, cfg["vocab_size"], (2, T)).astype(np.int32)
    lengths = np.array([T, 400], np.int32)
    at = np.stack([np.arange(T - 8, T), np.arange(392, 400)]).astype(np.int32)
    mine = np.asarray(family.logits_fn(cfg)(SEED, tokens, lengths, at))
    mc = ModelConfig.from_dict(cfg)
    for b in range(2):
        theirs = np.asarray(repo_ref.forward(
            mc, params, jnp.asarray(tokens[b:b + 1, :lengths[b]])))[0, at[b]]
        np.testing.assert_allclose(mine[b], theirs, rtol=0, atol=3e-4)


# -- the kind and the mix ----------------------------------------------------------
def test_long_docs_schedule_is_a_function_of_the_mix_and_seconds_alone():
    import hashlib

    mix = sched.load_mix("long-docs")
    a, b = sched.build(mix, 50.0), sched.build(sched.load_mix("long-docs"), 50.0)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) == hashlib.sha256(sched.serialise(a)).hexdigest()
    assert sched.digest(a) != sched.digest(sched.build(mix, 51.0))
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert sched.digest(sched.build(other, 50.0)) != sched.digest(a)
    seeds = {sched.load_mix(m)["schedule_seed"] for m in (
        "chat", "chat-long", "chat-burst", "sessions", "decode-heavy",
        "long-decode", "doc-qa", "short-long")}
    assert mix["schedule_seed"] not in seeds
    longer = [e for e in sched.build(mix, 60.0)["entries"] if e["due"] < 50.0]
    strip = lambda es: [{k: v for k, v in e.items() if k != "i"} for e in es]  # noqa: E731
    assert strip(longer) == strip(a["entries"])


def test_long_docs_is_the_issues_traffic_and_fits_the_served_context():
    mix = sched.load_mix("long-docs")
    assert mix["kind"] == "shared_docs" and mix["ramp_s"] == 30
    assert mix["doc_tokens"] == {"median": 12288, "sigma": 0.35, "min": 8192,
                                 "max": 20480}
    assert mix["asks_per_doc"] == {"min": 4, "max": 8}
    assert mix["ask_gap_s"] == {"min": 2.0, "max": 6.0}
    assert mix["question_tokens"] == {"min": 32, "max": 128}
    assert mix["output_tokens"] == {"median": 128, "sigma": 0.5, "min": 16, "max": 384}
    assert mix["slo"] == {"ttft_ms": 8000, "gap_ms": 100} and mix["drain_limit_s"] == 120
    assert round(mix["doc_rate_rps"] * 100) == pytest.approx(mix["doc_rate_rps"] * 100)
    limit = published()["serving"]["engine"]["max_model_len"]
    assert mix["max_total_tokens"] == 20992 <= limit == 24576
    entries = sched.build(mix, 50.0)["entries"]
    assert max(e["prompt"] + e["out"] for e in entries) <= mix["max_total_tokens"]
    # every document is past the dense regime: of a 12 288-token document's
    # queries 83% have more than 2 048 keys behind them
    assert min(e["doc_tokens"] for e in entries) >= 8192 > 2048
    assert 1 - 2048 / 12288 == pytest.approx(0.83, abs=0.005)
    by_doc: dict = {}
    for e in entries:
        by_doc.setdefault(e["doc"], []).append(e)
    for asks in by_doc.values():
        assert [a["ask"] for a in asks] == list(range(len(asks))) and len(asks) <= 8
        assert len({a["doc_tokens"] for a in asks}) == 1
        gaps = np.diff([a["due"] for a in asks])
        assert np.all((gaps >= 2.0) & (gaps <= 6.0))
    long_run = shared_docs.totals(sched.build(dict(mix, doc_rate_rps=2.0), 400.0))
    assert 0.78 <= long_run["window_shared_token_share"] <= 0.86   # 4-8 asks: 5/6


def test_the_probe_compares_the_longest_second_ask_and_the_shortest_first():
    mix = sched.load_mix("long-docs")
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("long-docs")) and len(waves) == 2
    first, second = waves
    docs = [j["shared_tokens"] for j in first]
    assert docs == [j["shared_tokens"] for j in second] == sorted(docs)
    assert docs[0] == 8192 and docs[-1] == 20480 and len(docs) == 6
    assert all(j["out"] == 64 for j in first + second)
    answers = []
    for wave in waves:
        for job in check.wave_jobs(7, 19360, wave, []):
            answers.append(dict(job, chosen=[9] * job["out"],
                                logprobs=[0.0] * job["out"]))
    kept = check.compared(check.sequences(answers))
    second_rows = {j["row"] for j in second}
    # the longest document's SECOND ask: a cache hit whose every decode
    # step selects 2 048 of 20k keys ...
    assert kept[0]["row"] in second_rows
    assert len(kept[0]["tokens"]) == 20480 + 128 + 64
    assert check.padded(len(kept[0]["tokens"])) == 21504
    # ... then row 0, the shortest document's FIRST ask: a cold prefill
    # through eight chunks; a third row would pass 32 768 padded tokens
    assert [s["row"] for s in kept[1:]] == [0] and 0 not in second_rows
    assert 8192 + 32 + 64 <= len(kept[1]["tokens"]) <= 8192 + 128 + 64
    assert check.padded(len(kept[1]["tokens"])) == 9216
    assert sum(check.padded(len(s["tokens"])) for s in kept) == 30720 \
        <= check.REFERENCE_TOKENS
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


# -- the benchmark's entries -----------------------------------------------------
AT_LEAST = {
    "ttft_p50_ms", "tpot_mean_ms", "dsa_index_roofline", "dsa_prefill_roofline",
    "dsa_decode_roofline", "dsa_selected_share", "moe_roofline.open",
    "moe_touched_share", "prefix_hit_share", "cached_token_share",
    "serve_compiles.open", "device_idle_share.open", "step_device_ms_p50.open"}


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    return (bench, {w["name"]: w for w in bench["workloads"]},
            {c["name"]: c for c in bench["configs"]}, metrics)


def test_the_cell_is_listed_where_its_readers_read():
    """Found by NAME and held as "at least these", by membership and
    order, never by last position: the next cell is appended behind this
    one and needs no skip."""
    bench, cells, configs, metrics = _benchmark()
    cell = cells[BENCH_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("glm-5", "long-docs", 1)
    assert len(cell["why"]) <= 200
    config = configs["glm-5"]
    assert len(config["why"]) <= 200 and config["source"] == published()["source"]
    assert config["reduced"] == published()["reduced"]
    assert config["file"] == "perf/configs/glm-5.json"
    names = [w["name"] for w in bench["workloads"]]
    assert names.index("mimo-v2-flash.short-long") < names.index(BENCH_CELL)
    listed = {name for name, m in metrics.items()
              if BENCH_CELL in m.get("workloads", ())}
    assert AT_LEAST <= listed
    # the dense latent kernels' cost functions count every cached key, and
    # classify_programs knows no kernel of this family: not on those lists
    assert not {"mla_decode_roofline.open", "mla_prefill_roofline",
                "prefill_device_share.open", "attn_decode_roofline.open",
                "state_slots_used_share.open", "window_pages_per_row"} & listed
    for name in listed:
        on = metrics[name]["workloads"]
        for earlier in ("kanana-2-30b.doc-qa", "mimo-v2-flash.short-long"):
            if earlier in on:
                assert on.index(earlier) < on.index(BENCH_CELL)   # appended
    for name, layer, moves, unit, source, better in (
            ("dsa_index_roofline", "kernels", "ttft_p50_ms", "%", "device_trace", "higher"),
            ("dsa_prefill_roofline", "kernels", "ttft_p50_ms", "%", "device_trace", "higher"),
            ("dsa_decode_roofline", "kernels", "tpot_mean_ms", "%", "device_trace", "higher"),
            ("dsa_selected_share", "sparse selection", "tpot_mean_ms", "%",
             "program_counter", "lower")):
        m = metrics[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"], m["better"]) == (
            layer, moves, unit, source, better)
        assert m["workloads"][0] == BENCH_CELL
        assert os.path.exists(os.path.join(REPO, "perf", "metrics", name + ".py"))
    assert len(bench["workloads"]) >= 9
    with open(os.path.join(REPO, "perf", "reference", "limits", BENCH_CELL + ".json")) as f:
        assert 0 < json.load(f)["logprob_err_mean"] < 1


# -- what the skipped case of an older test held (tests/conftest.py) ------------------
OPEN = ["mistral-7b.chat", "qwen3-next-80b.chat-long", "nemotron-3-nano-30b.chat-burst",
        "kanana-2-30b.doc-qa", "mimo-v2-flash.short-long"]
OPEN_STEMS = ("decode_period_ms", "step_host_wall_ms", "step_host_offcpu_ms",
              "loop_cpu_ms_per_step", "dispatch_drained_share", "prefill_fill_share",
              "inline_admit_share")


@pytest.mark.parametrize("stem", OPEN_STEMS)
def test_the_open_variants_keep_the_five_cells_before_this_one_in_their_order(stem):
    """``test_perf_mimo_v2_flash``'s case, a stem at a time: each ``.open``
    list BEGINS with the five open-loop cells it had, in their order, this
    cell follows them, and every cell is on exactly one variant's list."""
    _, cells, _, metrics = _benchmark()
    m = metrics[stem + ".open"]
    assert m["moves"] in ("tpot_mean_ms", "ttft_p50_ms")
    assert m["workloads"][:5] == OPEN and BENCH_CELL in m["workloads"][5:]
    listed = [c for n, mm_ in metrics.items()
              if n.partition(".")[0] == stem for c in mm_["workloads"]]
    assert sorted(listed) == sorted(cells)       # every cell, once
    assert set(m["workloads"]) <= set(metrics[m["moves"]]["workloads"])


def test_inline_admit_share_open_keeps_its_entry():
    _, _, _, metrics = _benchmark()
    entry = metrics["inline_admit_share.open"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "inline_admit_share.open", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step loop",
        "moves": "tpot_mean_ms"}
    assert set(entry["workloads"]) <= set(metrics["tpot_mean_ms"]["workloads"])


# -- the new readers ------------------------------------------------------------------
def test_the_costs_are_the_published_least_work():
    from dynamo_tpu.models import glm_moe_dsa as glm

    assert costs.PAIR_UNIT == glm.PAIR_UNIT == 1024
    assert set(glm.DSA_COUNT_NAMES) <= set(glm.COUNT_NAMES)
    assert costs.index_cost(1000, 32, 128) == (1000 * 32 * 128 * 2, 0.0)
    assert costs.prefill_attend_cost(1000, 64, 192, 64, 256) == (
        1000 * 64 * 2 * (256 + 256), 0.0)
    assert costs.decode_cost(2048, 20000, 512, 64, 128) == (
        0.0, 2048 * 576 * 2 + 20000 * 128 * 2)


class FakeRun:
    def __init__(self, config, ops=None):
        self.config, self.trace = config, {"ops": ops or {}}
        self.trace_span, self.samples = (10.0, 12.0), []
        self.device, self.notes = {"kind": "TPU v5 lite"}, []
        self.trace_dir = None


def _recorded(share: float = 0.25):
    """A capture of two cold 1 024-token chunks at 12k and 96 decode row
    steps at ~14k, 9 layers each: the counts' growth, and ops whose times
    make every roofline read ``share``."""
    pk = roofline.peaks("TPU v5 lite")
    chunk_pairs = 2 * (1024 * 11264 + 1024 * 1025 // 2) * 9 // 1024
    chunk_selected = 2 * 1024 * 2048 * 9 // 1024
    deltas = {"engine.dsa_index_pairs": chunk_pairs,
              "engine.dsa_prefill_selected": chunk_selected,
              "engine.dsa_decode_scored": 96 * 14000 * 9,
              "engine.dsa_decode_selected": 96 * 2048 * 9,
              "engine.dsa_calls": (2 + 24) * 9, "engine.moe_layer_calls": 26 * 8}
    index_s = chunk_pairs * 1024 * 32 * 128 * 2 / pk["bf16_flops_per_s"] / share
    attend_s = chunk_selected * 1024 * 64 * 1024 / pk["bf16_flops_per_s"] / share
    decode_s = (96 * 2048 * 9 * 1152 + 96 * 14000 * 9 * 256) / pk["hbm_bytes_per_s"] / share
    op = lambda calls, s: {"calls": calls, "total_s": s, "median_s": s / calls}  # noqa: E731
    ops = {"dsa_index_prefill.3_f32_1_1024_25600__custom-call": op(18, index_s),
           "dsa_select_prefill.4_f32_1_1024_25600__custom-call": op(18, 0.05),
           "dsa_prefill_attention.5_bf16_1_64_1024_512__custom-call": op(18, attend_s),
           "dsa_index_decode.6_f32_4_1_25600__custom-call": op(216, decode_s / 4),
           "dsa_select_decode.7_f32_4_1_25600__custom-call": op(216, decode_s / 4),
           "dsa_decode_attention.8_bf16_4_64_512__custom-call": op(216, decode_s / 2),
           "mla_decode_attention.9": op(50, 9.0)}
    return deltas, ops


def test_the_four_readers_on_a_recorded_run(monkeypatch):
    pub = hf_config(published())
    deltas, ops = _recorded(0.25)
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    run = FakeRun(pub, ops=ops)
    got = {m.__name__.rsplit(".", 1)[1]: m.read(run) for m in (
        dsa_index_roofline, dsa_prefill_roofline, dsa_decode_roofline,
        dsa_selected_share)}
    for name in ("dsa_index_roofline", "dsa_prefill_roofline", "dsa_decode_roofline"):
        assert got[name] == pytest.approx(25.0, rel=1e-3) and got[name] <= 100
    # two chunks at 12k attend 2 048 of ~11.8k scored; decode 2 048 of 14k
    scored = deltas["engine.dsa_index_pairs"] * 1024 + deltas["engine.dsa_decode_scored"]
    picked = deltas["engine.dsa_prefill_selected"] * 1024 \
        + deltas["engine.dsa_decode_selected"]
    assert got["dsa_selected_share"] == pytest.approx(100 * picked / scored)
    assert 10 < got["dsa_selected_share"] < 40
    notes = {k: v for n in run.notes for k, v in n.items()}
    assert notes["dsa_index_roofline"]["bound"] == "compute"
    assert notes["dsa_decode_roofline"]["bound"] == "bytes"
    assert notes["dsa_index_roofline"]["calls_counted"] == 234 == \
        notes["dsa_index_roofline"]["calls_traced"]


def test_what_the_index_scope_runs_beside_its_kernel_is_measured_too(monkeypatch):
    """The indexer's projections (named after the scope) and
    the XLA gather of the rows' ``index_k`` pages are part of the stage's
    seconds, found from the shapes the kernel's label states; an op of
    another program's shape, another plane's width or no scope is not."""
    pub = hf_config(published())
    deltas, ops = _recorded(0.25)
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    op = lambda calls, s: {"calls": calls, "total_s": s, "median_s": s / calls}  # noqa: E731
    index_s = ops["dsa_index_prefill.3_f32_1_1024_25600__custom-call"]["total_s"]
    decode_s = sum(v["total_s"] for k, v in ops.items() if "decode" in k and k.startswith("dsa_"))
    beside = {"dsa_index.31_f32_1024_4096__custom-call": op(18, index_s / 2),
              "fusion.40_bf16_200_128_128__fusion": op(18, index_s / 2),
              "dsa_index.32_f32_8_4096__custom-call": op(216, decode_s / 2),
              "fusion.41_bf16_800_128_128__fusion": op(216, decode_s / 2)}
    others = {"dsa_index.33_f32_64_4096__custom-call": op(9, 5.0),     # a 64-row program's
              "fusion.42_bf16_800_128_640__fusion": op(9, 5.0),        # the latent plane's width
              "fusion.43_bf16_136_128_128__fusion": op(9, 5.0),        # another table
              "step.44_f32_1024_4096__custom-call": op(9, 5.0)}        # no scope
    assert costs.label_dims("fusion.41_bf16_800_128_128__fusion") == (800, 128, 128)
    assert costs.label_dims("mla_decode_attention.9") == ()
    run = FakeRun(pub, ops={**ops, **beside, **others})
    assert set(costs.index_side_ops(run, "dsa_index_prefill", 128)) == {
        "dsa_index.31_f32_1024_4096__custom-call", "fusion.40_bf16_200_128_128__fusion"}
    assert set(costs.index_side_ops(run, "dsa_index_decode", 128)) == {
        "dsa_index.32_f32_8_4096__custom-call", "fusion.41_bf16_800_128_128__fusion"}
    assert dsa_index_roofline.read(run) == pytest.approx(12.5, rel=1e-3)
    assert dsa_decode_roofline.read(run) == pytest.approx(12.5, rel=1e-3)
    assert dsa_prefill_roofline.read(run) == pytest.approx(25.0, rel=1e-3)
    notes = {k: v for n in run.notes for k, v in n.items()}
    assert notes["dsa_decode_roofline"]["side_s"] == pytest.approx(decode_s)
    assert notes["dsa_decode_roofline"]["side_calls"] == 432
    assert notes["dsa_index_roofline"]["side_s"] == pytest.approx(index_s)
    # a program that walks the pages inside its kernel has no such op
    assert costs.index_side_ops(FakeRun(pub, ops=ops), "dsa_index_decode", 128) == {}


def test_a_straddled_call_an_absent_kernel_and_an_older_program_read_nothing(monkeypatch):
    pub = hf_config(published())
    deltas, ops = _recorded()
    readers = (dsa_index_roofline, dsa_prefill_roofline, dsa_decode_roofline,
               dsa_selected_share)
    # the counts hold a call more than the trace shows: a straddled edge
    monkeypatch.setattr(costs, "count_deltas",
                        lambda run: dict(deltas, **{"engine.dsa_calls": 235 + 9}))
    assert [m.read(FakeRun(pub, ops=ops)) for m in readers[:3]] == [None] * 3
    assert readers[3].read(FakeRun(pub, ops=ops)) is not None    # counts alone
    # a prefill in flight at the first edge: traced, not counted — low, never high
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    more = dict(ops, **{"dsa_index_prefill.3b": {
        "calls": 9, "total_s": sum(v["total_s"] for k, v in ops.items()
                                   if k.startswith("dsa_index_prefill")),
        "median_s": 0.01}})
    assert dsa_index_roofline.read(FakeRun(pub, ops=more)) == pytest.approx(12.5, rel=1e-3)
    # a count too high for the time raises, it is never clipped
    fast = {k: dict(v, total_s=v["total_s"] / 5) for k, v in ops.items()}
    for m in readers[:3]:
        with pytest.raises(roofline.RooflineError):
            m.read(FakeRun(pub, ops=fast))
    # no kernel of this family in the trace; no counts at all; the PARENT's
    # program (another family's counts): nothing, and no error
    assert [m.read(FakeRun(pub)) for m in readers[:3]] == [None] * 3
    for d in (None, {}, {"engine.moe_layer_calls": 9, "engine.mla_prefill_pairs": 5}):
        monkeypatch.setattr(costs, "count_deltas", lambda run, d=d: d)
        assert [m.read(FakeRun(pub, ops=ops)) for m in readers] == [None] * 4
    # another family's configuration with this family's ops in the trace
    monkeypatch.setattr(costs, "count_deltas", lambda run: deltas)
    with open(os.path.join(REPO, "perf", "configs", "kanana-2-30b.json")) as f:
        other = hf_config(json.load(f))
    assert [m.read(FakeRun(other, ops=ops)) for m in readers[:3]] == [None] * 3


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-glm-moe-dsa", "file": os.path.relpath(
        os.path.join(DATA, "tiny-glm-moe-dsa.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-glm-moe-dsa",
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
    # the capture lies past the ramp (3 s): documents of every age are
    # being asked by then, so prefill and decode calls fall inside it
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
    assert queries > 0 and 0 < hits < queries
    compared = phases["outputs"]["compared"]
    assert compared["rows_sent"] == 12 and compared["rows_compared"] >= 3
    assert phases["engine_up"]["kv_pool"]["total_blocks"] == 255
    # both planes' pages are back once the drain is over
    assert phases["window"]["blocks_at_ends"][2] == 255
    names = set(result["metrics"])
    if trace:
        assert {"prefix_hit_share", "cached_token_share", "moe_touched_share",
                "batch_running_mean.open", "dsa_selected_share"} <= names
        assert 0 < result["metrics"]["prefix_hit_share"]["value"] < 100
        assert 0 < result["metrics"]["cached_token_share"]["value"] < 100
        # contexts up to 420 tokens against a top 256: under 100 only if a
        # document past 256 tokens was asked inside the 2 s capture
        assert 0 < result["metrics"]["dsa_selected_share"]["value"] <= 100
        # the CPU's trace holds no TPU op: the three rooflines read nothing
        assert not {"dsa_index_roofline", "dsa_prefill_roofline",
                    "dsa_decode_roofline"} & names
    else:
        assert {"ttft_p50_ms", "tpot_mean_ms", "setup_s"} <= names
        assert result["metrics"]["tpot_mean_ms"]["value"] > 0
