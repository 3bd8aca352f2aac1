"""The ``deepseek_v3`` family (Kanana-2) in the benchmark, at a size a test
holds: its family module's seeded draw is the program's recipe value for
value, its ``a8`` control comes out as NOT correct by the limit the
program meets, the ``shared_docs`` kind's schedule is a function of the
mix and ``--seconds`` alone and a document's asks share exactly its
tokens, the probe's compared rows hold second asks, the new reader reads
what the program counts, and ``perf/run.py`` drives the family's cell end
to end on the CPU (server child, window, probe, reference child, result
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
from perf.metrics import deepseek_v3_costs as costs  # noqa: E402
from perf.metrics import mla_decode_roofline, mla_prefill_roofline  # noqa: E402
from perf.reference import check, control, deepseek_v3 as family  # noqa: E402
from perf.reference.family import family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402
from perf.traffic import schedule as sched  # noqa: E402
from perf.traffic.kinds import shared_docs  # noqa: E402

# the rehearsal's limit: the program (int8 weights, bf16 activations, on the
# CPU) reads 0.0241-0.0324 at widths of 128 over four seeds (0.0241 at the
# rehearsal's own), the a8 control 0.0380 / 0.0405 / 0.0535 at its three: the
# geometric mean of the program's largest and the control's smallest. Top 6
# of 16 experts: at top 3 of 8 one turned choice moved a token's logprob by
# 1.6 and the program's readings (0.012-0.061) straddled the control's
TINY_LIMIT = 0.035
CELL, MIX = "tiny-deepseek-v3.doc-qa", "tiny-doc-qa"
BENCH_CELL = "kanana-2-30b.doc-qa"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-deepseek-v3.json")) as f:
        return hf_config(json.load(f))


def published():
    with open(os.path.join(REPO, "perf", "configs", "kanana-2-30b.json")) as f:
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
    want = dict(D=2048, V=128256, H=32, Hk=1, Dh=576, rank=512, rope=64, nope=128,
                vd=128, E=128, Fe=768, Fs=1536, F=6144, k=6, L=12)
    assert {n: g[n] for n in want} == want
    assert g["dense"] == [0] and g["moe"] == list(range(1, 12))
    assert g["theta"] == 1e6 and g["interleave"] and g["scale"] == 2.448
    assert {"f32", "a8"} <= set(family.PRECISIONS)
    with pytest.raises(ValueError):
        family.logits_fn(cfg, "w4")


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 64), ("n_group", 2), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn"})])
def test_the_reference_builds_nothing_the_program_refuses(cfg, key, value):
    with pytest.raises(ValueError, match=key):
        family.geometry(dict(cfg, **{key: value}))


def test_the_configuration_file_is_the_catalog_row_cut_in_depth_alone():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    mine = published()
    assert mine["source"] == row["source_url"]
    assert mine["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in mine["reduced"]:
            assert mine[key] == value, key
    assert mine["num_hidden_layers"] == 12
    assert mine["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert mine["published"]["max_position_embeddings"] == 32768
    assert mine["serving"]["engine"] == {"max_model_len": 16384}
    assert "deployment" in mine and len(mine["assumed"]) >= 6


def test_reference_against_itself_reads_zero(cfg):
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-4 and got["positions"] == 48


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_the_a8_control_is_not_correct(cfg, seed):
    got = control.control_error(cfg, seed, "a8", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


STACKED = ["attn_norm", "mlp_norm", "mla_wq", "mla_wkva", "mla_kvnorm", "mla_wkvb",
           "mla_wo", "w_gate", "w_up", "w_down", "router", "router_bias",
           "ws_gate", "ws_up", "ws_down"]
EXPERTS = ["we_gate", "we_up", "we_down"]


@pytest.fixture(scope="module")
def program_params(cfg):
    from dynamo_tpu.models import ModelConfig, deepseek_v3 as ds

    mc = ModelConfig.from_dict(cfg)
    return ds.init_params_quantized(mc, seed=SEED), ds.param_shapes(mc)


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


def test_the_parameter_order_is_the_programs(program_params):
    assert list(family.PARAM_ORDER) == list(program_params[1])


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
    """Two plain references written apart (this one draws its weights and
    reads the queries a block at a time, the repo's takes the program's
    weights and a whole softmax) give the same logits — at a length that
    takes two query blocks, with a row shorter than the rectangle."""
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.reference import deepseek_v3 as repo_ref

    params, _ = program_params
    rng = np.random.default_rng(4)
    T = 2 * family.QUERY_BLOCK
    tokens = rng.integers(5, cfg["vocab_size"], (2, T)).astype(np.int32)
    lengths = np.array([T, 700], np.int32)
    at = np.stack([np.arange(T - 8, T), np.arange(692, 700)]).astype(np.int32)
    mine = np.asarray(family.logits_fn(cfg)(SEED, tokens, lengths, at))
    mc = ModelConfig.from_dict(cfg)
    for b in range(2):
        theirs = np.asarray(repo_ref.forward(
            mc, params, jnp.asarray(tokens[b:b + 1, :lengths[b]])))[0, at[b]]
        np.testing.assert_allclose(mine[b], theirs, rtol=0, atol=3e-4)


# -- the kind and the mix ----------------------------------------------------------
def test_shared_docs_schedule_is_a_function_of_the_mix_and_seconds_alone():
    mix = sched.load_mix("doc-qa")
    a, b = sched.build(mix, 50.0), sched.build(sched.load_mix("doc-qa"), 50.0)
    assert sched.digest(a) == sched.digest(b)
    assert sched.digest(a) != sched.digest(sched.build(mix, 51.0))
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert sched.digest(sched.build(other, 50.0)) != sched.digest(a)
    seeds = {sched.load_mix(m)["schedule_seed"] for m in (
        "chat", "chat-long", "chat-burst", "sessions", "decode-heavy", "long-decode")}
    assert mix["schedule_seed"] not in seeds
    # a longer window extends the same schedule: every ask due before the
    # shorter window's end is in both, the same
    longer = [e for e in sched.build(mix, 60.0)["entries"] if e["due"] < 50.0]
    strip = lambda es: [{k: v for k, v in e.items() if k != "i"} for e in es]  # noqa: E731
    assert strip(longer) == strip(a["entries"])
    assert [e["i"] for e in a["entries"]] == list(range(len(a["entries"])))
    assert [e["due"] for e in a["entries"]] == sorted(e["due"] for e in a["entries"])


def test_doc_qa_is_the_issues_traffic_and_fits_the_served_context():
    mix = sched.load_mix("doc-qa")
    assert mix["kind"] == "shared_docs" and mix["ramp_s"] == 20
    assert mix["doc_tokens"] == {"median": 8192, "sigma": 0.4, "min": 4096, "max": 14336}
    assert mix["asks_per_doc"] == {"min": 3, "max": 5}
    assert mix["ask_gap_s"] == {"min": 3.0, "max": 8.0}
    assert mix["question_tokens"] == {"min": 32, "max": 128}
    assert mix["output_tokens"] == {"median": 64, "sigma": 0.5, "min": 16, "max": 192}
    assert mix["slo"] == {"ttft_ms": 4000, "gap_ms": 80} and mix["drain_limit_s"] == 90
    assert round(mix["doc_rate_rps"] * 20) == pytest.approx(mix["doc_rate_rps"] * 20)
    limit = published()["serving"]["engine"]["max_model_len"]
    assert mix["max_total_tokens"] == limit == 16384
    entries = sched.build(mix, 50.0)["entries"]
    assert max(e["prompt"] + e["out"] for e in entries) <= limit
    by_doc: dict = {}
    for e in entries:
        by_doc.setdefault(e["doc"], []).append(e)
    whole = [d for d in by_doc.values() if d[0]["due"] + 4 * 8.0 < 50.0]
    assert whole and all(3 <= len(d) <= 5 for d in whole)
    for asks in by_doc.values():
        assert [a["ask"] for a in asks] == list(range(len(asks)))
        assert len({a["doc_tokens"] for a in asks}) == 1
        assert all(a["prompt"] == a["doc_tokens"] + a["question"] for a in asks)
        gaps = np.diff([a["due"] for a in asks])
        assert np.all((gaps >= 3.0) & (gaps <= 8.0))
    totals = shared_docs.totals(sched.build(mix, 50.0))
    # what the cache can serve: two thirds to three quarters in the long
    # run (3-5 asks a document); a window of nine documents, some of them
    # asked before it opened, reads a little over that
    assert 0.6 <= totals["window_shared_token_share"] <= 0.85
    long_run = shared_docs.totals(sched.build(dict(mix, doc_rate_rps=2.0), 400.0))
    assert 0.66 <= long_run["window_shared_token_share"] <= 0.76
    assert totals["window_requests"] == pytest.approx(
        50 * mix["doc_rate_rps"] * 4, rel=0.35)


def test_a_documents_asks_share_exactly_its_tokens():
    """What the driver sends: the asks of one document begin with the same
    ``doc_tokens`` ids and differ right after; two documents share nothing."""
    import asyncio

    mix = dict(sched.load_mix("doc-qa"), doc_rate_rps=2.0, ramp_s=0)
    mix["doc_tokens"] = {"median": 300, "sigma": 0.3, "min": 128, "max": 600}
    mix["ask_gap_s"] = {"min": 0.0, "max": 0.0}
    schedule = sched.build(mix, 3.0)
    schedule.update(seconds=3.0, ramp_s=0.0)
    sent: dict = {}

    class Load:
        t0, end = 0.0, 3.0
        ids = staticmethod(lambda key, n: sched.token_ids(2**31 + 5, key, n, 128256))

        async def sleep_until(self, t):
            pass

        async def request(self, key, due, prompt, out):
            sent[key] = list(prompt)

    load = Load()
    load.schedule, load.mix = schedule, mix
    asyncio.run(shared_docs.drive(load))
    entries = {("doc", e["doc"], e["ask"]): e for e in schedule["entries"]}
    assert set(sent) == set(entries) and len(sent) >= 6
    docs: dict = {}
    for key, ids in sent.items():
        e = entries[key]
        assert len(ids) == e["prompt"]
        docs.setdefault(e["doc"], []).append((ids[:e["doc_tokens"]], ids[e["doc_tokens"]:]))
    for asks in docs.values():
        assert all(a[0] == asks[0][0] for a in asks)
        assert len({tuple(a[1][:8]) for a in asks}) == len(asks)
    firsts = [tuple(a[0][0][:16]) for a in docs.values()]
    assert len(set(firsts)) == len(firsts)


def test_the_probes_compared_rows_hold_second_asks():
    mix = sched.load_mix("doc-qa")
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("doc-qa")) and len(waves) == 2
    first, second = waves
    assert len(first) == len(second) == 6
    docs = [j["shared_tokens"] for j in first]
    assert docs == [j["shared_tokens"] for j in second] == sorted(docs)
    assert docs[0] == 4096 and docs[-1] == 14336
    assert [j["shared"] for j in first] == [j["shared"] for j in second]
    rows = [j["row"] for j in first + second]
    assert sorted(rows) == list(range(12))
    assert all(j["out"] == 64 for j in first + second)   # check.POSITIONS / 12 rows
    # answered as the server would (any ids): what the reference reads
    answers = []
    for wave in waves:
        for job in check.wave_jobs(7, 128256, wave, []):
            answers.append(dict(job, chosen=[9] * job["out"],
                                logprobs=[0.0] * job["out"]))
    kept = check.compared(check.sequences(answers))
    second_rows = {j["row"] for j in second}
    assert kept[0]["row"] in second_rows          # the longest: a second ask
    assert len(kept[0]["tokens"]) == 14336 + 128 + 64
    hits = sum(s["row"] in second_rows for s in kept)
    assert len(kept) == 4 and hits == 3 and hits * 2 >= len(kept)
    assert any(s["row"] not in second_rows for s in kept)   # a cold row too
    assert sum(len(s["at"]) for s in kept) >= 256
    assert sum(check.padded(len(s["tokens"])) for s in kept) <= check.REFERENCE_TOKENS
    # the two waves of a document share its ids and differ in the question
    by_row = {a["row"]: a for a in answers}
    a, b = by_row[first[0]["row"]], by_row[second[0]["row"]]
    assert a["ids"][:4096] == b["ids"][:4096] and a["ids"][4096:] != b["ids"][4096:]
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


# -- the benchmark's entries -----------------------------------------------------
AT_LEAST = {
    "ttft_p50_ms", "tpot_mean_ms", "mla_decode_roofline.open", "mla_prefill_roofline",
    "moe_roofline.open", "moe_touched_share", "prefix_hit_share", "cached_token_share",
    "slo_met_share", "serve_compiles.open", "batch_running_mean.open",
    "kv_preemptions.open", "step_device_ms_p50.open", "device_idle_share.open",
    "prefill_ms_p50", "queue_wait_ms_p50", "prefill_fill_share.open"}


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
        "kanana-2-30b", "doc-qa", 1)
    assert len(cell["why"]) <= 200
    config = configs["kanana-2-30b"]
    assert len(config["why"]) <= 200 and config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == published()["source"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index("nemotron-3-nano-30b.chat-burst") < names.index(BENCH_CELL)
    listed = _listed(metrics, BENCH_CELL)
    assert AT_LEAST <= listed
    # no K/V decode kernel and no state plane in this family: their readers
    # would find nothing, so the cell is not on their lists
    assert not {"attn_decode_roofline.open", "state_slots_used_share.open",
                "qmm_roofline.open", "mla_decode_roofline", "moe_roofline"} & listed
    for name in listed:
        on = metrics[name]["workloads"]
        for earlier in ("mistral-7b.chat", "qwen3-next-80b.chat-long",
                        "nemotron-3-nano-30b.chat-burst"):
            if earlier in on:
                assert on.index(earlier) < on.index(BENCH_CELL)   # appended
    for name, moves in (("mla_decode_roofline.open", "tpot_mean_ms"),
                        ("mla_prefill_roofline", "ttft_p50_ms")):
        m = metrics[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"], m["better"]) == (
            "kernels", moves, "%", "device_trace", "higher")
        assert m in bench["per_layer"] and m["workloads"][0] == BENCH_CELL
    with open(os.path.join(REPO, "perf", "reference", "limits", BENCH_CELL + ".json")) as f:
        assert 0 < json.load(f)["logprob_err_mean"] < 1


# -- what the skipped cases of older tests held (tests/conftest.py) -------------------
OPEN = ["mistral-7b.chat", "qwen3-next-80b.chat-long", "nemotron-3-nano-30b.chat-burst"]
VARIANTS = {"open": ("tpot_mean_ms", OPEN),
            "sessions": ("tpot_mean_ms.sessions", ["mistral-7b.sessions"]),
            "closed": ("out_tok_s", ["qwen2.5-7b.decode-heavy",
                                     "kimi-linear-48b.long-decode"])}
STEMS = {"decode_period_ms": ("ms", "engine step loop"),
         "step_host_wall_ms": ("ms", "engine step loop"),
         "step_host_offcpu_ms": ("ms", "engine step loop"),
         "loop_cpu_ms_per_step": ("ms", "HTTP frontend"),
         "dispatch_drained_share": ("%", "device")}


@pytest.mark.parametrize("stem", sorted(STEMS))
def test_the_open_variant_of_each_count_history_stem_keeps_its_entry(stem):
    """``test_count_history``'s case of the same entry, the list held as
    "begins with the three cells it had, this cell behind them"."""
    bench, _, _, metrics = _benchmark()
    moves, cells = VARIANTS["open"]
    unit, layer = STEMS[stem]
    entry = dict(metrics[f"{stem}.open"])
    listed = entry.pop("workloads")
    assert entry == {"name": f"{stem}.open", "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer, "moves": moves}
    assert listed[:3] == cells and listed.index(BENCH_CELL) >= 3
    assert set(listed) <= set(metrics[moves]["workloads"])
    assert os.path.exists(os.path.join(REPO, "perf", "metrics", f"{stem}.py"))


def test_prefill_fill_share_open_keeps_its_entry():
    _, _, _, metrics = _benchmark()
    entry = dict(metrics["prefill_fill_share.open"])
    listed = entry.pop("workloads")
    assert entry == {"name": "prefill_fill_share.open", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "ttft_p50_ms"}
    assert listed[:3] == OPEN and BENCH_CELL in listed[3:]
    assert set(listed) <= set(metrics["ttft_p50_ms"]["workloads"])


def test_pr_40s_fifteen_entries_stay_together_and_cover_every_cell():
    bench, cells, _, _ = _benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    mine = [f"{s}.{v}" for s in STEMS for v in ("open", "sessions", "closed")]
    at = names.index(mine[0])
    assert names[at:at + 15] == mine              # contiguous, in order
    assert set(names[at + 15:]) >= {"mla_decode_roofline.open", "mla_prefill_roofline"}
    for stem in STEMS:
        listed = [c for m in bench["per_layer"]
                  if m["name"].partition(".")[0] == stem for c in m["workloads"]]
        assert sorted(listed) == sorted(cells)


def test_this_cells_probe_is_its_own_mix_at_the_size_the_window_runs():
    """``test_perf_reference``'s case for this cell: a function of the mix
    file alone, 768-1 024 positions, the same lengths for every seed, and
    a limit file of the cell's own; its kind's layout is held above."""
    mix = sched.load_mix("doc-qa")
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix("doc-qa"))
    assert 768 <= sum(j["out"] for w in waves for j in w) <= 1024
    assert max(j["shared_tokens"] + j["new"] for j in waves[1]) == 14336 + 128
    a = check.wave_jobs(1, 32000, waves[0], [])
    b = check.wave_jobs(2**31 + 5, 32000, waves[0], [])
    assert [len(j["ids"]) for j in a] == [len(j["ids"]) for j in b]
    assert a[0]["ids"] != b[0]["ids"]
    assert set(check.load_limits(BENCH_CELL)) == {"logprob_err_mean"}


def test_a_family_nobody_serves_is_still_an_error_that_names_it():
    """``deepseek-v3`` has a module now; the next unserved name with a
    ``-`` reads the same way."""
    from perf.reference.family import FamilyError

    assert family_of({"model_type": "deepseek-v3"}) is family     # "-" read as "_"
    cfg = {"model_type": "glm-5"}
    with pytest.raises(FamilyError) as err:
        family_of(cfg)
    assert all(part in str(err.value) for part in ("'glm-5'", "perf/reference/glm_5.py"))
    with pytest.raises(FamilyError):
        check.reference_logprobs(dict(cfg, vocab_size=16), 1, [], "f32")


# -- the new reader ------------------------------------------------------------------
def test_mla_prefill_cost_is_the_published_non_absorbed_count():
    ops, byts = costs.mla_prefill_cost(1000, 32, 128, 64, 128)
    assert ops == 1000 * 32 * 640 and byts == 0.0
    from dynamo_tpu.models import deepseek_v3 as ds

    assert costs.PAIR_UNIT == ds.PAIR_UNIT
    assert {"mla_prefill_pairs", "mla_prefill_calls",
            "mla_prefill_query_tokens"} <= set(ds.COUNT_NAMES)


class FakeRun:
    def __init__(self, config, ops=None, samples=()):
        self.config, self.trace = config, {"ops": ops or {}}
        self.trace_span, self.samples = (10.0, 12.0), list(samples)
        self.device, self.notes = {"kind": "TPU v5 lite"}, []
        self.trace_dir = None


def test_mla_prefill_roofline_is_least_over_the_kernels_time(monkeypatch):
    from perf import roofline
    from perf.metrics import kimi_linear_costs

    pub = hf_config(published())
    units = 400_000                                    # x 1 024 pairs
    deltas = {"engine.mla_prefill_pairs": units, "engine.mla_prefill_calls": 60,
              "engine.mla_prefill_query_tokens": 5 * 1024 * 12}
    monkeypatch.setattr(mla_prefill_roofline, "count_deltas", lambda run: deltas)
    peak = roofline.peaks("TPU v5 lite")["bf16_flops_per_s"]
    least = units * 1024 * 32 * 640 / peak
    ops = {"mla_prefill_attention.7": {"calls": 60, "total_s": least / 0.2,
                                       "median_s": least / 12},
           "mla_decode_attention.3": {"calls": 99, "total_s": 5.0, "median_s": 0.05}}
    run = FakeRun(pub, ops=ops)
    assert mla_prefill_roofline.read(run) == pytest.approx(20.0, rel=1e-3)
    note = run.notes[0]["mla_prefill_roofline"]
    assert note["calls_counted"] == note["calls_traced"] == 60
    assert note["bound"] == "compute"
    # a prefill in flight at the capture's first edge: traced, not counted
    more = dict(ops, **{"mla_prefill_attention.8": {
        "calls": 12, "total_s": least / 0.2, "median_s": least / 12}})
    assert mla_prefill_roofline.read(FakeRun(pub, ops=more)) == pytest.approx(10.0, rel=1e-3)
    # counted but not traced: the two sides are not the same calls
    few = {"mla_prefill_attention.7": dict(ops["mla_prefill_attention.7"], calls=48)}
    assert mla_prefill_roofline.read(FakeRun(pub, ops=few)) is None
    # a count too high for the time raises, it is never clipped
    fast = {"mla_prefill_attention.7": dict(ops["mla_prefill_attention.7"],
                                           total_s=least / 1.2)}
    with pytest.raises(roofline.RooflineError):
        mla_prefill_roofline.read(FakeRun(pub, ops=fast))
    assert mla_prefill_roofline.read(FakeRun(pub)) is None              # no kernel
    monkeypatch.setattr(mla_prefill_roofline, "count_deltas", lambda run: None)
    assert mla_prefill_roofline.read(FakeRun(pub, ops=ops)) is None     # no counts
    # the parent's program: the kernel's name is not in the trace, and
    # kimi's counts hold no pairs
    monkeypatch.setattr(mla_prefill_roofline, "count_deltas",
                        lambda run: {"engine.moe_layer_calls": 9})
    assert mla_prefill_roofline.read(FakeRun(pub, ops=ops)) is None
    assert kimi_linear_costs.engine_count(deltas, "mla_prefill_pairs") == units


def test_mla_decode_roofline_reads_this_family_at_its_variant():
    pub = hf_config(published())
    rows = [8200, 14000, 4100, 9000, 6000]
    from perf.metrics import kimi_linear_costs

    _, byts = kimi_linear_costs.mla_decode_cost(rows, 32, 512, 64)
    least = byts / 819e9
    ops = {"mla_decode_attention.3": {"calls": 120, "total_s": 120 * least / 0.25,
                                      "median_s": least / 0.25}}
    run = FakeRun(pub, ops=ops, samples=[{"t": 11.0, "contexts": rows}])
    assert mla_decode_roofline.read(run, "open") == pytest.approx(25.0, rel=1e-3)
    assert mla_decode_roofline.read(FakeRun(pub, ops={
        "mla_prefill_attention.7": ops["mla_decode_attention.3"]},
        samples=run.samples), "open") is None


# -- the rehearsal ------------------------------------------------------------------
def tiny_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-deepseek-v3", "file": os.path.relpath(
        os.path.join(DATA, "tiny-deepseek-v3.json"), REPO)}]
    bench["workloads"] = [{"name": CELL, "config": "tiny-deepseek-v3",
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
    # being asked by then, so prefill calls fall inside it
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
    # a first ask misses and a later one hits; how many later ones hit is
    # the machine's to say (an ask due while the document's first is still
    # in its prefill finds no cached page yet, and a loaded CPU is slow)
    assert queries > 0 and 0 < hits < queries
    compared = phases["outputs"]["compared"]
    assert compared["rows_sent"] == 12 and compared["rows_compared"] >= 3
    assert phases["engine_up"]["kv_pool"]["total_blocks"] == 255
    names = set(result["metrics"])
    if trace:
        assert {"prefix_hit_share", "cached_token_share", "moe_touched_share",
                "batch_running_mean.open"} <= names
        assert 0 < result["metrics"]["prefix_hit_share"]["value"] < 100
        assert 0 < result["metrics"]["cached_token_share"]["value"] < 100
        assert 0 < result["metrics"]["moe_touched_share"]["value"] <= 100
    else:
        assert {"ttft_p50_ms", "tpot_mean_ms", "setup_s"} <= names
        assert result["metrics"]["tpot_mean_ms"]["value"] > 0
