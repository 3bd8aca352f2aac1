"""The output check's reference and its control, at a size a test holds.

The control (the reference one precision step down, in the program's
place) has to come out as NOT correct; the reference against itself
reads exactly 0; and the reference's seeded draw is the program's
documented recipe, value for value. The reference is the one of the
configuration's family, found by its ``model_type``: a second family
enters as a file."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path.insert(0, REPO)

from perf.reference import check, control, model  # noqa: E402
from perf.reference.family import FamilyError, family_of  # noqa: E402
from perf.server import hf_config  # noqa: E402

TINY_LIMIT = 0.05  # the rehearsal's limit; the program reads 0.006 there


def jobs_for(cfg, seed, lengths=(40, 57, 33), out=6):
    wave = [{"row": r, "wave": 0, "new": n, "out": out}
            for r, n in enumerate(lengths)]
    return check.wave_jobs(seed, cfg["vocab_size"], wave, [])


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(DATA, "tiny-llama.json")) as f:
        return hf_config(json.load(f))


def test_reference_against_itself_reads_zero(cfg):
    # to float32's own rounding: the two read the rows in batches of other sizes
    got = control.control_error(cfg, 3, "f32", jobs_for(cfg, 3))
    assert got["logprob_err_max"] < 1e-5 and got["positions"] == 18


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_int4_weight_control_is_not_correct(cfg, seed):
    got = control.control_error(cfg, seed, "w4", jobs_for(cfg, seed))
    assert got["logprob_err_mean"] > TINY_LIMIT


@pytest.mark.parametrize("precision", ["a8", "f8"])
def test_activation_controls_read_above_zero(cfg, precision):
    got = control.control_error(cfg, 1, precision, jobs_for(cfg, 1))
    assert 0.0 < got["logprob_err_mean"] < 1.0


@pytest.mark.parametrize("precision", ["a8", "f8"])
def test_the_forced_control_reads_like_the_free_running_one(cfg, precision):
    """On the chip the control is read teacher-forced, on sequences that
    were already answered; it has to read what the control reads when it
    answers for itself (one precision step down the two choose nearly the
    same ids; int4 weights, 1 nat off, choose others and are not read so)."""
    lengths, out = (40, 57, 33, 71, 25, 64, 48, 90), 24
    free, forced = [], []
    for seed in (5, 6, 7):
        jobs = jobs_for(cfg, seed, lengths, out)
        free.append(control.control_error(cfg, seed, precision, jobs)
                    ["logprob_err_mean"])
        # sequences answered by someone else: the float32 reference itself
        seqs = check.sequences(control.control_answers(cfg, seed, "f32", jobs))
        forced.append(control.forced_error(
            seqs, check.reference_logprobs(cfg, seed, seqs, precision),
            check.reference_logprobs(cfg, seed, seqs, "f32"))["logprob_err_mean"])
    assert sum(forced) / sum(free) == pytest.approx(1.0, abs=0.15)


def test_the_draw_is_the_programs_recipe(cfg):
    """Same seed, same weights as ``init_params_quantized`` makes them."""
    import jax

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.quant import init_params_quantized

    seed = 11
    mc = ModelConfig.from_dict(cfg)
    params = init_params_quantized(mc, seed=seed)
    g = model.geometry(cfg)
    idx = model.param_index(g)
    key = jax.random.PRNGKey(seed)
    for name, layer, shape, axis in [
            ("wq", 1, (g["D"], g["H"] * g["Dh"]), -2),
            ("w_down", 0, (g["F"], g["D"]), -2)]:
        mine = model._draw(
            jax.random.fold_in(jax.random.fold_in(key, idx[name]), layer),
            shape, shape[0], axis, 8)
        theirs = (np.asarray(params[name][layer], np.float32)
                  * np.asarray(params[name + "_scale"][layer])[None, :])
        np.testing.assert_allclose(np.asarray(mine), theirs, rtol=0, atol=1e-7)
    embed = model._draw(jax.random.fold_in(key, idx["embed"]),
                        (g["V"], g["D"]), g["V"], -1, 8)
    theirs = (np.asarray(params["embed"], np.float32)
              * np.asarray(params["embed_scale"])[:, None])
    np.testing.assert_allclose(np.asarray(embed), theirs, rtol=0, atol=1e-7)
    bq = (jax.random.normal(jax.random.fold_in(key, idx["bq"]),
                            (g["L"], g["H"] * g["Dh"])) / np.sqrt(g["L"]))
    np.testing.assert_allclose(
        np.asarray(bq.astype(jax.numpy.bfloat16).astype(np.float32)),
        np.asarray(params["bq"], np.float32), rtol=0, atol=0)


def test_compare_is_the_mean_absolute_difference():
    seqs = [{"logprobs": [-1.0, -2.0]}, {"logprobs": [-3.0]}]
    got = check.compare(seqs, [[-1.5, -2.0], [-2.0]])
    assert got == {"logprob_err_mean": 0.5, "logprob_err_max": 1.0, "positions": 3}
    with pytest.raises(ValueError):
        check.compare([], [])


def test_a_follow_up_and_the_row_it_follows_are_one_sequence():
    first = {"row": 0, "wave": 0, "after": None, "ids": [5, 6, 7],
             "chosen": [8, 9], "logprobs": [-1.0, -2.0]}
    second = {"row": 0, "wave": 1, "after": 0, "ids": [5, 6, 7, 8, 9, 10, 11],
              "chosen": [12], "logprobs": [-3.0]}
    alone = {"row": 1, "wave": 0, "after": None, "ids": [5], "chosen": [6],
             "logprobs": [-0.5]}
    seqs = check.sequences([first, alone, second])
    assert [s["row"] for s in seqs] == [1, 0]
    chain = seqs[1]
    assert chain["tokens"] == [5, 6, 7, 8, 9, 10, 11, 12]
    assert chain["at"] == [2, 3, 6] and chain["chosen"] == [8, 9, 12]
    assert chain["logprobs"] == [-1.0, -2.0, -3.0]
    with pytest.raises(ValueError):
        check.sequences([first, dict(second, ids=[5, 6, 7, 8, 0, 10, 11])])


def test_the_reference_reads_the_longest_row_and_what_fits_beside_it():
    seqs = [{"row": r, "tokens": [0] * n} for r, n in enumerate(
        [600, 9000, 700, 30000, 100, 800])]
    took = check.compared(seqs)
    # 30000 -> 30720 padded; row 0 (1024) fits, row 1 (9216) ends it
    assert [s["row"] for s in took] == [3, 0]
    assert check.compared(seqs[:1]) == seqs[:1]


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = json.load(_f)["workloads"]


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_a_cells_probe_is_its_own_mix_at_the_size_the_window_runs(cell):
    """Rows as many as the mix keeps running, the longest prompts and
    contexts it sends, a function of the mix file alone; and a limit file
    of the cell's own."""
    from perf.traffic import schedule as sched

    mix = sched.load_mix(cell["traffic"])
    waves = check.probe_waves(mix)
    assert waves == check.probe_waves(sched.load_mix(cell["traffic"]))
    rows = len(waves[0])
    positions = sum(j["out"] for w in waves for j in w)
    assert 768 <= positions <= 1024
    longest = max(j.get("shared_tokens", 0) + j["new"] for j in waves[0])
    if mix["kind"] == "closed_loop":
        assert rows == mix["clients"] == 48       # the 64-row decode programs
    elif mix["kind"] == "sessions":
        assert rows == mix["live_sessions"] and len(waves) == 2
        assert longest > 2048                     # the contexts sessions reach
        assert all(j["after"] == j["row"] for j in waves[1])
    else:
        assert rows >= 12 and longest == 3072     # three prefill chunks
    a = check.wave_jobs(1, 32000, waves[0], [])
    b = check.wave_jobs(2**31 + 5, 32000, waves[0], [])
    assert [len(j["ids"]) for j in a] == [len(j["ids"]) for j in b]
    assert a[0]["ids"] != b[0]["ids"]
    assert set(check.load_limits(cell["name"])) == {"logprob_err_mean"}


TOY = {"vocab_size": 16, "hidden_size": 64}


@pytest.mark.parametrize("model_type,stem,source", [
    ("toyfam", "toyfam", None),
    ("toy-fam", "toy_fam", None),                      # "-" is read as "_"
    ("toy3", "some_family", 'FAMILIES = ("toy3", "toy4")'),  # listed, not named
])
def test_a_family_that_exists_only_as_a_file_is_found_and_called(
        family_files, model_type, stem, source):
    """No file under ``perf/`` changes: the module lives in the test's
    temporary directory, and the output check and its control both run
    ITS ``logits_fn``."""
    where = family_files(stem, source or "")
    cfg = dict(TOY, model_type=model_type)
    family = family_of(cfg)
    assert os.path.dirname(family.__file__) == where
    assert not os.path.exists(os.path.join(REPO, "perf", "reference", stem + ".py"))
    jobs = [{"row": 0, "wave": 0, "ids": [3, 4, 5], "out": 4},
            {"row": 1, "wave": 0, "ids": [9], "out": 4}]
    answers = control.control_answers(cfg, 7, "a8", jobs)
    # the toy "a8" puts the id two after the last one first
    assert [a["chosen"] for a in answers] == [[7, 9, 11, 13], [11, 13, 15, 1]]
    seqs = check.sequences([dict(a, after=None) for a in answers])
    ref = check.reference_logprobs(cfg, 7, seqs, "f32")
    assert [c[0] for c in family.CALLS] == ["a8"] * 4 + ["f32"]
    assert {c[1] for c in family.CALLS} == {7}
    # "f32" puts the id one after first: every chosen id lies one below its best
    for seq, got in zip(seqs, ref):
        for at, chosen, lp in zip(seq["at"], seq["chosen"], got):
            logits = -np.abs(np.arange(16.0) - (seq["tokens"][at] + 1) % 16)
            assert logits[chosen] == -1.0
            assert lp == pytest.approx(-1.0 - np.log(np.exp(logits).sum()), abs=1e-5)
    got = control.control_error(cfg, 7, "a8", jobs)
    assert got["positions"] == 8 and got["logprob_err_mean"] > 0.5


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CONFIG_FILES = [c["file"] for c in json.load(_f)["configs"]]


@pytest.mark.parametrize("model_type", ["llama", "mistral", "qwen2"])
def test_the_llama_family_resolves_to_model_py(model_type):
    assert family_of({"model_type": model_type}) is model
    assert model_type in model.FAMILIES


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_every_configuration_of_the_benchmark_finds_its_family(path):
    with open(os.path.join(REPO, path)) as f:
        cfg = hf_config(json.load(f))
    assert family_of(cfg) is model
    assert {"D", "V", "H", "Hk", "Dh"} <= set(family_of(cfg).geometry(cfg))


@pytest.mark.parametrize("cfg,says", [
    ({"model_type": "lfm2_moe"}, ("'lfm2_moe'", "perf/reference/lfm2_moe.py")),
    ({"model_type": "deepseek-v3"}, ("'deepseek-v3'", "perf/reference/deepseek_v3.py")),
    ({"hidden_size": 64}, ("no model_type",)),
    ({"model_type": "check"}, ("perf.reference.check", "logits_fn")),  # a file, no family
])
def test_an_unknown_family_is_an_error_that_names_it(cfg, says):
    """Never another family's equations under this model's name."""
    with pytest.raises(FamilyError) as err:
        family_of(cfg)
    assert all(part in str(err.value) for part in says)
    with pytest.raises(FamilyError):
        check.reference_logprobs(dict(cfg, vocab_size=16), 1, [], "f32")


def test_two_modules_that_list_one_model_type_are_refused(family_files):
    family_files("one", 'FAMILIES = ("twice",)')
    family_files("other", 'FAMILIES = ("twice",)')
    with pytest.raises(FamilyError, match="more than one"):
        family_of({"model_type": "twice"})


def test_model_py_meets_the_contract_of_a_family_module(cfg):
    assert {"f32", "a8"} <= set(model.PRECISIONS)
    g = model.geometry(cfg)
    assert {"D", "V", "H", "Hk", "Dh"} <= set(g)
    widths = model.layer_matmuls(g)
    assert sum(len(v) for v in widths.values()) == 6   # q k v o gate+up down
    assert widths[g["F"]] == [(g["D"], 2, False)]
    assert (g["F"], 1, True) in widths[g["D"]]
    with pytest.raises(ValueError, match="bf16"):
        model.logits_fn(cfg, "bf16")
    tokens = np.arange(24, dtype=np.int32).reshape(2, 12)
    logits = model.logits_fn(cfg, "f32")(
        5, tokens, np.array([12, 7], np.int32), np.array([[3, 11, 0], [6, 0, 0]], np.int32))
    assert logits.shape == (2, 3, g["V"]) and logits.dtype == np.float32
    lp = np.asarray(check.chosen_logprobs(logits, np.zeros((2, 3), np.int32)))
    assert lp.shape == (2, 3) and (lp < 0).all()
