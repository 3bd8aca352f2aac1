"""``glm_moe_dsa`` (GLM-5) below the engine: the layer mathematics of
``models/glm_moe_dsa.py`` against the plain reference
(``models/reference/glm_moe_dsa.py``) — prefill then decode through both
page planes with the selected SETS compared, the dense regime and the
sparse one, the indexer's LayerNorm, rotary layout and sign handling
against formulas written out here, the expert share, the seeded recipe,
kanana's draw, and what the family refuses by name."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, deepseek_v3 as ds, family
from dynamo_tpu.models import glm_moe_dsa as glm
from dynamo_tpu.models.reference import glm_moe_dsa as ref
from tests.deepseek_v3_tiny import tiny_deepseek
from tests.glm_moe_dsa_tiny import tiny_glm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8
TABLES = np.array([[1, 2, 3, 4, 8], [5, 6, 7, 0, 0], [0, 0, 0, 0, 0]], np.int32)


def published():
    with open(os.path.join(REPO, "perf", "configs", "glm-5.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses():
    cfg, raw = published()
    g = glm.Geometry(cfg)
    assert family(cfg) is glm
    assert cfg.owns_pages and not cfg.has_recurrent_state and not cfg.released_window
    assert (g.L, g.D, g.V, g.H) == (9, 6144, 19360, 64)
    assert (g.nope, g.rope, g.vd, g.rank, g.C, g.Cpad) == (192, 64, 256, 512, 576, 640)
    assert (g.q_rank, g.G, g.dI, g.topk) == (2048, 32, 128, 2048)
    assert (g.F, g.Fe, g.Fs, g.E, g.E_all, g.e0, g.k) == (12288, 2048, 2048, 16, 256, 0, 8)
    assert g.dense_layers == [0] and g.moe_layers == list(range(1, 9))
    # the rotary base comes from rope_parameters; the MTP layer is read by nothing
    assert "rope_theta" not in raw and cfg.rope_theta == 1e6
    assert cfg.rope_scaling is None and cfg.num_nextn_predict_layers == 1
    assert sorted(raw["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size"])
    assert raw["published"]["n_routed_experts"] == 256


def test_weights_and_pages_at_this_repos_byte_are_what_the_issue_reckoned():
    cfg, _ = published()
    shapes = glm.param_shapes(cfg)
    total = sum(int(np.prod(shape)) for name, (shape, _) in shapes.items()
                if name in glm.QUANT_AXIS)
    assert 7.1e9 < total < 7.3e9                         # the issue: 7.22 GB
    per_layer = lambda names: sum(                       # noqa: E731
        int(np.prod(shapes[n][0][1:])) for n in names)
    assert round(per_layer(
        ("mla_wqa", "mla_wqb", "mla_wkva", "mla_wkvb", "mla_wo")) / 1e6, 1) == 165.0
    assert round(per_layer(("idx_wq", "idx_wk", "idx_ww")) / 1e6, 1) == 9.4
    # a page: 128 tokens x (640 + 128) lanes x 2 B x 9 layers, both planes
    assert glm.page_bytes_per_block(cfg, 128, 2) == 128 * 768 * 2 * 9 == 1769472
    assert glm.page_bytes_per_block(cfg, 128, 2, plane="index_k") == 128 * 128 * 2 * 9


@pytest.mark.parametrize("bad, named", [
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(index_topk=0), "index_topk"),
    (dict(index_n_heads=0), "index_n_heads"),
    (dict(index_head_dim=4), "index_head_dim"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "n_group"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(rope_parameters={"rope_theta": 1e4, "rope_type": "yarn", "factor": 4}),
     "rope_scaling"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(qk_head_dim=32), "qk_head_dim"),
])
def test_what_is_not_built_is_refused_by_the_keys_name(bad, named):
    with pytest.raises(ValueError, match=named):
        glm.param_shapes(tiny_glm(**bad))


def test_check_engine_names_what_it_refuses():
    from dynamo_tpu.engine.config import EngineConfig

    glm.check_engine(EngineConfig(model_name="x", kv_cache_dtype="bfloat16"))
    for kw, named in ((dict(tensor_parallel_size=2), "tensor_parallel_size"),
                      (dict(host_kv_blocks=4), "host_kv_blocks"),
                      (dict(kv_cache_dtype="int8"), "int8"),
                      (dict(spec_decode="ngram"), "spec_decode")):
        with pytest.raises(ValueError, match=named):
            glm.check_engine(EngineConfig(model_name="x", **kw))


def _checksum(params) -> str:
    h = hashlib.sha256()
    for name in sorted(params):
        h.update(name.encode())
        h.update(np.asarray(params[name]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("quantized, want", [
    (False, "ff320c5bee50a9a6"), (True, "153069defd434a96")])
def test_kananas_seeded_draw_is_bit_identical_to_the_parents(quantized, want):
    """``deepseek_v3.param_shapes``' order is the recipe and
    ``perf/reference/deepseek_v3.py`` mirrors it: the expert share and the
    low-rank query this PR taught its ``Geometry`` move no index and no
    shape. The checksums are the PARENT tree's (commit 0fdda25) at
    ``tiny_deepseek()``, seed 5."""
    cfg = tiny_deepseek()
    params = (ds.init_params_quantized(cfg, seed=5) if quantized
              else ds.init_params(cfg, seed=5, dtype=jnp.float32))
    assert _checksum(params) == want
    assert list(ds.param_shapes(cfg))[5:10] == [
        "mla_wq", "mla_wkva", "mla_kvnorm", "mla_wkvb", "mla_wo"]


def test_the_seeded_recipe():
    """Parameter ``i`` of ``param_shapes`` order has key ``fold_in(PRNGKey(
    seed), i)``, layer ``j`` ``fold_in(., j)``: the low-rank query's three
    stand where ``mla_wq`` stood, the indexer's five come last; the
    LayerNorm's bias is standard normal, its weight ones, ``W^I_w``
    ``normal / sqrt(D)`` in float32."""
    cfg = tiny_glm()
    names = list(glm.param_shapes(cfg))
    assert names[:8] == ["embed", "final_norm", "lm_head", "attn_norm",
                         "mlp_norm", "mla_wqa", "mla_qnorm", "mla_wqb"]
    assert names[-5:] == ["idx_wq", "idx_wk", "idx_knorm", "idx_kbias", "idx_ww"]
    p = glm.init_params(cfg, seed=3, dtype=jnp.float32)
    root = jax.random.PRNGKey(3)

    def key(name, layer):
        return jax.random.fold_in(
            jax.random.fold_in(root, names.index(name)), layer)

    np.testing.assert_array_equal(
        np.asarray(p["idx_kbias"][1]),
        np.asarray(jax.random.normal(key("idx_kbias", 1), (16,), jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(p["idx_ww"][2]),
        np.asarray(jax.random.normal(key("idx_ww", 2), (64, 4), jnp.float32) / 8.0))
    np.testing.assert_allclose(
        np.asarray(p["mla_wqb"][0]),
        np.asarray(jax.random.normal(key("mla_wqb", 0), (24, 96), jnp.float32)
                   / np.sqrt(24.0)), rtol=1e-6)
    assert np.all(np.asarray(p["idx_knorm"]) == 1) and np.all(
        np.asarray(p["mla_qnorm"]) == 1)
    assert p["idx_ww"].dtype == jnp.float32
    q = glm.init_params_quantized(cfg, seed=3)
    assert q["idx_wq"].dtype == jnp.int8 and q["idx_wk"].dtype == jnp.int8
    assert q["idx_ww"].dtype == jnp.float32 and "idx_ww_scale" not in q


# -- the indexer's pieces against formulas written out here ------------------------------


def test_the_indexers_layernorm_has_a_bias_and_its_rotary_leads():
    """``k^I = LayerNorm(W^I_k h)`` with weight AND bias at eps 1e-6, then
    the FIRST 8 of its 16 values turned as adjacent pairs by ``p *
    theta^(-2i/8)``; the last 8 are not rotated. ``q^I`` alike, a head."""
    cfg = tiny_glm()
    g = glm.Geometry(cfg)
    p = glm.init_params(cfg, seed=4, dtype=jnp.float32)
    p = dict(p, idx_knorm=jnp.asarray(
        np.random.default_rng(0).uniform(0.5, 1.5, (3, 16)), jnp.float32))
    rng = np.random.default_rng(1)
    h = rng.normal(size=(1, 5, 64)).astype(np.float32)
    c_q = rng.normal(size=(1, 5, 24)).astype(np.float32)
    pos = np.array([[3, 4, 5, 6, 7]], np.int32)
    q, k, w = glm.indexer_inputs(cfg, g, p, jnp.asarray(h), jnp.asarray(c_q), 1,
                                 jnp.asarray(pos))
    raw = h[0] @ np.asarray(p["idx_wk"][1])                       # [5, 16]
    mean = raw.mean(-1, keepdims=True)
    var = ((raw - mean) ** 2).mean(-1, keepdims=True)
    normed = (raw - mean) / np.sqrt(var + 1e-6) * np.asarray(p["idx_knorm"][1]) \
        + np.asarray(p["idx_kbias"][1])
    want = normed.copy()
    for t in range(5):
        for i in range(4):
            ang = pos[0, t] * 10000.0 ** (-2 * i / 8)
            a, b = normed[t, 2 * i], normed[t, 2 * i + 1]
            want[t, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[t, 2 * i + 1] = a * np.sin(ang) + b * np.cos(ang)
    np.testing.assert_allclose(np.asarray(k)[0], want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(k)[0][:, 8:], normed[:, 8:], atol=2e-5)
    # the bias is there: without it the keys differ
    assert np.abs(normed - (normed - np.asarray(p["idx_kbias"][1]))).max() > 0.1
    qraw = (c_q[0] @ np.asarray(p["idx_wq"][1])).reshape(5, 4, 16)
    np.testing.assert_allclose(np.asarray(q)[0][..., 8:], qraw[..., 8:], atol=2e-5)
    ang = pos[0, 2] * 10000.0 ** (-2 * 1 / 8)                     # token 2, pair 1
    np.testing.assert_allclose(
        np.asarray(q)[0, 2, 3, 2],
        qraw[2, 3, 2] * np.cos(ang) - qraw[2, 3, 3] * np.sin(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(w)[0], h[0] @ np.asarray(p["idx_ww"][1]),
                               atol=2e-5)


def test_a_negative_head_weight_lowers_a_keys_rank():
    """``I = sum_g w_g ReLU(q_g . k)``: the sign of ``w`` is outside the
    ReLU. One head, one query: with ``w = +1`` the key of the largest dot
    product ranks first, with ``w = -1`` last among the positive ones, and
    keys of negative dot products score exactly 0 either way."""
    from dynamo_tpu.ops import dsa

    k = jnp.asarray([[[1.0, 0.0], [3.0, 0.0], [-2.0, 0.0], [2.0, 0.0]]])
    q = jnp.asarray([[[[1.0, 0.0]]]])                              # [1, 1, 1, 2]
    start, ctx = jnp.asarray([3]), jnp.asarray([4])
    for sign, best, worst in ((1.0, 1, 2), (-1.0, 2, 1)):
        s = np.asarray(dsa.index_scores_xla(
            q, jnp.asarray([[[sign]]]), k, start, ctx))[0, 0]
        np.testing.assert_allclose(s, sign * np.array([1.0, 3.0, 0.0, 2.0]))
        assert int(np.argmax(s)) == best and int(np.argmin(s)) == worst
        top2 = np.asarray(dsa.select_topk_xla(jnp.asarray(s)[None, None], 2))[0, 0]
        assert top2.tolist() == ([0, 1, 0, 1] if sign > 0 else [1, 0, 1, 0])


# -- the step ---------------------------------------------------------------------------


MLA_MIXER = glm.hybrid.mla_mixer


def prefill_then_decode(cfg, p, kernels, monkeypatch, dtype=jnp.float32, steps=4,
                        lens=(27, 14), dense=False, record=None):
    """Two rows of unequal length and a garbage row: one prefill
    rectangle, then ``steps`` decode steps (row 0 crosses from table
    column 3 into column 4 at position 32); the logits of each.
    ``record``: a list that receives every layer's marks. ``dense``: the
    indexer taken out — the mixer is handed no selection, so every cached
    key is attended (the control lives here, not on the served forward)."""
    monkeypatch.setattr(glm, "kernels_active", lambda: kernels)
    monkeypatch.setattr(
        glm.hybrid, "mla_mixer",
        (lambda *a, **kw: MLA_MIXER(*a, **{**kw, "select": None})) if dense
        else MLA_MIXER)
    if record is not None:
        inner = glm.select_keys

        def recording(*a, **kw):
            sel, index_k = inner(*a, **kw)
            record.append(np.asarray(sel))
            return sel, index_k

        monkeypatch.setattr(glm, "select_keys", recording)
    lens = list(lens)
    pages, counts = glm.init_cache(cfg, 10, BS, dtype=dtype)
    T = 32
    t, pos = np.zeros((3, T), np.int32), np.zeros((3, T), np.int32)
    sm = np.zeros((3, T), np.int32)
    toks = np.random.default_rng(7).integers(0, 256, (2, 40)).astype(np.int32)
    for r, n in enumerate(lens):
        t[r, :n], pos[r, :n] = toks[r, :n], np.arange(n)
        sm[r, :n] = [TABLES[r, i // BS] * BS + i % BS for i in range(n)]
    logits, pages, counts = glm.forward(
        cfg, p, pages, counts, t, pos, sm.reshape(-1), TABLES,
        np.array(lens + [0], np.int32),
        np.array([lens[0] - 1, lens[1] - 1, 0], np.int32), BS)
    outs = [np.asarray(logits[:2], np.float32)]
    for step in range(steps):
        cur = [n + step for n in lens]
        t1 = np.array([[toks[0, cur[0]]], [toks[1, cur[1]]], [0]], np.int32)
        p1 = np.array([[cur[0]], [cur[1]], [0]], np.int32)
        s1 = np.array([TABLES[r, c // BS] * BS + c % BS
                       for r, c in enumerate(cur)] + [0], np.int32)
        logits, pages, counts = glm.forward(
            cfg, p, pages, counts, t1, p1, s1, TABLES,
            np.array([c + 1 for c in cur] + [0], np.int32),
            np.zeros((3,), np.int32), BS)
        outs.append(np.asarray(logits[:2], np.float32))
    return np.stack(outs), toks, lens, np.asarray(counts["counts"]), pages


def reference_at(cfg, p, toks, lens, steps, dense=False):
    """The reference's logits at the same positions [1 + steps, 2, V] and
    its selections a row [L, n + steps, n + steps]."""
    out = np.zeros((1 + steps, 2, cfg.vocab_size), np.float32)
    masks = []
    for r, n in enumerate(lens):
        logits, mask = ref.forward(cfg, p, jnp.asarray(toks[r:r + 1, :n + steps]),
                                   dense=dense, return_selected=True)
        out[:, r] = np.asarray(logits)[0, n - 1:n + steps]
        masks.append(np.asarray(mask)[:, 0])
    return out, masks


def selections_agree(record, masks, lens, steps, layers) -> tuple[int, int]:
    """(keys on which program and reference agree, keys compared): the
    program's marks are in TABLE order, which for these rows is position
    order; call 0 is the prefill, the others one decode step each."""
    same = total = 0
    for call in range(1 + steps):
        for layer in range(layers):
            marks = record[call * layers + layer]
            for r, n in enumerate(lens):
                if call == 0:
                    got, want = marks[r, :n, :n] > 0.5, masks[r][layer][:n, :n]
                else:
                    p_abs = n + call - 1
                    got = marks[r, 0, :p_abs + 1] > 0.5
                    want = masks[r][layer][p_abs, :p_abs + 1]
                same += int((got == want).sum())
                total += got.size
    return same, total


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_prefill_and_decode_meet_the_reference_with_the_selected_sets_equal(
        kernels, monkeypatch):
    """float32 end to end, through the gathered XLA forms and through the
    four Pallas kernels (interpreted here: index score, exact top k, the
    masked page walk in prefill and in decode): row 0's 27 tokens have up
    to 27 keys behind a query of which 12 are attended, its decode steps
    cross from table column 3 into column 4 (position 32); row 1 crosses
    12 keys inside its prompt. Logits to 2e-4 — summation order — and
    every selection of every layer the reference's, key for key."""
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9, dtype=jnp.float32)
    record: list = []
    got, toks, lens, counts, _ = prefill_then_decode(
        cfg, p, kernels, monkeypatch, steps=6, record=record)
    want, masks = reference_at(cfg, p, toks, lens, 6)
    np.testing.assert_allclose(got, want, atol=2e-4)
    same, total = selections_agree(record, masks, lens, 6, 3)
    assert same == total and total > 3000
    named = dict(zip(glm.COUNT_NAMES, counts.tolist()))
    assert named["dsa_calls"] == 3 * 7 and named["moe_layer_calls"] == 2 * 7
    # six decode steps, three layers, contexts 28..33 and 15..20
    assert named["dsa_decode_scored"] == 3 * (sum(range(28, 34)) + sum(range(15, 21)))
    assert named["dsa_decode_selected"] == 3 * 12 * 12


def test_prefill_counts_are_by_position(monkeypatch):
    """A token at position p scores p + 1 keys and attends min(12, p + 1)."""
    monkeypatch.setattr(glm, "PAIR_UNIT", 1)
    c = np.asarray(glm.dsa_counts(
        jnp.asarray([0, 5, 20, 0]), jnp.asarray([27, 9, 4, 0]),
        jnp.asarray([27, 14, 24, 0]), 32, 3, 12))
    scored = sum(range(1, 28)) + sum(range(6, 15)) + sum(range(21, 25))
    picked = sum(min(12, x) for x in (*range(1, 28), *range(6, 15), *range(21, 25)))
    assert c.tolist() == [3 * scored, 3 * picked, 0, 0, 3]


def test_a_context_within_the_top_k_is_dense_latent_attention(monkeypatch):
    """At most ``index_topk`` = 12 keys behind every query: the selection
    is every key, through the same code, and the logits are those of the
    family with the indexer taken out — bit for bit."""
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9, dtype=jnp.float32)
    sparse, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=2, lens=(10, 7))
    dense, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=2, lens=(10, 7),
                                    dense=True)
    np.testing.assert_array_equal(sparse, dense)


def test_a_context_above_the_top_k_is_not_dense_and_matches_the_reference(monkeypatch):
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9, dtype=jnp.float32)
    sparse, toks, lens, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=2)
    dense, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=2, dense=True)
    assert np.abs(sparse - dense).max() > 0.5          # the selection matters
    want, _ = reference_at(cfg, p, toks, lens, 2)
    want_dense, _ = reference_at(cfg, p, toks, lens, 2, dense=True)
    np.testing.assert_allclose(sparse, want, atol=2e-4)
    np.testing.assert_allclose(dense, want_dense, atol=2e-4)


def test_bfloat16_stays_within_its_rounding_and_most_selections_agree(monkeypatch):
    """bf16 matrices, operands, both page planes AND logits under a
    float32 residual stream, against the float32 reference of the SAME
    bf16-rounded weights. Stated tolerances, with their reasons: the
    MEDIAN over the 8 compared (step, row) positions of the mean logit
    error is under 0.02 — ``deepseek_v3``'s bound: a few roundings of 2**-8
    in quadrature over 3 layers, what a position shows whose selections
    all agree (measured 0.006-0.016); the mean over ALL positions is under
    0.2 and no logit is off by more than 3 — a key ranked 12th in float32
    and 13th in bfloat16 changes one of only 12 attended keys at this
    size, which moves that one position like a router flip does, by ten
    to fifty times the rounding (measured 0.04-0.11 / 0.5-2.5 over seeds
    9-11; at the published 2 048 keys a flip is one key of 2 048). Beside
    it the share of (query, key) marks that agree with the reference's
    selection is asserted: at least 99% (measured 99.4-99.8%)."""
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9)                    # bf16 matrices
    record: list = []
    got, toks, lens, _, _ = prefill_then_decode(
        cfg, p, False, monkeypatch, dtype=jnp.bfloat16, steps=3, record=record)
    want, masks = reference_at(cfg, p, toks, lens, 3)
    err = np.abs(got - want)
    assert np.median(err.mean(-1)) < 0.02
    assert err.mean() < 0.2 and err.max() < 3.0
    same, total = selections_agree(record, masks, lens, 3, 3)
    assert 0.99 < same / total


def test_right_padding_and_a_garbage_row_do_not_move_the_logits(monkeypatch):
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9, dtype=jnp.float32)
    base, toks, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=1)
    n = 27
    pages, counts = glm.init_cache(cfg, 10, BS, dtype=jnp.float32)
    sm = np.array([(1 + i // BS) * BS + i % BS for i in range(n)], np.int32)
    logits, *_ = glm.forward(
        cfg, p, pages, counts, toks[:1, :n], np.arange(n)[None], sm,
        np.array([[1, 2, 3, 4]], np.int32), np.array([n], np.int32),
        np.array([n - 1], np.int32), BS)
    np.testing.assert_allclose(np.asarray(logits)[0], base[0, 0], atol=2e-5)


def test_both_planes_are_written_at_the_same_slots(monkeypatch):
    cfg = tiny_glm()
    p = glm.init_params(cfg, seed=9, dtype=jnp.float32)
    *_, pages = prefill_then_decode(cfg, p, False, monkeypatch, steps=1)
    assert set(pages) == {"latent", "index_k"}
    assert pages["latent"].shape == (3, 80, 128) and pages["index_k"].shape == (3, 80, 16)
    written = {name: np.nonzero(np.abs(np.asarray(plane)).sum((0, 2)))[0].tolist()
               for name, plane in pages.items()}
    # row 0: 28 tokens from slot 8; row 1: 15 from slot 40; the garbage row's slot 0
    want = sorted({0, *range(8, 36), *range(40, 55)})
    assert written["latent"] == want and written["index_k"] == want


# -- the expert share ---------------------------------------------------------------------


def test_the_shards_shares_add_up_to_the_uncut_layer():
    """The guide's section 4: the router scores all 8 experts and takes
    its top 2 among all of them; each of 4 shards computes its own 2
    experts' part; the four parts, with the shared MLP counted once, add
    up to what the uncut reference gives for the whole layer."""
    whole_cfg = tiny_glm()
    p = glm.init_params(whole_cfg, seed=11, dtype=jnp.float32)
    w = ref.dequantized(p)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 9, 64)), jnp.float32)
    want = np.asarray(ref.expert_ffn(whole_cfg, w, 1, x))
    shared = np.asarray(ref.gated_mlp(
        x.reshape(18, 64), w["ws_gate"][1], w["ws_up"][1], w["ws_down"][1])
    ).reshape(2, 9, 64)
    total = np.zeros_like(want)
    for shard in range(4):
        cfg = tiny_glm(n_routed_experts=2, expert_shards=4, expert_shard_index=shard)
        g = glm.Geometry(cfg)
        assert (g.E, g.E_all, g.e0) == (2, 8, 2 * shard)
        held = {name: (p[name][:, 2 * shard:2 * shard + 2]
                       if name.startswith("we_") else p[name]) for name in p}
        out, counts = ds.moe_ffn(cfg, g, held, x, 1)
        # the reference given the same share says the same
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.expert_ffn(cfg, ref.dequantized(held), 1, x)),
            atol=2e-5)
        total += np.asarray(out) - shared
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    assert list(glm.param_shapes(cfg)["router"][0]) == [2, 64, 8]
    assert list(glm.param_shapes(cfg)["we_up"][0]) == [2, 2, 64, 32]
