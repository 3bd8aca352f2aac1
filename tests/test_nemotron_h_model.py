"""Nemotron-H below the engine: the layer mathematics of
``models/nemotron_h.py`` against the plain reference
(``models/reference/nemotron_h.py``) — prefill then decode through the
cache on logits, the chunked and the kernel form of the Mamba-2
recurrence, the pieces of the mixer one at a time, the shares of a
divided expert layer, the ``-`` layer, the seeded recipe and the family
lookup. (What ``models/hybrid.py``'s edit leaves of the two delta-rule
families' step programs: ``tests/test_hybrid_expert_form.py``.)"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, family, hybrid
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.reference import kimi_linear as kimi_ref
from dynamo_tpu.models.reference import nemotron_h as ref
from tests.nemotron_h_tiny import tiny_nemotron_h

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- configuration and lookup ------------------------------------------------
def published():
    with open(os.path.join(REPO, "perf", "configs", "nemotron-3-nano-30b.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses_into_layer_kinds():
    cfg, raw = published()
    g = nh.Geometry(cfg)
    assert family(cfg) is nh and cfg.has_recurrent_state and nh.RECURRENT_STATE
    assert cfg.layer_letters() == list("MEMEM*EMEMEM*")
    assert [kind for kind, _ in g.plan] == [
        "ssm", "moe", "ssm", "moe", "ssm", "attn", "moe", "ssm", "moe", "ssm",
        "moe", "ssm", "attn"]
    assert [i for _, i in g.plan] == [0, 0, 1, 1, 2, 0, 2, 3, 3, 4, 4, 5, 1]
    assert (g.E, g.E_all, g.e0, g.k) == (128, 128, 0, 6)
    assert (g.H, g.Hk, g.Dh) == (32, 2, 128)
    assert (g.Hm, g.dm, g.N, g.G, g.inner, g.conv) == (64, 64, 128, 8, 4096, 6144)
    assert (g.Fe, g.Fs, g.F) == (1856, 3712, 1856)
    assert cfg.rms_norm_eps == 1e-5                        # norm_eps, by its own key
    assert cfg.vocab_size == 131072 and cfg.max_position_embeddings == 262144
    assert raw["published"]["num_hidden_layers"] == 52
    assert raw["published"]["hybrid_override_pattern"].startswith("MEMEM*EMEMEM*")
    # one whole period of the published mix, and four layers after it
    period = raw["published"]["hybrid_override_pattern"][:9]
    assert sorted(period) == sorted("MMMMEEEE*")


def test_weights_state_and_pages_are_what_the_issue_reckoned():
    cfg, _ = published()
    shapes = nh.param_shapes(cfg)
    total = sum(int(np.prod(shape)) for name, (shape, _) in shapes.items()
                if name in nh.QUANT_AXIS)
    assert 7.4e9 < total < 7.55e9                           # 7.47 GB at int8
    assert int(np.prod(shapes["we_up"][0][2:])) * 2 == 2 * 2688 * 1856   # 9.98M an expert
    assert shapes["m_win"][0] == (6, 2688, 10240) and shapes["m_wdt"][0] == (6, 2688, 64)
    # a slot: 64 x 64 x 128 float32 + the float32 tail (3 rows of 6144)
    assert nh.state_bytes(cfg, 65, 2) / 65 / 6 == 64 * 64 * 128 * 4 + 3 * 6144 * 4
    assert 0.84e9 < nh.state_bytes(cfg, 65, 2) < 0.86e9
    # a token: K and V of 2 heads of 128 in 2 layers, bf16 = 2 KB
    assert nh.page_bytes_per_block(cfg, 128, 2) == 128 * 2048
    pages, state = nh.cache_shapes(cfg, 10, 128, 65)
    assert pages["k"] == pages["v"] == (2, 1280 * 2, 128)   # (token, head) rows
    assert state["ssm"] == (6, 65, 64, 64, 128)              # the state size minor
    # a slot's 3 tail rows in rows of one lane tile: ONE block of whole tiles
    assert state["conv"] == (6, 65, 3 * 6144 // 128, 128) and 6144 // 128 % 8 == 0


def test_the_family_is_found_by_its_name():
    assert family(ModelConfig(model_type="nemotron_h")) is nh
    with pytest.raises(LookupError) as err:
        family(ModelConfig(model_type="nemotron-x"))
    assert "nemotron_h" in str(err.value)


@pytest.mark.parametrize("bad", [
    dict(hybrid_override_pattern="MEM*E"),                  # five letters, six layers
    dict(hybrid_override_pattern="MEM*EX"),
    dict(mamba_num_heads=7),                                # no multiple of the groups
    dict(use_conv_bias=False),
    dict(conv_kernel=1),
    dict(num_key_value_heads=3),
    dict(mlp_hidden_act="silu"),
    dict(attention_bias=True),
    dict(mamba_proj_bias=True),
    dict(n_group=2),
    dict(n_shared_experts=2),
    dict(num_experts_per_tok=9),
])
def test_what_is_not_built_is_refused_when_the_shapes_are_made(bad):
    with pytest.raises(ValueError):
        nh.param_shapes(tiny_nemotron_h(**bad))


def test_a_pattern_of_some_kinds_makes_only_their_parameters():
    shapes = nh.param_shapes(tiny_nemotron_h(
        hybrid_override_pattern="MM-", num_hidden_layers=3))
    assert "m_win" in shapes and "w_up" in shapes
    assert not {"attn_wq", "router", "we_up"} & set(shapes)


def test_the_seeded_recipe():
    cfg = tiny_nemotron_h()
    p = nh.init_params_quantized(cfg, seed=2**31 + 5)
    a = np.exp(np.asarray(p["m_A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.log1p(np.exp(np.asarray(p["m_dt_bias"])))        # softplus
    assert dt.min() >= 0.999e-3 and dt.max() <= 1.001e-1
    for name in ("norm", "final_norm", "m_onorm", "m_D"):
        assert np.all(np.asarray(p[name]) == 1.0)
    for name in ("router_bias", "m_conv_bias"):
        assert np.all(np.asarray(p[name]) == 0.0)
    for name in ("router", "m_conv"):
        assert p[name].dtype == jnp.float32 and name + "_scale" not in p
    for name in nh.QUANT_AXIS:
        assert p[name].dtype == jnp.int8 and name + "_scale" in p, name
    f = nh.init_params(cfg, seed=2**31 + 5, dtype=jnp.float32)
    w = np.asarray(f["we_up"][1, 3])
    q = np.asarray(p["we_up"][1, 3], np.float32) * np.asarray(p["we_up_scale"][1, 3])
    assert np.abs(w - q).max() <= np.abs(w).max(0).max() / 127
    # the floor holds a step that the range would let lower
    low = hybrid.draw_dt_bias(jax.random.PRNGKey(0), (64,), 1e-6, 1e-5, 1e-4)
    np.testing.assert_allclose(np.log1p(np.exp(np.asarray(low))), 1e-4, rtol=1e-3)


# -- the state-space recurrence ------------------------------------------------------
def ssm_inputs(B, T, H, P, G, N, seed=0, pad_from=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, T, H, P)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, size=(B, T, H)).astype(np.float32)
    A = rng.uniform(1.0, 16.0, size=(H,)).astype(np.float32)
    if pad_from is not None:
        for b, n in enumerate(pad_from):
            dt[b, n:] = 0.0
    Bm, C = (rng.normal(size=(B, T, G, N)).astype(np.float32) for _ in range(2))
    S = rng.normal(size=(B, H, P, N)).astype(np.float32)
    return tuple(map(jnp.asarray, (x, dt, -A * dt, Bm, C, S)))


def token_by_token(x, dt, glog, Bm, C, S):
    outs = []
    for t in range(x.shape[1]):
        o, S = nh.ssm_decode(x[:, t], dt[:, t], glog[:, t], Bm[:, t], C[:, t], S)
        outs.append(o)
    return jnp.stack(outs, axis=1), S


def plain_update(x, dt, glog, Bm, C, S):
    """The recurrence written out in numpy, a head at a time."""
    x, dt, glog, Bm, C, S = (np.asarray(a, np.float64) for a in (x, dt, glog, Bm, C, S))
    B, H, P = x.shape
    rep = H // Bm.shape[1]
    y = np.zeros((B, H, P))
    for b in range(B):
        for h in range(H):
            S[b, h] = np.exp(glog[b, h]) * S[b, h] + np.outer(
                dt[b, h] * x[b, h], Bm[b, h // rep])
            y[b, h] = S[b, h] @ C[b, h // rep]
    return y, S


def test_the_decode_rule_is_the_recurrence_written_out():
    x, dt, glog, Bm, C, S = ssm_inputs(2, 1, 8, 4, 2, 16)
    y, S2 = nh.ssm_decode(x[:, 0], dt[:, 0], glog[:, 0], Bm[:, 0], C[:, 0], S)
    y_want, S_want = plain_update(x[:, 0], dt[:, 0], glog[:, 0], Bm[:, 0], C[:, 0], S)
    np.testing.assert_allclose(y, y_want, atol=1e-4)
    np.testing.assert_allclose(S2, S_want, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 8, 16, 32])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk):
    """Matrix products in blocks of ``chunk`` tokens against one update a
    token: float32 summation order alone (<= 2e-4 on outputs of size ~10)."""
    args = ssm_inputs(2, 32, 8, 4, 2, 16)
    y, S = nh.ssd_chunked(*args, chunk=chunk)
    y_want, S_want = token_by_token(*args)
    assert np.abs(np.asarray(y_want)).max() > 3.0
    np.testing.assert_allclose(y, y_want, atol=2e-4)
    np.testing.assert_allclose(S, S_want, atol=2e-4)


def test_the_chunked_scan_survives_underflow_and_ignores_padding():
    x, dt, glog, Bm, C, S = ssm_inputs(1, 64, 4, 4, 2, 16, seed=3)
    glog = jnp.full_like(glog, -8.0)          # e^{-512} over the block: 0, never inf
    y, S2 = nh.ssd_chunked(x, dt, glog, Bm, C, S, chunk=64)
    y_want, S_want = token_by_token(x, dt, glog, Bm, C, S)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, y_want, atol=1e-5)
    np.testing.assert_allclose(S2, S_want, atol=1e-5)
    # a padded token (dt 0, so log decay 0) changes nothing
    args = ssm_inputs(2, 16, 4, 4, 2, 16, seed=1, pad_from=(5, 16))
    _, S = nh.ssd_chunked(*args, chunk=8)
    x, dt, glog, Bm, C, S0 = args
    _, S_short = token_by_token(x[:1, :5], dt[:1, :5], glog[:1, :5], Bm[:1, :5],
                                C[:1, :5], S0[:1])
    np.testing.assert_allclose(S[0], S_short[0], atol=1e-4)


@pytest.mark.parametrize("heads,groups", [(16, 2), (32, 8), (8, 8)])
def test_the_decode_kernel_is_the_plain_update(heads, groups):
    """``ssm_decode_update`` (interpret mode) on a plane of several
    layers: the rows' slots of ONE layer change, a ``fresh`` row reads
    zeros whatever its slot held, padded rows meet in slot 0."""
    from dynamo_tpu.ops.ssm import ssm_decode_update

    rng = np.random.default_rng(0)
    Lm, S, P, N, B = 2, 6, 8, 128, 4
    plane = rng.normal(size=(Lm, S, heads, P, N)).astype(np.float32)
    x, dt, glog, Bm, C, _ = ssm_inputs(B, 1, heads, P, groups, N, seed=2)
    slots = jnp.asarray([2, 4, 0, 0], jnp.int32)           # two padded rows
    fresh = jnp.asarray([0, 1, 0, 1], jnp.int32)
    S0 = jnp.where(fresh[:, None, None, None] != 0, 0.0, jnp.asarray(plane)[1, slots])
    y_want, S_want = nh.ssm_decode(
        x[:, 0], dt[:, 0], glog[:, 0], Bm[:, 0], C[:, 0], S0)
    y, new = ssm_decode_update(
        jnp.asarray(plane), 1, slots, fresh, x[:, 0], dt[:, 0],
        jnp.exp(glog[:, 0]), Bm[:, 0], C[:, 0], interpret=True)
    np.testing.assert_allclose(y, y_want, atol=2e-3)
    np.testing.assert_allclose(new[1, slots[:2]], S_want[:2], atol=1e-4)
    untouched = np.ones((Lm, S), bool)
    untouched[1, [0, 2, 4]] = False
    assert np.array_equal(np.asarray(new)[untouched], plane[untouched])


# -- the expert layer ------------------------------------------------------------
def test_routing_is_sigmoid_scores_chosen_with_the_bias_weighted_without_it():
    cfg = tiny_nemotron_h()
    p = nh.init_params(cfg, seed=1, dtype=jnp.float32)
    bias = np.zeros((2, 8), np.float32)
    bias[1, 5] = 10.0                                       # expert 5 is always chosen
    p = dict(p, router_bias=jnp.asarray(bias))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(11, 64)).astype(np.float32))
    w, topi = nh.moe_routing(cfg, p, x, 1)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"][1]))))
    want_i = np.argsort(-(s + bias[1]), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(want_i, -1))
    assert (np.asarray(topi) == 5).any(-1).all()
    chosen = np.take_along_axis(s, np.asarray(topi), -1)    # the scores WITHOUT the bias
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
    plain, _ = nh.moe_routing(tiny_nemotron_h(norm_topk_prob=False), p, x, 1)
    np.testing.assert_allclose(plain, chosen * 2.5, rtol=1e-5)


def halves_of(p_whole):
    """The two shares of an 8-expert layer: 4 experts each, the whole
    router, everything else alike."""
    out = []
    for shard in (0, 1):
        cfg = tiny_nemotron_h(n_routed_experts=4, expert_shards=2,
                              expert_shard_index=shard)
        p = dict(p_whole)
        for name in ("we_up", "we_down"):
            p[name] = p_whole[name][:, 4 * shard: 4 * shard + 4]
        out.append((cfg, p))
    return out


@pytest.mark.parametrize("form", ["one-block", "blocks", "ragged-blocks"])
def test_the_shares_of_a_divided_expert_layer_add_up_to_the_whole(form, monkeypatch):
    """Model-configs guide, section 4: shard 0's and shard 1's routed
    parts, with the shared expert counted once, are what the uncut
    reference gives for the whole layer — however the tokens fall into
    blocks (all at once, whole blocks, a last block padded out: no
    bucketed step has one, and its padding must count nothing).
    float32: <= 3e-5 on outputs of size ~1."""
    tokens = (2, 9) if form == "one-block" else (3, 40)
    if form == "blocks":
        monkeypatch.setattr(nh, "MOE_DENSE_BLOCK", 40)       # 120 tokens: 3 blocks
    if form == "ragged-blocks":
        monkeypatch.setattr(nh, "MOE_DENSE_BLOCK", 50)       # 120 tokens: 50 50 20
    whole = tiny_nemotron_h()
    p = nh.init_params(whole, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(*tokens, 64)).astype(np.float32))
    w32 = kimi_ref.dequantized(p)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn(whole, w32, 1, h)
    parts, routed, seen = [], [], []
    for cfg, ps in halves_of(p):
        g = nh.Geometry(cfg)
        assert (g.E, g.E_all, g.e0) == (4, 8, 4 * cfg.expert_shard_index)
        out, counts = nh.moe_ffn(cfg, g, ps, h, 1)
        parts.append(out)
        routed.append(nh.moe_ffn(cfg, g, ps, h, 1, shared=False)[0])
        seen.append(np.asarray(counts))
    shared = parts[0] - routed[0]
    np.testing.assert_allclose(shared, parts[1] - routed[1], atol=2e-5)
    assert np.abs(np.asarray(shared)).max() > 1e-3
    np.testing.assert_allclose(routed[0] + routed[1] + shared, want, atol=3e-5)
    for (cfg, ps), part in zip(halves_of(p), parts):
        with jax.default_matmul_precision("highest"):
            own = ref.expert_ffn(cfg, kimi_ref.dequantized(ps), 1, h)
        np.testing.assert_allclose(part, own, atol=3e-5)
    n = tokens[0] * tokens[1]
    assert seen[0][1] + seen[1][1] == n * whole.num_experts_per_tok
    blocks = 1 if form == "one-block" else 3
    assert seen[0][0] == seen[1][0] == blocks
    assert blocks <= seen[0][2] <= 4 * blocks and blocks <= seen[1][2] <= 4 * blocks


def test_padding_is_not_counted_as_expert_traffic():
    cfg = tiny_nemotron_h()
    p = nh.init_params(cfg, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 6, 64)).astype(np.float32))
    valid = jnp.asarray([[1, 1, 1, 0, 0, 0], [1, 0, 0, 0, 0, 0]], bool)
    _, counts = nh.moe_ffn(cfg, nh.Geometry(cfg), p, h, 0, valid)
    assert counts.tolist()[:2] == [1, 4 * cfg.num_experts_per_tok]


@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_the_two_forms_of_a_two_matrix_expert_agree(quantized):
    """``hybrid.moe_local`` hands either of its forms the expert's form:
    rows sorted by expert through ``ragged_dot`` give what every expert
    over every row gives, for ``RELU2`` as for the gated form. (This
    family runs the second alone: PERF.md, PR 37.)"""
    cfg = tiny_nemotron_h()
    p = (nh.init_params_quantized(cfg, seed=3) if quantized
         else nh.init_params(cfg, seed=3, dtype=jnp.float32))
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 64)).astype(np.float32))
    w, topi = nh.moe_routing(cfg, p, x, 0)
    every, _ = hybrid.moe_local(p, x, w, topi, 0, 0, 8, 24, form=hybrid.RELU2)
    by_rows, _ = hybrid.moe_local(p, x, w, topi, 0, 0, 8, 8, form=hybrid.RELU2)
    assert np.abs(np.asarray(every)).max() > 1e-2
    np.testing.assert_allclose(every, by_rows, atol=1e-4)


def test_the_two_matrix_forms_are_relu_squared():
    x = jnp.asarray([[-2.0, 0.0, 0.5, 3.0]])
    np.testing.assert_allclose(hybrid.relu2(x), [[0.0, 0.0, 0.25, 9.0]])
    assert hybrid.RELU2.reads == ("we_up",) and hybrid.RELU2.down == "we_down"
    assert hybrid.GATED_SILU.reads == ("we_gate", "we_up")
    cfg = tiny_nemotron_h()
    p = nh.init_params(cfg, seed=3, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 64)).astype(np.float32))
    got = hybrid.relu2_mlp(p, ("w_up", "w_down"), h, 0)
    want = np.square(np.maximum(np.asarray(h) @ np.asarray(p["w_up"][0]), 0)) \
        @ np.asarray(p["w_down"][0])
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- the whole step against the whole reference ----------------------------------
BS = 8
TABLES = np.array([[1, 2, 3, 4, 2], [5, 6, 7, 0, 1], [0, 0, 0, 0, 0]], np.int32)


def prefill(cfg, p, pages, state, toks, lens, starts=(0, 0), T=32):
    """One prefill rectangle: row r holds tokens starts[r] ... lens[r]-1."""
    t, pos = np.zeros((3, T), np.int32), np.zeros((3, T), np.int32)
    sm = np.zeros((3, T), np.int32)
    last = np.zeros((3,), np.int32)
    for r, (a, n) in enumerate(zip(starts, lens)):
        t[r, :n - a], pos[r, :n - a] = toks[r, a:n], np.arange(a, n)
        sm[r, :n - a] = [TABLES[r, i // BS] * BS + i % BS for i in range(a, n)]
        last[r] = n - a - 1
    return nh.forward(cfg, p, pages, state, t, pos, sm.reshape(-1), TABLES,
                      np.array(list(lens) + [0], np.int32), last, BS)


def decode(cfg, p, pages, state, toks, cur):
    t1 = np.array([[toks[0, cur[0]]], [toks[1, cur[1]]], [0]], np.int32)
    p1 = np.array([[cur[0]], [cur[1]], [0]], np.int32)
    s1 = np.array([TABLES[r, c // BS] * BS + c % BS
                   for r, c in enumerate(cur)] + [0], np.int32)
    return nh.forward(cfg, p, pages, state, t1, p1, s1, TABLES,
                      np.array([c + 1 for c in cur] + [0], np.int32),
                      np.zeros((3,), np.int32), BS)


def through_the_cache(cfg, p, toks, lens, steps, split=None, poison=0.0,
                      dtype=jnp.float32):
    """Prefill (in two rectangles when ``split``: the state and the tail
    cross a chunk through the plane), then ``steps`` decode steps.
    Returns the logits [steps + 1, 2, V]."""
    pages, state = nh.init_cache(cfg, 8, BS, dtype=dtype, state_slots=3)
    state["ssm"] = state["ssm"] + poison
    state["conv"] = state["conv"] + poison
    if split:
        _, pages, state = prefill(cfg, p, pages, state, toks, split)
        logits, pages, state = prefill(cfg, p, pages, state, toks, lens, starts=split)
    else:
        logits, pages, state = prefill(cfg, p, pages, state, toks, lens)
    outs = [np.asarray(logits[:2], np.float32)]
    for step in range(steps):
        logits, pages, state = decode(cfg, p, pages, state, toks,
                                      [n + step for n in lens])
        outs.append(np.asarray(logits[:2], np.float32))
    return np.stack(outs), state


def reference_logits(cfg, p, toks, lens, steps, **switches):
    full = np.asarray(ref.forward(cfg, p, jnp.asarray(toks), **switches))
    return np.stack([np.stack([full[r, n - 1 + s] for r, n in enumerate(lens)])
                     for s in range(steps + 1)])


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_nemotron_h()
    p = nh.init_params(cfg, seed=9, dtype=jnp.float32)
    # the draw's bias 0 and D 1 would hide a dropped bias or skip
    rng = np.random.default_rng(5)
    p = dict(p, m_conv_bias=jnp.asarray(rng.normal(size=p["m_conv_bias"].shape) * 0.5,
                                        jnp.float32),
             m_D=jnp.asarray(rng.uniform(0.5, 2.0, size=p["m_D"].shape), jnp.float32),
             m_onorm=jnp.asarray(rng.uniform(0.5, 1.5, size=p["m_onorm"].shape),
                                 jnp.float32))
    toks = rng.integers(0, 256, (2, 30)).astype(np.int32)
    return cfg, p, toks


# float32 activations: program and reference differ by summation order alone,
# 2e-6 measured on logits of size ~4; with bf16 operands in the program's
# place the same comparison reads 3e-2 (the last test of this file)
TOL = 1e-4


@pytest.mark.parametrize("split", [None, (8, 5)], ids=["one-chunk", "crosses-a-chunk"])
def test_prefill_then_decode_through_the_cache_is_the_reference_on_logits(tiny, split):
    """A reused slot (poisoned) starts from zeros at position 0; the
    prompt of the split case crosses two prefill rectangles, its state and
    tail carried by the plane."""
    cfg, p, toks = tiny
    lens, steps = (19, 11), 4
    got, state = through_the_cache(cfg, p, toks, lens, steps, split, poison=7.0)
    want = reference_logits(cfg, p, toks, lens, steps)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)
    counts = state["counts"].tolist()
    assert counts[0] == 2 * (steps + 1 + bool(split))       # two expert layers a step
    assert counts[3] == sum(lens)                  # real tokens through the chunked scan
    assert counts[4] == 5                # and their blocks of 8: 3 + 2 | 1 + 1, 2 + 1


def test_a_recomputed_row_is_exact(tiny):
    """Preemption gives the slot back; the row is recomputed from its
    tokens (prompt + what it had generated) in whatever slot it gets."""
    cfg, p, toks = tiny
    first, _ = through_the_cache(cfg, p, toks, (19, 11), 3)
    again, _ = through_the_cache(cfg, p, toks, (22, 14), 0, poison=3.0)
    np.testing.assert_allclose(again[0], first[3], atol=TOL)


@pytest.mark.parametrize("piece", ["conv_bias", "skip", "gate", "attn_scale"])
def test_each_piece_of_the_mixers_is_in_the_program(tiny, piece):
    """The program meets the reference (above); with one piece switched
    off IN THE REFERENCE ONLY it must not."""
    cfg, p, toks = tiny
    lens = (19, 11)
    got, _ = through_the_cache(cfg, p, toks, lens, 1)
    without = reference_logits(cfg, p, toks, lens, 1, **{piece: False})
    assert np.abs(got - without).max() > 1e-2


def test_the_step_through_the_kernels_is_the_plain_step(tiny, monkeypatch):
    """Decode and prefill with the Pallas kernels (interpreted here): the
    Mamba-2 update in place on the state plane and the paged-attention
    kernels at this family's heads give what the XLA forms give."""
    cfg, p, toks = tiny
    plain, plain_state = through_the_cache(cfg, p, toks, (19, 11), 2, split=(8, 5))
    monkeypatch.setattr(hybrid, "kernels_active", lambda: True)
    kern, kern_state = through_the_cache(cfg, p, toks, (19, 11), 2, split=(8, 5))
    np.testing.assert_allclose(kern, plain, atol=TOL)
    np.testing.assert_allclose(kern_state["ssm"][:, 1:], plain_state["ssm"][:, 1:],
                               atol=1e-4)
    np.testing.assert_allclose(kern_state["conv"][:, 1:], plain_state["conv"][:, 1:],
                               atol=1e-5)


def test_bf16_activations_fail_the_float32_tolerance_and_stay_near(tiny):
    """The served precision (int8 weights, bf16 operands and pages)
    against the reference reading the same int8 weights in float32: what
    is left is operand rounding — far outside ``TOL`` (so a program that
    computed in bf16 where the test states float32 would fail it), and
    within a few percent of a logit's size (mean < 0.05, max < 0.3 on
    logits of ~4)."""
    cfg, _, toks = tiny
    p = nh.init_params_quantized(cfg, seed=9)
    got, _ = through_the_cache(cfg, p, toks, (19, 11), 1, dtype=jnp.bfloat16)
    err = np.abs(got - reference_logits(cfg, p, toks, (19, 11), 1))
    assert err.max() > 100 * TOL
    assert err.mean() < 0.05 and err.max() < 0.3
