"""Qwen3-Next below the engine: the layer mathematics of
``models/qwen3_next.py`` against the plain reference
(``models/reference/qwen3_next.py``) — prefill then decode through the
cache on logits, the chunked and the kernel form of the delta rule with
a decay a head, the pieces of gated attention one at a time, the shares
of a divided expert block, the seeded recipe, and the family lookup."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, family, hybrid, kimi_linear, llama
from dynamo_tpu.models import qwen3_next as qn
from dynamo_tpu.models.reference import kimi_linear as kimi_ref
from dynamo_tpu.models.reference import qwen3_next as ref
from tests import state_plane_cases as spc
from tests.qwen3_next_tiny import tiny_qwen3_next

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- configuration and lookup ------------------------------------------------
def published():
    with open(os.path.join(REPO, "perf", "configs", "qwen3-next-80b.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses_into_layer_kinds():
    cfg, raw = published()
    g = qn.Geometry(cfg)
    assert family(cfg) is qn and cfg.has_recurrent_state
    assert [g.kind_index(i)[0] for i in range(8)] == [
        "gdn", "gdn", "gdn", "attn", "gdn", "gdn", "gdn", "attn"]
    assert (g.E, g.E_all, g.e0, g.k) == (256, 512, 0, 10)
    assert (g.H, g.Hk, g.Dh, g.rot) == (16, 2, 256, 64)
    assert (g.Hlk, g.Hl, g.dk, g.dl, g.conv, g.VD) == (16, 32, 128, 128, 8192, 4096)
    assert cfg.vocab_size == 594 * 128 and raw["published"]["vocab_size"] == 1187 * 128
    assert cfg.max_position_embeddings == 262144


def test_weights_state_and_pages_are_what_the_issue_reckoned():
    cfg, _ = published()
    shapes = qn.param_shapes(cfg)
    total = sum(int(np.prod(shape)) for name, (shape, _) in shapes.items()
                if name in qn.QUANT_AXIS)
    assert 6.9e9 < total < 7.1e9
    assert int(np.prod(shapes["we_gate"][0][1:])) * 3 == 256 * 3 * 2048 * 512
    # a slot: 32 x 128 x 128 float32 + the float32 tail (3 rows of 8192)
    assert qn.state_bytes(cfg, 65, 2) / 65 / 6 == 32 * 128 * 128 * 4 + 3 * 8192 * 4
    # a token: K and V of 2 heads of 256 in 2 layers, bf16
    assert qn.page_bytes_per_block(cfg, 128, 2) == 128 * 4096
    pages, state = qn.cache_shapes(cfg, 10, 128, 65)
    assert pages["k"] == pages["v"] == (2, 1280 * 2, 256)   # (token, head) rows
    assert state["gdn"] == (6, 65, 32, 128, 128)
    # a slot's 3 tail rows in rows of one lane tile: ONE block of whole tiles
    assert state["conv"] == (6, 65, 3 * 8192 // 128, 128) and 8192 // 128 % 8 == 0


def test_a_family_is_found_by_its_name_or_an_error_names_what_exists():
    assert family(ModelConfig()) is llama
    assert family(ModelConfig(model_type="mistral")) is llama
    assert not ModelConfig(model_type="qwen2").has_recurrent_state
    assert family(ModelConfig(model_type="kimi_linear")) is kimi_linear
    assert kimi_linear.RECURRENT_STATE and qn.RECURRENT_STATE
    # a module of the package that is no family module is not one by its name
    for name in ("config", "hybrid", "no-such-model"):
        with pytest.raises(LookupError) as err:
            family(ModelConfig(model_type=name))
        for word in ("qwen3_next", "kimi_linear", "llama", "mistral", repr(name)):
            assert word in str(err.value)


@pytest.mark.parametrize("bad", [
    dict(full_attention_interval=0),
    dict(linear_num_value_heads=3),
    dict(linear_key_head_dim=8),
    dict(partial_rotary_factor=0.2),
    dict(decoder_sparse_step=2),
    dict(mlp_only_layers=[1]),
    dict(shared_expert_intermediate_size=0),
    dict(num_experts_per_tok=9),
])
def test_what_is_not_built_is_refused_when_the_shapes_are_made(bad):
    with pytest.raises(ValueError):
        qn.param_shapes(tiny_qwen3_next(**bad))


def test_the_seeded_recipe():
    cfg = tiny_qwen3_next()
    p = qn.init_params_quantized(cfg, seed=2**31 + 5)
    a = np.exp(np.asarray(p["gdn_A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.log1p(np.exp(np.asarray(p["gdn_dt_bias"])))      # softplus
    assert dt.min() >= 0.999e-3 and dt.max() <= 1.001e-1
    for name in ("attn_norm", "mlp_norm", "final_norm", "attn_qnorm", "attn_knorm"):
        assert np.all(np.asarray(p[name]) == 0.0)              # (1 + w)
    assert np.all(np.asarray(p["gdn_onorm"]) == 1.0)
    for name in ("router", "shared_gate", "gdn_conv"):
        assert p[name].dtype == jnp.float32 and name + "_scale" not in p
    for name in qn.QUANT_AXIS:
        assert p[name].dtype == jnp.int8 and name + "_scale" in p, name
    f = qn.init_params(cfg, seed=2**31 + 5, dtype=jnp.float32)
    w = np.asarray(f["we_up"][1, 3])
    q = np.asarray(p["we_up"][1, 3], np.float32) * np.asarray(p["we_up_scale"][1, 3])
    assert np.abs(w - q).max() <= np.abs(w).max(0).max() / 127


# -- the delta rule with one decay a head ----------------------------------------
def gdn_inputs(B, T, H, d, seed=0, pad_from=None):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, T, H, d)).astype(np.float32) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    glog = -rng.uniform(0.0, 2.0, size=(B, T, H, 1)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(B, T, H)).astype(np.float32)
    if pad_from is not None:
        for b, n in enumerate(pad_from):
            glog[b, n:] = 0.0
            beta[b, n:] = 0.0
    S = rng.normal(size=(B, H, d, d)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, v, glog, beta, S)))


def token_by_token(q, k, v, glog, beta, S):
    outs = []
    for t in range(q.shape[1]):
        o, S = hybrid.delta_decode(q[:, t], k[:, t], v[:, t], glog[:, t], beta[:, t], S)
        outs.append(o)
    return jnp.stack(outs, axis=1), S


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_rule_with_a_scalar_decay_is_the_token_rule(chunk):
    args = gdn_inputs(2, 32, 3, 16)
    o, S = hybrid.delta_chunked(*args, chunk=chunk)
    o_want, S_want = token_by_token(*args)
    np.testing.assert_allclose(o, o_want, atol=2e-4)
    np.testing.assert_allclose(S, S_want, atol=2e-4)
    # and it is the per-channel form given the broadcast decay
    q, k, v, glog, beta, S0 = args
    o_b, S_b = hybrid.delta_chunked(
        q, k, v, jnp.broadcast_to(glog, q.shape), beta, S0, chunk=chunk)
    np.testing.assert_allclose(o, o_b, atol=2e-4)
    np.testing.assert_allclose(S, S_b, atol=2e-4)


def test_chunked_scalar_decay_survives_underflow_and_ignores_padding():
    q, k, v, glog, beta, S = gdn_inputs(1, 64, 2, 16, seed=3)
    glog = jnp.full_like(glog, -8.0)
    o, S2 = hybrid.delta_chunked(q, k, v, glog, beta, S, chunk=64)
    o_want, S_want = token_by_token(q, k, v, glog, beta, S)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S2, S_want, atol=1e-5)
    args = gdn_inputs(2, 16, 2, 16, seed=1, pad_from=(5, 16))
    _, S = hybrid.delta_chunked(*args, chunk=8)
    q, k, v, glog, beta, S0 = args
    _, S_short = token_by_token(q[:1, :5], k[:1, :5], v[:1, :5], glog[:1, :5],
                                beta[:1, :5], S0[:1])
    np.testing.assert_allclose(S[0], S_short[0], atol=1e-4)


@pytest.mark.parametrize("name", sorted(spc.CASES))
def test_the_decode_kernel_takes_the_broadcast_decay(name):
    """``kda_decode_update`` given a head's scalar decay broadcast over
    its key channels is ``hybrid.delta_decode``, padded rows anywhere."""
    from dynamo_tpu.ops.kda import kda_decode_update

    rng = np.random.default_rng(0)
    H, d = 8, 128
    slots, fresh = spc.case(name)
    plane = rng.normal(size=(2, spc.SLOTS, H, d, d)).astype(np.float32)
    q, k, v, glog, beta, _ = gdn_inputs(len(slots), 1, H, d, seed=2)
    S0 = jnp.where(fresh[:, None, None, None] != 0, 0.0, jnp.asarray(plane)[1, slots])
    o_want, S_want = hybrid.delta_decode(
        q[:, 0], k[:, 0], v[:, 0], glog[:, 0], beta[:, 0], S0)
    o, new = kda_decode_update(
        jnp.asarray(plane), 1, slots, fresh, q[:, 0], k[:, 0], v[:, 0],
        jnp.broadcast_to(glog[:, 0], q[:, 0].shape), beta[:, 0], interpret=True)
    spc.check_rows(o, o_want, slots, atol=2e-3)
    spc.check_plane(new, plane, 1, slots, S_want, atol=1e-4)


# -- the expert block ------------------------------------------------------------
def test_routing_is_softmax_over_all_then_top_k_renormalised():
    cfg = tiny_qwen3_next()
    p = qn.init_params(cfg, seed=1, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(11, 64)).astype(np.float32))
    w, topi = qn.moe_routing(cfg, p, x, 1)
    logits = np.asarray(x) @ np.asarray(p["router"][1])
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    want_i = np.argsort(-s, axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(want_i, -1))
    chosen = np.take_along_axis(s, np.asarray(topi), -1)
    np.testing.assert_allclose(w, chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
    plain, _ = qn.moe_routing(tiny_qwen3_next(norm_topk_prob=False), p, x, 1)
    np.testing.assert_allclose(plain, chosen, rtol=1e-5)


def halves_of(p_whole):
    """The two shares of an 8-expert block: 4 experts each, the whole
    router, everything else alike."""
    out = []
    for shard in (0, 1):
        cfg = tiny_qwen3_next(num_experts=4, expert_shards=2, expert_shard_index=shard)
        p = dict(p_whole)
        for name in ("we_gate", "we_up", "we_down"):
            p[name] = p_whole[name][:, 4 * shard: 4 * shard + 4]
        out.append((cfg, p))
    return out


@pytest.mark.parametrize("tokens", [(2, 9), (3, 40)], ids=["dense-form", "grouped-form"])
def test_the_shares_of_a_divided_expert_block_add_up_to_the_whole(tokens, monkeypatch):
    """Model-configs guide, section 4: shard 0's and shard 1's routed
    parts, with the gated shared expert counted once, are what the uncut
    reference gives for the whole block."""
    monkeypatch.setattr(qn, "MOE_DENSE_TOKENS", 64)    # 120 tokens: the sorted form
    whole = tiny_qwen3_next()
    p = qn.init_params(whole, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(*tokens, 64)).astype(np.float32))
    w32 = kimi_ref.dequantized(p)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn(whole, w32, 2, h)
    parts, routed, seen = [], [], []
    for cfg, ps in halves_of(p):
        g = qn.Geometry(cfg)
        out, counts = qn.moe_ffn(cfg, g, ps, h, 2)
        parts.append(out)
        routed.append(qn.moe_ffn(cfg, g, ps, h, 2, shared=False)[0])
        seen.append(np.asarray(counts))
    shared = parts[0] - routed[0]
    np.testing.assert_allclose(shared, parts[1] - routed[1], atol=2e-5)
    assert np.abs(np.asarray(shared)).max() > 1e-3
    np.testing.assert_allclose(routed[0] + routed[1] + shared, want, atol=3e-5)
    for (cfg, ps), part in zip(halves_of(p), parts):
        with jax.default_matmul_precision("highest"):
            own = ref.expert_ffn(cfg, kimi_ref.dequantized(ps), 2, h)
        np.testing.assert_allclose(part, own, atol=3e-5)
    n = tokens[0] * tokens[1]
    assert seen[0][1] + seen[1][1] == n * whole.num_experts_per_tok
    assert 1 <= seen[0][2] <= 4 and 1 <= seen[1][2] <= 4


def test_padding_is_not_counted_as_expert_traffic():
    cfg = tiny_qwen3_next()
    p = qn.init_params(cfg, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 6, 64)).astype(np.float32))
    valid = jnp.asarray([[1, 1, 1, 0, 0, 0], [1, 0, 0, 0, 0, 0]], bool)
    _, counts = qn.moe_ffn(cfg, qn.Geometry(cfg), p, h, 0, valid)
    assert counts.tolist()[:2] == [1, 4 * cfg.num_experts_per_tok]


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
    return qn.forward(cfg, p, pages, state, t, pos, sm.reshape(-1), TABLES,
                      np.array(list(lens) + [0], np.int32), last, BS)


def decode(cfg, p, pages, state, toks, cur):
    t1 = np.array([[toks[0, cur[0]]], [toks[1, cur[1]]], [0]], np.int32)
    p1 = np.array([[cur[0]], [cur[1]], [0]], np.int32)
    s1 = np.array([TABLES[r, c // BS] * BS + c % BS
                   for r, c in enumerate(cur)] + [0], np.int32)
    return qn.forward(cfg, p, pages, state, t1, p1, s1, TABLES,
                      np.array([c + 1 for c in cur] + [0], np.int32),
                      np.zeros((3,), np.int32), BS)


def through_the_cache(cfg, p, toks, lens, steps, split=None, poison=0.0):
    """Prefill (in two rectangles when ``split``: the state and the tail
    cross a chunk through the plane), then ``steps`` decode steps.
    Returns the logits [steps + 1, 2, V]."""
    pages, state = qn.init_cache(cfg, 8, BS, dtype=jnp.float32, state_slots=3)
    state["gdn"] = state["gdn"] + poison
    state["conv"] = state["conv"] + poison
    if split:
        _, pages, state = prefill(cfg, p, pages, state, toks, split)
        logits, pages, state = prefill(cfg, p, pages, state, toks, lens, starts=split)
    else:
        logits, pages, state = prefill(cfg, p, pages, state, toks, lens)
    outs = [np.asarray(logits[:2])]
    for step in range(steps):
        logits, pages, state = decode(cfg, p, pages, state, toks,
                                      [n + step for n in lens])
        outs.append(np.asarray(logits[:2]))
    return np.stack(outs), state


def reference_logits(cfg, p, toks, lens, steps, **switches):
    full = np.asarray(ref.forward(cfg, p, jnp.asarray(toks), **switches))
    return np.stack([np.stack([full[r, n - 1 + s] for r, n in enumerate(lens)])
                     for s in range(steps + 1)])


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_qwen3_next()
    p = qn.init_params(cfg, seed=9, dtype=jnp.float32)
    toks = np.random.default_rng(7).integers(0, 256, (2, 30)).astype(np.int32)
    return cfg, p, toks


@pytest.mark.parametrize("split", [None, (8, 5)], ids=["one-chunk", "crosses-a-chunk"])
def test_prefill_then_decode_through_the_cache_is_the_reference_on_logits(tiny, split):
    """float32 activations: <= 1e-3 on logits of size ~3 (summation order
    only). A reused slot (poisoned) starts from zeros at position 0."""
    cfg, p, toks = tiny
    lens, steps = (19, 11), 4
    got, state = through_the_cache(cfg, p, toks, lens, steps, split, poison=7.0)
    want = reference_logits(cfg, p, toks, lens, steps)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-3)
    counts = state["counts"].tolist()
    assert counts[0] == 4 * (steps + 1 + bool(split))
    assert counts[3] == sum(lens)                  # real tokens through the chunked rule
    assert counts[4] == (4 if split else 2)        # and their blocks: a row a rectangle


def test_a_recomputed_row_is_exact(tiny):
    """Preemption gives the slot back; the row is recomputed from its
    tokens (prompt + what it had generated) in whatever slot it gets."""
    cfg, p, toks = tiny
    first, _ = through_the_cache(cfg, p, toks, (19, 11), 3)
    again, _ = through_the_cache(cfg, p, toks, (22, 14), 0, poison=3.0)
    np.testing.assert_allclose(again[0], first[3], atol=1e-4)


@pytest.mark.parametrize("piece", ["rotary", "qk_norm", "out_gate"])
def test_each_piece_of_gated_attention_is_in_the_program(tiny, piece):
    """The program meets the reference (previous test); with one piece
    switched off IN THE REFERENCE ONLY it must not."""
    cfg, p, toks = tiny
    p = dict(p, attn_qnorm=p["attn_qnorm"] + 0.5, attn_knorm=p["attn_knorm"] - 0.3)
    lens = (19, 11)
    got, _ = through_the_cache(cfg, p, toks, lens, 1)
    np.testing.assert_allclose(got, reference_logits(cfg, p, toks, lens, 1), atol=1e-3)
    without = reference_logits(cfg, p, toks, lens, 1, **{piece: False})
    assert np.abs(got - without).max() > 1e-2


def test_partial_rotary_rotates_a_quarter_of_each_head():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 5, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 5, 1, 16)).astype(np.float32))
    pos = jnp.arange(3, 8)[None, :]
    qr, kr = qn.partial_rope(q, k, pos, 1e7, 4)
    assert np.array_equal(qr[..., 4:], q[..., 4:]) and np.array_equal(kr[..., 4:], k[..., 4:])
    np.testing.assert_allclose(qr, ref.rotate(q, pos, 1e7, 4), atol=1e-6)
    assert np.abs(np.asarray(qr[..., :4] - q[..., :4])).max() > 0.1


def test_the_step_through_the_kernels_is_the_plain_step(tiny, monkeypatch):
    """Decode and prefill with the Pallas kernels (interpreted here): the
    delta-rule update in place on the state plane and the paged-attention
    kernels at this family's heads give what the XLA forms give."""
    cfg, p, toks = tiny
    plain, plain_state = through_the_cache(cfg, p, toks, (19, 11), 2)
    monkeypatch.setattr(hybrid, "kernels_active", lambda: True)
    kern, kern_state = through_the_cache(cfg, p, toks, (19, 11), 2)
    np.testing.assert_allclose(kern, plain, atol=2e-4)
    np.testing.assert_allclose(kern_state["gdn"][:, 1:], plain_state["gdn"][:, 1:],
                               atol=1e-4)


def test_the_served_precision_stays_near_the_reference(tiny):
    """int8 weights, bf16 operands: the reference reads the same int8
    weights in float32, so what is left is operand rounding — a few
    percent of a logit's size at this depth (limit 0.15 on logits of
    ~3; float32 activations read 1e-5: the test above)."""
    cfg, _, toks = tiny
    p = qn.init_params_quantized(cfg, seed=9)
    pages, state = qn.init_cache(cfg, 8, BS, dtype=jnp.bfloat16, state_slots=3)
    logits, pages, state = prefill(cfg, p, pages, state, toks, (19, 11))
    want = reference_logits(cfg, p, toks, (19, 11), 0)[0]
    err = np.abs(np.asarray(logits[:2], np.float32) - want)
    assert err.mean() < 0.05 and err.max() < 0.3
