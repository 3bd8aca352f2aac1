"""Kimi-Linear below the engine: the layer mathematics of
``models/kimi_linear.py`` against the plain reference
(``models/reference/kimi_linear.py``), the share of a divided expert
layer, the routing rule, the chunked and the kernel form of the KDA
recurrence, the seeded recipe, and the state plane's slot accounting."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator, NoBlocksError, StateSlots
from dynamo_tpu.engine.scheduler import Scheduler, Sequence
from dynamo_tpu.models import ModelConfig, family, kimi_linear as kl, llama
from dynamo_tpu.models.reference import kimi_linear as ref
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.tokens import TokenBlockSequence
from tests import state_plane_cases as spc
from tests.kimi_tiny import tiny_kimi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- configuration -----------------------------------------------------------
def published():
    with open(os.path.join(REPO, "perf", "configs", "kimi-linear-48b.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses_into_layer_kinds():
    cfg, raw = published()
    g = kl.Geometry(cfg)
    assert family(cfg) is kl and family(ModelConfig()) is llama
    assert cfg.has_recurrent_state and not ModelConfig().has_recurrent_state
    assert [g.kind_index(i)[0] for i in range(9)] == [
        "kda", "kda", "kda", "mla", "kda", "kda", "kda", "mla", "kda"]
    assert [g.kind_index(i)[2] for i in range(9)] == ["dense"] + ["moe"] * 8
    assert (g.E, g.E_all, g.e0) == (128, 256, 0)
    assert (g.C, g.Cpad, g.HD, g.Hl, g.dl) == (576, 640, 4096, 32, 128)
    assert cfg.max_position_embeddings == raw["published"]["model_max_length"]


def test_weights_at_int8_are_what_the_issue_reckoned():
    cfg, _ = published()
    total = sum(int(np.prod(shape)) for name, (shape, _) in
                kl.param_shapes(cfg).items() if name in kl.QUANT_AXIS)
    assert 8.0e9 < total < 8.2e9
    # a slot: 32 x 128 x 128 float32 + the float32 convolution tail (3 rows of 3 x 4096)
    assert kl.state_bytes(cfg, 65, 2) / 65 / 7 == 32 * 128 * 128 * 4 + 3 * 3 * 4096 * 4
    assert kl.page_bytes_per_block(cfg, 128, 2) == 2 * 128 * 640 * 2


@pytest.mark.parametrize("bad", [
    dict(linear_attn_config=dict(kda_layers=[1, 2], full_attn_layers=[4],
                                 num_heads=4, head_dim=16,
                                 short_conv_kernel_size=4)),
    dict(moe_router_activation_func="softmax"),
    dict(num_expert_group=2),
    dict(q_lora_rank=64),
    dict(num_shared_experts=2),
])
def test_what_is_not_built_is_refused_when_the_shapes_are_made(bad):
    with pytest.raises(ValueError):
        kl.param_shapes(tiny_kimi(**bad))


def test_the_seeded_recipe():
    cfg = tiny_kimi()
    p = kl.init_params_quantized(cfg, seed=2**31 + 5)
    a = np.exp(np.asarray(p["kda_A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.log1p(np.exp(np.asarray(p["kda_dt_bias"])))      # softplus
    assert dt.min() >= 0.999e-3 and dt.max() <= 1.001e-1
    for name in ("attn_norm", "mlp_norm", "final_norm", "kda_onorm", "mla_kvnorm"):
        assert np.all(np.asarray(p[name]) == 1.0)
    assert np.all(np.asarray(p["router_bias"]) == 0.0)
    assert p["router"].dtype == jnp.float32 and p["kda_conv"].dtype == jnp.float32
    for name in kl.QUANT_AXIS:
        assert p[name].dtype == jnp.int8 and name + "_scale" in p, name
    # float and int8 draws are the same numbers, the second quantized
    f = kl.init_params(cfg, seed=2**31 + 5, dtype=jnp.float32)
    w = np.asarray(f["we_up"][1, 3])
    q = np.asarray(p["we_up"][1, 3], np.float32) * np.asarray(p["we_up_scale"][1, 3])
    assert np.abs(w - q).max() <= np.abs(w).max(0).max() / 127


# -- the KDA recurrence ------------------------------------------------------------
def kda_inputs(B, T, H, d, seed=0, pad_from=None):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(B, T, H, d)).astype(np.float32) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    glog = -rng.uniform(0.0, 2.0, size=(B, T, H, d)).astype(np.float32)
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
        o, S = kl.kda_decode(q[:, t], k[:, t], v[:, t], glog[:, t], beta[:, t], S)
        outs.append(o)
    return jnp.stack(outs, axis=1), S


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_recurrence_is_the_token_by_token_one(chunk):
    args = kda_inputs(2, 32, 3, 16)
    o, S = kl.kda_chunked(*args, chunk=chunk)
    o_want, S_want = token_by_token(*args)
    np.testing.assert_allclose(o, o_want, atol=2e-4)
    np.testing.assert_allclose(S, S_want, atol=2e-4)


def test_chunked_recurrence_survives_a_decay_that_underflows():
    """64 steps of log-decay -8: exp(+512) would overflow any form that
    divides by the cumulative decay; every exponent here is <= 0."""
    q, k, v, glog, beta, S = kda_inputs(1, 64, 2, 16, seed=3)
    glog = jnp.full_like(glog, -8.0)
    o, S2 = kl.kda_chunked(q, k, v, glog, beta, S, chunk=64)
    o_want, S_want = token_by_token(q, k, v, glog, beta, S)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(S2, S_want, atol=1e-5)


def test_padding_tokens_leave_the_state_alone():
    args = kda_inputs(2, 16, 2, 16, seed=1, pad_from=(5, 16))
    _, S = kl.kda_chunked(*args, chunk=8)
    q, k, v, glog, beta, S0 = args
    _, S_short = token_by_token(q[:1, :5], k[:1, :5], v[:1, :5], glog[:1, :5],
                                beta[:1, :5], S0[:1])
    np.testing.assert_allclose(S[0], S_short[0], atol=1e-4)


@pytest.mark.parametrize("rows,T,want", [(1, 1024, 64), (8, 256, 64), (32, 128, 32),
                                         (64, 128, 16), (4, 16, 16)])
def test_more_rows_take_shorter_chunks(rows, T, want):
    assert kl.kda_chunk_for(rows, T) == want and T % want == 0


@pytest.mark.parametrize("H,d", [(16, 128), (64, 16), (12, 16)],
                         ids=["one_block", "two_blocks", "odd_heads"])
@pytest.mark.parametrize("name", sorted(spc.CASES))
def test_the_kernel_updates_live_rows_in_place_and_moves_nothing_else(name, H, d):
    """``kda_decode_update`` (interpreted) against the plain step, with
    padded rows anywhere in the batch: H 64 is two head blocks, 12 is
    neither a multiple of the block nor of a pass."""
    from dynamo_tpu.ops.kda import kda_decode_update

    rng = np.random.default_rng(0)
    slots, fresh = spc.case(name)
    plane = rng.normal(size=(3, spc.SLOTS, H, d, d)).astype(np.float32)
    q, k, v, glog, beta, _ = kda_inputs(len(slots), 1, H, d, seed=2)
    S0 = jnp.where(fresh[:, None, None, None] != 0, 0.0, jnp.asarray(plane)[1, slots])
    o_want, S_want = kl.kda_decode(q[:, 0], k[:, 0], v[:, 0], glog[:, 0], beta[:, 0], S0)
    o, new = kda_decode_update(jnp.asarray(plane), 1, slots, fresh, q[:, 0], k[:, 0],
                               v[:, 0], glog[:, 0], beta[:, 0], interpret=True)
    spc.check_rows(o, o_want, slots, atol=2e-3)
    spc.check_plane(new, plane, 1, slots, S_want, atol=1e-4)


# -- the expert layer ----------------------------------------------------------------
def test_routing_selects_by_score_plus_bias_and_weighs_by_score():
    cfg = tiny_kimi(num_experts_per_token=3)
    p = kl.init_params(cfg, seed=1, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    bias = rng.normal(size=(8,)).astype(np.float32) * 2
    p["router_bias"] = p["router_bias"].at[1].set(bias)
    x = jnp.asarray(rng.normal(size=(11, 64)).astype(np.float32))
    w, topi = kl.moe_routing(cfg, p, x, 1)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"][1]))))
    want_i = np.argsort(-(s + bias), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(topi), -1), np.sort(want_i, -1))
    chosen = np.take_along_axis(s, np.asarray(topi), -1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * 2.446, rtol=1e-5)
    # the bias moved the choice and not the weights
    _, plain = kl.moe_routing(cfg, dict(p, router_bias=jnp.zeros_like(p["router_bias"])), x, 1)
    assert not np.array_equal(np.sort(np.asarray(plain), -1), np.sort(want_i, -1))


def test_without_renormalisation_the_scores_are_only_scaled():
    cfg = tiny_kimi(moe_renormalize=False)
    p = kl.init_params(cfg, seed=1, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 64)).astype(np.float32))
    w, topi = kl.moe_routing(cfg, p, x, 0)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"][0]))))
    np.testing.assert_allclose(
        w, np.take_along_axis(s, np.asarray(topi), -1) * 2.446, rtol=1e-5)


def halves_of(cfg_whole, p_whole):
    """The two shares of an 8-expert layer: 4 experts each, the whole
    router, everything else alike."""
    out = []
    for shard in (0, 1):
        cfg = tiny_kimi(num_experts=4, expert_shards=2, expert_shard_index=shard)
        p = dict(p_whole)
        for name in ("we_gate", "we_up", "we_down"):
            p[name] = p_whole[name][:, 4 * shard: 4 * shard + 4]
        out.append((cfg, p))
    return out


@pytest.mark.parametrize("tokens", [(2, 9), (3, 40)], ids=["dense-form", "grouped-form"])
def test_the_shares_of_a_divided_expert_layer_add_up_to_the_whole(tokens):
    """Model-configs guide, section 4: the parts of the result that all
    the shares give, with the shared expert counted once, are what the
    uncut reference gives for the whole layer."""
    whole = tiny_kimi()
    p = kl.init_params(whole, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(*tokens, 64)).astype(np.float32))
    w32 = ref.dequantized(p)
    want = ref.expert_ffn(whole, w32, 2, h)
    shared = ref.gated_mlp(h, w32["ws_gate"][2], w32["ws_up"][2], w32["ws_down"][2])
    parts, seen = [], []
    for cfg, ps in halves_of(whole, p):
        out, counts = kl.moe_ffn(cfg, kl.Geometry(cfg), ps, h, 2)
        parts.append(out)
        seen.append(np.asarray(counts))
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want, atol=2e-5)
    # each share alone is its own reference's share too
    for (cfg, ps), part in zip(halves_of(whole, p), parts):
        np.testing.assert_allclose(
            part, ref.expert_ffn(cfg, ref.dequantized(ps), 2, h), atol=2e-5)
    n = tokens[0] * tokens[1]
    assert seen[0][1] + seen[1][1] == n * whole.num_experts_per_token
    assert 1 <= seen[0][2] <= 4 and 1 <= seen[1][2] <= 4


def test_the_two_forms_of_the_held_experts_agree():
    cfg = tiny_kimi(num_experts=4, expert_shards=2, expert_shard_index=1)
    p = kl.init_params_quantized(cfg, seed=3)
    g = kl.Geometry(cfg)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(24, 64)).astype(np.float32))
    w, topi = kl.moe_routing(cfg, p, x, 1)
    local = (topi >= g.e0) & (topi < g.e0 + g.E)
    w = jnp.where(local, w, 0.0)
    local_e = jnp.where(local, topi - g.e0, g.E)
    combine = jnp.zeros((24, g.E + 1)).at[jnp.arange(24)[:, None], local_e].add(w)
    dense = kl.moe_local_dense(p, x, combine[:, : g.E], 1)
    grouped = kl.moe_local_grouped(p, x, w, local_e, 1, g.E)
    np.testing.assert_allclose(dense, grouped, atol=1e-4)


def test_padding_is_not_counted_as_expert_traffic():
    cfg = tiny_kimi()
    p = kl.init_params(cfg, seed=4, dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 6, 64)).astype(np.float32))
    valid = jnp.asarray([[1, 1, 1, 0, 0, 0], [1, 0, 0, 0, 0, 0]], bool)
    _, counts = kl.moe_ffn(cfg, kl.Geometry(cfg), p, h, 0, valid)
    assert counts.tolist()[:2] == [1, 4 * cfg.num_experts_per_token]
    assert counts[2] <= 8


# -- the whole step against the whole reference --------------------------------------
def test_right_padding_and_a_garbage_row_do_not_move_the_logits():
    cfg = tiny_kimi()
    p = kl.init_params(cfg, seed=9, dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(0, 256, (1, 13)).astype(np.int32)
    want = np.asarray(ref.forward(cfg, p, jnp.asarray(toks)))[0, -1]
    bs, T = 8, 32
    pages, state = kl.init_cache(cfg, 8, bs, dtype=jnp.float32, state_slots=3)
    # poison the slot: a row that starts at position 0 must not read it
    state["kda"] = state["kda"] + 7.0
    state["conv"] = state["conv"] + 7.0
    t = np.zeros((2, T), np.int32)
    t[0, :13] = toks[0]
    pos = np.zeros((2, T), np.int32)
    pos[0, :13] = np.arange(13)
    sm = np.zeros((2, T), np.int32)
    sm[0, :13] = [(1 + i // bs) * bs + i % bs for i in range(13)]
    tables = np.array([[1, 2, 0, 0, 2], [0, 0, 0, 0, 0]], np.int32)
    logits, pages, state = kl.forward(
        cfg, p, pages, state, t, pos, sm.reshape(-1), tables,
        np.array([13, 0], np.int32), np.array([12, 0], np.int32), bs)
    np.testing.assert_allclose(logits[0], want, atol=2e-4)
    assert state["counts"].tolist()[0] == 4      # four expert layers ran
    # the convolution tail holds the last three REAL inputs, not padding
    tail = np.asarray(state["conv"][0, 2])      # 3 rows of 3HD, one after another
    assert np.abs(tail).max() > 0 and np.abs(tail - 7.0).min() > 1e-3


def test_the_step_through_the_kernels_is_the_plain_step(monkeypatch):
    """Decode with the Pallas kernels (interpreted here): the KDA update
    in place on the state plane and latent attention over the row's own
    pages give what XLA's gather-then-compute forms give."""
    cfg = tiny_kimi()
    p = kl.init_params(cfg, seed=9, dtype=jnp.float32)
    bs, rng = 8, np.random.default_rng(6)
    tables = np.array([[1, 2, 3, 0, 2], [4, 5, 6, 0, 1], [0, 0, 0, 0, 0]], np.int32)
    lens = [19, 11]

    def run(kernels: bool):
        monkeypatch.setattr(kl, "kernels_active", lambda: kernels)
        pages, state = kl.init_cache(cfg, 8, bs, dtype=jnp.float32, state_slots=3)
        T = 32
        t, pos = np.zeros((3, T), np.int32), np.zeros((3, T), np.int32)
        sm = np.zeros((3, T), np.int32)
        toks = np.random.default_rng(7).integers(0, 256, (2, 24)).astype(np.int32)
        for r, n in enumerate(lens):
            t[r, :n], pos[r, :n] = toks[r, :n], np.arange(n)
            sm[r, :n] = [tables[r, i // bs] * bs + i % bs for i in range(n)]
        _, pages, state = kl.forward(
            cfg, p, pages, state, t, pos, sm.reshape(-1), tables,
            np.array(lens + [0], np.int32), np.array([18, 10, 0], np.int32), bs)
        outs = []
        for step in range(3):
            cur = [n + step for n in lens]
            t1 = np.array([[toks[0, cur[0]]], [toks[1, cur[1]]], [0]], np.int32)
            p1 = np.array([[cur[0]], [cur[1]], [0]], np.int32)
            s1 = np.array([tables[r, c // bs] * bs + c % bs
                           for r, c in enumerate(cur)] + [0], np.int32)
            logits, pages, state = kl.forward(
                cfg, p, pages, state, t1, p1, s1, tables,
                np.array([c + 1 for c in cur] + [0], np.int32),
                np.zeros((3,), np.int32), bs)
            outs.append(np.asarray(logits[:2]))
        return np.stack(outs), np.asarray(state["kda"][:, 1:])

    plain, plain_state = run(False)
    kern, kern_state = run(True)
    np.testing.assert_allclose(kern, plain, atol=2e-4)
    np.testing.assert_allclose(kern_state, plain_state, atol=2e-4)


def test_the_reference_refuses_nothing_the_program_serves():
    """Both references (this repo's and the benchmark's) are checked
    against each other in tests/perf_harness; here: int8 parameters are
    read through their scales."""
    cfg = tiny_kimi()
    p8 = kl.init_params_quantized(cfg, seed=2)
    w = ref.dequantized(p8)
    assert all(a.dtype == jnp.float32 for a in w.values())
    assert not any(n.endswith("_scale") for n in w)
    got = np.asarray(w["embed"][5])
    np.testing.assert_allclose(
        got, np.asarray(p8["embed"][5], np.float32) * float(p8["embed_scale"][5]))


# -- the state plane's slots ----------------------------------------------------------
def test_state_slots_are_held_once_and_slot_zero_never():
    slots = StateSlots(4)
    got = [slots.acquire() for _ in range(3)]
    assert sorted(got) == [1, 2, 3] and slots.num_used == 3 and slots.num_free == 0
    with pytest.raises(NoBlocksError):
        slots.acquire()
    slots.release(2)
    assert slots.acquire() == 2
    for bad in (0, 4, -1):
        with pytest.raises(ValueError):
            slots.release(bad)
    slots.release(1)
    with pytest.raises(ValueError):
        slots.release(1)
    with pytest.raises(ValueError):
        StateSlots(1)


def make_seq(n: int, rid: str) -> Sequence:
    req = PreprocessedRequest(request_id=rid, token_ids=list(range(1, n + 1)),
                              stop=StopConditions(max_tokens=4))
    return Sequence(request=req, tokens=TokenBlockSequence(req.token_ids, 8))


def stateful_scheduler(slots: int = 3) -> Scheduler:
    sched = Scheduler(BlockAllocator(32, 8, enable_prefix_caching=False), 8,
                      max_batch_size=4, prefill_chunk_size=16, max_model_len=64)
    sched.state_slots = StateSlots(slots)
    return sched


def test_admission_takes_a_slot_and_every_way_out_gives_it_back():
    from dynamo_tpu.protocols.common import FinishReason

    sched = stateful_scheduler(slots=3)          # two usable slots
    a, b, c = make_seq(10, "a"), make_seq(10, "b"), make_seq(10, "c")
    for s in (a, b, c):
        sched.add_request(s)
    sched._admit()
    assert a.state_slot and b.state_slot and a.state_slot != b.state_slot
    assert c.state_slot == 0 and len(sched.waiting) == 1   # no slot: it waits
    sched.finish(a, FinishReason.CANCELLED)                # abort
    assert a.state_slot == 0 and sched.state_slots.num_used == 1
    sched._admit()
    assert c.state_slot != 0 and sched.state_slots.num_used == 2
    sched.prefilling.remove(b)
    sched.running.append(b)
    sched._preempt(b)                                      # preemption
    assert b.state_slot == 0 and b.block_table == [] and sched.preemptions == 1
    sched.finish(c, FinishReason.LENGTH)                   # finish
    assert sched.state_slots.num_used == 0
    assert (sched.prefix_queries, sched.prefix_hits) == (3, 0)


def test_every_table_row_ends_in_the_rows_state_slot():
    sched = stateful_scheduler(slots=5)
    seqs = [make_seq(10, "a"), make_seq(20, "b")]
    for s in seqs:
        sched.add_request(s)
    sched._admit()
    works = sched._plan_prefill_batch()
    arrays = sched.build_prefill_batch_arrays(works)
    tables = arrays["block_tables"]
    assert tables.shape[1] == sched._table_width(3) == 8 + 1
    for i, w in enumerate(works):
        assert tables[i, -1] == w.seq.state_slot > 0
        assert tables[i, : len(w.seq.block_table)].tolist() == w.seq.block_table
    assert (tables[len(works):] == 0).all()                # padded rows: slot 0
    wide = sched.widen_tables(tables, 17)
    assert wide.shape[1] == 17 and (wide[:, -1] == tables[:, -1]).all()
    assert (wide[:, :8] == tables[:, :8]).all() and (wide[:, 8:-1] == 0).all()
    for s in seqs:
        s.state = s.state.__class__("running")
        sched.prefilling.remove(s)
        sched.running.append(s)
    d = sched.build_decode_arrays(seqs)["block_tables"]
    assert [d[i, -1] for i in range(2)] == [s.state_slot for s in seqs]
    # a model without recurrent state: tables as they always were
    plain = Scheduler(BlockAllocator(32, 8), 8, max_batch_size=4)
    assert plain.table_extra == 0 and plain._table_width(3) == 8
