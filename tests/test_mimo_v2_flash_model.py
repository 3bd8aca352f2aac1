"""``mimo_v2_flash`` below the engine: the layer mathematics of
``models/mimo_v2_flash.py`` against the plain reference
(``models/reference/mimo_v2_flash.py``) — chunked prefill then decode
through BOTH page planes with the window plane's dead columns zeroed and
their pages scribbled over, sinks, K wider than V, two KV head counts,
the rotated leading third against ``rotate_half`` written out here, the
expert shares, routing with a selection bias, the kernels in interpret
mode at these geometries, the seeded recipe and what the family refuses
by name."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.allocator import WindowPlane
from dynamo_tpu.models import ModelConfig, family, hybrid
from dynamo_tpu.models import mimo_v2_flash as mm
from dynamo_tpu.models.reference import mimo_v2_flash as ref
from dynamo_tpu.ops import paged_attention as pa
from tests.mimo_v2_flash_tiny import tiny_mimo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 end to end: differences are summation order
TOL = 2e-4


def published():
    with open(os.path.join(REPO, "perf", "configs", "mimo-v2-flash.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses():
    cfg, raw = published()
    g = mm.Geometry(cfg)
    assert family(cfg) is mm
    assert cfg.owns_pages and not cfg.has_recurrent_state
    assert cfg.released_window == 128
    assert (g.L, g.D, g.V, g.H) == (12, 4096, 152576, 64)
    assert (g.Dk, g.Dv, g.Dkp, g.rot, g.window) == (192, 128, 256, 64, 128)
    assert g.full_layers == [0, 5, 11]
    assert g.window_layers == [1, 2, 3, 4, 6, 7, 8, 9, 10]
    assert g.kinds["full"][1:] == (4, 5e6, False, None)
    assert g.kinds["win"][1:] == (8, 1e4, True, 128)
    assert (g.F, g.Fe, g.E, g.E_all, g.e0, g.k) == (16384, 2048, 16, 256, 0, 8)
    assert g.dense_layers == [0] and g.moe_layers == list(range(1, 12))
    assert g.vscale == 0.707 and g.route_scale == 1.0
    assert cfg.rms_norm_eps == 1e-5
    assert raw["published"]["num_hidden_layers"] == 48
    assert raw["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts"]


def test_weights_and_pages_at_this_repos_byte_are_what_the_issue_reckoned():
    cfg, _ = published()
    shapes = mm.param_shapes(cfg)
    size = {n: int(np.prod(s)) for n, (s, _) in shapes.items()}
    total = sum(v for n, v in size.items() if n in mm.QUANT_AXIS)
    assert 6.99e9 < total < 7.01e9
    per_full = sum(size[f"full_{n}"] for n in ("wq", "wk", "wv", "wo")) / 3
    per_win = sum(size[f"win_{n}"] for n in ("wq", "wk", "wv", "wo")) / 9
    assert round(per_full / 1e6, 2) == 89.13 and round(per_win / 1e6, 2) == 94.37
    # a 128-token page as stored (K in 256 lanes): 1.18 / 7.08 MB
    assert mm.page_bytes_per_block(cfg, 128, 2) == 3 * 128 * 4 * 384 * 2
    assert mm.page_bytes_per_block(cfg, 128, 2, plane="window") \
        == 9 * 128 * 8 * 384 * 2 == 7077888
    pshape = mm.cache_shapes(cfg, 10, 128, 5)
    assert pshape["full_k"] == (3, 10 * 128 * 4, 256)
    assert pshape["win_v"] == (9, 5 * 128 * 8, 128)


@pytest.mark.parametrize("bad, named", [
    (dict(hybrid_layer_pattern=[0, 1, 1]), "hybrid_layer_pattern"),
    (dict(hybrid_layer_pattern=None), "hybrid_layer_pattern"),
    (dict(moe_layer_freq=1), "moe_layer_freq"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(attention_chunk_size=64), "attention_chunk_size"),
    (dict(sliding_window_size=64), "sliding_window_size"),
    (dict(swa_head_dim=32), "swa_head_dim"),
    (dict(swa_num_attention_heads=4), "swa_num_attention_heads"),
    (dict(v_head_dim=0), "v_head_dim"),
    (dict(n_shared_experts=1), "n_shared_experts"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "n_group"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(partial_rotary_factor=0.3), "partial_rotary_factor"),
    (dict(swa_num_key_value_heads=3), "KV heads"),
])
def test_what_is_not_built_is_refused_by_the_keys_name(bad, named):
    with pytest.raises(ValueError, match=named):
        mm.param_shapes(tiny_mimo(**bad))


def test_check_engine_names_what_it_refuses():
    from dynamo_tpu.engine.config import EngineConfig

    mm.check_engine(EngineConfig(model_name="x", kv_cache_dtype="bfloat16"))
    for kw, named in ((dict(tensor_parallel_size=2), "tensor_parallel_size"),
                      (dict(host_kv_blocks=4), "host_kv_blocks"),
                      (dict(kv_cache_dtype="int8"), "int8"),
                      (dict(spec_decode="ngram"), "spec_decode")):
        with pytest.raises(ValueError, match=named):
            mm.check_engine(EngineConfig(model_name="x", **kw))
    with pytest.raises(ValueError, match="int8 K/V cache"):
        mm.init_cache(tiny_mimo(), 4, 8, dtype=jnp.int8)


def test_the_third_question_is_asked_of_the_family_alone():
    from tests.deepseek_v3_tiny import tiny_deepseek

    assert tiny_mimo().released_window == 12
    assert tiny_mimo(hybrid_layer_pattern=[0, 0, 0, 0]).released_window == 0
    assert tiny_deepseek().released_window == 0
    assert ModelConfig().released_window == 0


def test_the_seeded_recipe():
    cfg = tiny_mimo()
    names = list(mm.param_shapes(cfg))
    assert names == [
        "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
        "full_wq", "full_wk", "full_wv", "full_wo",
        "win_wq", "win_wk", "win_wv", "win_wo", "win_sink",
        "w_gate", "w_up", "w_down", "router", "router_bias",
        "we_gate", "we_up", "we_down"]
    p = mm.init_params(cfg, seed=4, dtype=jnp.float32)
    root = jax.random.PRNGKey(4)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(root, names.index("we_up")), 1), 3)
    want = jax.random.normal(key, (64, 32), jnp.float32) / np.sqrt(64)
    np.testing.assert_allclose(p["we_up"][1, 3], want, rtol=1e-6)
    key = jax.random.fold_in(jax.random.fold_in(
        root, names.index("win_sink")), 1)
    np.testing.assert_allclose(
        p["win_sink"][1], jax.random.normal(key, (8,), jnp.float32), rtol=1e-6)
    assert 0.5 < float(jnp.std(p["win_sink"])) < 1.6     # N(0, 1): they matter
    assert np.all(np.asarray(p["router_bias"]) == 0)
    assert p["router"].shape == (3, 64, 8)               # scores ALL experts
    assert p["we_up"].shape == (3, 4, 64, 32)            # holds its shard
    assert p["full_wk"].shape == (2, 64, 2 * 24)
    assert p["win_wk"].shape == (2, 64, 4 * 24) and p["win_wv"].shape == (2, 64, 64)
    p8 = mm.init_params_quantized(cfg, seed=4)
    assert p8["win_wq"].dtype == jnp.int8 and p8["router"].dtype == jnp.float32
    assert p8["win_sink"].dtype == jnp.float32 and "win_sink_scale" not in p8


# -- rotary -----------------------------------------------------------------------
def rotate_half_leading(x, positions, theta, rot):
    """The published form written out: ``x_rot * cos + rotate_half(x_rot)
    * sin`` on the LEADING ``rot`` values, cos / sin of ``cat(freqs,
    freqs)``; the rest of the head passes. x [B, T, H, d] (numpy)."""
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    freqs = positions[..., None].astype(np.float64) * inv       # [B, T, rot/2]
    emb = np.concatenate([freqs, freqs], -1)[:, :, None, :]
    xr, rest = x[..., :rot].astype(np.float64), x[..., rot:]
    half = np.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], -1)
    return np.concatenate([xr * np.cos(emb) + half * np.sin(emb), rest], -1)


@pytest.mark.parametrize("theta", [5e6, 1e4])
def test_rotary_turns_the_leading_third_as_rotate_half_does(theta):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, 24)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 4], [40, 41, 42, 43, 1000]])
    rot = int(24 * 0.334)
    assert rot == 8
    want = rotate_half_leading(x, pos, theta, rot)
    got_ref = ref.rotate_leading(jnp.asarray(x), jnp.asarray(pos), theta, rot)
    np.testing.assert_allclose(got_ref, want, atol=2e-5)
    got, _ = mm.partial_rope(jnp.asarray(x), jnp.asarray(x), jnp.asarray(pos),
                             theta, rot)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[..., rot:], x[..., rot:])
    assert not np.allclose(np.asarray(got)[:, 1:, :, :rot], x[:, 1:, :, :rot])


def test_the_two_kinds_turn_by_their_own_bases():
    cfg = tiny_mimo()
    g = mm.Geometry(cfg)
    assert g.kinds["full"][2] == 5e6 and g.kinds["win"][2] == 1e4
    assert (g.kinds["full"][1], g.kinds["win"][1]) == (2, 4)
    assert (g.Dk, g.Dv, g.Dkp, g.rot) == (24, 16, 128, 8)


# -- the step, by hand: chunks then decode through both planes ---------------------
class Planes:
    """Tables of one row in both planes, the window plane's filled and
    released by ``allocator.WindowPlane`` itself; a released page is
    scribbled over in the cache, so a read of it would show."""

    def __init__(self, cfg, block_size, n_pages, release=True):
        self.bs, self.W = block_size, n_pages
        self.full = list(range(1, n_pages + 1))
        self.plane = WindowPlane(n_pages + 1, block_size, cfg.sliding_window)
        self.plane._free.reverse()     # other ids than the full plane's
        self.win: list[int] = []
        self.release = release
        self.peak = 0

    def tables(self, upto_tokens, next_query):
        cols = -(-upto_tokens // self.bs)
        self.plane.cover(self.win, cols, next_query)
        self.peak = max(self.peak, self.plane.held(self.win))
        t = np.zeros((1, 2 * self.W), np.int32)
        t[0, : self.W] = self.full
        t[0, self.W: self.W + len(self.win)] = self.win
        return t

    def advance(self, pages, next_query):
        """Release behind ``next_query`` and scribble over what went."""
        if not self.release:
            return pages
        before = list(self.win)
        self.plane.release_behind(self.win, next_query)
        gone = [b for b, a in zip(before, self.win) if b and not a]
        g = self.bs
        for name in ("win_k", "win_v"):
            hk = pages[name].shape[1] // ((self.W + 1) * g)
            for b in gone:
                pages[name] = pages[name].at[
                    :, b * g * hk: (b + 1) * g * hk].set(1e4)
        return pages


def run_program(cfg, params, tokens, block_size, chunk, release=True,
                dtype=jnp.float32):
    """Prefill the first ``len(tokens) // 2`` tokens in chunks of
    ``chunk`` (one row, its tables built by ``Planes``), then decode the
    rest one token at a time (teacher-forced); returns the logits after
    each step's last token, and the Planes."""
    n = len(tokens)
    n_prompt = n // 2
    W = -(-n // block_size) + 1
    planes = Planes(cfg, block_size, W, release)
    pages, counts = mm.init_cache(cfg, W + 1, block_size, dtype=dtype,
                                  window_blocks=W + 1)
    out = []
    # one compile a step shape (the chunk, its remainder, a decode step)
    forward = jax.jit(lambda *a: mm.forward(cfg, *a, block_size))

    def step(start, t):
        nonlocal pages, counts
        toks = np.asarray([tokens[start: start + t]], np.int32)
        pos = np.arange(start, start + t)[None].astype(np.int32)
        tables = planes.tables(start + t, start)
        slots = np.asarray(
            [planes.full[p // block_size] * block_size + p % block_size
             for p in range(start, start + t)], np.int32)
        logits, pages, counts = forward(
            params, pages, counts, toks, pos, slots, tables,
            np.asarray([start + t], np.int32), np.asarray([t - 1], np.int32))
        pages = planes.advance(dict(pages), start + t)
        out.append((start + t - 1, np.asarray(logits[0], np.float32)))

    start = 0
    while start < n_prompt:
        t = min(chunk, n_prompt - start)
        step(start, t)
        start += t
    for p in range(n_prompt, n):
        step(p, 1)
    return out, planes, counts


GEOMETRIES = [(8, 4, 7), (12, 8, 16), (24, 16, 10), (16, 16, 16), (9, 16, 5)]


@pytest.mark.parametrize("window,block_size,chunk", GEOMETRIES)
def test_chunked_prefill_then_decode_through_both_planes_meets_the_reference(
        window, block_size, chunk):
    """Window and page are independent; the window plane's dead columns
    read 0 and their pages hold 1e4: logits still meet the reference's
    full forward pass, and the row never holds more window pages than
    the window and a step's tokens span."""
    cfg = tiny_mimo(sliding_window=window, sliding_window_size=window,
                    attention_chunk_size=window)
    params = mm.init_params(cfg, seed=3, dtype=jnp.float32)
    rng = np.random.default_rng(window)
    tokens = rng.integers(3, 250, size=90).tolist()
    want = np.asarray(ref.forward(cfg, params, jnp.asarray([tokens])))[0]
    got, planes, counts = run_program(cfg, params, tokens, block_size, chunk)
    assert len(got) >= 45 + 3
    for at, logits in got:
        np.testing.assert_allclose(logits, want[at], atol=TOL, rtol=TOL)
    assert planes.plane.released_total > 0
    assert planes.peak <= planes.plane.span_pages(chunk)
    assert planes.peak < len(planes.full) - 1       # never the row's length


def test_releasing_changes_no_logit():
    """The same steps with nothing released: bit for bit the same."""
    cfg = tiny_mimo()
    params = mm.init_params(cfg, seed=3, dtype=jnp.float32)
    tokens = np.random.default_rng(1).integers(3, 250, size=80).tolist()
    a, pa_, _ = run_program(cfg, params, tokens, 8, 16, release=True)
    b, pb_, _ = run_program(cfg, params, tokens, 8, 16, release=False)
    assert pa_.plane.released_total > 0 and pb_.plane.released_total == 0
    assert pb_.peak == -(-80 // 8) > pa_.peak
    for (at, x), (bt, y) in zip(a, b):
        assert at == bt
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("sinks", ["zero", "normal", "large"])
def test_sinks_take_probability_and_add_no_value(sinks):
    """A zero sink is not no sink (it is a column of logit 0), N(0, 1)
    sinks differ from it, and each meets the reference."""
    cfg = tiny_mimo()
    params = dict(mm.init_params(cfg, seed=3, dtype=jnp.float32))
    base = params["win_sink"]
    params["win_sink"] = {"zero": jnp.zeros_like(base), "normal": base,
                          "large": base + 4.0}[sinks]
    tokens = np.random.default_rng(2).integers(3, 250, size=40).tolist()
    want = np.asarray(ref.forward(cfg, params, jnp.asarray([tokens])))[0]
    got, _, _ = run_program(cfg, params, tokens, 8, 16)
    for at, logits in got:
        np.testing.assert_allclose(logits, want[at], atol=TOL, rtol=TOL)
    other = dict(params, win_sink=base + 1.0)
    moved = np.asarray(ref.forward(cfg, other, jnp.asarray([tokens])))[0]
    assert np.abs(moved - want).max() > 1e-3
    no_sink = tiny_mimo(add_swa_attention_sink_bias=False)
    bare = {k: v for k, v in params.items() if k != "win_sink"}
    without = np.asarray(ref.forward(no_sink, bare, jnp.asarray([tokens])))[0]
    assert np.abs(without - want).max() > 1e-3


def test_full_layers_take_a_sink_where_the_configuration_says_so():
    cfg = tiny_mimo(add_full_attention_sink_bias=True)
    assert "full_sink" in mm.param_shapes(cfg)
    params = mm.init_params(cfg, seed=3, dtype=jnp.float32)
    tokens = np.random.default_rng(5).integers(3, 250, size=30).tolist()
    want = np.asarray(ref.forward(cfg, params, jnp.asarray([tokens])))[0]
    got, _, _ = run_program(cfg, params, tokens, 8, 8)
    for at, logits in got:
        np.testing.assert_allclose(logits, want[at], atol=TOL, rtol=TOL)


def test_bfloat16_operands_and_pages_stay_near_the_reference():
    """bf16 parameters, activations and pages against the float32
    reference ON THE SAME (bf16-rounded) parameters: what differs is the
    rounding of every matmul operand and cached K / V to 8 bits of
    mantissa (2**-9 relative each), which four layers carry to about
    1e-2 of the logits' spread of ~1 (measured over three seeds: mean
    absolute error 0.009-0.015, a step's largest error 0.02-0.03 in the
    median). The bounds are three times that, and an order of magnitude
    under what a wrong mask, sink or head mapping gives (> 0.5 at every
    step). A SINGLE step may read far more (0.9 seen): the top-3 of 8
    sigmoid scores at a near tie chooses another expert in bf16, which is
    a different function, not a rounding — so the step-wise maximum is
    held by its median, not by its worst."""
    cfg = tiny_mimo()
    params = mm.init_params(cfg, seed=3)             # bf16 matrices
    tokens = np.random.default_rng(3).integers(3, 250, size=60).tolist()
    want = np.asarray(ref.forward(cfg, params, jnp.asarray([tokens])))[0]
    got, _, _ = run_program(cfg, params, tokens, 8, 16, dtype=jnp.bfloat16)
    worst = np.asarray([np.abs(l - want[at]).max() for at, l in got])
    mean = np.mean([np.abs(l - want[at]).mean() for at, l in got])
    assert np.median(worst) < 0.1 and mean < 0.045, (np.median(worst), mean)
    assert float(np.std(want)) > 0.3


# -- experts ----------------------------------------------------------------------
def test_the_shards_shares_add_up_to_the_uncut_layer():
    """The guide's test of a cut expert layer: the layer output of the
    UNCUT model (all experts in one process) equals the sum of what each
    of the ``expert_shards`` processes computes from its own run of
    experts — program and reference alike."""
    shards, held = 4, 2
    whole = tiny_mimo(n_routed_experts=shards * held, expert_shards=1)
    pw = mm.init_params(whole, seed=9, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64), jnp.float32)
    want = np.asarray(ref.expert_ffn(whole, ref.dequantized(pw), 1, h))
    total_ref = np.zeros_like(want)
    total_prog = np.zeros_like(want)
    seen_assignments = 0
    for s in range(shards):
        cut = tiny_mimo(n_routed_experts=held, expert_shards=shards,
                        expert_shard_index=s)
        g = mm.Geometry(cut)
        assert (g.E, g.E_all, g.e0) == (held, shards * held, s * held)
        ps = dict(pw)
        for name in ("we_gate", "we_up", "we_down"):
            ps[name] = pw[name][:, s * held: (s + 1) * held]
        total_ref += np.asarray(ref.expert_ffn(cut, ref.dequantized(ps), 1, h))
        out, counts = mm.moe_ffn(cut, g, ps, h, 1)
        total_prog += np.asarray(out)
        seen_assignments += int(counts[1])
    np.testing.assert_allclose(total_ref, want, atol=1e-5)
    np.testing.assert_allclose(total_prog, want, atol=1e-4)
    assert seen_assignments == 2 * 5 * 3            # every choice, once


def test_top_k_routing_with_a_selection_bias():
    """The bias chooses, the unbiased scores weigh; renormalised over the
    chosen; ``routed_scaling_factor`` null is 1."""
    cfg = tiny_mimo()
    g = mm.Geometry(cfg)
    p = dict(mm.init_params(cfg, seed=2, dtype=jnp.float32))
    bias = np.zeros((3, 8), np.float32)
    bias[1, 6] = 10.0                                # expert 6: always chosen
    bias[1, 0] = -10.0                               # expert 0: never
    p["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 64), jnp.float32)
    w, topi = mm.moe_routing(cfg, g, p, x, 1)
    topi, w = np.asarray(topi), np.asarray(w)
    assert topi.shape == (7, 3) and (topi == 6).any(1).all()
    assert not (topi == 0).any()
    s = np.asarray(jax.nn.sigmoid(x @ p["router"][1]))
    chosen = np.take_along_axis(s, topi, 1)
    np.testing.assert_allclose(w, chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(1), 1.0, rtol=1e-5)
    wr, tr = ref.routing(cfg, ref.dequantized(p), 1, x)
    np.testing.assert_array_equal(np.asarray(tr), topi)
    np.testing.assert_allclose(np.asarray(wr), w, rtol=1e-5)
    # the program's layer with that bias meets the reference's
    h = x.reshape(1, 7, 64)
    out, _ = mm.moe_ffn(cfg, g, p, h, 1)
    np.testing.assert_allclose(
        out, ref.expert_ffn(cfg, ref.dequantized(p), 1, h), atol=1e-4)


# -- the kernels at these geometries, in interpret mode ------------------------------
def _paged(rng, rows, hk, dk, dv, bs, pages_per_row, ctx):
    n_pages = rows * pages_per_row + 1
    k = rng.standard_normal((n_pages * bs, hk, dk)).astype(np.float32)
    v = rng.standard_normal((n_pages * bs, hk, dv)).astype(np.float32)
    tables = 1 + np.arange(rows * pages_per_row, dtype=np.int32).reshape(
        rows, pages_per_row)
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables), \
        jnp.asarray(ctx, jnp.int32)


def _dense_attention(q, k, v, tables, q_pos, ctx, bs, window, sinks, scale):
    """Plain numpy: q [B, T, H, dk] at positions q_pos [B, T]."""
    B, T, H, _ = q.shape
    hk = k.shape[1]
    out = np.zeros((B, T, H, v.shape[-1]), np.float64)
    for b in range(B):
        for t in range(T):
            p = int(q_pos[b, t])
            if p < 0 or p >= ctx[b]:
                continue
            lo = 0 if window is None else max(0, p - window + 1)
            js = np.arange(lo, p + 1)
            slots = np.asarray(tables)[b, js // bs] * bs + js % bs
            for h in range(H):
                kv = h // (H // hk)
                s = np.asarray(k)[slots, kv] @ np.asarray(q)[b, t, h] * scale
                logits = s if sinks is None else np.append(s, sinks[h])
                e = np.exp(logits - logits.max())
                pr = (e / e.sum())[: len(js)]
                out[b, t, h] = pr @ np.asarray(v)[slots, kv]
    return out


@pytest.mark.parametrize("window,sinks", [(None, False), (12, True), (40, True),
                                          (12, False), (None, True)])
def test_decode_kernel_with_wider_keys_a_window_and_sinks(window, sinks):
    rng = np.random.default_rng(7)
    B, H, hk, dk, dv, bs, W = 3, 8, 4, 32, 16, 8, 6
    ctx = [41, 0, 13]
    k, v, tables, ctx_a = _paged(rng, B, hk, dk, dv, bs, W, ctx)
    q = jnp.asarray(rng.standard_normal((B, H, dk)), jnp.float32)
    sink = rng.standard_normal(H).astype(np.float32) if sinks else None
    if window is not None:
        # dead columns: released, then pointing at a scribbled page
        t = np.asarray(tables).copy()
        for b, c in enumerate(ctx):
            t[b, : max(0, c - window) // bs] = 0
        tables = jnp.asarray(t)
        k = k.at[:bs].set(1e4)
        v = v.at[:bs].set(1e4)
    got = pa.paged_attention_decode(
        q, k, v, tables, ctx_a, block_size=bs, sliding_window=window,
        sinks=None if sink is None else jnp.asarray(sink), scale=24 ** -0.5,
        interpret=True)
    assert got.shape == (B, H, dv)
    want = _dense_attention(
        np.asarray(q)[:, None], k, v, tables,
        np.asarray(ctx)[:, None] - 1, ctx, bs, window, sink, 24 ** -0.5)[:, 0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.all(np.asarray(got)[1] == 0)           # a row of context 0


@pytest.mark.parametrize("window,sinks", [(None, False), (12, True), (5, True)])
def test_prefill_kernel_with_wider_keys_a_window_and_sinks(window, sinks):
    rng = np.random.default_rng(8)
    B, T, H, hk, dk, dv, bs, W = 2, 16, 8, 4, 32, 16, 8, 5
    start, ctx = [16, 3], [32, 11]                   # row 1: 8 real queries
    k, v, tables, ctx_a = _paged(rng, B, hk, dk, dv, bs, W, ctx)
    q = jnp.asarray(rng.standard_normal((B, T, H, dk)), jnp.float32)
    sink = rng.standard_normal(H).astype(np.float32) if sinks else None
    got = pa.paged_attention_prefill_stacked(
        q, k[None], v[None], jnp.int32(0), tables, jnp.asarray(start, jnp.int32),
        ctx_a, block_size=bs, sliding_window=window,
        sinks=None if sink is None else jnp.asarray(sink), scale=24 ** -0.5,
        interpret=True)
    assert got.shape == (B, T, H, dv)
    q_pos = np.asarray(start)[:, None] + np.arange(T)[None]
    want = _dense_attention(q, k, v, tables, q_pos, ctx, bs, window, sink,
                            24 ** -0.5)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_kernels_defaults_are_what_the_dense_families_call():
    """No sinks, no scale, equal widths: the arguments the llama family
    passes; the result is the plain softmax at ``Dh ** -0.5``."""
    rng = np.random.default_rng(9)
    B, H, hk, d, bs, W = 2, 4, 2, 16, 8, 3
    ctx = [20, 9]
    k, v, tables, ctx_a = _paged(rng, B, hk, d, d, bs, W, ctx)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    got = pa.paged_attention_decode(q, k, v, tables, ctx_a, block_size=bs,
                                    interpret=True)
    want = _dense_attention(np.asarray(q)[:, None], k, v, tables,
                            np.asarray(ctx)[:, None] - 1, ctx, bs, None, None,
                            d ** -0.5)[:, 0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert pa.decode_pages_per_block(128, 8, 128, 2) == 4
    assert pa.decode_pages_per_block(128, 4, 128, 2) == 8
    assert pa.decode_pages_per_block(128, 8, 256, 2, 128) == 4
    assert pa.decode_pages_per_block(128, 4, 256, 2, 128) == 8


def test_the_step_through_the_kernels_meets_the_reference(monkeypatch):
    """The family's kernel branches (decode over the absolute window
    table, prefill over the gathered live columns) in interpret mode."""
    monkeypatch.setattr(mm, "kernels_active", lambda: True)
    cfg = tiny_mimo()
    params = mm.init_params(cfg, seed=3, dtype=jnp.float32)
    tokens = np.random.default_rng(6).integers(3, 250, size=48).tolist()
    want = np.asarray(ref.forward(cfg, params, jnp.asarray([tokens])))[0]
    got, planes, _ = run_program(cfg, params, tokens, 8, 16)
    for at, logits in got:
        np.testing.assert_allclose(logits, want[at], atol=TOL, rtol=TOL)
    assert planes.plane.released_total > 0


# -- window columns, slots and counts --------------------------------------------
def test_window_columns_are_the_live_span_rebased():
    tables = jnp.asarray(np.arange(100, 120, dtype=np.int32).reshape(1, 20))
    sub, base = mm.window_columns(tables, jnp.asarray([37]), 12, 8, 16)
    # queries 37 .. 52 read keys 26 .. 52: columns 3 .. 6
    assert mm.window_span(12, 8, 16, 20) == 5
    assert np.asarray(sub).tolist() == [[103, 104, 105, 106, 107]]
    assert int(base[0]) == 24
    sub, base = mm.window_columns(tables, jnp.asarray([155]), 12, 8, 1)
    assert np.asarray(sub).tolist() == [[118, 119, 119]] and int(base[0]) == 144
    assert mm.window_span(128, 128, 1024, 128) == 10
    assert mm.window_span(128, 128, 1, 128) == 2
    for w, bs, t in ((12, 8, 16), (128, 128, 1), (9, 16, 5), (1, 4, 1)):
        assert mm.window_span(w, bs, t, 1 << 20) \
            == WindowPlane(2, bs, w).span_pages(t)


def test_window_slots_follow_the_table_and_padding_goes_to_the_garbage_slot():
    tables = jnp.asarray([[0, 0, 7, 9], [4, 0, 0, 0]], jnp.int32)
    positions = jnp.asarray([[22, 23, 24], [3, 0, 0]], jnp.int32)
    full_slots = jnp.asarray([50, 51, 52, 19, 0, 0], jnp.int32)
    got = mm.window_slots(tables, positions, full_slots, 8)
    assert np.asarray(got).tolist() == [7 * 8 + 6, 7 * 8 + 7, 9 * 8, 4 * 8 + 3, 0, 0]


def test_the_counts_are_the_pairs_and_keys_by_position(monkeypatch):
    monkeypatch.setattr(mm, "PAIR_UNIT", 1)
    cfg = tiny_mimo()
    g = mm.Geometry(cfg)
    w = g.window
    start, n = np.asarray([0, 20, 7]), np.asarray([5, 16, 0])
    got = np.asarray(mm.attended(
        g, 16, jnp.asarray(start), jnp.asarray(n), jnp.asarray(start + n)))
    full = sum(p + 1 for s, m in zip(start, n) for p in range(s, s + m))
    win = sum(min(p + 1, w) for s, m in zip(start, n) for p in range(s, s + m))
    assert got.tolist() == [2 * full, 2 * win, 0, 0, 2, 2, 0, 0]
    ctx = np.asarray([30, 0, 5])
    got = np.asarray(mm.attended(g, 1, jnp.asarray(ctx - 1), jnp.asarray([1, 0, 1]),
                                 jnp.asarray(ctx)))
    assert got.tolist() == [0, 0, 2 * 35, 2 * (12 + 0 + 5), 0, 0, 2, 2]
    assert mm.COUNT_NAMES[:3] == hybrid.MOE_COUNT_NAMES
    assert len(mm.COUNT_NAMES) == 11
