"""``deepseek_v3`` below the engine: the layer mathematics of
``models/deepseek_v3.py`` against the plain reference
(``models/reference/deepseek_v3.py``): the rotary part against HF's
de-interleave-then-``rotate_half`` form written out here, absorbed decode
against the reference's non-absorbed heads, the flash prefill kernel
against the gathered form, the routing rule with a selection bias, the
seeded recipe, and what the family refuses by name."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import ModelConfig, deepseek_v3 as ds, family, hybrid
from dynamo_tpu.models.reference import deepseek_v3 as ref
from tests.deepseek_v3_tiny import tiny_deepseek

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def published():
    with open(os.path.join(REPO, "perf", "configs", "kanana-2-30b.json")) as f:
        raw = json.load(f)
    return ModelConfig.from_dict(raw), raw


def test_the_benchmark_configuration_parses():
    cfg, raw = published()
    g = ds.Geometry(cfg)
    assert family(cfg) is ds
    assert cfg.owns_pages and not cfg.has_recurrent_state
    assert (g.L, g.D, g.V, g.H) == (12, 2048, 128256, 32)
    assert (g.nope, g.rope, g.vd, g.rank, g.C, g.Cpad) == (128, 64, 128, 512, 576, 640)
    assert (g.F, g.Fe, g.Fs, g.E, g.k) == (6144, 768, 1536, 128, 6)
    assert g.dense_layers == [0] and g.moe_layers == list(range(1, 12))
    assert cfg.rope_theta == 1e6 and cfg.rope_interleave and cfg.rope_scaling is None
    assert cfg.max_position_embeddings == raw["published"]["max_position_embeddings"]
    assert raw["reduced"] == ["num_hidden_layers"]


def test_weights_and_pages_at_this_repos_byte_are_what_the_issue_reckoned():
    cfg, _ = published()
    shapes = ds.param_shapes(cfg)
    total = sum(int(np.prod(shape)) for name, (shape, _) in shapes.items()
                if name in ds.QUANT_AXIS)
    assert 7.6e9 < total < 7.7e9
    layer_attention = sum(int(np.prod(shapes[n][0][1:])) for n in (
        "mla_wq", "mla_wkva", "mla_wkvb", "mla_wo"))
    assert round(layer_attention / 1e6, 2) == 26.35
    # a page: 128 tokens x 640 lanes x 2 B x 12 layers
    assert ds.page_bytes_per_block(cfg, 128, 2) == 128 * 640 * 2 * 12 == 1966080


@pytest.mark.parametrize("bad, named", [
    (dict(q_lora_rank=64), "q_lora_rank"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "n_group"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(qk_head_dim=32), "qk_head_dim"),
])
def test_what_is_not_built_is_refused_by_the_keys_name(bad, named):
    with pytest.raises(ValueError, match=named):
        ds.param_shapes(tiny_deepseek(**bad))


def test_check_engine_names_what_it_refuses():
    from dynamo_tpu.engine.config import EngineConfig

    ok = EngineConfig(model_name="x", kv_cache_dtype="bfloat16")
    ds.check_engine(ok)
    for kw, named in ((dict(tensor_parallel_size=2), "tensor_parallel_size"),
                      (dict(host_kv_blocks=4), "host_kv_blocks"),
                      (dict(kv_cache_dtype="int8"), "int8"),
                      (dict(spec_decode="ngram"), "spec_decode")):
        with pytest.raises(ValueError, match=named):
            ds.check_engine(EngineConfig(model_name="x", **kw))
    with pytest.raises(ValueError, match="int8 latent cache"):
        ds.init_cache(tiny_deepseek(), 4, 8, dtype=jnp.int8)


def test_the_seeded_recipe():
    cfg = tiny_deepseek()
    names = list(ds.param_shapes(cfg))
    assert names == [
        "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm", "mla_wq",
        "mla_wkva", "mla_kvnorm", "mla_wkvb", "mla_wo", "w_gate", "w_up",
        "w_down", "router", "router_bias", "ws_gate", "ws_up", "ws_down",
        "we_gate", "we_up", "we_down"]
    p = ds.init_params(cfg, seed=4, dtype=jnp.float32)
    root = jax.random.PRNGKey(4)
    i = names.index("we_up")
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(root, i), 1), 3)
    want = jax.random.normal(key, (64, 32), jnp.float32) / np.sqrt(64)
    np.testing.assert_allclose(p["we_up"][1, 3], want, rtol=1e-6)
    assert np.all(np.asarray(p["mla_kvnorm"]) == 1)
    assert np.all(np.asarray(p["router_bias"]) == 0)
    assert p["router"].dtype == jnp.float32
    p8 = ds.init_params_quantized(cfg, seed=4)
    assert p8["mla_wkva"].dtype == jnp.int8 and p8["router"].dtype == jnp.float32
    assert p8["mla_wkva_scale"].shape == (3, 40)
    w = ref.dequantized(p8)
    np.testing.assert_allclose(
        np.asarray(w["mla_wq"][2]),
        np.asarray(p8["mla_wq"][2], np.float32) * np.asarray(p8["mla_wq_scale"][2]))


# -- rotary -----------------------------------------------------------------------
def hf_apply_rotary_pos_emb_interleave(q, k, cos, sin):
    """HF ``modeling_deepseek_v3.apply_rotary_pos_emb_interleave`` written
    out: q [B, H, T, d], k [B, 1, T, d], cos / sin [B, T, d]."""
    def rotate_half(x):
        x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
        return np.concatenate([-x2, x1], axis=-1)

    cos, sin = cos[:, None], sin[:, None]
    b, h, s, d = q.shape
    q = q.reshape(b, h, s, d // 2, 2).swapaxes(4, 3).reshape(b, h, s, d)
    b, h, s, d = k.shape
    k = k.reshape(b, h, s, d // 2, 2).swapaxes(4, 3).reshape(b, h, s, d)
    return q * cos + rotate_half(q) * sin, k * cos + rotate_half(k) * sin


def test_rotary_gives_hfs_dot_products():
    rng = np.random.default_rng(0)
    B, H, T, d, theta = 2, 3, 7, 8, 1e6
    q = rng.normal(size=(B, T, H, d)).astype(np.float32)
    k = rng.normal(size=(B, T, d)).astype(np.float32)
    pos = np.stack([np.arange(T), np.arange(T) + 11])
    inv = theta ** (-np.arange(0, d, 2) / d)
    freqs = pos[..., None] * inv                        # HF: emb = cat(freqs, freqs)
    emb = np.concatenate([freqs, freqs], -1)
    hq, hk = hf_apply_rotary_pos_emb_interleave(
        q.transpose(0, 2, 1, 3), k[:, None], np.cos(emb), np.sin(emb))
    want = np.einsum("bhtd,bsd->bhts", hq, hk[:, 0])
    for rotate in (
        lambda x: hybrid.rotary_pairs(jnp.asarray(x), jnp.asarray(pos), theta, True),
        lambda x: ref.rotate(jnp.asarray(x), jnp.asarray(pos), theta, True),
    ):
        got = np.einsum("bthd,bsd->bhts", np.asarray(rotate(q)), np.asarray(rotate(k)))
        np.testing.assert_allclose(got, want, atol=1e-4)
    # the half-split form IS rotate_half without the move
    half = np.asarray(hybrid.rotary_pairs(jnp.asarray(k), jnp.asarray(pos), theta, False))
    x1, x2 = k[..., :4], k[..., 4:]
    np.testing.assert_allclose(
        half, k * np.cos(emb) + np.concatenate([-x2, x1], -1) * np.sin(emb), atol=1e-5)
    np.testing.assert_allclose(
        half, np.asarray(ref.rotate(jnp.asarray(k), jnp.asarray(pos), theta, False)),
        atol=1e-6)


# -- routing -------------------------------------------------------------------------
def test_top_k_is_by_score_plus_bias_and_the_weights_are_the_scores():
    cfg = tiny_deepseek()
    p = dict(ds.init_params(cfg, seed=1, dtype=jnp.float32))
    bias = np.zeros((2, 8), np.float32)
    bias[0, 5] = 10.0                  # expert 5 is always chosen in layer 0 ...
    bias[0, 2] = -10.0                 # ... and expert 2 never
    p["router_bias"] = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    w, topi = ds.moe_routing(cfg, p, x, 0)
    topi, w = np.asarray(topi), np.asarray(w)
    assert np.all((topi == 5).any(-1)) and not (topi == 2).any()
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(p["router"][0])))
    chosen = np.take_along_axis(s, topi, -1)           # WITHOUT the bias
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * 2.448, rtol=1e-5)
    rw, rtopi = ref.routing(cfg, ref.dequantized(p), 0, x)
    assert np.array_equal(np.sort(np.asarray(rtopi)), np.sort(topi))
    np.testing.assert_allclose(np.sort(np.asarray(rw)), np.sort(w), rtol=1e-5)


# -- the step ------------------------------------------------------------------------
def prefill_then_decode(cfg, p, kernels, monkeypatch, dtype=jnp.float32, steps=3):
    """Two rows of unequal length and a garbage row: one prefill
    rectangle, then ``steps`` decode steps; the logits of each."""
    monkeypatch.setattr(ds, "kernels_active", lambda: kernels)
    bs = 8
    tables = np.array([[1, 2, 3, 0], [4, 5, 6, 0], [0, 0, 0, 0]], np.int32)
    lens = [19, 11]
    pages, counts = ds.init_cache(cfg, 8, bs, dtype=dtype)
    T = 32
    t, pos = np.zeros((3, T), np.int32), np.zeros((3, T), np.int32)
    sm = np.zeros((3, T), np.int32)
    toks = np.random.default_rng(7).integers(0, 256, (2, 24)).astype(np.int32)
    for r, n in enumerate(lens):
        t[r, :n], pos[r, :n] = toks[r, :n], np.arange(n)
        sm[r, :n] = [tables[r, i // bs] * bs + i % bs for i in range(n)]
    logits, pages, counts = ds.forward(
        cfg, p, pages, counts, t, pos, sm.reshape(-1), tables,
        np.array(lens + [0], np.int32), np.array([18, 10, 0], np.int32), bs)
    outs = [np.asarray(logits[:2], np.float32)]
    for step in range(steps):
        cur = [n + step for n in lens]
        t1 = np.array([[toks[0, cur[0]]], [toks[1, cur[1]]], [0]], np.int32)
        p1 = np.array([[cur[0]], [cur[1]], [0]], np.int32)
        s1 = np.array([tables[r, c // bs] * bs + c % bs
                       for r, c in enumerate(cur)] + [0], np.int32)
        logits, pages, counts = ds.forward(
            cfg, p, pages, counts, t1, p1, s1, tables,
            np.array([c + 1 for c in cur] + [0], np.int32),
            np.zeros((3,), np.int32), bs)
        outs.append(np.asarray(logits[:2], np.float32))
    return np.stack(outs), toks, lens, np.asarray(counts["counts"])


def reference_at(cfg, p, toks, lens, steps):
    """The reference's logits at the same positions: [1 + steps, 2, V]."""
    out = np.zeros((1 + steps, 2, cfg.vocab_size), np.float32)
    for r, n in enumerate(lens):
        logits = np.asarray(ref.forward(cfg, p, jnp.asarray(toks[r:r + 1, :n + steps])))[0]
        out[:, r] = logits[n - 1:n + steps]
    return out


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_prefill_and_absorbed_decode_meet_the_non_absorbed_reference(kernels, monkeypatch):
    """float32 end to end: the absorbed scores and latent-space values
    are the reference's per-head ones up to summation order — through
    the gathered XLA forms and through both Pallas kernels (interpreted
    here: flash prefill over the rows' own pages, flash decode)."""
    cfg = tiny_deepseek()
    p = ds.init_params(cfg, seed=9, dtype=jnp.float32)
    got, toks, lens, counts = prefill_then_decode(cfg, p, kernels, monkeypatch)
    np.testing.assert_allclose(got, reference_at(cfg, p, toks, lens, 3), atol=2e-4)
    # 2 expert layers x 4 calls; 30 real prefill tokens x 3 layers
    assert counts[0] == 8 and counts[3] == 3 * 30


def test_bfloat16_stays_within_its_rounding_of_the_reference(monkeypatch):
    """bf16 matrices, operands, pages AND logits (8 bits of mantissa:
    a relative step of 2**-8, so a logit of magnitude 1-4 is itself
    rounded by up to 0.008-0.016) under a float32 residual stream,
    against the float32 reference of the SAME bf16-rounded weights. The
    stated tolerance: a mean of 0.02 — a few such roundings adding in
    quadrature over 3 layers — and a maximum of 0.25 over the 2 048
    logits, since one token whose router scores tie to within bf16 can
    take another expert and move by ten times the mean."""
    cfg = tiny_deepseek()
    p = ds.init_params(cfg, seed=9)                    # bf16 matrices
    got, toks, lens, _ = prefill_then_decode(cfg, p, False, monkeypatch,
                                             dtype=jnp.bfloat16)
    want = reference_at(cfg, p, toks, lens, 3)
    assert np.abs(got - want).mean() < 0.02
    assert np.abs(got - want).max() < 0.25


def test_absorbed_decode_gives_the_references_heads(monkeypatch):
    """One layer's attention alone: the program's decode step (queries
    absorb W_kvb's key half, its value half applied after) against the
    heads the reference builds from k_h and v_h."""
    cfg = tiny_deepseek(num_hidden_layers=1, first_k_dense_replace=1)
    p = ds.init_params(cfg, seed=3, dtype=jnp.float32)
    w = ref.dequantized(p)
    g = ds.Geometry(cfg)
    rng = np.random.default_rng(5)
    T, bs = 13, 8
    h = jnp.asarray(rng.normal(size=(1, T, 64)), jnp.float32)
    want = np.asarray(ref.attention(cfg, w, 0, h))              # [1, T, D]
    tables = jnp.asarray([[1, 2]], jnp.int32)
    slots = np.array([8 + i for i in range(T)], np.int32)
    latent = jnp.zeros((1, 4 * bs, g.Cpad), jnp.float32)
    pos = jnp.arange(T)[None]

    def rotate_at(positions):
        return lambda x: hybrid.rotary_pairs(x, positions, 10000.0, True)

    # prefill 12 tokens, then decode the 13th
    _, latent = hybrid.mla_mixer(
        p, h[:, :12], 0, latent, g.latent, cfg.rms_norm_eps, pos[:, :12],
        jnp.asarray(slots[:12]), tables, jnp.asarray([12]), bs, False,
        rotate=rotate_at(pos[:, :12]))
    for kernels in (False, True):
        out, _ = hybrid.mla_mixer(
            p, h[:, 12:], 0, latent, g.latent, cfg.rms_norm_eps, pos[:, 12:],
            jnp.asarray(slots[12:]), tables, jnp.asarray([13]), bs, kernels,
            rotate=rotate_at(pos[:, 12:]))
        np.testing.assert_allclose(np.asarray(out)[0, 0], want[0, 12], atol=2e-5)


@pytest.mark.parametrize("pages_per_block", [1, 2, 3, None])
def test_flash_prefill_over_cached_pages_is_the_gathered_form(pages_per_block):
    """A chunk that starts at position 16 over two cached pages, beside a
    row that starts at 0 and a garbage row: the kernel (interpreted), at
    one, two and three pages a compute block and at the rule's own,
    against plain XLA over the gathered table."""
    from dynamo_tpu.ops.mla import mla_prefill_attention

    rng = np.random.default_rng(1)
    bs, H, C, rank, T = 8, 4, 128, 96, 16
    latent = jnp.asarray(rng.normal(size=(2, 10 * bs, C)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, T, H, C)) * 0.2, jnp.float32)
    tables = jnp.asarray([[3, 4, 5, 6, 0], [7, 8, 0, 0, 0], [0, 0, 0, 0, 0]], jnp.int32)
    start = jnp.asarray([16, 0, 0], jnp.int32)
    ctx = jnp.asarray([29, 11, 0], jnp.int32)
    got = np.asarray(mla_prefill_attention(
        q, latent, jnp.int32(1), tables, start, ctx, block_size=bs, rank=rank,
        interpret=True, pages_per_block=pages_per_block))
    rows = np.asarray(latent)[1][(np.asarray(tables)[:, :, None] * bs
                                  + np.arange(bs)).reshape(3, -1)]   # [3, S, C]
    for b in range(2):
        n = int(ctx[b] - start[b])
        for t in range(n):
            p_abs = int(start[b]) + t
            s = np.einsum("hc,sc->hs", np.asarray(q)[b, t], rows[b, :p_abs + 1])
            pr = np.exp(s - s.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            np.testing.assert_allclose(
                got[b, t], pr @ rows[b, :p_abs + 1, :rank], atol=2e-5)
    assert np.isfinite(got).all()


def test_right_padding_and_a_garbage_row_do_not_move_the_logits(monkeypatch):
    cfg = tiny_deepseek()
    p = ds.init_params(cfg, seed=9, dtype=jnp.float32)
    base, *_ = prefill_then_decode(cfg, p, False, monkeypatch, steps=1)
    monkeypatch.setattr(ds, "kernels_active", lambda: False)
    # the same first row alone, its rectangle exactly as long as it is
    bs = 8
    toks = np.random.default_rng(7).integers(0, 256, (2, 24)).astype(np.int32)
    pages, counts = ds.init_cache(cfg, 8, bs, dtype=jnp.float32)
    n = 19
    sm = np.array([(1 + i // bs) * bs + i % bs for i in range(n)], np.int32)
    logits, *_ = ds.forward(
        cfg, p, pages, counts, toks[:1, :n], np.arange(n)[None], sm,
        np.array([[1, 2, 3]], np.int32), np.array([n], np.int32),
        np.array([n - 1], np.int32), bs)
    np.testing.assert_allclose(np.asarray(logits)[0], base[0, 0], atol=2e-5)
