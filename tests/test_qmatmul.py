"""Fused int8 dequant-matmul kernels (ops/qmatmul.py): numerics vs the
reference ``mm()`` path, every fused epilogue variant, the engine-level
greedy bit-identity contract (DYN_MATMUL_IMPL=reference vs =pallas in
interpret mode — ISSUE 9 acceptance), and the one tile rule
(``default_tiles``) at every served configuration's shapes.

All kernel calls run ``interpret=True`` (tier-1 is CPU); the engine
tests register a size-1 mesh through JaxEngine.launch so
``pallas_matmul_active()`` holds exactly as it does on a single chip.
"""

import asyncio
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops import qmatmul
from dynamo_tpu.ops.qmatmul import (
    default_tiles,
    m_bucket,
    qmm,
    qmm_gate_up,
    qmm_lm_head,
)

RNG = np.random.default_rng(7)


def _mk(m, k, n, dtype=jnp.bfloat16, lead=()):
    x = jnp.asarray(RNG.standard_normal((*lead, m, k)), dtype)
    w = jnp.asarray(RNG.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(RNG.uniform(0.001, 0.02, n), jnp.float32)
    return x, w, s


def _ref_mm(x, w, s):
    """The reference mm() epilogue: mixed dot, f32 accumulate, scale in
    f32, round to the activation dtype."""
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (y * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# Kernel numerics
# ---------------------------------------------------------------------------


def test_qmm_f32_epilogue_exact_single_k_tile():
    """With one K tile there is no accumulation-order freedom: the int8
    upcast, the f32 products, and the f32 scale multiply must be EXACT
    against the reference dot (int8 -> float is lossless, products of
    floats are exact in f32 preferred-type accumulation)."""
    x, w, s = _mk(5, 64, 256, dtype=jnp.float32)
    y = qmm(x, w, s, interpret=True)  # K=64 -> bk=K (single tile)
    ref = _ref_mm(x, w, s)
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


def test_qmm_bf16_within_tolerance_tiled_k():
    """Forced multi-tile K: only accumulation ORDER differs from the
    reference, so the bf16 outputs may differ by at most ~1 ulp."""
    x, w, s = _mk(33, 512, 384)
    y = qmm(x, w, s, interpret=True, tiles=(64, 128, 128))
    ref = _ref_mm(x, w, s)
    a, b = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    # 1 bf16 ulp at the observed magnitudes (~|x| <= 8 here)
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=6e-2)
    assert y.shape == (33, 384)  # padded rows sliced back off


def test_qmm_leading_batch_dims():
    x, w, s = _mk(6, 64, 128, lead=(3,))
    y = qmm(x, w, s, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(_ref_mm(x, w, s), np.float32)
    )


def test_qmm_residual_epilogue_matches_reference_rounding():
    """residual + (acc*scale).astype(dtype): the add happens in the
    OUTPUT dtype, exactly like the reference ``x + mm(...).astype``
    composition — single K tile makes it bit-exact."""
    x, w, s = _mk(8, 128, 256)
    r = jnp.asarray(RNG.standard_normal((8, 256)), jnp.bfloat16)
    y = qmm(x, w, s, residual=r, interpret=True)
    ref = r + _ref_mm(x, w, s)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(ref, np.float32)
    )


@pytest.mark.parametrize("act", ["silu", "gelu"])
def test_qmm_gate_up_fused(act):
    """act(x@Wg*sg) * (x@Wu*su) with both matmul outputs rounded to the
    activation dtype BEFORE the activation — the reference
    ``mlp_act(mm(gate)) * mm(up)`` rounding points. silu keeps the
    reference's two roundings (sigmoid, then the product); tanh-gelu's
    longer chain runs in f32 and rounds once at its end (the chip has no
    bf16 vector unit, and XLA's own fused bf16 chain keeps f32
    intermediates there), so its oracle is the f32 chain on the rounded
    gate."""
    x, wg, sg = _mk(8, 128, 256)
    _, wu, su = _mk(8, 128, 256)
    y = qmm_gate_up(x, wg, sg, wu, su, act=act, interpret=True)
    g, u = _ref_mm(x, wg, sg), _ref_mm(x, wu, su)
    if act == "gelu":
        a = jax.nn.gelu(g.astype(jnp.float32), approximate=True).astype(g.dtype)
    else:
        a = jax.nn.silu(g)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(a * u, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_qmm_gate_up_rejects_unknown_act():
    x, wg, sg = _mk(8, 128, 128)
    with pytest.raises(ValueError, match="unsupported activation"):
        qmm_gate_up(x, wg, sg, wg, sg, act="relu6", interpret=True)


def _assert_within_one_bf16_ulp(y, ref) -> None:
    """Kernel and reference feed the same exact products to f32
    accumulators and differ only in summation ORDER (XLA:CPU blocks the
    mixed bf16 x int8 dot and the kernel's padded bf16 x bf16 tile dot
    differently), so the f32 sums differ in their last bits and the
    bf16 roundings by at most one ulp (2^-7 relative)."""
    a, b = np.asarray(y, np.float32), np.asarray(ref, np.float32)
    bound = np.maximum(np.abs(a), np.abs(b)) * 2.0 ** -7
    assert np.all(np.abs(a - b) <= bound), float(np.max(np.abs(a - b) - bound))


def test_qmm_lm_head_vocab_tiled():
    """The vocab-tiled variant over a non-power-of-two N that only a
    subset of tile widths divide (128256 = 167 * 768 — the real
    flagship vocab's divisibility structure, scaled down)."""
    V = 768 * 3
    x, w, s = _mk(4, 64, V)
    y = qmm_lm_head(x, w, s, interpret=True)
    assert y.shape == (4, V) and y.dtype == x.dtype
    _assert_within_one_bf16_ulp(y, _ref_mm(x, w, s))


# ---------------------------------------------------------------------------
# Tile selection
# ---------------------------------------------------------------------------


def test_m_bucket_monotonic():
    assert m_bucket(1) == 8
    assert m_bucket(8) == 8
    assert m_bucket(9) == 16
    assert m_bucket(64) == 64
    # beyond the ladder the bucket rounds UP (rounding down would make
    # the pad width negative and crash the wrapper)
    top = qmatmul.M_BUCKETS[-1]
    assert m_bucket(top + 1) == 2 * top
    assert m_bucket(3 * top) == 3 * top


def test_qmm_m_above_largest_bucket():
    """M past the bucket ladder (e.g. a wide prefill rectangle) must
    compute, not crash on a negative pad."""
    top = qmatmul.M_BUCKETS[-1]
    x, w, s = _mk(top + 3, 64, 128, dtype=jnp.float32)
    y = qmm(x, w, s, interpret=True)
    assert y.shape == (top + 3, 128)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(_ref_mm(x, w, s)))


def test_qmm_rejects_non_dividing_explicit_tiles():
    """An explicit `tiles` that doesn't divide the problem must fail
    loudly (a silent floor-
    divided grid would leave output columns unwritten)."""
    x, w, s = _mk(8, 256, 256)
    with pytest.raises(ValueError, match="must divide"):
        qmm(x, w, s, interpret=True, tiles=(8, 200, 256))


def _kimi_qmm_shapes() -> list[tuple[int, int, str]]:
    """(K, N, kind) of every ``qmm`` call of ``kimi-linear-48b`` at its
    published widths: the stacked weights ``models/kimi_linear.py``
    hands ``_mm`` (which takes the kernel where both dimensions are
    multiples of 128) and the head, which goes through
    ``llama.lm_head``. Shapes come from the model's ``param_shapes`` over
    the benchmark's configuration file."""
    from dynamo_tpu.models import kimi_linear
    from dynamo_tpu.models.config import ModelConfig

    path = os.path.join(
        os.path.dirname(__file__), "..", "perf", "configs",
        "kimi-linear-48b.json",
    )
    with open(path) as f:
        shapes = kimi_linear.param_shapes(ModelConfig.from_dict(json.load(f)))
    through_mm = (
        "kda_wq", "kda_wk", "kda_wv", "kda_wfa", "kda_wfb", "kda_wb",
        "kda_wga", "kda_wgb", "kda_wo", "mla_wq", "mla_wkva", "mla_wo",
        "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
    )
    out = {
        (*shapes[n][0][-2:], "mm") for n in through_mm
        if all(d % 128 == 0 for d in shapes[n][0][-2:])
    }
    return sorted(out) + [(*shapes["lm_head"][0], "lm_head")]


_KIMI_QMM = _kimi_qmm_shapes()


@pytest.mark.parametrize(
    "mb,K,N,kind",
    [
        (64, 4096, 4096, "mm"),
        (64, 4096, 1024, "mm"),
        (64, 4096, 14336, "gate_up"),
        (64, 14336, 4096, "residual"),
        (64, 4096, 128256, "lm_head"),
        (8, 64, 96, "mm"),  # tiny/odd: full-dim fallbacks
        # kimi-linear-48b (hidden 2304 = 18 * 128) at its decode rows
        *[(mb, *shape) for shape in _KIMI_QMM for mb in (8, 32, 64)],
    ],
)
def test_default_tiles_always_legal(mb, K, N, kind):
    bm, bn, bk = default_tiles(mb, K, N, kind)
    assert mb % bm == 0 and N % bn == 0 and K % bk == 0
    assert bn == N or bn % 128 == 0
    assert bk == K or bk % 128 == 0


@pytest.mark.parametrize(
    "K,N,kind",
    [
        # mistral-7b: wq / wkv / gate+up / w_down / head
        (4096, 4096, "mm"), (4096, 1024, "mm"), (4096, 14336, "gate_up"),
        (14336, 4096, "residual"), (4096, 32000, "lm_head"),
        # qwen2.5-7b: 3584 = 7 * 512, 18944 = 37 * 512, 152064 = 99 * 1536
        (3584, 3584, "mm"), (3584, 512, "mm"), (3584, 18944, "gate_up"),
        (18944, 3584, "residual"), (3584, 152064, "lm_head"),
        # kimi-linear-48b: KDA / MLA projections, dense FFN, shared
        # expert, head. The 128 x 4096 bottleneck weights are 512 KB
        # and bn stops at DECODE_BN_MAX, so they stream as two 256 KB
        # tiles where one would do; repairing that changes the tiles the
        # kimi cell measures (PERF.md section 7)
        *[
            pytest.param(*shape, marks=pytest.mark.xfail(
                strict=True,
                reason="bn <= DECODE_BN_MAX splits a 512 KB weight",
            )) if shape[:2] == (128, 4096) else shape
            for shape in _KIMI_QMM
        ],
    ],
)
@pytest.mark.parametrize("mb", [8, 32, 64])
def test_decode_rows_stream_wide_weight_tiles(mb, K, N, kind):
    """At decode rows the weight tile is sized for the HBM stream (v5e,
    PERF.md PR 26: 256 KB tiles stream at half of HBM speed, >= 1 MB at
    76-79%): between half of DECODE_TILE and all of it at the served
    widths, never past it, and lane-aligned; a weight no larger than
    half of DECODE_TILE is one tile. Prefill rows keep the compute-sized
    tiles."""
    bm, bn, bk = default_tiles(mb, K, N, kind)
    assert bm == mb and N % bn == 0 and K % bk == 0
    assert bn % 128 == 0 and bk % 128 == 0
    if K * N <= qmatmul.DECODE_TILE // 2:
        assert (bn, bk) == (N, K)
        return
    assert qmatmul.DECODE_TILE // 2 <= bn * bk <= qmatmul.DECODE_TILE
    assert bn <= qmatmul.DECODE_BN_MAX
    _, pbn, pbk = default_tiles(256, K, N, kind)
    assert pbn * pbk <= 1024 * 512 < bn * bk


def test_lm_head_tiles_divide_flagship_vocab():
    # 128256 is not divisible by 512; the lm_head candidate ladder must
    # land on a divisor (768), not crash or fall back to full-V tiles
    _, bn, _ = default_tiles(64, 4096, 128256, "lm_head")
    assert 128256 % bn == 0 and bn >= 256


# ---------------------------------------------------------------------------
# Stacked weights: the kernel reads layer l of [L, K, N] in place
# ---------------------------------------------------------------------------

# (D, F) at test size with the served models' divisibility: Mistral-7B
# 4096/14336 over 8 (w_down's K = 1792 = 7 * 256), Qwen2.5-7B 3584/18944
# over 4 (every K a multiple of 7 * 128 — never a power of two)
_GEOMETRIES = {"mistral-like": (512, 1792), "qwen-like": (896, 4736)}
_L = 3


def _stacked_case(kind: str, D: int, F: int, dtype):
    """(call(weights, scales, layer), stacked weights, stacked scales)
    for one kind; ``layer`` may be an int, a traced scalar, or None with
    2-D slices."""
    rng = np.random.default_rng(11)
    K, N = {"mm": (D, D), "residual": (F, D), "gate_up": (D, F)}[kind]
    x = jnp.asarray(rng.standard_normal((8, K)), dtype)
    r = jnp.asarray(rng.standard_normal((8, N)), dtype)
    ws = [jnp.asarray(rng.integers(-127, 128, (_L, K, N)), jnp.int8)
          for _ in range(2)]
    ss = [jnp.asarray(rng.uniform(0.001, 0.02, (_L, N)), jnp.float32)
          for _ in range(2)]

    def call(w, s, layer):
        if kind == "gate_up":
            return qmm_gate_up(
                x, w[0], s[0], w[1], s[1], interpret=True, layer=layer
            )
        res = r if kind == "residual" else None
        return qmm(x, w[0], s[0], residual=res, interpret=True, layer=layer)

    return call, ws, ss


@pytest.mark.parametrize("layer", [0, 1, _L - 1])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("kind", ["mm", "residual", "gate_up"])
def test_stacked_weight_equals_its_layer_slice(kind, geometry, layer):
    """qmm over the whole [L, K, N] array with a layer index is the 2-D
    call on w[layer] BIT FOR BIT (same tiles, same accumulation order:
    only where the weight tile is fetched from differs), in bf16 and
    f32 — what lets the layer scan stop slicing its weights."""
    D, F = _GEOMETRIES[geometry]
    for dtype in (jnp.bfloat16, jnp.float32):
        call, ws, ss = _stacked_case(kind, D, F, dtype)
        got = call(ws, ss, jnp.int32(layer))
        want = call([w[layer] for w in ws], [s[layer] for s in ss], None)
        assert got.dtype == want.dtype == dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32)
        )


def test_stacked_layer_index_traced_in_scan():
    """The layer index is a traced scan counter, as in llama.forward:
    the stacked arrays are closed over (loop invariants) and every
    iteration reads its own layer."""
    D, F = 256, 384
    call, ws, ss = _stacked_case("gate_up", D, F, jnp.bfloat16)

    def body(carry, i):
        return carry, call(ws, ss, i)

    _, got = jax.jit(
        lambda: jax.lax.scan(body, 0, jnp.arange(_L, dtype=jnp.int32))
    )()
    for l in range(_L):
        want = call([w[l] for w in ws], [s[l] for s in ss], None)
        np.testing.assert_array_equal(
            np.asarray(got[l], np.float32), np.asarray(want, np.float32)
        )


def test_stacked_weight_rejects_mismatched_scale():
    x, w, s = _mk(8, 64, 128)
    with pytest.raises(AssertionError):
        qmm(x, jnp.stack([w, w]), s, interpret=True, layer=jnp.int32(1))


# ---------------------------------------------------------------------------
# Model-level dispatch + engine greedy bit-identity (the acceptance gate)
# ---------------------------------------------------------------------------


def test_matmul_impl_dispatch(monkeypatch):
    from dynamo_tpu.models import llama

    monkeypatch.setenv("DYN_MATMUL_IMPL", "reference")
    assert llama.matmul_impl() == "reference"
    assert not llama.pallas_matmul_active()
    monkeypatch.setenv("DYN_MATMUL_IMPL", "pallas")
    assert llama.matmul_impl() == "pallas"
    monkeypatch.delenv("DYN_MATMUL_IMPL")
    # auto off-TPU = reference (kernels only via explicit opt-in here)
    assert llama.matmul_impl() == "reference"


PROMPT = list(range(1, 20))


async def _engine_tokens(model_cfg, decode_steps: int):
    """(greedy tokens, the engine's params) for the fixed prompt."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    engine = await JaxEngine.launch(
        EngineConfig(
            model_path="", model_name="qmm", random_weights=True,
            quantization="int8", num_blocks=64, block_size=8,
            max_batch_size=4, decode_steps=decode_steps,
            kv_cache_dtype="int8",
        ),
        model_config=model_cfg,
    )
    try:
        req = PreprocessedRequest(
            request_id="q", token_ids=PROMPT,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=10, ignore_eos=True),
        )
        toks: list[int] = []
        async for out in engine.as_async_engine().generate(req, Context()):
            toks.extend(out.token_ids)
        # host copies: shutdown gives the device arrays back
        return toks, jax.device_get(engine.params)
    finally:
        await engine.shutdown()


def _reference_logits(mc, params, tokens: list[int]) -> np.ndarray:
    """Reference-impl logits for the token after ``tokens`` (one prefill
    over a fresh int8 cache, the engine's cache dtype)."""
    from dynamo_tpu.models.llama import forward, init_cache
    from dynamo_tpu.utils.testing import make_paged_inputs

    bs, T = 8, len(tokens)
    n_blocks = -(-T // bs)
    _, pos, slots, tables, ctx, last = make_paged_inputs(
        mc.vocab_size, 1, T, bs, n_blocks
    )
    k, v = init_cache(mc, n_blocks + 1, bs, dtype=jnp.int8)
    logits, _, _ = forward(
        mc, params, k, v, np.asarray([tokens], np.int32), pos, slots,
        tables, ctx, last, bs,
    )
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("decode_steps", [1, 2])
def test_engine_greedy_reference_vs_pallas(decode_steps, monkeypatch):
    """The engine's greedy output under DYN_MATMUL_IMPL=reference and
    =pallas (interpret mode on CPU), over the int8 KV cache, on both
    the single-step (overlapped pipeline) and fused-window decode
    paths. The two impls differ by f32 summation order (<= 1 bf16 ulp
    per matmul, see _assert_within_one_bf16_ulp), so on a random-weight
    model the streams agree until a NEAR-TIE: at the first token where
    they part, the reference's own logits for the two candidates must
    be within the tolerance the on-chip kernel check uses (2e-2 of the
    largest |logit|). Bit-identical streams were an accident of the
    older XLA:CPU dot blocking."""
    from dynamo_tpu.models.config import ModelConfig

    mc = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256,
    )
    monkeypatch.setenv("DYN_MATMUL_IMPL", "reference")
    ref, params = asyncio.run(_engine_tokens(mc, decode_steps))
    monkeypatch.setenv("DYN_MATMUL_IMPL", "pallas")
    pal, _ = asyncio.run(_engine_tokens(mc, decode_steps))
    assert len(ref) == len(pal) == 10
    assert ref[0] == pal[0]
    if ref == pal:
        return
    i = next(j for j in range(10) if ref[j] != pal[j])
    monkeypatch.setenv("DYN_MATMUL_IMPL", "reference")
    logits = _reference_logits(mc, params, PROMPT + ref[:i])
    tol = 2e-2 * float(np.max(np.abs(logits)))
    for tok in (ref[i], pal[i]):
        assert float(np.max(logits) - logits[tok]) <= tol, (i, tok)


# ---------------------------------------------------------------------------
# The weights are never sliced before a kernel (a count over the jaxpr)
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of whatever it nests — down to,
    not into, the kernels (their bodies read tiles, not HBM arrays)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(eqn):
                yield from _walk(sub)


async def _decode_step_layer_scan(mc):
    """The layer scan of the engine's own decode step: its equation,
    traced from ``JaxEngine._step_fn`` over the engine's parameters and
    caches with the arrays prewarm hands it (4 decode rows)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.sampling import SamplingBatch
    from dynamo_tpu.protocols.common import SamplingOptions

    engine = await JaxEngine.launch(
        EngineConfig(
            model_path="", model_name="qmm", random_weights=True,
            quantization="int8", num_blocks=16, block_size=8,
            max_batch_size=4, prewarm=False,
        ),
        model_config=mc,
    )
    try:
        b, width = 4, 4
        sampling = SamplingBatch.from_options(
            [SamplingOptions(use_greedy=True)] * b, [0] * b
        )
        jaxpr = jax.make_jaxpr(engine._step_fn)(
            engine.params, engine.k_cache, engine.v_cache,
            np.zeros((b, 1), np.int32), np.zeros((b, 1), np.int32),
            np.zeros((b,), np.int32), np.zeros((b, width), np.int32),
            np.zeros((b,), np.int32), np.zeros((b,), np.int32),
            sampling.arrays,
        )
    finally:
        await engine.shutdown()
    scans = [
        e for e in _walk(jaxpr.jaxpr)
        if e.primitive.name == "scan"
        and e.params["length"] == mc.num_hidden_layers
    ]
    assert len(scans) == 1
    return scans[0]


def _int8_matrices(avals):
    return [a.shape for a in avals if a.dtype == jnp.int8 and a.ndim == 2]


@pytest.mark.parametrize("impl", ["pallas", "reference"])
def test_decode_step_never_slices_a_weight_before_a_kernel(impl, monkeypatch):
    """On the Pallas path every qmm kernel of the layer body is handed
    the whole [L, K, N] int8 array (rank 3) and NOTHING in the scan —
    not its xs, not a dynamic_slice, not an index — yields an int8
    [K, N]: XLA would materialize such a slice in front of the custom
    call, one copy per weight per layer per step (PERF.md, PR 26). The
    reference path keeps scanning its weights: XLA fuses that slice
    into its own dot."""
    from dynamo_tpu.models.config import ModelConfig

    mc = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256,
    )
    monkeypatch.setenv("DYN_MATMUL_IMPL", impl)
    scan = asyncio.run(_decode_step_layer_scan(mc))
    body = scan.params["jaxpr"].jaxpr
    n_fixed = scan.params["num_consts"] + scan.params["num_carry"]
    xs = _int8_matrices(v.aval for v in body.invars[n_fixed:])
    if impl == "reference":
        # wq wk wv wo w_gate w_up w_down, sliced by the scan
        assert len(xs) == 7, xs
        return
    assert xs == []
    produced = [
        (e.primitive.name, shapes) for e in _walk(body)
        if (shapes := _int8_matrices(v.aval for v in e.outvars))
    ]
    assert produced == []
    kernels = [
        [v.aval.shape for v in e.invars if v.aval.dtype == jnp.int8]
        for e in _walk(body) if e.primitive.name == "pallas_call"
    ]
    kernels = [shapes for shapes in kernels if shapes]  # bf16 KV: qmm only
    # wq, wk, wv, wo + residual, gate+up (two weights), w_down + residual
    assert sorted(len(k) for k in kernels) == [1, 1, 1, 1, 1, 2]
    L = mc.num_hidden_layers
    assert all(len(s) == 3 and s[0] == L for k in kernels for s in k), kernels
