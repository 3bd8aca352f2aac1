"""Fused int8-weight × float-activation matmul Pallas kernels.

THE weight-bound decode hot path (ROADMAP item 2). The engine's int8
serving weights (models/quant.py) used to reach the MXU through a mixed
int8×bf16 ``jax.lax.dot_general`` — XLA materializes the upcast weight
tile in a way that never approaches int8-byte-bound (measured only
~1.3-2× over bf16 on v5e, far from the 2× byte ratio, and worse once
the scale multiply lands as a separate HBM-visiting op). These kernels
do what Marlin-style fused dequant GEMMs do on GPU: stream the int8
weight tiles from HBM, upcast **in register**, accumulate in f32, and
apply the per-output-channel f32 scale in the epilogue — the upcast
never exists in HBM, so the weight read is byte-bound at 1 B/elem.

Kernel family (one body, flag-specialized like ops/paged_attention.py):

- ``qmm``            — y = (x @ w_int8) * scale, optional fused
                        residual add in the epilogue (``wo`` / ``w_down``:
                        the decode residual never round-trips HBM between
                        the matmul and the add);
- ``qmm_gate_up``    — act(x @ Wg * sg) * (x @ Wu * su): both MLP weight
                        tensors stream through ONE kernel pass and the
                        SiLU·mul epilogue runs on the f32 accumulators'
                        tiles in VMEM (the [M, F] gate/up intermediates
                        never hit HBM);
- ``qmm_lm_head``    — the vocab-tiled variant: at V=128256 the LM head
                        is the single largest weight read of a decode
                        step, so it gets N-tiles of its own.

Numerics contract (tests/test_qmatmul.py): int8→bf16 upcast is exact,
products accumulate in f32, the dequant scale applies in f32, and the
output rounds to the activation dtype exactly like the reference
``models.llama.mm`` epilogue — residual adds and the SiLU·mul run in
the output dtype so both impls round at the same points. Remaining
differences vs the reference are K-tile accumulation ORDER only.

Weights are addressed as STACKED arrays: the operand is the model's
whole ``[L, K, N]`` parameter plus a layer index that rides as a
scalar-prefetch argument and is read by the weight (and scale)
BlockSpec's index map — the pattern of ``_decode_kernel_stacked``
(ops/paged_attention.py), for the same reason. XLA cannot fuse a
producer slice into a custom call: when the layer scan handed these
kernels a ``[K, N]`` slice of its ``xs``, XLA copied the slice out
first (``dynamic-slice_bitcast_fusion.*_s8_K_N``, one per weight per
layer per step, at ~92% of HBM read speed) and the kernel then read
the copy, so every weight byte crossed HBM twice in time — 45% of
device time at 8 decode rows, more than the kernels themselves (v5e,
PERF.md PR 26). A plain ``[K, N]`` weight (LM head, the pipeline stage
loop, selfcheck) is the same call with L = 1 and layer 0 — one body,
one pallas_call, no second path.

Grid = (M-tiles, N-tiles, K-tiles), K innermost: the f32 accumulator
lives in VMEM scratch across K steps and every weight byte is read
exactly once per M-tile. Tile sizes come from ``default_tiles``, a
function of (M-bucket, K, N, kind) alone: it reads no file and no
environment, so one commit runs one tiling wherever it is checked out.

Dispatch lives in ``models.llama.matmul_impl`` (DYN_MATMUL_IMPL =
auto|reference|pallas, mirroring DYN_ATTN_IMPL); off-TPU the kernels
run interpreted so tier-1 exercises them on CPU. Multi-device meshes
keep the reference path: the contraction axis of ``wo``/``w_down`` is
tp-sharded, and a shard_mapped qmatmul would need its own psum story —
single-chip decode is where the weight-bound win lives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# M (token-rows) buckets the tiles are chosen for; the wrapper pads
# every call up to its bucket (padded rows compute zeros and are sliced
# off), so one compiled kernel serves each bucket like the engine's
# batch buckets do.
M_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192)


def m_bucket(m: int) -> int:
    for b in M_BUCKETS:
        if m <= b:
            return b
    # beyond the ladder: round UP to a multiple of the largest bucket
    # (rounding down would make the pad width negative and crash; every
    # bm candidate <= 512 divides any multiple of 8192)
    top = M_BUCKETS[-1]
    return -(-m // top) * top


# ---------------------------------------------------------------------------
# Tile selection
# ---------------------------------------------------------------------------


def _largest_divisor(n: int, candidates: tuple[int, ...]) -> int:
    """Largest candidate dividing n, else n itself (a full dim is always
    a legal Mosaic block dim regardless of alignment)."""
    for c in candidates:
        if c <= n and n % c == 0:
            return c
    return n


# Decode row buckets (<= 64 rows) are weight-byte-bound: the kernel is
# a DMA stream of int8 tiles out of HBM, and a grid step's fixed cost
# (~0.3 us on v5e) is as long as the DMA of a 256 KB tile. Measured
# there at 8 rows (PERF.md PR 26): 256 KB tiles stream at 50-53% of
# HBM speed, >= 1 MB tiles at 76-79%, flat beyond; at equal bytes a
# wider bn beats a deeper bk (longer contiguous runs in the row-major
# weight). So: (bn, bk) of at most DECODE_TILE weight bytes, bn first,
# up to DECODE_BN_MAX.
DECODE_ROWS_MAX = 64
DECODE_TILE = 1 << 20
DECODE_BN_MAX = 2048


def _lane_divisors(n: int, cap: int) -> list[int]:
    """Multiples of 128 dividing n, largest first, none above cap; n
    itself when there is none (a full dim is always a legal block dim)."""
    return [
        d for d in range(min(cap, n) // 128 * 128, 0, -128) if n % d == 0
    ] or [n]


def _decode_weight_tile(K: int, N: int) -> tuple[int, int]:
    """(bn, bk): the widest bn whose deepest fitting bk fills at least
    half of DECODE_TILE; the largest tile there is when none does."""
    tiles = []
    bks = _lane_divisors(K, K)
    for bn in _lane_divisors(N, DECODE_BN_MAX):
        # the deepest bk this bn leaves room for (the shallowest if none)
        bk = next((b for b in bks if bn * b <= DECODE_TILE), bks[-1])
        if 2 * bn * bk >= DECODE_TILE:
            return bn, bk
        tiles.append((bn, bk))
    return max(tiles, key=lambda t: t[0] * t[1])


def default_tiles(mb: int, K: int, N: int, kind: str) -> tuple[int, int, int]:
    """Heuristic (bm, bn, bk). Rationale: bm covers the whole decode
    batch in one tile (M is tiny next to K/N); at prefill rows bk ~512
    keeps the x tile and accumulator small while amortizing the K-loop
    and bn ~512-1024 makes the int8 weight tile the dominant VMEM
    tenant (that's the stream we must keep wide); at decode rows the
    weight tile is sized for the HBM stream (``_decode_weight_tile``).
    All non-full tiles are multiples of 128 so both the int8 sublane
    rule (32) and the lane rule (128) hold."""
    bm = min(mb, 256)
    if mb <= DECODE_ROWS_MAX:
        return (bm, *_decode_weight_tile(K, N))
    bk = _largest_divisor(K, (512, 256, 128))
    if kind == "lm_head":
        # vocab is huge and M tiny: widen N so the weight stream (the
        # only traffic that matters at [D, 128256]) runs long tiles.
        # 768 divides 128256 (= 167 * 768); 512 does not.
        bn = _largest_divisor(N, (1024, 768, 512, 384, 256, 128))
    else:
        bn = _largest_divisor(N, (512, 384, 256, 128))
    if kind == "gate_up":
        # two weight tiles + two accumulators live at once: halve K
        # depth to keep the working set near the single-weight variants'
        bk = _largest_divisor(K, (256, 128))
    return bm, bn, bk


def _valid_tiles(tiles, mb: int, K: int, N: int) -> bool:
    """Whether (bm, bn, bk) is a legal blocking of the padded problem:
    what an explicit ``tiles=`` argument is held to."""
    bm, bn, bk = tiles
    if mb % bm or N % bn or K % bk:
        return False
    # non-full tiles must satisfy the lane rule
    if bn != N and bn % 128:
        return False
    if bk != K and bk % 128:
        return False
    if bm != mb and bm % 8:
        return False
    return True


def _kind_fn(kind: str, w, s, res, tiles):
    """The EXACT kernel variant the serving path dispatches for this
    kind — the residual epilogue streams an extra [bm, bn] input per
    tile, a different traffic profile than the plain kernel; a stacked
    ``[L, K, N]`` weight (what the layer scan hands a layer's matmuls)
    is read at its last layer."""
    layer = w.shape[0] - 1 if w.ndim == 3 else None
    if kind == "gate_up":
        return lambda a: qmm_gate_up(a, w, s, w, s, tiles=tiles, layer=layer)
    if kind == "residual":
        return lambda a: qmm(a, w, s, residual=res, tiles=tiles, layer=layer)
    if kind == "lm_head":
        return lambda a: qmm_lm_head(a, w, s, tiles=tiles)
    return lambda a: qmm(a, w, s, tiles=tiles, layer=layer)


def verify_compiles(
    m: int, K: int, N: int, kind: str, dtype=jnp.bfloat16, layers: int = 1
) -> None:
    """Compile (never run) the kernel for this shape with the tiling
    that WILL be used, in the form that will be served: a layer's
    matmuls over ``[layers, K, N]`` stacked weights, the head over
    ``[K, N]``; raises what the compiler raises.
    The engine calls this at start-up when no prewarm will compile the
    step functions, so a refused tiling fails there and not at the
    first request."""
    mb = m_bucket(m)
    sds = jax.ShapeDtypeStruct
    # the layer scan's matmuls get the model's stacked parameters, the
    # head a plain matrix
    lead = () if kind == "lm_head" else (layers,)

    # weights ride as arguments here: shapes only, nothing is allocated
    def call(a, w, s, res):
        return _kind_fn(kind, w, s, res, None)(a)

    jax.jit(call).lower(
        sds((mb, K), dtype), sds((*lead, K, N), jnp.int8),
        sds((*lead, N), jnp.float32), sds((mb, N), dtype),
    ).compile()


# ---------------------------------------------------------------------------
# The kernel body (flag-specialized: residual / gate-up epilogues)
# ---------------------------------------------------------------------------


def _act(name: str, g: jax.Array, dtype) -> jax.Array:
    """Gate activation on f32 values that hold ``dtype``-rounded numbers,
    mirroring models.llama.mlp_act (same failure contract: silently
    substituting silu would serve corrupt logits).

    The math runs in f32 — v5e has no bf16 vector unit and Mosaic
    refuses the bf16 ``logistic`` lowering — and ``rnd`` re-rounds to
    the output dtype where the reference's ``dtype`` ops round: silu is
    ``g * sigmoid(g)`` (two ops, two roundings); tanh-gelu is a longer
    chain that is rounded once at its end here."""
    def rnd(v: jax.Array) -> jax.Array:
        return v.astype(dtype).astype(jnp.float32)

    if name == "gelu":
        return rnd(jax.nn.gelu(g, approximate=True))
    if name == "silu":
        return rnd(g * rnd(jax.nn.sigmoid(g)))
    raise ValueError(f"unsupported activation {name!r}")


def _qmm_kernel(
    layer_ref,  # scalar prefetch: [1] int32 — read by the BlockSpecs only
    *refs,
    n_k: int,
    fused: str,  # "" | "residual" | "gate_up"
    act: str,
):
    """One (bm, bn) output tile accumulated over the K grid axis.

    The weights are stacked ``[L, K, N]`` with the layer as a
    scalar-prefetch index that the weight and scale BlockSpecs read
    (``_qmm_call``); the layer dimension is squeezed, so the body sees
    the same (bk, bn) and (1, bn) tiles whatever L is.

    refs layout by variant:
      plain:    x, w, s, o, acc
      residual: x, w, s, r, o, acc
      gate_up:  x, wg, sg, wu, su, o, accg, accu

    The int8 weight tile upcasts to the activation dtype IN REGISTER
    (exact: |w| <= 127 is representable in bf16) and feeds the MXU as a
    native bf16×bf16 dot with f32 accumulation — the dequant scale
    multiplies the f32 accumulator once, in the epilogue."""
    if fused == "gate_up":
        x_ref, wg_ref, sg_ref, wu_ref, su_ref, o_ref, accg_ref, accu_ref = refs
    elif fused == "residual":
        x_ref, w_ref, s_ref, r_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if fused == "gate_up":
            accg_ref[:] = jnp.zeros_like(accg_ref)
            accu_ref[:] = jnp.zeros_like(accu_ref)
        else:
            acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    dims = (((1,), (0,)), ((), ()))
    if fused == "gate_up":
        accg_ref[:] += jax.lax.dot_general(
            x, wg_ref[:].astype(x.dtype), dims,
            preferred_element_type=jnp.float32,
        )
        accu_ref[:] += jax.lax.dot_general(
            x, wu_ref[:].astype(x.dtype), dims,
            preferred_element_type=jnp.float32,
        )
    else:
        acc_ref[:] += jax.lax.dot_general(
            x, w_ref[:].astype(x.dtype), dims,
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == n_k - 1)
    def _epilogue():
        if fused == "gate_up":
            # round each dequantized matmul to the output dtype BEFORE
            # the activation — the same rounding points as the reference
            # mlp_act(mm(gate)) * mm(up) composition
            # (a product of two bf16 values is exact in f32, so the
            # final f32 multiply + one rounding equals a bf16 multiply)
            dt = o_ref.dtype
            g = (accg_ref[:] * sg_ref[:]).astype(dt).astype(jnp.float32)
            u = (accu_ref[:] * su_ref[:]).astype(dt).astype(jnp.float32)
            o_ref[:] = (_act(act, g, dt) * u).astype(dt)
        elif fused == "residual":
            # residual add in the output dtype (reference: x + mm(...)
            # .astype(x.dtype) — the cast happens before the add)
            o_ref[:] = r_ref[:] + (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)
        else:
            o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


def _qmm_call(
    x2: jax.Array,  # [M, K] float activations (bf16/f32)
    weights: list[jax.Array],  # one [L, K, N] / [K, N] int8, two for gate_up
    scales: list[jax.Array],  # matching [L, N] / [N] f32 per-channel scales
    layer: Optional[jax.Array],  # scalar int32 (may be traced) into L
    residual2: Optional[jax.Array],  # [M, N] or None
    kind: str,
    fused: str,
    act: str,
    interpret: bool,
    tiles: Optional[tuple[int, int, int]],
    out_dtype=None,
) -> jax.Array:
    """THE pallas_call of the family. The weight and scale BlockSpecs
    read the layer from a scalar-prefetch ref, so only the tiles the
    grid visits move, once, straight out of the stacked parameters (why:
    the module docstring). A 2-D ``[K, N]`` weight is the same call with
    L = 1 and layer 0 — a leading unit dimension is a free reshape."""
    M, K = x2.shape
    if weights[0].ndim == 2:  # one layer, read at 0, whatever was passed
        weights = [w[None] for w in weights]
        scales = [s[None] for s in scales]
        layer = 0
    assert layer is not None, "a stacked weight needs its layer index"
    L, _, N = weights[0].shape
    for w, s in zip(weights, scales):
        assert w.dtype == jnp.int8 and w.shape == (L, K, N), (w.shape, K, N)
        assert s.shape == (L, N), (s.shape, L, N)
    mp = m_bucket(M)
    bm, bn, bk = (
        tiles if tiles is not None else default_tiles(mp, K, N, kind)
    )
    bm = min(bm, mp)
    # a non-dividing blocking would silently leave output columns
    # unwritten (grid floor-division drops the remainder), so an
    # explicit `tiles` that is not legal fails loudly instead
    if not _valid_tiles((bm, bn, bk), mp, K, N):
        raise ValueError(
            f"tiles (bm={bm}, bn={bn}, bk={bk}) must divide the padded "
            f"problem (M={mp}, N={N}, K={K})"
        )
    if M != mp:
        x2 = jnp.pad(x2, ((0, mp - M), (0, 0)))
        if residual2 is not None:
            residual2 = jnp.pad(residual2, ((0, mp - M), (0, 0)))
    grid = (mp // bm, N // bn, K // bk)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k, lyr: (i, k))]
    inputs: list[jax.Array] = [x2]
    for w, s in zip(weights, scales):
        in_specs.append(
            pl.BlockSpec((None, bk, bn), lambda i, j, k, lyr: (lyr[0], k, j))
        )
        in_specs.append(
            pl.BlockSpec((None, 1, bn), lambda i, j, k, lyr: (lyr[0], 0, j))
        )
        inputs.append(w)
        inputs.append(s.reshape(L, 1, N).astype(jnp.float32))
    if residual2 is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, lyr: (i, j)))
        inputs.append(residual2)

    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if fused == "gate_up":
        scratch.append(pltpu.VMEM((bm, bn), jnp.float32))

    # no name= and no jit of its own: the benchmark's readers find the
    # kernels by the name an unnamed pallas_call gets (PERF.md section 7)
    out = pl.pallas_call(
        functools.partial(
            _qmm_kernel, n_k=grid[2], fused=fused, act=act
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the layer
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, lyr: (i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((mp, N), out_dtype or x2.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    return out[:M] if M != mp else out


def _flatten(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    return x.reshape(-1, x.shape[-1]), x.shape[:-1]


def qmm(
    x: jax.Array,  # [..., K] float activations
    w: jax.Array,  # [K, N] int8, or the stacked [L, K, N] with ``layer``
    scale: jax.Array,  # [N] / [L, N] f32 per-output-channel dequant scale
    residual: Optional[jax.Array] = None,  # [..., N] fused epilogue add
    kind: str = "mm",
    interpret: bool = False,
    tiles: Optional[tuple[int, int, int]] = None,
    layer: Optional[jax.Array] = None,  # scalar int32 index into L
    out_dtype=None,  # None = x.dtype; float32 keeps the accumulator's bits
) -> jax.Array:
    """y = (x @ w) * scale (+ residual), rounded to x.dtype — the
    in-kernel-dequant replacement for the reference ``mm`` epilogue.

    With ``layer`` the weight and scale are the model's whole stacked
    ``[L, K, N]`` / ``[L, N]`` parameters and the kernel reads layer
    ``layer`` (a traced scan index is fine) straight out of them: the
    layer scan must NOT slice ``w[layer]`` first, or XLA stages the
    slice in a copy of its own before the call (``_qmm_call``). A
    ``[K, N]`` weight has no layers and ignores ``layer``."""
    x2, lead = _flatten(x)
    r2 = None
    if residual is not None:
        r2, _ = _flatten(residual)
        kind = "residual" if kind == "mm" else kind
    y = _qmm_call(
        x2, [w], [scale], layer, r2, kind,
        "residual" if residual is not None else "", "silu", interpret, tiles,
        out_dtype,
    )
    return y.reshape(*lead, w.shape[-1])


def qmm_gate_up(
    x: jax.Array,  # [..., D]
    w_gate: jax.Array,  # [D, F] int8, or stacked [L, D, F] with ``layer``
    gate_scale: jax.Array,  # [F] / [L, F] f32
    w_up: jax.Array,  # [D, F] / [L, D, F] int8
    up_scale: jax.Array,  # [F] / [L, F] f32
    act: str = "silu",
    interpret: bool = False,
    tiles: Optional[tuple[int, int, int]] = None,
    layer: Optional[jax.Array] = None,  # scalar int32 index into L
) -> jax.Array:
    """act(x @ Wg * sg) * (x @ Wu * su) — both MLP weights stream in one
    kernel pass; the [..., F] gate/up intermediates never touch HBM.
    ``layer``: both weights are read out of their stacked arrays, as in
    :func:`qmm` — the two largest copies of a layer never happen."""
    x2, lead = _flatten(x)
    y = _qmm_call(
        x2, [w_gate, w_up], [gate_scale, up_scale], layer, None, "gate_up",
        "gate_up", act, interpret, tiles,
    )
    return y.reshape(*lead, w_gate.shape[-1])


def qmm_lm_head(
    x: jax.Array,  # [..., D] final hidden states
    w: jax.Array,  # [D, V] int8
    scale: jax.Array,  # [V] f32
    interpret: bool = False,
    tiles: Optional[tuple[int, int, int]] = None,
) -> jax.Array:
    """The vocab-tiled LM-head qmm (its own tile rule: at V=128256 this
    is the single largest weight read per decode step). Output rounds
    to x.dtype exactly like ``mm`` — the caller upcasts to f32 for
    sampling, same as the reference path."""
    x2, lead = _flatten(x)
    y = _qmm_call(
        x2, [w], [scale], None, None, "lm_head", "", "silu", interpret, tiles
    )
    return y.reshape(*lead, w.shape[1])
