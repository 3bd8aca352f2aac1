"""Llama-family decoder in pure functional JAX with paged KV cache.

Covers the Llama-architecture family the reference serves through its
engines: Llama/DeepSeek-R1-Distill, Mistral (sliding-window attention),
Qwen2 (QKV bias), and Mixtral-style MoE — one decoder, config-driven.

The flagship native engine model (reference analogue: the external vLLM
engine the reference shells out to — here the model is first-class,
SURVEY.md §7 step 4). Design choices for TPU:

- params are a flat pytree with layers **stacked on a leading L axis** and
  the transformer body is a single `lax.scan` over layers: one layer gets
  compiled once regardless of depth — fast compiles, identical performance.
- one **unified step function** serves prefill and decode: write new K/V
  into the paged cache at `slot_mapping`, gather each sequence's pages via
  its block table, and do masked attention. Decode is the T=1 special case.
  (The Pallas paged-attention kernel in ops/ replaces the gather on TPU.)
- GQA with head_dim-scaled RoPE; RMSNorm in f32; weights/activations bf16;
  attention softmax in f32.
- TP sharding over the "tp" mesh axis: q/k/v/o heads and MLP hidden are
  sharded; the KV cache is sharded on its KV-head axis so paged attention
  is fully local to each TP shard; XLA inserts the psum on o_proj/down_proj
  output via sharding propagation.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]

# the model_type values this module serves beside its own name
# (models/__init__.py family): dense GQA decoders, Mixtral-routed MLPs,
# gemma norms, a vision tower in front
FAMILIES = ("llama", "mistral", "mixtral", "qwen2", "gemma", "llava")


# ---------------------------------------------------------------------------
# Parameter init / sharding specs
# ---------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype). Layer params carry a leading L axis."""
    L = cfg.num_hidden_layers
    D = cfg.hidden_size
    H = cfg.num_attention_heads
    Hk = cfg.num_key_value_heads
    Dh = cfg.head_dim
    F = cfg.intermediate_size
    V = cfg.vocab_size
    bf16 = jnp.bfloat16
    shapes = {
        "embed": ((V, D), bf16),
        "attn_norm": ((L, D), jnp.float32),
        "wq": ((L, D, H * Dh), bf16),
        "wk": ((L, D, Hk * Dh), bf16),
        "wv": ((L, D, Hk * Dh), bf16),
        "wo": ((L, H * Dh, D), bf16),
        "mlp_norm": ((L, D), jnp.float32),
        "final_norm": ((D,), jnp.float32),
        "lm_head": ((D, V), bf16),
    }
    if cfg.attention_bias:
        shapes.update(
            {
                "bq": ((L, H * Dh), bf16),
                "bk": ((L, Hk * Dh), bf16),
                "bv": ((L, Hk * Dh), bf16),
            }
        )
    if cfg.is_moe:
        E = cfg.num_local_experts
        shapes.update(
            {
                "router": ((L, D, E), bf16),
                "w_gate": ((L, E, D, F), bf16),
                "w_up": ((L, E, D, F), bf16),
                "w_down": ((L, E, F, D), bf16),
            }
        )
    else:
        shapes.update(
            {
                "w_gate": ((L, D, F), bf16),
                "w_up": ((L, D, F), bf16),
                "w_down": ((L, F, D), bf16),
            }
        )
    return shapes


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """PartitionSpecs per param (tp shards heads/hidden, ep shards experts)."""
    specs = {
        "embed": P("tp", None),
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }
    if cfg.attention_bias:
        specs.update(
            {"bq": P(None, "tp"), "bk": P(None, "tp"), "bv": P(None, "tp")}
        )
    if cfg.is_moe:
        specs.update(
            {
                "router": P(None, None, None),
                "w_gate": P(None, "ep", None, "tp"),
                "w_up": P(None, "ep", None, "tp"),
                "w_down": P(None, "ep", "tp", None),
            }
        )
    else:
        specs.update(
            {
                "w_gate": P(None, None, "tp"),
                "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None),
            }
        )
    return specs


def _random_leaf(key, *, shape, dtype, scale: float, ones: bool):
    if ones:
        return jnp.ones(shape, dtype=dtype)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Optional[Mesh] = None,
                specs: Optional[dict] = None) -> Params:
    """Random init (for tests / benchmarks without weights). ``specs``
    overrides the default TP PartitionSpecs (e.g. pp-sharded stacks)."""
    shapes = param_shapes(cfg)
    specs = specs if specs is not None else param_specs(cfg)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(shapes))
    params: Params = {}
    for (name, (shape, dtype)), k in zip(shapes.items(), keys):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / math.sqrt(max(1, fan_in))
        gen = functools.partial(
            _random_leaf, shape=shape, dtype=dtype, scale=scale,
            ones=name.endswith("norm"),
        )
        if mesh is not None:
            # generated INTO the sharding: each device draws only its
            # shard (threefry is partitionable, so the values do not
            # depend on the mesh). Drawing the whole [L, D, F] stack in
            # f32 on one device first would need 7.5 GB at 8B widths.
            gen = jax.jit(
                gen, out_shardings=NamedSharding(mesh, specs[name])
            )
        params[name] = gen(k)
    return params


def cache_shape(
    cfg: ModelConfig, num_blocks: int, block_size: int
) -> tuple[int, int, int, int]:
    """KV cache per K and V: [L, num_blocks*block_size, Hkv, Dh]."""
    return (
        cfg.num_hidden_layers,
        num_blocks * block_size,
        cfg.num_key_value_heads,
        cfg.head_dim,
    )


CACHE_SPEC = P(None, None, "tp", None)
# int8-cache scale arrays [L, N, Hk, bs]: tp shards the head axis
SCALE_SPEC = P(None, None, "tp", None)


def kv_cache_is_quantized(cache) -> bool:
    """True when ``cache`` is an int8 (values, scales) pair rather than
    a plain float array. The quantized cache threads through jit/scan/
    donation as a pytree; only code that indexes into it branches."""
    return isinstance(cache, tuple)


def init_cache(
    cfg: ModelConfig,
    num_blocks: int,
    block_size: int,
    mesh: Optional[Mesh] = None,
    dtype=jnp.bfloat16,
    spec: Optional[P] = None,
):
    """Zeroed paged KV cache: (k_cache, v_cache). Float dtypes give
    plain arrays (fp8 e4m3 = scale-free quantized storage); int8 gives
    (values, scales) pairs with per-(slot, head) f32 scales
    (ops/kv_quant.py documents the scale layout)."""
    shape = cache_shape(cfg, num_blocks, block_size)
    # allocated INTO the sharding: a cache sized for a whole tp mesh
    # (24.6 GB at tp=4 on v5e) never fits the one device that
    # zeros-then-device_put would build it on first
    sh = ssh = None
    if mesh is not None:
        sh = NamedSharding(mesh, spec if spec is not None else CACHE_SPEC)
        ssh = NamedSharding(mesh, SCALE_SPEC)
    k = jnp.zeros(shape, dtype=dtype, device=sh)
    v = jnp.zeros(shape, dtype=dtype, device=sh)
    if jnp.dtype(dtype) != jnp.int8:
        return k, v
    from dynamo_tpu.ops.kv_quant import kv_scale_shape

    sshape = kv_scale_shape(
        cfg.num_hidden_layers, num_blocks, block_size,
        cfg.num_key_value_heads,
    )
    ks = jnp.ones(sshape, jnp.float32, device=ssh)
    vs = jnp.ones(sshape, jnp.float32, device=ssh)
    return (k, ks), (v, vs)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float,
            bias_one: bool = False) -> jax.Array:
    """RMSNorm in f32. ``bias_one``: gemma stores weights as (w - 1) and
    the norm multiplies by (1 + w)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    scale = (1.0 + w) if bias_one else w
    out = xf * jax.lax.rsqrt(var + eps) * scale
    return out.astype(x.dtype)


def mlp_act(cfg: ModelConfig, g: jax.Array) -> jax.Array:
    """Gate activation: silu (llama family) or tanh-gelu (gemma).
    Unknown activations fail loudly — a silent silu fallback would serve
    corrupted logits for checkpoints we don't actually support."""
    if cfg.hidden_act == "gelu":
        return jax.nn.gelu(g, approximate=True)
    if cfg.hidden_act == "silu":
        return jax.nn.silu(g)
    raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")


def scale_embed(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Gemma-family sqrt(hidden) embedding scaling (no-op otherwise)."""
    if not cfg.scale_embeddings:
        return x
    return (x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)).astype(x.dtype)


def matmul_impl() -> str:
    """Quantized-matmul implementation: DYN_MATMUL_IMPL =
    auto|reference|pallas (mirrors DYN_ATTN_IMPL).

    auto = the fused dequant Pallas kernels (ops/qmatmul.py) on TPU for
    single-device serving (jax.device_count() == 1, or an engine-
    registered size-1 mesh), the XLA mixed-dtype dot elsewhere. Off-TPU
    the kernels run interpreted (correct but slow — tests only).
    Multi-device meshes stay on the reference path: wo/w_down contract
    a tp-sharded axis, and the kernels carry no psum story."""
    impl = os.environ.get("DYN_MATMUL_IMPL", "auto")
    if impl == "auto":
        if jax.default_backend() == "tpu" and _single_device_matmul():
            return "pallas"
        return "reference"
    return impl


def _single_device_matmul() -> bool:
    return jax.device_count() == 1 or (
        _ATTN_MESH is not None and _ATTN_MESH.size == 1
    )


def pallas_matmul_active() -> bool:
    """True when quantized matmuls will ACTUALLY dispatch the Pallas
    dequant kernels — impl choice AND an unsharded-weights
    configuration (the same shape of predicate as
    pallas_attention_active)."""
    return matmul_impl() == "pallas" and _single_device_matmul()


def _qmm_interpret() -> bool:
    return jax.default_backend() != "tpu"


def mm(
    p: Params, name: str, x: jax.Array, layer: Optional[jax.Array] = None
) -> jax.Array:
    """x @ p[name], transparently handling int8 weight-only quantization
    (models/quant.py). Reference epilogue: a mixed-dtype dot (bf16
    activations × int8 weight, f32 accumulation) keeps HBM reads
    int8-sized — measured ~1.3-2× decode speedup over bf16 on v5e —
    then the per-output-channel scale applies to the f32 product before
    casting back. Under DYN_MATMUL_IMPL=pallas the fused dequant kernel
    (ops/qmatmul.py) does the same math with the upcast in-register,
    which is what actually reaches int8-byte-bound weight reads.

    ``layer``: the scan's layer index, used where ``p[name]`` is the
    whole stacked ``[L, K, N]`` array — the kernel then reads its layer
    in place (a ``[K, N]`` weight has no layers and ignores it).
    Slicing the stack here instead would put a copy of the matrix in
    front of every call (ops/qmatmul.py ``_qmm_call``); the reference
    dot below never sees a stacked weight, XLA fuses the scan's slice
    into it."""
    w = p[name]
    if w.dtype == jnp.int8:
        if pallas_matmul_active():
            from dynamo_tpu.ops.qmatmul import qmm

            return qmm(
                x, w, p[name + "_scale"], interpret=_qmm_interpret(),
                layer=layer,
            )
        y = jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * p[name + "_scale"]).astype(x.dtype)
    return x @ w


def embed_lookup(p: Params, tokens: jax.Array) -> jax.Array:
    """Token embedding rows, rescaled per row when the table is int8."""
    w = p["embed"]
    x = jnp.take(w, tokens, axis=0)
    if w.dtype == jnp.int8:
        s = jnp.take(p["embed_scale"], tokens, axis=0)  # [B, T]
        x = x.astype(jnp.bfloat16) * s[..., None].astype(jnp.bfloat16)
    return x


def rope(q: jax.Array, k: jax.Array, positions: jax.Array, theta: float) -> tuple[jax.Array, jax.Array]:
    """Rotary embeddings; q/k: [B, T, H, Dh], positions: [B, T]."""
    dh = q.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, T, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x: jax.Array) -> jax.Array:
        x1, x2 = x[..., :half], x[..., half:]
        xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate(
            [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
        ).astype(x.dtype)

    return rot(q), rot(k)


def paged_attention_reference(
    q: jax.Array,  # [B, T, H, Dh]
    k_cache_l: jax.Array,  # [n_slots, Hkv, Dh] (one layer)
    v_cache_l: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32 block ids
    positions: jax.Array,  # [B, T] absolute positions of the queries
    context_lens: jax.Array,  # [B] total valid tokens per sequence
    block_size: int,
    sliding_window: Optional[int] = None,
    sinks: Optional[jax.Array] = None,  # [H] f32: a learned logit a head
    scale: Optional[float] = None,
) -> jax.Array:
    """Gather-then-attend paged attention (XLA reference path).

    Works on any backend; the Pallas kernel (ops/paged_attention.py) is the
    TPU fast path with identical semantics. ``sliding_window`` masks keys
    older than the window (Mistral-family). As there, V rows may be
    narrower than K rows, ``sinks`` is one more softmax column a head
    that adds no value, and ``scale`` replaces ``Dh ** -0.5``.
    """
    if kv_cache_is_quantized(k_cache_l):
        # int8 cache: dequantize each layer-slice pair in f32, then run
        # the plain path (test oracle; the kernels scale in-register)
        from dynamo_tpu.ops.kv_quant import gather_slot_scales

        (kv_l, ks_l), (vv_l, vs_l) = k_cache_l, v_cache_l
        Hk = kv_l.shape[-2]
        B = q.shape[0]
        S = block_tables.shape[1] * block_size
        slot_ids = (
            block_tables[:, :, None] * block_size
            + jnp.arange(block_size, dtype=block_tables.dtype)[None, None, :]
        ).reshape(B, S)
        ksc = gather_slot_scales(ks_l, slot_ids, block_size, Hk)
        vsc = gather_slot_scales(vs_l, slot_ids, block_size, Hk)
        keys = (
            kv_l[slot_ids].astype(jnp.float32) * ksc[..., None]
        ).astype(q.dtype)
        vals = (
            vv_l[slot_ids].astype(jnp.float32) * vsc[..., None]
        ).astype(q.dtype)
        return _reference_attend(
            q, keys, vals, positions, context_lens, sliding_window, sinks,
            scale,
        )
    B, T, H, Dh = q.shape
    Hk = k_cache_l.shape[-2]
    S = block_tables.shape[1] * block_size
    # gather pages: [B, S] flat slot ids
    slot_ids = (
        block_tables[:, :, None] * block_size
        + jnp.arange(block_size, dtype=block_tables.dtype)[None, None, :]
    ).reshape(B, S)
    keys = k_cache_l[slot_ids]  # [B, S, Hk, Dh]
    vals = v_cache_l[slot_ids]
    if keys.dtype != q.dtype:
        # quantized (fp8) cache: dequantize for the einsum (exact cast)
        keys = keys.astype(q.dtype)
        vals = vals.astype(q.dtype)
    return _reference_attend(
        q, keys, vals, positions, context_lens, sliding_window, sinks, scale
    )


def _reference_attend(
    q: jax.Array,  # [B, T, H, Dh]
    keys: jax.Array,  # [B, S, Hk, Dh] gathered (and dequantized) pages
    vals: jax.Array,
    positions: jax.Array,
    context_lens: jax.Array,
    sliding_window: Optional[int],
    sinks: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Masked-attention tail of the XLA reference path.

    GQA via grouped einsum — no [B, S, H, Dh] materialization of
    group-expanded keys/values (the repeat would multiply attention's
    HBM traffic by H/Hk)."""
    B, T, H, Dh = q.shape
    Hk = keys.shape[-2]
    S = keys.shape[1]
    group = H // Hk
    qg = q.reshape(B, T, Hk, group, Dh)
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, keys, preferred_element_type=jnp.float32
    ) * scale  # [B, Hk, G, T, S]
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]
    pos_q = positions[:, None, None, :, None]
    mask = (key_pos <= pos_q) & (
        key_pos < context_lens[:, None, None, None, None]
    )
    if sliding_window is not None:
        mask = mask & (key_pos > pos_q - sliding_window)
    scores = jnp.where(mask, scores, -1e30)
    if sinks is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    else:
        # the sink is one more column of the softmax, then dropped
        col = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, Hk, group, 1, 1),
            scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, col], axis=-1), axis=-1
        )[..., :-1].astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vals)
    return out.reshape(B, T, H, vals.shape[-1])


# Mesh for multi-device Pallas attention: attention is local per
# KV-head shard, so the decode kernel runs under shard_map over "tp"
# (one kernel instance per shard, no collectives). Set by the engine
# BEFORE tracing its step functions (module state is captured at trace
# time); pp engines leave it unset — inside the pp stage rotation "tp"
# is a GSPMD auto axis that a manual shard_map can't claim.
_ATTN_MESH: Optional[Mesh] = None


def set_attention_mesh(mesh: Optional[Mesh]) -> None:
    global _ATTN_MESH
    _ATTN_MESH = mesh


def get_attention_mesh() -> Optional[Mesh]:
    return _ATTN_MESH


def shard_attention_kernel(
    kern, mesh: Mesh, *, prefill: bool, quantized: bool
):
    """Wrap a stacked paged-attention kernel so one instance runs per tp
    shard: q heads and the cache's KV-head axis (dim 2 of the stacked
    layout) are tp-sharded; layer index, tables, start positions and ctx
    ride replicated, as do the other mesh axes (dp/ep/sp). int8 scale
    arrays shard on their head axis (SCALE_SPEC). Argument order is the
    kernels' own: (q, k, v, layer, tables, [start,] ctx[, ks, vs]).

    The shard_map is manual over EVERY mesh axis: a Mosaic kernel cannot
    sit in a partly automatic region (the chip's compiler refuses it,
    "Mosaic kernels cannot be automatically partitioned"), even when the
    axes left automatic have size 1."""
    qspec = P(None, None, "tp", None) if prefill else P(None, "tp", None)
    in_specs = (qspec, CACHE_SPEC, CACHE_SPEC, P(), P(None, None))
    in_specs += (P(None), P(None)) if prefill else (P(None),)
    if quantized:
        in_specs += (SCALE_SPEC, SCALE_SPEC)
    return jax.shard_map(
        kern, mesh=mesh, in_specs=in_specs, out_specs=qspec,
        check_vma=False,
    )


def pallas_attention_active() -> bool:
    """True when the model will ACTUALLY dispatch the Pallas attention
    kernels (the predicate attend_mlp uses) — impl choice AND a usable
    device/mesh configuration. The engine's HBM auto-sizing keys off
    this same predicate: sizing on attn_impl() alone would zero the
    XLA-path scores-transient budget in configurations (e.g. pp meshes,
    where the attention mesh is deliberately unset) that still run the
    reference path."""
    return attn_impl() == "pallas" and (
        jax.device_count() == 1 or _ATTN_MESH is not None
    )


def attn_impl() -> str:
    """Attention implementation: DYN_ATTN_IMPL = auto|reference|pallas.

    auto = the Pallas decode kernel on TPU (single device, or any tp
    mesh registered via set_attention_mesh), XLA gather path elsewhere
    (Pallas runs interpreted off-TPU: correct but slow — tests only).
    """
    impl = os.environ.get("DYN_ATTN_IMPL", "auto")
    if impl == "auto":
        if jax.default_backend() == "tpu" and (
            jax.device_count() == 1 or _ATTN_MESH is not None
        ):
            return "pallas"
        return "reference"
    return impl


# ---------------------------------------------------------------------------
# The unified forward step
# ---------------------------------------------------------------------------


def fused_mlp_ok(cfg: ModelConfig, lp: Params) -> bool:
    """The fused dequant epilogues serve this layer: dense MLP with
    every hot-path weight int8-quantized and a kernel-supported gate
    activation, under the Pallas matmul impl."""
    return (
        not cfg.is_moe
        and pallas_matmul_active()
        and cfg.hidden_act in ("silu", "gelu")
        and all(
            n in lp and lp[n].dtype == jnp.int8
            for n in ("wo", "w_gate", "w_up", "w_down")
        )
    )


def post_attn_mlp(
    cfg: ModelConfig, lp: Params, x: jax.Array, a: jax.Array,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Everything after attention: output projection + MLP/MoE residual
    — ONE copy shared by every attention variant. ``a`` is the
    flattened attention output [B, T, H*Dh].

    Under the Pallas matmul impl (int8 weights) the decode hot path
    runs three fused kernels instead of five ops: wo with the residual
    add in-epilogue, ONE gate/up pass with SiLU·mul in-kernel (the
    [.., F] intermediates never hit HBM), and w_down with the second
    residual add in-epilogue — the rounding points match the reference
    composition exactly (ops/qmatmul.py).

    ``layer``: the scan's layer index. In ``forward`` the int8 matrices
    of ``lp`` are the whole stacked ``[L, K, N]`` arrays and the kernels
    read layer ``layer`` out of them (see :func:`mm`); callers that
    hold one layer's ``[K, N]`` slices (the pipeline stage loop) pass
    none."""
    if fused_mlp_ok(cfg, lp):
        from dynamo_tpu.ops.qmatmul import qmm, qmm_gate_up

        interp = _qmm_interpret()
        x = qmm(
            a, lp["wo"], lp["wo_scale"], residual=x, interpret=interp,
            layer=layer,
        )
        h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.norm_bias_one)
        hh = qmm_gate_up(
            h, lp["w_gate"], lp["w_gate_scale"],
            lp["w_up"], lp["w_up_scale"],
            act=cfg.hidden_act, interpret=interp, layer=layer,
        )
        return qmm(
            hh, lp["w_down"], lp["w_down_scale"], residual=x,
            interpret=interp, layer=layer,
        )
    x = x + mm(lp, "wo", a, layer).astype(x.dtype)
    h = rmsnorm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.norm_bias_one)
    if cfg.is_moe:
        x = x + _moe_mlp(cfg, lp, h).astype(x.dtype)
    else:
        mlp_out = mm(
            lp, "w_down",
            mlp_act(cfg, mm(lp, "w_gate", h, layer)) * mm(lp, "w_up", h, layer),
            layer,
        )
        x = x + mlp_out.astype(x.dtype)
    return x


def make_layer_parts(
    cfg: ModelConfig,
    positions: jax.Array,  # [B, T]
    block_tables: jax.Array,  # [B, max_blocks]
    context_lens: jax.Array,  # [B]
    block_size: int,
):
    """The layer math in two halves so callers choose WHERE the KV write
    lands (layer slice vs full carried stack) without duplicating it:

      qkv(lp, x)                 -> (q, k, v) roped, [B, T, H*, Dh]
      attend_mlp(lp, x, q, kcl, vcl) -> new x (reads the layer cache
                                    AFTER the caller wrote k/v into it)

    ``layer`` (qkv, attend_mlp) / ``layer_idx`` (attend_mlp_stacked) is
    the scan's layer index for the weights of ``lp`` that are still
    stacked ``[L, K, N]`` arrays (see :func:`mm`).
    """
    H, Hk, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    def qkv(lp, x, layer=None):
        B, T = x.shape[0], x.shape[1]
        h = rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_bias_one)
        q = mm(lp, "wq", h, layer)
        k = mm(lp, "wk", h, layer)
        v = mm(lp, "wv", h, layer)
        if cfg.attention_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(B, T, H, Dh)
        k = k.reshape(B, T, Hk, Dh)
        v = v.reshape(B, T, Hk, Dh)
        q, k = rope(q, k, positions, cfg.rope_theta)
        return q, k, v

    # one predicate for dispatch AND the engine's HBM sizing
    _use_pallas_decode = pallas_attention_active

    def _pallas_decode_attn(q, stacked_args):
        """Run the flash-decode kernel (shard_mapped per tp shard on
        multi-device meshes). ``stacked_args`` = (k_cache, v_cache,
        layer_idx) over the stacked [L, ...] cache — the single kernel
        body serves the per-layer API too (ops/paged_attention.py)."""
        import functools as _ft

        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_stacked,
        )

        k_cache, v_cache, layer_idx = stacked_args
        ksc = vsc = None
        if kv_cache_is_quantized(k_cache):
            (k_cache, ksc), (v_cache, vsc) = k_cache, v_cache
        base = _ft.partial(
            paged_attention_decode_stacked,
            block_size=block_size,
            sliding_window=cfg.sliding_window,
            interpret=jax.default_backend() != "tpu",
        )
        if ksc is None:
            kern = base
        else:
            def kern(q_, kc_, vc_, li_, bt_, cl_, ks_, vs_):
                return base(
                    q_, kc_, vc_, li_, bt_, cl_, k_scale=ks_, v_scale=vs_
                )
        mesh = _ATTN_MESH
        if mesh is not None and mesh.size > 1:
            kern = shard_attention_kernel(
                kern, mesh, prefill=False, quantized=ksc is not None
            )
        args = (q[:, 0], k_cache, v_cache, layer_idx, block_tables,
                context_lens)
        if ksc is not None:
            args += (ksc, vsc)
        return kern(*args)[:, None]  # [B, 1, H, Dh]

    def _pallas_prefill_attn(q, stacked_args):
        """Flash prefill over the paged cache (T > 1): tile×page grid,
        online softmax — no [T, S] score materialization (the XLA
        reference path's [B, Hk, G, T, S] tensor is ~400 MB at
        T=1024/S=3072 and its HBM traffic dominates long-prompt TTFT).
        Prefill rows are contiguous token runs, so the kernel derives
        per-token positions from positions[:, 0]."""
        import functools as _ft

        from dynamo_tpu.ops.paged_attention import (
            paged_attention_prefill_stacked,
        )

        k_cache, v_cache, layer_idx = stacked_args
        ksc = vsc = None
        if kv_cache_is_quantized(k_cache):
            (k_cache, ksc), (v_cache, vsc) = k_cache, v_cache
        base = _ft.partial(
            paged_attention_prefill_stacked,
            block_size=block_size,
            sliding_window=cfg.sliding_window,
            interpret=jax.default_backend() != "tpu",
        )
        if ksc is None:
            kern = base
        else:
            def kern(q_, kc_, vc_, li_, bt_, st_, cl_, ks_, vs_):
                return base(
                    q_, kc_, vc_, li_, bt_, st_, cl_,
                    k_scale=ks_, v_scale=vs_,
                )
        mesh = _ATTN_MESH
        if mesh is not None and mesh.size > 1:
            kern = shard_attention_kernel(
                kern, mesh, prefill=True, quantized=ksc is not None
            )
        args = (q, k_cache, v_cache, layer_idx, block_tables,
                positions[:, 0], context_lens)
        if ksc is not None:
            args += (ksc, vsc)
        return kern(*args)  # [B, T, H, Dh]

    def _post_attn(lp, x, attn, layer=None):
        B, T = x.shape[0], x.shape[1]
        return post_attn_mlp(cfg, lp, x, attn.reshape(B, T, H * Dh), layer)

    def _expand1(cache_l):
        """Per-layer cache -> 1-layer stack (free expand-dims), for
        plain arrays and int8 (values, scales) pairs alike."""
        if kv_cache_is_quantized(cache_l):
            return (cache_l[0][None], cache_l[1][None])
        return cache_l[None]

    def attend_mlp(lp, x, q, k_cache_l, v_cache_l, layer=None):
        T = x.shape[1]
        if T == 1 and _use_pallas_decode():
            # per-layer cache: run as a 1-layer stack (free expand-dims)
            attn = _pallas_decode_attn(
                q, (_expand1(k_cache_l), _expand1(v_cache_l), jnp.int32(0))
            )
        elif _use_pallas_decode():
            attn = _pallas_prefill_attn(
                q, (_expand1(k_cache_l), _expand1(v_cache_l), jnp.int32(0))
            )
        else:
            attn = paged_attention_reference(
                q, k_cache_l, v_cache_l, block_tables, positions,
                context_lens, block_size, cfg.sliding_window,
            )
        return _post_attn(lp, x, attn, layer)

    def attend_mlp_stacked(lp, x, q, k_cache, v_cache, layer_idx):
        """attend_mlp over layer ``layer_idx`` of the FULL stacked cache.

        The decode hot path: slicing the layer out of the carried cache
        before a pallas_call materializes a full-layer copy at the
        custom-call boundary (measured ~11 ms/step on a 4.7 GB cache,
        linear in cache size — the r3 closed-batch regression). The
        stacked kernel indexes the layer inside its BlockSpec instead,
        so only referenced pages move (ops/paged_attention.py
        paged_attention_decode_stacked). Non-decode shapes and the XLA
        reference path slice the layer as before — XLA fuses that slice
        into its own gather."""
        T = x.shape[1]
        if _use_pallas_decode():
            attn = (
                _pallas_decode_attn(q, (k_cache, v_cache, layer_idx))
                if T == 1
                else _pallas_prefill_attn(q, (k_cache, v_cache, layer_idx))
            )
            return _post_attn(lp, x, attn, layer_idx)
        def slice_layer(cache):
            if kv_cache_is_quantized(cache):
                return tuple(
                    jax.lax.dynamic_index_in_dim(c, layer_idx, 0, keepdims=False)
                    for c in cache
                )
            return jax.lax.dynamic_index_in_dim(
                cache, layer_idx, 0, keepdims=False
            )

        return attend_mlp(
            lp, x, q, slice_layer(k_cache), slice_layer(v_cache), layer_idx
        )

    return qkv, attend_mlp, attend_mlp_stacked


def make_layer_fn(
    cfg: ModelConfig,
    positions: jax.Array,  # [B, T]
    slot_mapping: jax.Array,  # [B*T]
    block_tables: jax.Array,  # [B, max_blocks]
    context_lens: jax.Array,  # [B]
    block_size: int,
):
    """Per-layer scan body: (x, (layer_params, k_cache_l, v_cache_l)) -> ...

    Shared by the plain lax.scan forward and the pipeline-parallel stage
    loop (parallel/pipeline.py), which calls it with per-microbatch args.
    """
    Hk, Dh = cfg.num_key_value_heads, cfg.head_dim
    qkv, attend_mlp, _ = make_layer_parts(
        cfg, positions, block_tables, context_lens, block_size
    )

    def layer_fn(x, scanned):
        B, T = x.shape[0], x.shape[1]
        lp, k_cache_l, v_cache_l = scanned
        if kv_cache_is_quantized(k_cache_l):
            raise NotImplementedError(
                "int8 KV cache is not supported on the pipeline-parallel "
                "path (per-layer xs/ys cache layout); use bfloat16 or "
                "float8_e4m3fn with pipeline_parallel_size > 1"
            )
        q, k, v = qkv(lp, x)
        # write new kv into the paged cache (layer slice); astype is the
        # quantization step for fp8 caches (RN convert), a no-op for bf16
        k_cache_l = k_cache_l.at[slot_mapping].set(
            k.reshape(B * T, Hk, Dh).astype(k_cache_l.dtype)
        )
        v_cache_l = v_cache_l.at[slot_mapping].set(
            v.reshape(B * T, Hk, Dh).astype(v_cache_l.dtype)
        )
        x = attend_mlp(lp, x, q, k_cache_l, v_cache_l)
        return x, (k_cache_l, v_cache_l)

    return layer_fn


_GLOBAL_PARAMS = (
    "embed", "final_norm", "lm_head", "embed_scale", "lm_head_scale",
)


def layer_param_names(params: Params) -> list[str]:
    return [k for k in params if k not in _GLOBAL_PARAMS]


def forward(
    cfg: ModelConfig,
    params: Params,
    k_cache: jax.Array,  # [L, n_slots, Hkv, Dh]
    v_cache: jax.Array,
    tokens: jax.Array,  # [B, T] int32 (padded)
    positions: jax.Array,  # [B, T] int32 absolute positions (padded: 0)
    slot_mapping: jax.Array,  # [B*T] int32 flat cache slots (padded: slot 0)
    block_tables: jax.Array,  # [B, max_blocks] int32 (padded: block 0)
    context_lens: jax.Array,  # [B] int32 valid tokens incl. new ones
    last_token_idx: jax.Array,  # [B] int32 index of last real token in T
    block_size: int,
    extra_embeds: Optional[jax.Array] = None,  # [B, T, D] injected embeds
    embeds_mask: Optional[jax.Array] = None,  # [B, T] bool: use injected
    logits_all: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One model step. Returns (logits[B, V], new_k_cache, new_v_cache).

    ``extra_embeds``/``embeds_mask`` splice precomputed embeddings (image
    patches from models/vision.py) over the token embeddings at masked
    positions — the multimodal injection point (reference:
    examples/multimodal encode-worker → LLM embedding handoff).

    ``logits_all=True`` (trace-time constant) returns logits at EVERY
    fed position — [B, T, V] instead of [B, V] — the speculative-decode
    verify step needs the target distribution at each draft position
    (dynamo_tpu/spec). Only sensible for small T: the lm_head matmul and
    the [B, T, V] f32 output scale linearly with T.
    """
    x = scale_embed(cfg, embed_lookup(params, tokens))  # [B, T, D]
    if extra_embeds is not None:
        assert embeds_mask is not None
        x = jnp.where(embeds_mask[..., None], extra_embeds.astype(x.dtype), x)

    layer_params = {k: params[k] for k in layer_param_names(params)}

    # The KV cache rides the scan CARRY with the new k/v scattered
    # DIRECTLY into the full stack at [layer, slots] — NOT the xs/ys
    # stream. Scanned-over caches make XLA materialize a re-stacked
    # copy of the ENTIRE cache (an HLO temp of cache size — with an
    # auto-sized multi-GB cache that alone OOMs the chip, and it costs
    # a read+write of all cache bytes per step); a carried cache
    # aliases in place, and the direct scatter touches only the
    # written rows (a slice-copy+DUS variant still moved one full
    # layer slice per layer). Measured on v5e (8B int8, fused K=32):
    # 24.6 xs/ys -> 20.7 slice-DUS -> 19.3 direct-scatter ms/step;
    # engine 882 -> 1022 -> 1090 tok/s. Prefill (T>1) uses the same
    # formulation: its chunk amortizes the scatter and the peak-memory
    # profile stays flat (pipeline-parallel stages keep the xs/ys
    # layout over their L/pp slice — parallel/pipeline.py).
    Hk, Dh = cfg.num_key_value_heads, cfg.head_dim
    qkv, _attend_mlp, attend_mlp_stacked = make_layer_parts(
        cfg, positions, block_tables, context_lens, block_size
    )
    B, T = tokens.shape

    quantized = kv_cache_is_quantized(k_cache)
    if quantized:
        from dynamo_tpu.ops.kv_quant import (
            quantize_kv,
            scale_scatter_indices,
        )

        n_idx, off_idx = scale_scatter_indices(slot_mapping, block_size)

    def write_kv(cache, new, i):
        """Scatter this layer's fresh K or V rows [B*T, Hk, Dh] into the
        carried cache at ``slot_mapping`` — the int8 path quantizes
        per (token, head) and scatters the scales alongside; the astype
        is the fp8 quantization step (bf16 no-op).

        Scale-write forms matter enormously here: only the CANONICAL
        scatter (one indexed axis + suffix window — the values write's
        form) updates the carried array in place. The decode path
        (T=1) therefore read-modify-writes whole [Hk, bs] page tiles —
        safe because decode rows own distinct tail pages (padded rows
        all hit the garbage page 0, where racing writes are harmless).
        The indexed-slice form (``.at[i, n, :, off]``) makes XLA
        materialize + copy the full scale plane per layer at the
        Pallas custom-call boundary (measured: +2 ms/step at a
        500-block cache, scaling with cache size) — prefill keeps it
        because a chunk writes many slots per page (tile RMW would
        race) and its cost amortizes over the chunk's tokens."""
        if not quantized:
            return cache.at[i, slot_mapping].set(new.astype(cache.dtype))
        q8, sc = quantize_kv(new)
        vals, scales = cache
        vals = vals.at[i, slot_mapping].set(q8)
        if T == 1:
            bs_ = scales.shape[-1]
            page = scales[i, n_idx]  # [M, Hk, bs] gather
            col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs_), 2)
            page = jnp.where(
                col == off_idx[:, None, None], sc[:, :, None], page
            )
            scales = scales.at[i, n_idx].set(page)
        else:
            scales = scales.at[i, n_idx, :, off_idx].set(sc)
        return (vals, scales)

    # The int8 weight matrices do NOT ride the scan's xs on the Pallas
    # matmul path, for the cache's reason above: xs hands body a
    # [K, N] slice of each stacked [L, K, N] array, XLA cannot fuse a
    # producer slice into the kernels' custom calls, so it copied every
    # matrix out before every matmul — the weights crossed HBM twice a
    # step (measured on v5e, PERF.md PR 26: the copies were 45% of
    # device time at 8 decode rows). body closes over the whole arrays
    # (loop invariants, like the block tables) and the kernels read
    # layer i in place (ops/qmatmul.py _qmm_call). Their scales go the
    # same way; norms, biases, MoE experts and everything on the
    # reference path stay in xs, where XLA fuses the slice into its
    # own dot.
    stacked = {}
    if pallas_matmul_active():
        stacked = {
            n: w for n, w in layer_params.items()
            if w.dtype == jnp.int8 and w.ndim == 3
        }
        stacked.update({
            n + "_scale": layer_params[n + "_scale"] for n in list(stacked)
        })
    scanned = {n: w for n, w in layer_params.items() if n not in stacked}

    def body(carry, inp):
        x, kc, vc = carry
        lp, i = inp
        lp = {**lp, **stacked}
        q, k, v = qkv(lp, x, i)
        kc = write_kv(kc, k.reshape(B * T, Hk, Dh), i)
        vc = write_kv(vc, v.reshape(B * T, Hk, Dh), i)
        # attention reads the layer THROUGH the stacked cache (no layer
        # slice materialized — see attend_mlp_stacked)
        x = attend_mlp_stacked(lp, x, q, kc, vc, i)
        return (x, kc, vc), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body, (x, k_cache, v_cache),
        (scanned, jnp.arange(cfg.num_hidden_layers)),
    )

    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps, cfg.norm_bias_one)
    if logits_all:
        # every position's logits (speculative verify) — [B, T, V]
        return lm_head(params, x), new_k, new_v
    # logits only at each sequence's last real token
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]  # [B, D]
    return lm_head(params, x_last), new_k, new_v  # [B, V]


def lm_head(p: Params, x: jax.Array) -> jax.Array:
    """Final-hidden → f32 logits. Int8 tables under the Pallas impl go
    through the vocab-tiled kernel variant (its own tile rule — at
    V=128256 the LM head is the single largest weight read of a decode
    step); either path rounds through the activation dtype before the
    f32 upcast, so the logits grid is identical."""
    w = p["lm_head"]
    if w.dtype == jnp.int8 and pallas_matmul_active():
        from dynamo_tpu.ops.qmatmul import qmm_lm_head

        return qmm_lm_head(
            x, w, p["lm_head_scale"], interpret=_qmm_interpret()
        ).astype(jnp.float32)
    return mm(p, "lm_head", x).astype(jnp.float32)


def moe_impl() -> str:
    """MoE formulation: DYN_MOE_IMPL = auto|dense|sparse.

    auto = sparse top-k routing (grouped matmul — FLOPs and expert
    weight reads scale with k/E). dense evaluates every expert and
    masks: compute-correct and useful as the parity oracle, but a real
    Mixtral-8x7B top-2 pays E/k = 4× the FLOPs and streams ALL expert
    weights every step (VERDICT r2 weak #4).
    """
    return os.environ.get("DYN_MOE_IMPL", "auto")


def _moe_mlp(cfg: ModelConfig, lp: Params, h: jax.Array) -> jax.Array:
    if moe_impl() == "dense":
        return _moe_mlp_dense(cfg, lp, h)
    return _moe_mlp_sparse(cfg, lp, h)


def _moe_mlp_dense(cfg: ModelConfig, lp: Params, h: jax.Array) -> jax.Array:
    """Mixtral-style sparse MoE MLP (dense-compute formulation).

    Computes router softmax over E experts, selects top-k, and evaluates
    via einsum over the expert axis with a top-k weight mask — the
    MXU-friendly formulation: no scatter/gather, experts sharded on "ep".
    """
    B, T, D = h.shape
    E, k = cfg.num_local_experts, cfg.num_experts_per_tok
    logits = (h @ lp["router"]).astype(jnp.float32)  # [B, T, E]
    weights = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(weights, k)  # [B, T, k]
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    # dense routing mask [B, T, E] of normalized top-k weights
    routing = (
        jnp.zeros((B, T, E), jnp.float32)
        .at[
            jnp.arange(B)[:, None, None],
            jnp.arange(T)[None, :, None],
            topi,
        ]
        .set(topw)
    ).astype(h.dtype)
    # expert compute: g/u/d per expert; einsum keeps everything batched.
    # int8 expert weights upcast in the dot with trailing-aligned
    # per-channel scales ([E, F] / [E, D] broadcast over [B, T, ...]).
    def qeinsum(eq: str, x: jax.Array, name: str) -> jax.Array:
        w = lp[name]
        if w.dtype == jnp.int8:
            y = jnp.einsum(eq, x, w.astype(x.dtype))
            return y * lp[name + "_scale"].astype(y.dtype)
        return jnp.einsum(eq, x, w)

    ge = qeinsum("btd,edf->btef", h, "w_gate")
    ue = qeinsum("btd,edf->btef", h, "w_up")
    he = jax.nn.silu(ge) * ue  # [B, T, E, F]
    oe = qeinsum("btef,efd->bted", he, "w_down")
    return jnp.einsum("bted,bte->btd", oe, routing)


def _moe_routing(cfg: ModelConfig, lp: Params, x: jax.Array):
    """Shared router: x [N, D] -> (top weights [N, k], top ids [N, k])."""
    k = cfg.num_experts_per_tok
    logits = (x @ lp["router"]).astype(jnp.float32)  # [N, E]
    weights = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(weights, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    return topw, topi


def _grouped_mlp(
    lp: Params,
    xs: jax.Array,  # [M, D] tokens sorted by expert
    group_sizes: jax.Array,  # [E_local (+1 dead)] rows per expert
    expert_of_row: jax.Array,  # [M] expert id per sorted row (scale gather)
    pad_dead_expert: bool = False,
) -> jax.Array:
    """gate/up/down through per-expert grouped matmuls
    (jax.lax.ragged_dot): each expert's weights are read once per step
    and only its assigned rows are computed — the megablocks-style
    formulation, FLOPs/bytes ∝ assigned rows, not E.

    int8 expert weights upcast inside the dot (XLA fuses the convert
    into the operand read) with per-expert per-channel scales gathered
    per ROW. ``pad_dead_expert`` appends a zero expert for rows owned
    by other ep shards.
    """

    # bf16 ragged_dot inside a manual shard_map region crashes XLA:CPU
    # ("Invalid binary instruction opcode copy"); the virtual-mesh test
    # rung upcasts to f32 (strictly more precise), TPU stays bf16
    cpu = jax.default_backend() == "cpu"

    def gdot(name: str, inp: jax.Array) -> jax.Array:
        w = lp[name]  # [E, D, F] / [E, F, D]
        out_dtype = inp.dtype
        if pad_dead_expert:
            w = jnp.concatenate(
                [w, jnp.zeros((1, *w.shape[1:]), w.dtype)], axis=0
            )
        if w.dtype == jnp.int8:
            y = jax.lax.ragged_dot(
                inp.astype(jnp.float32) if cpu else inp,
                w.astype(jnp.float32 if cpu else inp.dtype),
                group_sizes,
                preferred_element_type=jnp.float32,
            )
            scale = lp[name + "_scale"]  # [E, out]
            if pad_dead_expert:
                scale = jnp.concatenate(
                    [scale, jnp.zeros((1, scale.shape[1]), scale.dtype)],
                    axis=0,
                )
            y = y * jnp.take(scale, expert_of_row, axis=0)
            return y.astype(out_dtype)
        if cpu:
            return jax.lax.ragged_dot(
                inp.astype(jnp.float32), w.astype(jnp.float32), group_sizes
            ).astype(out_dtype)
        return jax.lax.ragged_dot(inp, w, group_sizes)

    g = gdot("w_gate", xs)
    u = gdot("w_up", xs)
    return gdot("w_down", jax.nn.silu(g) * u)  # [M, D]


def _moe_mlp_sparse(cfg: ModelConfig, lp: Params, h: jax.Array) -> jax.Array:
    """Top-k routed MoE: sort token-expert assignments by expert, run
    grouped matmuls over contiguous per-expert row ranges, unsort and
    combine. Under an "ep" mesh axis the computation runs inside
    shard_map: each shard keeps its E/ep local experts' rows (remote
    rows go to a zero 'dead' expert) and the combine psums over "ep" —
    expert weights never leave their shard (reference analogue: the
    role of EP in SURVEY §2.6; BASELINE config 4)."""
    B, T, D = h.shape
    E, k = cfg.num_local_experts, cfg.num_experts_per_tok
    N = B * T
    x = h.reshape(N, D)
    topw, topi = _moe_routing(cfg, lp, x)

    mesh = _ATTN_MESH
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1

    def local_compute(lp_l, x_l, topw_l, topi_l, shard: Optional[int]):
        """One shard's contribution. ``shard`` None = all experts."""
        e_loc = E // ep if shard is not None else E
        flat_e = topi_l.reshape(-1)  # [N*k] global expert ids
        if shard is not None:
            e0 = shard * e_loc
            local = (flat_e >= e0) & (flat_e < e0 + e_loc)
            flat_e = jnp.where(local, flat_e - e0, e_loc)  # dead = e_loc
        order = jnp.argsort(flat_e)  # stable: ties keep token order
        sorted_e = flat_e[order]
        tok_of_row = (jnp.arange(N * k) // k)[order]
        xs = jnp.take(x_l, tok_of_row, axis=0)  # [N*k, D]
        n_groups = e_loc + (1 if shard is not None else 0)
        group_sizes = jnp.bincount(sorted_e, length=n_groups)
        o = _grouped_mlp(
            lp_l, xs, group_sizes, sorted_e,
            pad_dead_expert=shard is not None,
        )  # [N*k, D]
        # unsort back to [N, k] assignment order and combine
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
        o = jnp.take(o, inv, axis=0).reshape(N, k, D)
        w = topw_l
        if shard is not None:
            keep = (topi_l >= e0) & (topi_l < e0 + e_loc)
            w = jnp.where(keep, w, 0.0)
        return jnp.sum(o * w[..., None].astype(o.dtype), axis=1)  # [N, D]

    if mesh is not None and mesh.size > 1 and E % max(ep, 1) == 0:
        # Fully-manual shard_map over BOTH "ep" and "tp": the expert
        # stacks are tp-sharded on their hidden axis too (param_specs),
        # and a partial-manual region with tp left auto crashes the
        # partitioner around ragged_dot. gate/up contract the unsharded
        # D (outputs F/tp-local, no collective); down contracts the
        # tp-sharded F, so the final psum sums over ("tp", "ep") — one
        # collective for both the hidden reduction and the expert
        # combine.
        expert_specs = {
            "w_gate": P("ep", None, "tp"),
            "w_up": P("ep", None, "tp"),
            "w_down": P("ep", "tp", None),
            "w_gate_scale": P("ep", "tp"),
            "w_up_scale": P("ep", "tp"),
            "w_down_scale": P("ep", None),
        }
        expert_keys = tuple(n for n in expert_specs if n in lp)
        lp_experts = {n: lp[n] for n in expert_keys}
        lp_specs = {n: expert_specs[n] for n in expert_keys}
        x_in = x
        if jax.default_backend() == "cpu":
            # XLA:CPU dies on bf16 operands inside this manual region
            # ("Invalid binary instruction opcode copy") — the virtual-
            # mesh test rung converts OUTSIDE the shard_map (strictly
            # more precise); TPU runs bf16 as-is
            lp_experts = {
                n: (a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a)
                for n, a in lp_experts.items()
            }
            x_in = x.astype(jnp.float32)

        def shard_fn(lp_e, x_r, topw_r, topi_r):
            shard = jax.lax.axis_index("ep")
            out = local_compute(lp_e, x_r, topw_r, topi_r, shard)
            return jax.lax.psum(out, ("ep", "tp"))

        out = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(lp_specs, P(None, None), P(None, None), P(None, None)),
            out_specs=P(None, None),
            axis_names={"ep", "tp"},
            check_vma=False,
        )(lp_experts, x_in, topw, topi).astype(h.dtype)
    else:
        out = local_compute(lp, x, topw, topi, None)
    return out.reshape(B, T, D)
