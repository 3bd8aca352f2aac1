"""Kimi-Linear: a decoder whose layers are of several kinds, on the
engine's normal step.

Mixers (``linear_attn_config``: 1-based layer lists):

- KDA, a gated delta rule with a per-channel decay. Each sequence keeps
  a FIXED-SIZE state per layer — a ``[H, d, d]`` float32 matrix per head
  and the last ``kernel - 1`` inputs of a short causal convolution — in
  the state plane, at the slot the scheduler gave the sequence.
- MLA, latent attention without rotary embedding. The paged cache holds
  one ``kv_lora_rank + qk_rope_head_dim`` wide row a token a layer (the
  normalised latent and the shared key part); queries absorb the latent's
  up-projection, so attention runs over the latent rows themselves.

Feed-forward: a dense SiLU-gated MLP in the first
``first_k_dense_replace`` layers, then an expert layer — sigmoid router
over ALL experts, top-k by score plus selection bias, weights from the
scores, renormalised and scaled — that is told which experts it holds
(``num_experts`` of them, the ``expert_shard_index``-th of
``expert_shards`` runs) and computes their part plus the shared expert.

How this differs from ``models/llama.py`` for the engine:

- the two cache pytrees the step threads through are ``pages``
  (``{"latent": [Lm, slots, C padded to whole 128-lane tiles]}``) and
  ``state`` (``{"kda": [Lk, S, H, d, d] f32, "conv": [Lk, S, (kernel-1) *
  3*H*d / 128, 128] f32, "counts": int32 [3]}``) instead of K and V;
- a row's table is its pages THEN its state slot: the scheduler appends
  the slot as the table's last column (``Scheduler.state_slots``), so no
  step function grows an argument. Slot 0, like page 0, is the garbage
  slot of padded rows;
- a row that starts at position 0 starts from a zero state, whatever its
  slot held: a reused slot needs no clearing, and a preempted sequence
  recomputed from its tokens is exact. Right padding (tokens at or past
  ``context_lens``) never enters the state or the convolution tail.

Layers are unrolled in Python (nine kinds-of-layer do not share a scan
body); every int8 matrix is read in place out of its stacked array by
the ``qmm`` kernels where its shape allows (a multiple of 128 both ways),
and through the plain mixed dot where not (``mla_wkva``: 576 outputs,
``kda_wb``: 32).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]

LOW_RANK = 128       # decay and output-gate bottleneck (not in config.json)
MLA_QUERY_TOKENS = 512  # query tokens whose scores exist at once (prefill)
# at most this many tokens go through every held expert at once; more are
# sorted by expert and go through the grouped matmul
MOE_DENSE_TOKENS = 64
# what a step holds beside weights, pages and state, at the published
# widths: a prefill tile's attention scores (512 tokens x 32 heads x the
# table's 4k rows, float32, a few copies), the decay blocks of a KDA
# chunk, the grouped matmuls' sorted rows — the engine leaves this free
# when it sizes the pages
STEP_TRANSIENT_BYTES = 4 << 30
COUNT_NAMES = hybrid.MOE_COUNT_NAMES
RECURRENT_STATE = True   # every admitted sequence holds a state slot


class Geometry:
    """The sizes of one configuration, worked out once."""

    def __init__(self, cfg: ModelConfig):
        la = cfg.linear_attn_config or {}
        self.L = cfg.num_hidden_layers
        self.D = cfg.hidden_size
        self.V = cfg.vocab_size
        self.kda_layers = [i - 1 for i in la.get("kda_layers", [])]
        self.mla_layers = [i - 1 for i in la.get("full_attn_layers", [])]
        if sorted(self.kda_layers + self.mla_layers) != list(range(self.L)):
            raise ValueError(
                "linear_attn_config must give every one of the "
                f"{self.L} layers one mixer: kda_layers "
                f"{la.get('kda_layers')}, full_attn_layers "
                f"{la.get('full_attn_layers')}"
            )
        self.Hl = la.get("num_heads", cfg.num_attention_heads)
        self.dl = la.get("head_dim", cfg.head_dim)
        self.kernel = la.get("short_conv_kernel_size", 4)
        self.HD = self.Hl * self.dl
        self.H = cfg.num_attention_heads
        self.nope = cfg.qk_nope_head_dim
        self.rope = cfg.qk_rope_head_dim
        self.vd = cfg.v_head_dim
        self.rank = cfg.kv_lora_rank
        self.C = self.rank + self.rope          # one cached latent row
        # as stored: padded to whole 128-lane tiles. A 576-wide minor
        # dimension makes the chip's default layout put the SLOTS minor
        # instead, and every step then copies the whole plane into the
        # row-major layout the step wants and back (measured: 11 ms of a
        # 29 ms decode step, PERF.md PR 29)
        self.Cpad = -(-self.C // 128) * 128
        self.latent = hybrid.Latent(self.H, self.nope, self.rope, self.vd,
                                    self.rank, self.Cpad, MLA_QUERY_TOKENS)
        self.F = cfg.intermediate_size
        self.Fe = cfg.moe_intermediate_size
        self.E = cfg.num_experts                # held here
        self.E_all = cfg.num_experts * cfg.expert_shards
        self.e0 = cfg.expert_shard_index * cfg.num_experts
        self.dense_layers = list(range(min(cfg.first_k_dense_replace, self.L)))
        self.moe_layers = [i for i in range(self.L) if i not in self.dense_layers]
        if cfg.q_lora_rank is not None:
            raise ValueError("kimi_linear with q_lora_rank is not built")
        if cfg.num_expert_group != 1 or cfg.topk_group != 1:
            raise ValueError("kimi_linear expert groups other than 1 are not built")
        if cfg.moe_router_activation_func != "sigmoid":
            raise ValueError(
                f"kimi_linear router {cfg.moe_router_activation_func!r} is "
                "not built (sigmoid only)"
            )
        if self.moe_layers and cfg.num_shared_experts != 1:
            raise ValueError("kimi_linear is built for exactly 1 shared expert")

    def kind_index(self, layer: int) -> tuple[str, int, str, int]:
        """(mixer kind, index in its stack, ffn kind, index in its stack)."""
        if layer in self.kda_layers:
            mixer = ("kda", self.kda_layers.index(layer))
        else:
            mixer = ("mla", self.mla_layers.index(layer))
        if layer in self.dense_layers:
            ffn = ("dense", self.dense_layers.index(layer))
        else:
            ffn = ("moe", self.moe_layers.index(layer))
        return (*mixer, *ffn)


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

# name -> axis the int8 scales reduce over (weight-only int8, as
# models/quant.py: per output channel; embedding rows per row)
QUANT_AXIS = {
    "embed": -1, "lm_head": -2,
    "kda_wq": -2, "kda_wk": -2, "kda_wv": -2, "kda_wfa": -2, "kda_wfb": -2,
    "kda_wb": -2, "kda_wga": -2, "kda_wgb": -2, "kda_wo": -2,
    "mla_wq": -2, "mla_wkva": -2, "mla_wkvb": -2, "mla_wo": -2,
    "w_gate": -2, "w_up": -2, "w_down": -2,
    "ws_gate": -2, "ws_up": -2, "ws_down": -2,
    "we_gate": -2, "we_up": -2, "we_down": -2,
}


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype); layer parameters are stacked per KIND."""
    g = Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    Lk, Lm = len(g.kda_layers), len(g.mla_layers)
    Ld, Le = len(g.dense_layers), len(g.moe_layers)
    D, HD, R, K1 = g.D, g.HD, LOW_RANK, g.kernel
    shapes: dict = {
        "embed": ((g.V, D), bf16),
        "final_norm": ((D,), f32),
        "lm_head": ((D, g.V), bf16),
        "attn_norm": ((g.L, D), f32),
        "mlp_norm": ((g.L, D), f32),
    }
    if Lk:
        shapes.update({
            "kda_wq": ((Lk, D, HD), bf16),
            "kda_wk": ((Lk, D, HD), bf16),
            "kda_wv": ((Lk, D, HD), bf16),
            "kda_conv": ((Lk, K1, 3 * HD), f32),   # q | k | v channels
            "kda_wfa": ((Lk, D, R), bf16),
            "kda_wfb": ((Lk, R, HD), bf16),
            "kda_A_log": ((Lk, g.Hl), f32),
            "kda_dt_bias": ((Lk, HD), f32),
            "kda_wb": ((Lk, D, g.Hl), bf16),
            "kda_wga": ((Lk, D, R), bf16),
            "kda_wgb": ((Lk, R, HD), bf16),
            "kda_onorm": ((Lk, g.dl), f32),
            "kda_wo": ((Lk, HD, D), bf16),
        })
    if Lm:
        shapes.update({
            "mla_wq": ((Lm, D, g.H * (g.nope + g.rope)), bf16),
            "mla_wkva": ((Lm, D, g.C), bf16),
            "mla_kvnorm": ((Lm, g.rank), f32),
            "mla_wkvb": ((Lm, g.rank, g.H * (g.nope + g.vd)), bf16),
            "mla_wo": ((Lm, g.H * g.vd, D), bf16),
        })
    if Ld:
        shapes.update({
            "w_gate": ((Ld, D, g.F), bf16),
            "w_up": ((Ld, D, g.F), bf16),
            "w_down": ((Ld, g.F, D), bf16),
        })
    if Le:
        shapes.update({
            "router": ((Le, D, g.E_all), f32),
            "router_bias": ((Le, g.E_all), f32),
            "ws_gate": ((Le, D, g.Fe), bf16),
            "ws_up": ((Le, D, g.Fe), bf16),
            "ws_down": ((Le, g.Fe, D), bf16),
            "we_gate": ((Le, g.E, D, g.Fe), bf16),
            "we_up": ((Le, g.E, D, g.Fe), bf16),
            "we_down": ((Le, g.E, g.Fe, D), bf16),
        })
    return shapes


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """One device holds everything (check_engine refuses tp/ep/pp > 1)."""
    return {name: P() for name in param_shapes(cfg)}


def _draw_one(name: str, key, shape: tuple[int, ...]):
    """One leading slice of parameter ``name`` in float32 — the recipe:
    norms 1; selection bias 0; ``A_log = log(U(1, 16))``; ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1]; everything
    else ``normal / sqrt(fan_in)`` (fan_in: the second-to-last axis)."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    if name == "kda_A_log":
        return hybrid.draw_A_log(key, shape)
    if name == "kda_dt_bias":
        return hybrid.draw_dt_bias(key, shape)
    return hybrid.draw_normal(key, shape)


def _init(cfg: ModelConfig, seed: int, mesh, quantize: bool, dtype) -> Params:
    """The seeded draw (``hybrid.init``: parameter ``i`` of
    ``param_shapes`` order, layer ``j``, expert ``e``)."""
    return hybrid.init(param_shapes(cfg), _draw_one, QUANT_AXIS, seed, mesh,
                       quantize, dtype)


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Optional[Mesh] = None,
                specs: Optional[dict] = None, dtype=None) -> Params:
    """The seeded draw, unquantized. ``dtype`` overrides bfloat16 for the
    matrices (float32 in tests, so the program meets its reference to
    rounding)."""
    return _init(cfg, seed, mesh, False, dtype)


def init_params_quantized(cfg: ModelConfig, seed: int = 0,
                          mesh: Optional[Mesh] = None,
                          specs: Optional[dict] = None) -> Params:
    """The seeded draw as served: every matrix weight-only int8 with a
    float32 scale per output channel, made and quantized on the device."""
    return _init(cfg, seed, mesh, True, None)


# ---------------------------------------------------------------------------
# The cache: latent pages, and the per-sequence state plane
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, num_blocks: int, block_size: int,
                 state_slots: int) -> tuple[dict, dict]:
    g = Geometry(cfg)
    Lk, Lm = len(g.kda_layers), len(g.mla_layers)
    pages = {"latent": (max(1, Lm), num_blocks * block_size, g.Cpad)}
    state = {
        "kda": (max(1, Lk), state_slots, g.Hl, g.dl, g.dl),
        # a slot's tail rows one after another, in rows of one lane tile
        "conv": (max(1, Lk), state_slots,
                 *hybrid.conv_tail_shape(g.kernel, 3 * g.HD)),
    }
    return pages, state


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int) -> int:
    """Bytes one block of pages takes over all layers (the engine sizes
    the pool with it)."""
    g = Geometry(cfg)
    return max(1, len(g.mla_layers)) * block_size * g.Cpad * itemsize


def state_bytes(cfg: ModelConfig, state_slots: int, itemsize: int) -> int:
    g = Geometry(cfg)
    per_slot = g.Hl * g.dl * g.dl * 4 + (g.kernel - 1) * 3 * g.HD * 4
    return max(1, len(g.kda_layers)) * state_slots * per_slot


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None, state_slots: int = 2):
    """(pages, state), zeroed. ``state_slots`` counts the garbage slot 0."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("kimi_linear has no int8 latent cache")
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    pshape, sshape = cache_shapes(cfg, num_blocks, block_size, state_slots)
    pages = {"latent": jnp.zeros(pshape["latent"], dtype, device=sh)}
    state = {
        "kda": jnp.zeros(sshape["kda"], jnp.float32, device=sh),
        # float32 like the matmul results the convolution reads: a token
        # sees the same inputs whether they came from the tail or the chunk
        "conv": jnp.zeros(sshape["conv"], jnp.float32, device=sh),
        # cumulative, on the device, read at a profiler capture's edges
        # (engine.program_counts): expert-layer calls, assignments of real
        # tokens to held experts, held experts touched (COUNT_NAMES)
        "counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh),
    }
    return pages, state


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong."""
    hybrid.check_engine(
        config, "model_type kimi_linear (recurrent state + latent pages)")



# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


# shared with the other hybrid family (models/hybrid.py)
kernels_active = hybrid.kernels_active
_mm = hybrid.mm
_gated_mlp = hybrid.gated_mlp
kda_decode, kda_chunked = hybrid.delta_decode, hybrid.delta_chunked
kda_chunk_for = hybrid.delta_chunk_for
moe_local_dense, moe_local_grouped = hybrid.moe_local_dense, hybrid.moe_local_grouped


def kda_decay_log(p: Params, f: jax.Array, idx: int, g: Geometry) -> jax.Array:
    """log a_t = -exp(A_log_h) * softplus(f_t + dt_bias): [..., H, d], <= 0."""
    f = f.astype(jnp.float32) + p["kda_dt_bias"][idx]
    f = f.reshape(*f.shape[:-1], g.Hl, g.dl)
    return -jnp.exp(p["kda_A_log"][idx])[:, None] * jax.nn.softplus(f)


def moe_routing(cfg: ModelConfig, p: Params, x: jax.Array, idx: int):
    """x [N, D] -> (weights [N, k] float32, expert ids [N, k]) over ALL
    experts (``hybrid.sigmoid_routing``)."""
    return hybrid.sigmoid_routing(
        p["router"][idx], p["router_bias"][idx], x, cfg.num_experts_per_token,
        cfg.moe_renormalize, cfg.routed_scaling_factor)


def moe_ffn(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array,
            idx: int, valid: Optional[jax.Array] = None,
            h_route: Optional[jax.Array] = None):
    """This process's part of the expert layer: its own experts' share of
    the routed sum (what other shards' experts add is theirs to compute)
    plus the shared expert. Returns (out, counts int32 [3]): this call,
    the assignments of real tokens (``valid`` [B, T]; padding is not
    traffic) to held experts, and the held experts they touched.
    ``h_route``: the same hidden state before it was rounded to the
    activation dtype — the router reads that one (a choice among 256
    near-equal scores turns on the last bits)."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    w, topi = moe_routing(
        cfg, p, x if h_route is None else h_route.reshape(B * T, D), idx)
    routed, counts = hybrid.moe_local(
        p, x, w, topi, idx, g.e0, g.E, MOE_DENSE_TOKENS, valid)
    shared = _gated_mlp(p, ("ws_gate", "ws_up", "ws_down"), h, idx)
    return routed.reshape(B, T, D) + shared.astype(jnp.float32), counts


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"latent": [Lm, slots, Cpad]}
    state: dict,              # {"kda": [Lk, S, H, d, d], "conv": [Lk, S, 3 * 3HD / 128, 128], "counts": [3]}
    tokens: jax.Array,        # [B, T]
    positions: jax.Array,     # [B, T] (padded: 0)
    slot_mapping: jax.Array,  # [B*T] flat page slots (padded: 0)
    block_tables: jax.Array,  # [B, pages + 1]: the LAST column is the state slot
    context_lens: jax.Array,  # [B] valid tokens incl. the new ones
    last_token_idx: jax.Array,
    block_size: int,
    extra_embeds: Optional[jax.Array] = None,
    embeds_mask: Optional[jax.Array] = None,
    logits_all: bool = False,
):
    """One model step: (logits [B, V], pages, state). Same contract as
    ``models/llama.py`` ``forward``; the engine threads ``pages`` and
    ``state`` where it threads K and V."""
    if extra_embeds is not None or logits_all:
        raise NotImplementedError(
            "kimi_linear: no injected embeddings, no all-position logits")
    g = Geometry(cfg)
    B, T = tokens.shape
    eps = cfg.rms_norm_eps
    tables, sslot = block_tables[:, :-1], block_tables[:, -1]
    start = positions[:, 0]
    n_valid = jnp.clip(context_lens - start, 0, T)            # [B]
    valid = jnp.arange(T)[None, :] < n_valid[:, None]         # [B, T]
    fresh = start == 0                                        # zero state in
    latent, kda_plane, conv_plane = pages["latent"], state["kda"], state["conv"]
    counts = state["counts"]
    # the residual stream is float32 (the layers add small terms to a
    # large sum: rounding it to bf16 at every add is the first thing that
    # turns a near-tie of the router over); the layers read it rounded to
    # the activation dtype, as the matmul kernels take it
    x = llama.embed_lookup(params, tokens)
    act = x.dtype
    x = x.astype(jnp.float32)

    def kda_mixer(h, ki, kda_plane, conv_plane):
        qkv = jnp.concatenate(
            [_mm(params, n, h, ki) for n in ("kda_wq", "kda_wk", "kda_wv")], -1)
        y, conv_plane = hybrid.conv_step(
            conv_plane, ki, sslot, fresh, n_valid, qkv, params["kda_conv"][ki],
            kernels=kernels_active())
        q, k, v = (a.reshape(B, T, g.Hl, g.dl)
                   for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * g.dl ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        glog = kda_decay_log(params, _mm(
            params, "kda_wfb", _mm(params, "kda_wfa", h, ki).astype(act), ki), ki, g)
        beta = jax.nn.sigmoid(_mm(params, "kda_wb", h, ki).astype(jnp.float32))
        glog = jnp.where(valid[:, :, None, None], glog, 0.0)
        beta = jnp.where(valid[:, :, None], beta, 0.0)
        if T == 1 and kernels_active():
            # in place on the plane: no gather before, no scatter after
            from dynamo_tpu.ops.kda import kda_decode_update

            o, kda_plane = kda_decode_update(
                kda_plane, jnp.int32(ki), sslot, fresh, q[:, 0], k[:, 0],
                v[:, 0], glog[:, 0], beta[:, 0],
                interpret=jax.default_backend() != "tpu")
            o = o[:, None]
        else:
            S = jnp.where(fresh[:, None, None, None], 0.0, kda_plane[ki, sslot])
            if T == 1:
                o, S = kda_decode(
                    q[:, 0], k[:, 0], v[:, 0], glog[:, 0], beta[:, 0], S)
                o = o[:, None]
            else:
                o, S = kda_chunked(q, k, v, glog, beta, S)
            kda_plane = kda_plane.at[ki, sslot].set(S)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * params["kda_onorm"][ki]
        gate = _mm(params, "kda_wgb", _mm(params, "kda_wga", h, ki).astype(act), ki)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32).reshape(o.shape))
        out = _mm(params, "kda_wo", o.reshape(B, T, g.HD).astype(h.dtype), ki)
        return out, kda_plane, conv_plane

    def mla_mixer(h, mi, latent):
        return hybrid.mla_mixer(
            params, h, mi, latent, g.latent, eps, positions, slot_mapping,
            tables, context_lens, block_size, kernels_active())

    for layer in range(g.L):
        mixer, mi, ffn, fi = g.kind_index(layer)
        h = llama.rmsnorm(x, params["attn_norm"][layer], eps).astype(act)
        if mixer == "kda":
            out, kda_plane, conv_plane = kda_mixer(h, mi, kda_plane, conv_plane)
        else:
            out, latent = mla_mixer(h, mi, latent)
        x = x + out.astype(jnp.float32)
        h32 = llama.rmsnorm(x, params["mlp_norm"][layer], eps)
        h = h32.astype(act)
        if ffn == "dense":
            out = _gated_mlp(params, ("w_gate", "w_up", "w_down"), h, fi)
        else:
            out, seen = moe_ffn(cfg, g, params, h, fi, valid, h32)
            counts = counts + seen
        x = x + out.astype(jnp.float32)

    x = llama.rmsnorm(x, params["final_norm"], eps).astype(act)
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (llama.lm_head(params, x_last), {"latent": latent},
            {"kda": kda_plane, "conv": conv_plane, "counts": counts})
