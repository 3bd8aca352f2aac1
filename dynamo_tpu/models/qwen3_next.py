"""Qwen3-Next: Gated DeltaNet layers with every
``full_attention_interval``-th layer gated GQA attention, and an expert
block in every layer, on the engine's normal step.

Mixers (layer ``i``, 0-based, attends where ``(i + 1) %
full_attention_interval == 0``):

- Gated DeltaNet: the gated delta rule with ONE decay scalar a value
  head; ``linear_num_key_heads`` q/k heads each serve ``Hv / Hk`` value
  heads; one depthwise causal convolution over the concatenated q, k, v
  channels; the output RMS-normalised a head and gated by ``silu(z)``.
  Each sequence keeps a ``[Hv, dk, dv]`` float32 state a layer and the
  convolution's last ``kernel - 1`` inputs in the state plane, at the
  slot the scheduler gave it. It is KDA's recurrence with the log-decay
  constant over a head's channels: the chunked form and the decode
  kernel are the ones ``models/kimi_linear.py`` runs (``models/hybrid.py``,
  ``ops/kda.py``).
- Gated attention: ``q_proj`` carries a sigmoid output gate beside each
  query head; q and k are RMS-normalised a head (``1 + w``); the first
  ``partial_rotary_factor`` of each head is rotated; K and V live in
  pages read by the paged-attention kernels the dense families run. The
  pages are stored as their (token, head) rows, ``[L_attn, slots * Hkv,
  Dh]`` — the bytes of ``[L_attn, slots, Hkv, Dh]`` in the order the
  decode kernel's page view ``[bs * Hkv, Dh]`` reads them: with 2 heads
  of 256 the 4-D shape gets a 2-row tile on the chip and every decode
  call would first copy the whole pool into the view's 16-row tiles
  (1.6 GB a call at 6 000 pages; found by compiling for the described
  chip). Prefill gathers the rows' own pages (10 MB a row at a table of
  40) and hands the prefill kernel that small cache.

Feed-forward, every layer: softmax over ALL experts, the top
``num_experts_per_tok`` renormalised, of which this process computes the
part its own experts give (``num_experts`` held, the
``expert_shard_index``-th of ``expert_shards`` runs), plus a shared
expert scaled by ``sigmoid(x . w_sg)``.

For the engine this is ``models/kimi_linear.py``'s arrangement with K/V
pages where that has latent pages: ``pages`` = ``{"k", "v"}``, ``state``
= ``{"gdn", "conv", "counts"}``, a row's table is its pages then its
state slot, a row at position 0 starts from a zero state, right padding
never enters state or tail.

Column order of the fused projections (a permutation of the released
code's, which groups them by key head): ``gdn_wqkvz`` = q (Hk*dk) | k
(Hk*dk) | v (Hv*dv) | z (Hv*dv); ``gdn_wba`` = b (Hv) | a (Hv);
``attn_wq`` = per head [q (Dh) | gate (Dh)].
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]

RECURRENT_STATE = True   # every admitted sequence holds a state slot
# at most this many tokens go through every held expert at once; more are
# sorted by expert and go through the grouped matmul. Measured on a v5e at
# this shape (256 held experts of 2048 x 512, a layer; PERF.md, PR 33):
# every-expert 1.15-1.22 ms up to 64 tokens, 1.48 / 2.84 / 5.32 / 8.90 /
# 11.13 at 128 / 256 / 512 / 768 / 1 024; sorted rows 4.1-5.8 up to 64,
# 6.85 / 8.99 / 9.39 / 9.80 / 10.45 — the sorted form pays a bf16 copy of
# the layer's experts whatever the rows, and wins from about 1 000 tokens
MOE_DENSE_TOKENS = 512
# what a step holds beside weights, pages and state, at the published
# widths: the grouped matmuls' bf16 copies of a layer's held experts
# (1.6 GB) and sorted rows, a prefill chunk's float32 projections
STEP_TRANSIENT_BYTES = 4 << 30
COUNT_NAMES = hybrid.MOE_COUNT_NAMES + (
    "recurrent_prefill_tokens", "recurrent_prefill_chunks")


class Geometry:
    """The sizes of one configuration, worked out once."""

    def __init__(self, cfg: ModelConfig):
        self.L = cfg.num_hidden_layers
        self.D = cfg.hidden_size
        self.V = cfg.vocab_size
        every = cfg.full_attention_interval
        if every < 1:
            raise ValueError("qwen3_next needs full_attention_interval >= 1")
        self.attn_layers = [i for i in range(self.L) if (i + 1) % every == 0]
        self.gdn_layers = [i for i in range(self.L) if (i + 1) % every != 0]
        self.H = cfg.num_attention_heads
        self.Hk = cfg.num_key_value_heads
        self.Dh = cfg.head_dim
        self.rot = int(self.Dh * cfg.partial_rotary_factor)
        self.Hlk = cfg.linear_num_key_heads
        self.Hl = cfg.linear_num_value_heads
        self.dk = cfg.linear_key_head_dim
        self.dl = cfg.linear_value_head_dim
        self.kernel = cfg.linear_conv_kernel_dim
        self.QK = self.Hlk * self.dk              # q (and k) channels
        self.VD = self.Hl * self.dl               # v (and z) channels
        self.conv = 2 * self.QK + self.VD         # channels the convolution sees
        self.Fe = cfg.moe_intermediate_size
        self.Fs = cfg.shared_expert_intermediate_size
        self.E = cfg.num_experts                  # held here
        self.E_all = cfg.num_experts * cfg.expert_shards
        self.e0 = cfg.expert_shard_index * cfg.num_experts
        self.k = cfg.num_experts_per_tok
        if self.gdn_layers and (
                self.Hlk < 1 or self.Hl % self.Hlk or self.dk != self.dl):
            raise ValueError(
                "qwen3_next Gated DeltaNet is built for value heads a "
                "multiple of the key heads and equal key / value head sizes: "
                f"{self.Hlk} / {self.Hl} heads of {self.dk} / {self.dl}")
        if self.rot % 2 or not 0 < self.rot <= self.Dh:
            raise ValueError(
                f"partial_rotary_factor {cfg.partial_rotary_factor} of head_dim "
                f"{self.Dh} is no even number of rotated dims")
        if cfg.decoder_sparse_step != 1 or cfg.mlp_only_layers:
            raise ValueError(
                "qwen3_next is built with the expert block in every layer "
                "(decoder_sparse_step 1, mlp_only_layers [])")
        if self.Fs < 1 or self.E < 1 or not 0 < self.k <= self.E_all:
            raise ValueError(
                "qwen3_next needs routed experts, num_experts_per_tok of them "
                "a token, and a shared expert")

    def kind_index(self, layer: int) -> tuple[str, int]:
        """(mixer kind, index in its stack)."""
        if layer in self.gdn_layers:
            return "gdn", self.gdn_layers.index(layer)
        return "attn", self.attn_layers.index(layer)


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

# name -> axis the int8 scales reduce over (weight-only int8, as
# models/quant.py: per output channel; embedding rows per row)
QUANT_AXIS = {
    "embed": -1, "lm_head": -2,
    "gdn_wqkvz": -2, "gdn_wba": -2, "gdn_wo": -2,
    "attn_wq": -2, "attn_wk": -2, "attn_wv": -2, "attn_wo": -2,
    "ws_gate": -2, "ws_up": -2, "ws_down": -2,
    "we_gate": -2, "we_up": -2, "we_down": -2,
}
# RMSNorm weights stored as (w - 1): the norm multiplies by (1 + w)
_NORMS_BIAS_ONE = ("final_norm", "attn_norm", "mlp_norm", "attn_qnorm",
                   "attn_knorm")


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype); layer parameters are stacked per KIND."""
    g = Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    Lg, La, D = len(g.gdn_layers), len(g.attn_layers), g.D
    shapes: dict = {
        "embed": ((g.V, D), bf16),
        "final_norm": ((D,), f32),
        "lm_head": ((D, g.V), bf16),
        "attn_norm": ((g.L, D), f32),
        "mlp_norm": ((g.L, D), f32),
    }
    if Lg:
        shapes.update({
            "gdn_wqkvz": ((Lg, D, g.conv + g.VD), bf16),   # q | k | v | z
            "gdn_wba": ((Lg, D, 2 * g.Hl), bf16),          # b | a
            "gdn_conv": ((Lg, g.kernel, g.conv), f32),     # q | k | v channels
            "gdn_A_log": ((Lg, g.Hl), f32),
            "gdn_dt_bias": ((Lg, g.Hl), f32),
            "gdn_onorm": ((Lg, g.dl), f32),
            "gdn_wo": ((Lg, g.VD, D), bf16),
        })
    if La:
        shapes.update({
            "attn_wq": ((La, D, g.H * 2 * g.Dh), bf16),    # a head: q | gate
            "attn_wk": ((La, D, g.Hk * g.Dh), bf16),
            "attn_wv": ((La, D, g.Hk * g.Dh), bf16),
            "attn_qnorm": ((La, g.Dh), f32),
            "attn_knorm": ((La, g.Dh), f32),
            "attn_wo": ((La, g.H * g.Dh, D), bf16),
        })
    shapes.update({
        "router": ((g.L, D, g.E_all), f32),
        "shared_gate": ((g.L, D, 1), f32),
        "ws_gate": ((g.L, D, g.Fs), bf16),
        "ws_up": ((g.L, D, g.Fs), bf16),
        "ws_down": ((g.L, g.Fs, D), bf16),
        "we_gate": ((g.L, g.E, D, g.Fe), bf16),
        "we_up": ((g.L, g.E, D, g.Fe), bf16),
        "we_down": ((g.L, g.E, g.Fe, D), bf16),
    })
    return shapes


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """One device holds everything (check_engine refuses tp/ep/pp > 1)."""
    return {name: P() for name in param_shapes(cfg)}


def _draw_one(name: str, key, shape: tuple[int, ...]):
    """One leading slice of parameter ``name`` in float32 — the recipe:
    the ``(1 + w)`` norms 0 (layer, final, q and k norms), the Gated
    DeltaNet output norm 1; ``A_log = log(U(1, 16))``; ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1]; everything
    else ``normal / sqrt(fan_in)`` (fan_in: the second-to-last axis)."""
    if name in _NORMS_BIAS_ONE:
        return jnp.zeros(shape, jnp.float32)
    if name == "gdn_onorm":
        return jnp.ones(shape, jnp.float32)
    if name == "gdn_A_log":
        return hybrid.draw_A_log(key, shape)
    if name == "gdn_dt_bias":
        return hybrid.draw_dt_bias(key, shape)
    return hybrid.draw_normal(key, shape)


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Optional[Mesh] = None,
                specs: Optional[dict] = None, dtype=None) -> Params:
    """The seeded draw (``hybrid.init``), unquantized. ``dtype``
    overrides bfloat16 for the matrices (float32 in tests, so the program
    meets its reference to rounding)."""
    return hybrid.init(param_shapes(cfg), _draw_one, QUANT_AXIS, seed, mesh,
                       False, dtype)


def init_params_quantized(cfg: ModelConfig, seed: int = 0,
                          mesh: Optional[Mesh] = None,
                          specs: Optional[dict] = None) -> Params:
    """The seeded draw as served: every matrix weight-only int8 with a
    float32 scale per output channel, made and quantized on the device;
    the convolution, the router and the shared expert's gate vector stay
    float32."""
    return hybrid.init(param_shapes(cfg), _draw_one, QUANT_AXIS, seed, mesh,
                       True, None)


# ---------------------------------------------------------------------------
# The cache: K/V pages of the attention layers, and the state plane
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, num_blocks: int, block_size: int,
                 state_slots: int) -> tuple[dict, dict]:
    g = Geometry(cfg)
    Lg, La = len(g.gdn_layers), len(g.attn_layers)
    # a slot's Hk rows of Dh, one after the other (the module docstring)
    kv = (max(1, La), num_blocks * block_size * g.Hk, g.Dh)
    pages = {"k": kv, "v": kv}
    state = {
        "gdn": (max(1, Lg), state_slots, g.Hl, g.dk, g.dl),
        # a slot's tail rows one after another, in rows of one lane tile
        "conv": (max(1, Lg), state_slots,
                 *hybrid.conv_tail_shape(g.kernel, g.conv)),
    }
    return pages, state


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int) -> int:
    """Bytes one block of K and V pages takes over the attention layers
    (the engine sizes the pool with it)."""
    g = Geometry(cfg)
    return 2 * max(1, len(g.attn_layers)) * block_size * g.Hk * g.Dh * itemsize


def state_bytes(cfg: ModelConfig, state_slots: int, itemsize: int) -> int:
    g = Geometry(cfg)
    per_slot = g.Hl * g.dk * g.dl * 4 + (g.kernel - 1) * g.conv * 4
    return max(1, len(g.gdn_layers)) * state_slots * per_slot


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None, state_slots: int = 2):
    """(pages, state), zeroed. ``state_slots`` counts the garbage slot 0."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("qwen3_next has no int8 K/V cache")
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    pshape, sshape = cache_shapes(cfg, num_blocks, block_size, state_slots)
    pages = {n: jnp.zeros(s, dtype, device=sh) for n, s in pshape.items()}
    state = {
        "gdn": jnp.zeros(sshape["gdn"], jnp.float32, device=sh),
        # float32 like the matmul results the convolution reads: a token
        # sees the same inputs whether they came from the tail or the chunk
        "conv": jnp.zeros(sshape["conv"], jnp.float32, device=sh),
        # cumulative, on the device, read at a profiler capture's edges
        # (engine.program_counts, COUNT_NAMES): expert-layer calls,
        # assignments of real tokens to held experts, held experts
        # touched; real tokens and blocks of tokens through the chunked rule
        "counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh),
    }
    return pages, state


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong."""
    hybrid.check_engine(
        config, "model_type qwen3_next (recurrent state beside K/V pages)")


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def norm1(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """``x / rms(x) * (1 + w)`` in float32."""
    return llama.rmsnorm(x.astype(jnp.float32), w, eps, bias_one=True)


def partial_rope(q: jax.Array, k: jax.Array, positions: jax.Array,
                 theta: float, rot: int) -> tuple[jax.Array, jax.Array]:
    """Rotary embedding (half-rotation form) on the first ``rot`` dims of
    each head; the others pass. q, k [B, T, H*, Dh]."""
    qr, kr = llama.rope(q[..., :rot], k[..., :rot], positions, theta)
    return (jnp.concatenate([qr, q[..., rot:]], axis=-1),
            jnp.concatenate([kr, k[..., rot:]], axis=-1))


def gdn_decay_log(p: Params, a: jax.Array, idx: int) -> jax.Array:
    """g_t = -exp(A_log_h) * softplus(a_t + dt_bias_h): [..., Hv], <= 0."""
    a = a.astype(jnp.float32) + p["gdn_dt_bias"][idx]
    return -jnp.exp(p["gdn_A_log"][idx]) * jax.nn.softplus(a)


def moe_routing(cfg: ModelConfig, p: Params, x: jax.Array, idx: int):
    """x [N, D] -> (weights [N, k] float32, expert ids [N, k]) over ALL
    experts: softmax over every expert, the k largest, renormalised over
    the chosen (``norm_topk_prob``)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(x.astype(jnp.float32) @ p["router"][idx], axis=-1)
    w, topi = jax.lax.top_k(s, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, topi


def moe_ffn(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array,
            idx: int, valid: Optional[jax.Array] = None,
            h_route: Optional[jax.Array] = None, shared: bool = True):
    """This process's part of the expert block: its own experts' share of
    the routed sum (``hybrid.moe_local``) plus, with ``shared``, the
    gated shared expert. Returns (out, counts int32 [3]). ``h_route``:
    the same hidden state before it was rounded to the activation dtype —
    the router and the shared expert's gate read that one (a choice among
    512 near-equal scores turns on the last bits)."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    x32 = (x if h_route is None else h_route.reshape(B * T, D)).astype(jnp.float32)
    with jax.named_scope("moe_block"):
        w, topi = moe_routing(cfg, p, x32, idx)
        out, counts = hybrid.moe_local(
            p, x, w, topi, idx, g.e0, g.E, MOE_DENSE_TOKENS,
            None if valid is None else valid.reshape(B * T))
        if shared:
            with jax.default_matmul_precision("highest"):
                sg = jax.nn.sigmoid(x32 @ p["shared_gate"][idx])       # [N, 1]
            out = out + sg * hybrid.gated_mlp(
                p, ("ws_gate", "ws_up", "ws_down"), x, idx).astype(jnp.float32)
    return out.reshape(B, T, D), counts


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"k", "v": [La, slots * Hk, Dh]}
    state: dict,              # {"gdn": [Lg, S, Hv, dk, dv], "conv": [Lg, S, 3 * conv / 128, 128], "counts": [5]}
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
            "qwen3_next: no injected embeddings, no all-position logits")
    g = Geometry(cfg)
    mm = hybrid.mm
    B, T = tokens.shape
    eps = cfg.rms_norm_eps
    tables, sslot = block_tables[:, :-1], block_tables[:, -1]
    start = positions[:, 0]
    n_valid = jnp.clip(context_lens - start, 0, T)            # [B]
    valid = jnp.arange(T)[None, :] < n_valid[:, None]         # [B, T]
    fresh = start == 0                                        # zero state in
    k_pages, v_pages = pages["k"], pages["v"]
    gdn_plane, conv_plane, counts = state["gdn"], state["conv"], state["counts"]
    kernels = hybrid.kernels_active()
    interpret = jax.default_backend() != "tpu"
    # the residual stream is float32 and the layers read it rounded to the
    # activation dtype (models/kimi_linear.py forward says why)
    x = llama.embed_lookup(params, tokens)
    act = x.dtype
    x = x.astype(jnp.float32)

    def gdn_mixer(h, gi, gdn_plane, conv_plane):
        rep = g.Hl // g.Hlk
        qkvz = mm(params, "gdn_wqkvz", h, gi)                  # [B, T, conv + VD]
        qkv, z = qkvz[..., : g.conv], qkvz[..., g.conv:]
        ba = mm(params, "gdn_wba", h, gi).astype(jnp.float32)  # [B, T, 2 Hv]
        y, conv_plane = hybrid.conv_step(
            conv_plane, gi, sslot, fresh, n_valid, qkv, params["gdn_conv"][gi],
            kernels=kernels)
        y = jax.nn.silu(y)
        q = y[..., : g.QK].reshape(B, T, g.Hlk, g.dk)
        k = y[..., g.QK: 2 * g.QK].reshape(B, T, g.Hlk, g.dk)
        v = y[..., 2 * g.QK:].reshape(B, T, g.Hl, g.dl)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * g.dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        # key head j serves value heads j * rep ... j * rep + rep - 1
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
        beta = jnp.where(valid[:, :, None], jax.nn.sigmoid(ba[..., : g.Hl]), 0.0)
        glog = jnp.where(valid[:, :, None],
                         gdn_decay_log(params, ba[..., g.Hl:], gi), 0.0)
        if T == 1 and kernels:
            # in place on the plane: no gather before, no scatter after;
            # the kernel takes the decay a key channel (ops/kda.py)
            from dynamo_tpu.ops.kda import kda_decode_update

            o, gdn_plane = kda_decode_update(
                gdn_plane, jnp.int32(gi), sslot, fresh, q[:, 0], k[:, 0],
                v[:, 0], jnp.broadcast_to(glog[:, 0, :, None], q[:, 0].shape),
                beta[:, 0], interpret=interpret)
            o = o[:, None]
        else:
            S = jnp.where(fresh[:, None, None, None], 0.0, gdn_plane[gi, sslot])
            if T == 1:
                o, S = hybrid.delta_decode(
                    q[:, 0], k[:, 0], v[:, 0], glog[:, 0, :, None], beta[:, 0], S)
                o = o[:, None]
            else:
                o, S = hybrid.delta_chunked(q, k, v, glog[..., None], beta, S)
            gdn_plane = gdn_plane.at[gi, sslot].set(S)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * params["gdn_onorm"][gi]
        o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
        out = mm(params, "gdn_wo", o.reshape(B, T, g.VD).astype(h.dtype), gi)
        return out, gdn_plane, conv_plane

    def attn_mixer(h, ai, k_pages, v_pages):
        qg = mm(params, "attn_wq", h, ai).reshape(B, T, g.H, 2 * g.Dh)
        q, gate = qg[..., : g.Dh], qg[..., g.Dh:]
        k = mm(params, "attn_wk", h, ai).reshape(B, T, g.Hk, g.Dh)
        v = mm(params, "attn_wv", h, ai).reshape(B, T, g.Hk, g.Dh)
        q = norm1(q, params["attn_qnorm"][ai], eps)
        k = norm1(k, params["attn_knorm"][ai], eps)
        q, k = partial_rope(q, k, positions, cfg.rope_theta, g.rot)
        q = q.astype(act)
        rows = (slot_mapping[:, None] * g.Hk + jnp.arange(g.Hk)).reshape(-1)
        k_pages = k_pages.at[ai, rows].set(
            k.reshape(B * T * g.Hk, g.Dh).astype(k_pages.dtype))
        v_pages = v_pages.at[ai, rows].set(
            v.reshape(B * T * g.Hk, g.Dh).astype(v_pages.dtype))
        slots = k_pages.shape[1] // g.Hk
        if kernels and T == 1:
            from dynamo_tpu.ops.paged_attention import (
                paged_attention_decode_stacked,
            )

            # the kernel's page view of this shape is the stored bytes
            shape4 = (k_pages.shape[0], slots, g.Hk, g.Dh)
            attn = paged_attention_decode_stacked(
                q[:, 0], k_pages.reshape(shape4), v_pages.reshape(shape4),
                jnp.int32(ai), tables, context_lens, block_size=block_size,
                interpret=interpret)[:, None]
        elif kernels:
            from dynamo_tpu.ops.paged_attention import (
                paged_attention_prefill_stacked,
            )

            # the stored rows in place, as decode reads them: a tile walks
            # its own live pages, whatever the table's width
            shape4 = (k_pages.shape[0], slots, g.Hk, g.Dh)
            attn = paged_attention_prefill_stacked(
                q, k_pages.reshape(shape4), v_pages.reshape(shape4),
                jnp.int32(ai), tables, start, context_lens,
                block_size=block_size, interpret=interpret)
        else:
            attn = llama.paged_attention_reference(
                q, k_pages[ai].reshape(slots, g.Hk, g.Dh),
                v_pages[ai].reshape(slots, g.Hk, g.Dh), tables, positions,
                context_lens, block_size)
        attn = attn.astype(jnp.float32) * jax.nn.sigmoid(gate)
        out = mm(params, "attn_wo", attn.reshape(B, T, g.H * g.Dh).astype(act), ai)
        return out, k_pages, v_pages

    for layer in range(g.L):
        mixer, mi = g.kind_index(layer)
        h = norm1(x, params["attn_norm"][layer], eps).astype(act)
        if mixer == "gdn":
            with jax.named_scope("gdn_mixer"):
                out, gdn_plane, conv_plane = gdn_mixer(h, mi, gdn_plane, conv_plane)
        else:
            with jax.named_scope("attn_mixer"):
                out, k_pages, v_pages = attn_mixer(h, mi, k_pages, v_pages)
        x = x + out.astype(jnp.float32)
        h32 = norm1(x, params["mlp_norm"][layer], eps)
        out, seen = moe_ffn(cfg, g, params, h32.astype(act), layer, valid, h32)
        x = x + out
        counts = counts.at[:3].add(seen)

    if T > 1 and g.gdn_layers:
        # real tokens, and blocks of them, through the chunked rule (a step)
        C = hybrid.delta_chunk_for(B, T)
        counts = counts.at[3:].add(jnp.stack([
            jnp.sum(n_valid, dtype=jnp.int32),
            jnp.sum(-(-n_valid // C), dtype=jnp.int32)]))
    x = norm1(x, params["final_norm"], eps).astype(act)
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (llama.lm_head(params, x_last), {"k": k_pages, "v": v_pages},
            {"gdn": gdn_plane, "conv": conv_plane, "counts": counts})
