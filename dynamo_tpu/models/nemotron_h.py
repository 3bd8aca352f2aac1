"""Nemotron-H: a decoder whose every layer is ONE part — a Mamba-2
state-space mixer, GQA attention without rotary embedding, sigmoid-routed
relu^2 experts, or a relu^2 MLP — chosen by a letter of
``hybrid_override_pattern`` (``M``, ``*``, ``E``, ``-``), on the engine's
normal step.

Every layer is ``x = x + part(rmsnorm(x) * w)`` (plain weight, eps
``norm_eps``); then ``norm_f`` and the untied head.

``M``, Mamba-2 (``H`` heads of ``P``, state ``N``, ``G`` groups of B / C,
``inner = H P``): ``[z | xBC] = x W_in`` (inner | inner + 2 G N), ``dt = x
W_dt`` (H); ``xBC_t <- silu(b_c + sum_i w_c[i] xBC_{t-(K-1)+i})``, depthwise
and causal, the ``K - 1`` earlier rows a sequence's tail in the state
plane; ``xBC = x (H x P) | B (G x N) | C (G x N)``, head ``h`` reading
group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)`` (no clamp:
``time_step_limit`` is absent from the published config), ``a =
exp(-exp(A_log) dt)`` a head; a head's ``[P, N]`` float32 state
``h_t = a_t h_{t-1} + (dt_t x_t) (x) B_t``, ``y_t = h_t C_t + D x_t``;
``y <- rmsnorm_groups(y * silu(z)) * w_n`` over ``G`` groups of ``inner /
G`` (gate first, then the norm); ``out = y W_out``. Decode is one update a
row in place on the plane (``ops/ssm.py``); prefill is the same recurrence
in blocks of ``chunk_size`` tokens by matrix products alone
(``ssd_chunked``). State and tail cross prefill chunks through the plane.

``*``, attention: ``q, k, v = x W_q, x W_k, x W_v``, NO rotary embedding
(the published ``NemotronHAttention`` applies none; ``rope_theta`` and
``partial_rotary_factor`` are dead keys), scale ``Dh^-1/2``, causal, over
the row's pages; ``out = o W_o``. K and V live in pages stored as their
(token, head) rows, ``[La, slots * Hk, Dh]``, read by the paged-attention
kernels every family runs (``models/qwen3_next.py`` says why rows).

``E``, experts: ``s = sigmoid(x W_r)`` in float32 from the unrounded
state; the top ``k`` of ``s + e_score_correction_bias``; ``w = s[chosen] /
(sum + 1e-20) * routed_scaling_factor``; ``out = sum_e w_e relu(x U_e)^2
D_e + relu(x U_s)^2 D_s`` — two matrices an expert, no gate, and a shared
expert that is not gated either. The held experts go through
``hybrid.moe_local`` in the ``RELU2`` form; ``n_routed_experts`` counts
those held here (the ``expert_shard_index``-th of ``expert_shards`` runs).

``-``: ``relu(x U)^2 D`` of ``intermediate_size``.

Departures from the published code: the fused ``in_proj`` is stored as its
``z | x | B | C`` columns (``m_win``) and its ``dt`` columns (``m_wdt``)
apart — 10 304 columns are no whole 128-lane tiles, 10 240 are, and a
weight-only int8 scale a column is the same either way; the float32
residual stream (``residual_in_fp32`` false upstream), matmul results,
router, state, convolution and tails; ``n_group`` / ``topk_group`` other
than 1 are refused (1 makes the grouped choice the plain one).

For the engine this is ``models/qwen3_next.py``'s arrangement: ``pages`` =
``{"k", "v"}``, ``state`` = ``{"ssm", "conv", "counts"}``, a row's table is
its pages then its state slot, a row at position 0 starts from a zero
state, right padding (``dt = 0``) never enters state or tail.
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
# every held expert runs over the tokens, MOE_DENSE_BLOCK of them at a
# time (the every-expert form's [E, block, Fe] float32 result is 0.49 GB
# here, and a layer's weights are read once a block); there is no sorted
# form. Measured on a v5e at this shape (128 held experts of 2688 x 1856,
# a layer; PERF.md, PR 37): every-expert 1.78-1.79 ms up to 64 tokens (88%
# of HBM speed), 2.27 / 3.83 / 5.96 / 8.29 / 13.95 / 18.01 at 128 / 256 /
# 384 / 512 / 768 / 1 024 (compute-bound from ~128: 73% of the MXU's peak on
# 21x the chosen experts' work); rows sorted by expert through ``ragged_dot``
# 17.2 / 36.5 / 41.6 / 46.4 / 49.4 / 54.8 at 8 / 64 / 256 / 512 / 1 024 /
# 2 048 — the grouped matmul lays the 1856-wide experts out anew (18 ms a
# layer whatever the rows) and loses at every size a step can have
MOE_DENSE_BLOCK = 512
# what a step holds beside weights, pages and state, at the published
# widths: a block's [E, 512, Fe] float32 projections and their bf16 copy
# (0.73 GB), a prefill chunk's float32 projections and decay blocks (the
# described chip's compiler counts 0.96 GB at 4 x 1 024 tokens, twice
# over here: tests/test_chip_compile.py)
STEP_TRANSIENT_BYTES = 2 << 30
COUNT_NAMES = hybrid.MOE_COUNT_NAMES + (
    "recurrent_prefill_tokens", "recurrent_prefill_chunks")
KINDS = {"M": "ssm", "*": "attn", "E": "moe", "-": "mlp"}


class Geometry:
    """The sizes of one configuration, worked out once."""

    def __init__(self, cfg: ModelConfig):
        self.L = cfg.num_hidden_layers
        self.D = cfg.hidden_size
        self.V = cfg.vocab_size
        kinds = [KINDS[c] for c in cfg.layer_letters()]
        self.layers = {kind: [i for i, k in enumerate(kinds) if k == kind]
                       for kind in KINDS.values()}
        self.plan = [(kind, self.layers[kind].index(i))
                     for i, kind in enumerate(kinds)]
        self.H = cfg.num_attention_heads
        self.Hk = cfg.num_key_value_heads
        self.Dh = cfg.head_dim
        self.Hm = cfg.mamba_num_heads
        self.dm = cfg.mamba_head_dim
        self.N = cfg.ssm_state_size
        self.G = cfg.n_groups
        self.kernel = cfg.conv_kernel
        self.chunk = cfg.chunk_size
        self.inner = self.Hm * self.dm            # x (and z) channels
        self.conv = self.inner + 2 * self.G * self.N   # x | B | C
        self.F = cfg.intermediate_size
        self.Fe = cfg.moe_intermediate_size
        self.Fs = cfg.moe_shared_expert_intermediate_size
        self.E = cfg.n_routed_experts             # held here
        self.E_all = cfg.n_routed_experts * cfg.expert_shards
        self.e0 = cfg.expert_shard_index * cfg.n_routed_experts
        self.k = cfg.num_experts_per_tok
        if self.layers["ssm"] and (
                min(self.Hm, self.dm, self.N, self.G) < 1 or self.Hm % self.G
                or self.inner % self.G or self.kernel < 2 or self.chunk < 1
                or not cfg.use_conv_bias):
            raise ValueError(
                "nemotron_h Mamba-2 is built for heads a multiple of the "
                "B / C groups, a convolution of >= 2 taps with a bias: "
                f"{self.Hm} heads of {self.dm}, state {self.N}, {self.G} "
                f"groups, {self.kernel} taps, use_conv_bias {cfg.use_conv_bias}")
        if self.layers["attn"] and (self.Hk < 1 or self.H % self.Hk):
            raise ValueError(
                f"nemotron_h attention: {self.H} query heads over {self.Hk}")
        biased = [name for name in ("attention_bias", "use_bias",
                                    "mamba_proj_bias", "mlp_bias")
                  if getattr(cfg, name)]
        if cfg.mlp_hidden_act != "relu2" or biased:
            raise ValueError(
                "nemotron_h is built with relu2 feed-forward parts and no "
                f"projection bias: mlp_hidden_act {cfg.mlp_hidden_act!r}, "
                f"set: {biased}")
        if self.layers["moe"]:
            if cfg.n_group != 1 or cfg.topk_group != 1:
                raise ValueError(
                    "nemotron_h router groups other than 1 are not built")
            if cfg.n_shared_experts != 1 or self.Fs < 1:
                raise ValueError(
                    "nemotron_h is built for exactly 1 shared expert")
            if self.E < 1 or not 0 < self.k <= self.E_all:
                raise ValueError(
                    "nemotron_h needs routed experts and num_experts_per_tok "
                    "of them a token")


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

# name -> axis the int8 scales reduce over (weight-only int8, as
# models/quant.py: per output channel; embedding rows per row)
QUANT_AXIS = {
    "embed": -1, "lm_head": -2,
    "m_win": -2, "m_wdt": -2, "m_wo": -2,
    "attn_wq": -2, "attn_wk": -2, "attn_wv": -2, "attn_wo": -2,
    "ws_up": -2, "ws_down": -2, "we_up": -2, "we_down": -2,
    "w_up": -2, "w_down": -2,
}


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype); layer parameters are stacked per KIND."""
    g = Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    Lm, La = len(g.layers["ssm"]), len(g.layers["attn"])
    Le, Ld = len(g.layers["moe"]), len(g.layers["mlp"])
    D = g.D
    shapes: dict = {
        "embed": ((g.V, D), bf16),
        "final_norm": ((D,), f32),
        "lm_head": ((D, g.V), bf16),
        "norm": ((g.L, D), f32),
    }
    if Lm:
        shapes.update({
            "m_win": ((Lm, D, g.inner + g.conv), bf16),     # z | x | B | C
            "m_wdt": ((Lm, D, g.Hm), bf16),
            "m_conv": ((Lm, g.kernel, g.conv), f32),        # x | B | C channels
            "m_conv_bias": ((Lm, g.conv), f32),
            "m_A_log": ((Lm, g.Hm), f32),
            "m_dt_bias": ((Lm, g.Hm), f32),
            "m_D": ((Lm, g.Hm), f32),
            "m_onorm": ((Lm, g.inner), f32),
            "m_wo": ((Lm, g.inner, D), bf16),
        })
    if La:
        shapes.update({
            "attn_wq": ((La, D, g.H * g.Dh), bf16),
            "attn_wk": ((La, D, g.Hk * g.Dh), bf16),
            "attn_wv": ((La, D, g.Hk * g.Dh), bf16),
            "attn_wo": ((La, g.H * g.Dh, D), bf16),
        })
    if Le:
        shapes.update({
            "router": ((Le, D, g.E_all), f32),
            "router_bias": ((Le, g.E_all), f32),
            "ws_up": ((Le, D, g.Fs), bf16),
            "ws_down": ((Le, g.Fs, D), bf16),
            "we_up": ((Le, g.E, D, g.Fe), bf16),
            "we_down": ((Le, g.E, g.Fe, D), bf16),
        })
    if Ld:
        shapes.update({
            "w_up": ((Ld, D, g.F), bf16),
            "w_down": ((Ld, g.F, D), bf16),
        })
    return shapes


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """One device holds everything (check_engine refuses tp/ep/pp > 1)."""
    return {name: P() for name in param_shapes(cfg)}


def _draw(cfg: ModelConfig):
    def draw_one(name: str, key, shape: tuple[int, ...]):
        """One leading slice of parameter ``name`` in float32 — the
        recipe: norms and ``D`` 1; the convolution's and the router's bias
        0; ``A_log = log(U(1, 16))``; ``dt_bias`` from ``time_step_min /
        max / floor``; everything else ``normal / sqrt(fan_in)`` (fan_in:
        the second-to-last axis)."""
        if name.endswith("norm") or name == "m_D":
            return jnp.ones(shape, jnp.float32)
        if name in ("router_bias", "m_conv_bias"):
            return jnp.zeros(shape, jnp.float32)
        if name == "m_A_log":
            return hybrid.draw_A_log(key, shape)
        if name == "m_dt_bias":
            return hybrid.draw_dt_bias(key, shape, cfg.time_step_min,
                                       cfg.time_step_max, cfg.time_step_floor)
        return hybrid.draw_normal(key, shape)

    return draw_one


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Optional[Mesh] = None,
                specs: Optional[dict] = None, dtype=None) -> Params:
    """The seeded draw (``hybrid.init``), unquantized. ``dtype``
    overrides bfloat16 for the matrices (float32 in tests, so the program
    meets its reference to rounding)."""
    return hybrid.init(param_shapes(cfg), _draw(cfg), QUANT_AXIS, seed, mesh,
                       False, dtype)


def init_params_quantized(cfg: ModelConfig, seed: int = 0,
                          mesh: Optional[Mesh] = None,
                          specs: Optional[dict] = None) -> Params:
    """The seeded draw as served: every matrix weight-only int8 with a
    float32 scale per output channel, made and quantized on the device;
    the convolution, the router and the per-head vectors stay float32."""
    return hybrid.init(param_shapes(cfg), _draw(cfg), QUANT_AXIS, seed, mesh,
                       True, None)


# ---------------------------------------------------------------------------
# The cache: K/V pages of the attention layers, and the state plane
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, num_blocks: int, block_size: int,
                 state_slots: int) -> tuple[dict, dict]:
    g = Geometry(cfg)
    Lm, La = len(g.layers["ssm"]), len(g.layers["attn"])
    # a slot's Hk rows of Dh, one after the other (models/qwen3_next.py)
    kv = (max(1, La), num_blocks * block_size * g.Hk, g.Dh)
    pages = {"k": kv, "v": kv}
    state = {
        # the state size N minor: a head's [P, N] block is whole lane tiles
        "ssm": (max(1, Lm), state_slots, g.Hm, g.dm, g.N),
        # a slot's tail rows one after another, in rows of one lane tile
        "conv": (max(1, Lm), state_slots,
                 *hybrid.conv_tail_shape(g.kernel, g.conv)),
    }
    return pages, state


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int) -> int:
    """Bytes one block of K and V pages takes over the attention layers
    (the engine sizes the pool with it)."""
    g = Geometry(cfg)
    return 2 * max(1, len(g.layers["attn"])) * block_size * g.Hk * g.Dh * itemsize


def state_bytes(cfg: ModelConfig, state_slots: int, itemsize: int) -> int:
    g = Geometry(cfg)
    per_slot = g.Hm * g.dm * g.N * 4 + (g.kernel - 1) * g.conv * 4
    return max(1, len(g.layers["ssm"])) * state_slots * per_slot


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None, state_slots: int = 2):
    """(pages, state), zeroed. ``state_slots`` counts the garbage slot 0."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("nemotron_h has no int8 K/V cache")
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    pshape, sshape = cache_shapes(cfg, num_blocks, block_size, state_slots)
    pages = {n: jnp.zeros(s, dtype, device=sh) for n, s in pshape.items()}
    state = {
        "ssm": jnp.zeros(sshape["ssm"], jnp.float32, device=sh),
        # float32 like the matmul results the convolution reads: a token
        # sees the same inputs whether they came from the tail or the chunk
        "conv": jnp.zeros(sshape["conv"], jnp.float32, device=sh),
        # cumulative, on the device, read at a profiler capture's edges
        # (engine.program_counts, COUNT_NAMES): expert-layer calls,
        # assignments of real tokens to held experts, held experts
        # touched; real tokens and blocks of tokens through the chunked scan
        "counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh),
    }
    return pages, state


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong."""
    hybrid.check_engine(
        config, "model_type nemotron_h (recurrent state beside K/V pages)")


# ---------------------------------------------------------------------------
# The state-space recurrence
# ---------------------------------------------------------------------------


def ssm_decode(x, dt, glog, Bm, C, S):
    """One recurrent update a row. x [B, H, P]; dt, glog [B, H] (``glog =
    -exp(A_log) dt``); Bm, C [B, G, N]; S [B, H, P, N] float32. Returns
    (y [B, H, P] without the ``D`` skip, S')."""
    rep = x.shape[1] // Bm.shape[1]
    Bh, Ch = jnp.repeat(Bm, rep, axis=1), jnp.repeat(C, rep, axis=1)
    with jax.named_scope("ssm_decode"), jax.default_matmul_precision("highest"):
        S = jnp.exp(glog)[..., None, None] * S \
            + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
        return jnp.einsum("bhpn,bhn->bhp", S, Ch), S


def ssd_chunked(x, dt, glog, Bm, C, S, chunk: int):
    """The same recurrence over T tokens, ``chunk`` at a time, by matrix
    products alone. x [B, T, H, P] float32; dt, glog [B, T, H]; Bm, C [B,
    T, G, N]; S [B, H, P, N].

    With ``G_t`` the log decay summed from the block's start (every
    exponent below is of ``G_t - G_j <= 0``, ``t >= j``: nothing overflows):
      ``y_t = e^{G_t} S0 C_t + sum_{j<=t} e^{G_t - G_j} (C_t . B_j) dt_j x_j``;
      ``S' = e^{G_Q} S0 + sum_j e^{G_Q - G_j} (dt_j x_j) (x) B_j``.
    A token with ``dt = 0`` (padding: then ``glog = 0`` too) changes nothing.
    Heads are kept as (group, head of the group), so B and C are never
    repeated."""
    B, T, H, P_ = x.shape
    G, N = Bm.shape[2:]
    rep = H // G
    Q = min(chunk, T)
    assert T % Q == 0, (T, Q)

    def blocks(a):
        return jnp.moveaxis(a.reshape(B, T // Q, Q, *a.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(S, blk):
        xc, dtc, gc, bc, cc = blk
        xc = xc.reshape(B, Q, G, rep, P_)
        dtc, gc = dtc.reshape(B, Q, G, rep), gc.reshape(B, Q, G, rep)
        Gs = jnp.cumsum(gc, axis=1)                           # [B, Q, G, r]
        diff = Gs[:, :, None] - Gs[:, None, :]                # [B, t, j, G, r]
        decay = jnp.exp(jnp.where(tri[None, :, :, None, None], diff, -jnp.inf))
        cb = jnp.einsum("btgn,bjgn->btjg", cc, bc)            # C_t . B_j
        w = cb[..., None] * decay * dtc[:, None]              # [B, t, j, G, r]
        Sg = S.reshape(B, G, rep, P_, N)
        y = jnp.einsum("btjgr,bjgrp->btgrp", w, xc) + jnp.exp(Gs)[..., None] \
            * jnp.einsum("bgrpn,btgn->btgrp", Sg, cc)
        last = Gs[:, -1]                                      # [B, G, r]
        xw = xc * (dtc * jnp.exp(last[:, None] - Gs))[..., None]
        Sg = jnp.exp(last)[..., None, None] * Sg + jnp.einsum(
            "bjgrp,bjgn->bgrpn", xw, bc)
        return Sg.reshape(B, H, P_, N), y.reshape(B, Q, H, P_)

    with jax.named_scope("ssd_chunked"), jax.default_matmul_precision("highest"):
        S, y = jax.lax.scan(body, S, tuple(map(blocks, (x, dt, glog, Bm, C))))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, H, P_), S


# ---------------------------------------------------------------------------
# The experts
# ---------------------------------------------------------------------------


def moe_routing(cfg: ModelConfig, p: Params, x: jax.Array, idx: int):
    """x [N, D] -> (weights [N, k] float32, expert ids [N, k]) over ALL
    experts (``hybrid.sigmoid_routing``; the bias is the correction bias)."""
    return hybrid.sigmoid_routing(
        p["router"][idx], p["router_bias"][idx], x, cfg.num_experts_per_tok,
        cfg.norm_topk_prob, cfg.routed_scaling_factor)


def moe_ffn(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array,
            idx: int, valid: Optional[jax.Array] = None,
            h_route: Optional[jax.Array] = None, shared: bool = True):
    """This process's part of the expert layer: its own experts' share of
    the routed sum (``hybrid.moe_local``, two matrices an expert) plus,
    with ``shared``, the shared expert. Every held expert runs over the
    tokens, ``MOE_DENSE_BLOCK`` at a time. Returns (out, counts int32 [3]);
    a block counts as a call of its own (it reads the layer's experts once). ``h_route``: the same hidden state
    before it was rounded to the activation dtype — the router reads that."""
    B, T, D = h.shape
    N = B * T
    x = h.reshape(N, D)
    route = x if h_route is None else h_route.reshape(N, D)
    real = jnp.ones((N,), bool) if valid is None else valid.reshape(N)

    def local(x, route, real):
        w, topi = moe_routing(cfg, p, route, idx)
        return hybrid.moe_local(p, x, w, topi, idx, g.e0, g.E,
                                MOE_DENSE_BLOCK, real, hybrid.RELU2)

    with jax.named_scope("moe_block"):
        if N <= MOE_DENSE_BLOCK:
            out, counts = local(x, route, real)
        else:
            blocks = -(-N // MOE_DENSE_BLOCK)
            pad = blocks * MOE_DENSE_BLOCK - N   # 0 at every bucketed step

            def blocked(a):
                if pad:   # padded tokens are not ``real``: they count nothing
                    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                return a.reshape(blocks, MOE_DENSE_BLOCK, *a.shape[1:])

            out, counts = jax.lax.map(
                lambda a: local(*a), (blocked(x), blocked(route), blocked(real)))
            out = out.reshape(blocks * MOE_DENSE_BLOCK, D)[:N]
            counts = jnp.sum(counts, axis=0)
        if shared:
            out = out + hybrid.relu2_mlp(
                p, ("ws_up", "ws_down"), x, idx).astype(jnp.float32)
    return out.reshape(B, T, D), counts


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"k", "v": [La, slots * Hk, Dh]}
    state: dict,              # {"ssm": [Lm, S, H, P, N], "conv": [Lm, S, 3 * conv / 128, 128], "counts": [5]}
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
            "nemotron_h: no injected embeddings, no all-position logits")
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
    ssm_plane, conv_plane, counts = state["ssm"], state["conv"], state["counts"]
    kernels = hybrid.kernels_active()
    interpret = jax.default_backend() != "tpu"
    # the residual stream is float32 and the layers read it rounded to the
    # activation dtype (models/kimi_linear.py forward says why)
    x = llama.embed_lookup(params, tokens)
    act = x.dtype
    x = x.astype(jnp.float32)

    def ssm_mixer(h, mi, ssm_plane, conv_plane):
        zx = mm(params, "m_win", h, mi)                        # [B, T, inner + conv]
        z, xbc = zx[..., : g.inner], zx[..., g.inner:]
        dt = mm(params, "m_wdt", h, mi).astype(jnp.float32)    # [B, T, H]
        with jax.named_scope("ssm_conv"):
            y, conv_plane = hybrid.conv_step(
                conv_plane, mi, sslot, fresh, n_valid, xbc, params["m_conv"][mi],
                params["m_conv_bias"][mi], kernels=kernels)
            y = jax.nn.silu(y)
        xs = y[..., : g.inner].reshape(B, T, g.Hm, g.dm)
        Bm = y[..., g.inner: g.inner + g.G * g.N].reshape(B, T, g.G, g.N)
        C = y[..., g.inner + g.G * g.N:].reshape(B, T, g.G, g.N)
        # a padded token takes no step: dt 0, decay 1
        dt = jnp.where(valid[:, :, None],
                       jax.nn.softplus(dt + params["m_dt_bias"][mi]), 0.0)
        glog = -jnp.exp(params["m_A_log"][mi]) * dt            # [B, T, H], <= 0
        if T == 1 and kernels:
            # in place on the plane: no gather before, no scatter after
            from dynamo_tpu.ops.ssm import ssm_decode_update

            o, ssm_plane = ssm_decode_update(
                ssm_plane, jnp.int32(mi), sslot, fresh, xs[:, 0], dt[:, 0],
                jnp.exp(glog[:, 0]), Bm[:, 0], C[:, 0], interpret=interpret)
            o = o[:, None]
        else:
            S = jnp.where(fresh[:, None, None, None], 0.0, ssm_plane[mi, sslot])
            if T == 1:
                o, S = ssm_decode(xs[:, 0], dt[:, 0], glog[:, 0], Bm[:, 0],
                                  C[:, 0], S)
                o = o[:, None]
            else:
                o, S = ssd_chunked(xs, dt, glog, Bm, C, S, g.chunk)
            ssm_plane = ssm_plane.at[mi, sslot].set(S)
        o = o + params["m_D"][mi][:, None] * xs
        # gate first, then the norm over each of the G groups of channels
        o = o.reshape(B, T, g.inner) * jax.nn.silu(z.astype(jnp.float32))
        o = o.reshape(B, T, g.G, g.inner // g.G)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = o.reshape(B, T, g.inner) * params["m_onorm"][mi]
        return mm(params, "m_wo", o.astype(act), mi), ssm_plane, conv_plane

    def attn_mixer(h, ai, k_pages, v_pages):
        q = mm(params, "attn_wq", h, ai).reshape(B, T, g.H, g.Dh).astype(act)
        k = mm(params, "attn_wk", h, ai).reshape(B, T, g.Hk, g.Dh)
        v = mm(params, "attn_wv", h, ai).reshape(B, T, g.Hk, g.Dh)
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
        out = mm(params, "attn_wo", attn.reshape(B, T, g.H * g.Dh).astype(act), ai)
        return out, k_pages, v_pages

    for layer, (kind, i) in enumerate(g.plan):
        h32 = llama.rmsnorm(x, params["norm"][layer], eps)
        h = h32.astype(act)
        if kind == "ssm":
            with jax.named_scope("ssm_mixer"):
                out, ssm_plane, conv_plane = ssm_mixer(h, i, ssm_plane, conv_plane)
        elif kind == "attn":
            with jax.named_scope("attn_mixer"):
                out, k_pages, v_pages = attn_mixer(h, i, k_pages, v_pages)
        elif kind == "moe":
            out, seen = moe_ffn(cfg, g, params, h, i, valid, h32)
            counts = counts.at[:3].add(seen)
        else:
            out = hybrid.relu2_mlp(params, ("w_up", "w_down"), h, i)
        x = x + out.astype(jnp.float32)

    if T > 1 and g.layers["ssm"]:
        # real tokens, and blocks of them, through the chunked scan (a step)
        Q = min(g.chunk, T)
        counts = counts.at[3:].add(jnp.stack([
            jnp.sum(n_valid, dtype=jnp.int32),
            jnp.sum(-(-n_valid // Q), dtype=jnp.int32)]))
    x = llama.rmsnorm(x, params["final_norm"], eps).astype(act)
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (llama.lm_head(params, x_last), {"k": k_pages, "v": v_pages},
            {"ssm": ssm_plane, "conv": conv_plane, "counts": counts})
