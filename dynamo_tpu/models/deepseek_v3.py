"""DeepSeek-V3-shaped decoders (``model_type: deepseek_v3``; Kanana-2):
latent attention with a decoupled rotary part in EVERY layer, a dense
SiLU-gated MLP in the first ``first_k_dense_replace`` layers and
sigmoid-routed experts plus an ungated shared MLP in the others — on the
engine's normal step.

What this family is to the engine (``models/__init__.py``): it OWNS ITS
PAGES — one ``kv_lora_rank + qk_rope_head_dim`` wide row a token a layer,
``[c | k_r]``, the normalised latent and the one ROTATED key part all
heads share, in whole 128-lane tiles (``page_bytes_per_block``) — and
keeps NO recurrent state: no state plane, no slot column in the table,
so a page is all there is to know about its tokens, the prefix cache
serves it, and a preempted row resumes from its cached pages. The second
cache pytree holds only the on-device counts.

Latent attention is ``hybrid.mla_mixer`` (shared with ``kimi_linear``,
which runs it without rotary): queries absorb the key half of ``W_kvb``
and attend the cached rows themselves; the value half is applied to the
output in latent space. Decode runs ``ops/mla.py``
``mla_decode_attention``. PREFILL runs ``mla_prefill_attention``, the
same ABSORBED form as a flash kernel over the row's own pages: a tile of
32 query tokens x 32 heads walks its live pages eight to a compute block
(6.9 ms a 1 024-token chunk over 14k keys on a v5e, 14.7 a page a grid
step: PERF.md, PR 50), 2 x (640 + 512)
FLOP a (query, key, head) where building ``k_h``, ``v_h`` a head would
take 2 x (192 + 128) — chosen because nothing of size ``T x S`` or
``S x H x 256`` ever exists in HBM at S = 16 384 (the up-projected form
holds 268 MB of keys and values a row and re-projects them every chunk;
the gathered XLA form holds ``[H, t, S]`` float32 scores, 1 GB a copy at
t = 512), and a chunk at any start position reads cached pages exactly
as it reads an earlier chunk's. What a step holds beside weights and
pages is stated in ``STEP_TRANSIENT_BYTES``.

Layers are unrolled in Python, as the other families of ``hybrid.py``
are (the eleven expert layers are alike and a scan is open to them; the
unrolled step is the form those families have proven on the chip).
The residual stream, matmul results and the router's input stay float32.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]

# at most this many tokens go through every expert at once; more are
# sorted by expert and go through the grouped matmul
MOE_DENSE_TOKENS = 64
# what a step holds beside weights and pages at the published widths and
# the largest prefill rectangle (4 096 tokens): the bf16 copies of ONE
# layer's experts that the grouped matmul reads (128 x 3 x 2 048 x 768 x
# 2 B = 1.2 GB; hybrid.moe_local_grouped ties them to their rows so that
# one layer's live at a time), the absorbed queries and the latent-space
# output ([4 096, 32, 640 + 512] bf16 + the float32 result = 0.9 GB), the
# sorted rows of the grouped matmuls (4 096 x 6 x (2 048 + 2 x 768) x 4 B
# = 0.35 GB), the float32 logits of a rectangle's rows. No attention
# score and no up-projected key or value is among them, whatever the
# table's width. The described chip's compiler counts 1.86 GB at 4 x
# 1 024 tokens under a 136-page table, 0.58 GB at 1 x 512
# (tests/test_chip_compile.py holds it under this bound)
STEP_TRANSIENT_BYTES = 3 << 30
COUNT_NAMES = hybrid.MOE_COUNT_NAMES + (
    "mla_prefill_query_tokens", "mla_prefill_pairs", "mla_prefill_calls")
# ``mla_prefill_pairs`` counts in units of this many (query, key) pairs:
# the chip attends single pairs faster than an int32 of them could be
# read as a difference (2**32 in under a second at the kernel's peak)
PAIR_UNIT = 1024


class Geometry:
    """The sizes of one configuration, worked out once; what the family
    does not build raises here, by the key's name."""

    def __init__(self, cfg: ModelConfig, low_rank_query: bool = False):
        refused = {
            "q_lora_rank (a low-rank query projection)":
                cfg.q_lora_rank is not None and not low_rank_query,
            "n_group / topk_group > 1 (group-limited routing)":
                cfg.n_group != 1 or cfg.topk_group != 1,
            "rope_scaling (YaRN-scaled rotary)": cfg.rope_scaling is not None,
            f"scoring_func {cfg.scoring_func!r} (sigmoid only)":
                cfg.scoring_func != "sigmoid",
        }
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError("deepseek_v3 does not build: " + "; ".join(bad))
        self.L = cfg.num_hidden_layers
        self.D = cfg.hidden_size
        self.V = cfg.vocab_size
        self.H = cfg.num_attention_heads
        self.nope = cfg.qk_nope_head_dim
        self.rope = cfg.qk_rope_head_dim
        if cfg.qk_head_dim not in (None, self.nope + self.rope):
            raise ValueError(
                f"qk_head_dim {cfg.qk_head_dim} is not qk_nope_head_dim + "
                f"qk_rope_head_dim = {self.nope + self.rope}")
        self.vd = cfg.v_head_dim
        self.rank = cfg.kv_lora_rank
        self.C = self.rank + self.rope          # one cached latent row
        # as stored: whole 128-lane tiles (models/kimi_linear.py Geometry)
        self.Cpad = -(-self.C // 128) * 128
        self.latent = hybrid.Latent(self.H, self.nope, self.rope, self.vd,
                                    self.rank, self.Cpad)
        self.F = cfg.intermediate_size
        self.Fe = cfg.moe_intermediate_size
        self.Fs = cfg.moe_intermediate_size * cfg.n_shared_experts
        # held here; the router scores E_all and this process holds the
        # expert_shard_index-th run of E of them (1 / 0: all of them)
        self.E = cfg.n_routed_experts
        self.E_all = self.E * cfg.expert_shards
        self.e0 = cfg.expert_shard_index * self.E
        self.k = cfg.num_experts_per_tok
        self.dense_layers = list(range(min(cfg.first_k_dense_replace, self.L)))
        self.moe_layers = [i for i in range(self.L) if i not in self.dense_layers]
        if self.moe_layers and not (self.E and self.k and self.Fs):
            raise ValueError(
                "deepseek_v3 expert layers need n_routed_experts, "
                "num_experts_per_tok and n_shared_experts")


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

QUANT_AXIS = {
    "embed": -1, "lm_head": -2,
    "mla_wq": -2, "mla_wkva": -2, "mla_wkvb": -2, "mla_wo": -2,
    "w_gate": -2, "w_up": -2, "w_down": -2,
    "ws_gate": -2, "ws_up": -2, "ws_down": -2,
    "we_gate": -2, "we_up": -2, "we_down": -2,
}


def param_shapes(cfg: ModelConfig, g: Optional[Geometry] = None
                 ) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype); layer parameters are stacked per KIND.
    ``g``: a geometry of this kind worked out already
    (``models/glm_moe_dsa.py``)."""
    g = g or Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    L, Ld, Le, D = g.L, len(g.dense_layers), len(g.moe_layers), g.D
    shapes: dict = {
        "embed": ((g.V, D), bf16),
        "final_norm": ((D,), f32),
        "lm_head": ((D, g.V), bf16),
        "attn_norm": ((L, D), f32),
        "mlp_norm": ((L, D), f32),
        "mla_wq": ((L, D, g.H * (g.nope + g.rope)), bf16),
        "mla_wkva": ((L, D, g.C), bf16),
        "mla_kvnorm": ((L, g.rank), f32),
        "mla_wkvb": ((L, g.rank, g.H * (g.nope + g.vd)), bf16),
        "mla_wo": ((L, g.H * g.vd, D), bf16),
    }
    if Ld:
        shapes.update({
            "w_gate": ((Ld, D, g.F), bf16),
            "w_up": ((Ld, D, g.F), bf16),
            "w_down": ((Ld, g.F, D), bf16),
        })
    if Le:
        shapes.update({
            "router": ((Le, D, g.E_all), f32),
            "router_bias": ((Le, g.E_all), f32),     # e_score_correction_bias
            "ws_gate": ((Le, D, g.Fs), bf16),
            "ws_up": ((Le, D, g.Fs), bf16),
            "ws_down": ((Le, g.Fs, D), bf16),
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
    norms 1; selection bias 0; everything else ``normal / sqrt(fan_in)``."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    return hybrid.draw_normal(key, shape)


def _init(cfg: ModelConfig, seed: int, mesh, quantize: bool, dtype) -> Params:
    return hybrid.init(param_shapes(cfg), _draw_one, QUANT_AXIS, seed, mesh,
                       quantize, dtype)


def init_params(cfg: ModelConfig, seed: int = 0, mesh: Optional[Mesh] = None,
                specs: Optional[dict] = None, dtype=None) -> Params:
    """The seeded draw, unquantized (``dtype`` float32 in tests)."""
    return _init(cfg, seed, mesh, False, dtype)


def init_params_quantized(cfg: ModelConfig, seed: int = 0,
                          mesh: Optional[Mesh] = None,
                          specs: Optional[dict] = None) -> Params:
    """The seeded draw as served: every matrix weight-only int8 with a
    float32 scale per output channel, made and quantized on the device."""
    return _init(cfg, seed, mesh, True, None)


# ---------------------------------------------------------------------------
# The cache: latent pages, and the counts
# ---------------------------------------------------------------------------


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int) -> int:
    """Bytes one block of pages takes over all layers (the engine sizes
    the pool with it)."""
    g = Geometry(cfg)
    return g.L * block_size * g.Cpad * itemsize


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None):
    """(pages, counts), zeroed: ``{"latent": [L, slots, Cpad]}`` and
    ``{"counts": int32 [len(COUNT_NAMES)]}`` — no state plane."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("deepseek_v3 has no int8 latent cache")
    g = Geometry(cfg)
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    pages = {"latent": jnp.zeros(
        (g.L, num_blocks * block_size, g.Cpad), dtype, device=sh)}
    # cumulative, on the device, read at a profiler capture's edges
    # (engine.program_counts)
    counts = {"counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh)}
    return pages, counts


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong: more than one device, speculation, KVBM
    offload of latent pages, int8 pages (``hybrid.check_engine``; block
    export and import by ``engine.refuse_kv_transfer``)."""
    hybrid.check_engine(config, "model_type deepseek_v3 (latent pages)")


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

kernels_active = hybrid.kernels_active


def moe_routing(cfg: ModelConfig, p: Params, x: jax.Array, idx: int):
    """Top ``num_experts_per_tok`` of ``sigmoid + e_score_correction_bias``
    (``n_group`` 1: the group limit is inert), weights the scores
    themselves, renormalised, scaled (``hybrid.sigmoid_routing``)."""
    return hybrid.sigmoid_routing(
        p["router"][idx], p["router_bias"][idx], x, cfg.num_experts_per_tok,
        cfg.norm_topk_prob, cfg.routed_scaling_factor)


def moe_ffn(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array, idx: int,
            valid: Optional[jax.Array] = None,
            h_route: Optional[jax.Array] = None):
    """The routed sum over the ``E`` experts held here (``g.e0`` on: all
    of them unless the configuration states a share) plus the shared MLP,
    ungated. Returns (out float32, counts int32 [3]); ``h_route`` is
    ``h`` before it was rounded to the activation dtype — the router's."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    w, topi = moe_routing(
        cfg, p, x if h_route is None else h_route.reshape(B * T, D), idx)
    routed, counts = hybrid.moe_local(
        p, x, w, topi, idx, g.e0, g.E, MOE_DENSE_TOKENS, valid)
    shared = hybrid.gated_mlp(p, ("ws_gate", "ws_up", "ws_down"), h, idx)
    return routed.reshape(B, T, D) + shared.astype(jnp.float32), counts


def prefill_counts(start: jax.Array, n_valid: jax.Array, layers: int):
    """What a prefill call attended, summed over ``layers``: its real
    query tokens, and the valid (query, key) pairs — a row's token at
    position ``p`` sees ``p + 1`` keys — in units of ``PAIR_UNIT``, and
    the attention calls themselves (one a layer), as int32 [3]
    (``COUNT_NAMES``' last three). A call's rectangle holds at most
    ``max_prefill_tokens`` tokens under ``max_model_len`` keys, so its
    single pairs fit an int32 before they are divided."""
    n = n_valid.astype(jnp.int32)
    pairs = n * start.astype(jnp.int32) + n * (n + 1) // 2
    return jnp.stack([layers * jnp.sum(n),
                      layers * jnp.sum(pairs) // PAIR_UNIT,
                      jnp.int32(layers)])


def decoder(cfg: ModelConfig, g: Geometry, params: Params, tokens: jax.Array,
            positions: jax.Array, context_lens: jax.Array, attend):
    """The layers of this geometry around an attention of the caller's:
    ``attend(layer, h [B, T, D]) -> out`` (it threads its own pages).
    Returns (the residual stream [B, T, D] float32 before the final
    norm, the activation dtype, the expert counts int32 [3], start [B],
    n_valid [B]); ``head``
    makes the logits of it. Shared with ``models/glm_moe_dsa.py``."""
    B, T = tokens.shape
    eps = cfg.rms_norm_eps
    start = positions[:, 0]
    n_valid = jnp.clip(context_lens - start, 0, T)            # [B]
    valid = jnp.arange(T)[None, :] < n_valid[:, None]         # [B, T]
    seen = jnp.zeros((len(hybrid.MOE_COUNT_NAMES),), jnp.int32)

    x = llama.embed_lookup(params, tokens)
    act = x.dtype
    x = x.astype(jnp.float32)
    for layer in range(g.L):
        h = llama.rmsnorm(x, params["attn_norm"][layer], eps).astype(act)
        x = x + attend(layer, h).astype(jnp.float32)
        h32 = llama.rmsnorm(x, params["mlp_norm"][layer], eps)
        h = h32.astype(act)
        if layer in g.dense_layers:
            out = hybrid.gated_mlp(params, ("w_gate", "w_up", "w_down"), h,
                                   g.dense_layers.index(layer))
        else:
            out, c = moe_ffn(cfg, g, params, h, g.moe_layers.index(layer),
                             valid, h32)
            seen = seen + c
        x = x + out.astype(jnp.float32)
    return x, act, seen, start, n_valid


def head(cfg: ModelConfig, params: Params, x: jax.Array, act,
         last_token_idx: jax.Array) -> jax.Array:
    """Final norm and the untied head at each row's last token, the
    matmul's operand in the activation dtype ``act``: [B, V]."""
    x = llama.rmsnorm(x, params["final_norm"], cfg.rms_norm_eps).astype(act)
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return llama.lm_head(params, x_last)


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"latent": [L, slots, Cpad]}
    counts: dict,             # {"counts": int32 [6]}
    tokens: jax.Array,        # [B, T]
    positions: jax.Array,     # [B, T] (padded: 0)
    slot_mapping: jax.Array,  # [B*T] flat page slots (padded: 0)
    block_tables: jax.Array,  # [B, pages]: no state-slot column
    context_lens: jax.Array,  # [B] valid tokens incl. the new ones
    last_token_idx: jax.Array,
    block_size: int,
    extra_embeds: Optional[jax.Array] = None,
    embeds_mask: Optional[jax.Array] = None,
    logits_all: bool = False,
):
    """One model step: (logits [B, V], pages, counts). Same contract as
    ``models/llama.py`` ``forward``; the engine threads ``pages`` and
    ``counts`` where it threads K and V."""
    if extra_embeds is not None or logits_all:
        raise NotImplementedError(
            "deepseek_v3: no injected embeddings, no all-position logits")
    g = Geometry(cfg)
    T = tokens.shape[1]
    latent = pages["latent"]

    def rotate(x):
        return hybrid.rotary_pairs(x.astype(jnp.float32), positions,
                                   float(cfg.rope_theta), cfg.rope_interleave)

    def attend(layer, h):
        nonlocal latent
        out, latent = hybrid.mla_mixer(
            params, h, layer, latent, g.latent, cfg.rms_norm_eps, positions,
            slot_mapping, block_tables, context_lens, block_size,
            kernels_active(), rotate=rotate,
            flash_prefill=True, attend_scope="mla_prefill_attend")
        return out

    x, act, seen, start, n_valid = decoder(
        cfg, g, params, tokens, positions, context_lens, attend)
    attended = (prefill_counts(start, n_valid, g.L) if T > 1
                else jnp.zeros((3,), jnp.int32))
    return (head(cfg, params, x, act, last_token_idx), {"latent": latent},
            {"counts": counts["counts"] + jnp.concatenate([seen, attended])})
