"""GLM-5-shaped decoders (``model_type: glm_moe_dsa``): ``deepseek_v3``'s
layers — rotary latent attention in every layer, leading dense MLPs, then
sigmoid-routed experts plus a shared one — with a LOW-RANK QUERY and
DeepSeek Sparse Attention: every layer has a learned indexer, and a query
attends only the ``index_topk`` keys it scores highest.

This module IMPORTS ``models/deepseek_v3.py`` rather than listing a second
``model_type`` in it: the geometry, the routing, ``moe_ffn``, the layer
loop (``decoder`` / ``head``), the prefill counts and the seeded draw are
that module's, used as they stand; what is GLM-5's own — the parameters
of the low-rank query and the indexer, the second page plane, the
selection, its counts — lives here, and the ``deepseek_v3`` recipe keeps
every index it had (``param_shapes`` there lists what it listed, in its
order; this family's recipe is its own list, which
``perf/reference/glm_moe_dsa.py`` mirrors).

What this family is to the engine (``models/__init__.py``): it OWNS ITS
PAGES and keeps NO recurrent state, so the prefix cache serves it and a
preempted row resumes from its cached pages. A page holds TWO per-token
planes under ONE block id and one table: ``latent`` ``[L, slots, Cpad]``
(the ``[c | rot(k_r)]`` row of ``deepseek_v3``) and ``index_k`` ``[L,
slots, index_head_dim]`` (the token's indexer key ``k^I``, normalised
and rotated) — ``page_bytes_per_block`` counts both, so whatever holds,
shares, frees or resumes a page carries the indexer's keys with the
latents, and no second allocator exists.

A layer's attention (``hybrid.mla_mixer`` with ``select``), token at
position ``p``:

  c_q = RMSNorm(W_qa h);  q = W_qb c_q           (the low-rank query)
  q^I = W^I_qb c_q  as G heads of d;  k^I = LayerNorm(W^I_k h) (weight,
  bias, eps 1e-6), ONE head;  the first ``qk_rope_head_dim`` values of
  each q^I head and of k^I rotated by p;  w = W^I_w h  (float32)
  I[p, j] = sum_g w[p, g] ReLU(q^I[p, g] . k^I[j]),  j <= p
  S_p = the min(index_topk, p + 1) keys of largest I, ties to the lower j
  softmax over S_p only — one S_p for all heads.

``k^I`` is written at ``slot_mapping`` beside the latent row, then the
row's cached ``k^I`` pages are gathered in table order and scored
(``ops/dsa.py`` ``index_scores``: a ``[T, S]`` float32 score a layer),
the exact top ``index_topk`` becomes a mask (``select_topk``), and the
attend step is ``ops/mla.py``'s page walk under that mask — the MASKED
WALK: every live page is read, unselected keys are masked. The other
exact form, a gather of the selected rows, was timed beside it on the
chip and lost at this family's contexts (PERF.md section 6, PR 49;
``perf/tools/dsa_forms.py``). A context of at most ``index_topk`` keys
selects every key: the layer is then dense latent attention, through
the same code. Chunked prefill needs nothing more: a chunk at any start
position scores the cached ``k^I`` pages exactly as it reads an earlier
chunk's.

Not built, refused by the key's name: ``n_group`` / ``topk_group`` > 1,
a scaled rotary (``rope_scaling``, a ``rope_parameters.rope_type`` other
than default), a ``scoring_func`` other than sigmoid, an indexer without
its sizes. ``num_nextn_predict_layers`` (the multi-token-prediction
layer) is read by nothing: the main model's logits do not depend on it.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import deepseek_v3, hybrid
from dynamo_tpu.models.config import ModelConfig

Params = dict[str, Any]

# what a step holds beside weights and pages at the published widths and
# the largest prefill rectangle (4 x 1 024 tokens under a 200-page table):
# the bf16 copies of ONE layer's 16 held experts (16 x 3 x 6 144 x 2 048 x
# 2 B = 1.2 GB), the absorbed queries and the latent-space output ([4 096,
# 64, 640 + 512] bf16 + the float32 result: 1.2-1.8 GB), ONE layer's index
# score and its mask ([4 096, 25 600] float32 twice = 0.8 GB) and the
# gathered indexer keys, the sorted rows of the grouped matmuls, the
# float32 logits: the described chip's compiler counts 4.16 GB there, 1.61
# GB at 1 x 1 024, 0.56-0.80 GB for a decode step
# (tests/test_chip_compile.py holds them under this bound)
STEP_TRANSIENT_BYTES = 9 << 29
DSA_COUNT_NAMES = ("dsa_index_pairs", "dsa_prefill_selected",
                   "dsa_decode_scored", "dsa_decode_selected", "dsa_calls")
COUNT_NAMES = hybrid.MOE_COUNT_NAMES + DSA_COUNT_NAMES
# the two prefill counts are in units of this many (query, key) pairs
PAIR_UNIT = deepseek_v3.PAIR_UNIT
INDEX_NORM_EPS = 1e-6


class Geometry(deepseek_v3.Geometry):
    """``deepseek_v3``'s sizes with a low-rank query and the indexer's."""

    def __init__(self, cfg: ModelConfig):
        super().__init__(cfg, low_rank_query=True)
        self.q_rank = cfg.q_lora_rank
        self.G = cfg.index_n_heads
        self.dI = cfg.index_head_dim
        self.topk = cfg.index_topk
        missing = [name for name, v in (
            ("q_lora_rank", self.q_rank), ("index_n_heads", self.G),
            ("index_head_dim", self.dI), ("index_topk", self.topk)) if not v]
        if missing:
            raise ValueError("glm_moe_dsa needs " + ", ".join(missing))
        if self.rope > self.dI:
            raise ValueError(
                f"index_head_dim {self.dI} is narrower than the "
                f"qk_rope_head_dim {self.rope} values of it that are rotated")


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

QUANT_AXIS = {
    **{n: a for n, a in deepseek_v3.QUANT_AXIS.items() if n != "mla_wq"},
    "mla_wqa": -2, "mla_wqb": -2, "idx_wq": -2, "idx_wk": -2,
}


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """``deepseek_v3``'s stacks in their order with ``mla_wq`` replaced,
    in its place, by the low-rank query's three, then the indexer's five
    at the end."""
    g = Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    L, D = g.L, g.D
    shapes: dict = {}
    for name, spec in deepseek_v3.param_shapes(cfg, g).items():
        if name == "mla_wq":
            shapes["mla_wqa"] = ((L, D, g.q_rank), bf16)
            shapes["mla_qnorm"] = ((L, g.q_rank), f32)
            shapes["mla_wqb"] = ((L, g.q_rank, g.H * (g.nope + g.rope)), bf16)
        else:
            shapes[name] = spec
    shapes.update({
        "idx_wq": ((L, g.q_rank, g.G * g.dI), bf16),
        "idx_wk": ((L, D, g.dI), bf16),
        "idx_knorm": ((L, g.dI), f32),          # the LayerNorm's weight ...
        "idx_kbias": ((L, g.dI), f32),          # ... and its bias
        "idx_ww": ((L, D, g.G), f32),           # the heads' weights: float32
    })
    return shapes


def param_specs(cfg: ModelConfig) -> dict[str, P]:
    """One device holds everything (check_engine refuses tp/ep/pp > 1)."""
    return {name: P() for name in param_shapes(cfg)}


def _draw_one(name: str, key, shape: tuple[int, ...]):
    """``deepseek_v3``'s rule (norms 1, selection bias 0, ``normal /
    sqrt(fan_in)``), and the indexer LayerNorm's bias standard normal, so
    that it moves the order of the keys."""
    if name == "idx_kbias":
        return jax.random.normal(key, shape, jnp.float32)
    return deepseek_v3._draw_one(name, key, shape)


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
# The cache: two planes under one page id, and the counts
# ---------------------------------------------------------------------------

def plane_widths(cfg: ModelConfig) -> dict[str, int]:
    """Values a token a layer in each plane, as stored."""
    g = Geometry(cfg)
    return {"latent": g.Cpad, "index_k": g.dI}


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int,
                         plane: Optional[str] = None) -> int:
    """Bytes one block of pages takes over all layers and BOTH planes
    (the engine sizes the pool with it); ``plane``: that plane's alone."""
    widths = plane_widths(cfg)
    lanes = widths[plane] if plane else sum(widths.values())
    return cfg.num_hidden_layers * block_size * lanes * itemsize


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None):
    """(pages, counts), zeroed: ``{"latent": [L, slots, Cpad], "index_k":
    [L, slots, index_head_dim]}`` — one block id space, one table — and
    ``{"counts": int32 [len(COUNT_NAMES)]}``; no state plane."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("glm_moe_dsa has no int8 page planes")
    g = Geometry(cfg)
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    slots = num_blocks * block_size
    pages = {name: jnp.zeros((g.L, slots, width), dtype, device=sh)
             for name, width in plane_widths(cfg).items()}
    counts = {"counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh)}
    return pages, counts


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong (``hybrid.check_engine``; block export and
    import by ``engine.refuse_kv_transfer``)."""
    hybrid.check_engine(
        config, "model_type glm_moe_dsa (latent and indexer-key pages)")


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

kernels_active = hybrid.kernels_active


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rotate_leading(x: jax.Array, positions: jax.Array, n: int, theta: float,
                   interleave: bool) -> jax.Array:
    """The first ``n`` values of ``x [B, T, ..., d]`` (float32) rotated
    at ``positions``, the rest as they are."""
    return jnp.concatenate([
        hybrid.rotary_pairs(x[..., :n], positions, theta, interleave),
        x[..., n:]], axis=-1)


def indexer_inputs(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array,
                   c_q: jax.Array, layer: int, positions: jax.Array):
    """(q^I [B, T, G, d], k^I [B, T, d], w [B, T, G] float32) of layer
    ``layer``: ``h`` the attention's normalised input, ``c_q`` the
    normalised query latent."""
    B, T, _ = h.shape
    act = h.dtype
    theta = float(cfg.rope_theta)
    inter = bool(cfg.indexer_rope_interleave)
    q = hybrid.mm(p, "idx_wq", c_q.astype(act), layer).reshape(B, T, g.G, g.dI)
    k = layernorm(hybrid.mm(p, "idx_wk", h, layer), p["idx_knorm"][layer],
                  p["idx_kbias"][layer], INDEX_NORM_EPS)
    q = rotate_leading(q.astype(jnp.float32), positions, g.rope, theta, inter)
    k = rotate_leading(k, positions, g.rope, theta, inter)
    with jax.default_matmul_precision("highest"):
        w = h.astype(jnp.float32) @ p["idx_ww"][layer]
    return q.astype(act), k.astype(act), w


def select_keys(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array,
                c_q: jax.Array, layer: int, index_k: jax.Array,
                positions: jax.Array, slot_mapping: jax.Array,
                tables: jax.Array, context_lens: jax.Array, block_size: int,
                kernels: bool):
    """The layer's selection: (marks [B, T, S] float32 — 1.0 at the keys
    a query attends — and ``index_k`` with this step's keys written).
    ``S`` is the table's columns x ``block_size``, in table order."""
    from dynamo_tpu.ops import dsa

    B, T, _ = h.shape
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope("dsa_index"):
        q, k, w = indexer_inputs(cfg, g, p, h, c_q, layer, positions)
        index_k = index_k.at[layer, slot_mapping].set(
            k.reshape(B * T, g.dI).astype(index_k.dtype))
        # the row's pages, a PAGE a gathered element (32 KB at the published
        # sizes): gathered a token at a time (256 B) the same bytes took
        # twenty times as long on the chip
        S = tables.shape[1] * block_size
        keys = index_k.reshape(g.L, -1, block_size, g.dI)[layer, tables].reshape(
            B, S, g.dI)
        if kernels:
            scores = dsa.index_scores(q, w, keys, positions[:, 0], context_lens,
                                      interpret=interpret)
        else:
            scores = dsa.index_scores_xla(q, w, keys, positions[:, 0],
                                          context_lens)
    with jax.named_scope("dsa_select"):
        if kernels:
            sel = dsa.select_topk(scores, context_lens, k=g.topk,
                                  interpret=interpret)
        else:
            sel = dsa.select_topk_xla(scores, g.topk)
    return sel, index_k


def dsa_counts(start: jax.Array, n_valid: jax.Array, context_lens: jax.Array,
               T: int, layers: int, topk: int):
    """``DSA_COUNT_NAMES`` of one call, summed over ``layers``: a prefill
    token at position ``p`` scores ``p + 1`` keys and attends ``min(topk,
    p + 1)`` (both in ``PAIR_UNIT``s); a decode row scores its context
    and attends ``min(topk, context)``."""
    n = n_valid.astype(jnp.int32)
    ctx = jnp.where(n > 0, context_lens.astype(jnp.int32), 0)
    zero = jnp.int32(0)
    if T == 1:
        return jnp.stack([zero, zero, layers * jnp.sum(ctx),
                          layers * jnp.sum(jnp.minimum(ctx, topk)),
                          jnp.int32(layers)])
    start = start.astype(jnp.int32)
    pairs = n * start + n * (n + 1) // 2
    # tokens at positions < topk attend all their keys, the others topk
    dense = jnp.clip(topk - start, 0, n)       # tokens with p + 1 <= topk
    picked = dense * start + dense * (dense + 1) // 2 + (n - dense) * topk
    return jnp.stack([layers * jnp.sum(pairs) // PAIR_UNIT,
                      layers * jnp.sum(picked) // PAIR_UNIT,
                      zero, zero, jnp.int32(layers)])


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"latent": [L, slots, Cpad], "index_k": [L, slots, d]}
    counts: dict,             # {"counts": int32 [len(COUNT_NAMES)]}
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
    ``models/llama.py`` ``forward``."""
    if extra_embeds is not None or logits_all:
        raise NotImplementedError(
            "glm_moe_dsa: no injected embeddings, no all-position logits")
    g = Geometry(cfg)
    T = tokens.shape[1]
    latent, index_k = pages["latent"], pages["index_k"]
    kernels = kernels_active()

    def rotate(x):
        return hybrid.rotary_pairs(x.astype(jnp.float32), positions,
                                   float(cfg.rope_theta), cfg.rope_interleave)

    def attend(layer, h):
        nonlocal latent, index_k

        def select(c_q):
            nonlocal index_k
            sel, index_k = select_keys(
                cfg, g, params, h, c_q, layer, index_k, positions,
                slot_mapping, block_tables, context_lens, block_size, kernels)
            return sel

        out, latent = hybrid.mla_mixer(
            params, h, layer, latent, g.latent, cfg.rms_norm_eps, positions,
            slot_mapping, block_tables, context_lens, block_size, kernels,
            rotate=rotate, flash_prefill=True, attend_scope="dsa_attend",
            select=select)
        return out

    x, act, seen, start, n_valid = deepseek_v3.decoder(
        cfg, g, params, tokens, positions, context_lens, attend)
    picked = dsa_counts(start, n_valid, context_lens, T, g.L, g.topk)
    return (deepseek_v3.head(cfg, params, x, act, last_token_idx),
            {"latent": latent, "index_k": index_k},
            {"counts": counts["counts"] + jnp.concatenate([seen, picked])})
