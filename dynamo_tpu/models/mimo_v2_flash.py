"""MiMo-V2-Flash (``model_type: mimo_v2_flash``): window attention (the
last ``sliding_window`` keys, a learned sink a query head) and full
attention mixed by ``hybrid_layer_pattern``, the two kinds with their own
KV head counts and rotary bases, K heads ``head_dim`` wide and V heads
``v_head_dim``; a dense SiLU-gated MLP where ``moe_layer_freq`` says 0
and sigmoid-routed experts with no shared one elsewhere — on the
engine's normal step.

What this family is to the engine (``models/__init__.py``): it OWNS ITS
PAGES, keeps NO recurrent state, and answers the third question — ONE OF
ITS PLANES IS RELEASED BEHIND A WINDOW (``released_window``):

- the FULL plane, ``full_k`` / ``full_v``: the K and V of the full
  layers, a page of which lives as long as its row. Its ids, its table
  and its slot mapping are the engine's own (``BlockAllocator``);
- the WINDOW plane, ``win_k`` / ``win_v``: the K and V of the window
  layers, with its own block ids (``allocator.WindowPlane``). A row's
  table of it is the second half of ``block_tables``, indexed by the
  same ABSOLUTE column as the first half — a column behind the window
  reads 0 and is never dereferenced: the decode kernel walks from the
  page of key ``ctx - window`` on, the prefill kernel a tile from the
  page of its first query's window edge on (the XLA path reads the
  ``span`` columns a chunk's window can touch, rebased:
  ``window_columns``). The full-width table costs 4 B a column
  a row (512 B at 128 columns) against a ring's modulus in every
  reader. Where a token's K and V go in this plane follows from the
  table and the token's position, on the device (``window_slots``);

both stored as their (token, head) rows ``[layers, slots * Hk, width]``,
the bytes the decode kernel's page view reads in place
(``models/qwen3_next.py`` says why a 4-head 4-D shape would not be). K
ROWS ARE STORED 256 LANES WIDE for a 192-wide head (zeros behind it, and
behind the queries: the scores are the same, the scale stays
``192 ** -0.5``): a 192-lane minor dimension is a tile and a half on the
chip. The described v5e's compiler lays a ``[..., 192]`` bf16 pool out
in 256 lanes whatever its shape says and REFUSES the decode kernel's
page copy from it ("Slice shape along dimension 3 must be aligned to
tiling (128), but is 192"; ``tests/test_chip_compile.py`` holds that), so
192 stored lanes would save no byte and lose the kernel — here the
padding is stated, sized (``page_bytes_per_block``: K 256 + V 128 = 384
lanes a token and head where the published widths are 320) and never
re-laid-out.

Attention is ``ops/paged_attention.py``'s one decode and one prefill
kernel, given what each CALL needs (KV heads and widths from the arrays;
window, sinks, scale as arguments), under the names
``paged_attention_{decode,prefill}_stacked_{full,window}`` (the wrappers'
``name`` argument) so that a trace tells the kinds apart; off the chip ``llama.paged_attention_
reference`` over the same tables. ``attention_value_scale`` multiplies V
in the open, in float32, before it is rounded into its page. Routing and
experts are ``hybrid.sigmoid_routing`` / ``hybrid.moe_local``: the router
scores all ``n_routed_experts * expert_shards`` experts, this process
computes the share of the ``n_routed_experts`` it holds.

Layers are unrolled in Python, as the other families of ``hybrid.py``
are. The residual stream, matmul results and the router's input stay
float32.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models import hybrid, llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.qwen3_next import partial_rope
from dynamo_tpu.ops import paged_attention as pa

Params = dict[str, Any]

# at most this many tokens go through every held expert at once; more are
# sorted by expert and go through the grouped matmul (16 held experts of
# 4 096 x 2 048: the every-expert form reads 0.4 GB a layer whatever the
# rows, which a decode batch pays anyway)
MOE_DENSE_TOKENS = 64
# what a step holds beside weights and pages at the published widths: the
# largest prefill rectangle's (32 rows x 128 = max_prefill_tokens 4 096
# tokens) grouped expert matmuls — the bf16 copies of ONE layer's held
# experts (16 x 3 x 4 096 x 2 048 x 2 B = 0.75 GiB) and the rows of ALL
# 4 096 x 8 assignments sorted by expert, held or not (``hybrid.
# moe_local_grouped``: the rows in, gate, up, mid, down and the unsorted
# result, 1.9 GiB) — beside which q, k, v (the prefill kernel reads the
# pages in place) and the dense layer's 16 384-wide intermediate are
# small. The described chip's compiler counts 3.03 GiB at 32 x 128, 1.92
# at 8 x 256, 0.92 at 1 x 1 024, 0.57 at 1 x 512, 0.04 at a 64-row decode
# step (tests/test_chip_compile.py holds them under this bound). Until
# PR 51 the many-row rectangles read 2.71 / 1.44: their attention ran as a
# loop over groups of rows around a gather of the rows' pages, and XLA
# scheduled nothing across that loop; without it the scheduler overlaps
# more of a layer's neighbours with the expert matmuls (no buffer of the
# attention's own is among the large ones)
STEP_TRANSIENT_BYTES = 13 << 28
ATTN_COUNT_NAMES = (
    "attn_full_pairs", "attn_window_pairs",
    "attn_full_decode_keys", "attn_window_decode_keys",
    "attn_full_prefill_calls", "attn_window_prefill_calls",
    "attn_full_decode_calls", "attn_window_decode_calls")
COUNT_NAMES = hybrid.MOE_COUNT_NAMES + ATTN_COUNT_NAMES
# ``attn_*_pairs`` count in units of this many (query, key) pairs: the
# chip attends single pairs faster than an int32 of them could be read
# as a difference (models/deepseek_v3.py PAIR_UNIT)
PAIR_UNIT = 1024
LANES = 128


class Geometry:
    """The sizes of one configuration, worked out once; what the family
    does not build raises here, by the key's name."""

    def __init__(self, cfg: ModelConfig):
        self.L = cfg.num_hidden_layers
        pattern, freq = cfg.hybrid_layer_pattern, cfg.moe_layer_freq
        for key, val in (("hybrid_layer_pattern", pattern),
                         ("moe_layer_freq", freq)):
            if (not isinstance(val, list) or len(val) != self.L
                    or set(val) - {0, 1}):
                raise ValueError(
                    f"mimo_v2_flash needs {key} as num_hidden_layers = "
                    f"{self.L} entries of 0 / 1, got {val!r}")
        self.window = cfg.sliding_window
        refused = {
            "sliding_window (the window layers' width) missing":
                not self.window or self.window < 1,
            "sliding_window_size / attention_chunk_size other than "
            "sliding_window (chunked attention is not built)": any(
                v not in (None, self.window) for v in
                (cfg.sliding_window_size, cfg.attention_chunk_size)),
            "swa_num_attention_heads / swa_head_dim / swa_v_head_dim other "
            "than the full layers' (one query geometry is built)":
                cfg.swa_num_attention_heads not in (None, cfg.num_attention_heads)
                or cfg.swa_head_dim not in (None, cfg.head_dim)
                or cfg.swa_v_head_dim not in (None, cfg.v_head_dim),
            "v_head_dim missing": not cfg.v_head_dim,
            "n_shared_experts (a shared expert)": bool(cfg.n_shared_experts),
            "n_group / topk_group > 1 (group-limited routing)":
                cfg.n_group != 1 or cfg.topk_group != 1,
            f"scoring_func {cfg.scoring_func!r} (sigmoid only)":
                cfg.scoring_func != "sigmoid",
            f"topk_method {cfg.topk_method!r} (noaux_tc only)":
                cfg.topk_method != "noaux_tc",
            "rope_scaling (scaled rotary)": cfg.rope_scaling is not None,
            "attention_bias": bool(cfg.attention_bias),
            f"hidden_act {cfg.hidden_act!r} (silu only)":
                cfg.hidden_act != "silu",
            "tie_word_embeddings": bool(cfg.tie_word_embeddings),
        }
        bad = [name for name, hit in refused.items() if hit]
        if bad:
            raise ValueError("mimo_v2_flash does not build: " + "; ".join(bad))
        self.D = cfg.hidden_size
        self.V = cfg.vocab_size
        self.H = cfg.num_attention_heads
        self.Dk = cfg.head_dim
        self.Dv = cfg.v_head_dim
        # K rows as stored: whole 128-lane tiles (the module docstring)
        self.Dkp = -(-self.Dk // LANES) * LANES
        self.rot = int(self.Dk * cfg.partial_rotary_factor)
        if self.rot % 2 or not 0 < self.rot <= self.Dk:
            raise ValueError(
                f"partial_rotary_factor {cfg.partial_rotary_factor} of "
                f"head_dim {self.Dk} is no even number of rotated dims")
        self.vscale = (1.0 if cfg.attention_value_scale is None
                       else float(cfg.attention_value_scale))
        self.full_layers = [i for i, k in enumerate(pattern) if k == 0]
        self.window_layers = [i for i, k in enumerate(pattern) if k == 1]
        # by kind: (layers, KV heads, rotary base, sinks?, window)
        self.kinds = {
            "full": (self.full_layers, cfg.num_key_value_heads,
                     float(cfg.rope_theta),
                     bool(cfg.add_full_attention_sink_bias), None),
            "win": (self.window_layers,
                    cfg.swa_num_key_value_heads or cfg.num_key_value_heads,
                    float(cfg.swa_rope_theta or cfg.rope_theta),
                    bool(cfg.add_swa_attention_sink_bias), self.window),
        }
        for kind, (_, hk, *_rest) in self.kinds.items():
            if hk < 1 or self.H % hk:
                raise ValueError(
                    f"{kind} layers: {self.H} query heads over {hk} KV heads")
        self.F = cfg.intermediate_size
        self.Fe = cfg.moe_intermediate_size
        self.E = cfg.n_routed_experts             # held here
        self.E_all = self.E * cfg.expert_shards   # what the router scores
        self.e0 = cfg.expert_shard_index * self.E
        self.k = cfg.num_experts_per_tok
        self.route_scale = (1.0 if cfg.routed_scaling_factor is None
                            else float(cfg.routed_scaling_factor))
        self.dense_layers = [i for i, f in enumerate(freq) if f == 0]
        self.moe_layers = [i for i, f in enumerate(freq) if f == 1]
        if self.moe_layers and not (self.E and self.k and self.Fe):
            raise ValueError(
                "mimo_v2_flash expert layers need n_routed_experts, "
                "num_experts_per_tok and moe_intermediate_size")

    def kind_of(self, layer: int) -> tuple[str, int]:
        """(``"full"`` | ``"win"``, the layer's index among its kind)."""
        if layer in self.full_layers:
            return "full", self.full_layers.index(layer)
        return "win", self.window_layers.index(layer)


def released_window(cfg: ModelConfig) -> int:
    """THE THIRD QUESTION (``models/__init__.py``): the window plane's
    pages are released behind this many keys; 0 where no layer is a
    window layer (everything then lives in the full plane)."""
    g = Geometry(cfg)
    return g.window if g.window_layers else 0


# ---------------------------------------------------------------------------
# Parameters. The ORDER of param_shapes is part of the seeded recipe.
# ---------------------------------------------------------------------------

QUANT_AXIS = {
    "embed": -1, "lm_head": -2,
    "full_wq": -2, "full_wk": -2, "full_wv": -2, "full_wo": -2,
    "win_wq": -2, "win_wk": -2, "win_wv": -2, "win_wo": -2,
    "w_gate": -2, "w_up": -2, "w_down": -2,
    "we_gate": -2, "we_up": -2, "we_down": -2,
}


def param_shapes(cfg: ModelConfig) -> dict[str, tuple[tuple[int, ...], Any]]:
    """name -> (shape, dtype); layer parameters are stacked per KIND."""
    g = Geometry(cfg)
    bf16, f32 = jnp.bfloat16, jnp.float32
    L, D = g.L, g.D
    shapes: dict = {
        "embed": ((g.V, D), bf16),
        "final_norm": ((D,), f32),
        "lm_head": ((D, g.V), bf16),
        "attn_norm": ((L, D), f32),
        "mlp_norm": ((L, D), f32),
    }
    for kind, (layers, hk, _, sinks, _) in g.kinds.items():
        n = len(layers)
        if not n:
            continue
        shapes.update({
            f"{kind}_wq": ((n, D, g.H * g.Dk), bf16),
            f"{kind}_wk": ((n, D, hk * g.Dk), bf16),
            f"{kind}_wv": ((n, D, hk * g.Dv), bf16),
            f"{kind}_wo": ((n, g.H * g.Dv, D), bf16),
        })
        if sinks:
            shapes[f"{kind}_sink"] = ((n, g.H), f32)
    Ld, Le = len(g.dense_layers), len(g.moe_layers)
    if Ld:
        shapes.update({
            "w_gate": ((Ld, D, g.F), bf16),
            "w_up": ((Ld, D, g.F), bf16),
            "w_down": ((Ld, g.F, D), bf16),
        })
    if Le:
        shapes.update({
            "router": ((Le, D, g.E_all), f32),
            "router_bias": ((Le, g.E_all), f32),   # e_score_correction_bias
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
    norms 1; selection bias 0; sinks N(0, 1), so that they matter;
    everything else ``normal / sqrt(fan_in)``."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    if name.endswith("_sink"):
        return jax.random.normal(key, shape, jnp.float32)
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
# The cache: two page planes, and the counts
# ---------------------------------------------------------------------------


def page_bytes_per_block(cfg: ModelConfig, block_size: int, itemsize: int,
                         plane: str = "full") -> int:
    """Bytes one block of a plane's K and V pages takes over that plane's
    layers, as stored (K in ``Dkp`` lanes): the engine sizes the full
    plane's pool with it and takes the window plane's (``plane="window"``)
    off the top."""
    g = Geometry(cfg)
    layers, hk, *_ = g.kinds["win" if plane == "window" else "full"]
    return max(1, len(layers)) * block_size * hk * (g.Dkp + g.Dv) * itemsize


def cache_shapes(cfg: ModelConfig, num_blocks: int, block_size: int,
                 window_blocks: int) -> dict:
    g = Geometry(cfg)
    out = {}
    for kind, blocks in (("full", num_blocks), ("win", window_blocks)):
        layers, hk, *_ = g.kinds[kind]
        rows = blocks * block_size * hk
        out[f"{kind}_k"] = (max(1, len(layers)), rows, g.Dkp)
        out[f"{kind}_v"] = (max(1, len(layers)), rows, g.Dv)
    return out


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               mesh: Optional[Mesh] = None, dtype=jnp.bfloat16,
               spec: Optional[P] = None, window_blocks: int = 2):
    """(pages, counts), zeroed: ``{"full_k", "full_v", "win_k", "win_v"}``
    as (token, head) rows, and ``{"counts": int32 [len(COUNT_NAMES)]}``.
    ``window_blocks`` counts the window plane's garbage block 0."""
    if jnp.dtype(dtype) == jnp.int8:
        raise ValueError("mimo_v2_flash has no int8 K/V cache")
    sh = NamedSharding(mesh, P()) if mesh is not None else None
    pages = {n: jnp.zeros(s, dtype, device=sh) for n, s in cache_shapes(
        cfg, num_blocks, block_size, window_blocks).items()}
    # cumulative, on the device, read at a profiler capture's edges
    # (engine.program_counts)
    counts = {"counts": jnp.zeros((len(COUNT_NAMES),), jnp.int32, device=sh)}
    return pages, counts


def check_engine(config) -> None:
    """What is not built for this family is refused when the engine
    starts, never served wrong: more than one device, speculation, KVBM
    offload, int8 pages (``hybrid.check_engine``; block export and
    import — KV transfer between workers — by ``engine.refuse_kv_
    transfer``: neither knows the window plane)."""
    hybrid.check_engine(
        config, "model_type mimo_v2_flash (two page planes, one released)")


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

kernels_active = hybrid.kernels_active


# the device op of a Pallas call is named after ``name``: a trace has to
# tell the two kinds' calls apart
DECODE = {kind: functools.partial(
    pa.paged_attention_decode_stacked,
    name=f"paged_attention_decode_stacked_{name}")
    for kind, name in (("full", "full"), ("win", "window"))}
PREFILL = {kind: functools.partial(
    pa.paged_attention_prefill_stacked,
    name=f"paged_attention_prefill_stacked_{name}")
    for kind, name in (("full", "full"), ("win", "window"))}


def window_span(window: int, block_size: int, T: int, width: int) -> int:
    """Table columns the keys of ``T`` consecutive queries can touch under
    ``window`` (``allocator.WindowPlane.span_pages``), at most ``width``."""
    run = window - 1 + T
    return min(width, (run - 2) // block_size + 2 if run > 1 else 1)


def window_columns(tables: jax.Array, start: jax.Array, window: int,
                   block_size: int, T: int):
    """The window plane's LIVE columns of each row for queries ``start
    ... start + T - 1``: (sub-table ``[B, span]``, the position of its
    first key ``[B]``). Positions rebased by that offset see the same
    causal and window masks; a column past the row's pages repeats the
    last one or reads the garbage page, and its keys lie at or past the
    row's context, masked."""
    W = tables.shape[1]
    first = jnp.maximum(start - (window - 1), 0) // block_size
    cols = first[:, None] + jnp.arange(window_span(window, block_size, T, W))
    sub = jnp.take_along_axis(tables, jnp.minimum(cols, W - 1), axis=1)
    return sub, first * block_size


def window_slots(tables: jax.Array, positions: jax.Array,
                 slot_mapping: jax.Array, block_size: int) -> jax.Array:
    """Where each token's K and V go in the window plane: its position's
    column of the row's window table. A token the engine sends to the
    garbage slot (padding, a masked step of a fused window: full-plane
    slot 0, which no real token has) goes to this plane's garbage slot."""
    page = jnp.take_along_axis(tables, positions // block_size, axis=1)
    slot = (page * block_size + positions % block_size).reshape(-1)
    return jnp.where(slot_mapping > 0, slot, 0)


def moe_routing(cfg: ModelConfig, g: Geometry, p: Params, x: jax.Array,
                idx: int):
    """Top ``num_experts_per_tok`` of ``sigmoid + e_score_correction_bias``
    over ALL experts (``n_group`` 1: the group limit is inert), weights
    the scores themselves, renormalised (``hybrid.sigmoid_routing``)."""
    return hybrid.sigmoid_routing(
        p["router"][idx], p["router_bias"][idx], x, g.k, cfg.norm_topk_prob,
        g.route_scale)


def moe_ffn(cfg: ModelConfig, g: Geometry, p: Params, h: jax.Array, idx: int,
            valid: Optional[jax.Array] = None,
            h_route: Optional[jax.Array] = None):
    """This process's experts' share of the routed sum (no shared
    expert). Returns (out float32, counts int32 [3]); ``h_route`` is ``h``
    before it was rounded to the activation dtype — the router's."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    w, topi = moe_routing(
        cfg, g, p, x if h_route is None else h_route.reshape(B * T, D), idx)
    with jax.named_scope("moe_block"):
        routed, counts = hybrid.moe_local(
            p, x, w, topi, idx, g.e0, g.E, MOE_DENSE_TOKENS,
            None if valid is None else valid.reshape(B * T))
    return routed.reshape(B, T, D), counts


def attended(g: Geometry, T: int, start: jax.Array, n_valid: jax.Array,
             context_lens: jax.Array) -> jax.Array:
    """What the step's attention calls attended, int32 in
    ``ATTN_COUNT_NAMES``' order. Prefill (``T > 1``): the valid (query,
    key) pairs, summed over layers, in units of ``PAIR_UNIT`` — a query at
    position ``p`` sees ``p + 1`` keys in a full layer, ``min(p + 1,
    window)`` in a window layer — and the calls. Decode: the keys the
    calls read, summed over rows and layers (``ctx``, or ``min(ctx,
    window)``), and the calls. A rectangle holds at most
    ``max_prefill_tokens`` tokens under ``max_model_len`` keys, so its
    single pairs fit an int32 before they are divided."""
    Lf, Lw, w = len(g.full_layers), len(g.window_layers), g.window
    zero = jnp.int32(0)
    if T == 1:
        ctx = context_lens.astype(jnp.int32)
        return jnp.stack([
            zero, zero, Lf * jnp.sum(ctx), Lw * jnp.sum(jnp.minimum(ctx, w)),
            zero, zero, jnp.int32(Lf), jnp.int32(Lw)])
    n, s = n_valid.astype(jnp.int32), start.astype(jnp.int32)

    def seen(m):   # keys that queries 0 .. m - 1 of a window layer see
        return jnp.where(m <= w, m * (m + 1) // 2,
                         w * (w + 1) // 2 + (m - w) * w)

    return jnp.stack([
        Lf * jnp.sum(n * s + n * (n + 1) // 2) // PAIR_UNIT,
        Lw * jnp.sum(seen(s + n) - seen(s)) // PAIR_UNIT,
        zero, zero, jnp.int32(Lf), jnp.int32(Lw), zero, zero])


def forward(
    cfg: ModelConfig,
    params: Params,
    pages: dict,              # {"full_k", "full_v", "win_k", "win_v"}
    counts: dict,             # {"counts": int32 [11]}
    tokens: jax.Array,        # [B, T]
    positions: jax.Array,     # [B, T] (padded: 0)
    slot_mapping: jax.Array,  # [B*T] flat FULL-plane slots (padded: 0)
    block_tables: jax.Array,  # [B, 2 W]: full-plane columns | window-plane
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
            "mimo_v2_flash: no injected embeddings, no all-position logits")
    g = Geometry(cfg)
    mm = hybrid.mm
    B, T = tokens.shape
    eps = cfg.rms_norm_eps
    W = block_tables.shape[1] // 2
    tables = {"full": block_tables[:, :W], "win": block_tables[:, W:]}
    start = positions[:, 0]
    n_valid = jnp.clip(context_lens - start, 0, T)            # [B]
    valid = jnp.arange(T)[None, :] < n_valid[:, None]         # [B, T]
    slots = {"full": slot_mapping,
             "win": window_slots(tables["win"], positions, slot_mapping,
                                 block_size)}
    pages = dict(pages)
    kernels = kernels_active()
    interpret = jax.default_backend() != "tpu"
    scale = g.Dk ** -0.5
    seen = jnp.zeros((len(hybrid.MOE_COUNT_NAMES),), jnp.int32)

    x = llama.embed_lookup(params, tokens)
    act = x.dtype
    x = x.astype(jnp.float32)

    def attention(h, kind, ai):
        _, hk, theta, has_sink, window = g.kinds[kind]
        q = mm(params, f"{kind}_wq", h, ai).reshape(B, T, g.H, g.Dk)
        k = mm(params, f"{kind}_wk", h, ai).reshape(B, T, hk, g.Dk)
        v = mm(params, f"{kind}_wv", h, ai).reshape(B, T, hk, g.Dv) * g.vscale
        q, k = partial_rope(q, k, positions, theta, g.rot)
        pad = [(0, 0)] * 3 + [(0, g.Dkp - g.Dk)]
        q = jnp.pad(q.astype(act), pad)
        kp, vp = pages[f"{kind}_k"], pages[f"{kind}_v"]
        rows = (slots[kind][:, None] * hk + jnp.arange(hk)).reshape(-1)
        kp = kp.at[ai, rows].set(
            jnp.pad(k, pad).reshape(B * T * hk, g.Dkp).astype(kp.dtype))
        vp = vp.at[ai, rows].set(
            v.reshape(B * T * hk, g.Dv).astype(vp.dtype))
        pages[f"{kind}_k"], pages[f"{kind}_v"] = kp, vp
        sink = params[f"{kind}_sink"][ai] if has_sink else None
        n_slots = kp.shape[1] // hk
        if kernels and T == 1:
            # the kernel's page view of this shape is the stored bytes;
            # under a window it walks the row's live pages alone
            attn = DECODE[kind](
                q[:, 0], kp.reshape(kp.shape[0], n_slots, hk, g.Dkp),
                vp.reshape(vp.shape[0], n_slots, hk, g.Dv), jnp.int32(ai),
                tables[kind], context_lens, block_size=block_size,
                sliding_window=window, sinks=sink, scale=scale,
                interpret=interpret)[:, None]
            return attn
        if kernels:
            # the stored rows in place, as decode reads them: a tile walks
            # its own live pages (under a window those its queries can see)
            return PREFILL[kind](
                q, kp.reshape(kp.shape[0], n_slots, hk, g.Dkp),
                vp.reshape(vp.shape[0], n_slots, hk, g.Dv), jnp.int32(ai),
                tables[kind], start, context_lens, block_size=block_size,
                sliding_window=window, sinks=sink, scale=scale,
                interpret=interpret)
        # the columns the reference reads, and the position its first key
        # has: every column of the full plane; of the window plane the span
        # a chunk's window can touch, rebased
        if window is None:
            sub, base = tables[kind], jnp.zeros_like(start)
        else:
            sub, base = window_columns(tables[kind], start, window,
                                       block_size, T)
        return llama.paged_attention_reference(
            q, kp[ai].reshape(n_slots, hk, g.Dkp),
            vp[ai].reshape(n_slots, hk, g.Dv), sub,
            positions - base[:, None], jnp.maximum(context_lens - base, 0),
            block_size, sliding_window=window, sinks=sink, scale=scale)

    for layer in range(g.L):
        kind, ai = g.kind_of(layer)
        h = llama.rmsnorm(x, params["attn_norm"][layer], eps).astype(act)
        with jax.named_scope("attn_full" if kind == "full" else "attn_window"):
            attn = attention(h, kind, ai)
            out = mm(params, f"{kind}_wo",
                     attn.reshape(B, T, g.H * g.Dv).astype(act), ai)
        x = x + out.astype(jnp.float32)
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

    x = llama.rmsnorm(x, params["final_norm"], eps).astype(act)
    x_last = jnp.take_along_axis(
        x, last_token_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    grown = jnp.concatenate(
        [seen, attended(g, T, start, n_valid, context_lens)])
    return (llama.lm_head(params, x_last), pages,
            {"counts": counts["counts"] + grown})
