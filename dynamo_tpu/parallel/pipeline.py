"""Pipeline parallelism: GPipe-style SPMD stage rotation over a "pp" axis.

The reference only passes a pipeline-parallel knob down to its engines
(reference: SURVEY.md §2.6 — "config knob passed to engines only"); here
PP is native. TPU-idiomatic formulation:

- layer-stacked params (leading L axis, models/llama.py) are sharded over
  the "pp" mesh axis: each stage holds L/pp contiguous layers — no
  parameter broadcast, stage weights live on the stage's devices only.
- the batch is split into M microbatches; a `shard_map` over "pp" runs the
  classic GPipe rotation as a `lax.scan` over M+pp-1 ticks: every tick,
  each stage runs its local layers on its current microbatch and
  `ppermute`s the activation to the next stage. Bubble fraction is
  (pp-1)/(M+pp-1), amortised by choosing M >= pp.
- "pp" is a *manual* shard_map axis; "tp"/"dp" remain auto axes, so
  tensor-parallel matmul shardings propagate inside each stage untouched
  (partial-auto shard_map) and XLA still inserts the tp psums over ICI.
- the per-stage paged KV cache slice ([L/pp, slots, Hkv, Dh]) is updated
  in place by each tick; invalid (bubble) ticks write to the pad slot 0,
  which the allocator reserves as scratch.

This mirrors how the transformer scan treats layers as data: the pipeline
is just the same scan distributed over devices with a rotating carry.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    Params,
    embed_lookup,
    layer_param_names,
    mm,
    make_layer_fn,
    param_specs,
    rmsnorm,
    scale_embed,
)


def pp_param_specs(cfg: ModelConfig) -> dict[str, P]:
    """PartitionSpecs with layer-stacked params sharded over "pp" (axis 0).

    tp/ep placements from the base specs are preserved; non-layer params
    (embed/final_norm/lm_head) stay replicated across pp.
    """
    base = param_specs(cfg)
    out: dict[str, P] = {}
    for name, spec in base.items():
        if name in ("embed", "final_norm", "lm_head"):
            out[name] = spec
        else:
            out[name] = P("pp", *spec[1:])
    return out


PP_CACHE_SPEC = P("pp", None, "tp", None)

# shard_map specs may only mention the manual axis ("pp"); tp/ep shardings
# on the same arrays ride along as auto (GSPMD-managed) axes.
_PP_ONLY_CACHE_SPEC = P("pp", None, None, None)


def _pp_only(spec: P) -> P:
    return P(*(ax if ax == "pp" else None for ax in spec))


def forward_pp(
    cfg: ModelConfig,
    params: Params,
    k_cache: jax.Array,  # [L, n_slots, Hkv, Dh], L sharded over pp
    v_cache: jax.Array,
    tokens: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
    slot_mapping: jax.Array,  # [B*T]
    block_tables: jax.Array,  # [B, max_blocks]
    context_lens: jax.Array,  # [B]
    last_token_idx: jax.Array,  # [B]
    block_size: int,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pipeline-parallel model step. Same contract as models.llama.forward.

    B must be divisible by num_microbatches (default: pp size).
    """
    pp = mesh.shape["pp"]
    B, T = tokens.shape
    if num_microbatches is None:
        # largest divisor of B that is <= pp: amortises the bubble without
        # ever rejecting a batch the plain forward would accept
        M = next(m for m in range(min(pp, B), 0, -1) if B % m == 0)
    else:
        M = num_microbatches
    if M < 1 or B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    Bm = B // M

    x = scale_embed(cfg, embed_lookup(params, tokens))  # [B, T, D]
    D = x.shape[-1]

    # microbatch views
    x_mb = x.reshape(M, Bm, T, D)
    pos_mb = positions.reshape(M, Bm, T)
    slots_mb = slot_mapping.reshape(M, Bm * T)
    tables_mb = block_tables.reshape(M, Bm, -1)
    ctx_mb = context_lens.reshape(M, Bm)
    last_mb = last_token_idx.reshape(M, Bm)

    lp = {k: params[k] for k in layer_param_names(params)}
    base_pp = pp_param_specs(cfg)

    def _lp_spec(k: str) -> P:
        if k.endswith("_scale"):
            # int8 scales: the weight's pp spec with the contraction
            # axis (-2) dropped (models/quant.py scale_spec)
            from dynamo_tpu.models.quant import scale_spec

            return _pp_only(scale_spec(base_pp[k[: -len("_scale")]], -2))
        return _pp_only(base_pp[k])

    lp_specs = {k: _lp_spec(k) for k in lp}

    def stage(lp_local, kc, vc, x_mb, pos_mb, slots_mb, tables_mb, ctx_mb,
              last_mb):
        r = jax.lax.axis_index("pp")
        n_ticks = M + pp - 1
        perm = [(j, (j + 1) % pp) for j in range(pp)]

        def tick(carry, t):
            x_prev, kc, vc, outs = carry
            mb = t - r  # microbatch index this stage works on this tick
            valid = (mb >= 0) & (mb < M)
            i = jnp.clip(mb, 0, M - 1)
            pos = pos_mb[i]
            # bubble ticks write garbage K/V to pad slot 0 (reserved)
            slots = jnp.where(valid, slots_mb[i], 0)
            tables = tables_mb[i]
            ctx = ctx_mb[i]
            # x_mb[i] is varying (indexed by the rank-derived i); stage 0
            # ingests a fresh microbatch, others take the permuted carry
            x_in = jnp.where(r == 0, x_mb[i], x_prev)
            layer_fn = make_layer_fn(cfg, pos, slots, tables, ctx, block_size)
            y, (kc, vc) = jax.lax.scan(layer_fn, x_in, (lp_local, kc, vc))
            # only each sequence's last-token hidden feeds the logits:
            # accumulate [Bm, D] per microbatch, not the full [Bm, T, D]
            y_last = jnp.take_along_axis(
                y, last_mb[i][:, None, None].astype(jnp.int32), axis=1
            )[:, 0]
            # select (not multiply-mask: bubble-tick garbage may be inf/nan)
            # and accumulate in f32 — bf16 psum under partial-auto shard_map
            # trips an XLA crash ("invalid binary opcode copy")
            is_out = valid & (r == pp - 1)
            outs = outs.at[i].set(
                jnp.where(is_out, y_last.astype(jnp.float32), outs[i])
            )
            x_next = jax.lax.ppermute(y, "pp", perm)
            return (x_next, kc, vc, outs), None

        varying = lambda a: jax.lax.pcast(a, ("pp",), to="varying")
        init = (
            varying(jnp.zeros_like(x_mb[0])),
            kc,
            vc,
            varying(jnp.zeros((M, Bm, D), jnp.float32)),
        )
        (x_last, kc, vc, outs), _ = jax.lax.scan(
            tick, init, jnp.arange(n_ticks)
        )
        # outs is zero except on the last stage; psum replicates across pp
        outs = jax.lax.psum(outs, "pp").astype(x_mb.dtype)
        return outs, kc, vc

    outs, new_k, new_v = jax.shard_map(
        stage,
        mesh=mesh,
        in_specs=(
            lp_specs,
            _PP_ONLY_CACHE_SPEC,
            _PP_ONLY_CACHE_SPEC,
            P(),
            P(),
            P(),
            P(),
            P(),
            P(),
        ),
        out_specs=(P(), _PP_ONLY_CACHE_SPEC, _PP_ONLY_CACHE_SPEC),
        axis_names={"pp"},
    )(lp, k_cache, v_cache, x_mb, pos_mb, slots_mb, tables_mb, ctx_mb,
      last_mb)

    x_last = outs.reshape(B, D)
    x_last = rmsnorm(x_last, params["final_norm"], cfg.rms_norm_eps, cfg.norm_bias_one)
    logits = mm(params, "lm_head", x_last).astype(jnp.float32)
    return logits, new_k, new_v
