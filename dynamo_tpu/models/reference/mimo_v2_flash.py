"""The plain reference of ``models/mimo_v2_flash.py``: the whole forward
pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
pages, no chunks: masks are built from positions, the sink is an
explicit extra column of the softmax, the expert layer loops over the
held experts one at a time. It takes the program's PARAMETERS (any
dtype; int8 is dequantized) and nothing else of the program.

Equations (layer ``l``; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``;
RMSNorm eps ``layernorm_epsilon``; no biases; token at position ``p``;
``kind(l)`` full where ``hybrid_layer_pattern[l]`` is 0, window where 1):

  q = W_q h as H heads of head_dim;  k = W_k h as Hk heads of head_dim;
  v = attention_value_scale * (W_v h) as Hk heads of v_head_dim, Hk =
  num_key_value_heads (full) / swa_num_key_value_heads (window).
  Rotary on the first int(head_dim * partial_rotary_factor) values of
  every q and k head, the half-split pairs (i, i + rot/2) turned by
  p * theta^(-2i/rot), theta = rope_theta (full) / swa_rope_theta
  (window); the other values pass.
  s_hj = q_h . k_g(h),j / sqrt(head_dim), g(h) = h // (H / Hk), over keys
  j <= p (full) or p - sliding_window < j <= p (window). A window layer
  holds one learned sink b_h a query head (add_swa_attention_sink_bias):
  P_hj = exp(s_hj - m) / (exp(b_h - m) + sum_j' exp(s_hj' - m)) — the
  sink takes probability and adds no value. o_h = sum_j P_hj v_g(h),j;
  x += W_o [o_1 .. o_H].
  Feed-forward: where moe_layer_freq[l] is 0, W_down(silu(W_gate h) *
  W_up h); else s = sigmoid(W_r h) over ALL n_routed_experts *
  expert_shards experts, the top k of s + b, weights s_i / (sum of the k
  + 1e-20) (norm_topk_prob), times routed_scaling_factor (null: 1), over
  gated SiLU experts — of which only the HELD ones (expert_shard_index's
  run of n_routed_experts) are summed: the same expert share as the
  program, what the other shards' experts add is left out in both.
  Final RMSNorm, untied head.

Departures from the published modelling code (to this repo's knowledge of
it; each is pinned by a test of ``tests/test_mimo_v2_flash_model.py``):
- the rotated values LEAD the head and are turned by ``rotate_half``
  (pairs (i, i + rot/2)) — written out here, not imported;
- the sink enters as one more column of the softmax that is then dropped
  (gpt-oss's form); no q / k normalisation;
- ``attention_chunk_size`` / ``sliding_window_size`` repeat the window
  and change nothing; ``n_group`` 1 / ``topk_group`` 1 make the
  ``noaux_tc`` grouped choice the plain top k;
- the published code computes in the checkpoint's bfloat16 / fp8 with a
  float32 softmax and router; this is float32 throughout;
- the multi-token-prediction layers are not among the config's keys and
  are not built.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from dynamo_tpu.models.reference.kimi_linear import (
    dequantized,
    gated_mlp,
    rmsnorm,
)


def layer_kinds(cfg) -> list[tuple[str, int]]:
    """(``"full"`` | ``"win"``, index among its kind) of every layer."""
    seen = {"full": 0, "win": 0}
    out = []
    for flag in cfg.hybrid_layer_pattern:
        kind = "win" if flag else "full"
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def rotate_leading(x, positions, theta: float, rot: int):
    """``x [B, T, heads, d]``: the first ``rot`` values of each head turned
    at ``positions [B, T]`` — pairs (i, i + rot/2) by p * theta^(-2i/rot)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def attention_heads(cfg, w: dict, kind: str, i: int, x, positions=None,
                    query_block: int = 0):
    """The heads' outputs ``o [B, T, H, v_head_dim]`` of layer ``i`` of
    ``kind`` before ``W_o``. ``query_block`` > 0 attends that many
    queries at a time (a long row's [H, T, T] scores do not fit at once);
    the arithmetic of each query is the same."""
    H, Dk, Dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    window = cfg.sliding_window if kind == "win" else None
    Hk = (cfg.swa_num_key_value_heads if kind == "win"
          else cfg.num_key_value_heads)
    theta = float(cfg.swa_rope_theta if kind == "win" else cfg.rope_theta)
    rot = int(Dk * cfg.partial_rotary_factor)
    vscale = (1.0 if cfg.attention_value_scale is None
              else cfg.attention_value_scale)
    B, T, _ = x.shape
    pos = (jnp.broadcast_to(jnp.arange(T)[None], (B, T))
           if positions is None else positions)
    q = (x @ w[f"{kind}_wq"][i]).reshape(B, T, H, Dk)
    k = (x @ w[f"{kind}_wk"][i]).reshape(B, T, Hk, Dk)
    v = vscale * (x @ w[f"{kind}_wv"][i]).reshape(B, T, Hk, Dv)
    q = rotate_leading(q, pos, theta, rot)
    k = rotate_leading(k, pos, theta, rot)
    # head h reads KV head h // (H / Hk)
    k = jnp.repeat(k, H // Hk, axis=2)
    v = jnp.repeat(v, H // Hk, axis=2)
    sink = w.get(f"{kind}_sink")
    out = []
    step = query_block or T
    for t0 in range(0, T, step):
        qb, pq = q[:, t0:t0 + step], pos[:, t0:t0 + step]
        s = jnp.einsum("bthd,bshd->bhts", qb, k) / math.sqrt(Dk)
        seen = pos[:, None, :] <= pq[:, :, None]                # [B, t, S]
        if window is not None:
            seen &= pos[:, None, :] > pq[:, :, None] - window
        s = jnp.where(seen[:, None], s, -1e30)
        if sink is not None:
            col = jnp.broadcast_to(sink[i][None, :, None, None],
                                   s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bhts,bshv->bthv", p, v))
    return jnp.concatenate(out, axis=1)


def attention(cfg, w: dict, kind: str, i: int, x, query_block: int = 0):
    B, T, _ = x.shape
    heads = attention_heads(cfg, w, kind, i, x, query_block=query_block)
    return heads.reshape(B, T, -1) @ w[f"{kind}_wo"][i]


def routing(cfg, w: dict, i: int, x):
    """x [N, D] -> (weights [N, k], expert ids [N, k]) over ALL experts."""
    s = jax.nn.sigmoid(x @ w["router"][i])
    _, topi = jax.lax.top_k(s + w["router_bias"][i], cfg.num_experts_per_tok)
    wt = jnp.take_along_axis(s, topi, axis=-1)
    if cfg.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    scale = cfg.routed_scaling_factor
    return wt * (1.0 if scale is None else scale), topi


def expert_ffn(cfg, w: dict, i: int, x):
    """The held experts' share of the routed sum (no shared expert)."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    wt, topi = routing(cfg, w, i, xf)
    e0 = cfg.expert_shard_index * cfg.n_routed_experts
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_routed_experts):
        share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
        y = y + share[:, None] * gated_mlp(
            xf, w["we_gate"][i][e], w["we_up"][i][e], w["we_down"][i][e])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens, query_block: int = 0):
    """tokens [B, T] -> logits [B, T, V] float32, every position."""
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        x = jnp.take(w["embed"], tokens, axis=0)
        dense = moe = 0
        for layer, (kind, ai) in enumerate(layer_kinds(cfg)):
            h = rmsnorm(x, w["attn_norm"][layer], cfg.rms_norm_eps)
            x = x + attention(cfg, w, kind, ai, h, query_block)
            h = rmsnorm(x, w["mlp_norm"][layer], cfg.rms_norm_eps)
            if cfg.moe_layer_freq[layer]:
                x = x + expert_ffn(cfg, w, moe, h)
                moe += 1
            else:
                x = x + gated_mlp(h, w["w_gate"][dense], w["w_up"][dense],
                                  w["w_down"][dense])
                dense += 1
        x = rmsnorm(x, w["final_norm"], cfg.rms_norm_eps)
        return x @ w["lm_head"]
