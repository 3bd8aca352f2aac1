"""The plain reference of ``models/deepseek_v3.py``: the whole forward
pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks, NOT absorbed: it builds ``k_h`` and ``v_h`` a head from the
latent and takes a full causal softmax; the expert layer loops over the
experts one at a time. It takes the program's PARAMETERS (any dtype;
int8 is dequantized) and nothing else of the program.

Equations (a layer; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``;
RMSNorm eps ``rms_norm_eps``; no biases; token ``t`` at position ``p``):

  q = W_q h as H heads of [q_nope | q_pe];  [c_kv | k_pe] = W_kva h;
  c = RMSNorm(c_kv; w_kvnorm);  k_r = R_p k_pe (ONE key part shared by
  all heads),  q_r = R_p q_pe, where R_p rotates the adjacent pairs
  (x_2i, x_2i+1) by p * theta^(-2i/rope);  [k_nope_h | v_h] = W_kvb c;
  s_h = (q_nope_h . k_nope_h + q_r_h . k_r) / sqrt(nope + rope), causal
  softmax, o_h = sum p v_h, x += W_o [o_1 .. o_H].
  Feed-forward: the first ``first_k_dense_replace`` layers
  W_down(silu(W_gate h) * W_up h); the others s = sigmoid(W_r h), top k
  of s + b, weights s_i / (sum of the k + 1e-20) * routed_scaling_factor
  over experts of the same gated form, plus the shared MLP (width
  ``n_shared_experts * moe_intermediate_size``) added ungated.
  Final RMSNorm, untied head.

Departures from the published modelling code (HF ``modeling_deepseek_v3``):
- rotary: HF's ``apply_rotary_pos_emb_interleave`` first moves the pairs
  (x_2i, x_2i+1) to (i, i + rope/2) and then applies ``rotate_half``;
  here the pairs are turned where they stand. q and k are permuted alike,
  so every dot product — all attention reads of them — is the same;
- ``n_group`` 1 / ``topk_group`` 1: HF's group-limited choice (mask all
  but the best ``topk_group`` groups, then top k) is then the plain top k
  written here; ``rope_scaling`` null: no YaRN factor on the softmax scale;
- HF computes in the checkpoint's bfloat16 with float32 softmax and
  router; this is float32 throughout;
- ``rope_interleave`` false (not Kanana-2's) takes the half-split pairs.
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


def rotate(x, positions, theta: float, interleave: bool):
    """``x [B, T, ..., d]`` rotated at ``positions [B, T]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = ang.reshape(*positions.shape, *(1,) * (x.ndim - 3), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention_heads(cfg, w: dict, i: int, x):
    """The heads' outputs ``o [B, T, H, v]`` of layer ``i`` before
    ``W_o`` (a test compares the program's absorbed decode with them)."""
    H, nope, rope, vd, rank = (
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank)
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    theta, inter = float(cfg.rope_theta), bool(cfg.rope_interleave)
    q = (x @ w["mla_wq"][i]).reshape(B, T, H, nope + rope)
    kv = x @ w["mla_wkva"][i]
    c = rmsnorm(kv[..., :rank], w["mla_kvnorm"][i], cfg.rms_norm_eps)
    k_r = rotate(kv[..., rank:], pos, theta, inter)            # [B, T, rope]
    q_r = rotate(q[..., nope:], pos, theta, inter)             # [B, T, H, rope]
    up = (c @ w["mla_wkvb"][i]).reshape(B, T, H, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_r[:, :, None], (B, T, H, rope))], -1)
    qf = jnp.concatenate([q[..., :nope], q_r], -1)
    s = jnp.einsum("bthd,bshd->bhts", qf, k) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    return jnp.einsum("bhts,bshv->bthv", p, up[..., nope:])


def attention(cfg, w: dict, i: int, x):
    B, T, _ = x.shape
    return attention_heads(cfg, w, i, x).reshape(B, T, -1) @ w["mla_wo"][i]


def routing(cfg, w: dict, i: int, x):
    """x [N, D] -> (weights [N, k], expert ids [N, k])."""
    s = jax.nn.sigmoid(x @ w["router"][i])
    _, topi = jax.lax.top_k(s + w["router_bias"][i], cfg.num_experts_per_tok)
    wt = jnp.take_along_axis(s, topi, axis=-1)
    if cfg.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return wt * cfg.routed_scaling_factor, topi


def expert_ffn(cfg, w: dict, i: int, x):
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    wt, topi = routing(cfg, w, i, xf)
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_routed_experts):
        share = jnp.sum(jnp.where(topi == e, wt, 0.0), axis=-1)
        y = y + share[:, None] * gated_mlp(
            xf, w["we_gate"][i][e], w["we_up"][i][e], w["we_down"][i][e])
    y = y + gated_mlp(xf, w["ws_gate"][i], w["ws_up"][i], w["ws_down"][i])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens):
    """tokens [B, T] -> logits [B, T, V] float32, every position."""
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        x = jnp.take(w["embed"], tokens, axis=0)
        for layer in range(cfg.num_hidden_layers):
            h = rmsnorm(x, w["attn_norm"][layer], cfg.rms_norm_eps)
            x = x + attention(cfg, w, layer, h)
            h = rmsnorm(x, w["mlp_norm"][layer], cfg.rms_norm_eps)
            if layer < cfg.first_k_dense_replace:
                x = x + gated_mlp(h, w["w_gate"][layer], w["w_up"][layer],
                                  w["w_down"][layer])
            else:
                x = x + expert_ffn(cfg, w, layer - cfg.first_k_dense_replace, h)
        x = rmsnorm(x, w["final_norm"], cfg.rms_norm_eps)
        return x @ w["lm_head"]
