"""The plain reference of ``models/glm_moe_dsa.py`` (GLM-5): the whole
forward pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks, NOT absorbed: keys and values are UP-PROJECTED a head from the
latent, the index score is the ``[T, T]`` matrix as written, ``S_p`` comes
from a full stable sort of it, the softmax runs over an explicit ``-inf``
mask built from ``S_p``, and the expert layer loops over the held experts
one at a time. It takes the program's PARAMETERS (any dtype; int8 is
dequantized) and nothing else of the program.

Equations (a layer; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``;
RMSNorm eps ``rms_norm_eps``; no biases but the LayerNorm's; token at
position ``p``; H heads):

  c_q = RMSNorm(W_qa h; w_qnorm);  q = W_qb c_q as H heads of [q_n | q_r]
  [c' | k_r] = W_kva h;  c = RMSNorm(c'; w_kvnorm);  [k_n,h | v_h] = W_kvb c
  s_hj = (q_n,h . k_n,h,j + R_p q_r,h . R_j k_r,j) / sqrt(nope + rope),
  R_p turning the adjacent pairs (x_2i, x_2i+1) by p * theta^(-2i/rope)
  (``rope_interleave``; half-split pairs otherwise)

  the indexer:  q^I = W^I_qb c_q as G heads of d;  k^I = LayerNorm(W^I_k h;
  weight, bias, eps 1e-6);  the FIRST ``rope`` values of every q^I head
  and of k^I turned by the token's position (pairs by
  ``indexer_rope_interleave``);  w = W^I_w h
  I_pj = sum_g w_pg ReLU(q^I_pg . k^I_j),  j <= p
  S_p = the min(index_topk, p + 1) keys j <= p of largest I_pj, ties to
  the lower j;  P_hj = softmax over j in S_p of s_hj, 0 elsewhere;
  o_h = sum_j P_hj v_h,j;  x += W_o [o_1 .. o_H]

  feed-forward: ``models/reference/deepseek_v3.py``'s, with the expert
  SHARE of the configuration: the router scores ``n_routed_experts *
  expert_shards`` experts and takes its top k among all of them; only the
  held run (``expert_shard_index``) is summed, the shared MLP once; what
  the other shards' experts would add is left out, as in the program.

Departures from the published DSA inference code, each without effect on
what is compared or stated here as a precision:
- the published index score is multiplied by ``G^-1/2 * d^-1/2``: a
  positive constant, the order of the keys and so ``S_p`` is unchanged;
  dropped, in the program too;
- the published code turns ``q^I`` and ``k^I`` by a Hadamard matrix
  before rounding them to fp8: orthogonal, every dot product is the same
  in exact arithmetic; not built. fp8 is a precision, not an equation:
  the program keeps ``k^I`` pages in bfloat16, this reference in float32;
- rotary: HF de-interleaves the pairs and then uses ``rotate_half``; here
  they are turned where they stand — q and k alike, the same dot products;
- ``num_nextn_predict_layers`` (the multi-token-prediction layer) is not
  built: the main model's logits do not depend on it;
- ``n_group`` 1 / ``topk_group`` 1: the group-limited choice is the plain
  top k written here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from dynamo_tpu.models.reference.deepseek_v3 import rotate, routing
from dynamo_tpu.models.reference.kimi_linear import (
    dequantized,
    gated_mlp,
    rmsnorm,
)

INDEX_NORM_EPS = 1e-6


def layernorm(x, weight, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def index_scores(cfg, w: dict, i: int, h, c_q):
    """``I [B, T, T]`` of layer ``i``, ``-inf`` above the diagonal."""
    B, T, _ = h.shape
    G, d, n = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    theta, inter = float(cfg.rope_theta), bool(cfg.indexer_rope_interleave)

    def turned(x):
        return jnp.concatenate(
            [rotate(x[..., :n], pos, theta, inter), x[..., n:]], -1)

    q = turned((c_q @ w["idx_wq"][i]).reshape(B, T, G, d))
    k = turned(layernorm(h @ w["idx_wk"][i], w["idx_knorm"][i],
                         w["idx_kbias"][i], INDEX_NORM_EPS))
    weights = h @ w["idx_ww"][i]                                   # [B, T, G]
    dots = jnp.einsum("btgd,bsd->btgs", q, k)
    score = jnp.sum(weights[..., None] * jax.nn.relu(dots), axis=2)
    causal = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(causal, score, -jnp.inf)


def selected(cfg, score):
    """``S_p`` as a mask [B, T, T]: by a full stable sort, the first
    ``index_topk`` keys in descending score (ties: the lower index), of
    which only keys ``j <= p`` count."""
    B, T, _ = score.shape
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :cfg.index_topk]
    picked = jnp.zeros((B, T, T), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None], order].set(True)
    return picked & jnp.tril(jnp.ones((T, T), bool))


def attention(cfg, w: dict, i: int, h, dense: bool = False,
              return_selected: bool = False):
    """``dense``: the indexer taken out (every key ``j <= p`` attended)."""
    H, nope, rope, vd, rank = (
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank)
    B, T, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    theta, inter = float(cfg.rope_theta), bool(cfg.rope_interleave)
    c_q = rmsnorm(h @ w["mla_wqa"][i], w["mla_qnorm"][i], cfg.rms_norm_eps)
    q = (c_q @ w["mla_wqb"][i]).reshape(B, T, H, nope + rope)
    kv = h @ w["mla_wkva"][i]
    c = rmsnorm(kv[..., :rank], w["mla_kvnorm"][i], cfg.rms_norm_eps)
    k_r = rotate(kv[..., rank:], pos, theta, inter)
    q_r = rotate(q[..., nope:], pos, theta, inter)
    up = (c @ w["mla_wkvb"][i]).reshape(B, T, H, nope + vd)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_r[:, :, None], (B, T, H, rope))], -1)
    qf = jnp.concatenate([q[..., :nope], q_r], -1)
    s = jnp.einsum("bthd,bshd->bhts", qf, k) / math.sqrt(nope + rope)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    if not dense:
        mask = selected(cfg, index_scores(cfg, w, i, h, c_q))
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshv->bthv", p, up[..., nope:])
    out = o.reshape(B, T, H * vd) @ w["mla_wo"][i]
    return (out, mask) if return_selected else out


def expert_ffn(cfg, w: dict, i: int, x):
    """The held experts' share of the routed sum plus the shared MLP."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    wt, topi = routing(cfg, w, i, xf)
    e0 = cfg.expert_shard_index * cfg.n_routed_experts
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_routed_experts):
        share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
        y = y + share[:, None] * gated_mlp(
            xf, w["we_gate"][i][e], w["we_up"][i][e], w["we_down"][i][e])
    y = y + gated_mlp(xf, w["ws_gate"][i], w["ws_up"][i], w["ws_down"][i])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens, dense: bool = False,
            return_selected: bool = False):
    """tokens [B, T] -> logits [B, T, V] float32, every position (and,
    where asked, the layers' selections [L, B, T, T])."""
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        x = jnp.take(w["embed"], tokens, axis=0)
        masks = []
        for layer in range(cfg.num_hidden_layers):
            h = rmsnorm(x, w["attn_norm"][layer], cfg.rms_norm_eps)
            out, mask = attention(cfg, w, layer, h, dense, True)
            masks.append(mask)
            x = x + out
            h = rmsnorm(x, w["mlp_norm"][layer], cfg.rms_norm_eps)
            if layer < cfg.first_k_dense_replace:
                x = x + gated_mlp(h, w["w_gate"][layer], w["w_up"][layer],
                                  w["w_down"][layer])
            else:
                x = x + expert_ffn(cfg, w, layer - cfg.first_k_dense_replace, h)
        x = rmsnorm(x, w["final_norm"], cfg.rms_norm_eps)
        logits = x @ w["lm_head"]
        return (logits, jnp.stack(masks)) if return_selected else logits
