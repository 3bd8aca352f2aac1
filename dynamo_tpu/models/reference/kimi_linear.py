"""The plain reference of ``models/kimi_linear.py``: the whole forward
pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks: the KDA recurrence runs token by token, MLA expands keys and
values from the latent and takes a full causal softmax, the expert layer
loops over experts one at a time. It takes the program's PARAMETERS (any
dtype; int8 is dequantized) and nothing else of the program.

Equations (layers numbered from 1; pre-norm residual blocks, RMSNorm,
no rotary embedding anywhere):

KDA mixer, H heads of size d, kernel K:
  q, k, v = SiLU(conv(x Wq | x Wk | x Wv)), the convolution depthwise and
  causal, y_t = sum_i w[i] * in_{t-(K-1)+i}; q and k L2-normalised per
  head, q scaled by d^-0.5;
  a_t = exp(-exp(A_log_h) * softplus((x Wfa) Wfb + dt_bias))  in (0,1)^{H x d};
  beta_t = sigmoid(x Wb);
  S' = Diag(a_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
  o_t = S_t^T q_t;  out = (RMSNorm_d(o_t) * sigmoid((x Wga) Wgb)) Wo.
MLA mixer: q = x Wq -> [H, nope + rope]; kv = x Wkva -> [rank + rope];
  c = RMSNorm(kv[:rank]); k_r = kv[rank:] (shared by the heads, NOT
  rotated); [k_n | v] = c Wkvb -> [H, nope + v]; k = [k_n | k_r];
  causal softmax(q k^T / sqrt(nope + rope)) v; Wo.
Expert FFN: s = sigmoid(x Wr); top k of s + b; w = s[chosen] / sum * scale;
  y = sum over the chosen experts THAT ARE HELD of w_e E_e(x), plus the
  shared expert; E(x) = Wdown(SiLU(Wgate x) * Wup x). ``held`` and
  ``shared`` let a test add the shares of a divided layer up.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp



def dequantized(params: dict) -> dict:
    """Every parameter in float32 (int8 values x their scales)."""
    out = {}
    for name, w in params.items():
        if name.endswith("_scale"):
            continue
        w = jnp.asarray(w)
        if w.dtype == jnp.int8:
            s = jnp.asarray(params[name + "_scale"], jnp.float32)
            w = (w.astype(jnp.float32) * s[..., None] if name == "embed"
                 else w.astype(jnp.float32) * s[..., None, :])
        out[name] = w.astype(jnp.float32)
    return out


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def kda_mixer(cfg, w: dict, i: int, x):
    """x [B, T, D] -> [B, T, D]; ``w`` float32 parameters, ``i`` the
    layer's index among the KDA layers."""
    la = cfg.linear_attn_config
    H, d, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    B, T, _ = x.shape
    qkv = jnp.concatenate(
        [x @ w["kda_wq"][i], x @ w["kda_wk"][i], x @ w["kda_wv"][i]], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * w["kda_conv"][i][j] for j in range(K))
    q, k, v = (a.reshape(B, T, H, d)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = (x @ w["kda_wfa"][i]) @ w["kda_wfb"][i] + w["kda_dt_bias"][i]
    a = jnp.exp(-jnp.exp(w["kda_A_log"][i])[:, None]
                * jax.nn.softplus(f.reshape(B, T, H, d)))
    beta = jax.nn.sigmoid(x @ w["kda_wb"][i])                  # [B, T, H]

    def step(S, inp):
        q_t, k_t, v_t, a_t, b_t = inp                          # [B, H, .]
        S = a_t[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((B, H, d, d), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta)))
    o = jnp.moveaxis(o, 0, 1)                                  # [B, T, H, d]
    o = rmsnorm(o, w["kda_onorm"][i], cfg.rms_norm_eps)
    gate = jax.nn.sigmoid((x @ w["kda_wga"][i]) @ w["kda_wgb"][i])
    return (o * gate.reshape(B, T, H, d)).reshape(B, T, H * d) @ w["kda_wo"][i]


def mla_mixer(cfg, w: dict, i: int, x):
    H, nope, rope, vd, rank = (
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank)
    B, T, _ = x.shape
    q = (x @ w["mla_wq"][i]).reshape(B, T, H, nope + rope)
    kv = x @ w["mla_wkva"][i]
    c = rmsnorm(kv[..., :rank], w["mla_kvnorm"][i], cfg.rms_norm_eps)
    k_r = jnp.broadcast_to(kv[..., None, rank:], (B, T, H, rope))
    up = (c @ w["mla_wkvb"][i]).reshape(B, T, H, nope + vd)
    k = jnp.concatenate([up[..., :nope], k_r], axis=-1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bhts,bshv->bthv", p, up[..., nope:])
    return o.reshape(B, T, H * vd) @ w["mla_wo"][i]


def routing(cfg, w: dict, i: int, x):
    """x [N, D] -> (weights [N, k], expert ids [N, k]) over all experts."""
    s = jax.nn.sigmoid(x @ w["router"][i])
    _, topi = jax.lax.top_k(s + w["router_bias"][i], cfg.num_experts_per_token)
    wt = jnp.take_along_axis(s, topi, axis=-1)
    if cfg.moe_renormalize:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    return wt * cfg.routed_scaling_factor, topi


def expert_ffn(cfg, w: dict, i: int, x, shared: bool = True):
    """x [B, T, D]: the held experts' part of the routed sum (``w``'s
    expert stacks hold experts ``expert_shard_index * num_experts ...``)
    plus, with ``shared``, the shared expert."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    wt, topi = routing(cfg, w, i, xf)
    e0 = cfg.expert_shard_index * cfg.num_experts
    y = jnp.zeros_like(xf)
    for e in range(cfg.num_experts):
        share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
        y = y + share[:, None] * gated_mlp(
            xf, w["we_gate"][i][e], w["we_up"][i][e], w["we_down"][i][e])
    if shared:
        y = y + gated_mlp(xf, w["ws_gate"][i], w["ws_up"][i], w["ws_down"][i])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens):
    """tokens [B, T] -> logits [B, T, V] float32, every position."""
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        la = cfg.linear_attn_config
        kda = [n - 1 for n in la["kda_layers"]]
        mla = [n - 1 for n in la["full_attn_layers"]]
        x = jnp.take(w["embed"], tokens, axis=0)
        n_dense = 0
        for layer in range(cfg.num_hidden_layers):
            h = rmsnorm(x, w["attn_norm"][layer], cfg.rms_norm_eps)
            if layer in kda:
                x = x + kda_mixer(cfg, w, kda.index(layer), h)
            else:
                x = x + mla_mixer(cfg, w, mla.index(layer), h)
            h = rmsnorm(x, w["mlp_norm"][layer], cfg.rms_norm_eps)
            if layer < cfg.first_k_dense_replace:
                x = x + gated_mlp(h, w["w_gate"][layer], w["w_up"][layer],
                                  w["w_down"][layer])
                n_dense += 1
            else:
                x = x + expert_ffn(cfg, w, layer - n_dense, h)
        x = rmsnorm(x, w["final_norm"], cfg.rms_norm_eps)
        return x @ w["lm_head"]
