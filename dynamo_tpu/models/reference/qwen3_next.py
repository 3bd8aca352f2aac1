"""The plain reference of ``models/qwen3_next.py``: the whole forward
pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks: the delta rule runs token by token, attention is a full causal
softmax, the expert block loops over experts one at a time. It takes the
program's PARAMETERS (any dtype; int8 is dequantized) and nothing else
of the program.

Equations (layers numbered from 0; ``x += Mixer(norm(x)); x +=
Experts(norm(x))``; ``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` for
the layer norms, the final norm and the q / k norms):

Gated attention (layers with ``(i + 1) % full_attention_interval == 0``):
  ``[q | gate] = x Wq`` a head (``Dh`` each), ``k = x Wk``, ``v = x Wv``;
  ``q, k = norm(q), norm(k)`` over each head; the first ``rot =
  partial_rotary_factor * Dh`` dims of q and k rotated (half-rotation
  form: pairs ``(j, j + rot/2)``, frequency ``theta^(-2j/rot)``), the
  others pass; causal ``softmax(q k^T / sqrt(Dh)) v`` with ``H / Hk``
  query heads a KV head; ``(attn * sigmoid(gate)) Wo``.
Gated DeltaNet (the other layers; Hk key heads, Hv value heads of d):
  ``[q | k | v | z] = x Wqkvz``, ``[b | a] = x Wba``;
  ``q, k, v = SiLU(conv([q | k | v]))``, the convolution depthwise and
  causal, ``y_t = sum_i w[i] in_{t-(K-1)+i}``; q, k L2-normalised a head
  (eps 1e-6 inside the root), ``q *= d^-0.5``, both repeated so that key
  head j serves value heads ``j Hv/Hk ...``;
  ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``;
  ``S <- exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t u^T``;
  ``o_t = S^T q_t``; out = ``(w o / sqrt(mean(o^2) + eps) * SiLU(z)) Wo``.
Expert block: ``p = softmax(x Wr)`` over all experts; the k largest,
  renormalised (``norm_topk_prob``); ``y = sum over the chosen experts
  THAT ARE HELD of w_e E_e(x)`` plus ``sigmoid(x . w_sg) E_shared(x)``;
  ``E(x) = Wdown(SiLU(Wgate x) * Wup x)``. ``shared`` lets a test add the
  shares of a divided layer up.

``rotary`` / ``qk_norm`` / ``out_gate`` switch a piece OFF (tests: the
program must then differ).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from dynamo_tpu.models.reference.kimi_linear import dequantized, gated_mlp


def norm1(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotate(x, positions, theta: float, rot: int):
    """x [B, T, H, Dh]: the first ``rot`` dims rotated, pairs (j, j + rot/2)."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attn_mixer(cfg, w: dict, i: int, x, rotary=True, qk_norm=True, out_gate=True):
    H, Hk, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    B, T, _ = x.shape
    qg = (x @ w["attn_wq"][i]).reshape(B, T, H, 2 * Dh)
    q, gate = qg[..., :Dh], qg[..., Dh:]
    k = (x @ w["attn_wk"][i]).reshape(B, T, Hk, Dh)
    v = (x @ w["attn_wv"][i]).reshape(B, T, Hk, Dh)
    if qk_norm:
        q = norm1(q, w["attn_qnorm"][i], cfg.rms_norm_eps)
        k = norm1(k, w["attn_knorm"][i], cfg.rms_norm_eps)
    if rotary:
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        rot = int(Dh * cfg.partial_rotary_factor)
        q = rotate(q, pos, cfg.rope_theta, rot)
        k = rotate(k, pos, cfg.rope_theta, rot)
    k, v = (jnp.repeat(a, H // Hk, axis=2) for a in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v)
    if out_gate:
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(B, T, H * Dh) @ w["attn_wo"][i]


def gdn_mixer(cfg, w: dict, i: int, x):
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    d, K = cfg.linear_key_head_dim, cfg.linear_conv_kernel_dim
    B, T, _ = x.shape
    qkvz = x @ w["gdn_wqkvz"][i]
    ba = x @ w["gdn_wba"][i]
    qkv, z = qkvz[..., : 2 * Hk * d + Hv * d], qkvz[..., 2 * Hk * d + Hv * d:]
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    conv = jax.nn.silu(sum(padded[:, j:j + T] * w["gdn_conv"][i][j] for j in range(K)))
    q = conv[..., : Hk * d].reshape(B, T, Hk, d)
    k = conv[..., Hk * d: 2 * Hk * d].reshape(B, T, Hk, d)
    v = conv[..., 2 * Hk * d:].reshape(B, T, Hv, d)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :Hv])                        # [B, T, Hv]
    decay = jnp.exp(-jnp.exp(w["gdn_A_log"][i])
                    * jax.nn.softplus(ba[..., Hv:] + w["gdn_dt_bias"][i]))

    def step(S, inp):
        q_t, k_t, v_t, a_t, b_t = inp                          # [B, Hv, .]
        S = a_t[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((B, Hv, d, d), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, decay, beta)))
    o = jnp.moveaxis(o, 0, 1)                                  # [B, T, Hv, d]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps) \
        * w["gdn_onorm"][i]
    o = o * jax.nn.silu(z.reshape(B, T, Hv, d))
    return o.reshape(B, T, Hv * d) @ w["gdn_wo"][i]


def routing(cfg, w: dict, i: int, x):
    """x [N, D] -> (weights [N, k], expert ids [N, k]) over all experts."""
    s = jax.nn.softmax(x @ w["router"][i], axis=-1)
    wt, topi = jax.lax.top_k(s, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    return wt, topi


def expert_ffn(cfg, w: dict, i: int, x, shared: bool = True):
    """x [B, T, D]: the held experts' part of the routed sum (``w``'s
    expert stacks hold experts ``expert_shard_index * num_experts ...``)
    plus, with ``shared``, the gated shared expert."""
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
        y = y + jax.nn.sigmoid(xf @ w["shared_gate"][i]) * gated_mlp(
            xf, w["ws_gate"][i], w["ws_up"][i], w["ws_down"][i])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens, **switches):
    """tokens [B, T] -> logits [B, T, V] float32, every position.
    ``switches``: ``rotary`` / ``qk_norm`` / ``out_gate`` = False."""
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        every = cfg.full_attention_interval
        x = jnp.take(w["embed"], tokens, axis=0)
        n_attn = n_gdn = 0
        for layer in range(cfg.num_hidden_layers):
            h = norm1(x, w["attn_norm"][layer], cfg.rms_norm_eps)
            if (layer + 1) % every == 0:
                x = x + attn_mixer(cfg, w, n_attn, h, **switches)
                n_attn += 1
            else:
                x = x + gdn_mixer(cfg, w, n_gdn, h)
                n_gdn += 1
            h = norm1(x, w["mlp_norm"][layer], cfg.rms_norm_eps)
            x = x + expert_ffn(cfg, w, layer, h)
        x = norm1(x, w["final_norm"], cfg.rms_norm_eps)
        return x @ w["lm_head"]
