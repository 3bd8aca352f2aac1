"""The plain reference of ``models/nemotron_h.py``: the whole forward
pass over whole sequences in float32 at
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks: the state-space recurrence runs token by token, attention is a
full causal softmax, the expert layer loops over experts one at a time.
It takes the program's PARAMETERS (any dtype; int8 is dequantized) and
nothing else of the program.

Equations (layers numbered from 0; layer ``i`` is ``x += part_i(norm(x))``
with ``norm(x) = x / sqrt(mean(x^2) + eps) * w`` and ``part_i`` chosen by
letter ``i`` of ``hybrid_override_pattern``; then the final norm and the
untied head):

``M`` (Mamba-2; H heads of P, state N, G groups, inner = H P):
  ``[z | xBC] = x W_in``, ``dt = x W_dt``;
  ``xBC_t <- SiLU(b_c + sum_i w_c[i] xBC_{t-(K-1)+i})`` (zeros before the
  sequence); ``xBC = x (H x P) | B (G x N) | C (G x N)``, head h reads
  group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(
  A_log) dt)``; ``h_t = a_t h_{t-1} + (dt_t x_t) (x) B_t`` from ``h = 0``;
  ``y_t = h_t C_t + D x_t``; ``y <- norm_groups(y * SiLU(z)) * w_n`` (the
  norm over each of G groups of ``inner / G`` channels); ``y W_out``.
``*`` (attention): ``q, k, v = x Wq, x Wk, x Wv``; no rotary embedding;
  causal ``softmax(q k^T / sqrt(Dh)) v`` with ``H / Hk`` query heads a KV
  head; ``o Wo``.
``E`` (experts): ``s = sigmoid(x Wr)``; the k largest of ``s + bias``;
  ``w = s[chosen] / (sum + 1e-20) * routed_scaling_factor`` (the division
  where ``norm_topk_prob``); ``y = sum over the chosen experts THAT ARE
  HELD of w_e relu(x U_e)^2 D_e`` plus ``relu(x U_s)^2 D_s``. ``shared``
  lets a test add the shares of a divided layer up.
``-``: ``relu(x U)^2 D``.

``conv_bias`` / ``skip`` / ``gate`` / ``attn_scale`` switch a piece OFF
(tests: the program must then differ).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from dynamo_tpu.models.reference.kimi_linear import dequantized, rmsnorm


def relu2_mlp(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def ssm_mixer(cfg, w: dict, i: int, x, conv_bias=True, skip=True, gate=True):
    H, P, N, G = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
                  cfg.n_groups)
    K, inner = cfg.conv_kernel, H * P
    B, T, _ = x.shape
    zx = x @ w["m_win"][i]
    z, xbc = zx[..., :inner], zx[..., inner:]
    dt = jax.nn.softplus(x @ w["m_wdt"][i] + w["m_dt_bias"][i])   # [B, T, H]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * w["m_conv"][i][j] for j in range(K))
    if conv_bias:
        conv = conv + w["m_conv_bias"][i]
    conv = jax.nn.silu(conv)
    xs = conv[..., :inner].reshape(B, T, H, P)
    Bm = conv[..., inner: inner + G * N].reshape(B, T, G, N)
    C = conv[..., inner + G * N:].reshape(B, T, G, N)
    Bm, C = (jnp.repeat(a, H // G, axis=2) for a in (Bm, C))    # [B, T, H, N]
    a = jnp.exp(-jnp.exp(w["m_A_log"][i]) * dt)

    def step(h, inp):
        x_t, dt_t, a_t, b_t, c_t = inp
        h = a_t[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (xs, dt, a, Bm, C)))
    y = jnp.moveaxis(y, 0, 1)                                   # [B, T, H, P]
    if skip:
        y = y + w["m_D"][i][:, None] * xs
    y = y.reshape(B, T, inner)
    if gate:
        y = y * jax.nn.silu(z)
    y = y.reshape(B, T, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.rms_norm_eps)
    return (y.reshape(B, T, inner) * w["m_onorm"][i]) @ w["m_wo"][i]


def attn_mixer(cfg, w: dict, i: int, x, attn_scale=True):
    H, Hk, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    B, T, _ = x.shape
    q = (x @ w["attn_wq"][i]).reshape(B, T, H, Dh)
    k = (x @ w["attn_wk"][i]).reshape(B, T, Hk, Dh)
    v = (x @ w["attn_wv"][i]).reshape(B, T, Hk, Dh)
    k, v = (jnp.repeat(a, H // Hk, axis=2) for a in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k)
    if attn_scale:
        s = s / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v)
    return o.reshape(B, T, H * Dh) @ w["attn_wo"][i]


def routing(cfg, w: dict, i: int, x):
    """x [N, D] -> (weights [N, k], expert ids [N, k]) over all experts."""
    s = jax.nn.sigmoid(x @ w["router"][i])
    _, topi = jax.lax.top_k(s + w["router_bias"][i], cfg.num_experts_per_tok)
    wt = jnp.take_along_axis(s, topi, axis=-1)
    if cfg.norm_topk_prob:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return wt * cfg.routed_scaling_factor, topi


def expert_ffn(cfg, w: dict, i: int, x, shared: bool = True):
    """x [B, T, D]: the held experts' part of the routed sum (``w``'s
    expert stacks hold experts ``expert_shard_index * n_routed_experts
    ...``) plus, with ``shared``, the shared expert."""
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    wt, topi = routing(cfg, w, i, xf)
    e0 = cfg.expert_shard_index * cfg.n_routed_experts
    y = jnp.zeros_like(xf)
    for e in range(cfg.n_routed_experts):
        share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
        y = y + share[:, None] * relu2_mlp(xf, w["we_up"][i][e], w["we_down"][i][e])
    if shared:
        y = y + relu2_mlp(xf, w["ws_up"][i], w["ws_down"][i])
    return y.reshape(B, T, D)


def forward(cfg, params: dict, tokens, **switches):
    """tokens [B, T] -> logits [B, T, V] float32, every position.
    ``switches``: ``conv_bias`` / ``skip`` / ``gate`` / ``attn_scale`` =
    False."""
    ssm_sw = {k: v for k, v in switches.items() if k != "attn_scale"}
    attn_sw = {k: v for k, v in switches.items() if k == "attn_scale"}
    with jax.default_matmul_precision("highest"):
        w = dequantized(params)
        x = jnp.take(w["embed"], tokens, axis=0)
        seen = {"M": 0, "*": 0, "E": 0, "-": 0}
        for layer, letter in enumerate(cfg.hybrid_override_pattern):
            h = rmsnorm(x, w["norm"][layer], cfg.rms_norm_eps)
            i = seen[letter]
            seen[letter] += 1
            if letter == "M":
                x = x + ssm_mixer(cfg, w, i, h, **ssm_sw)
            elif letter == "*":
                x = x + attn_mixer(cfg, w, i, h, **attn_sw)
            elif letter == "E":
                x = x + expert_ffn(cfg, w, i, h)
            else:
                x = x + relu2_mlp(h, w["w_up"][i], w["w_down"][i])
        x = rmsnorm(x, w["final_norm"], cfg.rms_norm_eps)
        return x @ w["lm_head"]
