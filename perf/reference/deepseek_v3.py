"""The plain reference of the ``deepseek_v3`` family (Kanana-2): the
forward pass in float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no chunks, NOT absorbed: keys and values are
built a head from the latent. It takes NOTHING from the program: the
equations are written out here, and the weights are drawn here from the
seed by the recipe the configuration file states (``assumed``): parameter
``i`` of ``PARAM_ORDER`` has key ``fold_in(PRNGKey(seed), i)``, layer
``j`` of its stack ``fold_in(., j)``, expert ``e`` of a layer
``fold_in(., e)``; ``normal / sqrt(fan_in)`` then symmetric
per-output-channel int8 (per row for the embedding); norms 1;
``e_score_correction_bias`` 0; the router kept float32. The int8 values
and scales are used in float32. The leading dense layer runs first, then
one scan over the expert layers (they are alike: one compiled body),
each drawing its weights inside and its experts one at a time, so at
most one expert's float32 matrices exist. Attention reads the queries
``QUERY_BLOCK`` at a time, so a 15 360-token row's scores are
``[32, 512, 15 360]`` float32 = 1 GB and fit the chip.

Equations (a layer; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``; eps
``rms_norm_eps``; no biases; token at position ``p``):
``q = W_q h`` as H heads of ``[q_nope | q_pe]``; ``[c_kv | k_pe] = W_kva
h``; ``c = RMSNorm(c_kv)``; ``k_r = R_p k_pe`` (one key part for all
heads), ``q_r = R_p q_pe``, ``R_p`` turning the adjacent pairs ``(x_2i,
x_2i+1)`` by ``p * theta^(-2i/rope)`` (HF de-interleaves and then uses
``rotate_half``: the same dot products); ``[k_nope_h | v_h] = W_kvb c``;
``s_h = (q_nope_h . k_nope_h + q_r_h . k_r) / sqrt(nope + rope)``, causal
softmax, ``o_h = sum p v_h``, ``W_o``. Feed-forward: layer 1
``W_down(silu(W_gate h) * W_up h)``; the others ``s = sigmoid(W_r h)``,
top k of ``s + b``, weights ``s_i / (sum + 1e-20) * scale``, experts of
the same gated form, plus the shared MLP (``n_shared_experts`` x the
expert width) added ungated. Final RMSNorm, untied head.

``precision`` selects the CONTROL: ``"a8"`` quantises the input of every
weight matmul per token to 8 bits (bf16 -> int8 activations, the step
below what the configuration states).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "a8")
QUERY_BLOCK = 512

# models/deepseek_v3.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
    "mla_wq", "mla_wkva", "mla_kvnorm", "mla_wkvb", "mla_wo",
    "w_gate", "w_up", "w_down",
    "router", "router_bias", "ws_gate", "ws_up", "ws_down",
    "we_gate", "we_up", "we_down",
)
FLOAT32 = ("router",)   # drawn like a matrix, never quantized


def geometry(cfg: dict) -> dict:
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid")):
        if cfg.get(key, want) != want:
            raise ValueError(f"the deepseek_v3 reference does not build "
                             f"{key} = {cfg.get(key)!r}")
    L = cfg["num_hidden_layers"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dense = list(range(min(cfg["first_k_dense_replace"], L)))
    if dense != [0] or L < 2:
        raise ValueError("the deepseek_v3 reference is written for one "
                         "leading dense layer and expert layers after it")
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        # the kernel readers' shapes: latent attention has one shared
        # cached head of rank + rope values under H query heads
        H=cfg["num_attention_heads"], Hk=1, Dh=rank + rope,
        dense=dense, moe=list(range(1, L)),
        nope=cfg["qk_nope_head_dim"], rope=rope, vd=cfg["v_head_dim"], rank=rank,
        theta=float(cfg["rope_theta"]), interleave=bool(cfg.get("rope_interleave", True)),
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        E=cfg["n_routed_experts"], shards=1, shard=0,
        k=cfg["num_experts_per_tok"], scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["norm_topk_prob"]), eps=float(cfg["rms_norm_eps"]),
    )


def _quantise(w, axis: int):
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def draw(key, name: str, shape: tuple):
    """One leading slice of parameter ``name`` as the configuration
    serves it, in float32."""
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(max(1, fan_in))
    if name in FLOAT32:
        return w
    return _quantise(w, -1 if name == "embed" else -2)


def _act_quant(x, precision: str):
    if precision != "a8":
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rmsnorm(x, eps: float):
    # norm weights are ones in the seeded draw
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotate(x, pos, theta: float, interleave: bool):
    """``x [B, T, ..., d]`` turned at ``pos [T]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos.astype(jnp.float32)[:, None] * inv).reshape(
        1, pos.shape[0], *(1,) * (x.ndim - 3), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def logits_fn(cfg: dict, precision: str = "f32"):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    g = geometry(cfg)
    idx = {n: i for i, n in enumerate(PARAM_ORDER)}
    D, V = g["D"], g["V"]

    def mm(x, w):
        return jnp.dot(_act_quant(x, precision), w)

    def f(key, tokens, lengths, at):
        B, T = tokens.shape
        pos = jnp.arange(T)

        def w(name, j, shape, e=None):
            k = jax.random.fold_in(jax.random.fold_in(key, idx[name]), j)
            if e is not None:
                k = jax.random.fold_in(k, e)
            return draw(k, name, shape)

        def attention(x, j):
            H, nope, rope, vd, rank = (g[n] for n in ("H", "nope", "rope", "vd", "rank"))
            q = mm(x, w("mla_wq", j, (D, H * (nope + rope)))).reshape(
                B, T, H, nope + rope)
            kv = mm(x, w("mla_wkva", j, (D, rank + rope)))
            c = _rmsnorm(kv[..., :rank], g["eps"])
            k_r = rotate(kv[..., rank:], pos, g["theta"], g["interleave"])
            q = jnp.concatenate(
                [q[..., :nope],
                 rotate(q[..., nope:], pos, g["theta"], g["interleave"])], -1)
            up = mm(c, w("mla_wkvb", j, (rank, H * (nope + vd)))).reshape(
                B, T, H, nope + vd)
            k = jnp.concatenate(
                [up[..., :nope], jnp.broadcast_to(k_r[:, :, None], (B, T, H, rope))], -1)
            v = up[..., nope:]
            tq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

            def block(qb_pos):
                qb, pb = qb_pos                               # [B, tq, H, .], [tq]
                s = jnp.einsum("bthd,bshd->bhts", qb, k) / math.sqrt(nope + rope)
                mask = (pos[None, :] <= pb[:, None])[None, None] & (
                    pos[None, None, None, :] < lengths[:, None, None, None])
                p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
                return jnp.einsum("bhts,bshv->bthv", p, v)

            o = jax.lax.map(block, (
                jnp.moveaxis(q.reshape(B, T // tq, tq, H, nope + rope), 1, 0),
                pos.reshape(T // tq, tq)))
            o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * vd)
            return mm(o, w("mla_wo", j, (H * vd, D)))

        def gated(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def experts(x, j):
            E, Fe, Fs = g["E"], g["Fe"], g["Fs"]
            xf = x.reshape(B * T, D)
            s = jax.nn.sigmoid(mm(xf, w("router", j, (D, E))))
            _, topi = jax.lax.top_k(s + w("router_bias", j, (E,)), g["k"])
            wt = jnp.take_along_axis(s, topi, axis=-1)
            if g["renorm"]:
                wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
            wt = wt * g["scale"]

            def one(y, e):
                share = jnp.sum(jnp.where(topi == e, wt, 0.0), axis=-1)
                out = gated(xf, w("we_gate", j, (D, Fe), e),
                            w("we_up", j, (D, Fe), e), w("we_down", j, (Fe, D), e))
                return y + share[:, None] * out, None

            y, _ = jax.lax.scan(one, jnp.zeros_like(xf), jnp.arange(E))
            y = y + gated(xf, w("ws_gate", j, (D, Fs)), w("ws_up", j, (D, Fs)),
                          w("ws_down", j, (Fs, D)))
            return y.reshape(B, T, D)

        def dense(x, j):
            F = g["F"]
            return gated(x, w("w_gate", j, (D, F)), w("w_up", j, (D, F)),
                         w("w_down", j, (F, D)))

        def expert_layer(x, layer):
            x = x + attention(_rmsnorm(x, g["eps"]), layer)
            # the expert stacks start at the first expert layer
            x = x + experts(_rmsnorm(x, g["eps"]), layer - len(g["dense"]))
            return x, None

        embed = draw(jax.random.fold_in(key, idx["embed"]), "embed", (V, D))
        x = jnp.take(embed, tokens, axis=0)
        x = x + attention(_rmsnorm(x, g["eps"]), 0)
        x = x + dense(_rmsnorm(x, g["eps"]), 0)
        x, _ = jax.lax.scan(expert_layer, x, jnp.asarray(g["moe"], jnp.int32))
        x = _rmsnorm(x, g["eps"])
        x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)
        head = draw(jax.random.fold_in(key, idx["lm_head"]), "lm_head", (D, V))
        return mm(x_at, head)

    jitted = jax.jit(f)

    def run(seed: int, tokens, lengths, at):
        with jax.default_matmul_precision("highest"):
            return jitted(jax.random.PRNGKey(seed), tokens, lengths, at)

    return run
