"""The plain reference of the ``kimi_linear`` family: the forward pass in
float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no chunks. It takes NOTHING from the program:
the equations are written out here, and the weights are drawn here from
the seed by the recipe the configuration file states (``assumed``):
parameter ``i`` of ``PARAM_ORDER`` has key ``fold_in(PRNGKey(seed), i)``,
layer ``j`` of its stack ``fold_in(., j)``, expert ``e`` of a layer
``fold_in(., e)``; ``normal / sqrt(fan_in)`` then symmetric
per-output-channel int8 (per row for the embedding); norms 1; selection
bias 0; ``A_log = log(U(1, 16))``; ``dt_bias`` the inverse softplus of a
log-uniform step in [1e-3, 1e-1]; convolution kernels and the router kept
float32. The int8 values and scales are used in float32. Weights are
drawn layer by layer inside one scan over the layers (a layer's kinds
choose its branches), and an expert layer's experts one at a time, so at
most one expert's float32 matrices exist and each kind compiles once.

The configuration holds a SHARE of the published model (its file says
which): ``num_experts`` experts of ``num_experts * expert_shards`` that
the router scores, the ``expert_shard_index``-th run of them; what the
absent experts would add is left out, here as in the program.

Equations (layers numbered from 1; ``x += Mixer(RMSNorm(x)); x +=
FFN(RMSNorm(x))``; final RMSNorm; untied head; no rotary embedding):

KDA (layers of ``linear_attn_config.kda_layers``; H heads of size d,
kernel K): ``q, k, v = SiLU(conv(x Wq | x Wk | x Wv))``, the convolution
depthwise and causal, ``y_t = sum_i w[i] in_{t-(K-1)+i}``; q, k
L2-normalised per head, q scaled by d^-0.5;
``a_t = exp(-exp(A_log_h) softplus((x Wfa) Wfb + dt_bias))``;
``beta_t = sigmoid(x Wb)``; ``S' = Diag(a_t) S_{t-1}``;
``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``; output
``(RMSNorm_d(o_t) * sigmoid((x Wga) Wgb)) Wo`` — token by token.
MLA (``full_attn_layers``): ``q = x Wq -> [H, nope + rope]``;
``kv = x Wkva``; ``c = RMSNorm(kv[:rank])``; ``k_r = kv[rank:]`` (one per
token, shared by the heads, not rotated); ``[k_n | v] = c Wkvb``; full
causal softmax of ``q [k_n | k_r]^T / sqrt(nope + rope)``; ``Wo``.
Feed-forward: dense SiLU-gated in the first ``first_k_dense_replace``
layers; else ``s = sigmoid(x Wr)``, top k of ``s + b``, ``w = s[chosen]``
renormalised and scaled, ``y = sum_e w_e E_e(x)`` over the chosen experts
held here ``+ E_shared(x)``.

``precision`` selects the CONTROL: ``"a8"`` quantises the input of every
weight matmul per token to 8 bits (bf16 -> int8 activations, the step
below what the configuration states).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "a8")
LOW_RANK = 128

# models/kimi_linear.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
    "kda_wq", "kda_wk", "kda_wv", "kda_conv", "kda_wfa", "kda_wfb",
    "kda_A_log", "kda_dt_bias", "kda_wb", "kda_wga", "kda_wgb", "kda_onorm",
    "kda_wo",
    "mla_wq", "mla_wkva", "mla_kvnorm", "mla_wkvb", "mla_wo",
    "w_gate", "w_up", "w_down",
    "router", "router_bias", "ws_gate", "ws_up", "ws_down",
    "we_gate", "we_up", "we_down",
)
FLOAT32 = ("kda_conv", "router")   # drawn like a matrix, never quantized


def geometry(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    L = cfg["num_hidden_layers"]
    kda = [i - 1 for i in la["kda_layers"]]
    mla = [i - 1 for i in la["full_attn_layers"]]
    if sorted(kda + mla) != list(range(L)):
        raise ValueError("linear_attn_config does not cover the layers")
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dense = list(range(min(cfg["first_k_dense_replace"], L)))
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        # the kernel readers' shapes: latent attention has one shared
        # cached head of rank + rope values under H query heads
        H=cfg["num_attention_heads"], Hk=1, Dh=rank + rope,
        kda=kda, mla=mla, dense=dense,
        moe=[i for i in range(L) if i not in dense],
        Hl=la["num_heads"], dl=la["head_dim"], K=la["short_conv_kernel_size"],
        nope=cfg["qk_nope_head_dim"], rope=rope, vd=cfg["v_head_dim"], rank=rank,
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        E=cfg["num_experts"], shards=cfg.get("expert_shards", 1),
        shard=cfg.get("expert_shard_index", 0),
        k=cfg["num_experts_per_token"], scale=float(cfg["routed_scaling_factor"]),
        renorm=bool(cfg["moe_renormalize"]), eps=float(cfg["rms_norm_eps"]),
    )


def param_index(g: dict) -> dict[str, int]:
    have = {"kda_": bool(g["kda"]), "mla_": bool(g["mla"]),
            "w_": bool(g["dense"]), "moe": bool(g["moe"])}

    def present(name: str) -> bool:
        if name.startswith(("kda_", "mla_")):
            return have[name[:4]]
        if name in ("w_gate", "w_up", "w_down"):
            return have["w_"]
        if name.startswith(("router", "ws_", "we_")):
            return have["moe"]
        return True

    return {n: i for i, n in enumerate(n for n in PARAM_ORDER if present(n))}


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
    if name == "kda_A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "kda_dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
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


def logits_fn(cfg: dict, precision: str = "f32"):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    g = geometry(cfg)
    idx = param_index(g)
    D, V = g["D"], g["V"]

    def mm(x, w):
        return jnp.dot(_act_quant(x, precision), w)

    def f(key, tokens, lengths, at):
        B, T = tokens.shape

        def w(name, j, shape, e=None):
            k = jax.random.fold_in(jax.random.fold_in(key, idx[name]), j)
            if e is not None:
                k = jax.random.fold_in(k, e)
            return draw(k, name, shape)

        def kda(x, j):
            H, d, K, HD = g["Hl"], g["dl"], g["K"], g["Hl"] * g["dl"]
            qkv = jnp.concatenate(
                [mm(x, w(n, j, (D, HD))) for n in ("kda_wq", "kda_wk", "kda_wv")], -1)
            padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
            cw = w("kda_conv", j, (K, 3 * HD))
            conv = sum(padded[:, i:i + T] * cw[i] for i in range(K))
            q, k, v = (a.reshape(B, T, H, d)
                       for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
            q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
            k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            fdec = mm(mm(x, w("kda_wfa", j, (D, LOW_RANK))),
                      w("kda_wfb", j, (LOW_RANK, HD))) + w("kda_dt_bias", j, (HD,))
            a = jnp.exp(-jnp.exp(w("kda_A_log", j, (H,)))[:, None]
                        * jax.nn.softplus(fdec.reshape(B, T, H, d)))
            beta = jax.nn.sigmoid(mm(x, w("kda_wb", j, (D, H))))

            def step(S, inp):
                q_t, k_t, v_t, a_t, b_t = inp
                S = a_t[..., None] * S
                u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
                S = S + k_t[..., :, None] * u[..., None, :]
                return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

            _, o = jax.lax.scan(
                step, jnp.zeros((B, H, d, d), jnp.float32),
                tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta)))
            o = _rmsnorm(jnp.moveaxis(o, 0, 1), g["eps"])
            gate = jax.nn.sigmoid(mm(mm(x, w("kda_wga", j, (D, LOW_RANK))),
                                     w("kda_wgb", j, (LOW_RANK, HD))))
            return mm((o * gate.reshape(B, T, H, d)).reshape(B, T, HD),
                      w("kda_wo", j, (HD, D)))

        def mla(x, j):
            H, nope, rope, vd, rank = (g[n] for n in ("H", "nope", "rope", "vd", "rank"))
            q = mm(x, w("mla_wq", j, (D, H * (nope + rope)))).reshape(
                B, T, H, nope + rope)
            kv = mm(x, w("mla_wkva", j, (D, rank + rope)))
            c = _rmsnorm(kv[..., :rank], g["eps"])
            k_r = jnp.broadcast_to(kv[..., None, rank:], (B, T, H, rope))
            up = mm(c, w("mla_wkvb", j, (rank, H * (nope + vd)))).reshape(
                B, T, H, nope + vd)
            k = jnp.concatenate([up[..., :nope], k_r], axis=-1)
            s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(nope + rope)
            pos = jnp.arange(T)
            mask = (pos[None, :] <= pos[:, None])[None, None] & (
                pos[None, None, None, :] < lengths[:, None, None, None])
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
            o = jnp.einsum("bhts,bshv->bthv", p, up[..., nope:])
            return mm(o.reshape(B, T, H * vd), w("mla_wo", j, (H * vd, D)))

        def gated(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def experts(x, j):
            E, Fe = g["E"], g["Fe"]
            xf = x.reshape(B * T, D)
            s = jax.nn.sigmoid(mm(xf, w("router", j, (D, E * g["shards"]))))
            _, topi = jax.lax.top_k(
                s + w("router_bias", j, (E * g["shards"],)), g["k"])
            wt = jnp.take_along_axis(s, topi, axis=-1)
            if g["renorm"]:
                wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
            wt = wt * g["scale"]
            e0 = g["shard"] * E

            def one(y, e):
                share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
                out = gated(xf, w("we_gate", j, (D, Fe), e),
                            w("we_up", j, (D, Fe), e), w("we_down", j, (Fe, D), e))
                return y + share[:, None] * out, None

            y, _ = jax.lax.scan(one, jnp.zeros_like(xf), jnp.arange(E))
            y = y + gated(xf, w("ws_gate", j, (D, Fe)), w("ws_up", j, (D, Fe)),
                          w("ws_down", j, (Fe, D)))
            return y.reshape(B, T, D)

        def dense(x, j):
            F = g["F"]
            return gated(x, w("w_gate", j, (D, F)), w("w_up", j, (D, F)),
                         w("w_down", j, (F, D)))

        def either(flag, yes, no, have_yes: bool, have_no: bool, x, j):
            if have_yes and have_no:
                return jax.lax.cond(flag, yes, no, x, j)
            return yes(x, j) if have_yes else no(x, j)

        def layer(x, kinds):
            # one body for every layer (each kind is compiled once): the
            # layer's kinds and its index in each kind's stack ride the
            # scan, and the weights are drawn from the folded-in index
            is_kda, mixer_j, is_dense, ffn_j = kinds
            x = x + either(is_kda, kda, mla, bool(g["kda"]), bool(g["mla"]),
                           _rmsnorm(x, g["eps"]), mixer_j)
            x = x + either(is_dense, dense, experts, bool(g["dense"]),
                           bool(g["moe"]), _rmsnorm(x, g["eps"]), ffn_j)
            return x, None

        layers = range(g["L"])
        kinds = (
            jnp.asarray([i in g["kda"] for i in layers]),
            jnp.asarray([(g["kda"] if i in g["kda"] else g["mla"]).index(i)
                         for i in layers], jnp.int32),
            jnp.asarray([i in g["dense"] for i in layers]),
            jnp.asarray([(g["dense"] if i in g["dense"] else g["moe"]).index(i)
                         for i in layers], jnp.int32),
        )
        embed = draw(jax.random.fold_in(key, idx["embed"]), "embed", (V, D))
        x, _ = jax.lax.scan(layer, jnp.take(embed, tokens, axis=0), kinds)
        x = _rmsnorm(x, g["eps"])
        x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)
        head = draw(jax.random.fold_in(key, idx["lm_head"]), "lm_head", (D, V))
        return mm(x_at, head)

    jitted = jax.jit(f)

    def run(seed: int, tokens, lengths, at):
        with jax.default_matmul_precision("highest"):
            return jitted(jax.random.PRNGKey(seed), tokens, lengths, at)

    return run
