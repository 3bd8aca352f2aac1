"""The plain reference of the ``qwen3_next`` family: the forward pass in
float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no chunks. It takes NOTHING from the program:
the equations are written out here, and the weights are drawn here from
the seed by the recipe the configuration file states (``assumed``):
parameter ``i`` of ``PARAM_ORDER`` has key ``fold_in(PRNGKey(seed), i)``,
layer ``j`` of its stack ``fold_in(., j)``, expert ``e`` of a layer
``fold_in(., e)``; ``normal / sqrt(fan_in)`` then symmetric
per-output-channel int8 (per row for the embedding); the ``(1 + w)``
norms ``w = 0``, the Gated DeltaNet output norm 1; ``A_log = log(U(1,
16))``; ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3,
1e-1]; the convolution, the router and the shared expert's gate vector
kept float32. The int8 values and scales are used in float32. Weights
are drawn layer by layer inside one scan over the layers (the layer's
kind chooses its branch), and an expert block's experts one at a time.

The configuration holds a SHARE of the published model (its file says
which): ``num_experts`` experts of ``num_experts * expert_shards`` that
the router scores, the ``expert_shard_index``-th run of them; what the
absent experts would add is left out, here as in the program.

Equations (layer ``i`` from 0; ``x += Mixer(norm(x)); x +=
Experts(norm(x))``; final norm; untied head; ``norm(x) = x /
sqrt(mean(x^2) + eps) * (1 + w)``, ``w`` = 0 in the seeded draw):

Gated attention (``(i + 1) % full_attention_interval == 0``): ``[q |
gate] = x Wq`` a head, ``k = x Wk``, ``v = x Wv``; q and k normalised
over each head; the first ``partial_rotary_factor * Dh`` dims of q and k
rotated in the half-rotation form (pairs ``(j, j + rot/2)``, frequency
``theta^(-2j/rot)``), the others pass; causal ``softmax(q k^T /
sqrt(Dh)) v``, ``H / Hk`` query heads a KV head; ``(attn *
sigmoid(gate)) Wo``.
Gated DeltaNet (the other layers): ``[q | k | v | z] = x Wqkvz``, ``[b |
a] = x Wba``; ``q, k, v = SiLU(conv([q | k | v]))``, depthwise and
causal; q, k L2-normalised a head (eps 1e-6 inside the root), ``q *=
d^-0.5``, key head j serving value heads ``j Hv/Hk ...``; ``beta =
sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``; a head's state
``S <- exp(g_t) S``; ``u = beta_t (v_t - S^T k_t)``; ``S <- S + k_t
u^T``; ``o_t = S^T q_t`` — token by token; output ``(o / sqrt(mean(o^2) +
eps) * SiLU(z)) Wo`` (the output norm's weight is 1 in the draw).
Expert block: ``p = softmax(x Wr)`` over all experts, the k largest,
renormalised (``norm_topk_prob``), ``y = sum_e w_e E_e(x)`` over the
chosen experts held here ``+ sigmoid(x . w_sg) E_shared(x)``.

``precision`` selects the CONTROL: ``"a8"`` quantises the input of every
weight matmul per token to 8 bits (bf16 -> int8 activations, the step
below what the configuration states); the float32 router and gate
vector read the unquantised state, as the program's do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "a8")

# models/qwen3_next.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
    "gdn_wqkvz", "gdn_wba", "gdn_conv", "gdn_A_log", "gdn_dt_bias",
    "gdn_onorm", "gdn_wo",
    "attn_wq", "attn_wk", "attn_wv", "attn_qnorm", "attn_knorm", "attn_wo",
    "router", "shared_gate", "ws_gate", "ws_up", "ws_down",
    "we_gate", "we_up", "we_down",
)
FLOAT32 = ("gdn_conv", "router", "shared_gate")   # drawn like a matrix, never quantized
ZEROS = ("final_norm", "attn_norm", "mlp_norm", "attn_qnorm", "attn_knorm")


def geometry(cfg: dict) -> dict:
    L, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    attn = [i for i in range(L) if (i + 1) % every == 0]
    Dh = cfg["head_dim"]
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        # the kernel readers' shapes: the paged-attention kernels' heads,
        # the state update's heads (Hl, dl), the held experts (E, Fe, k)
        H=cfg["num_attention_heads"], Hk=cfg["num_key_value_heads"], Dh=Dh,
        rot=int(Dh * cfg["partial_rotary_factor"]), theta=float(cfg["rope_theta"]),
        attn=attn, gdn=[i for i in range(L) if i not in attn],
        Hlk=cfg["linear_num_key_heads"], Hl=cfg["linear_num_value_heads"],
        dl=cfg["linear_value_head_dim"], K=cfg["linear_conv_kernel_dim"],
        Fe=cfg["moe_intermediate_size"], Fs=cfg["shared_expert_intermediate_size"],
        E=cfg["num_experts"], shards=cfg.get("expert_shards", 1),
        shard=cfg.get("expert_shard_index", 0), k=cfg["num_experts_per_tok"],
        renorm=bool(cfg["norm_topk_prob"]), eps=float(cfg["rms_norm_eps"]),
    )


def param_index(g: dict) -> dict[str, int]:
    def present(name: str) -> bool:
        if name.startswith("gdn_"):
            return bool(g["gdn"])
        if name.startswith("attn_w") or name in ("attn_qnorm", "attn_knorm"):
            return bool(g["attn"])
        return True

    return {n: i for i, n in enumerate(n for n in PARAM_ORDER if present(n))}


def _quantise(w, axis: int):
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def draw(key, name: str, shape: tuple):
    """One leading slice of parameter ``name`` as the configuration
    serves it, in float32."""
    if name in ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if name == "gdn_onorm":
        return jnp.ones(shape, jnp.float32)
    if name == "gdn_A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "gdn_dt_bias":
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


def _norm(x, eps: float):
    # (1 + w) with w = 0, and the output norm's w = 1: both multiply by 1
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate(x, pos, theta: float, rot: int):
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs             # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


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

        def gdn(x, j):
            Hk, Hv, d, K = g["Hlk"], g["Hl"], g["dl"], g["K"]
            QK, VD = Hk * d, Hv * d
            qkvz = mm(x, w("gdn_wqkvz", j, (D, 2 * QK + 2 * VD)))
            ba = mm(x, w("gdn_wba", j, (D, 2 * Hv)))
            qkv, z = qkvz[..., : 2 * QK + VD], qkvz[..., 2 * QK + VD:]
            padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
            cw = w("gdn_conv", j, (K, 2 * QK + VD))
            conv = jax.nn.silu(sum(padded[:, i:i + T] * cw[i] for i in range(K)))
            q = conv[..., :QK].reshape(B, T, Hk, d)
            k = conv[..., QK: 2 * QK].reshape(B, T, Hk, d)
            v = conv[..., 2 * QK:].reshape(B, T, Hv, d)
            q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
            k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
            beta = jax.nn.sigmoid(ba[..., :Hv])
            decay = jnp.exp(-jnp.exp(w("gdn_A_log", j, (Hv,))) * jax.nn.softplus(
                ba[..., Hv:] + w("gdn_dt_bias", j, (Hv,))))

            def step(S, inp):
                q_t, k_t, v_t, a_t, b_t = inp
                S = a_t[..., None, None] * S
                u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
                S = S + k_t[..., :, None] * u[..., None, :]
                return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

            _, o = jax.lax.scan(
                step, jnp.zeros((B, Hv, d, d), jnp.float32),
                tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, decay, beta)))
            o = _norm(jnp.moveaxis(o, 0, 1), g["eps"])
            o = o * jax.nn.silu(z.reshape(B, T, Hv, d))
            return mm(o.reshape(B, T, VD), w("gdn_wo", j, (VD, D)))

        def attn(x, j):
            H, Hk, Dh = g["H"], g["Hk"], g["Dh"]
            qg = mm(x, w("attn_wq", j, (D, H * 2 * Dh))).reshape(B, T, H, 2 * Dh)
            q, gate = qg[..., :Dh], qg[..., Dh:]
            k = mm(x, w("attn_wk", j, (D, Hk * Dh))).reshape(B, T, Hk, Dh)
            v = mm(x, w("attn_wv", j, (D, Hk * Dh))).reshape(B, T, Hk, Dh)
            pos = jnp.arange(T)
            q = _rotate(_norm(q, g["eps"]), pos, g["theta"], g["rot"])
            k = _rotate(_norm(k, g["eps"]), pos, g["theta"], g["rot"])
            k, v = (jnp.repeat(a, H // Hk, axis=2) for a in (k, v))
            s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(Dh)
            mask = (pos[None, :] <= pos[:, None])[None, None] & (
                pos[None, None, None, :] < lengths[:, None, None, None])
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
            o = jnp.einsum("bhts,bshd->bthd", p, v) * jax.nn.sigmoid(gate)
            return mm(o.reshape(B, T, H * Dh), w("attn_wo", j, (H * Dh, D)))

        def gated(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def experts(x, j):
            E, Fe, Fs = g["E"], g["Fe"], g["Fs"]
            xf = x.reshape(B * T, D)
            s = jax.nn.softmax(jnp.dot(xf, w("router", j, (D, E * g["shards"]))), -1)
            wt, topi = jax.lax.top_k(s, g["k"])
            if g["renorm"]:
                wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
            e0 = g["shard"] * E

            def one(y, e):
                share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
                out = gated(xf, w("we_gate", j, (D, Fe), e),
                            w("we_up", j, (D, Fe), e), w("we_down", j, (Fe, D), e))
                return y + share[:, None] * out, None

            y, _ = jax.lax.scan(one, jnp.zeros_like(xf), jnp.arange(E))
            sg = jax.nn.sigmoid(jnp.dot(xf, w("shared_gate", j, (D, 1))))
            y = y + sg * gated(xf, w("ws_gate", j, (D, Fs)), w("ws_up", j, (D, Fs)),
                               w("ws_down", j, (Fs, D)))
            return y.reshape(B, T, D)

        def layer(x, kinds):
            # one body for every layer (each kind is compiled once): the
            # layer's kind and its index in the kind's stack ride the scan
            is_attn, mixer_j, layer_j = kinds
            h = _norm(x, g["eps"])
            if g["attn"] and g["gdn"]:
                x = x + jax.lax.cond(is_attn, attn, gdn, h, mixer_j)
            else:
                x = x + (attn if g["attn"] else gdn)(h, mixer_j)
            return x + experts(_norm(x, g["eps"]), layer_j), None

        layers = range(g["L"])
        kinds = (
            jnp.asarray([i in g["attn"] for i in layers]),
            jnp.asarray([(g["attn"] if i in g["attn"] else g["gdn"]).index(i)
                         for i in layers], jnp.int32),
            jnp.arange(g["L"], dtype=jnp.int32),
        )
        embed = draw(jax.random.fold_in(key, idx["embed"]), "embed", (V, D))
        x, _ = jax.lax.scan(layer, jnp.take(embed, tokens, axis=0), kinds)
        x = _norm(x, g["eps"])
        x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)
        head = draw(jax.random.fold_in(key, idx["lm_head"]), "lm_head", (D, V))
        return mm(x_at, head)

    jitted = jax.jit(f)

    def run(seed: int, tokens, lengths, at):
        with jax.default_matmul_precision("highest"):
            return jitted(jax.random.PRNGKey(seed), tokens, lengths, at)

    return run
