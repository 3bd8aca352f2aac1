"""The plain reference of the ``nemotron_h`` family: the forward pass in
float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no chunks. It takes NOTHING from the program:
the equations are written out here, and the weights are drawn here from
the seed by the recipe the configuration file states (``assumed``):
parameter ``i`` of ``PARAM_ORDER`` has key ``fold_in(PRNGKey(seed), i)``,
layer ``j`` of its stack ``fold_in(., j)``, expert ``e`` of a layer
``fold_in(., e)``; ``normal / sqrt(fan_in)`` then symmetric
per-output-channel int8 (per row for the embedding); norm weights and
``D`` 1, the convolution's bias and the router's correction bias 0;
``A_log = log(U(1, 16))``; ``dt_bias`` the inverse softplus of a
log-uniform step in ``[time_step_min, time_step_max]`` held above
``time_step_floor``; the convolution and the router kept float32. The
int8 values and scales are used in float32. Weights are drawn layer by
layer inside one scan over the layers (the layer's letter chooses its
branch), and an expert layer's experts one at a time.

Equations (layer ``i`` from 0: ``x += part_i(norm(x))``, ``part_i`` by
letter ``i`` of ``hybrid_override_pattern``; final norm; untied head;
``norm(x) = x / sqrt(mean(x^2) + eps) * w``, ``w`` = 1 in the draw):

``M`` Mamba-2 (H heads of P, state N, G groups, inner = H P): ``[z | x |
B | C] = h W_in`` (inner | inner | G N | G N), ``dt = h W_dt``; ``[x | B |
C]_t <- SiLU(b + sum_i w[i] [x | B | C]_{t-(K-1)+i})`` depthwise, zeros
before the sequence; ``dt = softplus(dt + dt_bias)``; ``a = exp(-exp(A_log)
dt)``; a head's ``[P, N]`` state from zero, token by token, ``h_t = a_t
h_{t-1} + (dt_t x_t) (x) B_t`` with head h reading group ``h // (H / G)``;
``y_t = h_t C_t + D x_t``; ``y <- norm over each of G groups of (y *
SiLU(z))``; ``y W_out``.
``*`` attention: ``q, k, v = h Wq, h Wk, h Wv``; NO rotary embedding;
causal ``softmax(q k^T / sqrt(Dh)) v``, ``H / Hk`` query heads a KV head;
``o Wo``.
``E`` experts: ``s = sigmoid(h Wr)``; the k largest of ``s + bias``; ``w =
s[chosen] / (sum + 1e-20) * routed_scaling_factor``; ``y = sum_e w_e
relu(h U_e)^2 D_e`` over the chosen experts held here ``+ relu(h U_s)^2
D_s``.
``-``: ``relu(h U)^2 D``.

``precision`` selects the CONTROL: ``"a8"`` quantises the input of every
weight matmul per token to 8 bits (bf16 -> int8 activations, the step
below what the configuration states); the float32 router reads the
unquantised state, as the program's does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "a8")

# models/nemotron_h.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "norm",
    "m_win", "m_wdt", "m_conv", "m_conv_bias", "m_A_log", "m_dt_bias", "m_D",
    "m_onorm", "m_wo",
    "attn_wq", "attn_wk", "attn_wv", "attn_wo",
    "router", "router_bias", "ws_up", "ws_down", "we_up", "we_down",
    "w_up", "w_down",
)
KIND_OF = {"m_": "M", "attn_": "*", "router": "E", "ws_": "E", "we_": "E", "w_": "-"}
FLOAT32 = ("m_conv", "router")       # drawn like a matrix, never quantized
ONES = ("final_norm", "norm", "m_onorm", "m_D")
ZEROS = ("m_conv_bias", "router_bias")
LETTERS = "M*E-"


def geometry(cfg: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"]
    L = cfg["num_hidden_layers"]
    if len(pattern) != L or set(pattern) - set(LETTERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} for {L} layers")
    Hm, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"], pattern=pattern,
        # the kernel readers' shapes: the paged-attention kernels' heads,
        # the state update's (Hm, P, N, G), the held experts (E, Fe, k)
        H=cfg["num_attention_heads"], Hk=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], Hm=Hm, P=P, N=N, G=G, K=cfg["conv_kernel"],
        inner=Hm * P, conv=Hm * P + 2 * G * N,
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["moe_shared_expert_intermediate_size"],
        expert_form="updown",         # two matrices an expert, no gate
        E=cfg["n_routed_experts"], shards=cfg.get("expert_shards", 1),
        shard=cfg.get("expert_shard_index", 0), k=cfg["num_experts_per_tok"],
        renorm=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
        eps=float(cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))),
        dt_range=(float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                  float(cfg["time_step_floor"])),
    )


def param_index(g: dict) -> dict[str, int]:
    def present(name: str) -> bool:
        for prefix, letter in KIND_OF.items():
            if name.startswith(prefix):
                return letter in g["pattern"]
        return True

    return {n: i for i, n in enumerate(n for n in PARAM_ORDER if present(n))}


def _quantise(w, axis: int):
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def draw(key, name: str, shape: tuple, dt_range=(1e-3, 1e-1, 1e-4)):
    """One leading slice of parameter ``name`` as the configuration
    serves it, in float32."""
    if name in ONES:
        return jnp.ones(shape, jnp.float32)
    if name in ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if name == "m_A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "m_dt_bias":
        lo, hi, floor = dt_range
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
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
    # every norm weight is 1 in the draw
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
            return draw(k, name, shape, g["dt_range"])

        def relu2(x, up, down):
            return mm(jnp.square(jax.nn.relu(mm(x, up))), down)

        def mamba(x, j):
            Hm, P, N, G, K = g["Hm"], g["P"], g["N"], g["G"], g["K"]
            inner, conv = g["inner"], g["conv"]
            zx = mm(x, w("m_win", j, (D, inner + conv)))
            z, xbc = zx[..., :inner], zx[..., inner:]
            dt = jax.nn.softplus(mm(x, w("m_wdt", j, (D, Hm)))
                                 + w("m_dt_bias", j, (Hm,)))
            padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            cw = w("m_conv", j, (K, conv))
            y = jax.nn.silu(w("m_conv_bias", j, (conv,)) + sum(
                padded[:, i:i + T] * cw[i] for i in range(K)))
            xs = y[..., :inner].reshape(B, T, Hm, P)
            Bm = jnp.repeat(y[..., inner: inner + G * N].reshape(B, T, G, N),
                            Hm // G, axis=2)
            C = jnp.repeat(y[..., inner + G * N:].reshape(B, T, G, N),
                           Hm // G, axis=2)
            a = jnp.exp(-jnp.exp(w("m_A_log", j, (Hm,))) * dt)

            def step(h, inp):
                x_t, dt_t, a_t, b_t, c_t = inp
                h = a_t[..., None, None] * h \
                    + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
                return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

            _, o = jax.lax.scan(
                step, jnp.zeros((B, Hm, P, N), jnp.float32),
                tuple(jnp.moveaxis(t, 1, 0) for t in (xs, dt, a, Bm, C)))
            o = jnp.moveaxis(o, 0, 1) + w("m_D", j, (Hm,))[:, None] * xs
            o = o.reshape(B, T, inner) * jax.nn.silu(z)
            o = _norm(o.reshape(B, T, G, inner // G), g["eps"]).reshape(B, T, inner)
            return mm(o, w("m_wo", j, (inner, D)))

        def attn(x, j):
            H, Hk, Dh = g["H"], g["Hk"], g["Dh"]
            q = mm(x, w("attn_wq", j, (D, H * Dh))).reshape(B, T, H, Dh)
            k = mm(x, w("attn_wk", j, (D, Hk * Dh))).reshape(B, T, Hk, Dh)
            v = mm(x, w("attn_wv", j, (D, Hk * Dh))).reshape(B, T, Hk, Dh)
            k, v = (jnp.repeat(a, H // Hk, axis=2) for a in (k, v))
            pos = jnp.arange(T)
            s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(Dh)
            mask = (pos[None, :] <= pos[:, None])[None, None] & (
                pos[None, None, None, :] < lengths[:, None, None, None])
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
            o = jnp.einsum("bhts,bshd->bthd", p, v)
            return mm(o.reshape(B, T, H * Dh), w("attn_wo", j, (H * Dh, D)))

        def experts(x, j):
            E, Fe, Fs = g["E"], g["Fe"], g["Fs"]
            xf = x.reshape(B * T, D)
            s = jax.nn.sigmoid(jnp.dot(xf, w("router", j, (D, E * g["shards"]))))
            _, topi = jax.lax.top_k(
                s + w("router_bias", j, (E * g["shards"],)), g["k"])
            wt = jnp.take_along_axis(s, topi, axis=-1)
            if g["renorm"]:
                wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
            wt = wt * g["scaling"]
            e0 = g["shard"] * E

            def one(y, e):
                share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
                out = relu2(xf, w("we_up", j, (D, Fe), e), w("we_down", j, (Fe, D), e))
                return y + share[:, None] * out, None

            y, _ = jax.lax.scan(one, jnp.zeros_like(xf), jnp.arange(E))
            y = y + relu2(xf, w("ws_up", j, (D, Fs)), w("ws_down", j, (Fs, D)))
            return y.reshape(B, T, D)

        def mlp(x, j):
            return relu2(x, w("w_up", j, (D, g["F"])), w("w_down", j, (g["F"], D)))

        parts = {"M": mamba, "*": attn, "E": experts, "-": mlp}
        present = [c for c in LETTERS if c in g["pattern"]]
        branches = [parts[c] for c in present]

        def layer(x, kinds):
            # one body for every layer (each kind is compiled once): the
            # layer's kind and its index in the kind's stack ride the scan
            kind, part_j = kinds
            h = _norm(x, g["eps"])
            if len(branches) == 1:
                return x + branches[0](h, part_j), None
            return x + jax.lax.switch(kind, branches, h, part_j), None

        seen: dict = {}
        part_j = []
        for c in g["pattern"]:
            part_j.append(seen.get(c, 0))
            seen[c] = part_j[-1] + 1
        kinds = (jnp.asarray([present.index(c) for c in g["pattern"]], jnp.int32),
                 jnp.asarray(part_j, jnp.int32))
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
