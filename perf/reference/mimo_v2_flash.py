"""The plain reference of the ``mimo_v2_flash`` family (MiMo-V2-Flash):
the forward pass in float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no pages, no chunks: masks come from positions,
the sink is an explicit extra column of the softmax, every held expert is
visited by a plain loop. It takes NOTHING from the program: the equations
are written out here, and the weights are drawn here from the seed by the
recipe the configuration file states (``assumed``): parameter ``i`` of
``PARAM_ORDER`` has key ``fold_in(PRNGKey(seed), i)``, layer ``j`` of its
stack — a stack holds the layers of ONE KIND: full attention, window
attention, dense MLP, expert MLP — ``fold_in(., j)``, expert ``e`` of a
layer ``fold_in(., e)``; ``normal / sqrt(fan_in)`` then symmetric
per-output-channel int8 (per row for the embedding); norms 1;
``e_score_correction_bias`` 0; sinks N(0, 1) float32; the router kept
float32. The int8 values and scales are used in float32. Consecutive
layers that are alike (same attention kind, same feed-forward) run as one
scan, each layer drawing its weights inside and its experts one at a
time, so at most one expert's float32 matrices exist. Attention reads
the queries ``QUERY_BLOCK`` at a time — a 15 360-token row's scores are
``[64, 256, 15 360]`` float32 = 1 GB in a full layer — and a window layer
reads, for a block of queries, only the ``QUERY_BLOCK + window`` keys
that any of them can see (the mask, built from positions, is the same;
the keys left out have probability 0).

Equations (layer ``l``; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``;
eps ``layernorm_epsilon``; no biases; token at position ``p``; ``kind(l)``
full where ``hybrid_layer_pattern[l]`` is 0, window where 1):
``q = W_q h`` as 64 heads of 192; ``k = W_k h`` as ``Hk`` heads of 192,
``v = 0.707 * (W_v h)`` as ``Hk`` heads of 128 (``attention_value_scale``),
``Hk`` 4 (full) / 8 (window). Rotary on the first ``int(192 * 0.334)`` =
64 values of every q and k head, pairs ``(i, i + 32)`` by ``p *
theta^(-2i/64)``, ``theta`` 5e6 (full) / 1e4 (window). ``s_hj = q_h .
k_g(h),j / sqrt(192)``, ``g(h) = h // (64 / Hk)``, over ``j <= p`` (full)
or ``p - 128 < j <= p`` (window); a window layer's learned sink ``b_h``:
``P_hj = exp(s_hj - m) / (exp(b_h - m) + sum exp(s_hj' - m))``; ``o_h =
sum P_hj v_g(h),j``; ``W_o``. Feed-forward: layer 0 ``W_down(silu(W_gate
h) * W_up h)``; the others ``s = sigmoid(W_r h)`` over all 256, top 8 of
``s + b``, weights ``s_i / (sum + 1e-20)``, experts of the same gated
form — of which the HELD ones (``n_routed_experts`` = 16 of
``expert_shards`` x 16) are summed, as in the program. Final RMSNorm,
untied head.

``precision`` selects the CONTROL: ``"a8"`` quantises the input of every
weight matmul per token to 8 bits (bf16 -> int8 activations, the step
below what the configuration states).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "a8")
QUERY_BLOCK = 256

# models/mimo_v2_flash.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
    "full_wq", "full_wk", "full_wv", "full_wo",
    "win_wq", "win_wk", "win_wv", "win_wo", "win_sink",
    "w_gate", "w_up", "w_down", "router", "router_bias",
    "we_gate", "we_up", "we_down",
)
FLOAT32 = ("router",)   # drawn like a matrix, never quantized


def geometry(cfg: dict) -> dict:
    for key, want in (("rope_scaling", None), ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_shared_experts", None), ("attention_bias", False),
                      ("add_full_attention_sink_bias", False),
                      ("add_swa_attention_sink_bias", True)):
        if (cfg.get(key, want) or None) != (want or None):
            raise ValueError(f"the mimo_v2_flash reference does not build "
                             f"{key} = {cfg.get(key)!r}")
    L = cfg["num_hidden_layers"]
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    if len(pattern) != L or len(freq) != L:
        raise ValueError("hybrid_layer_pattern / moe_layer_freq must give "
                         f"every one of the {L} layers")
    window = cfg["sliding_window"]
    for key in ("sliding_window_size", "attention_chunk_size"):
        if cfg.get(key, window) != window:
            raise ValueError(f"{key} = {cfg[key]} is not the window {window}")
    full = [i for i, k in enumerate(pattern) if not k]
    win = [i for i, k in enumerate(pattern) if k]
    Dk = cfg["head_dim"]
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"],
        # the generic kernel readers' shapes are the FULL layers'; this
        # family's own readers take each kind's
        Hk=cfg["num_key_value_heads"], Dh=Dk,
        Hk_full=cfg["num_key_value_heads"],
        Hk_window=cfg["swa_num_key_value_heads"],
        Dk=Dk, Dv=cfg["v_head_dim"], window=window,
        rot=int(Dk * cfg["partial_rotary_factor"]),
        theta_full=float(cfg["rope_theta"]),
        theta_window=float(cfg["swa_rope_theta"]),
        vscale=float(cfg["attention_value_scale"]),
        full=full, win=win, L_full=len(full), L_window=len(win),
        dense=[i for i, f in enumerate(freq) if not f],
        moe=[i for i, f in enumerate(freq) if f],
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        E=cfg["n_routed_experts"], shards=cfg.get("expert_shards", 1),
        shard=cfg.get("expert_shard_index", 0),
        k=cfg["num_experts_per_tok"],
        scale=float(cfg.get("routed_scaling_factor") or 1.0),
        renorm=bool(cfg["norm_topk_prob"]),
        eps=float(cfg["layernorm_epsilon"]),
    )


def layer_runs(g: dict) -> list[dict]:
    """Consecutive layers that are alike, as ``{kind, moe, attn: [index
    among the kind's layers], ffn: [index among its feed-forward's]}``."""
    runs: list[dict] = []
    for layer in range(g["L"]):
        kind = "full" if layer in g["full"] else "win"
        moe = layer in g["moe"]
        attn = g[kind].index(layer)
        ffn = (g["moe"] if moe else g["dense"]).index(layer)
        if runs and (runs[-1]["kind"], runs[-1]["moe"]) == (kind, moe):
            runs[-1]["attn"].append(attn)
            runs[-1]["ffn"].append(ffn)
            runs[-1]["layers"].append(layer)
        else:
            runs.append({"kind": kind, "moe": moe, "attn": [attn],
                         "ffn": [ffn], "layers": [layer]})
    return runs


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
    if name.endswith("_sink"):
        return jax.random.normal(key, shape, jnp.float32)
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


def rotate_leading(x, pos, theta: float, rot: int):
    """``x [B, T, heads, d]``: the first ``rot`` values of each head turned
    at ``pos [T]`` — pairs (i, i + rot/2) by p * theta^(-2i/rot)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def logits_fn(cfg: dict, precision: str = "f32"):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    g = geometry(cfg)
    idx = {n: i for i, n in enumerate(PARAM_ORDER)}
    D, V, H, Dk, Dv = g["D"], g["V"], g["H"], g["Dk"], g["Dv"]

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

        def attention(x, kind, j):
            window = g["window"] if kind == "win" else None
            Hk = g["Hk_window" if kind == "win" else "Hk_full"]
            theta = g["theta_window" if kind == "win" else "theta_full"]
            G = H // Hk
            q = mm(x, w(f"{kind}_wq", j, (D, H * Dk))).reshape(B, T, H, Dk)
            k = mm(x, w(f"{kind}_wk", j, (D, Hk * Dk))).reshape(B, T, Hk, Dk)
            v = g["vscale"] * mm(x, w(f"{kind}_wv", j, (D, Hk * Dv))).reshape(
                B, T, Hk, Dv)
            q = rotate_leading(q, pos, theta, g["rot"])
            k = rotate_leading(k, pos, theta, g["rot"])
            sink = w("win_sink", j, (H,)) if kind == "win" else None
            tq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
            # a window layer's block of queries sees at most tq + window
            # - 1 keys: those are cut out (front-padded, so that every
            # block cuts the same length), a full layer's sees them all
            S = T if window is None else min(T, tq + window)
            lead = 0 if window is None or S == T else window
            kp = jnp.pad(k, ((0, 0), (lead, 0), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (lead, 0), (0, 0), (0, 0)))

            def block(args):
                qb, pb, t0 = args                   # [B, tq, H, Dk], [tq], ()
                if lead:
                    kb = jax.lax.dynamic_slice_in_dim(kp, t0, S, axis=1)
                    vb = jax.lax.dynamic_slice_in_dim(vp, t0, S, axis=1)
                    pk = t0 - lead + jnp.arange(S)
                else:
                    kb, vb, pk = kp, vp, pos
                s = jnp.einsum("btkgd,bskd->bkgts",
                               qb.reshape(B, tq, Hk, G, Dk), kb) / math.sqrt(Dk)
                seen = (pk[None, :] <= pb[:, None]) & (pk[None, :] >= 0)
                if window is not None:
                    seen &= pk[None, :] > pb[:, None] - window
                mask = seen[None, None, None] & (
                    pk[None, None, None, None, :]
                    < lengths[:, None, None, None, None])
                s = jnp.where(mask, s, -1e30)
                if sink is not None:
                    col = jnp.broadcast_to(
                        sink.reshape(1, Hk, G, 1, 1), s.shape[:-1] + (1,))
                    p = jax.nn.softmax(
                        jnp.concatenate([s, col], -1), axis=-1)[..., :-1]
                else:
                    p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("bkgts,bskv->btkgv", p, vb).reshape(
                    B, tq, H * Dv)

            o = jax.lax.map(block, (
                jnp.moveaxis(q.reshape(B, T // tq, tq, H, Dk), 1, 0),
                pos.reshape(T // tq, tq), jnp.arange(0, T, tq)))
            o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * Dv)
            return mm(o, w(f"{kind}_wo", j, (H * Dv, D)))

        def gated(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def experts(x, j):
            E, Fe = g["E"], g["Fe"]
            E_all, e0 = E * g["shards"], g["shard"] * E
            xf = x.reshape(B * T, D)
            s = jax.nn.sigmoid(mm(xf, w("router", j, (D, E_all))))
            _, topi = jax.lax.top_k(s + w("router_bias", j, (E_all,)), g["k"])
            wt = jnp.take_along_axis(s, topi, axis=-1)
            if g["renorm"]:
                wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
            wt = wt * g["scale"]

            def one(y, e):
                share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
                out = gated(xf, w("we_gate", j, (D, Fe), e),
                            w("we_up", j, (D, Fe), e), w("we_down", j, (Fe, D), e))
                return y + share[:, None] * out, None

            y, _ = jax.lax.scan(one, jnp.zeros_like(xf), jnp.arange(E))
            return y.reshape(B, T, D)

        def dense(x, j):
            F = g["F"]
            return gated(x, w("w_gate", j, (D, F)), w("w_up", j, (D, F)),
                         w("w_down", j, (F, D)))

        embed = draw(jax.random.fold_in(key, idx["embed"]), "embed", (V, D))
        x = jnp.take(embed, tokens, axis=0)
        for run in layer_runs(g):
            def layer(x, ij, run=run):
                x = x + attention(_rmsnorm(x, g["eps"]), run["kind"], ij[0])
                ffn = experts if run["moe"] else dense
                return x + ffn(_rmsnorm(x, g["eps"]), ij[1]), None

            x, _ = jax.lax.scan(layer, x, (
                jnp.asarray(run["attn"], jnp.int32),
                jnp.asarray(run["ffn"], jnp.int32)))
        x = _rmsnorm(x, g["eps"])
        x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)
        head = draw(jax.random.fold_in(key, idx["lm_head"]), "lm_head", (D, V))
        return mm(x_at, head)

    jitted = jax.jit(f)

    def run(seed: int, tokens, lengths, at):
        with jax.default_matmul_precision("highest"):
            return jitted(jax.random.PRNGKey(seed), tokens, lengths, at)

    return run
