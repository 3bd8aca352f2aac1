"""The plain reference of the ``glm_moe_dsa`` family (GLM-5): the
forward pass in float32, to the contract at the top of ``model.py``.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
— no kernels, no cache, no chunks, NOT absorbed: keys and values are
UP-PROJECTED a head from the latent; the index score is computed as
written over every key, masked explicitly, its top ``index_topk`` taken
by ``lax.top_k`` (equal scores: the lower index first), and the softmax
runs over an explicit ``-inf`` mask built from that set. It takes NOTHING
from the program: the equations are written out here, and the weights are
drawn here from the seed by the recipe the configuration file states
(``assumed``): parameter ``i`` of ``PARAM_ORDER`` has key
``fold_in(PRNGKey(seed), i)``, layer ``j`` of its stack ``fold_in(., j)``,
expert ``e`` of a layer ``fold_in(., e)``; ``normal / sqrt(fan_in)`` then
symmetric per-output-channel int8 (per row for the embedding); norm
weights 1 (the indexer LayerNorm's too), its bias standard normal;
``e_score_correction_bias`` 0; the router and the indexer's head weights
kept float32. The int8 values and scales are used in float32. The leading
dense layer runs first, then one scan over the expert layers (one
compiled body), each drawing its weights inside and its HELD experts one
at a time. Attention and the index score read the queries
``QUERY_BLOCK`` at a time, so a 21 504-token row's scores are ``[64, 256,
21 504]`` float32 = 1.4 GB and its index products ``[256, 32, 21 504]``
= 0.7 GB, and fit the chip.

Equations (a layer; ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``; eps
``rms_norm_eps``; no biases but the LayerNorm's; token at position ``p``):
``c_q = RMSNorm(W_qa h)``, ``q = W_qb c_q`` as H heads of ``[q_nope |
q_pe]``; ``[c_kv | k_pe] = W_kva h``; ``c = RMSNorm(c_kv)``; ``k_r = R_p
k_pe`` (one key part for all heads), ``q_r = R_p q_pe``, ``R_p`` turning
the adjacent pairs ``(x_2i, x_2i+1)`` by ``p * theta^(-2i/rope)``;
``[k_nope_h | v_h] = W_kvb c``; ``s_h = (q_nope_h . k_nope_h + q_r_h .
k_r) / sqrt(nope + rope)``. The indexer: ``q^I = W^I_qb c_q`` as G heads
of d, ``k^I = LayerNorm(W^I_k h)`` (weight, bias, eps 1e-6), the FIRST
``rope`` values of each turned by the token's position, ``w = W^I_w h``;
``I_pj = sum_g w_pg ReLU(q^I_pg . k^I_j)`` for ``j <= p``; ``S_p`` = the
``min(index_topk, p + 1)`` keys of largest ``I_pj``, ties to the lower
``j``; the softmax over ``S_p`` only, one ``S_p`` for all heads; ``o_h =
sum p v_h``, ``W_o``. Feed-forward: layer 0 ``W_down(silu(W_gate h) *
W_up h)``; the others ``s = sigmoid(W_r h)`` over ALL ``E x
expert_shards`` experts, top k of ``s + b``, weights ``s_i / (sum +
1e-20) * scale``, of which the HELD experts' (``expert_shard_index``'s
run) are summed, plus the shared MLP once; what the other shards'
experts would add is left out, as in the program. Final RMSNorm, untied
head over the vocabulary slice.

Departures from the published DSA code (``models/reference/
glm_moe_dsa.py`` lists them with their reasons): the index score's
positive constants dropped, no Hadamard turn, no fp8, no
multi-token-prediction layer.

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
INDEX_NORM_EPS = 1e-6

# models/glm_moe_dsa.py param_shapes order: the index is part of the recipe
PARAM_ORDER = (
    "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
    "mla_wqa", "mla_qnorm", "mla_wqb", "mla_wkva", "mla_kvnorm", "mla_wkvb",
    "mla_wo",
    "w_gate", "w_up", "w_down",
    "router", "router_bias", "ws_gate", "ws_up", "ws_down",
    "we_gate", "we_up", "we_down",
    "idx_wq", "idx_wk", "idx_knorm", "idx_kbias", "idx_ww",
)
FLOAT32 = ("router", "idx_ww")   # drawn like a matrix, never quantized


def geometry(cfg: dict) -> dict:
    rope_kind = (cfg.get("rope_parameters") or {}).get("rope_type", "default")
    for key, got, want in (
            ("rope_scaling", cfg.get("rope_scaling"), None),
            ("rope_parameters.rope_type", rope_kind, "default"),
            ("n_group", cfg.get("n_group", 1), 1),
            ("topk_group", cfg.get("topk_group", 1), 1),
            ("scoring_func", cfg.get("scoring_func", "sigmoid"), "sigmoid")):
        if got != want:
            raise ValueError(f"the glm_moe_dsa reference does not build "
                             f"{key} = {got!r}")
    L = cfg["num_hidden_layers"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dense = list(range(min(cfg["first_k_dense_replace"], L)))
    if dense != [0] or L < 2:
        raise ValueError("the glm_moe_dsa reference is written for one "
                         "leading dense layer and expert layers after it")
    theta = cfg.get("rope_theta", (cfg.get("rope_parameters") or {}).get("rope_theta"))
    return dict(
        L=L, D=cfg["hidden_size"], V=cfg["vocab_size"],
        # the kernel readers' shapes: latent attention has one shared
        # cached head of rank + rope values under H query heads
        H=cfg["num_attention_heads"], Hk=1, Dh=rank + rope,
        dense=dense, moe=list(range(1, L)),
        nope=cfg["qk_nope_head_dim"], rope=rope, vd=cfg["v_head_dim"], rank=rank,
        q_rank=cfg["q_lora_rank"], G=cfg["index_n_heads"], dI=cfg["index_head_dim"],
        topk=cfg["index_topk"],
        theta=float(theta), interleave=bool(cfg.get("rope_interleave", True)),
        index_interleave=bool(cfg.get("indexer_rope_interleave", True)),
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        # E: the experts HELD here; the router scores E x shards
        E=cfg["n_routed_experts"], shards=cfg.get("expert_shards", 1),
        shard=cfg.get("expert_shard_index", 0),
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
    if name == "idx_kbias":
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


def _layernorm(x, bias, eps: float):
    # the weight is ones in the seeded draw; the bias is not
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) + bias


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
            G, dI, q_rank = g["G"], g["dI"], g["q_rank"]
            c_q = _rmsnorm(mm(x, w("mla_wqa", j, (D, q_rank))), g["eps"])
            q = mm(c_q, w("mla_wqb", j, (q_rank, H * (nope + rope)))).reshape(
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

            # the indexer: the first ``rope`` values of a head are turned
            def turned(y):
                return jnp.concatenate(
                    [rotate(y[..., :rope], pos, g["theta"], g["index_interleave"]),
                     y[..., rope:]], -1)

            q_i = turned(mm(c_q, w("idx_wq", j, (q_rank, G * dI))).reshape(B, T, G, dI))
            k_i = turned(_layernorm(mm(x, w("idx_wk", j, (D, dI))),
                                    w("idx_kbias", j, (dI,)), INDEX_NORM_EPS))
            w_i = mm(x, w("idx_ww", j, (D, G)))                       # [B, T, G]
            tq = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
            top = min(g["topk"], T)

            def block(blk):
                qb, qib, wib, pb = blk          # [B, tq, H, .] [B, tq, G, dI] [B, tq, G] [tq]
                seen = (pos[None, :] <= pb[:, None])[None] & (
                    pos[None, None, :] < lengths[:, None, None])      # [B, tq, T]
                dots = jnp.einsum("btgd,bsd->btgs", qib, k_i)
                score = jnp.sum(wib[..., None] * jax.nn.relu(dots), axis=2)
                score = jnp.where(seen, score, -jnp.inf)
                _, best = jax.lax.top_k(score, top)                   # [B, tq, top]
                picked = jnp.zeros(score.shape, bool).at[
                    jnp.arange(B)[:, None, None],
                    jnp.arange(score.shape[1])[None, :, None], best].set(True)
                sel = picked & seen
                s = jnp.einsum("bthd,bshd->bhts", qb, k) / math.sqrt(nope + rope)
                p = jax.nn.softmax(jnp.where(sel[:, None], s, -jnp.inf), axis=-1)
                # a padded query (no key it may see) gives NaN rows: zero them
                p = jnp.where(sel[:, None], p, 0.0)
                return jnp.einsum("bhts,bshv->bthv", p, v)

            def blocks(y):
                return jnp.moveaxis(y.reshape(B, T // tq, tq, *y.shape[2:]), 1, 0)

            o = jax.lax.map(block, (blocks(q), blocks(q_i), blocks(w_i),
                                    pos.reshape(T // tq, tq)))
            o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * vd)
            return mm(o, w("mla_wo", j, (H * vd, D)))

        def gated(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def experts(x, j):
            E, Fe, Fs = g["E"], g["Fe"], g["Fs"]
            E_all, e0 = E * g["shards"], g["shard"] * E
            xf = x.reshape(B * T, D)
            s = jax.nn.sigmoid(mm(xf, w("router", j, (D, E_all))))
            _, topi = jax.lax.top_k(s + w("router_bias", j, (E_all,)), g["k"])
            wt = jnp.take_along_axis(s, topi, axis=-1)
            if g["renorm"]:
                wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
            wt = wt * g["scale"]

            def one(y, e):       # held expert e is the router's e0 + e
                share = jnp.sum(jnp.where(topi == e0 + e, wt, 0.0), axis=-1)
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
