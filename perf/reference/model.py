"""The plain reference of the llama family (``FAMILIES`` below): the
forward pass in float32.

THE CONTRACT OF A FAMILY MODULE. A configuration's ``model_type`` names
its family module (``perf/reference/family.py``: the file of that name,
else the module whose ``FAMILIES`` lists it). Such a module gives:

- ``logits_fn(cfg, precision) -> run``, with ``run(seed, tokens [B, T],
  lengths [B], at [B, P]) -> logits [B, P, V]`` in float32 under
  ``jax.default_matmul_precision("highest")``: the logits at the
  positions ``at`` of each right-padded sequence. ``cfg`` holds the
  published ``config.json`` keys. The weights are drawn INSIDE from
  ``seed`` by the recipe the configuration file states; nothing is read
  from the program;
- ``PRECISIONS``: ``"f32"`` and at least the control one step below what
  the configuration states (``"a8"`` here); ``logits_fn`` refuses others;
- ``geometry(cfg)``: a dict with at least ``D, V, H, Hk, Dh`` (the kernel
  readers' shapes);
- optionally ``layer_matmuls(g)``: result width -> the ``qmm_cost``
  arguments ``(K, weights in the call, residual fused)`` of one layer's
  matmuls of that width, for ``perf/metrics/qmm_roofline.py`` (which
  matmuls a layer has is the family's knowledge; without it that reader
  says nothing);
- optionally ``FAMILIES``: the ``model_type`` values it covers beside its
  own file name.

What is the same for every family stays in ``check.py``: the probe, the
padding and blocking of rows, ``chosen_logprobs``, ``compare``.

THIS MODULE. Straightforward ``jax.numpy`` at
``jax.default_matmul_precision("highest")`` — no kernels, no cache, no batching tricks. It takes NOTHING from the
program: the weights are drawn here from the seed by the same recipe the
program's random initialiser documents (``models/quant.py``
``init_params_quantized``: parameter i of ``param_shapes`` order, layer j,
``normal(fold_in(fold_in(PRNGKey(seed), i), j)) / sqrt(fan_in)``, then
symmetric per-output-channel int8), the recipe being part of what a
configuration file states (``assumed``). The int8 values and scales are
then used in float32: the configuration states int8 WEIGHTS, and bf16
for everything else, which the reference replaces by float32.

Departures from the published architectures: none in the equations
(RMSNorm, rotary embeddings in the half-split layout, grouped-query
causal attention with the sliding window where the config has one,
SiLU-gated MLP, q/k/v bias for qwen2, untied head).

``precision`` selects the CONTROL variants, the reference computed one
step below what the configuration states:
  "a8"  — int8 activations: the input of every matmul is quantised per
          token, symmetric, to 8 bits (bf16 -> int8, the step a W8A8
          kernel would take);
  "f8"  — fp8 activations: the input of every matmul is rounded to
          float8_e4m3fn (bf16 -> fp8);
  "w4"  — int4 weights: the same draw quantised to 4 bits per output
          channel (int8 -> int4).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FAMILIES = ("llama", "mistral", "qwen2")
PRECISIONS = ("f32", "a8", "f8", "w4")

# models/llama.py param_shapes order: the index is part of the recipe
PARAM_ORDER = ("embed", "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
               "final_norm", "lm_head", "bq", "bk", "bv",
               "w_gate", "w_up", "w_down")
_BIAS = ("bq", "bk", "bv")


def geometry(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    g = dict(
        L=cfg["num_hidden_layers"], D=cfg["hidden_size"],
        F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
        Hk=cfg["num_key_value_heads"],
        Dh=cfg.get("head_dim") or cfg["hidden_size"] // H,
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        bias=cfg.get("model_type") == "qwen2" or bool(cfg.get("attention_bias")),
    )
    window = cfg.get("sliding_window")
    if cfg.get("model_type") == "qwen2" and not cfg.get("use_sliding_window", False):
        window = None
    g["window"] = window
    return g


def layer_matmuls(g: dict) -> dict:
    """Result width -> the ``qmm_cost`` arguments of a layer's matmuls of
    that width: (K, weights in the call, residual fused)."""
    D, F, q, kv = g["D"], g["F"], g["H"] * g["Dh"], g["Hk"] * g["Dh"]
    by_n: dict = {}
    for n, k, weights, residual in ((q, D, 1, False), (kv, D, 1, False),
                                    (kv, D, 1, False), (D, q, 1, True),
                                    (F, D, 2, False), (D, F, 1, True)):
        by_n.setdefault(n, []).append((k, weights, residual))
    return by_n


def param_index(g: dict) -> dict[str, int]:
    names = [n for n in PARAM_ORDER if g["bias"] or n not in _BIAS]
    return {n: i for i, n in enumerate(names)}


def _quantise(w, axis: int, bits: int):
    """Symmetric per-channel quantisation over ``axis``; returns the
    dequantised float32 weight (values x scales)."""
    top = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / top
    return jnp.clip(jnp.round(w / scale), -top, top) * scale


def _draw(key, shape, fan_in: int, axis: int, bits: int):
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(max(1, fan_in)))
    return _quantise(w, axis, bits)


def _act_quant(x, precision: str):
    if precision == "f8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision != "a8":
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rmsnorm(x, eps: float):
    # norm weights are ones in the seeded draw
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta: float):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits_fn(cfg: dict, precision: str = "f32"):
    """``f(seed_key, tokens [B, T], lengths [B], at [B, P]) -> logits
    [B, P, V]`` at the positions ``at`` of each sequence, float32.
    The whole model in one jitted call: weights are drawn layer by layer
    inside the scan, so at most one layer's float32 weights exist."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")
    g = geometry(cfg)
    idx = param_index(g)
    bits = 4 if precision == "w4" else 8
    L, D, F, V, H, Hk, Dh = (g[k] for k in ("L", "D", "F", "V", "H", "Hk", "Dh"))

    def mm(x, w):
        return jnp.dot(_act_quant(x, precision), w)

    def f(key, tokens, lengths, at):
        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        kq = pos[:, None, :]          # key positions   [B, 1, T]
        qp = pos[:, :, None]          # query positions [B, T, 1]
        mask = (kq <= qp) & (kq < lengths[:, None, None])
        if g["window"] is not None:
            mask = mask & (kq > qp - g["window"])

        def sub(name):
            return jax.random.fold_in(key, idx[name])

        # embedding rows: per-ROW scales (axis -1), gathered after the draw
        embed = _draw(sub("embed"), (V, D), V, -1, bits)
        x = jnp.take(embed, tokens, axis=0)
        if g["bias"]:
            biases = {
                n: (jax.random.normal(sub(n), (L, w), jnp.float32)
                    * (1.0 / math.sqrt(L))).astype(jnp.bfloat16).astype(jnp.float32)
                for n, w in (("bq", H * Dh), ("bk", Hk * Dh), ("bv", Hk * Dh))
            }
        else:
            biases = {}

        def layer(x, j):
            def w(name, shape):
                return _draw(jax.random.fold_in(sub(name), j), shape,
                             shape[0], -2, bits)

            h = _rmsnorm(x, g["eps"])
            q, k, v = (mm(h, w("wq", (D, H * Dh))), mm(h, w("wk", (D, Hk * Dh))),
                       mm(h, w("wv", (D, Hk * Dh))))
            if g["bias"]:
                q, k, v = q + biases["bq"][j], k + biases["bk"][j], v + biases["bv"][j]
            q = _rope(q.reshape(B, T, H, Dh), pos, g["theta"])
            k = _rope(k.reshape(B, T, Hk, Dh), pos, g["theta"])
            v = v.reshape(B, T, Hk, Dh)
            qg = q.reshape(B, T, Hk, H // Hk, Dh)
            s = jnp.einsum("btkgd,bskd->bkgts", qg, k) / math.sqrt(Dh)
            s = jnp.where(mask[:, None, None, :, :], s, -1e30)
            a = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, axis=-1), v)
            x = x + mm(a.reshape(B, T, H * Dh), w("wo", (H * Dh, D)))
            h = _rmsnorm(x, g["eps"])
            gate, up = mm(h, w("w_gate", (D, F))), mm(h, w("w_up", (D, F)))
            x = x + mm(jax.nn.silu(gate) * up, w("w_down", (F, D)))
            return x, None

        x, _ = jax.lax.scan(layer, x, jnp.arange(L))
        x = _rmsnorm(x, g["eps"])
        x_at = jnp.take_along_axis(x, at[:, :, None], axis=1)       # [B, P, D]
        head = _draw(sub("lm_head"), (D, V), D, -2, bits)
        return mm(x_at, head)

    def run(seed: int, tokens, lengths, at):
        with jax.default_matmul_precision("highest"):
            return jitted(jax.random.PRNGKey(seed), tokens, lengths, at)

    jitted = jax.jit(f)
    return run
