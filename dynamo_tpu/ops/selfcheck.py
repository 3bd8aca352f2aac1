"""Pallas kernels vs the plain XLA paths they replace, on the device.

``python -m dynamo_tpu.ops.selfcheck '<json spec>'`` runs every kernel
of the serving path once on fixed seeded inputs at the spec's widths and
prints ONE JSON line: the device JAX found, and per kernel how far it is
from the XLA reference (the ``mm`` mixed-dot epilogue of models/llama.py
for the fused-dequant matmuls, the gather path
``paged_attention_reference`` for the attention kernels) and the bound
it was held to — a bound tied to the kernel's arithmetic, so an error
confined to small outputs does not hide behind the largest one:

- matmuls, ELEMENTWISE in bf16 ulps of the terms the output is rounded
  from (``ULP_LIMITS``);
- attention, per query row, the largest difference over the row's
  largest reference magnitude (``ROW_TOLERANCE``) — a long context
  averages its values down to a small row that a whole-tensor norm
  would never see.

Exit code 1 when any kernel is outside its bound or produced a
non-finite value — a kernel that compiles and computes garbage fails
here.

Off-TPU the kernels run interpreted (the CPU rehearsal of
``chip_smoke.py``); the line says so. This process takes the chip: run
it before, never beside, a server.

Spec keys: ``D, F, V, H, Hk, Dh`` (model widths), ``block_size``,
``m`` (token rows of the matmul checks), ``m_large`` (rows of the one
prefill-sized ``qmm`` case, where the M tiling differs), ``ctx`` (decode
context lengths, one sequence each), ``prefill`` ([prior context,
chunk]), ``seed``, and optionally ``require_platform`` (report the
device and stop when JAX found another one).
"""

from __future__ import annotations

import json
import sys
import time

# Attention: per query row, max |kernel - reference| / max |reference|.
# Both sides feed the same bf16 (or dequantised int8) values to f32
# accumulators and round to bf16; they differ by summation order, the
# bf16 rounding of the softmax weights, and so by ~1 ulp at the row's
# largest element (2^-7 of it at worst), plus ~2^-9 each from the
# weights' and the dequantised values' rounding. Held to 4 * 2^-7.
ROW_TOLERANCE = 2.0 ** -5

# Matmuls: |kernel - reference| <= limit * 2^-7 * base, elementwise.
# 2^-7 * |x| bounds one bf16 ulp at x. Kernel and reference feed the
# same exact products to f32 accumulators and differ in summation ORDER
# only, so the value each rounds to bf16 agrees to ~1e-6 of the output
# scale: one flipped rounding at most where nothing else is rounded.
#   qmm / lm_head        base = |out|: 1 flip, held to 2.
#   + residual           base = |out| + |mm|: the product's flip and the
#                        sum's, each in its own ulp; held to 2.
#   gate_up              base = |out| (silu) — g's flip through silu
#                        (slope <= 1.4 at these |g|), silu's own, u's
#                        and the product's: <= 4.4; held to 8. tanh-gelu:
#                        base = |out| + |g*u|/2, because 0.5*g*(1+tanh)
#                        cancels for g < 0 and the reference's bf16
#                        chain rounds the addends, not the result.
# Below ULP_FLOOR of the largest reference magnitude an output's own ulp
# is smaller than the f32 summation noise (~1e-5 of the scale at
# K=14336), so the base is not let under it.
ULP_LIMITS = {"qmm": 2.0, "qmm_residual": 2.0, "gate_up": 8.0}
ULP_FLOOR = 2.0 ** -8


def _ulps(out, ref, extra=None) -> tuple[float, bool]:
    """max over elements of |out-ref| / (2^-7 * base), and finiteness;
    base = max(|out|, |ref|) + extra, floored (see above)."""
    import numpy as np

    a = np.asarray(out, np.float32)
    b = np.asarray(ref, np.float32)
    base = np.maximum(np.abs(a), np.abs(b))
    if extra is not None:
        base = base + np.abs(np.asarray(extra, np.float32))
    base = np.maximum(base, ULP_FLOOR * float(np.max(np.abs(b))))
    finite = bool(np.isfinite(a).all())
    return float(np.max(np.abs(a - b) / (2.0 ** -7 * base))), finite


def _row_diff(out, ref) -> tuple[float, bool]:
    """max over rows (one query token's one head) of the row's largest
    difference over its largest reference magnitude."""
    import numpy as np

    a = np.asarray(out, np.float32)
    a = a.reshape(-1, a.shape[-1])
    b = np.asarray(ref, np.float32).reshape(a.shape)
    finite = bool(np.isfinite(a).all())
    denom = np.maximum(np.max(np.abs(b), axis=1), 1e-30)
    return float(np.max(np.max(np.abs(a - b), axis=1) / denom)), finite


def _matmul_checks(spec: dict, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.qmatmul import qmm, qmm_gate_up, qmm_lm_head

    D, F, V = spec["D"], spec["F"], spec["V"]
    H, Hk, Dh, m = spec["H"], spec["Hk"], spec["Dh"], spec["m"]
    key = jax.random.PRNGKey(spec["seed"])

    # the served form of a layer's matmuls: the kernel reads layer
    # LAYER of a stacked [LAYERS, K, N] weight through a traced index
    LAYERS, LAYER = 2, 1

    def weight(i: int, k: int, n: int, stacked: bool = False):
        lead = (LAYERS,) if stacked else ()
        w = jax.random.randint(
            jax.random.fold_in(key, i), (*lead, k, n), -127, 128, jnp.int8
        )
        s = jax.random.uniform(
            jax.random.fold_in(key, 100 + i), (*lead, n), jnp.float32,
            0.5 / (127 * k ** 0.5), 1.5 / (127 * k ** 0.5),
        )
        return w, s

    def layer_of(stacked: bool, *arrays):
        """(the traced layer index or None, each array's LAYER slice)."""
        if not stacked:
            return None, arrays
        return jnp.int32(LAYER), tuple(a[LAYER] for a in arrays)

    def act(i: int, k: int, rows: int = m):
        return jax.random.normal(
            jax.random.fold_in(key, 200 + i), (rows, k), jnp.float32
        ).astype(jnp.bfloat16)

    def ref_mm(x, w, s):
        # the reference epilogue of models.llama.mm
        y = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * s).astype(x.dtype)

    out: dict = {}
    cases = {
        "qmm_wq": (D, H * Dh, False, m, False),
        "qmm_wkv": (D, Hk * Dh, False, m, False),
        "qmm_wo_residual": (H * Dh, D, True, m, False),
        "qmm_w_down_residual": (F, D, True, m, False),
        # a prefill rectangle: more than one M tile
        f"qmm_wq_m{spec['m_large']}": (D, H * Dh, False, spec["m_large"],
                                      False),
        "qmm_wq_stacked": (D, H * Dh, False, m, True),
        "qmm_w_down_residual_stacked": (F, D, True, m, True),
    }
    for i, (name, (k, n, residual, rows, stacked)) in enumerate(cases.items()):
        x, (w, s) = act(i, k, rows), weight(i, k, n, stacked)
        layer, (w_l, s_l) = layer_of(stacked, w, s)
        product = jax.jit(ref_mm)(x, w_l, s_l)
        if residual:
            r = act(50 + i, n, rows)
            got = jax.jit(
                lambda x, w, s, r, l: qmm(
                    x, w, s, residual=r, interpret=interpret, layer=l
                )
            )(x, w, s, r, layer)
            out[name] = (*_ulps(got, r + product, extra=product),
                         ULP_LIMITS["qmm_residual"])
        else:
            got = jax.jit(
                lambda x, w, s, l: qmm(x, w, s, interpret=interpret, layer=l)
            )(x, w, s, layer)
            out[name] = (*_ulps(got, product), ULP_LIMITS["qmm"])

    # the gate activations of models.llama._mlp_act on the bf16 gate
    x = act(10, D)
    for stacked, suffix, names in ((False, "", ("silu", "gelu")),
                                   (True, "_stacked", ("silu",))):
        (wg, sg), (wu, su) = weight(10, D, F, stacked), weight(11, D, F, stacked)
        layer, (wg_l, sg_l, wu_l, su_l) = layer_of(stacked, wg, sg, wu, su)
        g, u = jax.jit(ref_mm)(x, wg_l, sg_l), jax.jit(ref_mm)(x, wu_l, su_l)
        acts = {
            "silu": (jax.nn.silu, None),
            "gelu": (lambda g: jax.nn.gelu(g, approximate=True),
                     0.5 * g.astype(jnp.float32) * u.astype(jnp.float32)),
        }
        for name in names:
            fn, extra = acts[name]
            got = jax.jit(
                lambda x, wg, sg, wu, su, l: qmm_gate_up(
                    x, wg, sg, wu, su, act=name, interpret=interpret, layer=l
                )
            )(x, wg, sg, wu, su, layer)
            want = jax.jit(lambda g, u: fn(g) * u)(g, u)
            out[f"qmm_gate_up_{name}{suffix}"] = (
                *_ulps(got, want, extra=extra), ULP_LIMITS["gate_up"]
            )

    x, (w, s) = act(12, D), weight(12, D, V)
    got = jax.jit(lambda x, w, s: qmm_lm_head(x, w, s, interpret=interpret))(
        x, w, s
    )
    out["qmm_lm_head"] = (
        *_ulps(got, jax.jit(ref_mm)(x, w, s)), ULP_LIMITS["qmm"]
    )
    return {
        name: {"max_ulps": round(ulps, 3), "limit": limit, "finite": finite}
        for name, (ulps, finite, limit) in out.items()
    }


def _attention_checks(spec: dict, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import paged_attention_reference
    from dynamo_tpu.ops.kv_quant import quantize_kv
    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode_stacked,
        paged_attention_prefill_stacked,
    )

    H, Hk, Dh, bs = spec["H"], spec["Hk"], spec["Dh"], spec["block_size"]
    L, layer = 2, 1  # a stacked cache; attend over its second layer
    rng = np.random.default_rng(spec["seed"])
    ctx = list(spec["ctx"])
    prior, chunk = spec["prefill"]
    lens = ctx + [prior + chunk]
    pages = [-(-c // bs) for c in lens]
    n_blocks = 1 + sum(pages)  # block 0 is the reserved scratch block
    W = max(pages)
    tables = np.zeros((len(lens), W), np.int32)
    ids = rng.permutation(np.arange(1, n_blocks, dtype=np.int32))
    at = 0
    for b, n in enumerate(pages):
        tables[b, :n] = ids[at:at + n]
        at += n
    kf = rng.standard_normal((L, n_blocks * bs, Hk, Dh)).astype(np.float32)
    vf = rng.standard_normal((L, n_blocks * bs, Hk, Dh)).astype(np.float32)
    caches = {"bf16": (jnp.asarray(kf, jnp.bfloat16),
                       jnp.asarray(vf, jnp.bfloat16), None, None)}

    def quantized(x):
        q, sc = quantize_kv(jnp.asarray(x))  # sc: [L, slots, Hk]
        sc = sc.reshape(L, n_blocks, bs, Hk).transpose(0, 1, 3, 2)
        return q, sc  # scales stored [L, N, Hk, bs] (ops/kv_quant.py)

    (kq, ks), (vq, vs) = quantized(kf), quantized(vf)
    caches["int8"] = (kq, vq, ks, vs)

    B = len(ctx)
    q_dec = jnp.asarray(
        rng.standard_normal((B, H, Dh)), jnp.bfloat16
    )
    q_pre = jnp.asarray(
        rng.standard_normal((1, chunk, H, Dh)), jnp.bfloat16
    )
    dec_tables = jnp.asarray(tables[:B])
    dec_ctx = jnp.asarray(ctx, jnp.int32)
    pre_tables = jnp.asarray(tables[B:])
    pre_ctx = jnp.asarray([prior + chunk], jnp.int32)
    pre_start = jnp.asarray([prior], jnp.int32)
    pre_pos = (prior + jnp.arange(chunk, dtype=jnp.int32))[None]
    dec_pos = (dec_ctx - 1)[:, None]

    out: dict = {}
    for name, (k, v, ksc, vsc) in caches.items():
        scales = {} if ksc is None else {"k_scale": ksc, "v_scale": vsc}
        if ksc is None:
            k_l, v_l = k[layer], v[layer]
        else:
            k_l, v_l = (k[layer], ksc[layer]), (v[layer], vsc[layer])
        got = jax.jit(
            lambda q, k, v, t, c, **kw: paged_attention_decode_stacked(
                q, k, v, jnp.int32(layer), t, c, block_size=bs,
                interpret=interpret, **kw,
            )
        )(q_dec, k, v, dec_tables, dec_ctx, **scales)
        want = paged_attention_reference(
            q_dec[:, None], k_l, v_l, dec_tables, dec_pos, dec_ctx, bs
        )[:, 0]
        out[f"attn_decode_{name}"] = _row_diff(got, want)
        got = jax.jit(
            lambda q, k, v, t, s, c, **kw: paged_attention_prefill_stacked(
                q, k, v, jnp.int32(layer), t, s, c, block_size=bs,
                interpret=interpret, **kw,
            )
        )(q_pre, k, v, pre_tables, pre_start, pre_ctx, **scales)
        want = paged_attention_reference(
            q_pre, k_l, v_l, pre_tables, pre_pos, pre_ctx, bs
        )
        out[f"attn_prefill_{name}"] = _row_diff(got, want)
    return {
        name: {"max_row_diff": round(diff, 6), "limit": ROW_TOLERANCE,
               "finite": finite}
        for name, (diff, finite) in out.items()
    }


def run(spec: dict) -> dict:
    """The report (see the module docstring); ``ok`` sums it up."""
    import jax

    from dynamo_tpu.utils.jaxtools import describe_devices, enable_compile_cache

    enable_compile_cache()
    t0 = time.monotonic()
    device = describe_devices()
    interpret = jax.default_backend() != "tpu"
    want = spec.get("require_platform")
    if want and device["platform"] != want:
        # nothing is computed on a device the caller will refuse anyway
        return {
            "phase": "kernels_vs_reference", "device": device,
            "interpreted": interpret, "kernels": {},
            "seconds": 0.0, "ok": False,
            "error": f"platform is {device['platform']!r}, not {want!r}",
        }
    kernels = {
        **_matmul_checks(spec, interpret),
        **_attention_checks(spec, interpret),
    }
    return {
        "phase": "kernels_vs_reference",
        "device": device,
        "interpreted": interpret,
        "kernels": kernels,
        "seconds": round(time.monotonic() - t0, 1),
        "ok": all(
            k["finite"] and k.get("max_ulps", k.get("max_row_diff")) <= k["limit"]
            for k in kernels.values()
        ),
    }


if __name__ == "__main__":
    report = run(json.loads(sys.argv[1]))
    print(json.dumps(report), flush=True)
    sys.exit(0 if report["ok"] else 1)
