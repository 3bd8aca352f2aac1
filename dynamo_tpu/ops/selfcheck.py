"""Pallas kernels vs the plain XLA paths they replace, on the device.

``python -m dynamo_tpu.ops.selfcheck '<json spec>'`` runs every kernel
of the serving path once on fixed seeded inputs at the spec's widths and
prints ONE JSON line: the device JAX found, and per kernel the largest
difference from the XLA reference (the ``mm`` mixed-dot epilogue of
models/llama.py for the fused-dequant matmuls, the gather path
``paged_attention_reference`` for the attention kernels), normalised by
the reference's largest magnitude, with the tolerance it was held to.
Exit code 1 when any kernel is outside it or produced a non-finite
value — a kernel that compiles and computes garbage fails here.

Off-TPU the kernels run interpreted (the CPU rehearsal of
``chip_smoke.py``); the line says so. This process takes the chip: run
it before, never beside, a server.

Spec keys: ``D, F, V, H, Hk, Dh`` (model widths), ``block_size``,
``m`` (token rows of the matmul checks), ``ctx`` (decode context
lengths, one sequence each), ``prefill`` ([prior context, chunk]),
``seed``, and optionally ``require_platform`` (report the device and
stop when JAX found another one).
"""

from __future__ import annotations

import json
import sys
import time

# max |kernel - reference| / max |reference|. Both sides feed the same
# bf16 values to f32 accumulators and round the result to bf16: they
# differ by summation order and one bf16 rounding (2^-8 relative), and
# the attention paths by the bf16 rounding of the softmax weights too.
TOLERANCE = 2e-2


def _norm_diff(out, ref) -> tuple[float, bool]:
    import numpy as np

    a = np.asarray(out, np.float32)
    b = np.asarray(ref, np.float32)
    finite = bool(np.isfinite(a).all())
    denom = float(np.max(np.abs(b))) or 1.0
    return float(np.max(np.abs(a - b))) / denom, finite


def _matmul_checks(spec: dict, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.qmatmul import qmm, qmm_gate_up, qmm_lm_head

    D, F, V = spec["D"], spec["F"], spec["V"]
    H, Hk, Dh, m = spec["H"], spec["Hk"], spec["Dh"], spec["m"]
    key = jax.random.PRNGKey(spec["seed"])

    def weight(i: int, k: int, n: int):
        w = jax.random.randint(
            jax.random.fold_in(key, i), (k, n), -127, 128, jnp.int8
        )
        s = jax.random.uniform(
            jax.random.fold_in(key, 100 + i), (n,), jnp.float32,
            0.5 / (127 * k ** 0.5), 1.5 / (127 * k ** 0.5),
        )
        return w, s

    def act(i: int, k: int):
        return jax.random.normal(
            jax.random.fold_in(key, 200 + i), (m, k), jnp.float32
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
        "qmm_wq": (D, H * Dh, False),
        "qmm_wkv": (D, Hk * Dh, False),
        "qmm_wo_residual": (H * Dh, D, True),
        "qmm_w_down_residual": (F, D, True),
    }
    for i, (name, (k, n, residual)) in enumerate(cases.items()):
        x, (w, s) = act(i, k), weight(i, k, n)
        if residual:
            r = act(50 + i, n)
            got = jax.jit(
                lambda x, w, s, r: qmm(x, w, s, residual=r, interpret=interpret)
            )(x, w, s, r)
            want = jax.jit(lambda x, w, s, r: r + ref_mm(x, w, s))(x, w, s, r)
        else:
            got = jax.jit(
                lambda x, w, s: qmm(x, w, s, interpret=interpret)
            )(x, w, s)
            want = jax.jit(ref_mm)(x, w, s)
        out[name] = _norm_diff(got, want)

    x, (wg, sg), (wu, su) = act(10, D), weight(10, D, F), weight(11, D, F)
    got = jax.jit(
        lambda x, wg, sg, wu, su: qmm_gate_up(
            x, wg, sg, wu, su, act="silu", interpret=interpret
        )
    )(x, wg, sg, wu, su)
    want = jax.jit(
        lambda x, wg, sg, wu, su: jax.nn.silu(ref_mm(x, wg, sg))
        * ref_mm(x, wu, su)
    )(x, wg, sg, wu, su)
    out["qmm_gate_up"] = _norm_diff(got, want)

    x, (w, s) = act(12, D), weight(12, D, V)
    got = jax.jit(lambda x, w, s: qmm_lm_head(x, w, s, interpret=interpret))(
        x, w, s
    )
    out["qmm_lm_head"] = _norm_diff(got, jax.jit(ref_mm)(x, w, s))
    return out


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
        out[f"attn_decode_{name}"] = _norm_diff(got, want)
        got = jax.jit(
            lambda q, k, v, t, s, c, **kw: paged_attention_prefill_stacked(
                q, k, v, jnp.int32(layer), t, s, c, block_size=bs,
                interpret=interpret, **kw,
            )
        )(q_pre, k, v, pre_tables, pre_start, pre_ctx, **scales)
        want = paged_attention_reference(
            q_pre, k_l, v_l, pre_tables, pre_pos, pre_ctx, bs
        )
        out[f"attn_prefill_{name}"] = _norm_diff(got, want)
    return out


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
            "interpreted": interpret, "tolerance": TOLERANCE, "kernels": {},
            "seconds": 0.0, "ok": False,
            "error": f"platform is {device['platform']!r}, not {want!r}",
        }
    results = {
        **_matmul_checks(spec, interpret),
        **_attention_checks(spec, interpret),
    }
    kernels = {
        name: {"max_norm_diff": round(diff, 6), "finite": finite}
        for name, (diff, finite) in results.items()
    }
    return {
        "phase": "kernels_vs_reference",
        "device": device,
        "interpreted": interpret,
        "tolerance": TOLERANCE,
        "kernels": kernels,
        "seconds": round(time.monotonic() - t0, 1),
        "ok": all(
            k["finite"] and k["max_norm_diff"] <= TOLERANCE
            for k in kernels.values()
        ),
    }


if __name__ == "__main__":
    report = run(json.loads(sys.argv[1]))
    print(json.dumps(report), flush=True)
    sys.exit(0 if report["ok"] else 1)
