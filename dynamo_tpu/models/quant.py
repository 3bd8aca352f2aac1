"""Weight-only int8 quantization for the serving path.

Symmetric per-channel int8: each matmul weight stores an int8 tensor plus
an f32 scale per output channel (per vocab row for the embedding table).
The matmul runs in bf16 on the MXU with the int8 weight upcast on the fly
— HBM reads halve, which directly doubles the decode-throughput roofline
of a bandwidth-bound engine, and the real 8B flagship shape fits a single
16 GB v5e chip (bf16 does not).

The reference reaches the same operating point externally (FP8/AWQ
checkpoints served through vLLM/TRT-LLM, e.g. the
R1-Distill-Llama-70B-FP8-dynamic benchmark model,
examples/llm/benchmarks/README.md); here quantization is a first-class
engine knob (EngineConfig.quantization = "int8") applied at load time to
any bf16/f32 checkpoint.

Numerics: scale = amax/127 over the contraction axis, round-to-nearest,
error ~0.4% per weight — logits track bf16 closely (see
tests/test_quantization.py for the bound enforced in CI).
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

# weight name -> contraction axis reduced over when computing scales
# (the scale then broadcasts over the matmul output's channel axis)
QUANT_AXIS: dict[str, int] = {
    "wq": -2,
    "wk": -2,
    "wv": -2,
    "wo": -2,
    "w_gate": -2,
    "w_up": -2,
    "w_down": -2,
    "lm_head": -2,
    # embedding rows are gathered, not contracted: per-row scales,
    # applied to the gathered rows after lookup
    "embed": -1,
}

SCALE_SUFFIX = "_scale"


def is_quantized_name(name: str) -> bool:
    return name.endswith(SCALE_SUFFIX)


def np_to_f32(arr: np.ndarray) -> np.ndarray:
    """Checkpoint array -> f32, handling bf16 stored as raw uint16."""
    if arr.dtype == np.uint16:
        return (arr.astype(np.uint32) << 16).view(np.float32)
    return np.asarray(arr, np.float32)


def quantize_array(
    arr: np.ndarray, axis: int
) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-channel int8: -> (int8 values, f32 scales with
    ``axis`` dropped)."""
    a = np_to_f32(arr)
    amax = np.max(np.abs(a), axis=axis, keepdims=True)
    scale = np.maximum(amax, 1e-12) / 127.0
    q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
    return q, np.squeeze(scale, axis=axis).astype(np.float32)


def scale_spec(weight_spec, axis: int):
    """PartitionSpec for a scale tensor: the weight's spec with the
    contraction axis dropped (scales follow the output-channel sharding)."""
    from jax.sharding import PartitionSpec as P

    entries = list(weight_spec)
    del entries[axis]
    return P(*entries)


def init_params_quantized(
    cfg,
    seed: int = 0,
    mesh=None,
    specs: Optional[dict] = None,
):
    """Random-init already-quantized params (bench/tests without a
    checkpoint). Unlike init_params→quantize, the full bf16 pytree is
    NEVER materialized — the 8B flagship shape in bf16 would not fit the
    single 16 GB chip that int8 serving targets. Weights generate AND
    quantize on device, one leading slice at a time (f32 transient ≈ one
    layer), so nothing big crosses the host↔device link."""
    import math

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from dynamo_tpu.models.llama import param_shapes, param_specs

    shapes = param_shapes(cfg)
    specs = specs if specs is not None else param_specs(cfg)
    key = jax.random.PRNGKey(seed)
    params: dict[str, Any] = {}

    def gen_slice(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    def dev_quantize(arr, axis):
        amax = jnp.max(jnp.abs(arr), axis=axis, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(arr / scale), -127, 127).astype(jnp.int8)
        return q, jnp.squeeze(scale, axis=axis)

    def put(name: str, arr, spec) -> Any:
        if mesh is not None:
            arr = jax.device_put(arr, NamedSharding(mesh, spec))
        return arr

    for i, (name, (shape, dtype)) in enumerate(shapes.items()):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = 1.0 / math.sqrt(max(1, fan_in))
        k_name = jax.random.fold_in(key, i)
        if name not in QUANT_AXIS:
            if name.endswith("norm"):
                arr = jnp.ones(shape, dtype)
            else:
                arr = gen_slice(k_name, shape, std).astype(dtype)
            params[name] = put(name, arr, specs[name])
            continue
        axis = QUANT_AXIS[name]
        gq = jax.jit(lambda k: dev_quantize(gen_slice(k, shape[1:], std), axis)) \
            if len(shape) >= 3 else None
        if len(shape) >= 3:
            # stacked (leading L / L,E): slice-wise to bound the f32
            # transient to one layer
            qs, ss = [], []
            for j in range(shape[0]):
                q, s = gq(jax.random.fold_in(k_name, j))
                qs.append(q)
                ss.append(s)
            q_arr, s_arr = jnp.stack(qs), jnp.stack(ss)
        else:
            q_arr, s_arr = jax.jit(
                lambda k: dev_quantize(gen_slice(k, shape, std), axis)
            )(k_name)
        params[name] = put(name, q_arr, specs[name])
        params[name + SCALE_SUFFIX] = put(
            name + SCALE_SUFFIX, s_arr, scale_spec(specs[name], axis)
        )
    return params


def quantize_params_pytree(
    params: dict[str, Any],
    mesh=None,
    specs: Optional[dict] = None,
) -> dict[str, Any]:
    """Quantize an already-materialized (e.g. random-init) param pytree.
    Device arrays round-trip through the host; use the loader's streaming
    path for real checkpoints."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    out: dict[str, Any] = {}
    for name, arr in params.items():
        if name not in QUANT_AXIS:
            out[name] = arr
            continue
        axis = QUANT_AXIS[name]
        host = np.asarray(jnp.asarray(arr, jnp.float32))
        q, s = quantize_array(host, axis)
        qj, sj = jnp.asarray(q), jnp.asarray(s)
        if mesh is not None and specs is not None:
            wspec = specs[name]
            qj = jax.device_put(qj, NamedSharding(mesh, wspec))
            sj = jax.device_put(
                sj, NamedSharding(mesh, scale_spec(wspec, axis))
            )
        out[name] = qj
        out[name + SCALE_SUFFIX] = sj
    return out
