"""Load HF-format (safetensors) checkpoints into the stacked-layer pytree.

Analogue of the reference's model resolution path (reference:
lib/llm/src/local_model.rs, hub.rs — resolve local dir / download), minus
the hub download (deployments mount weights locally; zero-egress builds use
random init). Torch checkpoints store linear weights as [out, in]; our
params are [in, out], so projections are transposed on load. Per-layer
tensors are stacked onto the leading L axis to match the lax.scan layout.
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import Params, param_shapes, param_specs

log = logging.getLogger("dynamo_tpu.models.loader")

# our-name -> (hf per-layer template | hf global name, transpose?)
_LAYER_MAP = {
    "attn_norm": ("model.layers.{i}.input_layernorm.weight", False),
    "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
    "mlp_norm": ("model.layers.{i}.post_attention_layernorm.weight", False),
    "w_gate": ("model.layers.{i}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{i}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{i}.mlp.down_proj.weight", True),
    # qwen2-family QKV biases (only read when cfg.attention_bias)
    "bq": ("model.layers.{i}.self_attn.q_proj.bias", False),
    "bk": ("model.layers.{i}.self_attn.k_proj.bias", False),
    "bv": ("model.layers.{i}.self_attn.v_proj.bias", False),
}
# Mixtral-style MoE: router + per-expert w1(gate)/w3(up)/w2(down)
_MOE_LAYER_MAP = {
    "attn_norm": ("model.layers.{i}.input_layernorm.weight", False),
    "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
    "mlp_norm": ("model.layers.{i}.post_attention_layernorm.weight", False),
    "router": ("model.layers.{i}.block_sparse_moe.gate.weight", True),
    "w_gate": ("model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight", True),
    "w_up": ("model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight", True),
    "w_down": ("model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight", True),
    "bq": ("model.layers.{i}.self_attn.q_proj.bias", False),
    "bk": ("model.layers.{i}.self_attn.k_proj.bias", False),
    "bv": ("model.layers.{i}.self_attn.v_proj.bias", False),
}
_GLOBAL_MAP = {
    "embed": ("model.embed_tokens.weight", False),
    "final_norm": ("model.norm.weight", False),
    "lm_head": ("lm_head.weight", True),
}


def has_weights(model_dir: str) -> bool:
    return bool(glob.glob(os.path.join(model_dir, "*.safetensors")))


def resolve_model(
    model_path: str,
    model_config: Optional[ModelConfig] = None,
    random_weights: bool = False,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    specs_fn: Optional[Any] = None,
    quantize: Optional[str] = None,
):
    """Single entry for model bring-up: (ModelConfig, Params) from a
    single-file GGUF, an HF-format directory, or random init. The one
    copy of the load-priority cascade — the engine and the
    sequence-parallel prefill worker both go through here. ``specs_fn``
    maps the resolved ModelConfig to PartitionSpec overrides (e.g.
    pp-sharded layer stacks) and may validate/raise before any weight
    loads. ``quantize="int8"`` applies weight-only int8 at load
    (models/quant.py) regardless of source."""
    from dynamo_tpu.models import family
    from dynamo_tpu.models.llama import init_params

    if quantize not in (None, "int8"):
        raise ValueError(f"unsupported quantization {quantize!r}")
    if model_path and not random_weights:
        # repo-id paths resolve through the (gated) hub cache
        from dynamo_tpu.models.hub import resolve_hub_model

        model_path = resolve_hub_model(model_path)
    is_gguf = bool(model_path) and model_path.endswith(".gguf")
    reader = None
    try:
        if is_gguf and (model_config is None or not random_weights):
            # one reader for config AND weights: header parsing decodes
            # the full embedded vocab, don't pay it twice — and don't
            # pay it at all when neither is needed
            from dynamo_tpu.gguf import GGUFReader

            reader = GGUFReader(model_path)
        if model_config is None:
            if reader is not None:
                from dynamo_tpu.gguf import config_from_gguf

                model_config = config_from_gguf(reader)
            else:
                model_config = ModelConfig.from_dir(model_path)
        specs = specs_fn(model_config) if specs_fn is not None else None
        fam = family(model_config)
        if hasattr(fam, "init_params_quantized"):
            # a family with its own parameters draws them itself
            if not random_weights:
                raise NotImplementedError(
                    f"model_type {model_config.model_type!r}: no checkpoint "
                    "loader yet (random_weights only)"
                )
            log.warning("initializing RANDOM weights (%s)", quantize or "bf16")
            init = fam.init_params_quantized if quantize == "int8" \
                else fam.init_params
            return model_config, init(model_config, seed, mesh, specs)
        if not random_weights and reader is not None:
            from dynamo_tpu.gguf import load_params_from_gguf

            params = load_params_from_gguf(
                model_config, reader, mesh, specs, quantize=quantize
            )
        elif not random_weights and model_path and has_weights(model_path):
            # multi-process bring-up defaults to the shard-aware loader:
            # every rank materializing the full stacked weights would
            # need ~model-size host RAM per host (70B int8 = ~70 GB).
            # Force on/off with DYN_SHARDED_LOAD=1/0.
            knob = os.environ.get("DYN_SHARDED_LOAD", "")
            sharded = (
                knob == "1"
                or (knob != "0" and mesh is not None
                    and jax.process_count() > 1)
            )
            if sharded and mesh is not None:
                params = load_params_sharded(
                    model_config, model_path, mesh, specs, quantize=quantize
                )
            else:
                params = load_params(
                    model_config, model_path, mesh, specs, quantize=quantize
                )
        elif quantize == "int8":
            # host-side quantized random init: the bf16 pytree must
            # never materialize on device (8B bf16 > one 16 GB chip)
            log.warning("initializing RANDOM int8 weights (no checkpoint)")
            from dynamo_tpu.models.quant import init_params_quantized

            params = init_params_quantized(model_config, seed, mesh, specs)
        else:
            log.warning("initializing RANDOM weights (no checkpoint found)")
            params = init_params(model_config, seed, mesh, specs)
        return model_config, params
    finally:
        if reader is not None:
            reader.close()


class _ShardedCheckpoint:
    """Lazily reads tensors across sharded safetensors files."""

    def __init__(self, model_dir: str):
        self.files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
        if not self.files:
            raise FileNotFoundError(f"no *.safetensors under {model_dir}")
        index_path = os.path.join(model_dir, "model.safetensors.index.json")
        self._name_to_file: dict[str, str] = {}
        if os.path.exists(index_path):
            with open(index_path) as f:
                weight_map = json.load(f)["weight_map"]
            self._name_to_file = {
                k: os.path.join(model_dir, v) for k, v in weight_map.items()
            }
        else:
            from safetensors import safe_open

            for path in self.files:
                with safe_open(path, framework="np") as f:
                    for name in f.keys():
                        self._name_to_file[name] = path
        self._open_handles: dict[str, Any] = {}
        # VLM checkpoints (LLaVA layout) prefix the language model's
        # weights: the standard llama maps resolve transparently
        self._prefix = (
            "language_model."
            if "language_model.model.embed_tokens.weight" in self._name_to_file
            else ""
        )

    def names(self) -> set[str]:
        if not self._prefix:
            return set(self._name_to_file)
        return {
            n[len(self._prefix):] if n.startswith(self._prefix) else n
            for n in self._name_to_file
        }

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        if name not in self._name_to_file:
            name = self._prefix + name
        path = self._name_to_file[name]
        handle = self._open_handles.get(path)
        if handle is None:
            handle = safe_open(path, framework="np")
            self._open_handles[path] = handle
        return handle.get_tensor(name)


def _to_jax(arr: np.ndarray, dtype) -> jnp.ndarray:
    if arr.dtype == np.uint16:
        # numpy has no bfloat16: reinterpret via jax
        return jax.lax.bitcast_convert_type(jnp.asarray(arr), jnp.bfloat16).astype(dtype)
    return jnp.asarray(arr, dtype=dtype)


def load_params(
    cfg: ModelConfig, model_dir: str, mesh: Optional[Mesh] = None,
    specs: Optional[dict] = None, quantize: Optional[str] = None,
) -> Params:
    """Load and stack weights; device_put with shardings as we go so the
    full f32 copy never materializes on one device. ``specs`` overrides
    the default TP PartitionSpecs (e.g. pp-sharded layer stacks).
    ``quantize="int8"`` quantizes matmul weights per layer ON THE HOST
    (models/quant.py) so the device only ever holds int8 + scales — the
    real 8B flagship fits one 16 GB chip this way."""
    from dynamo_tpu.models import quant

    ckpt = _ShardedCheckpoint(model_dir)
    shapes = param_shapes(cfg)
    specs = specs if specs is not None else param_specs(cfg)
    params: Params = {}

    def quantizing(name: str) -> bool:
        return quantize == "int8" and name in quant.QUANT_AXIS

    def put(name: str, arr: jnp.ndarray) -> jnp.ndarray:
        shape, dtype = shapes[name]
        arr = arr.astype(dtype)
        if arr.shape != shape:
            raise ValueError(f"{name}: expected {shape}, got {arr.shape}")
        if mesh is not None:
            arr = jax.device_put(arr, NamedSharding(mesh, specs[name]))
        return arr

    def put_q(name: str, q_np: np.ndarray, s_np: np.ndarray) -> None:
        shape, _ = shapes[name]
        if q_np.shape != shape:
            raise ValueError(f"{name}: expected {shape}, got {q_np.shape}")
        qa, sa = jnp.asarray(q_np), jnp.asarray(s_np)
        if mesh is not None:
            wspec = specs[name]
            qa = jax.device_put(qa, NamedSharding(mesh, wspec))
            sa = jax.device_put(
                sa,
                NamedSharding(
                    mesh, quant.scale_spec(wspec, quant.QUANT_AXIS[name])
                ),
            )
        params[name] = qa
        params[name + quant.SCALE_SUFFIX] = sa

    def host_f32(hf_name: str, transpose: bool) -> np.ndarray:
        arr = quant.np_to_f32(ckpt.get(hf_name))
        return arr.T if transpose else arr

    for name, (hf_name, transpose) in _GLOBAL_MAP.items():
        if name == "lm_head" and hf_name not in ckpt.names():
            # tied embeddings. Quantized: lm_head = embed.T per-row
            # scales == embed's per-row scales (both reduce over D)
            if quantizing(name):
                put_q(
                    name,
                    np.asarray(params["embed"]).T,
                    np.asarray(params["embed" + quant.SCALE_SUFFIX]),
                )
            else:
                params[name] = put(name, params["embed"].T)
            continue
        if quantizing(name):
            q, s = quant.quantize_array(
                host_f32(hf_name, transpose), quant.QUANT_AXIS[name]
            )
            put_q(name, q, s)
            continue
        arr = _to_jax(ckpt.get(hf_name), shapes[name][1])
        if transpose:
            arr = arr.T
        params[name] = put(name, arr)

    L = cfg.num_hidden_layers
    layer_map = _MOE_LAYER_MAP if cfg.is_moe else _LAYER_MAP
    for name, (tmpl, transpose) in layer_map.items():
        if name not in shapes:
            continue
        if quantizing(name):
            # per-layer host quantization == quantizing the stacked
            # tensor (scales reduce only the contraction axis), with
            # peak host memory of one layer's f32 copy
            qs, ss = [], []
            for i in range(L):
                if "{e}" in tmpl:
                    eq, es = [], []
                    for e in range(cfg.num_local_experts):
                        q, s = quant.quantize_array(
                            host_f32(tmpl.format(i=i, e=e), transpose), -2
                        )
                        eq.append(q)
                        es.append(s)
                    qs.append(np.stack(eq))
                    ss.append(np.stack(es))
                else:
                    q, s = quant.quantize_array(
                        host_f32(tmpl.format(i=i), transpose), -2
                    )
                    qs.append(q)
                    ss.append(s)
            put_q(name, np.stack(qs), np.stack(ss))
            continue
        per_layer = []
        for i in range(L):
            if "{e}" in tmpl:
                # stack experts: [E, in, out]
                per_expert = []
                for e in range(cfg.num_local_experts):
                    arr = _to_jax(ckpt.get(tmpl.format(i=i, e=e)), shapes[name][1])
                    per_expert.append(arr.T if transpose else arr)
                per_layer.append(jnp.stack(per_expert))
            else:
                arr = _to_jax(ckpt.get(tmpl.format(i=i)), shapes[name][1])
                per_layer.append(arr.T if transpose else arr)
        params[name] = put(name, jnp.stack(per_layer))
    missing = set(shapes) - {k for k in params if not quant.is_quantized_name(k)}
    if missing:
        raise ValueError(
            f"checkpoint {model_dir} missing params: {sorted(missing)}"
        )
    log.info("loaded %d params from %s", len(params), model_dir)
    return params


def load_params_sharded(
    cfg: ModelConfig, model_dir: str, mesh: Mesh,
    specs: Optional[dict] = None, quantize: Optional[str] = None,
) -> Params:
    """Shard-aware checkpoint load for big models (the 70B ladder,
    BASELINE config 3): each process materializes ONLY the weight
    slices its addressable devices own, via safetensors partial reads
    driven by ``jax.make_array_from_callback`` — no host ever holds a
    full stacked tensor. Peak host memory:

    - unquantized: one SHARD of one stacked tensor at a time;
    - int8: one LAYER's f32 copy (global per-channel scales need the
      full contraction axis — e.g. wo/w_down shard the contraction
      dim, and slice-local scales would change the numerics) plus the
      accumulated local int8 shards — for 70B int8 on a 16-process
      v5e-16 that is ~0.9 GB transient + ~4.4 GB/process of shards vs
      ~70 GB/process for the stacked loader (docs/multihost.md has the
      full budget math).

    Produces arrays indistinguishable from ``load_params`` (same
    global values, same shardings). Reference role: multi-node engine
    bring-up where each rank loads its slice
    (launch/dynamo-run/src/lib.rs:141-160 MultiNodeConfig)."""
    from dynamo_tpu.models import quant

    ckpt = _ShardedCheckpoint(model_dir)
    shapes = param_shapes(cfg)
    specs = specs if specs is not None else param_specs(cfg)
    params: Params = {}
    L = cfg.num_hidden_layers
    names = ckpt.names()

    def read_slice(hf_name: str, transpose: bool, idx: tuple) -> np.ndarray:
        """Partial-read one tensor's [idx] in OUR orientation (HF linear
        weights are [out, in]; ours [in, out] — swap the slices, read,
        transpose)."""
        from safetensors import safe_open

        if hf_name not in ckpt._name_to_file:
            hf_name = ckpt._prefix + hf_name
        path = ckpt._name_to_file[hf_name]
        handle = ckpt._open_handles.get(path)
        if handle is None:
            handle = safe_open(path, framework="np")
            ckpt._open_handles[path] = handle
        sl = handle.get_slice(hf_name)
        if transpose:
            assert len(idx) == 2
            arr = sl[idx[1], idx[0]]
            arr = np.ascontiguousarray(np.asarray(arr).T)
        else:
            arr = np.asarray(sl[idx])
        return arr

    def to_np_dtype(arr: np.ndarray, dtype) -> np.ndarray:
        if arr.dtype == np.uint16:  # bf16 raw bits
            arr = quant.np_to_f32(arr)
        return np.asarray(
            jnp.asarray(arr).astype(dtype)
        )

    def build(name: str, shape, dtype, cb) -> jnp.ndarray:
        sharding = NamedSharding(mesh, specs.get(name) or P_EMPTY)
        return jax.make_array_from_callback(shape, sharding, cb)

    def add_plain(name: str, tmpl: str, transpose: bool) -> None:
        shape, dtype = shapes[name]
        E = cfg.num_local_experts

        def cb(index):
            if "{e}" in tmpl:
                # expert stack [L, E, in, out]: dims 0/1 = layer/expert;
                # each (layer, expert) is its own checkpoint tensor, so
                # the ep×tp shard reads only its expert slices' slices
                l_sl, e_sl = index[0], index[1]
                rest = tuple(index[2:])
                out = np.stack([
                    np.stack([
                        read_slice(
                            tmpl.format(i=i, e=e), transpose, rest
                        )
                        for e in range(*e_sl.indices(E))
                    ])
                    for i in range(*l_sl.indices(L))
                ])
            elif "{i}" in tmpl:  # stacked per-layer tensor: dim 0 = layer
                l_sl = index[0]
                rest = tuple(index[1:])
                layers = range(*l_sl.indices(L))
                parts = [
                    read_slice(tmpl.format(i=i), transpose, rest)
                    for i in layers
                ]
                out = np.stack(parts)
            else:
                out = read_slice(tmpl, transpose, tuple(index))
            return to_np_dtype(out, dtype)

        params[name] = build(name, shape, dtype, cb)

    def _assemble(shape, sharding, fill) -> jax.Array:
        """Build a sharded array by filling each LOCAL shard from
        ``fill(global_index) -> np.ndarray`` and assembling — the
        slicing orientation of make_array_from_callback without its
        one-callback-invocation-per-array structure (which would force
        re-deriving expensive intermediates per shard)."""
        dev_map = sharding.addressable_devices_indices_map(shape)
        arrays = [
            jax.device_put(fill(idx), d) for d, idx in dev_map.items()
        ]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays
        )

    def add_quantized(name: str, tmpl: str, transpose: bool,
                      tied_embed: bool = False) -> None:
        """int8 path: quantize each (layer) tensor exactly ONCE — global
        per-channel scales need the full contraction axis, which tp
        shards for wo/w_down — then hand every local shard its slice.
        Host transient: one layer's f32 + the local int8 shards."""
        shape, _ = shapes[name]
        # QUANT_AXIS is relative to the UNSTACKED tensor (e.g. -2 = the
        # contraction dim of one layer); for stacked tensors negative
        # axes line up unchanged
        axis = quant.QUANT_AXIS[name]
        wspec = specs[name]
        s_axis = axis if axis >= 0 else len(shape) + axis
        s_shape = shape[:s_axis] + shape[s_axis + 1 :]
        q_sh = NamedSharding(mesh, wspec)
        s_sh = NamedSharding(mesh, quant.scale_spec(wspec, axis))
        if "{i}" not in tmpl:
            full = quant.np_to_f32(ckpt.get(tmpl))
            if transpose or tied_embed:
                full = full.T
            q, s = quant.quantize_array(full, axis)
            del full
            params[name] = _assemble(shape, q_sh, lambda idx: q[idx])
            params[name + quant.SCALE_SUFFIX] = _assemble(
                s_shape, s_sh, lambda idx: s[idx]
            )
            return
        # stacked per-layer (and per-expert): quantize tensor-by-tensor,
        # append each local shard's slice as we go. Expert stacks
        # [L, E, ...] iterate (layer, expert) pairs layer-major; the
        # local parts list reshapes back to its [l_local, e_local, ...]
        # block. Host transient stays ONE unstacked tensor's f32.
        E = cfg.num_local_experts
        experts = "{e}" in tmpl
        q_map = q_sh.addressable_devices_indices_map(shape)
        s_map = s_sh.addressable_devices_indices_map(s_shape)
        q_parts: dict = {d: [] for d in q_map}
        s_parts: dict = {d: [] for d in s_map}
        pairs = (
            [(i, e) for i in range(L) for e in range(E)]
            if experts else [(i, None) for i in range(L)]
        )
        for i, e in pairs:
            raw = ckpt.get(
                tmpl.format(i=i, e=e) if experts else tmpl.format(i=i)
            )
            full = quant.np_to_f32(raw)
            if transpose:
                full = full.T
            q, s = quant.quantize_array(full, axis)
            del full
            lead = 2 if experts else 1

            def want(idx) -> bool:
                if i not in range(*idx[0].indices(L)):
                    return False
                return not experts or e in range(*idx[1].indices(E))

            for d, idx in q_map.items():
                if want(idx):
                    q_parts[d].append(q[tuple(idx[lead:])])
            for d, idx in s_map.items():
                if want(idx):
                    s_parts[d].append(s[tuple(idx[lead:])])

        def assemble(parts_map, index_map, full_shape, sharding):
            arrays = []
            for d, idx in index_map.items():
                stacked = np.stack(parts_map[d])
                if experts:
                    n_l = len(range(*idx[0].indices(L)))
                    n_e = len(range(*idx[1].indices(E)))
                    stacked = stacked.reshape(
                        n_l, n_e, *stacked.shape[1:]
                    )
                arrays.append(jax.device_put(stacked, d))
            return jax.make_array_from_single_device_arrays(
                full_shape, sharding, arrays
            )

        params[name] = assemble(q_parts, q_map, shape, q_sh)
        params[name + quant.SCALE_SUFFIX] = assemble(
            s_parts, s_map, s_shape, s_sh
        )

    def quantizing(name: str) -> bool:
        return quantize == "int8" and name in quant.QUANT_AXIS

    from jax.sharding import PartitionSpec as P_CLS

    P_EMPTY = P_CLS()

    for name, (hf_name, transpose) in _GLOBAL_MAP.items():
        if name == "lm_head" and hf_name not in names:
            # tied embeddings: lm_head[idx] = embed.T[idx]
            e_tmpl, _ = _GLOBAL_MAP["embed"]
            shape, dtype = shapes[name]
            if quantizing(name):
                # embed is [V, D]; tied lm_head is its transpose
                add_quantized(name, e_tmpl, transpose=False, tied_embed=True)
            else:

                def cb_t(index):
                    # swap slices: embed is [V, D], lm_head [D, V]
                    arr = read_slice(e_tmpl, True, tuple(index))
                    return to_np_dtype(arr, dtype)

                params[name] = build(name, shape, dtype, cb_t)
            continue
        if quantizing(name):
            add_quantized(name, hf_name, transpose)
        else:
            add_plain(name, hf_name, transpose)

    layer_map = _MOE_LAYER_MAP if cfg.is_moe else _LAYER_MAP
    for name, (tmpl, transpose) in layer_map.items():
        if name not in shapes:
            continue
        if quantizing(name):
            add_quantized(name, tmpl, transpose)
        else:
            add_plain(name, tmpl, transpose)
    missing = set(shapes) - {
        k for k in params if not quant.is_quantized_name(k)
    }
    if missing:
        raise ValueError(
            f"checkpoint {model_dir} missing params: {sorted(missing)}"
        )
    log.info(
        "sharded-loaded %d params from %s (local shards only)",
        len(params), model_dir,
    )
    return params
