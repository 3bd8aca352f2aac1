"""CPU sharding rehearsal: the full engine step on a virtual device mesh.

    python tests/sharding_rehearsal.py [n_devices]     # default 8

Builds an n-device ``jax.sharding.Mesh`` over VIRTUAL CPU devices and
runs the FULL engine step (forward + paged-KV update + sampling) under
real dp/tp shardings, then pp, an expert-parallel MoE step, a sharded
checkpoint load and ring attention — one step each on tiny shapes,
executing (not just compiling) so collective layouts are validated
before anything is sent to real chips (``chip_smoke.py --chips 4``).
Pallas kernels run interpreted here; what the chip's compiler accepts is
tests/test_chip_compile.py's business.

The rehearsal always runs in a child process pinned to the CPU platform
(``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count``):
the caller may already hold a chip, which belongs to one process, and a
CPU-only child never asks for it.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = "_DYN_SHARDING_REHEARSAL_CHILD"


def _step_args(cfg, params, k_cache, v_cache, batch, seq, block_size, n_blocks_per_seq):
    """Tiny prefill-step arrays for the unified model step."""
    from dynamo_tpu.utils.testing import make_paged_inputs

    arrays = make_paged_inputs(
        cfg.vocab_size, batch, seq, block_size, n_blocks_per_seq
    )
    return (params, k_cache, v_cache, *arrays)


def dryrun_multichip(n_devices: int) -> None:
    """Run the rehearsal on ``n_devices`` virtual CPU devices, in a
    child process; raises with the child's output when it fails."""
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ] + [f"--xla_force_host_platform_device_count={n_devices}"]
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags),
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        **{_CHILD: "1"},
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), str(n_devices)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=900,
    )
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharding rehearsal failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-4000:]}"
        )


def _dryrun_multichip_impl(n_devices: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.sampling import sample
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import (
        CACHE_SPEC,
        forward,
        init_cache,
        init_params,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {len(jax.devices())}"
    )
    devices = jax.devices()[:n_devices]

    def run_step(cfg, mesh, block_size=8, quantized=False, params=None):
        if params is not None:
            pass  # caller-supplied (e.g. the sharded-checkpoint leg)
        elif quantized:
            from dynamo_tpu.models.llama import param_specs
            from dynamo_tpu.models.quant import init_params_quantized

            params = init_params_quantized(
                cfg, seed=0, mesh=mesh, specs=param_specs(cfg)
            )
        else:
            params = init_params(cfg, seed=0, mesh=mesh)
        k_cache, v_cache = init_cache(cfg, num_blocks=32, block_size=block_size, mesh=mesh)

        def step(params, k_cache, v_cache, tokens, positions, slot_mapping,
                 block_tables, context_lens, last_token_idx, sampling):
            logits, new_k, new_v = forward(
                cfg, params, k_cache, v_cache, tokens, positions, slot_mapping,
                block_tables, context_lens, last_token_idx, block_size,
            )
            toks, lps = sample(logits, sampling)
            return toks, lps, new_k, new_v

        jitted = jax.jit(step, donate_argnums=(1, 2))
        B = 4
        args = _step_args(cfg, params, k_cache, v_cache, batch=B, seq=16,
                          block_size=block_size, n_blocks_per_seq=2)
        # batch sharded over dp
        data_sharding = NamedSharding(mesh, P("dp"))
        sampling = {
            "temperature": np.full((B,), 0.7, np.float32),
            "top_k": np.full((B,), 5, np.int32),
            "top_p": np.full((B,), 0.9, np.float32),
            "min_p": np.zeros((B,), np.float32),
            "seeds": np.arange(B, dtype=np.uint32),
            "bias_ids": np.zeros((B, 4), np.int32),
            "bias_vals": np.zeros((B, 4), np.float32),
        }
        with mesh:
            toks, lps, new_k, new_v = jitted(*args, sampling)
            jax.block_until_ready((toks, lps))
        assert toks.shape == (B,)
        return toks

    # --- dense model: dp × tp sharding -----------------------------------
    tp = min(4, n_devices)
    dp = n_devices // tp
    dense_cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=256,
    )
    mesh = build_mesh(MeshConfig(dp=dp, tp=tp), devices[: dp * tp])
    run_step(dense_cfg, mesh)
    print(f"dryrun dense ok: mesh dp={dp} tp={tp}")

    # --- dense model, int8 weight-only quantization, same dp × tp mesh ----
    run_step(dense_cfg, mesh, quantized=True)
    print(f"dryrun int8 ok: mesh dp={dp} tp={tp}")

    # --- Pallas decode attention under shard_map over tp ------------------
    # A T=1 decode step with DYN_ATTN_IMPL=pallas (interpret mode off-TPU)
    # must execute on the same dp×tp mesh and match the reference path's
    # greedy logits (models/llama.py attend_mlp shard_map wrap).
    import os as _os

    from dynamo_tpu.models.llama import forward as _fwd, set_attention_mesh

    def decode_logits(cfg, mesh, block_size=8):
        params = init_params(cfg, seed=0, mesh=mesh)
        k_cache, v_cache = init_cache(
            cfg, num_blocks=32, block_size=block_size, mesh=mesh
        )
        B = 4
        tokens = np.arange(1, B + 1, dtype=np.int32)[:, None]
        positions = np.full((B, 1), 3, np.int32)
        slots = np.arange(B, dtype=np.int32) * block_size + 3
        tables = (np.arange(B, dtype=np.int32)[:, None]
                  + np.zeros((B, 8), np.int32))
        ctx = np.full((B,), 4, np.int32)
        last = np.zeros((B,), np.int32)

        def step(params, k_cache, v_cache):
            logits, _, _ = _fwd(
                cfg, params, k_cache, v_cache, tokens, positions, slots,
                tables, ctx, last, block_size,
            )
            return logits

        with mesh:
            return np.asarray(jax.jit(step)(params, k_cache, v_cache))

    ref_logits = decode_logits(dense_cfg, mesh)
    _os.environ["DYN_ATTN_IMPL"] = "pallas"
    set_attention_mesh(mesh)
    try:
        pal_logits = decode_logits(dense_cfg, mesh)
    finally:
        _os.environ.pop("DYN_ATTN_IMPL", None)
        set_attention_mesh(None)
    # bf16 activations: near-zero logits flip relative error wildly, so
    # the bar is absolute closeness + identical greedy choices
    np.testing.assert_allclose(pal_logits, ref_logits, atol=0.1)
    assert (pal_logits.argmax(-1) == ref_logits.argmax(-1)).all()
    print(f"dryrun pallas-attention ok: shard_map tp={tp} (interpret)")

    # --- MoE model: dp × ep × tp sharding --------------------------------
    if n_devices >= 4:
        ep = 2
        tp2 = 2
        dp2 = n_devices // (ep * tp2)
        moe_cfg = ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
            max_position_embeddings=256, num_local_experts=4,
            num_experts_per_tok=2,
        )
        mesh = build_mesh(
            MeshConfig(dp=dp2, ep=ep, tp=tp2), devices[: dp2 * ep * tp2]
        )
        # sparse top-k routing runs inside shard_map over ep×tp
        # (models/llama.py _moe_mlp_sparse) — register the mesh like
        # the engine does, and check parity against the dense oracle
        set_attention_mesh(mesh)
        try:
            toks_sparse = run_step(moe_cfg, mesh)
        finally:
            set_attention_mesh(None)
        _os.environ["DYN_MOE_IMPL"] = "dense"
        try:
            toks_dense = run_step(moe_cfg, mesh)
        finally:
            _os.environ.pop("DYN_MOE_IMPL", None)
        assert (np.asarray(toks_sparse) == np.asarray(toks_dense)).all(), (
            "sparse-routed MoE diverged from the dense oracle"
        )
        print(f"dryrun moe ok: mesh dp={dp2} ep={ep} tp={tp2} (sparse==dense)")

    # --- Mixtral-geometry sharded checkpoint load over ep × tp ------------
    # (VERDICT r4 item 6: real-checkpoint path for BASELINE config 4 —
    # expert stacks [L, E, in, out] load per-shard, then serve a step)
    if n_devices >= 8:
        import shutil as _shutil
        import tempfile as _tempfile

        from dynamo_tpu.models.loader import load_params_sharded
        from tests.test_sharded_loader import _write_moe_checkpoint

        mix_cfg = ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=256,
            num_local_experts=4, num_experts_per_tok=2,
        )
        tmp_ck = _tempfile.mkdtemp(prefix="dyn_mix_ck_")
        try:
            _write_moe_checkpoint(mix_cfg, tmp_ck, seed=11)
            mesh = build_mesh(MeshConfig(ep=4, tp=2), devices[:8])
            mix_params = load_params_sharded(
                mix_cfg, tmp_ck, mesh, quantize="int8"
            )
            set_attention_mesh(mesh)
            try:
                run_step(mix_cfg, mesh, params=mix_params)
            finally:
                set_attention_mesh(None)
        finally:
            _shutil.rmtree(tmp_ck, ignore_errors=True)
        print("dryrun mixtral-sharded-load ok: ep=4 tp=2 int8 experts")

    # --- pipeline parallel: pp × tp stage rotation ------------------------
    if n_devices >= 4:
        from dynamo_tpu.parallel.pipeline import (
            PP_CACHE_SPEC,
            forward_pp,
            pp_param_specs,
        )

        pp_cfg = ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            max_position_embeddings=256,
        )
        pp_n, tp_n = 2, 2
        mesh = build_mesh(MeshConfig(pp=pp_n, tp=tp_n), devices[: pp_n * tp_n])
        block_size = 8
        params = init_params(pp_cfg, seed=0)
        specs = pp_param_specs(pp_cfg)
        params = {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()
        }
        k_cache, v_cache = init_cache(pp_cfg, num_blocks=32, block_size=block_size)
        cache_sh = NamedSharding(mesh, PP_CACHE_SPEC)
        k_cache = jax.device_put(k_cache, cache_sh)
        v_cache = jax.device_put(v_cache, cache_sh)
        args = _step_args(pp_cfg, params, k_cache, v_cache, batch=4, seq=16,
                          block_size=block_size, n_blocks_per_seq=2)
        with mesh:
            logits, _, _ = jax.jit(
                lambda p, kc, vc, *a: forward_pp(
                    pp_cfg, p, kc, vc, *a, block_size, mesh
                )
            )(*args)
            jax.block_until_ready(logits)
        assert logits.shape == (4, pp_cfg.vocab_size)
        print(f"dryrun pipeline-parallel ok: mesh pp={pp_n} tp={tp_n}")

    # --- full serving engine: pp×tp mesh, batched prefill + fused decode --
    if n_devices >= 4:
        import asyncio

        from dynamo_tpu.engine.config import EngineConfig
        from dynamo_tpu.engine.engine import JaxEngine
        from dynamo_tpu.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )
        from dynamo_tpu.runtime.engine import Context

        eng_cfg = ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            max_position_embeddings=256,
        )

        async def engine_step() -> int:
            engine = await JaxEngine.launch(
                EngineConfig(
                    model_path="", model_name="dryrun", random_weights=True,
                    num_blocks=32, block_size=8, max_batch_size=4,
                    pipeline_parallel_size=2, tensor_parallel_size=2,
                    decode_steps=4,
                ),
                model_config=eng_cfg,
            )
            try:
                prompt = list(range(1, 20))
                req = PreprocessedRequest(
                    request_id="dry", token_ids=prompt,
                    sampling=SamplingOptions(use_greedy=True),
                    stop=StopConditions(max_tokens=6, ignore_eos=True),
                )
                n = 0
                async for item in engine.as_async_engine().generate(req, Context()):
                    n += len(item.token_ids)
                # disagg KV export over the sharded (pp×tp) cache: the
                # gather must assemble whole blocks from all shards
                from dynamo_tpu.tokens import TokenBlockSequence

                hashes = TokenBlockSequence(
                    prompt, block_size=8
                ).sequence_hashes()
                exp, packed = await engine.export_kv_blocks(hashes)
                assert len(exp) >= 2, exp
                assert packed.shape[-2] == eng_cfg.num_key_value_heads
                assert float(abs(packed).sum()) > 0
                return n
            finally:
                await engine.shutdown()

        n_toks = asyncio.run(engine_step())
        assert n_toks == 6, n_toks
        print(
            "dryrun serving-engine ok: pp=2 tp=2 decode_steps=4 "
            "(+ sharded-cache KV export)"
        )

    # --- 70B ladder geometry: tp=8, int8, one KV head per shard ----------
    # BASELINE config 3 (R1-Distill-Llama-70B on v5e-16): what makes its
    # sharding DIFFERENT from the 8B legs is H=64/Hkv=8 at tp=8 — one
    # KV head per shard (the GQA edge), int8 weights, and wide MLP
    # ratios. Scaled dims, real ratios: H/Hkv = 8, F/D = 3.5.
    if n_devices >= 8:
        ladder_cfg = ModelConfig(
            vocab_size=512, hidden_size=256, intermediate_size=896,
            num_hidden_layers=2, num_attention_heads=64,
            num_key_value_heads=8, max_position_embeddings=256,
        )
        mesh = build_mesh(MeshConfig(tp=8), devices[:8])
        run_step(ladder_cfg, mesh, quantized=True)
        print("dryrun 70b-ladder ok: tp=8 int8 (Hkv/tp=1 GQA edge)")

        # and the tp×pp split the v5e-16 deployment uses (4 chips/host
        # -> tp=4 inside a host, pp=2 across): the full engine leg
        # above runs pp=2 tp=2; here the LADDER geometry compiles the
        # same way with pp-sharded layer stacks
        from dynamo_tpu.parallel.pipeline import (
            PP_CACHE_SPEC as _PPCS,
            forward_pp as _fpp,
            pp_param_specs as _pps,
        )

        mesh_pt = build_mesh(MeshConfig(pp=2, tp=4), devices[:8])
        bs_l = 8
        l_params = init_params(ladder_cfg, seed=0)
        l_specs = _pps(ladder_cfg)
        l_params = {
            k: jax.device_put(v, NamedSharding(mesh_pt, l_specs[k]))
            for k, v in l_params.items()
        }
        kc_l, vc_l = init_cache(ladder_cfg, num_blocks=32, block_size=bs_l)
        cache_sh_l = NamedSharding(mesh_pt, _PPCS)
        kc_l = jax.device_put(kc_l, cache_sh_l)
        vc_l = jax.device_put(vc_l, cache_sh_l)
        l_args = _step_args(ladder_cfg, l_params, kc_l, vc_l, batch=4,
                            seq=16, block_size=bs_l, n_blocks_per_seq=2)
        with mesh_pt:
            l_logits, _, _ = jax.jit(
                lambda p, kc, vc, *a: _fpp(
                    ladder_cfg, p, kc, vc, *a, bs_l, mesh_pt
                )
            )(*l_args)
            jax.block_until_ready(l_logits)
        assert l_logits.shape == (4, ladder_cfg.vocab_size)
        print("dryrun 70b-ladder ok: pp=2 tp=4 stage rotation")

    # --- long-context: ring attention over an sp (sequence) axis ----------
    from jax.sharding import Mesh

    from dynamo_tpu.parallel.ring_attention import ring_attention

    sp_mesh = Mesh(np.array(devices), ("sp",))
    B, T, H, Hk, Dh = 1, 8 * n_devices, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, T, Hk, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, T, Hk, Dh)), jnp.bfloat16)
    seq_sh = NamedSharding(sp_mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, seq_sh) for x in (q, k, v))
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, sp_mesh))(qs, ks, vs)
    jax.block_until_ready(out)
    assert out.shape == (B, T, H, Dh)
    print(f"dryrun ring-attention ok: sp={n_devices}")


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    if os.environ.get(_CHILD) == "1":
        _dryrun_multichip_impl(n)
    else:
        dryrun_multichip(n)
        print("sharding rehearsal passed")
