"""End-to-end serving benchmark: continuous-batching decode throughput.

Prints ONE JSON line: {"metric","value","unit","vs_baseline"}.

Runs the full native engine (scheduler + paged KV + fused jitted step) on
the available accelerator with a flagship-shaped Llama (random weights —
throughput is weight-agnostic). ``vs_baseline`` is measured throughput as
a fraction of the single-chip HBM roofline (weights + KV traffic at ~819
GB/s for v5e): 1.0 would mean perfectly bandwidth-bound decode, so higher
is better and the number is comparable across rounds.

Env knobs: DYN_BENCH_PLATFORM=cpu for a tiny smoke run; DYN_BENCH_BATCH,
DYN_BENCH_ISL, DYN_BENCH_OSL to override the workload;
DYN_BENCH_DECODE_STEPS (default 32) fuses that many decode steps per
device dispatch (one host round trip per window instead of per
token); DYN_BENCH_QUANT=int8|none (default int8 on
TPU: weight-only per-channel int8, which is also what lets the REAL
8B flagship shape fit one 16 GB chip — bf16 does not);
DYN_BENCH_MODEL=8b|3.8b (default 8b: R1-Distill-Llama-8B geometry,
BASELINE.md config 1); DYN_BENCH_KV_DTYPE=bfloat16|int8|float8_e4m3fn
(default int8 — the Pallas decode kernel dequantizes int8 pages
in-register, so the halved KV bytes are pure roofline headroom;
``--kv-dtype`` below records the bf16-vs-int8 delta);
DYN_MATMUL_IMPL=auto|reference|pallas selects the quantized-matmul
path (models/llama.py — auto is the fused dequant Pallas kernels on a
single TPU chip) and the headline JSON records the resolved impl.

The HEADLINE runs overlapped speculative decoding by default
(DYN_BENCH_SPEC=1: spec + the decode pipeline composed at
decode_steps=1 over the int8 KV cache — docs/speculative_decoding.md's
pipelined section; its JSON carries a ``spec`` stanza with drafter,
spec_tokens, accept_rate and draft_hidden_frac). DYN_BENCH_SPEC=0 is
the escape hatch back to the fused-window headline
(DYN_BENCH_DECODE_STEPS windows, no speculation).

``--spec`` switches to the speculative-decoding A/B mode: the same
workload runs once without and once with speculation (both at
decode_steps=1 — speculation replaces fused windows), and the JSON line
reports accept rate, proposed/accepted draft tokens, and out-tok/s for
both sides (vs_baseline = spec/plain throughput ratio). Knobs:
DYN_BENCH_SPEC_DRAFTER (default "ngram"), DYN_BENCH_SPEC_TOKENS
(default 4). Repetitive prompts (the self-drafting sweet spot) via
DYN_BENCH_SPEC_REPEAT=1 — the default keeps the standard random-prompt
workload, where the reported accept rate is an honest floor.

``--spec-overlap`` is the three-way composition A/B at decode_steps=1:
serial spec (overlap off) vs pipelined spec (the composition) vs plain
overlap (spec off) on the identical workload; vs_baseline =
pipelined-spec / serial-spec throughput, with draft_hidden_frac (how
much host draft wall time the pipeline hid under device execution) and
both sides' device_idle_frac reported so the win is measured, not
asserted.

``--matmul`` is the reference-vs-Pallas quantized-matmul A/B at the
headline config: the same workload runs once with
DYN_MATMUL_IMPL=reference (XLA mixed int8×bf16 dot) and once with
=pallas (ops/qmatmul.py fused dequant kernels); vs_baseline =
pallas/reference throughput. ``--kv-dtype`` is the bf16-vs-int8 KV
cache A/B (vs_baseline = int8/bf16). ``--phases`` augments the
headline JSON with a per-phase device-time + HBM-bytes breakdown
(attention / MLP / LM-head / sampling, docs/performance.md): each
phase microbenches the real step computation at the headline geometry
and reports its ideal HBM bytes and the bandwidth its measured time
implies — the roofline gap decomposed instead of guessed at.

``--sentinel`` is the bench regression gate: the headline workload runs
once and its tok/s + per-bucket attribution compare against the
committed ``BENCH_BASELINE.json`` (explicit noise bands; override with
``--baseline PATH`` / ``DYN_BENCH_BASELINE``). Exit 1 on regression,
with the attribution delta naming the bucket that ate the loss; exit 2
when the profile has no baseline (seed with ``--update-baseline``).
``--quick`` shrinks the workload for the CI CPU-interpret smoke tier;
``DYN_SENTINEL_REPORT=path`` writes the report JSON as an artifact.

``--guided`` is the guided-decoding A/B (docs/guided_decoding.md): the
same workload at decode_steps=1 runs once unconstrained and once under
a canned bounded JSON schema whose [B, V] allow-mask rides every
sampling step; vs_baseline = guided/plain throughput — the mask's
hot-path cost as a measured number. A guided-under-spec stanza reports
the accept rate with masks on (proposals filter through the automaton,
the verify step applies identical per-position masks);
DYN_BENCH_GUIDED_SPEC=0 skips it, DYN_BENCH_GUIDED_TOKENIZER points the
mask compiler at a different vocabulary.

``--fanout`` is the frontend host-plane ceiling (no accelerator, no
jax): the real HttpService over a synthetic chat engine, driven with a
non-stream RPS concurrency ladder and a concurrent-SSE stream ladder;
reports the requests/sec ceiling and stream fan-out ceiling with the
server loop's lag p99 per rung and the host-cost ledger's per-stream
breakdown, as ``frontend_fanout_rps`` / ``frontend_fanout_streams``
JSON lines gated against the committed ``cpu-fanout-*`` baseline
profile (exit 1 regression / exit 2 missing profile; ``--quick``,
``--update-baseline``, DYN_SENTINEL_REPORT as with ``--sentinel``).
docs/observability.md "Host data plane" is the reading guide.

``--kvfleet`` is the fleet KV fabric A/B (docs/kvbm.md "Fleet
fabric"; no accelerator, no jax): the canned diurnal trace with
Zipf-popular shared prefix families replays through the fleet
simulator twice — fabric off (every prompt reprefills its shared head)
and fabric on (catalog hits fetch it from a peer's host tier or the
shared bucket) — and reports the fleet prefix hit rate plus the
fraction of the recompute bill avoided, as ``kvfleet_hit_rate`` /
``kvfleet_reprefill_avoided`` JSON lines gated against the committed
``cpu-kvfleet-*`` baseline profile (exit 1 regression / exit 2 missing
profile; ``--quick``, ``--update-baseline``, DYN_SENTINEL_REPORT as
with ``--sentinel``). Knobs: DYN_BENCH_KVFLEET_DURATION /
DYN_BENCH_KVFLEET_SEED.

``--overlap`` is the serial-vs-overlap A/B (docs/performance.md): the
same workload at decode_steps=1 runs once with --no-overlap (fully
serial plan -> dispatch -> sync -> emit) and once with the overlapped
decode pipeline; vs_baseline = overlap/serial throughput, and both
sides report device_idle_frac so the attribution is measured, not
asserted. The headline run also emits device_idle_frac + per-step
overlap stats in its config; DYN_BENCH_OVERLAP=0 forces the serial
loop there (the escape hatch A/B at the headline decode_steps).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The roofline/byte-budget math lives in telemetry/roofline.py now —
# ONE formula shared with the engine's live attribution ledger
# (dynamo_roofline_frac), so the bench artifact and the serving gauges
# can never disagree about the denominator.
from dynamo_tpu.telemetry.roofline import (  # noqa: E402
    HBM_BW_BYTES,
    kv_bytes_per_token as _roofline_kv_bytes_per_token,
    param_bytes as _roofline_param_bytes,
    phase_ideal_bytes as _roofline_phase_ideal_bytes,
)


def _build_config(cpu_mode: bool):
    from dynamo_tpu.models.config import ModelConfig

    if cpu_mode:
        model = ModelConfig(
            vocab_size=2048, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=2048,
        )
        workload = dict(batch=4, isl=32, osl=16, num_blocks=256, block_size=16,
                        quant=os.environ.get("DYN_BENCH_QUANT", "none"),
                        model_name="tiny")
    else:
        quant = os.environ.get("DYN_BENCH_QUANT", "int8")
        bench_model = os.environ.get("DYN_BENCH_MODEL", "8b")
        if bench_model == "8b":
            # the REAL flagship geometry: DeepSeek-R1-Distill-Llama-8B
            # (BASELINE.md config 1). int8 weights ≈ 8 GB -> fits one
            # 16 GB v5e chip WITH a useful KV cache; bf16 (16 GB) does not.
            model = ModelConfig(
                vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                num_hidden_layers=32, num_attention_heads=32,
                num_key_value_heads=8, max_position_embeddings=8192,
            )
        else:
            # ~3.8B shape: the round-1 bf16 reference point
            model = ModelConfig(
                vocab_size=32768, hidden_size=4096, intermediate_size=14336,
                num_hidden_layers=16, num_attention_heads=32,
                num_key_value_heads=8, max_position_embeddings=8192,
            )
        # num_blocks None = auto-size from free HBM after weights load;
        # the fused multi-step scan needs transient headroom, hence the
        # conservative utilization below. block_size 128 = the TPU
        # serving default (MXU-width kernel dots; +20% measured over
        # 16-token pages)
        # batch 64 default: the cohort-admission fix (scheduler.py
        # plan() cohort gate) made wide closed batches pay — windows
        # are weights-bound, so doubling rows nearly doubles tokens
        # per window (measured ladder on-chip: B=32 1514, B=64 2181,
        # B=128 2464 tok/s at p50 TTFT 577/1048/1710 ms; B=64 is the
        # default as the throughput/TTFT balance, DYN_BENCH_BATCH
        # overrides)
        workload = dict(batch=64, isl=128, osl=128, num_blocks=None,
                        block_size=128, quant=quant, model_name=bench_model)
    workload["batch"] = int(os.environ.get("DYN_BENCH_BATCH", workload["batch"]))
    workload["isl"] = int(os.environ.get("DYN_BENCH_ISL", workload["isl"]))
    workload["osl"] = int(os.environ.get("DYN_BENCH_OSL", workload["osl"]))
    workload["block_size"] = int(
        os.environ.get("DYN_BENCH_BLOCK_SIZE", workload["block_size"])
    )
    return model, workload


def _param_bytes(mc, quant: str) -> int:
    return _roofline_param_bytes(mc, quant)


def _bench_kv_dtype() -> str:
    # int8 headline default: the decode kernel reads int8 pages with
    # in-register dequant, so halved KV bytes are pure roofline headroom
    # (the bf16-vs-int8 delta is recorded by --kv-dtype)
    return os.environ.get("DYN_BENCH_KV_DTYPE", "int8")


def _kv_bytes_per_token(mc, kv_dtype: str = None) -> float:
    return _roofline_kv_bytes_per_token(mc, kv_dtype or _bench_kv_dtype())


async def _run(
    model_cfg, wl, spec: bool = False, decode_steps=None, slo=None,
    overlap: bool = True, kv_dtype: str = None, guided: dict = None,
) -> dict:
    """``slo`` = (ttft_ms, itl_ms) targets; when set, the result dict
    gains slo_attainment / goodput_tokens / requests_met from the
    engine's SloTracker (the --chaos mode's scoreboard).

    ``overlap=False`` runs the fully serial step loop (the --no-overlap
    escape hatch) — the A/B baseline for _main_overlap_ab. Every run
    reports ``device_idle_frac``: the OverlapTracker's idle-gap growth
    over the measured window divided by wall time (0.0 = the device
    always had a dispatched step to chew on; the serial loop's value is
    exactly the host plan+unpack+emit share the pipeline removes).

    ``guided`` (a GuidedOptions-shaped dict) runs every request under
    that constraint (docs/guided_decoding.md): the engine loads the
    DYN_BENCH_GUIDED_TOKENIZER vocabulary (default: the tiny test
    tokenizer — mask COST is shape-dependent, not content-dependent),
    prewarms the masked variants, and each request decodes through the
    allow-mask on the serial step path (guided's divert discipline)."""
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.protocols.common import (
        GuidedOptions,
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    kv_dtype = kv_dtype or _bench_kv_dtype()
    # guided runs need a real tokenizer vocabulary to compile the mask
    # against; the synthetic bench model has none, so the tiny test
    # tokenizer stands in (mask hot-path cost depends on [B, V] shape,
    # not on which ids are allowed)
    guided_tok = os.environ.get(
        "DYN_BENCH_GUIDED_TOKENIZER",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tests", "data", "tiny_llama_model",
        ),
    )
    cfg = EngineConfig(
        model_path=guided_tok if guided else "",
        model_name="bench", random_weights=True,
        prewarm_guided=bool(guided),
        quantization="int8" if wl["quant"] == "int8" else None,
        kv_cache_dtype=kv_dtype,
        num_blocks=wl["num_blocks"], block_size=wl["block_size"],
        max_batch_size=wl["batch"],
        prefill_chunk_size=int(os.environ.get("DYN_BENCH_PREFILL_CHUNK", "1024")),
        max_model_len=wl["isl"] + wl["osl"] + 8,
        # K=64 windows both raise throughput AND lower p50 TTFT at this
        # closed-batch shape (r4 measured: 1490-1521 tok/s @ ~560 ms vs
        # 1389-1450 @ ~640-780 ms at K=32) — per-window fixed costs
        # amortize over twice the tokens. Serving configs tune their own
        # decode_steps (the sweeps run 32).
        decode_steps=(
            decode_steps
            if decode_steps is not None
            else int(os.environ.get("DYN_BENCH_DECODE_STEPS", "64"))
        ),
        spec_decode=(
            os.environ.get("DYN_BENCH_SPEC_DRAFTER", "ngram") if spec else ""
        ),
        spec_tokens=int(os.environ.get("DYN_BENCH_SPEC_TOKENS", "4")),
        overlap=overlap,
        hbm_utilization=0.7,
        slo_ttft_ms=(slo[0] if slo else None),
        slo_itl_ms=(slo[1] if slo else None),
    )
    # static serving shapes (EngineConfig.static_shapes, default on)
    # pin the decode batch, table width, and prefill buckets so the only
    # reachable step shapes are the ones warmup exercises — no compile
    # lands inside the measured window.
    print(f"# engine launching (compile ~minutes on first run)", file=sys.stderr, flush=True)
    engine = await JaxEngine.launch(cfg, model_config=model_cfg)
    print("# engine up", file=sys.stderr, flush=True)

    rng = np.random.default_rng(0)
    adapter = engine.as_async_engine()

    repeat_prompts = os.environ.get("DYN_BENCH_SPEC_REPEAT") == "1"

    async def one_request(i: int) -> tuple[float, float, int, list]:
        if repeat_prompts:
            # self-similar prompt (doc-repetition workload): the n-gram
            # drafter's sweet spot — accept rates here show the ceiling
            period = max(8, wl["isl"] // 8)
            unit = rng.integers(
                1, model_cfg.vocab_size, size=period
            ).tolist()
            prompt = (unit * (wl["isl"] // period + 1))[: wl["isl"]]
        else:
            prompt = rng.integers(
                1, model_cfg.vocab_size, size=wl["isl"]
            ).tolist()
        # unique head: avoid total prefix collapse (mod: warmup ids
        # 9000+ must stay inside the CPU smoke model's tiny vocab)
        prompt[0] = (7 + i) % (model_cfg.vocab_size - 1) + 1
        req = PreprocessedRequest(
            request_id=f"bench-{i}",
            token_ids=prompt,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=wl["osl"], ignore_eos=True),
            guided=GuidedOptions(**guided) if guided else None,
        )
        t_start = time.monotonic()
        t_first = None
        n = 0
        # chunk arrival log (t, tokens_in_chunk): fused windows deliver
        # tokens in bursts, so per-token ITL is each gap amortized over
        # the chunk it delivered
        arrivals: list[tuple[float, int]] = []
        async for item in adapter.generate(req, Context()):
            if item.token_ids:
                now = time.monotonic()
                if t_first is None:
                    t_first = now
                arrivals.append((now, len(item.token_ids)))
            n += len(item.token_ids)
        return t_start, t_first or time.monotonic(), n, arrivals

    # warmup at FULL batch: the measurement's shapes (batched prefill at
    # B=batch, decode at the batch bucket) must compile now, not inside
    # the timed run
    await asyncio.gather(*[one_request(9000 + i) for i in range(wl["batch"])])
    print("# warmup done; measuring", file=sys.stderr, flush=True)

    idle0 = engine.overlap.stats()
    t0 = time.monotonic()
    results = await asyncio.gather(*[one_request(i) for i in range(wl["batch"])])
    t1 = time.monotonic()
    idle1 = engine.overlap.stats()
    total_tokens = sum(r[2] for r in results)
    ttfts = [r[1] - r[0] for r in results]
    # per-token ITL samples across all requests: each inter-chunk gap
    # contributes one sample per token it delivered (tail percentiles
    # are what BENCH_* files exist to capture — p50 hides the stalls)
    itls: list[float] = []
    for _, _, _, arrivals in results:
        for (t_prev, _), (t_cur, k) in zip(arrivals, arrivals[1:]):
            if k > 0:
                itls.extend([(t_cur - t_prev) / k] * k)
    wall = t1 - t0
    tput = total_tokens / wall

    # roofline: per decode step, read all weights once + each seq's KV
    avg_ctx = wl["isl"] + wl["osl"] / 2
    step_bytes = _param_bytes(model_cfg, wl["quant"]) + wl["batch"] * avg_ctx * _kv_bytes_per_token(model_cfg, kv_dtype)
    roofline_tput = wl["batch"] / (step_bytes / HBM_BW_BYTES)

    # device-idle attribution over the MEASURED window only (warmup
    # compiles would otherwise swamp the number): the fraction of wall
    # time the device provably sat without a dispatched step while the
    # host did serial work (telemetry/overlap.py — a host-observable
    # lower bound; exact for the serial loop)
    idle_s = idle1["idle_gap_s_total"] - idle0["idle_gap_s_total"]
    steps = idle1["steps_dispatched"] - idle0["steps_dispatched"]
    overlap_stats = {
        "device_idle_frac": round(max(0.0, idle_s) / max(wall, 1e-9), 4),
        "idle_gap_s_total": round(max(0.0, idle_s), 4),
        "steps_dispatched": steps,
        "idle_gap_ms_per_step": round(
            max(0.0, idle_s) * 1e3 / max(steps, 1), 3
        ),
        # the tracker's max is lifetime-wide: report it only when it
        # GREW during the window (the new max happened in-measurement);
        # 0.0 otherwise, so a warmup-era gap never masquerades as the
        # measured run's worst step
        "max_idle_gap_ms": (
            idle1["max_idle_gap_ms"]
            if idle1["max_idle_gap_ms"] > idle0["max_idle_gap_ms"]
            else 0.0
        ),
        "overlap_enabled": overlap,
    }
    spec_proposed = engine.spec_proposed_total
    spec_accepted = engine.spec_accepted_total
    # overlapped spec pipeline accounting (docs/speculative_decoding.md):
    # fraction of host draft wall time hidden under device execution
    hid = engine.spec_draft_hidden_s_total
    exp = engine.spec_draft_exposed_s_total
    spec_hidden_frac = round(hid / (hid + exp), 4) if (hid + exp) > 0 else 0.0
    slo_stats = engine.slo.stats()
    # live perf attribution (telemetry/attribution.py): the ledger's
    # rolling window over the run — loss-bucket fractions plus the
    # live roofline_frac computed from the SAME formula as the
    # "roofline" denominator below (telemetry/roofline.py)
    attribution = engine.attribution.window_summary()
    # resolve the matmul impl WHILE the engine's mesh is registered:
    # shutdown clears it, after which auto would misreport "reference"
    # on multi-device hosts for a run that used the Pallas kernels
    matmul_impl = _resolved_matmul_impl()
    await engine.shutdown()
    return {
        "slo": slo_stats,
        "attribution": attribution,
        "overlap": overlap_stats,
        "kv_dtype": kv_dtype,
        "matmul_impl": matmul_impl,
        "tput": tput,
        "p50_ttft_s": _percentile(ttfts, 50),
        "p90_ttft_s": _percentile(ttfts, 90),
        "p99_ttft_s": _percentile(ttfts, 99),
        "p50_itl_s": _percentile(itls, 50),
        "p90_itl_s": _percentile(itls, 90),
        "p99_itl_s": _percentile(itls, 99),
        "total_tokens": total_tokens,
        "wall_s": wall,
        "roofline": roofline_tput,
        "spec_proposed": spec_proposed,
        "spec_accepted": spec_accepted,
        "spec_draft_hidden_frac": spec_hidden_frac,
    }


def _percentile(samples: list, p: float) -> float:
    """Nearest-rank percentile (0.0 on an empty sample set)."""
    if not samples:
        return 0.0
    import math

    s = sorted(samples)
    # true ceil — round() is round-half-to-even, which overshoots the
    # rank (to the max) whenever p*N/100 lands on an integer
    k = min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))
    return s[k]


def _main_spec_ab(model_cfg, wl) -> None:
    """--spec: A/B the same workload with and without speculation (both
    at decode_steps=1) and report accept rate + both throughputs."""
    base = asyncio.run(_run(model_cfg, wl, spec=False, decode_steps=1))
    spec = asyncio.run(_run(model_cfg, wl, spec=True, decode_steps=1))
    proposed, accepted = spec["spec_proposed"], spec["spec_accepted"]
    out = {
        "metric": "engine_spec_decode_ab_1chip",
        "value": round(spec["tput"], 2),
        "unit": "tokens/sec",
        # spec vs plain decode on the identical workload: > 1.0 means
        # speculation converted spare decode FLOPs into tokens/step
        "vs_baseline": round(spec["tput"] / max(base["tput"], 1e-9), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "drafter": os.environ.get("DYN_BENCH_SPEC_DRAFTER", "ngram"),
            "spec_tokens": int(os.environ.get("DYN_BENCH_SPEC_TOKENS", "4")),
            "repeat_prompts": os.environ.get("DYN_BENCH_SPEC_REPEAT") == "1",
            "plain_tok_s": round(base["tput"], 2),
            "spec_tok_s": round(spec["tput"], 2),
            "proposed_tokens": proposed,
            "accepted_tokens": accepted,
            "accept_rate": round(accepted / proposed, 4) if proposed else 0.0,
            "p50_ttft_ms_plain": round(base["p50_ttft_s"] * 1000, 1),
            "p50_ttft_ms_spec": round(spec["p50_ttft_s"] * 1000, 1),
            "p99_ttft_ms_plain": round(base["p99_ttft_s"] * 1000, 1),
            "p99_ttft_ms_spec": round(spec["p99_ttft_s"] * 1000, 1),
            "p99_itl_ms_plain": round(base["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_spec": round(spec["p99_itl_s"] * 1000, 2),
        },
    }
    print(json.dumps(out))
    print(
        f"# spec A/B: plain={base['tput']:.1f} spec={spec['tput']:.1f} tok/s "
        f"accept={out['config']['accept_rate']:.2%} "
        f"({accepted}/{proposed} drafts)",
        file=sys.stderr,
    )


def _main_spec_overlap_ab(model_cfg, wl) -> None:
    """--spec-overlap: the composition A/B (docs/speculative_decoding.md
    pipelined section). Three runs of the identical workload at
    decode_steps=1: serial spec (drafting fully exposed as device
    idle), pipelined spec (drafting hidden under the in-flight verify),
    and plain overlap (no speculation — the floor the composition must
    beat for spec to earn its verify rectangle). vs_baseline =
    pipelined-spec / serial-spec throughput; draft_hidden_frac is the
    measured fraction of draft wall time the pipeline hid."""
    serial = asyncio.run(
        _run(model_cfg, wl, spec=True, decode_steps=1, overlap=False)
    )
    piped = asyncio.run(
        _run(model_cfg, wl, spec=True, decode_steps=1, overlap=True)
    )
    plain = asyncio.run(
        _run(model_cfg, wl, spec=False, decode_steps=1, overlap=True)
    )
    prop, acc = piped["spec_proposed"], piped["spec_accepted"]
    out = {
        "metric": "engine_spec_overlap_ab_1chip",
        "value": round(piped["tput"], 2),
        "unit": "tokens/sec",
        # pipelined vs serial spec on the identical workload: > 1.0
        # means the double-buffered schedule converted exposed host
        # draft time into device work
        "vs_baseline": round(piped["tput"] / max(serial["tput"], 1e-9), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "drafter": os.environ.get("DYN_BENCH_SPEC_DRAFTER", "ngram"),
            "spec_tokens": int(os.environ.get("DYN_BENCH_SPEC_TOKENS", "4")),
            "repeat_prompts": os.environ.get("DYN_BENCH_SPEC_REPEAT") == "1",
            "serial_spec_tok_s": round(serial["tput"], 2),
            "pipelined_spec_tok_s": round(piped["tput"], 2),
            "plain_overlap_tok_s": round(plain["tput"], 2),
            "accept_rate": round(acc / prop, 4) if prop else 0.0,
            "proposed_tokens": prop,
            "accepted_tokens": acc,
            "draft_hidden_frac": piped["spec_draft_hidden_frac"],
            "serial_device_idle_frac":
                serial["overlap"]["device_idle_frac"],
            "pipelined_device_idle_frac":
                piped["overlap"]["device_idle_frac"],
            "p99_itl_ms_serial_spec": round(serial["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_pipelined_spec": round(piped["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_plain_overlap": round(plain["p99_itl_s"] * 1000, 2),
        },
    }
    print(json.dumps(out))
    print(
        f"# spec-overlap A/B: serial-spec={serial['tput']:.1f} "
        f"pipelined-spec={piped['tput']:.1f} "
        f"plain-overlap={plain['tput']:.1f} tok/s, "
        f"accept={out['config']['accept_rate']:.2%}, "
        f"draft_hidden={piped['spec_draft_hidden_frac']:.2%}",
        file=sys.stderr,
    )


# canned bench schema: bounded everywhere (strings capped, enum moods,
# boolean) so a random-weights model always terminates the document —
# what the A/B measures is the mask's hot-path cost, not schema luck
GUIDED_BENCH_SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string", "maxLength": 8},
        "ok": {"type": "boolean"},
        "mood": {"enum": ["happy", "sad", "neutral"]},
        "score": {"type": "string", "pattern": "[0-9]{1,3}"},
    },
    "required": ["name", "ok", "mood", "score"],
}


def _main_guided_ab(model_cfg, wl) -> None:
    """--guided: unconstrained vs schema-masked A/B at decode_steps=1
    (docs/guided_decoding.md) — the mask's hot-path cost as a measured
    number: per step the engine builds a [B, V] bool mask on host,
    ships it with the batch, and the jitted step drops disallowed
    logits to -inf before sampling. vs_baseline = guided/plain
    throughput on the identical workload (< 1.0 by the mask's cost;
    the gap IS the number). A guided-under-spec stanza reports the
    accept rate with masks on (drafts filter through the automaton
    before the verify step applies identical per-position masks);
    DYN_BENCH_GUIDED_SPEC=0 skips it."""
    guided_spec = {"kind": "json_schema", "json_schema": GUIDED_BENCH_SCHEMA}
    plain = asyncio.run(_run(model_cfg, wl, decode_steps=1))
    guided = asyncio.run(
        _run(model_cfg, wl, decode_steps=1, guided=guided_spec)
    )
    cfg = {
        "model": wl["model_name"],
        "batch": wl["batch"],
        "isl": wl["isl"],
        "osl": wl["osl"],
        "schema": "bench-canned-v1",
        "plain_tok_s": round(plain["tput"], 2),
        "guided_tok_s": round(guided["tput"], 2),
        "p99_itl_ms_plain": round(plain["p99_itl_s"] * 1000, 2),
        "p99_itl_ms_guided": round(guided["p99_itl_s"] * 1000, 2),
        "guided_device_idle_frac": guided["overlap"]["device_idle_frac"],
    }
    if os.environ.get("DYN_BENCH_GUIDED_SPEC", "1") != "0":
        gspec = asyncio.run(
            _run(model_cfg, wl, spec=True, decode_steps=1, guided=guided_spec)
        )
        prop, acc = gspec["spec_proposed"], gspec["spec_accepted"]
        cfg["spec"] = {
            "guided_spec_tok_s": round(gspec["tput"], 2),
            "proposed_tokens": prop,
            "accepted_tokens": acc,
            "accept_rate": round(acc / prop, 4) if prop else 0.0,
            "drafter": os.environ.get("DYN_BENCH_SPEC_DRAFTER", "ngram"),
            "spec_tokens": int(os.environ.get("DYN_BENCH_SPEC_TOKENS", "4")),
        }
    out = {
        "metric": "engine_guided_ab_1chip",
        "value": round(guided["tput"], 2),
        "unit": "tokens/sec",
        "vs_baseline": round(guided["tput"] / max(plain["tput"], 1e-9), 4),
        "config": cfg,
    }
    print(json.dumps(out))
    spec_note = (
        f" spec-accept={cfg['spec']['accept_rate']:.2%}"
        if "spec" in cfg else ""
    )
    print(
        f"# guided A/B: plain={plain['tput']:.1f} "
        f"guided={guided['tput']:.1f} tok/s "
        f"(x{out['vs_baseline']:.3f}){spec_note}",
        file=sys.stderr,
    )


def _main_overlap_ab(model_cfg, wl) -> None:
    """--overlap: serial-vs-overlap A/B at decode_steps=1 — the shape
    where the host's per-step plan+unpack+emit time is fully exposed,
    so the pipeline's contribution is attributable. vs_baseline is
    overlap/serial throughput on the identical workload; both sides
    report device_idle_frac (the serial side's value IS the host share
    the pipeline exists to hide — if it were ~0 there would be nothing
    to win and the A/B honestly reports that)."""
    serial = asyncio.run(
        _run(model_cfg, wl, decode_steps=1, overlap=False)
    )
    over = asyncio.run(_run(model_cfg, wl, decode_steps=1, overlap=True))
    out = {
        "metric": "engine_overlap_decode_ab_1chip",
        "value": round(over["tput"], 2),
        "unit": "tokens/sec",
        # overlapped pipeline vs the serial loop on the identical
        # workload: > 1.0 means the double-buffered host schedule
        # converted device idle gaps into tokens
        "vs_baseline": round(over["tput"] / max(serial["tput"], 1e-9), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "serial_tok_s": round(serial["tput"], 2),
            "overlap_tok_s": round(over["tput"], 2),
            "serial_device_idle_frac":
                serial["overlap"]["device_idle_frac"],
            "overlap_device_idle_frac":
                over["overlap"]["device_idle_frac"],
            "serial_idle_gap_ms_per_step":
                serial["overlap"]["idle_gap_ms_per_step"],
            "overlap_idle_gap_ms_per_step":
                over["overlap"]["idle_gap_ms_per_step"],
            "p50_itl_ms_serial": round(serial["p50_itl_s"] * 1000, 2),
            "p50_itl_ms_overlap": round(over["p50_itl_s"] * 1000, 2),
            "p99_itl_ms_serial": round(serial["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_overlap": round(over["p99_itl_s"] * 1000, 2),
        },
    }
    print(json.dumps(out))
    print(
        f"# overlap A/B: serial={serial['tput']:.1f} "
        f"overlap={over['tput']:.1f} tok/s, device_idle_frac "
        f"{serial['overlap']['device_idle_frac']:.3f} -> "
        f"{over['overlap']['device_idle_frac']:.3f}",
        file=sys.stderr,
    )


def _resolved_matmul_impl() -> str:
    from dynamo_tpu.models.llama import matmul_impl

    return matmul_impl()


def _main_matmul_ab(model_cfg, wl) -> None:
    """--matmul: reference-vs-Pallas quantized-matmul A/B at the
    headline config (same workload, same decode_steps). vs_baseline =
    pallas/reference throughput — > 1.0 means the in-register dequant
    kernels converted int8 weight bytes into tokens the XLA mixed-dtype
    dot could not. Off-TPU the Pallas side runs interpreted (a
    correctness smoke, not a speed number — the JSON records the
    backend so nobody reads a CPU ratio as a win)."""
    os.environ["DYN_MATMUL_IMPL"] = "reference"
    ref = asyncio.run(_run(model_cfg, wl))
    os.environ["DYN_MATMUL_IMPL"] = "pallas"
    try:
        pal = asyncio.run(_run(model_cfg, wl))
    finally:
        os.environ.pop("DYN_MATMUL_IMPL", None)
    import jax

    out = {
        "metric": "engine_matmul_ab_1chip",
        "value": round(pal["tput"], 2),
        "unit": "tokens/sec",
        "vs_baseline": round(pal["tput"] / max(ref["tput"], 1e-9), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "quant": wl["quant"],
            "kv_dtype": ref["kv_dtype"],
            "backend": jax.default_backend(),
            "reference_tok_s": round(ref["tput"], 2),
            "pallas_tok_s": round(pal["tput"], 2),
            "p50_itl_ms_reference": round(ref["p50_itl_s"] * 1000, 2),
            "p50_itl_ms_pallas": round(pal["p50_itl_s"] * 1000, 2),
            "p99_itl_ms_reference": round(ref["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_pallas": round(pal["p99_itl_s"] * 1000, 2),
        },
    }
    print(json.dumps(out))
    print(
        f"# matmul A/B: reference={ref['tput']:.1f} "
        f"pallas={pal['tput']:.1f} tok/s "
        f"(x{out['vs_baseline']:.3f})",
        file=sys.stderr,
    )


def _main_kv_dtype_ab(model_cfg, wl) -> None:
    """--kv-dtype: bf16-vs-int8 KV cache A/B at the headline config.
    vs_baseline = int8/bf16 throughput — the record of what flipping
    the headline default to the quantized cache actually bought (the
    decode kernel reads int8 pages + scales either way; only the cache
    bytes change)."""
    bf16 = asyncio.run(_run(model_cfg, wl, kv_dtype="bfloat16"))
    int8 = asyncio.run(_run(model_cfg, wl, kv_dtype="int8"))
    avg_ctx = wl["isl"] + wl["osl"] / 2
    out = {
        "metric": "engine_kv_dtype_ab_1chip",
        "value": round(int8["tput"], 2),
        "unit": "tokens/sec",
        "vs_baseline": round(int8["tput"] / max(bf16["tput"], 1e-9), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "quant": wl["quant"],
            "matmul_impl": int8["matmul_impl"],
            "bf16_tok_s": round(bf16["tput"], 2),
            "int8_tok_s": round(int8["tput"], 2),
            # the byte story behind the ratio: per-step KV traffic at
            # the workload's average context, both dtypes
            "kv_bytes_per_step_bf16": int(
                wl["batch"] * avg_ctx
                * _kv_bytes_per_token(model_cfg, "bfloat16")
            ),
            "kv_bytes_per_step_int8": int(
                wl["batch"] * avg_ctx
                * _kv_bytes_per_token(model_cfg, "int8")
            ),
            "p50_itl_ms_bf16": round(bf16["p50_itl_s"] * 1000, 2),
            "p50_itl_ms_int8": round(int8["p50_itl_s"] * 1000, 2),
            "p99_itl_ms_bf16": round(bf16["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_int8": round(int8["p99_itl_s"] * 1000, 2),
        },
    }
    print(json.dumps(out))
    print(
        f"# kv-dtype A/B: bf16={bf16['tput']:.1f} int8={int8['tput']:.1f} "
        f"tok/s (x{out['vs_baseline']:.3f})",
        file=sys.stderr,
    )


def _phase_breakdown(model_cfg, wl, kv_dtype: str) -> dict:
    """Decompose one decode step's device time into attention / MLP /
    LM-head / sampling by microbenching each phase's REAL computation
    (the serving params and cache geometry, the serving kernels) at the
    headline shape. Per phase: measured device ms, the ideal HBM bytes
    that phase must move, and the bandwidth the measured time implies —
    achieved-vs-ideal, so the roofline gap names its owner instead of
    being guessed at. ``step_ms_sum`` vs the engine-measured step time
    shows how much of a real step the decomposition accounts for."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.quant import init_params_quantized

    mc = model_cfg
    B = wl["batch"]
    bs = wl["block_size"]
    avg_ctx = int(wl["isl"] + wl["osl"] / 2)
    L, D, F, V = (
        mc.num_hidden_layers, mc.hidden_size, mc.intermediate_size,
        mc.vocab_size,
    )
    H, Hk, Dh = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
    quant = wl["quant"] == "int8"
    params = (
        init_params_quantized(mc, seed=0) if quant
        else llama.init_params(mc, seed=0)
    )
    # register a size-1 mesh exactly like the single-chip engine does,
    # so matmul_impl/pallas_matmul_active resolve HERE the same way
    # they did inside the headline run (the engine cleared the mesh at
    # shutdown; without this, multi-device hosts would microbench the
    # reference path while the headline ran the fused kernels)
    from jax.sharding import Mesh

    prev_mesh = llama.get_attention_mesh()
    llama.set_attention_mesh(
        Mesh(
            np.asarray(jax.devices()[:1]).reshape(1, 1, 1, 1),
            ("dp", "pp", "tp", "ep"),
        )
    )

    blocks_per_seq = -(-avg_ctx // bs)
    num_blocks = B * blocks_per_seq + 1
    cache_dt = {"int8": jnp.int8, "bfloat16": jnp.bfloat16}.get(
        kv_dtype, jnp.bfloat16
    )
    k_cache, v_cache = llama.init_cache(mc, num_blocks, bs, dtype=cache_dt)
    tables = np.asarray(
        1 + np.arange(B * blocks_per_seq).reshape(B, blocks_per_seq),
        np.int32,
    )
    ctx = np.full((B,), avg_ctx, np.int32)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.bfloat16)
    x_dec = jnp.asarray(rng.standard_normal((B, 1, D)), jnp.bfloat16)
    x_last = x_dec[:, 0]
    logits = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)

    interpret = jax.default_backend() != "tpu"
    lp = {
        k: params[k][0] if params[k].shape[0] == L else params[k]
        for k in llama.layer_param_names(params)
    }

    def attn_layer(q, kc, vc, t, c):
        from dynamo_tpu.ops.paged_attention import (
            paged_attention_decode_stacked,
        )

        ksc = vsc = None
        if llama.kv_cache_is_quantized(kc):
            (kc, ksc), (vc, vsc) = kc, vc
        return paged_attention_decode_stacked(
            q, kc, vc, jnp.int32(0), t, c, block_size=bs,
            interpret=interpret, k_scale=ksc, v_scale=vsc,
        )

    def mlp_full(x):
        """One layer's complete matmul set at the decode shape: the
        qkv projections feed wq's output through the SHARED
        post-attention chain (llama.post_attn_mlp — the exact served
        composition, fused Pallas epilogues and all; attention itself
        is the phase above). k/v are returned so DCE cannot drop their
        weight reads from the measurement."""
        h = llama.rmsnorm(x, lp["attn_norm"], mc.rms_norm_eps)
        a = llama.mm(lp, "wq", h)
        k = llama.mm(lp, "wk", h)
        v = llama.mm(lp, "wv", h)
        return llama.post_attn_mlp(mc, lp, x, a), k, v

    def lm_head_fn(x):
        return llama.lm_head(params, x)

    def sample_fn(lg):
        lse = jax.nn.logsumexp(lg, axis=-1)
        tok = jnp.argmax(lg, axis=-1)
        return tok, jnp.take_along_axis(lg, tok[:, None], 1)[:, 0] - lse

    def timed(fn, *args, reps: int = 5) -> float:
        f = jax.jit(fn)
        out = f(*args)
        jax.block_until_ready(out)  # compile outside the clock
        best = float("inf")
        for _ in range(reps):
            t0 = _time.monotonic()
            out = f(*args)
            jax.block_until_ready(out)
            best = min(best, _time.monotonic() - t0)
        return best

    try:
        t_attn1 = timed(
            attn_layer, q, k_cache, v_cache, jnp.asarray(tables),
            jnp.asarray(ctx),
        )
        t_mlp1 = timed(mlp_full, x_dec)
        t_lm = timed(lm_head_fn, x_last)
        t_sample = timed(sample_fn, logits)
    finally:
        llama.set_attention_mesh(prev_mesh)

    # per-phase byte budget from the SHARED roofline model
    # (telemetry/roofline.py) — the same prior the serving-side
    # attribution ledger splits device time with, so --phases and
    # /debug/attribution decompose against identical denominators
    ideal = _roofline_phase_ideal_bytes(
        mc, B, avg_ctx, "int8" if quant else None, kv_dtype
    )
    phases = {
        "attention": {
            "device_ms": round(t_attn1 * L * 1e3, 3),
            "ideal_bytes": ideal["attention"],
        },
        "mlp": {
            "device_ms": round(t_mlp1 * L * 1e3, 3),
            "ideal_bytes": ideal["mlp"],
        },
        "lm_head": {
            "device_ms": round(t_lm * 1e3, 3),
            "ideal_bytes": ideal["lm_head"],
        },
        "sampling": {
            "device_ms": round(t_sample * 1e3, 3),
            "ideal_bytes": ideal["sampling"],
        },
    }
    for ph in phases.values():
        dt = ph["device_ms"] / 1e3
        ph["implied_gbs"] = round(ph["ideal_bytes"] / max(dt, 1e-9) / 1e9, 2)
        ph["bw_frac"] = round(
            ph["ideal_bytes"] / max(dt, 1e-9) / HBM_BW_BYTES, 4
        )
    phases["step_ms_sum"] = round(
        sum(p["device_ms"] for p in phases.values() if isinstance(p, dict)),
        3,
    )
    return phases


def _migration_sim_ab() -> dict:
    """Goodput retained under a mid-burst worker kill with mid-stream
    migration on vs off (the live routers' default vs the PR-5 abort
    behavior), replayed on the PR-6 discrete-event fleet — no
    accelerator needed, deterministic at a fixed seed. Rides along with
    --chaos so the kill-recovery policy is benched next to the
    step-fault goodput number (docs/robustness.md)."""
    from dynamo_tpu.faults.plan import parse_plan
    from dynamo_tpu.sim import FleetSim, SimConfig, bursty_trace

    trace = bursty_trace(
        600.0, seed=2026, calm_rps=30.0, burst_rps=60.0,
        mean_calm_s=90.0, mean_burst_s=30.0,
    )
    kill = "seed=42;worker.liveness:kill@after=240"

    def run(migration, plan_spec=None):
        plan = parse_plan(plan_spec) if plan_spec else None
        return FleetSim(
            trace, SimConfig(initial_decode=3, migration=migration),
            plan=plan,
        ).run()

    base = run(True)  # fault-free reference
    on = run(True, kill)
    off = run(False, kill)
    g = max(1, base["goodput_tokens"])
    return {
        "sim_kill_plan": kill,
        "sim_goodput_retained_migration_on": round(
            on["goodput_tokens"] / g, 4
        ),
        "sim_goodput_retained_migration_off": round(
            off["goodput_tokens"] / g, 4
        ),
        "sim_resumed": on["resumed"],
        "sim_lost_migration_off": off["lost_inflight"],
    }


def _drain_sim_ab() -> dict:
    """Kill-vs-drain A/B on the discrete-event fleet: the same worker
    goes down at the same instant under the same seed — reactively
    (worker.liveness:kill — streams resume after full re-prefill) vs
    gracefully (worker.drain — proactive handoff, onboard-rate
    resumes, zero lost tokens). The headline is the SLO-attainment
    dip: the drain's must be strictly shallower
    (docs/robustness.md "Graceful drain & rolling restarts")."""
    from dynamo_tpu.faults.plan import parse_plan
    from dynamo_tpu.sim import FleetSim, SimConfig, bursty_trace

    trace = bursty_trace(
        600.0, seed=2026, calm_rps=30.0, burst_rps=60.0,
        mean_calm_s=90.0, mean_burst_s=30.0,
    )

    def run(point):
        plan = parse_plan(f"seed=42;{point}:kill@after=240")
        # kill_detect_s models the reactive path's death-detection gap
        # (stream error + failover backoff) — only kills pay it; the
        # drain's handoff latency is the config default
        return FleetSim(
            trace, SimConfig(initial_decode=3, kill_detect_s=2.0),
            plan=plan,
        ).run()

    def dip(res):
        att = [s["slo_attainment_mean"] for s in res["timeline"]]
        return 1.0 - min(att) if att else 0.0

    kill = run("worker.liveness")
    drain = run("worker.drain")
    return {
        "sim_fault_at_s": 240,
        "sim_attainment_dip_kill": round(dip(kill), 4),
        "sim_attainment_dip_drain": round(dip(drain), 4),
        "sim_streams_migrated_drain": drain["drained_inflight"],
        "sim_streams_hit_kill": kill["killed_inflight"],
        "sim_goodput_kill": kill["goodput_tokens"],
        "sim_goodput_drain": drain["goodput_tokens"],
    }


def _main_chaos_ab(model_cfg, wl) -> None:
    """--chaos: goodput/SLO attainment under a canned, fixed-seed fault
    plan vs the identical fault-free workload (docs/robustness.md).

    The plan (override with DYN_FAULTS) delays a fraction of engine
    steps and injects two transient step errors — the quarantine/retry
    machinery must absorb them. SLO targets default to 3x the fault-free
    run's p50s (env DYN_BENCH_SLO_TTFT_MS / DYN_BENCH_SLO_ITL_MS pin
    absolute targets instead)."""
    from dynamo_tpu import faults

    env_ttft = float(os.environ.get("DYN_BENCH_SLO_TTFT_MS", 0))
    env_itl = float(os.environ.get("DYN_BENCH_SLO_ITL_MS", 0))
    if env_ttft and env_itl:
        # both targets pinned: the probe run would be discarded — skip it
        ttft_ms, itl_ms = env_ttft, env_itl
    else:
        probe = asyncio.run(_run(model_cfg, wl))
        ttft_ms = env_ttft or max(50.0, probe["p50_ttft_s"] * 3e3)
        itl_ms = env_itl or max(5.0, probe["p50_itl_s"] * 3e3)
    slo = (round(ttft_ms, 2), round(itl_ms, 2))
    base = asyncio.run(_run(model_cfg, wl, slo=slo))

    plan_spec = os.environ.get("DYN_FAULTS") or (
        f"seed={os.environ.get('DYN_BENCH_CHAOS_SEED', '42')};"
        f"engine.step:delay={os.environ.get('DYN_BENCH_CHAOS_DELAY', '0.005')}"
        f"@p=0.2;engine.step:error@after=50@max=2"
    )
    injector = faults.activate(faults.parse_plan(plan_spec))
    try:
        chaos = asyncio.run(_run(model_cfg, wl, slo=slo))
        fired = injector.stats()["fired_total"]
    finally:
        faults.deactivate()

    base_goodput = base["slo"]["goodput_tokens_total"]
    chaos_goodput = chaos["slo"]["goodput_tokens_total"]
    out = {
        "metric": "engine_chaos_goodput_1chip",
        "value": round(chaos_goodput / max(chaos["wall_s"], 1e-9), 2),
        "unit": "goodput_tokens/sec",
        # goodput retained under the canned fault plan, relative to the
        # fault-free run at the same SLO targets (1.0 = chaos-immune)
        "vs_baseline": round(chaos_goodput / max(base_goodput, 1), 4),
        "config": {
            "model": wl["model_name"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "fault_plan": plan_spec,
            "faults_fired": fired,
            "slo_ttft_ms": slo[0],
            "slo_itl_ms": slo[1],
            "base_tok_s": round(base["tput"], 2),
            "chaos_tok_s": round(chaos["tput"], 2),
            "base_slo_attainment": round(base["slo"]["attainment"], 4),
            "chaos_slo_attainment": round(chaos["slo"]["attainment"], 4),
            "base_goodput_tokens": base_goodput,
            "chaos_goodput_tokens": chaos_goodput,
            "p99_ttft_ms_base": round(base["p99_ttft_s"] * 1000, 1),
            "p99_ttft_ms_chaos": round(chaos["p99_ttft_s"] * 1000, 1),
            "p99_itl_ms_base": round(base["p99_itl_s"] * 1000, 2),
            "p99_itl_ms_chaos": round(chaos["p99_itl_s"] * 1000, 2),
        },
    }
    # mid-stream migration A/B (sim-based; DYN_BENCH_CHAOS_MIGRATION=0
    # skips it): goodput retained through a worker kill, migration
    # on vs off
    if os.environ.get("DYN_BENCH_CHAOS_MIGRATION", "1") != "0":
        out["config"]["migration"] = mig = _migration_sim_ab()
        print(
            f"# migration A/B (sim kill): goodput retained "
            f"{mig['sim_goodput_retained_migration_off']:.4f} (off) -> "
            f"{mig['sim_goodput_retained_migration_on']:.4f} (on), "
            f"{mig['sim_resumed']} stream(s) resumed",
            file=sys.stderr,
        )
    # graceful-drain A/B (sim-based; DYN_BENCH_CHAOS_DRAIN=0 skips it):
    # the same departure as a kill vs as a planned drain — the drain's
    # attainment dip must be the shallower one
    if os.environ.get("DYN_BENCH_CHAOS_DRAIN", "1") != "0":
        out["config"]["drain"] = dr = _drain_sim_ab()
        print(
            f"# drain A/B (sim): attainment dip "
            f"{dr['sim_attainment_dip_kill']:.4f} (kill) -> "
            f"{dr['sim_attainment_dip_drain']:.4f} (drain), "
            f"{dr['sim_streams_migrated_drain']} stream(s) handed off",
            file=sys.stderr,
        )
    print(json.dumps(out))
    print(
        f"# chaos A/B: base={base['tput']:.1f} chaos={chaos['tput']:.1f} "
        f"tok/s, attainment {base['slo']['attainment']:.2%} -> "
        f"{chaos['slo']['attainment']:.2%}, {fired} fault(s) fired",
        file=sys.stderr,
    )


def _main_sim() -> None:
    """--sim: scaling-policy regression watch, no accelerator at all.

    Replays a canned diurnal+burst trace (fixed seed 2026) through the
    discrete-event fleet simulator at three static fleet sizes and once
    with the autoscaling planner, and reports SLO attainment + goodput
    per configuration as two JSON lines (planner_sim_slo_attainment /
    planner_sim_goodput). The headline attainment is of OFFERED load —
    shed and killed requests count as misses — so a policy cannot look
    healthy by rejecting traffic; per-row `slo_attainment` (of admitted
    work) is kept alongside. Policy regressions — watermark changes,
    admission defaults, degradation ladder — move these numbers while
    the chip benches stay flat. Knobs: DYN_BENCH_SIM_DURATION (sim
    seconds, default 1800), DYN_BENCH_SIM_SEED."""
    from dynamo_tpu.planner import PlannerConfig
    from dynamo_tpu.sim import (
        FleetSim,
        SimConfig,
        bursty_trace,
        diurnal_trace,
        merge_traces,
    )

    seed = int(os.environ.get("DYN_BENCH_SIM_SEED", "2026"))
    duration = float(os.environ.get("DYN_BENCH_SIM_DURATION", "1800"))
    trace = merge_traces(
        diurnal_trace(duration, seed, base_rps=12.0, peak_rps=45.0,
                      period_s=duration),
        bursty_trace(duration, seed + 1, calm_rps=4.0, burst_rps=60.0,
                     mean_calm_s=240.0, mean_burst_s=25.0),
    )
    fleet_sizes = (2, 4, 8)
    rows: dict[str, dict] = {}

    def run_one(decode: int, autoscale: bool) -> dict:
        cfg = SimConfig(initial_decode=decode, initial_prefill=1,
                        max_queue_depth=150, slo_ttft_ms=3000.0,
                        slo_itl_ms=60.0)
        fleet = FleetSim(trace, cfg)
        if autoscale:
            fleet.attach_planner(PlannerConfig(
                adjustment_interval_s=20.0, grace_cycles=2,
                reconcile_cycles=2, slo_target=0.95,
                min_decode=1, max_decode=max(fleet_sizes),
                min_prefill=1, max_prefill=4,
            ))
        res = fleet.run()
        # worker-seconds actually provisioned (resource cost) — the
        # timeline integral for EVERY row, so static and autoscaled
        # runs are costed over the same horizon (trace + drain)
        worker_ticks = sum(
            s["decode_workers_reporting"] for s in res["timeline"]
        ) * cfg.metric_interval_s
        return {
            "slo_attainment": round(res["slo_attainment"], 4),
            "slo_attainment_offered": round(
                res["slo_attainment_offered"], 4
            ),
            "goodput_tok_s": round(res["goodput_tok_s"], 2),
            "shed": res["shed"],
            "requests": res["requests"],
            "worker_seconds": round(worker_ticks, 1),
        }

    for n in fleet_sizes:
        rows[f"static-{n}"] = run_one(n, autoscale=False)
    rows["planner"] = run_one(2, autoscale=True)

    config = {
        "seed": seed,
        "duration_s": duration,
        "trace_requests": len(trace),
        "fleet_sizes": list(fleet_sizes),
        **rows,
    }
    peak = rows[f"static-{max(fleet_sizes)}"]
    dyn = rows["planner"]
    print(json.dumps({
        "metric": "planner_sim_slo_attainment",
        "value": dyn["slo_attainment_offered"],
        "unit": "fraction",
        # autoscaled offered-load attainment relative to the capacity-
        # planned static peak fleet (1.0 = planner matches peak
        # provisioning without peak cost)
        "vs_baseline": round(
            dyn["slo_attainment_offered"]
            / max(1e-9, peak["slo_attainment_offered"]), 4
        ),
        "config": config,
    }))
    print(json.dumps({
        "metric": "planner_sim_goodput",
        "value": dyn["goodput_tok_s"],
        "unit": "goodput_tokens/sec",
        "vs_baseline": round(
            dyn["goodput_tok_s"] / max(1e-9, peak["goodput_tok_s"]), 4
        ),
        "config": {
            "planner_worker_seconds": dyn["worker_seconds"],
            "static_peak_worker_seconds": peak["worker_seconds"],
        },
    }))
    print(
        "# sim: " + " ".join(
            f"{k}={v['slo_attainment_offered']:.3f}"
            f"@{v['goodput_tok_s']:.0f}tok/s"
            for k, v in rows.items()
        ),
        file=sys.stderr,
    )


def _kvfleet_compare(measured: dict, base: dict) -> dict:
    """Pure comparison for the kvfleet sentinel (unit-tested without a
    sim run): measured ``{"hit_rate", "avoided_frac"}`` vs a baseline
    entry with an explicit ``noise_frac``. Either headline falling
    below its floor is a regression; a zero hit rate or a recompute
    bill that did NOT shrink with the fabric on is an unconditional
    regression — the A/B invariant holds regardless of how wide the
    noise band is."""
    noise = float(base.get("noise_frac", 0.25))
    hit_floor = base["hit_rate"] * (1.0 - noise)
    avoided_floor = base["avoided_frac"] * (1.0 - noise)
    return {
        "regressed": (
            measured["hit_rate"] <= 0.0
            or measured["avoided_frac"] <= 0.0
            or measured["hit_rate"] < hit_floor
            or measured["avoided_frac"] < avoided_floor
        ),
        "hit_rate": round(measured["hit_rate"], 4),
        "baseline_hit_rate": base["hit_rate"],
        "floor_hit_rate": round(hit_floor, 4),
        "avoided_frac": round(measured["avoided_frac"], 4),
        "baseline_avoided_frac": base["avoided_frac"],
        "floor_avoided_frac": round(avoided_floor, 4),
        "noise_frac": noise,
    }


def _main_kvfleet() -> None:
    """--kvfleet: the fleet KV fabric A/B, pure host-side discrete-event
    run — no jax, no chip (docs/kvbm.md "Fleet fabric").

    The canned diurnal trace with Zipf-popular shared prefix families
    (sim/traces.py PrefixModel: a few giant system prompts dominate)
    replays through FleetSim twice: fabric off, where every request
    reprefills its shared head, and fabric on, where catalog hits fetch
    it at peer/bucket rate instead. Headlines:

    - ``kvfleet_hit_rate`` — fleet prefix hit rate over requests that
      carry a shared prefix;
    - ``kvfleet_reprefill_avoided`` — the fraction of the fabric-off
      recompute bill (prefilled tokens) the fabric removed.

    Both gate against the committed ``cpu-kvfleet-quick``/``-full``
    profile in BENCH_BASELINE.json (exit 1 regression / exit 2 missing
    profile; ``--update-baseline`` seeds; DYN_SENTINEL_REPORT writes
    the CI artifact). The determinism of the sim makes the noise band
    narrow by construction — the band absorbs deliberate model
    retuning, not run-to-run jitter."""
    from dynamo_tpu.sim import FleetSim, SimConfig, diurnal_trace
    from dynamo_tpu.sim.traces import PrefixModel

    argv = sys.argv[1:]
    quick = "--quick" in argv
    seed = int(os.environ.get("DYN_BENCH_KVFLEET_SEED", "7"))
    duration = float(os.environ.get(
        "DYN_BENCH_KVFLEET_DURATION", "300" if quick else "1200"
    ))
    trace = diurnal_trace(
        duration, seed, base_rps=8.0, peak_rps=24.0, period_s=duration,
        prefixes=PrefixModel(),
    )

    def run_one(fabric: bool) -> dict:
        cfg = SimConfig(
            initial_decode=4, initial_prefill=1, max_queue_depth=200,
            fabric=fabric,
        )
        return FleetSim(trace, cfg).run()["fabric"]

    off = run_one(fabric=False)
    on = run_one(fabric=True)
    hit_rate = on["fleet_hit_rate"]
    avoided = on["reprefill_tokens_avoided"]
    avoided_frac = avoided / max(1, off["prefilled_tokens"])
    measured = {"hit_rate": hit_rate, "avoided_frac": avoided_frac}

    # -- sentinel gate (same discipline as --sentinel / --fanout) ---------
    path = _sentinel_baseline_path()
    if "--baseline" in argv:
        i = argv.index("--baseline") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            raise SystemExit("--baseline requires a path argument")
        path = argv[i]
    key = f"cpu-kvfleet-{'quick' if quick else 'full'}"
    baselines: dict = {"profiles": {}}
    if os.path.exists(path):
        with open(path) as f:
            baselines = json.load(f)
    if "--update-baseline" in argv:
        baselines.setdefault("profiles", {})[key] = {
            "hit_rate": round(hit_rate, 4),
            "avoided_frac": round(avoided_frac, 4),
            # the sim is deterministic; the band exists for deliberate
            # trace/model retuning, not machine noise
            "noise_frac": 0.25,
        }
        with open(path, "w") as f:
            json.dump(baselines, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# kvfleet: baseline profile {key!r} written to {path}",
              file=sys.stderr)
    base = (baselines.get("profiles") or {}).get(key)
    config = {
        "profile": key,
        "baseline_path": path,
        "seed": seed,
        "duration_s": duration,
        "trace_requests": len(trace),
        "prefix_requests": on["prefix_requests"],
        "fleet_hits_host": on["fleet_hits_host"],
        "fleet_hits_bucket": on["fleet_hits_bucket"],
        "publishes": on["publishes"],
        "demoted_bucket": on["demoted_bucket"],
        "demoted_dropped": on["demoted_dropped"],
        "prefilled_tokens_off": off["prefilled_tokens"],
        "prefilled_tokens_on": on["prefilled_tokens"],
        "reprefill_tokens_avoided": avoided,
    }
    if base is None:
        print(json.dumps({
            "metric": "kvfleet_hit_rate", "value": round(hit_rate, 4),
            "unit": "fraction", "vs_baseline": 0.0,
            "config": {"error": f"no baseline profile {key!r} in {path}",
                       "hint": "run with --update-baseline and commit"},
        }))
        print(json.dumps({
            "metric": "kvfleet_reprefill_avoided",
            "value": round(avoided_frac, 4),
            "unit": "fraction_of_prefill_bill", "vs_baseline": 0.0,
            "config": {"error": f"no baseline profile {key!r} in {path}"},
        }))
        sys.exit(2)
    verdict = _kvfleet_compare(measured, base)
    out_hits = {
        "metric": "kvfleet_hit_rate",
        "value": round(hit_rate, 4),
        "unit": "fraction",
        "vs_baseline": round(hit_rate / max(base["hit_rate"], 1e-9), 4),
        "config": {**config, **verdict},
    }
    out_avoided = {
        "metric": "kvfleet_reprefill_avoided",
        "value": round(avoided_frac, 4),
        "unit": "fraction_of_prefill_bill",
        "vs_baseline": round(
            avoided_frac / max(base["avoided_frac"], 1e-9), 4
        ),
        "config": {"profile": key, **verdict},
    }
    print(json.dumps(out_hits))
    print(json.dumps(out_avoided))
    report_path = os.environ.get("DYN_SENTINEL_REPORT")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(
                {"hit_rate": out_hits, "avoided": out_avoided},
                f, indent=2,
            )
            f.write("\n")
    if verdict["regressed"]:
        print(
            f"# KVFLEET REGRESSION: hit_rate {verdict['hit_rate']} "
            f"(floor {verdict['floor_hit_rate']}) avoided_frac "
            f"{verdict['avoided_frac']} (floor "
            f"{verdict['floor_avoided_frac']}) vs baseline "
            f"hit_rate={base['hit_rate']} "
            f"avoided_frac={base['avoided_frac']} "
            f"-{verdict['noise_frac']:.0%}",
            file=sys.stderr,
        )
        sys.exit(1)
    print(
        f"# kvfleet OK: hit_rate {hit_rate:.3f}, "
        f"{avoided} reprefill tokens avoided "
        f"({avoided_frac:.1%} of the bill, {key})",
        file=sys.stderr,
    )


def _fanout_compare(measured: dict, base: dict) -> dict:
    """Pure comparison for the fan-out sentinel (unit-tested without a
    server): measured ``{"rps", "streams"}`` vs a baseline entry with an
    explicit ``noise_frac``. Either headline falling below its floor is
    a regression — host-plane throughput gates exactly like decode."""
    noise = float(base.get("noise_frac", 0.5))
    rps_floor = base["rps"] * (1.0 - noise)
    streams_floor = base["streams"] * (1.0 - noise)
    return {
        "regressed": (
            measured["rps"] < rps_floor
            or measured["streams"] < streams_floor
        ),
        "rps": round(measured["rps"], 1),
        "baseline_rps": base["rps"],
        "floor_rps": round(rps_floor, 1),
        "streams": measured["streams"],
        "baseline_streams": base["streams"],
        "floor_streams": int(streams_floor),
        "noise_frac": noise,
    }


def _main_fanout() -> None:
    """--fanout: the frontend host-plane ceiling — no accelerator, no
    jax (docs/observability.md "Host data plane").

    Boots the REAL HttpService (port 0, dedicated server thread/loop)
    over a synthetic chat engine, then drives it from a client loop:

    - a non-stream RPS ladder at rising concurrency (instant engine:
      every microsecond measured is host work — parse, admission,
      dispatch, aggregate, serialize), headline = best rung's req/s;
    - a concurrent-SSE stream ladder (paced engine holds every rung's
      streams open simultaneously), headline = the largest rung whose
      streams ALL completed; each rung reports the server loop's lag
      p99 over just that rung (LoopLagMonitor.reset_window between
      rungs) and the ledger's per-stream host cost.

    Emits TWO JSON lines — ``frontend_fanout_rps`` and
    ``frontend_fanout_streams`` — gated against the committed
    ``cpu-fanout-quick``/``cpu-fanout-full`` profile in
    BENCH_BASELINE.json exactly like the decode sentinel (exit 1
    regression / exit 2 missing profile; ``--update-baseline`` seeds;
    DYN_SENTINEL_REPORT writes the CI artifact). ``--quick`` shrinks
    both ladders for the CI tier. Knobs: DYN_BENCH_FANOUT_CHUNKS /
    DYN_BENCH_FANOUT_INTERVAL_S shape the synthetic stream."""
    import resource
    import threading

    import aiohttp

    from dynamo_tpu.http.service import HttpService, ModelManager
    from dynamo_tpu.protocols.openai import ChatDeltaGenerator
    from dynamo_tpu.telemetry.hostplane import LoopLagMonitor

    argv = sys.argv[1:]
    quick = "--quick" in argv
    chunks = int(os.environ.get("DYN_BENCH_FANOUT_CHUNKS", "4"))
    interval_s = float(os.environ.get("DYN_BENCH_FANOUT_INTERVAL_S", "0.05"))

    class _SyntheticEngine:
        """Chat engine of pure host cost: real ChatCompletionChunk
        objects (serialize cost is the production pydantic dump), zero
        chip work. ``interval_s`` > 0 paces chunks so N in-flight
        streams are N OPEN streams, not N sequential sprints."""

        def __init__(self, pace_s: float):
            self.pace_s = pace_s

        def generate(self, req, ctx):
            return self._gen(req, ctx)

        async def _gen(self, req, ctx):
            gen = ChatDeltaGenerator(model=req.model or "fanout")
            yield gen.role_chunk()
            for _ in range(chunks):
                if self.pace_s > 0:
                    await asyncio.sleep(self.pace_s)
                else:
                    await asyncio.sleep(0)
                yield gen.text_chunk("synthetic delta text ")
            yield gen.finish_chunk("stop")

    # both the client and server sockets of every stream live in THIS
    # process: 2 fds per open stream, so the ladder's top rung is
    # bounded by the nofile limit (recorded in the config stanza)
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        soft = hard
    fd_budget = max(64, (soft - 1000) // 2)
    if os.environ.get("DYN_BENCH_FANOUT_SMOKE") == "1":
        # tests/test_hostplane.py: the smallest honest run — one rung
        # per ladder, enough traffic to populate every surface
        rps_rungs = (2,)
        rps_reqs_per_rung = 20
        stream_rungs = (8,)
    elif quick:
        rps_rungs = (4, 16)
        rps_reqs_per_rung = 300
        stream_rungs = tuple(n for n in (64, 256) if n <= fd_budget)
    else:
        rps_rungs = (4, 16, 64, 256)
        rps_reqs_per_rung = 1500
        stream_rungs = tuple(
            n for n in (512, 2048, 8192) if n <= fd_budget
        )

    # -- server side: real HttpService on its own thread + loop ----------
    mm = ModelManager()
    mm.add_chat_model("fanout", _SyntheticEngine(pace_s=0.0))
    mm.add_chat_model("fanout-paced", _SyntheticEngine(pace_s=interval_s))
    # fine-grained heartbeat (20 ms) so a few-second rung still yields a
    # real p99; no blackbox — under deliberate overload the stall
    # counter is the signal, a dump per rung would be noise
    monitor = LoopLagMonitor(interval_s=0.02, window=4096)
    svc = HttpService(mm, host="127.0.0.1", port=0, lag_monitor=monitor)
    server_loop = asyncio.new_event_loop()
    started = threading.Event()

    def _serve() -> None:
        asyncio.set_event_loop(server_loop)
        server_loop.run_until_complete(svc.start())
        started.set()
        server_loop.run_forever()

    server = threading.Thread(target=_serve, name="fanout-server", daemon=True)
    server.start()
    if not started.wait(timeout=30):
        raise SystemExit("fanout: server failed to start")
    base_url = f"http://127.0.0.1:{svc.port}"

    def _reset_lag() -> None:
        server_loop.call_soon_threadsafe(monitor.reset_window)

    # -- client side ------------------------------------------------------
    async def _drive() -> dict:
        timeout = aiohttp.ClientTimeout(
            total=None, sock_connect=60, sock_read=120
        )
        conn = aiohttp.TCPConnector(limit=0)
        results: dict = {"rps_rungs": [], "stream_rungs": []}
        async with aiohttp.ClientSession(
            timeout=timeout, connector=conn
        ) as session:

            async def lag_now() -> dict:
                async with session.get(f"{base_url}/debug/hostplane") as r:
                    snap = await r.json()
                fe = snap.get("frontend", {})
                return {
                    "lag": fe.get("loop", {}).get("lag", {}),
                    "stalls": fe.get("loop", {}).get("stalls", 0),
                    "ledger": fe.get("ledger", {}),
                }

            body = {
                "model": "fanout",
                "messages": [{"role": "user", "content": "ping"}],
                "stream": False,
            }
            for conc in rps_rungs:
                _reset_lag()
                left = rps_reqs_per_rung
                errors = 0

                async def worker():
                    nonlocal left, errors
                    url = f"{base_url}/v1/chat/completions"
                    while left > 0:
                        left -= 1
                        async with session.post(url, json=body) as r:
                            await r.read()
                            if r.status != 200:
                                errors += 1

                t0 = time.monotonic()
                await asyncio.gather(*(worker() for _ in range(conc)))
                dt = time.monotonic() - t0
                probe = await lag_now()
                results["rps_rungs"].append({
                    "concurrency": conc,
                    "requests": rps_reqs_per_rung,
                    "errors": errors,
                    "rps": round(rps_reqs_per_rung / max(dt, 1e-9), 1),
                    "lag_p99_ms": probe["lag"].get("p99_ms", 0.0),
                    "lag_max_ms": probe["lag"].get("max_ms", 0.0),
                })

            sbody = dict(body, model="fanout-paced", stream=True)
            for n in stream_rungs:
                _reset_lag()
                failures = 0

                async def one_stream():
                    nonlocal failures
                    url = f"{base_url}/v1/chat/completions"
                    try:
                        async with session.post(url, json=sbody) as r:
                            ok = r.status == 200
                            async for _ in r.content:
                                pass
                            if not ok:
                                failures += 1
                    except (aiohttp.ClientError, OSError,
                            asyncio.TimeoutError):
                        failures += 1

                t0 = time.monotonic()
                tasks = []
                for i in range(n):
                    tasks.append(asyncio.ensure_future(one_stream()))
                    if i % 256 == 255:
                        # stagger socket bring-up so the listen backlog
                        # measures streaming fan-out, not SYN flooding
                        await asyncio.sleep(0)
                await asyncio.gather(*tasks)
                dt = time.monotonic() - t0
                probe = await lag_now()
                ledger = probe["ledger"]
                results["stream_rungs"].append({
                    "streams": n,
                    "failures": failures,
                    "wall_s": round(dt, 3),
                    "lag_p99_ms": probe["lag"].get("p99_ms", 0.0),
                    "lag_max_ms": probe["lag"].get("max_ms", 0.0),
                    "stalls_total": probe["stalls"],
                    "sse_write_ema_us": ledger.get("sse_write_ema_us"),
                    "host_stage_ms_mean": (
                        ledger.get("window", {}).get("stage_ms_mean", {})
                    ),
                })
        return results

    try:
        results = asyncio.run(_drive())
    finally:
        asyncio.run_coroutine_threadsafe(svc.stop(), server_loop).result(30)
        server_loop.call_soon_threadsafe(server_loop.stop)
        server.join(timeout=30)

    clean_rps = [r for r in results["rps_rungs"] if r["errors"] == 0]
    rps_ceiling = max((r["rps"] for r in clean_rps), default=0.0)
    clean_streams = [
        r for r in results["stream_rungs"] if r["failures"] == 0
    ]
    stream_ceiling = max((r["streams"] for r in clean_streams), default=0)

    # -- sentinel gate (same discipline as --sentinel) --------------------
    path = _sentinel_baseline_path()
    if "--baseline" in argv:
        i = argv.index("--baseline") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            raise SystemExit("--baseline requires a path argument")
        path = argv[i]
    key = f"cpu-fanout-{'quick' if quick else 'full'}"
    measured = {"rps": rps_ceiling, "streams": stream_ceiling}
    baselines: dict = {"profiles": {}}
    if os.path.exists(path):
        with open(path) as f:
            baselines = json.load(f)
    if "--update-baseline" in argv:
        baselines.setdefault("profiles", {})[key] = {
            "rps": round(rps_ceiling, 1),
            "streams": stream_ceiling,
            # single-core CI runners swing hard on pure host-throughput
            # numbers — wide explicit band, tighten per-fleet on purpose
            "noise_frac": 0.5,
        }
        with open(path, "w") as f:
            json.dump(baselines, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# fanout: baseline profile {key!r} written to {path}",
              file=sys.stderr)
    base = (baselines.get("profiles") or {}).get(key)
    config = {
        "profile": key,
        "baseline_path": path,
        "chunks_per_stream": chunks,
        "chunk_interval_s": interval_s,
        "fd_budget_streams": fd_budget,
        "rps_rungs": results["rps_rungs"],
        "stream_rungs": results["stream_rungs"],
    }
    if base is None:
        print(json.dumps({
            "metric": "frontend_fanout_rps", "value": rps_ceiling,
            "unit": "requests/sec", "vs_baseline": 0.0,
            "config": {"error": f"no baseline profile {key!r} in {path}",
                       "hint": "run with --update-baseline and commit"},
        }))
        print(json.dumps({
            "metric": "frontend_fanout_streams", "value": stream_ceiling,
            "unit": "concurrent_streams", "vs_baseline": 0.0,
            "config": {"error": f"no baseline profile {key!r} in {path}"},
        }))
        sys.exit(2)
    verdict = _fanout_compare(measured, base)
    out_rps = {
        "metric": "frontend_fanout_rps",
        "value": rps_ceiling,
        "unit": "requests/sec",
        "vs_baseline": round(rps_ceiling / max(base["rps"], 1e-9), 4),
        "config": {**config, **verdict},
    }
    out_streams = {
        "metric": "frontend_fanout_streams",
        "value": stream_ceiling,
        "unit": "concurrent_streams",
        "vs_baseline": round(
            stream_ceiling / max(base["streams"], 1e-9), 4
        ),
        "config": {"profile": key, **verdict},
    }
    print(json.dumps(out_rps))
    print(json.dumps(out_streams))
    report_path = os.environ.get("DYN_SENTINEL_REPORT")
    if report_path:
        with open(report_path, "w") as f:
            json.dump({"rps": out_rps, "streams": out_streams}, f, indent=2)
            f.write("\n")
    if verdict["regressed"]:
        print(
            f"# FANOUT REGRESSION: rps {verdict['rps']} (floor "
            f"{verdict['floor_rps']}) streams {verdict['streams']} "
            f"(floor {verdict['floor_streams']}) vs baseline "
            f"rps={base['rps']} streams={base['streams']} "
            f"-{verdict['noise_frac']:.0%}",
            file=sys.stderr,
        )
        sys.exit(1)
    print(
        f"# fanout OK: {rps_ceiling:.0f} req/s, {stream_ceiling} "
        f"concurrent streams ({key})",
        file=sys.stderr,
    )


def _sentinel_profile_key(
    cpu_mode: bool, wl: dict, quick: bool, spec: bool = True
) -> str:
    """Baseline entries key on platform + model + quick/full so a CPU
    CI run never compares against a TPU headline number. The default
    (spec+overlap) headline keeps the bare key; the DYN_BENCH_SPEC=0
    escape hatch gets its own ``-nospec`` profile — the two modes run
    entirely different step programs (fused windows vs the spec
    pipeline at decode_steps=1), so comparing across them would make
    the gate vacuous in one direction and a false alarm in the other."""
    return (
        f"{'cpu' if cpu_mode else 'tpu'}-{wl['model_name']}-"
        f"{'quick' if quick else 'full'}"
        + ("" if spec else "-nospec")
    )


def _sentinel_compare(measured: dict, base: dict) -> dict:
    """Pure comparison logic (unit-tested without an engine): measured
    ``{"tok_s", "roofline_frac", "step_time_frac"}`` vs a baseline
    entry with EXPLICIT noise bands. Returns the verdict dict printed
    as the sentinel report:

    - ``regressed`` — tok/s fell below ``base.tok_s × (1 − noise_frac)``
      (the gate; roofline_frac rides along informationally since it
      moves with tok/s by construction);
    - ``bucket_deltas`` — measured − baseline per attribution bucket;
    - ``losing_bucket`` — the bucket whose time share GREW most beyond
      the per-bucket noise band (``bucket_noise_abs``): the named owner
      of the lost tokens.
    """
    noise = float(base.get("noise_frac", 0.15))
    floor = base["tok_s"] * (1.0 - noise)
    regressed = measured["tok_s"] < floor
    bucket_noise = float(base.get("bucket_noise_abs", 0.05))
    deltas: dict[str, float] = {}
    losing, losing_delta = "", 0.0
    for bucket, base_frac in (base.get("step_time_frac") or {}).items():
        cur = (measured.get("step_time_frac") or {}).get(bucket, 0.0)
        d = round(cur - float(base_frac), 4)
        deltas[bucket] = d
        if d > losing_delta and d > bucket_noise:
            losing, losing_delta = bucket, d
    if regressed and not losing and deltas:
        # nothing beat the bucket band but the headline fell: name the
        # largest POSITIVE mover, or call the slowdown uniform — naming
        # a bucket that shrank would send the reader chasing the one
        # place the time did NOT go
        grew = {k: v for k, v in deltas.items() if v > 0}
        losing = max(grew, key=grew.get) if grew else "uniform"
    return {
        "regressed": regressed,
        "tok_s": round(measured["tok_s"], 2),
        "baseline_tok_s": base["tok_s"],
        "noise_frac": noise,
        "floor_tok_s": round(floor, 2),
        "roofline_frac": measured.get("roofline_frac"),
        "baseline_roofline_frac": base.get("roofline_frac"),
        "bucket_deltas": deltas,
        "bucket_noise_abs": bucket_noise,
        "losing_bucket": losing,
    }


def _sentinel_baseline_path() -> str:
    return os.environ.get("DYN_BENCH_BASELINE") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_BASELINE.json"
    )


def _main_sentinel(model_cfg, wl, cpu_mode: bool) -> None:
    """--sentinel: the bench regression gate (docs/observability.md
    "Perf attribution"). Runs the headline workload, compares tok/s and
    the attribution breakdown against the committed BENCH_BASELINE.json
    (override: --baseline PATH / DYN_BENCH_BASELINE), prints the
    attribution delta naming the bucket that ate the loss, and exits
    nonzero on regression. ``--quick`` shrinks the workload for the CI
    CPU-interpret smoke tier; ``--update-baseline`` rewrites this
    profile's entry from the measured run (commit the diff
    deliberately). DYN_SENTINEL_REPORT=path additionally writes the
    report JSON there (the CI artifact)."""
    argv = sys.argv[1:]
    quick = "--quick" in argv
    if quick:
        # small enough for a CI CPU run, big enough for a steady decode
        # window (the attribution fractions need some steps)
        wl = dict(wl, batch=min(wl["batch"], 2), isl=min(wl["isl"], 16),
                  osl=min(wl["osl"], 16))
    # the sentinel gates the HEADLINE configuration, which defaults to
    # overlapped speculative decoding at decode_steps=1 (DYN_BENCH_SPEC
    # escape hatch mirrors the headline's)
    headline_spec = os.environ.get("DYN_BENCH_SPEC", "1") != "0"
    decode_steps = 1 if headline_spec else (4 if quick else None)
    path = _sentinel_baseline_path()
    if "--baseline" in argv:
        i = argv.index("--baseline") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            raise SystemExit("--baseline requires a path argument")
        path = argv[i]
    key = _sentinel_profile_key(cpu_mode, wl, quick, spec=headline_spec)
    r = asyncio.run(_run(
        model_cfg, wl, spec=headline_spec, decode_steps=decode_steps
    ))
    attr = r["attribution"]
    measured = {
        "tok_s": r["tput"],
        "roofline_frac": (
            attr["roofline_frac"]
            if attr["roofline_frac"] is not None
            else round(r["tput"] / r["roofline"], 6)
        ),
        "step_time_frac": attr["frac"],
    }
    baselines: dict = {"profiles": {}}
    if os.path.exists(path):
        with open(path) as f:
            baselines = json.load(f)
    if "--update-baseline" in argv:
        baselines.setdefault("profiles", {})[key] = {
            "tok_s": round(measured["tok_s"], 2),
            "roofline_frac": round(measured["roofline_frac"], 6),
            "step_time_frac": {
                k: round(v, 4)
                for k, v in measured["step_time_frac"].items()
            },
            # explicit noise bands: CPU-interpret timings swing with
            # runner hardware, so the quick tier gets a wide gate —
            # tighten deliberately, per profile, when the fleet is known
            "noise_frac": 0.15 if not cpu_mode else 0.5,
            "bucket_noise_abs": 0.05 if not cpu_mode else 0.2,
        }
        with open(path, "w") as f:
            json.dump(baselines, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# sentinel: baseline profile {key!r} written to {path}",
              file=sys.stderr)
    base = (baselines.get("profiles") or {}).get(key)
    if base is None:
        print(json.dumps({
            "metric": "bench_sentinel", "value": round(r["tput"], 2),
            "unit": "tokens/sec", "vs_baseline": 0.0,
            "config": {"error": f"no baseline profile {key!r} in {path}",
                       "hint": "run with --update-baseline and commit"},
        }))
        sys.exit(2)
    verdict = _sentinel_compare(measured, base)
    out = {
        "metric": "bench_sentinel",
        "value": round(r["tput"], 2),
        "unit": "tokens/sec",
        "vs_baseline": round(r["tput"] / max(base["tok_s"], 1e-9), 4),
        "config": {"profile": key, "baseline_path": path, **verdict},
    }
    print(json.dumps(out))
    report_path = os.environ.get("DYN_SENTINEL_REPORT")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    if verdict["regressed"]:
        delta = verdict["bucket_deltas"].get(verdict["losing_bucket"], 0.0)
        print(
            f"# SENTINEL REGRESSION: {verdict['tok_s']} tok/s < floor "
            f"{verdict['floor_tok_s']} (baseline {base['tok_s']} "
            f"-{verdict['noise_frac']:.0%}); losing bucket: "
            f"{verdict['losing_bucket'] or 'unknown'} "
            f"({delta:+.4f} of step time)",
            file=sys.stderr,
        )
        sys.exit(1)
    print(
        f"# sentinel OK: {verdict['tok_s']} tok/s >= floor "
        f"{verdict['floor_tok_s']} ({key})",
        file=sys.stderr,
    )


def main() -> None:
    if "--sim" in sys.argv[1:]:
        _main_sim()  # pure host-side discrete-event run: no jax, no chip
        return
    if "--fanout" in sys.argv[1:]:
        _main_fanout()  # frontend host-plane ceiling: no jax, no chip
        return
    if "--kvfleet" in sys.argv[1:]:
        _main_kvfleet()  # fleet KV fabric A/B: no jax, no chip
        return
    from dynamo_tpu.utils.jaxtools import describe_devices, force_platform

    cpu_mode = os.environ.get("DYN_BENCH_PLATFORM") == "cpu"
    if cpu_mode:
        force_platform("cpu")
    # name the device every number below was taken on; without
    # DYN_BENCH_PLATFORM=cpu a run that found no TPU stops here, before
    # any model is built (JAX falls back to the CPU silently)
    device = {k: v for k, v in describe_devices().items()
              if k in ("platform", "kind", "count")}
    if not cpu_mode and device["platform"] != "tpu":
        print(json.dumps({
            "ok": False, "device": device,
            "error": "no TPU found; set DYN_BENCH_PLATFORM=cpu for a "
                     "CPU run (counts only, never a device metric)",
        }))
        sys.exit(2)
    model_cfg, wl = _build_config(cpu_mode)
    if "--sentinel" in sys.argv[1:]:
        _main_sentinel(model_cfg, wl, cpu_mode)
        return
    if "--spec" in sys.argv[1:]:
        _main_spec_ab(model_cfg, wl)
        return
    if "--chaos" in sys.argv[1:]:
        _main_chaos_ab(model_cfg, wl)
        return
    if "--spec-overlap" in sys.argv[1:]:
        _main_spec_overlap_ab(model_cfg, wl)
        return
    if "--overlap" in sys.argv[1:]:
        _main_overlap_ab(model_cfg, wl)
        return
    if "--guided" in sys.argv[1:]:
        _main_guided_ab(model_cfg, wl)
        return
    if "--matmul" in sys.argv[1:]:
        _main_matmul_ab(model_cfg, wl)
        return
    if "--kv-dtype" in sys.argv[1:]:
        _main_kv_dtype_ab(model_cfg, wl)
        return
    headline_overlap = os.environ.get("DYN_BENCH_OVERLAP", "1") != "0"
    # headline default: overlapped speculative decoding over int8 KV —
    # spec (accepted drafts multiply tokens/step) composed with the
    # decode pipeline (drafting hidden under the in-flight verify), at
    # decode_steps=1 (speculation replaces fused windows).
    # DYN_BENCH_SPEC=0 is the escape hatch back to the window headline.
    headline_spec = os.environ.get("DYN_BENCH_SPEC", "1") != "0"
    r = asyncio.run(_run(
        model_cfg, wl, overlap=headline_overlap, spec=headline_spec,
        decode_steps=1 if headline_spec else None,
    ))
    phases = (
        _phase_breakdown(model_cfg, wl, r["kv_dtype"])
        if "--phases" in sys.argv[1:]
        else None
    )
    out = {
        "metric": "engine_decode_throughput_1chip",
        "value": round(r["tput"], 2),
        "unit": "tokens/sec",
        "vs_baseline": round(r["tput"] / r["roofline"], 4),
        "device": device,
        # auditability: the exact workload behind the number
        "config": {
            "model": wl["model_name"],
            "layers": model_cfg.num_hidden_layers,
            "hidden": model_cfg.hidden_size,
            "vocab": model_cfg.vocab_size,
            "quant": wl["quant"],
            "kv_dtype": r["kv_dtype"],
            # resolved quantized-matmul impl (ops/qmatmul.py kernels vs
            # XLA mixed dot) — headline movement must name its lever
            "matmul_impl": r["matmul_impl"],
            "batch": wl["batch"],
            "isl": wl["isl"],
            "osl": wl["osl"],
            "decode_steps": (
                1 if headline_spec
                else int(os.environ.get("DYN_BENCH_DECODE_STEPS", "64"))
            ),
            # speculative decoding stanza (docs/speculative_decoding.md):
            # the headline's spec composition, or enabled=False under
            # the DYN_BENCH_SPEC=0 escape hatch
            "spec": (
                {
                    "enabled": True,
                    "drafter": os.environ.get(
                        "DYN_BENCH_SPEC_DRAFTER", "ngram"
                    ),
                    "spec_tokens": int(
                        os.environ.get("DYN_BENCH_SPEC_TOKENS", "4")
                    ),
                    "proposed_tokens": r["spec_proposed"],
                    "accepted_tokens": r["spec_accepted"],
                    "accept_rate": (
                        round(r["spec_accepted"] / r["spec_proposed"], 4)
                        if r["spec_proposed"] else 0.0
                    ),
                    "draft_hidden_frac": r["spec_draft_hidden_frac"],
                }
                if headline_spec
                else {"enabled": False}
            ),
            # overlapped-pipeline attribution (ISSUE 7): the device-idle
            # share of the measured wall plus per-step overlap stats —
            # movement in the headline number is attributable to the
            # pipeline only if this fraction moved with it
            "overlap": r["overlap"]["overlap_enabled"],
            # live attribution (telemetry/attribution.py): the serving-
            # side decomposition of this run's wall time; roofline_frac
            # here and vs_baseline above share one formula
            # (telemetry/roofline.py) so they must agree up to
            # windowing (the ledger's frac is decode-records-only and
            # skips engine-idle spans; vs_baseline divides by the whole
            # measured wall incl. prefill)
            "roofline_frac_live": r["attribution"]["roofline_frac"],
            "top_loss_bucket": r["attribution"]["top_loss_bucket"],
            "step_time_frac": {
                k: v for k, v in r["attribution"]["frac"].items() if v > 0
            },
            "device_idle_frac": r["overlap"]["device_idle_frac"],
            "idle_gap_ms_per_step": r["overlap"]["idle_gap_ms_per_step"],
            "max_idle_gap_ms": r["overlap"]["max_idle_gap_ms"],
            "steps_dispatched": r["overlap"]["steps_dispatched"],
            "p50_ttft_ms": round(r["p50_ttft_s"] * 1000, 1),
            # tails (ISSUE 4 satellite): the serving story lives in the
            # p90/p99, not the median — BENCH_* files must capture them
            "p90_ttft_ms": round(r["p90_ttft_s"] * 1000, 1),
            "p99_ttft_ms": round(r["p99_ttft_s"] * 1000, 1),
            "p50_itl_ms": round(r["p50_itl_s"] * 1000, 2),
            "p90_itl_ms": round(r["p90_itl_s"] * 1000, 2),
            "p99_itl_ms": round(r["p99_itl_s"] * 1000, 2),
        },
    }
    if phases is not None:
        # per-phase device-time + bytes breakdown (--phases): the
        # roofline gap decomposed in the artifact itself
        out["config"]["phases"] = phases
        step_ms_engine = round(
            wl["batch"] / max(r["tput"], 1e-9) * 1e3, 3
        )
        out["config"]["phases"]["step_ms_engine"] = step_ms_engine
    print(json.dumps(out))
    print(
        f"# detail: total_tokens={r['total_tokens']} wall={r['wall_s']:.2f}s "
        f"ttft p50/p90/p99={r['p50_ttft_s'] * 1000:.0f}/"
        f"{r['p90_ttft_s'] * 1000:.0f}/{r['p99_ttft_s'] * 1000:.0f}ms "
        f"itl p50/p99={r['p50_itl_s'] * 1000:.1f}/"
        f"{r['p99_itl_s'] * 1000:.1f}ms roofline={r['roofline']:.0f} tok/s "
        f"device_idle_frac={r['overlap']['device_idle_frac']:.3f}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
