"""Engine configuration."""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class EngineConfig:
    model_path: str = ""
    model_name: str = ""
    # parallelism (≈ reference flags.rs --tensor-parallel-size etc.)
    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    expert_parallel_size: int = 1
    pipeline_parallel_size: int = 1  # GPipe stage rotation (parallel/pipeline.py)
    num_nodes: int = 1
    node_rank: int = 0
    leader_addr: str = ""
    # KV cache. block_size None = auto: 128-token pages on TPU backends
    # (measured +20% decode and the prefill kernel's MXU-width match —
    # 16-wide pages run the flash dots at 16/128 systolic efficiency),
    # 16 elsewhere (CPU tests, finer prefix-cache granularity).
    block_size: Optional[int] = None
    num_blocks: Optional[int] = None  # None = size by gpu_memory_utilization
    hbm_utilization: float = 0.9
    # "bfloat16" or "float8_e4m3fn" (alias "fp8"): quantized fp8 KV
    # halves cache bytes per token — doubles long-context residency and
    # halves decode-attention HBM reads — at ~1/16 relative rounding
    # per element (reference analogue: vLLM --kv-cache-dtype fp8 the
    # reference passes through, lib/llm vLLM engine args). Scale-free
    # E4M3 storage: the Pallas kernels upcast to bf16 at the VMEM edge
    # (exact), so no per-page scale plumbing — an int8-with-scales
    # variant needs a lane->sublane scale-tile relayout Mosaic's TPU
    # lowering rejects ("unsupported shape cast").
    kv_cache_dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        aliases = {"fp8": "float8_e4m3fn", "float8": "float8_e4m3fn"}
        self.kv_cache_dtype = aliases.get(
            self.kv_cache_dtype, self.kv_cache_dtype
        )

    def wire_kv_dtype(self) -> str:
        """Dtype of PACKED KV blocks (host tiers, disagg wire): an int8
        device cache dequantizes at the block-copy boundary
        (ops/block_copy.py), so everything off-device stays bfloat16;
        float caches ship their own dtype."""
        return (
            "bfloat16" if self.kv_cache_dtype == "int8"
            else self.kv_cache_dtype
        )
    enable_prefix_caching: bool = True
    # KV offload tiers (G2 host / G3 disk; 0 = disabled)
    host_kv_blocks: int = 0
    disk_kv_blocks: int = 0
    disk_kv_path: str = ""
    kv_offload_batch: int = 16
    # restore-vs-recompute gate for the G2 host tier: at startup the
    # engine probes real host<->device copy bandwidth and disables the
    # tier when restoring a block costs more than recomputing its
    # tokens (block_size / this rate). A slow host link fails the
    # probe; the threshold is not measured on the attached chip. Set
    # kv_offload_force=True to keep the tier regardless (benchmarking,
    # known-fast links).
    kv_recompute_tok_per_s: float = 2000.0
    kv_offload_force: bool = False
    # G4 remote tier: bucket in the coordinator store's object plane
    # ("" = disabled; requires the worker to run with a store, and
    # host_kv_blocks > 0 for the demotion cascade to reach it)
    remote_kv_bucket: str = ""
    # batching
    max_batch_size: int = 64
    max_prefill_tokens: int = 4096
    prefill_chunk_size: int = 1024
    max_model_len: Optional[int] = None
    # fused multi-step decode: tokens generated per device dispatch.
    # >1 amortizes host↔device round-trips (the dominant decode cost
    # when dispatch latency is high); tokens stream in bursts of this
    # size and up to decode_steps-1 sampled-past-stop tokens are
    # discarded per finishing request.
    decode_steps: int = 1
    # mixed prefill+decode batching (needs decode_steps > 1): pending
    # prefill chunks ride the decode window's dispatch in a fixed
    # [rows, len] rectangle, so a straggler's prefill costs ~10-15% of a
    # window instead of a dedicated full-weight pass while decode
    # stalls. rows=0 disables (reference behavior: vLLM's mixed
    # scheduler, container/deps/vllm/...-patch :535).
    # rows=8: each mixed window graduates up to 8 prefills into decode;
    # at 4 windows per 128-token generation that sustains a full
    # 32-deep decode batch (4 rows measured as a decode-population cap
    # of 16 — half the batch idle)
    mixed_prefill_rows: int = 8
    mixed_prefill_len: int = 256
    # adaptive WIDE mixed rectangle: when few prompts are prefilling
    # (and decode occupancy is under mixed_wide_max_running, if set),
    # the mixed window swaps its rectangle for
    # [~rows*len/wide_len, wide_len] — same token budget, fewer rows —
    # so a long prompt prefills in backlog/wide_len windows instead of
    # backlog/len (a 3000-token prompt takes 12 windows through the
    # 256-token trickle; dedicated prefill instead starves decode; not
    # measured on the attached chip). 0 disables. The wide variant costs a few extra prewarm
    # compiles at startup.
    mixed_prefill_wide_len: int = 1024
    # decode-occupancy ceiling for the wide rectangle (None = no
    # ceiling, the default): the wide and narrow rectangles have the
    # SAME padded token budget, so when at most wide_rows prompts are
    # prefilling the wide swap costs decode nothing at any occupancy
    # (which is why the old ceiling of 4 was lifted; not measured on
    # the attached chip). The real guards are the prefilling-count
    # (<= wide_rows) and backlog (> narrow len) conditions in
    # scheduler._mixed_rect.
    mixed_wide_max_running: Optional[int] = None
    # speculative decoding (dynamo_tpu/spec; needs decode_steps == 1 —
    # fused windows and speculation are competing multi-token-per-
    # dispatch techniques and do not compose): a dependency-free drafter
    # proposes up to spec_tokens tokens per sequence per step, one
    # jitted verify forward scores them all through the paged-KV
    # attention, and rejection sampling keeps the longest accepted
    # prefix + 1 fresh token. "" disables; "ngram[:N]" = prompt-lookup
    # self-drafting, "bigram:PATH" = static table (spec/drafter.py).
    # Per-request opt-out via PreprocessedRequest.speculative=False
    # (OpenAI ext.speculative). docs/speculative_decoding.md covers K
    # tuning and accept-rate interpretation.
    spec_decode: str = ""
    spec_tokens: int = 4
    # overlapped decode pipeline (docs/performance.md): double-buffer
    # host scheduling against device execution so the only hot-path
    # sync waits on a result that is already (or nearly) done. At
    # decode_steps == 1 the plain decode loop runs dispatch(N+1) —
    # token column chained on device — before harvesting step N; at
    # decode_steps > 1 the cohort prefill dispatch additionally chains
    # its first tokens straight into the first decode window instead of
    # hard-syncing between the two. Greedy output is bit-identical with
    # overlap on or off (the compute is the same program over the same
    # values; only the host's position in the timeline moves).
    # False (--no-overlap) restores the fully serial
    # plan -> dispatch -> sync -> emit loop — the escape hatch, and
    # the side tests/test_overlap.py compares dispatch and sync counts
    # against.
    overlap: bool = True
    # explicit MID decode bucket override (None = auto: pad/2 when the
    # pad is >= 64). Deployments whose steady population sits well
    # under max_batch_size (e.g. long-context residency caps) can pin
    # a lighter window here at the cost of one more prewarmed variant
    # set.
    decode_batch_mid: Optional[int] = None
    # static serving shapes: pad the decode batch to max_batch_size and
    # block-table width to the max_model_len cap so the decode/mixed
    # dispatch is ONE compiled shape (padded rows are ~free — decode is
    # weight-read-bound). Composition-dependent buckets would compile
    # mid-serve: a TTFT stall of one whole step compile.
    static_shapes: bool = True
    # compile every reachable serving shape at startup (None = auto:
    # on for TPU backends, off elsewhere). A lazy compile of a 32-layer
    # step lands mid-serve as a TTFT stall of its whole compile time.
    prewarm: Optional[bool] = None
    # also prewarm the penalty-sampling AND logit-bias step variants
    # (each selects a separately-compiled step carrying its tables) —
    # covers the dedicated prefill shapes and the pure decode windows,
    # the only paths such requests take (they never ride the mixed
    # rectangle). Off by default: it multiplies startup compiles for
    # features many deployments never receive — the first such request
    # then pays a one-time compile stall instead. Multi-feature combos
    # in one batch (e.g. bias+penalties) always compile on first use.
    prewarm_penalties: bool = False
    # likewise for the top-logprobs step variant (requests with
    # top_logprobs > 0 / completions logprobs > 0). Off by default for
    # the same startup-cost reason; the first such request pays a
    # one-time compile stall instead.
    prewarm_logprobs: bool = False
    # likewise for the guided-decoding (allow-mask) step variants
    # (docs/guided_decoding.md): the masked serial prefill/decode
    # shapes, plus the masked spec-verify rectangle on spec engines.
    # Deployments serving structured-output traffic should turn this on
    # — it is what keeps a guided run serve-compile-free under
    # DYN_COMPILE_FENCE. The masked variant set mirrors the flags
    # above: guided+penalties/bias warm only with prewarm_penalties,
    # guided+top-logprobs only with prewarm_logprobs — combos outside
    # the opted-in set pay the same documented first-use compile their
    # unguided counterparts pay. Guided requests need decode_steps == 1
    # (the mask advances on host per committed token), so this flag
    # does too.
    prewarm_guided: bool = False
    # observability (telemetry/{recorder,slo}.py; docs/observability.md)
    # step flight recorder: ring of the last N step records, auto-dumped
    # to JSONL around anomalies. 0 disables recording entirely.
    flight_recorder_steps: int = 256
    # slow-step watchdog: a device step longer than this dumps the ring
    # (None = DYN_SLOW_STEP_MS env, else off). Millseconds of WALL time
    # per dispatch — size it to a few windows, not a single token.
    slow_step_ms: Optional[float] = None
    # where flight-recorder dumps land ("" = DYN_FLIGHT_DIR or tmpdir)
    flight_dump_dir: str = ""
    # SLO targets evaluated per finished request (engine-side TTFT =
    # submit -> first emitted token; ITL = mean decode inter-token
    # latency). None = no target; attainment/goodput then track 1.0 /
    # nothing while the raw TTFT/ITL histograms still populate.
    slo_ttft_ms: Optional[float] = None
    slo_itl_ms: Optional[float] = None
    # weights
    random_weights: bool = False  # bench/test mode: skip checkpoint load
    # weight-only quantization applied at load: None | "int8"
    # (per-channel symmetric, models/quant.py — halves weight HBM
    # traffic and fits the 8B flagship on one 16 GB chip)
    quantization: Optional[str] = None
    seed: int = 0
    extra: dict[str, Any] = field(default_factory=dict)

    def resolve_block_size(self) -> int:
        """The effective page size (see block_size). Initializes the
        JAX backend — call only where that is already safe."""
        if self.block_size is not None:
            return self.block_size
        import jax

        return 128 if jax.default_backend() == "tpu" else 16

    @property
    def mesh_devices(self) -> int:
        return (
            self.tensor_parallel_size
            * self.data_parallel_size
            * self.expert_parallel_size
        )


def load_engine_config(args: Any) -> EngineConfig:
    """Build an EngineConfig from CLI args (+ --extra-engine-args JSON)."""
    extra: dict[str, Any] = {}
    if getattr(args, "extra_engine_args", None):
        with open(args.extra_engine_args) as f:
            extra = json.load(f)
    cfg = EngineConfig(
        model_path=args.model_path or "",
        model_name=args.model_name or (args.model_path or "model").rstrip("/").rsplit("/", 1)[-1],
        tensor_parallel_size=getattr(args, "tensor_parallel_size", 1),
        pipeline_parallel_size=getattr(args, "pipeline_parallel_size", 1),
        num_nodes=getattr(args, "num_nodes", 1),
        node_rank=getattr(args, "node_rank", 0),
        leader_addr=getattr(args, "leader_addr", ""),
        quantization=getattr(args, "quantization", None),
        decode_steps=getattr(args, "decode_steps", 1),
        mixed_prefill_rows=getattr(
            args, "mixed_prefill_rows", EngineConfig.mixed_prefill_rows
        ),
        mixed_prefill_len=getattr(args, "mixed_prefill_len", 256),
        mixed_prefill_wide_len=getattr(
            args, "mixed_prefill_wide_len",
            EngineConfig.mixed_prefill_wide_len,
        ),
        mixed_wide_max_running=getattr(
            args, "mixed_wide_max_running",
            EngineConfig.mixed_wide_max_running,
        ),
        spec_decode=getattr(args, "spec_decode", "") or "",
        spec_tokens=getattr(args, "spec_tokens", EngineConfig.spec_tokens),
        prewarm_guided=getattr(args, "prewarm_guided", False),
        overlap=not getattr(args, "no_overlap", False),
        host_kv_blocks=getattr(args, "host_kv_blocks", 0),
        disk_kv_blocks=getattr(args, "disk_kv_blocks", 0),
        disk_kv_path=getattr(args, "disk_kv_path", ""),
        remote_kv_bucket=getattr(args, "remote_kv_bucket", ""),
        flight_recorder_steps=getattr(
            args, "flight_recorder_steps", EngineConfig.flight_recorder_steps
        ),
        slow_step_ms=getattr(args, "slow_step_ms", None),
        flight_dump_dir=getattr(args, "flight_dump_dir", "") or "",
        slo_ttft_ms=getattr(args, "slo_ttft_ms", None),
        slo_itl_ms=getattr(args, "slo_itl_ms", None),
    )
    for k, v in extra.items():
        if hasattr(cfg, k):
            setattr(cfg, k, v)
        else:
            cfg.extra[k] = v
    return cfg
