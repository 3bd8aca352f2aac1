"""JaxEngine: the async-facing native TPU inference engine.

Orchestration (≈ what vLLM's AsyncLLMEngine does for the reference):

- a dedicated **engine thread** runs the step loop (JAX dispatch blocks;
  the asyncio event loop must never wait on the device);
- one **fused jitted step** does forward + KV-cache update + sampling on
  device, with cache buffers donated so XLA updates them in place;
- per-request output queues bridge back into asyncio via
  ``loop.call_soon_threadsafe``;
- publishes ForwardPassMetrics-shaped stats for the KV router
  (reference: lib/llm/src/kv_router/publisher.rs ForwardPassMetrics).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import functools
import hashlib
import json
import logging
import os
import queue as thread_queue
import random
import threading
import time
import uuid
import zlib
from dataclasses import dataclass
from typing import Any, AsyncIterator, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu import faults
from dynamo_tpu.engine.allocator import (
    BlockAllocator,
    StateSlots,
    WindowPlane,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.kvbm import BlockLayout, KvbmConfig, KvBlockManager
from dynamo_tpu.ops.block_copy import gather_blocks, scatter_blocks
from dynamo_tpu.engine.sampling import (
    SamplingBatch,
    dense_gen_counts,
    dense_prompt_presence,
    sample,
)
from dynamo_tpu.engine.scheduler import (
    Scheduler,
    SeqState,
    Sequence,
    StepPlan,
    mixed_rect_of,
    prefill_rectangles,
    seq_gone,
)
from dynamo_tpu.models import ModelConfig, family as model_family
from dynamo_tpu.utils import affinity, compile_fence, transfer_fence
from dynamo_tpu.utils.bucketing import next_bucket
from dynamo_tpu.models.llama import (
    CACHE_SPEC,
    init_cache,
    param_specs,
)
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh
from dynamo_tpu.protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
)
from dynamo_tpu.runtime.engine import AsyncEngine, Context, EngineStream
from dynamo_tpu.telemetry import autopsy, get_tracer, new_trace_id
from dynamo_tpu.telemetry.debug import (
    note_counts,
    register_count_provider,
    register_debug_provider,
    unregister_count_provider,
    unregister_debug_provider,
)
from dynamo_tpu.telemetry.spans import (
    StepClock,
    bind_step_clock,
    note_loop_thread,
    step_span,
)
from dynamo_tpu.telemetry.blackbox import BlackBox
from dynamo_tpu.telemetry.hbm import HbmAccountant, device_peaks, tree_bytes
from dynamo_tpu.telemetry.instruments import (
    COMPILE_FENCE_EVENTS,
    ENGINE_BATCH_OCCUPANCY,
    ENGINE_COMPILE_EVENTS,
    ENGINE_PREWARM_SECONDS,
    ENGINE_QUEUE_DEPTH,
    ENGINE_REQUESTS_FINISHED,
    ENGINE_STEP_SECONDS,
    ENGINE_TOKENS_GENERATED,
    GUIDED_REQUESTS,
    KV_POOL_BLOCKS_ACTIVE,
    KV_POOL_BLOCKS_TOTAL,
    KV_POOL_CACHED_FREE_BLOCKS,
    SPEC_ACCEPT_RATE,
    SPEC_ACCEPTED_TOKENS,
    SPEC_DRAFT_HIDDEN_FRAC,
    SPEC_PROPOSED_TOKENS,
    SPEC_STEP_SECONDS,
    TRANSFER_FENCE_EVENTS,
)
from dynamo_tpu.telemetry.overlap import OverlapTracker
from dynamo_tpu.telemetry.recorder import FlightRecorder
from dynamo_tpu.telemetry.slo import SloConfig, SloTracker
from dynamo_tpu.tokens import DEFAULT_SALT, TokenBlockSequence

log = logging.getLogger("dynamo_tpu.engine")

# compile-event attribution: "prewarm" while ANY engine's _initialize/
# _prewarm runs, "serve" otherwise — a serve-phase compile is exactly
# the mid-serve TTFT stall the static-shape machinery exists to prevent,
# so it deserves its own counter series. jax.monitoring events carry no
# engine identity, so a refcount of initializing engines is the closest
# attribution a multi-engine process allows.
_initializing_engines = 0
_compile_listener_registered = False
# process-wide persistent-compile-cache hit/miss counts (jax.monitoring)
COMPILE_CACHE_EVENTS = {"hits": 0, "misses": 0}


def _register_compile_listener() -> None:
    """Count XLA compilations via jax.monitoring duration events
    (best-effort: event names vary across jax versions, so filter on
    substring; absence of the API degrades to no compile counting)."""
    global _compile_listener_registered
    if _compile_listener_registered:
        return
    _compile_listener_registered = True
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            if "compile" in event:
                phase = "prewarm" if _initializing_engines > 0 else "serve"
                ENGINE_COMPILE_EVENTS.labels(phase).inc()
                # compile fence (DYN_COMPILE_FENCE, docs/static_analysis
                # .md): the fence keeps its own allowed-window refcount
                # — _initialize registers it alongside this phase tag —
                # and collects anything outside it for _record_step to
                # escalate. Inert unless armed.
                compile_fence.note_compile(event, duration)

        def _on_event(event: str, **kw) -> None:
            # persistent compile cache traffic (utils/jaxtools.py rule):
            # a warm restart shows hits and no misses
            if event == "/jax/compilation_cache/cache_hits":
                COMPILE_CACHE_EVENTS["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                COMPILE_CACHE_EVENTS["misses"] += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:  # pragma: no cover — older/newer jax without the API
        log.debug("jax.monitoring unavailable; compile events not counted")


@dataclass
class ForwardPassMetrics:
    """Worker load metrics for routers/planners
    (reference: kv_router/protocols.rs:43-57)."""

    request_active_slots: int = 0
    request_total_slots: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0
    gpu_prefix_cache_hit_rate: float = 0.0
    # SLO/goodput signals (telemetry/slo.py): rolling attainment of the
    # configured TTFT/ITL targets and cumulative goodput tokens — the
    # Planner scales on *goodput*, not raw load, when targets are set.
    # slo_enabled lets aggregators average attainment over only the
    # workers that actually evaluate targets (a target-less worker's
    # constant 1.0 would dilute the fleet signal).
    slo_enabled: bool = False
    slo_attainment: float = 1.0
    goodput_tokens_total: int = 0

    def to_dict(self) -> dict:
        return self.__dict__.copy()



def _lag_add(lag: dict, entry: dict) -> None:
    """Charge an in-flight entry to a pipeline's lag ledger: ``vmap``
    maps id(seq) -> tokens the entry will add, sampled on device but
    not yet applied to host state (both pipelined step loops share
    this invariant — scheduler.plan_pipelined_* read the same map)."""
    for sid, v in entry["vmap"].items():
        lag[sid] = lag.get(sid, 0) + v


def _lag_sub(lag: dict, entry: dict) -> None:
    """Release a harvested entry's charges from the lag ledger."""
    for sid, v in entry["vmap"].items():
        left = lag.get(sid, 0) - v
        if left > 0:
            lag[sid] = left
        else:
            lag.pop(sid, None)


class JaxEngine:
    def __init__(self, config: EngineConfig):
        self.config = config
        self.model_config: Optional[ModelConfig] = None
        self.mesh = None
        self.params = None
        self.k_cache = None
        self.v_cache = None
        self.allocator: Optional[BlockAllocator] = None
        self.scheduler: Optional[Scheduler] = None
        self.kvbm: Optional[KvBlockManager] = None
        self.eos_token_ids: list[int] = []
        self._step_fn: Optional[Callable] = None
        self._step_fn_mm: Optional[Callable] = None
        self._multi_step_fn: Optional[Callable] = None
        self._mixed_step_fn: Optional[Callable] = None
        self._chain_next_fn: Optional[Callable] = None
        self._chain_join_fn: Optional[Callable] = None
        self._pack_pair_fn: Optional[Callable] = None
        # wide mixed rectangle (rows, len), set when enabled (see
        # _initialize; scheduler._mixed_rect picks per population)
        self._wide_rect: Optional[tuple[int, int]] = None
        # blocks the busy-path offload pump may move per serving step
        # (derived from the probed copy bandwidth in _gate_kv_offload;
        # 0 = transfers wait for idle moments; None = pump's own
        # default batch — the multihost sharded tier, which has no
        # local probe)
        self._kv_busy_pump_cap: Optional[int] = 0
        self._pp = config.pipeline_parallel_size
        # multi-host: rank 0 leads (scheduler + broadcast), others follow
        self._is_follower = config.num_nodes > 1 and config.node_rank > 0
        self._mh_broadcast = None  # StepBroadcaster on the leader
        self._thread: Optional[threading.Thread] = None
        self._incoming: thread_queue.Queue = thread_queue.Queue()
        self._control: thread_queue.Queue = thread_queue.Queue()
        self._wake = threading.Event()
        self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
        # graceful drain (runtime/drain.py; docs/robustness.md): once
        # set, submit() rejects new work and the step loop hands off
        # every eligible in-flight stream with FinishReason.MIGRATE
        self._draining = False  # dynalint: handoff=drain-flag — one-way bool, only ever flipped True; engine thread polls per step
        self._drain_migrated = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._seed_counter = 0
        # step-failure quarantine (see _quarantine_step_failure)
        self._last_plan: Optional[StepPlan] = None
        self._step_failures = 0
        # speculative decoding (dynamo_tpu/spec; config.spec_decode)
        self._drafter = None
        self._spec_step_fn: Optional[Callable] = None
        self._chain_spec_fn: Optional[Callable] = None
        # guided decoding (dynamo_tpu/guided; docs/guided_decoding.md):
        # the served tokenizer, loaded lazily on the first guided
        # request (submit thread — compiles never stall the step loop)
        # or eagerly when config.prewarm_guided
        self._guided_tokenizer = None
        # runtime suspend (degradation ladder rung 2, planner/
        # degradation.py): flipped from the asyncio thread, read by the
        # engine thread each step — a plain bool attr is race-free here
        self.spec_suspended = False
        self.spec_proposed_total = 0  # introspection counters
        self.spec_accepted_total = 0
        # overlapped spec pipeline accounting (docs/speculative_decoding.md):
        # wall seconds of host drafting hidden under device execution
        # (optimistic pre-drafts) vs exposed on the dispatch critical
        # path (first-step drafts + harvest-time repairs), and how often
        # the pre-draft's predicted tail matched the realized one.
        # Engine-thread writes; /debug/state reads advisorily.
        self.spec_draft_hidden_s_total = 0.0
        self.spec_draft_exposed_s_total = 0.0
        self.spec_predraft_hits = 0
        self.spec_predraft_misses = 0
        self.spec_pipeline_steps = 0
        # per-engine token counter (the registry counter is process-
        # global): /debug/state exposes it so `top` can derive tok/s
        # from deltas regardless of SLO configuration
        self.tokens_generated_total = 0
        # filled by _initialize: device, resolved kernel impls, timings
        self.device_report: dict = {"prewarm_s": 0.0}
        # recent sync=False dispatches whose device errors would DEFER
        # to a later synced step (_annotate_deferred_error)
        self._unsynced_steps: list[str] = []
        # device programs dispatched, by kind (program_counts)
        self._steps_dispatched: dict[str, int] = {}
        # models with recurrent state: state slots held, summed over the
        # programs dispatched, and slots there were (program_counts)
        self._state_slot_steps = [0, 0]
        # models with a released window plane: its pages in use, and the
        # rows admitted, each summed over the programs dispatched
        # (program_counts: their ratio is the window pages a row)
        self._window_page_steps = [0, 0]
        # single-step decode dispatches of _decode_pipeline, and those
        # of them issued with a step still in flight (program_counts)
        self._decode_dispatches = [0, 0]
        # decode dispatches (the pipeline's and the serial loop's): the
        # rows of the buckets they ran, and those of them that were
        # padding (program_counts: what a kernel that skips padded rows
        # is spared)
        self._decode_rows = [0, 0]
        # what that pipeline did without emptying itself, and why it
        # emptied itself when it did (program_counts): prefill dispatches
        # issued with a step in flight, finishes no step in flight held a
        # row of, and drains by reason
        self._inline = {"prefill_dispatches_inline": 0, "finishes_inline": 0}
        self._pipeline_drains = dict.fromkeys(self.DRAIN_REASONS, 0)
        # prefill dispatches: the prompt tokens their chunks held, and
        # rows x tokens of the rectangles they ran (program_counts)
        self._prefill_tokens = [0, 0]
        # the family's device-side counts (its module's COUNT_NAMES,
        # models/__init__.py): totals as Python ints, and the device's last
        # int32 reading (the device wraps, the totals do not)
        self._family_counts: dict[str, int] = {}
        self._family_counts_seen: Optional[np.ndarray] = None
        # observability (docs/observability.md): step flight recorder
        # with slow-step watchdog, SLO/goodput tracker, HBM accountant
        slow_ms = config.slow_step_ms
        if slow_ms is None:
            try:
                env = os.environ.get("DYN_SLOW_STEP_MS")
                slow_ms = float(env) if env else None
            except ValueError:
                log.warning("ignoring malformed DYN_SLOW_STEP_MS")
                slow_ms = None
        self.recorder: Optional[FlightRecorder] = (
            FlightRecorder(
                capacity=config.flight_recorder_steps,
                slow_step_s=slow_ms / 1e3 if slow_ms else None,
                dump_dir=config.flight_dump_dir,
                # a device idle gap as long as a slow step is the same
                # anomaly spent on the host side of the pipeline
                idle_gap_slow_s=slow_ms / 1e3 if slow_ms else None,
            )
            if config.flight_recorder_steps > 0
            else None
        )
        # overlapped decode pipeline (docs/performance.md): device
        # idle-gap accounting feeding the flight recorder's
        # idle_gap_ms stamps and /debug/state "overlap". Engine-thread
        # only.
        self.overlap = OverlapTracker()
        self.slo = SloTracker(
            SloConfig(ttft_ms=config.slo_ttft_ms, itl_ms=config.slo_itl_ms)
        )
        self.hbm = HbmAccountant()
        # anomaly-triggered black-box capture: slow-step/idle-gap
        # watchdog trips bundle the flight recorder ring + /debug/state
        # into one timestamped dump dir (rate-limited)
        self.blackbox = BlackBox(
            recorder=self.recorder,
            dump_dir=config.flight_dump_dir,
        )
        # per-dispatch phase timings (_run_device_step fills; the step
        # recorder reads) — a plain dict, engine-thread only
        self._last_phases: dict[str, float] = {}
        # the step loop's one clock (telemetry/spans.py): every
        # dyn.step.* phase, the loop's wall, periods and dispatches by
        # kind; bound to the engine thread at the top of _step_loop
        self.step_clock = StepClock()
        # the newest dispatch's token output, asked is_ready() just
        # before the next dispatch: had the device's queue run dry?
        self._newest_out: Any = None
        self._debug_name: Optional[str] = None
        try:
            self.PIPELINE_DEPTH = max(
                1, int(os.environ.get("DYN_PIPELINE_DEPTH", "2"))
            )
        except ValueError:
            log.warning("ignoring malformed DYN_PIPELINE_DEPTH; using 2")
            self.PIPELINE_DEPTH = 2
        self.kv_event_sink: Optional[Callable[[str, list[int], list[int]], None]] = None

    # ------------------------------------------------------------------
    # Launch
    # ------------------------------------------------------------------
    @classmethod
    async def launch(
        cls, config: EngineConfig, model_config: Optional[ModelConfig] = None,
        remote_kv_objects=None,
    ) -> "JaxEngine":
        """``model_config`` injection skips reading config.json from
        model_path (synthetic model shapes).
        ``remote_kv_objects``: a kvbm SyncObjectStore backing the G4
        remote tier when config.remote_kv_bucket is set."""
        engine = cls(config)
        engine.model_config = model_config
        engine._remote_kv_objects = remote_kv_objects
        loop = asyncio.get_running_loop()
        engine._loop = loop
        await loop.run_in_executor(None, engine._initialize)
        engine._running = True
        # affinity sanitizer (docs/static_analysis.md, DYN_AFFINITY_CHECK=1):
        # this thread IS the event loop; the step loop registers "engine"
        # at its own start. spec_suspended is engine-affine — the loop-side
        # writer (planner degradation rung) declares its handoff.
        affinity.register_thread("loop")
        # ... and the thread whose CPU the count history sets beside the
        # engine thread's (the two share one interpreter lock)
        note_loop_thread()
        affinity.guard_attrs(engine, {"spec_suspended": "engine"})
        engine._thread = threading.Thread(
            target=engine._step_loop, name="jax-engine", daemon=True
        )
        engine._thread.start()
        # live introspection: /debug/state serves this snapshot (latest
        # engine wins the bare "engine" name; shutdown unregisters only
        # its own registration)
        engine._debug_name = "engine"
        register_debug_provider(engine._debug_name, engine.debug_state)
        register_count_provider(engine._debug_name, engine.program_counts)
        if faults.ACTIVE is not None and engine.recorder is not None:
            # fired faults land in the flight recorder's ring so an
            # anomaly dump shows the injected chaos next to the steps
            # it perturbed
            recorder = engine.recorder
            faults.ACTIVE.add_listener(
                lambda rec: recorder.record(
                    "fault", 0.0,
                    point=rec.get("point"), fault_kind=rec.get("kind"),
                )
            )
        return engine

    def _initialize(self) -> None:
        global _initializing_engines
        _register_compile_listener()
        _initializing_engines += 1
        try:
            # the prewarm window registers both fences' allowed phase:
            # everything compiled (and every host<->device upload) in
            # here is sanctioned AOT warming; anything after is a
            # mid-serve compile/transfer the fences escalate. arm()
            # flips JAX's transfer guard to "disallow" first so the
            # serve phase inherits the armed guard.
            transfer_fence.arm()
            t0 = time.monotonic()
            with compile_fence.allow(), transfer_fence.allow():
                self._initialize_inner()
            self.device_report["init_s"] = round(time.monotonic() - t0, 3)
            self.device_report["compile_cache_events"] = dict(
                COMPILE_CACHE_EVENTS
            )
        finally:
            _initializing_engines -= 1

    def _initialize_inner(self) -> None:
        from dynamo_tpu.utils.jaxtools import enable_compile_cache

        cfg = self.config
        if cfg.spec_decode:
            # speculative decoding composes with neither fused windows
            # (both are multi-token-per-dispatch techniques competing
            # for the same step contract) nor the pp/multihost step
            # protocols (the verify step is a new jit signature the
            # follower/stage machinery doesn't mirror) — fail LOUDLY at
            # config time rather than silently serving without it
            if cfg.decode_steps > 1:
                raise ValueError(
                    "spec_decode requires decode_steps == 1 (fused "
                    "decode windows and speculation do not compose)"
                )
            if self._pp > 1:
                raise ValueError(
                    "spec_decode is not supported with "
                    "pipeline_parallel_size > 1"
                )
            if cfg.num_nodes > 1:
                raise ValueError(
                    "spec_decode is not supported with num_nodes > 1"
                )
            if cfg.spec_tokens < 1:
                raise ValueError(
                    f"spec_decode needs spec_tokens >= 1 (got "
                    f"{cfg.spec_tokens}); 0 would silently serve "
                    "without speculation while compiling a useless "
                    "verify shape"
                )
            from dynamo_tpu.spec import build_drafter

            self._drafter = build_drafter(cfg.spec_decode)
        if cfg.prewarm_guided and cfg.decode_steps > 1:
            # guided requests themselves are rejected per-request at
            # submit() on fused-window engines; a config asking to
            # prewarm their variants there is a deployment mistake
            raise ValueError(
                "prewarm_guided requires decode_steps == 1 (guided "
                "masks advance on host per committed token; fused "
                "windows sample K tokens per dispatch)"
            )
        if cfg.num_nodes > 1:
            # multi-host bring-up (reference: MultiNodeConfig, engines.rs:41)
            jax.distributed.initialize(
                coordinator_address=cfg.leader_addr,
                num_processes=cfg.num_nodes,
                process_id=cfg.node_rank,
            )
        # after distributed init: probing the backend before it would
        # break jax.distributed.initialize (must precede any XLA call)
        enable_compile_cache()  # restarts read compiled step variants back
        if cfg.block_size is None:
            # 128-token pages on TPU (MXU-width flash dots, +20%
            # measured decode), 16 elsewhere — see EngineConfig
            cfg.block_size = cfg.resolve_block_size()
        mesh_cfg = MeshConfig(
            dp=cfg.data_parallel_size,
            pp=cfg.pipeline_parallel_size,
            tp=cfg.tensor_parallel_size,
            ep=cfg.expert_parallel_size,
        )
        devices = jax.devices()[: mesh_cfg.size]
        from dynamo_tpu.utils.jaxtools import warn_if_cpu_fallback

        # an accelerator with no published peaks fails HERE, before any
        # weight loads
        device_peaks(devices[0])
        warn_if_cpu_fallback(log, f"engine {cfg.model_name!r}")
        self.mesh = build_mesh(mesh_cfg, devices)
        from dynamo_tpu.models.llama import set_attention_mesh

        if self._pp == 1:
            # enable the Pallas decode kernel on multi-device tp meshes
            # (shard_map over "tp"; see models/llama.py attend_mlp).
            # pp engines keep the gather path: "tp" is a GSPMD auto axis
            # inside the pp stage rotation.
            set_attention_mesh(self.mesh)
        else:
            # a stale mesh left by an earlier engine in this process
            # would poison the pp trace with a manual-tp shard_map
            set_attention_mesh(None)
        if cfg.num_nodes > 1 and cfg.node_rank == 0:
            from dynamo_tpu.parallel.multihost import StepBroadcaster

            self._mh_broadcast = StepBroadcaster()

        specs_fn = None
        cache_spec = None
        if self._pp > 1:
            # stage-sharded layer stacks + cache (parallel/pipeline.py);
            # resolve_model calls specs_fn once the config is known, so
            # the pp/layer-count compatibility check runs BEFORE any
            # expensive weight load
            from dynamo_tpu.parallel.pipeline import PP_CACHE_SPEC, pp_param_specs

            pp = self._pp

            def specs_fn(mc: ModelConfig) -> dict:
                if mc.num_hidden_layers % pp != 0:
                    raise ValueError(
                        f"pipeline_parallel_size={pp} must divide "
                        f"num_hidden_layers={mc.num_hidden_layers}"
                    )
                return pp_param_specs(mc)

            cache_spec = PP_CACHE_SPEC

        pp_specs_fn = specs_fn

        def specs_fn(mc: ModelConfig):  # noqa: F811 — runs before any weight loads
            # a family refuses here what it does not build (e.g. tp > 1
            # or speculation for a model with recurrent state)
            check = getattr(model_family(mc), "check_engine", None)
            if check is not None:
                check(cfg)
            return pp_specs_fn(mc) if pp_specs_fn is not None else None

        from dynamo_tpu.models import loader

        self.model_config, self.params = loader.resolve_model(
            cfg.model_path,
            model_config=self.model_config,
            random_weights=cfg.random_weights,
            seed=cfg.seed,
            mesh=self.mesh,
            specs_fn=specs_fn,
            quantize=cfg.quantization,
        )
        self.eos_token_ids = self.model_config.eos_token_ids

        if jnp.dtype(cfg.kv_cache_dtype) == jnp.int8:
            # int8 KV limits (ops/kv_quant.py documents the layout):
            # the in-kernel scale-tile reshape needs lane-multiple pages
            # on real TPUs, and the pp cache layout has no scale plane
            if self._pp > 1:
                raise ValueError(
                    "kv_cache_dtype=int8 is not supported with "
                    "pipeline_parallel_size > 1 (use bfloat16 or fp8)"
                )
            if (
                jax.default_backend() == "tpu"
                and cfg.block_size % 128 != 0
            ):
                raise ValueError(
                    f"kv_cache_dtype=int8 on TPU requires block_size to "
                    f"be a multiple of 128 (got {cfg.block_size}); the "
                    f"scale-tile reshape is lane-preserving only then"
                )
        num_blocks = cfg.num_blocks or self._auto_num_blocks(devices)
        if cfg.num_nodes > 1:
            # every process must build identically-shaped caches; only
            # the leader's HBM probe is authoritative
            from jax.experimental import multihost_utils

            num_blocks = int(
                multihost_utils.broadcast_one_to_all(np.int32(num_blocks))
            )
        stateful = self.model_config.has_recurrent_state
        cache_kw = {"state_slots": self._state_slot_count} if stateful else {}
        window = self.model_config.released_window
        if window:
            cache_kw["window_blocks"] = self._window_plane_blocks
            # each plane's bytes as the family counts its pages (what
            # _auto_num_blocks sized the pool with), for /debug/state
            fam = model_family(self.model_config)
            itemsize = jnp.dtype(cfg.kv_cache_dtype).itemsize
            planes = (("full", num_blocks),
                      ("window", self._window_plane_blocks))
            self._page_plane_bytes = {
                plane: blocks * fam.page_bytes_per_block(
                    self.model_config, cfg.block_size, itemsize, plane=plane)
                for plane, blocks in planes
            }
        self.k_cache, self.v_cache = model_family(self.model_config).init_cache(
            self.model_config,
            num_blocks,
            cfg.block_size,
            self.mesh,
            dtype=jnp.dtype(cfg.kv_cache_dtype),
            spec=cache_spec,
            **cache_kw,
        )
        self.allocator = BlockAllocator(
            num_blocks,
            cfg.block_size,
            # a cached page prefix is worth nothing without the recurrent
            # state at its end, and no state is snapshotted at page
            # boundaries: every admission is a counted miss, recomputed.
            # A family that owns its pages and keeps NO such state
            # (latent rows alone) is served from them like any other
            # nor is one worth anything without the released plane's last
            # pages (a family whose window layers free behind the window)
            enable_prefix_caching=cfg.enable_prefix_caching
            and not stateful and not window,
            on_event=self._on_kv_event,
        )
        self.scheduler = Scheduler(
            self.allocator,
            cfg.block_size,
            max_batch_size=cfg.max_batch_size,
            prefill_chunk_size=cfg.prefill_chunk_size,
            max_model_len=cfg.max_model_len
            or self.model_config.max_position_embeddings,
            max_prefill_tokens=cfg.max_prefill_tokens,
        )
        self.scheduler.decode_lookahead = max(1, cfg.decode_steps)
        self.scheduler.dispatches_ahead = self.PIPELINE_DEPTH + 1
        if self._drafter is not None:
            self.scheduler.spec_tokens = cfg.spec_tokens
        if stateful:
            self.scheduler.state_slots = StateSlots(self._state_slot_count)
        if window:
            self.scheduler.window_plane = WindowPlane(
                self._window_plane_blocks, cfg.block_size, window
            )
        if cfg.static_shapes:
            # one compiled decode/mixed shape: pad the decode batch to
            # max_batch_size and the table width to the max_model_len
            # cap (+ window growth margin). Composition-dependent
            # buckets would otherwise compile MID-SERVE (a TTFT stall
            # of one whole step compile per variant). Coarse prefill
            # buckets bound that path too.
            sched = self.scheduler
            sched.decode_batch_pad = next_bucket(
                cfg.max_batch_size, Scheduler.BATCH_BUCKETS
            )
            if sched.decode_batch_pad > 4:
                # low-concurrency bucket: a lone stream decodes in a
                # [4,1]-padded window (~10% lighter than the full pad)
                # for a handful of extra prewarmed variants
                sched.decode_batch_small = 4
            if cfg.decode_batch_mid is not None:
                # explicit override: the LARGEST bucket <= the request
                # strictly between the small bucket and the pad (a mid
                # bucket at/above the pad is a no-op, at/below small is
                # dead code that still costs AOT prewarms). 0 = no mid
                # bucket, explicitly (None = auto).
                lo = sched.decode_batch_small or 0
                fits = [
                    b for b in Scheduler.BATCH_BUCKETS
                    if lo < b < sched.decode_batch_pad
                    and b <= cfg.decode_batch_mid
                ]
                if cfg.decode_batch_mid > 0 and fits:
                    sched.decode_batch_mid = fits[-1]
                elif cfg.decode_batch_mid > 0:
                    log.warning(
                        "decode_batch_mid=%d has no bucket strictly "
                        "between the small bucket (%d) and the pad "
                        "(%d); ignoring the override",
                        cfg.decode_batch_mid, lo, sched.decode_batch_pad,
                    )
            elif sched.decode_batch_pad >= 64:
                # mid bucket: a half-occupancy population on a wide-pad
                # engine decodes in [pad/2]-windows (measured ~11% at
                # c=32 on a max_batch=64 engine) for one more set of
                # prewarmed variants
                sched.decode_batch_mid = sched.decode_batch_pad // 2
            eff_len = (
                cfg.max_model_len or self.model_config.max_position_embeddings
            )
            # capped by the cache itself: a sequence can never hold more
            # blocks than exist, and an uncapped long-context
            # max_position_embeddings would give every decode step a
            # thousands-wide dead block table (grid overhead per page)
            blocks_cap = min(
                -(-(eff_len + max(1, cfg.decode_steps))
                  // cfg.block_size) + 1,
                num_blocks,
            )
            sched.table_width_pad = max(
                Scheduler.TABLE_BUCKET,
                -(-blocks_cap // Scheduler.TABLE_BUCKET)
                * Scheduler.TABLE_BUCKET,
            )
            # prefill shapes: every (rows, tokens) rectangle is a
            # whole-model program compiled at start-up, so the set is
            # few (scheduler.prefill_rectangles says which; STATIC_* why),
            # and it is THE set: the planner, the array builder and
            # every prewarm loop read sched.prefill_rects and nothing
            # else. Row counts: a single row, so that a lone prompt on
            # an idle engine does not pay 8x padded compute (prefill is
            # compute-bound, unlike decode); the mixed rectangle's rows;
            # the budget-filling width (max_prefill_tokens / shortest
            # chunk) — without it a burst wider than the mixed rows
            # prefills in rows-sized waves that desynchronise decode for
            # the population's lifetime (measured at B=64: windows 16-40
            # wide at full-window cost, 924 vs 1505 tok/s); and the pad.
            # Defaults (budget 4096, chunk 1024, 8 mixed rows, pad 64):
            # 1x128 1x256 1x512 1x1024 8x128 8x256 32x128.
            pad = sched.decode_batch_pad
            budget_rows = (
                (cfg.max_prefill_tokens or 4096)
                // Scheduler.STATIC_CHUNK_TOKENS[0]
            )
            sched.prefill_rects = prefill_rectangles(
                sorted(
                    {1, pad}
                    | {max(1, min(r, pad))
                       for r in (cfg.mixed_prefill_rows, budget_rows)}
                ),
                Scheduler.STATIC_CHUNK_TOKENS,
                sched.max_prefill_tokens,
                cfg.prefill_chunk_size,
                Scheduler.STATIC_SINGLE_ROW_TOKENS,
            )
        if cfg.decode_steps > 1 and cfg.mixed_prefill_rows > 0:
            # the mixed window's FIXED rectangle is one of the set's
            # (_pad_prefill_rect pads the builder's arrays out to it; a
            # shape outside the set would crash every mixed step and
            # fail all in-flight requests): the row count that holds
            # the request, a length that exists AT that row count, then
            # down until it fits the prefill token budget the HBM
            # headroom sizing reserves for (see _auto_num_blocks area)
            sched = self.scheduler
            lens = sorted({t for _, t in sched.prefill_rects})
            cap = max(lens[0], sched.max_prefill_tokens)
            fit = mixed_rect_of(
                sched.prefill_rects, cfg.mixed_prefill_rows,
                cfg.mixed_prefill_len, cap,
            )
            if fit is None:
                # the smallest rectangle still exceeds the configured
                # prefill budget: running it anyway would silently
                # violate the HBM headroom that budget reserves
                log.warning(
                    "no mixed prefill rectangle for %dx%d fits "
                    "max_prefill_tokens=%d; disabling mixed batching",
                    cfg.mixed_prefill_rows, cfg.mixed_prefill_len, cap,
                )
                cfg.mixed_prefill_rows = 0
            else:
                cfg.mixed_prefill_rows, cfg.mixed_prefill_len = fit
            sched.mixed_prefill_rows = cfg.mixed_prefill_rows
            sched.mixed_prefill_len = cfg.mixed_prefill_len
            # adaptive WIDE rectangle: same token budget, fewer rows —
            # long prompts at low decode occupancy prefill in
            # backlog/wide_len windows instead of backlog/len
            # (config.mixed_prefill_wide_len; scheduler._mixed_rect)
            wide = getattr(cfg, "mixed_prefill_wide_len", 0)
            if cfg.mixed_prefill_rows > 0 and wide > cfg.mixed_prefill_len:
                # never wider than one prefill chunk: _plan_prefill_batch
                # caps every row's chunk at prefill_chunk_size, so a
                # longer rectangle would dispatch permanently-padded
                # dead tokens — the longest of the set's lengths that
                # neither the request, the chunk nor the cap falls short
                # of. The wide rect keeps the narrow rect's token budget
                # (rows*len): if no longer length leaves it a row, the
                # budget is too small for a wide variant and it stays
                # disabled
                budget = cfg.mixed_prefill_rows * cfg.mixed_prefill_len
                top = min(
                    next_bucket(min(wide, cfg.prefill_chunk_size), lens),
                    cfg.prefill_chunk_size, cap, budget,
                )
                wl = max([t for t in lens if t <= top], default=0)
                if wl > cfg.mixed_prefill_len:
                    wr = min(budget // wl, cap // wl)
                    if (wr, wl) not in sched.prefill_rects:
                        # the rectangle must be one of the set, or the
                        # builder finds none inside it and every wide
                        # mixed step crashes
                        sched.prefill_rects = sorted(
                            sched.prefill_rects + [(wr, wl)]
                        )
                    sched.mixed_prefill_wide_rows = wr
                    sched.mixed_prefill_wide_len = wl
                    sched.mixed_wide_max_running = getattr(
                        cfg, "mixed_wide_max_running", None
                    )
                    self._wide_rect = (wr, wl)
        self.scheduler.on_finish = self._emit_finish
        if cfg.disk_kv_blocks > 0 and cfg.host_kv_blocks <= 0:
            raise ValueError(
                "disk_kv_blocks requires host_kv_blocks > 0 (G3 demotion "
                "cascades from the G2 host tier)"
            )
        if cfg.remote_kv_bucket and cfg.host_kv_blocks <= 0:
            raise ValueError(
                "remote_kv_bucket requires host_kv_blocks > 0 (the G4 "
                "remote tier demotes from / onboards through the G2 host "
                "tier) — a configured remote tier must not vanish silently"
            )
        if cfg.host_kv_blocks > 0 and cfg.num_nodes > 1:
            # Sharded KV offload (docs/multihost.md): each process
            # offloads only its LOCAL shard via mirrored gather/scatter
            # broadcasts — G2 host tier only; disk/remote demotion and
            # disagg export stay single-host features.
            if cfg.disk_kv_blocks > 0 or cfg.remote_kv_bucket:
                log.warning(
                    "disk/remote KV tiers unsupported with num_nodes>1; "
                    "serving with the sharded host tier only"
                )
            if cfg.node_rank == 0:
                from dynamo_tpu.parallel.multihost import ShardedKvOffload

                assert self._mh_broadcast is not None
                self.kvbm = ShardedKvOffload(
                    self, self._mh_broadcast,
                    host_num_blocks=cfg.host_kv_blocks,
                    offload_batch=cfg.kv_offload_batch,
                )
                self.scheduler.onboard = self._safe_onboard
            # followers build their shard pool inside StepFollower.run
        elif cfg.host_kv_blocks > 0:
            self.kvbm = KvBlockManager(
                KvbmConfig(
                    host_num_blocks=cfg.host_kv_blocks,
                    disk_num_blocks=cfg.disk_kv_blocks,
                    disk_path=cfg.disk_kv_path
                    or f"/tmp/dynamo_tpu_kv_{os.getpid()}_{uuid.uuid4().hex[:8]}.bin",
                    offload_batch=cfg.kv_offload_batch,
                    remote_bucket=cfg.remote_kv_bucket,
                ),
                BlockLayout.for_model(
                    self.model_config, cfg.block_size, cfg.wire_kv_dtype()
                ),
                gather_fn=self._kv_gather,
                scatter_fn=self._kv_scatter,
                resolve_fn=self.allocator.lookup_block,
                remote_objects=getattr(self, "_remote_kv_objects", None),
            )
            self.scheduler.onboard = self._safe_onboard
        prewarm = cfg.prewarm
        if prewarm is None:
            prewarm = jax.default_backend() == "tpu"
        # without a prewarm nothing compiles before the first request:
        # have the qmatmul tilings verified by the compiler now instead
        if not prewarm:
            self._verify_qmatmul_compiles()
        self._build_step_fn()
        self._gate_kv_offload()
        if prewarm:
            self._prewarm()
        # HBM accounting: long-lived allocations once, live stats on
        # refresh (per-step sampled + every /debug/state snapshot)
        self.hbm.set_device(devices[0] if len(devices) else None)
        self._plane_bytes = (
            tree_bytes(self.k_cache), tree_bytes(self.v_cache)
        )
        self.hbm.set_static(
            tree_bytes(self.params), sum(self._plane_bytes)
        )
        self.hbm.refresh()
        from dynamo_tpu.models.llama import (
            attn_impl, matmul_impl, pallas_attention_active,
            pallas_matmul_active,
        )
        from dynamo_tpu.utils.jaxtools import (
            compile_cache_dir, describe_devices,
        )

        dev = describe_devices(devices)
        self.device_report.update({
            **dev,
            "attn_impl": attn_impl(),
            "attn_pallas_active": pallas_attention_active(),
            "matmul_impl": matmul_impl(),
            "matmul_pallas_active": (
                pallas_matmul_active() and cfg.quantization == "int8"
            ),
            "prewarm": bool(prewarm),
            "compile_cache_dir": compile_cache_dir(),
            # host chips this process is confined to (sdk/allocator.py);
            # device ids restart at 0 inside a confined process
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        })
        # device= is one JSON object (chip_smoke.py reads it from a
        # worker's log): platform, kind, device ids, the host chips the
        # process is confined to, and the kernel impls that resolved
        log.info(
            "engine up: %s, device=%s, mesh=%s, blocks=%d×%d",
            cfg.model_name, json.dumps(self.device_report),
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            num_blocks,
            cfg.block_size,
        )

    @property
    def _state_slot_count(self) -> int:
        """State-plane slots of a model with recurrent layers: one per
        sequence that can be admitted, and the garbage slot 0."""
        return self.config.max_batch_size + 1

    @property
    def _window_plane_blocks(self) -> int:
        """Pages of the window plane of a model whose window layers
        release behind the window (allocator.WindowPlane): what
        ``max_batch_size`` decoding rows hold at their bound — the
        window's pages and the look-ahead of the dispatches in flight —
        and beside it one prefill batch's tokens and the longest chunk's
        span, and the garbage block 0. No knob: from the batch size,
        the chunk size, the window and the page size alone; admission
        (Scheduler._window_admits) keeps every admitted row's bound
        free, so a smaller plane would admit fewer rows and never run
        out."""
        cfg = self.config
        span = functools.partial(
            WindowPlane.pages_spanned, self.model_config.released_window,
            cfg.block_size)
        ahead = 1 + (self.PIPELINE_DEPTH + 1) * max(1, cfg.decode_steps)
        return (
            1
            + cfg.max_batch_size * span(ahead)
            + -(-min(cfg.max_prefill_tokens or cfg.prefill_chunk_size,
                     cfg.max_batch_size * cfg.prefill_chunk_size)
                // cfg.block_size)
            + span(cfg.prefill_chunk_size)
        )

    def _verify_qmatmul_compiles(self) -> None:
        """Hand the chip's compiler every qmatmul shape the step
        functions can reach, with the tiling ``default_tiles`` gives it
        (nothing runs, nothing is allocated), so that a tiling the
        compiler refuses fails at start-up and not at the first
        request. Only called when no prewarm will compile the step
        functions themselves. The recurrent-state family is not
        covered: its unrolled layers call ``qmm`` per weight with a
        float32 result (models/kimi_linear.py ``_mm``), which this
        llama-family shape list does not describe; its kernels at
        published widths are compiled by tests/test_chip_compile.py.
        The same holds for every family that owns its pages."""
        from dynamo_tpu.models.llama import pallas_matmul_active

        if (
            jax.default_backend() != "tpu"
            or not pallas_matmul_active()
            or self.config.quantization != "int8"
            or self.model_config.owns_pages
        ):
            return
        mc, sched = self.model_config, self.scheduler
        assert mc is not None and sched is not None
        D, F, V = mc.hidden_size, mc.intermediate_size, mc.vocab_size
        H, Hk, Dh = (
            mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim,
        )
        decode_buckets = sorted(
            {b for b in (sched.decode_batch_small, sched.decode_batch_mid,
                         sched.decode_batch_pad) if b}
        ) or [1]
        ms = set(decode_buckets) | {b * t for b, t in sched.prefill_rects}
        if self.config.spec_decode:
            for b in decode_buckets:
                ms.add(b * (self.config.spec_tokens + 1))
        shapes: list[tuple[int, int, int, str]] = []
        for m in sorted(ms):
            shapes += [
                (m, D, H * Dh, "mm"),          # wq
                (m, D, Hk * Dh, "mm"),         # wk / wv
                (m, H * Dh, D, "residual"),    # wo + residual epilogue
                (m, F, D, "residual"),         # w_down + residual epilogue
                (m, D, F, "gate_up"),          # fused gate/up
            ]
        # lm_head reads [B, D] (last-token gather) on every non-spec
        # path; the spec verify path feeds the full [B, S] rectangle
        lm_ms = set(decode_buckets) | {b for b, _ in sched.prefill_rects}
        if self.config.spec_decode:
            lm_ms |= {b * (self.config.spec_tokens + 1) for b in decode_buckets}
        for m in sorted(lm_ms):
            shapes.append((m, D, V, "lm_head"))
        from dynamo_tpu.ops.qmatmul import verify_compiles

        for m, K, N, kind in shapes:
            verify_compiles(m, K, N, kind, layers=mc.num_hidden_layers)

    def _prewarm(self) -> None:
        """Compile every serving-path shape variant NOW, before the
        engine accepts traffic. With static_shapes the reachable set is
        small and fixed: the fused decode window, the mixed window, and
        the dedicated-prefill rectangles. A lazy compile would land
        mid-serve as a TTFT stall of its whole compile time (the
        compile fence counts them). All dummy work writes to the reserved garbage
        slot 0 with ctx=0, so the KV cache is untouched semantically."""
        sched = self.scheduler
        assert sched is not None
        t0 = time.monotonic()
        width = sched.table_width_of(
            sched.table_width_pad or sched.TABLE_BUCKET
        )

        def sampling_for(
            n: int, penalties: bool = False, toplp: bool = False,
            bias: bool = False,
        ) -> SamplingBatch:
            opts = (
                SamplingOptions(
                    temperature=1.0, frequency_penalty=0.1,
                    presence_penalty=0.1, repetition_penalty=1.1,
                )
                if penalties
                else SamplingOptions(use_greedy=True)
            )
            if bias:
                opts = opts.model_copy(update={"logit_bias": {1: 0.0}})
            return SamplingBatch.from_options(
                [opts] * n, [0] * n,
                [{} for _ in range(n)] if penalties else None,
                [np.zeros((0,), np.int32)] * n if penalties else None,
                [1] * n if toplp else None,
            )

        # Opt-in sampling-feature variants beyond the base signature,
        # as (penalties, toplp, bias) tuples. prewarm_penalties warms
        # the penalty AND logit-bias single-feature variants (the two
        # features that divert to dedicated prefill + pure windows);
        # prewarm_logprobs warms top-logprobs; with both flags the
        # penalties+toplp combo is warmed too. Multi-feature combos
        # beyond that (e.g. bias+penalties in one batch) still compile
        # on first use — the cross product would explode startup time.
        feat_variants: list[tuple[bool, bool, bool]] = [
            (False, False, False)
        ]
        if self.config.prewarm_logprobs:
            feat_variants.append((False, True, False))
        if self.config.prewarm_penalties:
            feat_variants.append((True, False, False))
            feat_variants.append((False, False, True))
        if self.config.prewarm_logprobs and self.config.prewarm_penalties:
            feat_variants.append((True, True, False))

        def prefill_arrays(b: int, t: int) -> dict[str, np.ndarray]:
            return {
                "tokens": np.zeros((b, t), np.int32),
                "positions": np.zeros((b, t), np.int32),
                "slot_mapping": np.zeros((b * t,), np.int32),
                "block_tables": np.zeros((b, width), np.int32),
                "context_lens": np.zeros((b,), np.int32),
                "last_token_idx": np.zeros((b,), np.int32),
            }

        def decode_arrays(b: int) -> dict[str, np.ndarray]:
            return {
                "tokens": np.zeros((b, 1), np.int32),
                "positions": np.zeros((b, 1), np.int32),
                "slot_mapping": np.zeros((b,), np.int32),
                "block_tables": np.zeros((b, width), np.int32),
                "context_lens": np.zeros((b,), np.int32),
                "valid_steps": np.zeros((b,), np.int32),
                "last_token_idx": np.zeros((b,), np.int32),
            }

        # NOTE: direct jitted calls, NOT _run_device_step — prewarm runs
        # during _initialize on every rank in the same order, before the
        # followers' receive loop exists, so the step broadcast must not
        # fire here (the jit's own collectives line up because all ranks
        # prewarm the same shapes in the same sequence).
        p_outs: dict[int, tuple] = {}  # base-variant prefill outputs

        def warm_prefill(b: int, chunk: int) -> None:
            t_rect = time.monotonic()
            for pv, tv, bv in feat_variants:
                a = prefill_arrays(b, chunk)
                s = sampling_for(b, penalties=pv, toplp=tv, bias=bv)
                step_args = (
                    self.params, self.k_cache, self.v_cache,
                    a["tokens"], a["positions"],
                    a["slot_mapping"], a["block_tables"],
                    a["context_lens"], a["last_token_idx"],
                    s.arrays,
                )
                if "mosaic_calls_in_step" not in self.device_report:
                    # what the first step hands the chip's compiler: a
                    # Pallas kernel that runs interpreted lowers to
                    # plain HLO and is not counted. The call below
                    # reuses this trace and lowering (measured, PERF.md
                    # PR 39): only the text is made for the count.
                    self.device_report["mosaic_calls_in_step"] = (
                        self._step_fn.lower(*step_args)
                        .as_text().count("tpu_custom_call")
                    )
                out = self._step_fn(*step_args)
                self.k_cache, self.v_cache = out[-2], out[-1]
                if not (pv or tv or bv):
                    # retained for the overlap-glue warm below
                    p_outs[b] = out[:2]
            # set-up time by program (its trace, lowering and load: the
            # device runs it while the host traces the next one)
            log.info(
                "prewarm: prefill %dx%d in %.2fs",
                b, chunk, time.monotonic() - t_rect,
            )

        # the FIRST call sees init_cache's arrays, every later one a
        # step's own outputs, which XLA places its own way (its
        # canonical output sharding is not init_cache's spelling:
        # another jit signature). So the first rectangle is warmed once
        # more, against the steady state; every other shape has met it
        # already, and a whole second pass would find each program
        # compiled and only run every padded rectangle again.
        for b, chunk in sched.prefill_rects + sched.prefill_rects[:1]:
            warm_prefill(b, chunk)
        jax.block_until_ready(self.k_cache)
        t_decode = time.monotonic()
        decode_buckets = sorted(
            {b for b in (sched.decode_batch_small, sched.decode_batch_mid,
                         sched.decode_batch_pad)
             if b}
        ) or [next_bucket(1, sched.BATCH_BUCKETS)]
        B = decode_buckets[-1]
        if self._multi_step_fn is not None:
            # opt-in sampling-feature window variants (the base window
            # is warmed with chaining below)
            for Bd in decode_buckets:
                for pv, tv, bv in feat_variants[1:]:
                    a = decode_arrays(Bd)
                    packed, _, self.k_cache, self.v_cache = (
                        self._multi_step_fn(
                            self.params, self.k_cache, self.v_cache,
                            a["tokens"], a["positions"], a["block_tables"],
                            a["context_lens"], a["valid_steps"],
                            sampling_for(
                                Bd, penalties=pv, toplp=tv, bias=bv
                            ).arrays,
                        )
                    )
                    jax.block_until_ready(packed)
        if self._multi_step_fn is None:
            # single-step decode serving shapes (decode_steps == 1)
            for Bd in decode_buckets:
                for pv, tv, bv in feat_variants:
                    a = decode_arrays(Bd)
                    s = sampling_for(Bd, penalties=pv, toplp=tv, bias=bv)
                    out = self._step_fn(
                        self.params, self.k_cache, self.v_cache,
                        a["tokens"], a["positions"], a["slot_mapping"],
                        a["block_tables"], a["context_lens"],
                        a["last_token_idx"], s.arrays,
                    )
                    self.k_cache, self.v_cache = out[-2], out[-1]
                    jax.block_until_ready(self.k_cache)
        if self._multi_step_fn is None and self._overlap_ok():
            # overlapped decode pipeline variants (docs/performance.md)
            # — warmed on spec engines too: zero-proposal/suspended/
            # opted-out batches fall back to the plain decode paths, and
            # an unwarmed chained variant would be a mid-serve compile.
            # the chained dispatch feeds the previous step's DEVICE
            # token column — a committed device array is a different
            # jit signature than host numpy — plus the packed harvest
            # and the chain gathers, including bucket transitions for a
            # shrinking population. An unwarmed variant is a mid-serve
            # compile.
            toks_by_bucket: dict[int, Any] = {}
            for Bd in decode_buckets:
                a = decode_arrays(Bd)
                s = sampling_for(Bd)
                out = self._step_fn(
                    self.params, self.k_cache, self.v_cache,
                    a["tokens"], a["positions"], a["slot_mapping"],
                    a["block_tables"], a["context_lens"],
                    a["last_token_idx"], s.arrays,
                )
                self.k_cache, self.v_cache = out[-2], out[-1]
                col = self._chain_next_fn(out[0], np.zeros((Bd,), np.int32))
                out = self._step_fn(
                    self.params, self.k_cache, self.v_cache,
                    col, a["positions"], a["slot_mapping"],
                    a["block_tables"], a["context_lens"],
                    a["last_token_idx"], s.arrays,
                )
                self.k_cache, self.v_cache = out[-2], out[-1]
                jax.block_until_ready(self._pack_pair_fn(out[0], out[1]))
                toks_by_bucket[Bd] = out[0]
            for b_from, tok in toks_by_bucket.items():
                for b_to in decode_buckets:
                    if b_to != b_from:
                        self._chain_next_fn(tok, np.zeros((b_to,), np.int32))
            # the pipeline's in-line admission: a prefill batch's packed
            # harvest, and its sampled column joined with the host's
            # tokens into each decode bucket — two small programs a row
            # count of the rectangles and one a (row count, bucket) pair;
            # the steps themselves are the ones warmed above
            for b, (nt, lp) in p_outs.items():
                jax.block_until_ready(self._pack_pair_fn(nt, lp))
                for Bd in decode_buckets:
                    self._chain_join_fn(
                        nt, np.zeros((Bd, 1), np.int32),
                        np.zeros((Bd,), np.int32),
                    )
        if self._multi_step_fn is not None and self._overlap_ok():
            # cohort-graduation glue (the window pipeline's prefill-only
            # entry): packed prefill harvest + first-token chain from
            # each prefill batch bucket into each decode bucket (the
            # chained window itself shares the chain_pure-warmed
            # signature — ns_rep2-constrained device column)
            for b, (nt, lp) in p_outs.items():
                jax.block_until_ready(self._pack_pair_fn(nt, lp))
                for Bd in decode_buckets:
                    self._chain_next_fn(nt, np.zeros((Bd,), np.int32))
        if self._spec_step_fn is not None:
            # speculative verify shapes: one fixed [B, spec_tokens+1]
            # rectangle per decode bucket (greedy and sampled rows share
            # the one compiled variant — verify's sampling machinery is
            # a runtime lax.cond)
            Ssp = self.config.spec_tokens + 1

            def spec_arrays(b: int) -> dict[str, np.ndarray]:
                return {
                    "tokens": np.zeros((b, Ssp), np.int32),
                    "positions": np.zeros((b, Ssp), np.int32),
                    "slot_mapping": np.zeros((b * Ssp,), np.int32),
                    "block_tables": np.zeros((b, width), np.int32),
                    "context_lens": np.zeros((b,), np.int32),
                    "draft_lens": np.zeros((b,), np.int32),
                }

            spec_packed: dict[int, Any] = {}
            for Bd in decode_buckets:
                sa = spec_arrays(Bd)
                packed, self.k_cache, self.v_cache = self._spec_step_fn(
                    self.params, self.k_cache, self.v_cache,
                    sa["tokens"], sa["positions"], sa["slot_mapping"],
                    sa["block_tables"], sa["context_lens"],
                    sa["draft_lens"], sampling_for(Bd).arrays,
                )
                jax.block_until_ready(packed)
                spec_packed[Bd] = packed
            if self._overlap_ok() and self._chain_spec_fn is not None:
                # pipelined spec variants (docs/speculative_decoding.md):
                # the verify rectangle fed a DEVICE token column — the
                # carry chained from the previous step's packed output
                # is a committed device array, a different jit signature
                # than host numpy — plus the chain gathers themselves,
                # including bucket TRANSITIONS for a shrinking
                # population. An unwarmed variant is a mid-serve
                # compile, the same gap the decode pipeline's prewarm
                # closes for plain decode.
                for Bd in decode_buckets:
                    # transitions only SHRINK (the pipeline never
                    # admits; survivors are a subset of the previous
                    # rows), so growing b_from < Bd pairs are
                    # unreachable and not worth a compile
                    for b_from in decode_buckets:
                        if b_from < Bd:
                            continue
                        col = self._chain_spec_fn(
                            spec_packed[b_from],
                            np.zeros((Bd, Ssp), np.int32),
                            np.zeros((Bd,), np.int32),
                        )
                        if b_from != Bd:
                            continue
                        sa = spec_arrays(Bd)
                        packed, self.k_cache, self.v_cache = (
                            self._spec_step_fn(
                                self.params, self.k_cache, self.v_cache,
                                col, sa["positions"], sa["slot_mapping"],
                                sa["block_tables"], sa["context_lens"],
                                sa["draft_lens"], sampling_for(Bd).arrays,
                            )
                        )
                        jax.block_until_ready(packed)
                        spec_packed[Bd] = packed
        lasts: dict[int, Any] = {}
        p_nexts: dict[int, Any] = {}
        if self._multi_step_fn is not None:
            for Bd in decode_buckets:
                a, s = decode_arrays(Bd), sampling_for(Bd)
                packed, last_tok, self.k_cache, self.v_cache = (
                    self._multi_step_fn(
                        self.params, self.k_cache, self.v_cache, a["tokens"],
                        a["positions"], a["block_tables"],
                        a["context_lens"], a["valid_steps"], s.arrays,
                    )
                )
                # the pipelined path feeds the previous window's DEVICE
                # token column — a committed device array is a different
                # jit signature than host numpy, so warm that variant
                # too (an unwarmed variant is a mid-serve compile)
                if self._chain_pure_fn is not None:
                    last_tok = self._chain_pure_fn(
                        last_tok, np.zeros((Bd,), np.int32)
                    )
                packed, last_tok, self.k_cache, self.v_cache = (
                    self._multi_step_fn(
                        self.params, self.k_cache, self.v_cache, last_tok,
                        a["positions"], a["block_tables"],
                        a["context_lens"], a["valid_steps"], s.arrays,
                    )
                )
                jax.block_until_ready(packed)
                lasts[Bd] = last_tok
        if (
            self._mixed_step_fn is not None
            and sched.mixed_prefill_rows > 0
        ):
            rects = [
                (self.config.mixed_prefill_rows, self.config.mixed_prefill_len)
            ]
            if self._wide_rect is not None:
                rects.append(self._wide_rect)
            for P, T in rects:
                p = prefill_arrays(P, T)
                sp = sampling_for(P)
                for Bd in decode_buckets:
                    d = decode_arrays(Bd)
                    sd = sampling_for(Bd)
                    flat, m_last, p_next, self.k_cache, self.v_cache = (
                        self._mixed_step_fn(
                            self.params, self.k_cache, self.v_cache,
                            p["tokens"], p["positions"], p["slot_mapping"],
                            p["block_tables"], p["context_lens"],
                            p["last_token_idx"], sp.arrays,
                            d["tokens"], d["positions"], d["block_tables"],
                            d["context_lens"], d["valid_steps"], sd.arrays,
                        )
                    )
                    assert self._chain_fn is not None
                    chained = self._chain_fn(
                        m_last, p_next, np.zeros((Bd,), np.int32)
                    )
                    # chained-token mixed variant (pipelined mixed windows)
                    flat, m_last, p_next, self.k_cache, self.v_cache = (
                        self._mixed_step_fn(
                            self.params, self.k_cache, self.v_cache,
                            p["tokens"], p["positions"], p["slot_mapping"],
                            p["block_tables"], p["context_lens"],
                            p["last_token_idx"], sp.arrays,
                            chained, d["positions"], d["block_tables"],
                            d["context_lens"], d["valid_steps"], sd.arrays,
                        )
                    )
                    jax.block_until_ready(flat)
                    lasts[Bd] = m_last
                    p_nexts[(Bd, P)] = p_next
        if self._chain_pure_fn is not None:
            # chain gathers across bucket TRANSITIONS (population
            # crossing the small-bucket boundary mid-pipeline), for
            # every prefill-rectangle width in play (narrow + wide)
            for b_from in decode_buckets:
                for b_to in decode_buckets:
                    if b_from == b_to or b_from not in lasts:
                        continue
                    idx = np.zeros((b_to,), np.int32)
                    self._chain_pure_fn(lasts[b_from], idx)
                    for (bf, pw), pn in p_nexts.items():
                        if bf == b_from:
                            self._chain_fn(lasts[b_from], pn, idx)
        if self.config.prewarm_guided:
            self._prewarm_guided(
                decode_buckets, sampling_for, prefill_arrays, decode_arrays,
            )
        if self.kvbm is not None and self._mh_broadcast is None:
            # (single-host manager only: the multihost sharded offload
            # runs mirrored gathers, a different program)
            # KV offload/onboard shapes: each gather/scatter id bucket is
            # its own cache-sized jit program — an unwarmed bucket lands
            # as a mid-serve stall exactly when the first conversation's
            # blocks offload (measured: the multi-turn A/B's first turns
            # all stalled ~80 s together). Warm the buckets the offload
            # batch and prompt-onboard paths can reach.
            from dynamo_tpu.ops.block_copy import ID_BUCKETS

            width_cap = sched.table_width_pad or 32
            max_ids = min(
                max(self.config.kv_offload_batch, width_cap),
                ID_BUCKETS[-1],
            )
            for b in [x for x in ID_BUCKETS if x <= max_ids]:
                ids = [0] * b  # garbage block: reads/writes are harmless
                data = self._kv_gather(ids)
                self._kv_scatter(ids, data)
            jax.block_until_ready(self.k_cache)
        log.info(
            "prewarm: decode buckets %s, their chained variants and the "
            "glue in %.2fs", decode_buckets, time.monotonic() - t_decode,
        )
        prewarm_s = time.monotonic() - t0
        self.device_report["prewarm_s"] = round(prewarm_s, 3)
        ENGINE_PREWARM_SECONDS.set(prewarm_s)
        log.info("prewarm done in %.1fs", prewarm_s)

    def _prewarm_guided(
        self, decode_buckets, sampling_for, prefill_arrays, decode_arrays,
    ) -> None:
        """Warm the guided (allow-mask) jit variants — the masked
        serial prefill rectangles and decode buckets, plus the masked
        spec-verify rectangle on spec engines (docs/guided_decoding.md).
        The mask is a presence-keyed sampling-pytree entry, so each is
        its own compiled signature; an unwarmed one would land as a
        mid-serve compile exactly when the first structured-output
        request arrives (the compile fence flags it). Runs AFTER the
        base warms, so every cache input already carries the
        steady-state sharding. Guided serving is serial by design
        (overlap/spec pipelines flush to serial), so no chained
        device-column masked variants exist to warm."""
        sched = self.scheduler
        assert sched is not None and self.model_config is not None
        V = self.model_config.vocab_size

        def masked(s: SamplingBatch, b: int, S: Optional[int] = None):
            out = SamplingBatch(dict(s.arrays))
            shape = (b, V) if S is None else (b, S, V)
            out.arrays["allow_mask"] = np.ones(shape, dtype=bool)
            return out

        # masked variants mirror the base prewarm's opt-in flag policy
        # (penalties/bias under prewarm_penalties, top-logprobs under
        # prewarm_logprobs): a guided request combined with a feature
        # whose flag is off pays the same documented first-use compile
        # the unguided feature pays
        feat_variants: list[tuple[bool, bool, bool]] = [
            (False, False, False)
        ]
        if self.config.prewarm_logprobs:
            feat_variants.append((False, True, False))
        if self.config.prewarm_penalties:
            feat_variants.append((True, False, False))
            feat_variants.append((False, False, True))
        for b, chunk in sched.prefill_rects:
            for pv, tv, bv in feat_variants:
                a = prefill_arrays(b, chunk)
                s = masked(
                    sampling_for(b, penalties=pv, toplp=tv, bias=bv), b
                )
                out = self._step_fn(
                    self.params, self.k_cache, self.v_cache,
                    a["tokens"], a["positions"], a["slot_mapping"],
                    a["block_tables"], a["context_lens"],
                    a["last_token_idx"], s.arrays,
                )
                self.k_cache, self.v_cache = out[-2], out[-1]
                jax.block_until_ready(self.k_cache)
        for Bd in decode_buckets:
            for pv, tv, bv in feat_variants:
                a = decode_arrays(Bd)
                s = masked(
                    sampling_for(Bd, penalties=pv, toplp=tv, bias=bv), Bd
                )
                out = self._step_fn(
                    self.params, self.k_cache, self.v_cache,
                    a["tokens"], a["positions"], a["slot_mapping"],
                    a["block_tables"], a["context_lens"],
                    a["last_token_idx"], s.arrays,
                )
                self.k_cache, self.v_cache = out[-2], out[-1]
                jax.block_until_ready(self.k_cache)
        if self._spec_step_fn is not None:
            Ssp = self.config.spec_tokens + 1
            width = sched.table_width_of(
                sched.table_width_pad or sched.TABLE_BUCKET
            )
            for Bd in decode_buckets:
                sa = {
                    "tokens": np.zeros((Bd, Ssp), np.int32),
                    "positions": np.zeros((Bd, Ssp), np.int32),
                    "slot_mapping": np.zeros((Bd * Ssp,), np.int32),
                    "block_tables": np.zeros((Bd, width), np.int32),
                    "context_lens": np.zeros((Bd,), np.int32),
                    "draft_lens": np.zeros((Bd,), np.int32),
                }
                s = masked(sampling_for(Bd), Bd, S=Ssp)
                packed, self.k_cache, self.v_cache = self._spec_step_fn(
                    self.params, self.k_cache, self.v_cache,
                    sa["tokens"], sa["positions"], sa["slot_mapping"],
                    sa["block_tables"], sa["context_lens"],
                    sa["draft_lens"], s.arrays,
                )
                jax.block_until_ready(packed)

    def _gate_kv_offload(self) -> None:
        """Restore-vs-recompute gate for the G2 host tier: probe the
        REAL host<->device copy bandwidth and drop the tier when
        restoring a block costs more than recomputing its tokens.

        Rationale: where a 16.8 MB block moves slower than the
        flash-prefill path recomputes its 128 tokens, every onboard and
        write-through offload makes multi-turn serving STRICTLY worse.
        Where the link is fast (or on CPU, where host==device) the
        probe passes and the tier behaves as designed; which side the
        attached chip falls on is not measured yet. kv_offload_force
        keeps the tier unconditionally."""
        cfg = self.config
        if self.kvbm is None:
            return
        if self._mh_broadcast is not None:
            # sharded tier: mirrored transfers, no local probe — keep
            # the full busy-path batch (None = pump default) rather
            # than starving offload to idle-only with no measurement
            self._kv_busy_pump_cap = None
            return
        n = 4
        ids = [0] * n  # garbage block: harmless reads/writes
        data = self._kv_gather(ids)  # compile
        self._kv_scatter(ids, data)
        jax.block_until_ready(self.k_cache)
        # best-of-3: one contended sample must not permanently kill a
        # tier the link can actually sustain (capacity question ->
        # best observed bandwidth is the right estimator)
        gather_bps = scatter_bps = 0.0
        for _ in range(3):
            t0 = time.monotonic()
            data = self._kv_gather(ids)
            t1 = time.monotonic()
            self._kv_scatter(ids, data)
            jax.block_until_ready(self.k_cache)
            t2 = time.monotonic()
            gather_bps = max(gather_bps, data.nbytes / max(t1 - t0, 1e-9))
            scatter_bps = max(scatter_bps, data.nbytes / max(t2 - t1, 1e-9))
        block_bytes = data.nbytes / n
        # restoring a block must beat recomputing block_size tokens
        required = block_bytes * cfg.kv_recompute_tok_per_s / max(
            1, cfg.block_size or 1
        )
        bps = min(gather_bps, scatter_bps)
        # busy-path offload cap from the measured bandwidth: allow only
        # what fits in ~20 ms between serving steps (0 on slow links —
        # transfers then wait for idle moments)
        self._kv_busy_pump_cap = min(4, int(bps * 0.02 / block_bytes))
        if bps >= required:
            log.info(
                "G2 host KV tier active: copy bandwidth %.0f MB/s >= "
                "threshold %.0f MB/s (busy-path cap %d blocks/step)",
                bps / 1e6, required / 1e6, self._kv_busy_pump_cap,
            )
        elif cfg.kv_offload_force or cfg.disk_kv_blocks > 0 or cfg.remote_kv_bucket:
            # explicitly configured G3/G4 tiers must not vanish behind
            # a probe (mirrors the config-time invariant above): keep
            # the cascade, loudly
            log.warning(
                "G2 host KV tier kept (%s) despite copy bandwidth "
                "%.0f MB/s < restore-beats-recompute threshold "
                "%.0f MB/s — restores will be slower than recompute "
                "on this link",
                "kv_offload_force" if cfg.kv_offload_force
                else "G3/G4 tiers configured",
                bps / 1e6, required / 1e6,
            )
        else:
            log.warning(
                "G2 host KV tier disabled: measured copy bandwidth "
                "%.0f MB/s (gather %.0f / scatter %.0f) is below the "
                "restore-beats-recompute threshold %.0f MB/s at "
                "kv_recompute_tok_per_s=%.0f — restoring blocks would "
                "be slower than re-prefilling them on this link. Set "
                "kv_offload_force=true to keep the tier.",
                bps / 1e6, gather_bps / 1e6, scatter_bps / 1e6,
                required / 1e6, cfg.kv_recompute_tok_per_s,
            )
            self._disable_kvbm()

    def _auto_num_blocks(self, devices) -> int:
        """Size the KV cache from the free HBM the device reports."""
        mc = self.model_config
        assert mc is not None
        # TPU tiling pads the cache's trailing [Hkv, Dh] dims (minor to
        # a 128-lane multiple, second-minor to the sublane tile) — a
        # small-geometry cache can occupy several× its unpadded bytes,
        # so size from PADDED dims or the chip overcommits at compile
        itemsize = jnp.dtype(self.config.kv_cache_dtype).itemsize
        dh_pad = -(-mc.head_dim // 128) * 128
        # second-minor bound: 8 covers the layouts observed on v5e for
        # the paged cache (bf16 caches lower to packed (..,128)(2,1)
        # tiles — empirically a [32,S,8,128] bf16 cache occupies its
        # unpadded bytes, so 16-sublane padding does NOT apply here)
        hk_pad = -(-mc.num_key_value_heads // 8) * 8
        bytes_per_block_total = (
            2  # K and V
            * mc.num_hidden_layers
            * self.config.block_size
            * hk_pad
            * dh_pad
            * itemsize
        )
        if jnp.dtype(self.config.kv_cache_dtype) == jnp.int8:
            # per-(slot, head) f32 scale planes ([L, N, Hk*bs] per K/V —
            # layout already lane-compact, no tile padding to model)
            bytes_per_block_total += (
                2 * mc.num_hidden_layers * self.config.block_size
                * mc.num_key_value_heads * 4
            )
        fam = model_family(mc)
        reserved = 0
        if mc.owns_pages:
            # the family's own pages (latent rows, or the K and V of its
            # attention layers alone); its own step transients come off
            # the top, and the state plane where it keeps one
            bytes_per_block_total = fam.page_bytes_per_block(
                mc, self.config.block_size, itemsize
            )
            reserved = fam.STEP_TRANSIENT_BYTES
            if mc.has_recurrent_state:
                reserved += fam.state_bytes(
                    mc, self._state_slot_count, itemsize
                )
            if mc.released_window:
                # the window plane comes off the top like a state plane:
                # its size does not follow from free memory
                reserved += self._window_plane_blocks * (
                    fam.page_bytes_per_block(
                        mc, self.config.block_size, itemsize, plane="window"
                    )
                )
        if getattr(devices[0], "platform", "") != "tpu":
            # CPU/virtual test backends: a modest fixed pool (their
            # memory_stats describe host RAM, which would size a
            # gigantic cache and stall bring-up allocating it)
            return 512
        stats = devices[0].memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{devices[0]} reports no memory_stats(); cannot size the "
                "KV cache from free HBM — set num_blocks explicitly"
            )
        free = stats["bytes_limit"] - stats["bytes_in_use"] - reserved
        # step-transient headroom the cache must leave: a full batched
        # prefill's activations dominate — per token roughly 6 D-wide
        # bf16 tensors (h/q/k/v/attn/out), 3 F-wide (gate/up/act, ×E for
        # dense-compute MoE), plus f32 attention scores H × S_table
        # a prefill step's token area is capped by max_prefill_tokens
        # (scheduler._plan_prefill_batch budget), NOT the full
        # batch × chunk rectangle — ×2 covers bucket padding
        area = min(
            self.config.max_batch_size * self.config.prefill_chunk_size,
            2 * (self.config.max_prefill_tokens or self.config.prefill_chunk_size),
        )
        # scores-width estimate: only the XLA reference attention
        # materializes [T, S] scores (one layer-transient, capped so an
        # uncapped max_position_embeddings can't swallow the budget).
        # The Pallas flash kernels keep scores in VMEM — charging HBM
        # for them would waste gigabytes of KV capacity exactly on the
        # long-context workloads that need it (at max_model_len 3328 /
        # max_prefill_tokens 4096 the phantom term is ~4 GB).
        from dynamo_tpu.models.llama import pallas_attention_active

        if pallas_attention_active():
            s_est = 0
        else:
            s_est = min(
                (self.config.max_model_len or mc.max_position_embeddings)
                + 8 * self.config.block_size,
                4096,
            )
        e_mult = max(1, mc.num_local_experts)
        per_tok = (
            12 * mc.hidden_size
            + 6 * mc.intermediate_size * e_mult
            + 4 * mc.num_attention_heads * s_est
        )
        # activations shard over tp (hidden/head axes), so the per-device
        # transient shrinks with tp; flat guard covers scan/fusion
        # scratch the per-token model misses
        transient = (
            area * per_tok / self.config.tensor_parallel_size + (512 << 20)
        )
        budget = max(0.0, free - transient) * self.config.hbm_utilization
        # cache is sharded over tp: each device holds Hkv/tp heads
        budget_total = budget * (self.config.tensor_parallel_size
                                  * self.config.pipeline_parallel_size)
        n = int(budget_total // bytes_per_block_total)
        one_seq = -(-(self.config.max_model_len or mc.max_position_embeddings)
                    // self.config.block_size) + 2
        if n < one_seq:
            log.warning(
                "auto-sized KV cache (%d blocks) can't hold one "
                "max_model_len sequence (%d blocks): serving will thrash "
                "— lower max_batch_size/prefill_chunk_size or set "
                "num_blocks explicitly", n, one_seq,
            )
        return max(16, min(n, 1_000_000))

    def _on_kv_event(self, op: str, hashes: list[int], blocks: list[int]) -> None:
        if self.kvbm is not None and op == "stored":
            for h, b in zip(hashes, blocks):
                self.kvbm.on_block_committed(h, b)
        if self.kv_event_sink is not None:
            self.kv_event_sink(op, hashes, blocks)

    def _safe_onboard(self, hashes: list[int], blocks: list[int]) -> int:
        """Onboarding is an optimization: a lower-tier failure degrades to
        G1-only (a 0 return just means 'prefill those tokens normally')."""
        if self.kvbm is None:
            return 0
        from dynamo_tpu.parallel.multihost import FatalMultihostError

        try:
            return self.kvbm.onboard(hashes, blocks)
        except FatalMultihostError:
            raise  # inside a mirrored collective: not recoverable
        except Exception:
            log.exception("kv onboard failed; disabling kvbm")
            self._disable_kvbm()
            return 0

    # -- KVBM device data path (engine thread only: caches are donated) ----
    def _kv_gather(self, block_ids: list[int]) -> np.ndarray:
        return gather_blocks(
            self.k_cache, self.v_cache, block_ids, self.config.block_size
        )

    def _kv_scatter(self, block_ids: list[int], data: np.ndarray) -> None:
        self.k_cache, self.v_cache = scatter_blocks(
            self.k_cache, self.v_cache, block_ids, data, self.config.block_size
        )

    # ------------------------------------------------------------------
    # The fused device step
    # ------------------------------------------------------------------
    def _build_step_fn(self) -> None:
        mc = self.model_config
        block_size = self.config.block_size
        assert mc is not None

        # Pin every step fn's outputs to ONE canonical sharding. A jit
        # signature includes each input's committed sharding, and the
        # caches/token columns thread from outputs back into inputs —
        # without pinning, the sharding lineage (init vs step-output vs
        # mixed-output) silently forks the signature and a "prewarmed"
        # shape recompiles at serve time (measured: a 69 s mid-serve
        # stall for an already-warmed prefill shape).
        from jax.sharding import NamedSharding, PartitionSpec as PSpec

        if self._pp > 1:
            from dynamo_tpu.parallel.pipeline import PP_CACHE_SPEC

            cache_sp = PP_CACHE_SPEC
        else:
            cache_sp = CACHE_SPEC
        ns_cache = NamedSharding(self.mesh, cache_sp)
        ns_rep2 = NamedSharding(self.mesh, PSpec(None, None))
        ns_rep1 = NamedSharding(self.mesh, PSpec(None))
        from dynamo_tpu.models.llama import SCALE_SPEC

        ns_scale = NamedSharding(self.mesh, SCALE_SPEC)

        ns_rep0 = NamedSharding(self.mesh, PSpec())

        def pin_caches(k, v):
            def pin(c):
                if isinstance(c, dict):  # a family's own planes: one device
                    return {
                        n: jax.lax.with_sharding_constraint(a, ns_rep0)
                        for n, a in c.items()
                    }
                if isinstance(c, tuple):  # int8 cache: (values, scales)
                    return (
                        jax.lax.with_sharding_constraint(c[0], ns_cache),
                        jax.lax.with_sharding_constraint(c[1], ns_scale),
                    )
                return jax.lax.with_sharding_constraint(c, ns_cache)

            return pin(k), pin(v)

        if self._pp > 1:
            from dynamo_tpu.parallel.pipeline import forward_pp

            mesh = self.mesh

            def forward(*a, **kw):  # noqa: F811 — pp-sharded model step
                return forward_pp(*a, mesh=mesh, **kw)
        else:
            forward = model_family(mc).forward  # noqa: F811

        def step(
            params,
            k_cache,
            v_cache,
            tokens,
            positions,
            slot_mapping,
            block_tables,
            context_lens,
            last_token_idx,
            sampling,  # SamplingBatch.arrays pytree
            *mm_args,  # optionally (extra_embeds, embeds_mask)
        ):
            logits, new_k, new_v = forward(
                mc,
                params,
                k_cache,
                v_cache,
                tokens,
                positions,
                slot_mapping,
                block_tables,
                context_lens,
                last_token_idx,
                block_size,
                *mm_args,
            )
            # sample() returns 2 outputs on the base path, 4 when the
            # batch carries the top-logprobs marker (a separately-traced
            # variant — the pytree structure differs)
            s_out = sample(logits, sampling)
            new_k, new_v = pin_caches(new_k, new_v)
            return (*s_out, new_k, new_v)

        # donate the caches: XLA aliases them in-place. One jitted fn
        # serves both arities (jit retraces per signature); the
        # multimodal variant compiles only if a request uses it.
        self._step_fn = jax.jit(step, donate_argnums=(1, 2))
        self._step_fn_mm = self._step_fn

        K = self.config.decode_steps
        bs = block_size

        def decode_window(
            params,
            k_cache,
            v_cache,
            tokens,  # [B, 1] the last sampled token per sequence
            positions,  # [B, 1] its position
            block_tables,
            context_lens,
            valid_steps,  # [B] steps the seq will actually keep (<= K)
            sampling,  # SamplingBatch.arrays pytree
        ):
            """K fused decode steps: one dispatch, K tokens per sequence.
            Slot mapping is recomputed on-device from the advancing
            positions; sampling seeds advance per step so outputs match
            K single steps exactly. When the batch carries penalty
            tables, a dense [B, V] generated-token count rides the scan
            carry and updates after every sampled token, so penalties
            inside the window are exact too. When it carries the
            top-logprobs marker, each step's top-TOPLP_N alternatives
            ride the packed output (ids exact in f32: vocab < 2^24)."""
            has_pen = "rep_pen" in sampling
            has_tlp = "top_lp_n" in sampling
            B = tokens.shape[0]
            V = mc.vocab_size
            gen0 = dense_gen_counts(sampling, V) if has_pen else jnp.zeros((B, 1))
            prompt_dense = (
                dense_prompt_presence(sampling, V) if has_pen else None
            )

            def body(carry, i):
                k_c, v_c, tok, pos, ctx, gen = carry
                pos_flat = pos[:, 0]
                slot = (
                    jnp.take_along_axis(
                        block_tables, (pos_flat // bs)[:, None], axis=1
                    )[:, 0]
                    * bs
                    + pos_flat % bs
                )
                # The scheduler only allocates blocks for each sequence's
                # remaining-token budget; steps past that window would have
                # their table lookup clipped onto the seq's LAST REAL block
                # (take_along_axis clips), corrupting possibly-shared KV.
                # Redirect surplus writes to slot 0 — block 0 is the
                # reserved garbage block. The surplus outputs are
                # discarded host-side by _emit_window.
                slot = jnp.where(i < valid_steps, slot, 0)
                logits, k_c, v_c = forward(
                    mc, params, k_c, v_c, tok, pos, slot, block_tables,
                    ctx, jnp.zeros_like(pos_flat), bs,
                )
                s_i = dict(sampling)
                s_i["seeds"] = sampling["seeds"] + i.astype(jnp.uint32)
                s_res = sample(
                    logits, s_i,
                    gen if has_pen else None,
                    prompt_dense,
                )
                nt = s_res[0]
                if has_pen:
                    gen = gen.at[jnp.arange(B), nt].add(1.0)
                return (k_c, v_c, nt[:, None], pos + 1, ctx + 1, gen), s_res

            carry = (k_cache, v_cache, tokens, positions, context_lens, gen0)
            (k_cache, v_cache, last_tok, *_), ys = jax.lax.scan(
                body, carry, jnp.arange(K)
            )
            toks, lps = ys[0], ys[1]
            # one packed host transfer per window (tokens are exact in
            # f32: vocab ids < 2^24), plus the device-resident last
            # token column for chaining the next window without a host
            # round trip
            cols = [toks.T.astype(jnp.float32), lps.T]
            if has_tlp:
                # [K, B, N] -> [B, K*N]
                tids, tlps = ys[2], ys[3]
                N = tids.shape[-1]
                cols.append(
                    tids.transpose(1, 0, 2).reshape(B, K * N).astype(jnp.float32)
                )
                cols.append(tlps.transpose(1, 0, 2).reshape(B, K * N))
            packed = jnp.concatenate(cols, axis=1)  # [B, 2K (+2KN)]
            k_cache, v_cache = pin_caches(k_cache, v_cache)
            last_tok = jax.lax.with_sharding_constraint(last_tok, ns_rep2)
            return packed, last_tok, k_cache, v_cache

        def mixed_step(
            params,
            k_cache,
            v_cache,
            # prefill rectangle [P, T] (fixed shape; engine pads)
            p_tokens,
            p_positions,
            p_slot_mapping,
            p_block_tables,
            p_context_lens,
            p_last_idx,
            p_sampling,
            # decode window [B, 1]
            d_tokens,
            d_positions,
            d_block_tables,
            d_context_lens,
            d_valid_steps,
            d_sampling,
        ):
            """Mixed continuous-batching step: the pending prefill
            chunks run FIRST (so new requests' first tokens land this
            window), then the K-step decode window — one dispatch, one
            host round trip, no decode stall for stragglers' prefills.
            The prefill rectangle's weight reads are shared with the
            window only at the XLA-fusion level; its real win is that a
            ~1k-token rectangle adds ~10-15% to a window instead of a
            dedicated full-weight pass per straggler."""
            p_logits, k_cache, v_cache = forward(
                mc, params, k_cache, v_cache, p_tokens, p_positions,
                p_slot_mapping, p_block_tables, p_context_lens,
                p_last_idx, bs,
            )
            # top-logprobs batches never reach the mixed step (the
            # window pipeline diverts them to dedicated prefill +
            # pure windows — see _window_pipeline), so both sampling
            # dicts here are 2-output variants
            p_next, p_lp = sample(p_logits, p_sampling)
            packed, last_tok, k_cache, v_cache = decode_window(
                params, k_cache, v_cache, d_tokens, d_positions,
                d_block_tables, d_context_lens, d_valid_steps, d_sampling,
            )
            # ONE flat host transfer for all outputs: each separate
            # device->host read is its own synchronisation, which would
            # triple the window's sync count (its cost is not measured
            # on the attached chip). p_next additionally returns device-resident so
            # a pipelined next window can chain graduated prefills'
            # first tokens without a host hop.
            flat = jnp.concatenate(
                [packed.reshape(-1), p_next.astype(jnp.float32), p_lp]
            )
            p_next = jax.lax.with_sharding_constraint(p_next, ns_rep1)
            return flat, last_tok, p_next, k_cache, v_cache

        def chain_tokens(last_tok, p_next, src_idx):
            """Next window's token column, gathered on device from the
            in-flight window's outputs: rows [0, B) of the concat are
            the decode window's last tokens, rows [B, B+P) the prefill
            rectangle's sampled tokens (graduations)."""
            cat = jnp.concatenate([last_tok[:, 0], p_next])
            return jax.lax.with_sharding_constraint(
                jnp.take(cat, src_idx)[:, None], ns_rep2
            )

        def chain_tokens_pure(last_tok, src_idx):
            """Chain from a pure decode window (no prefill rectangle
            outputs to graduate)."""
            return jax.lax.with_sharding_constraint(
                jnp.take(last_tok[:, 0], src_idx)[:, None], ns_rep2
            )

        def chain_next(next_tokens, src_idx):
            """Next step's [B', 1] token column gathered on device from
            a single-step dispatch's sampled tokens [B] (the overlapped
            decode pipeline) or a prefill batch's sampled first tokens
            (the cohort-graduation entry) — no host round trip."""
            return jax.lax.with_sharding_constraint(
                jnp.take(next_tokens, src_idx)[:, None], ns_rep2
            )

        def chain_join(column, host_tokens, src_idx):
            """Next decode step's [B', 1] token column where the newest
            dispatch is a prefill batch (the decode pipeline's in-line
            admission): a row whose last chunk that was takes its first
            token from the batch's sampled ``column`` [P] on the device,
            every other row (``src_idx`` -1) the token the host already
            holds in ``host_tokens`` [B', 1]."""
            took = jnp.take(column, jnp.maximum(src_idx, 0))
            return jax.lax.with_sharding_constraint(
                jnp.where(src_idx >= 0, took, host_tokens[:, 0])[:, None],
                ns_rep2,
            )

        def pack_pair(next_tokens, logprobs):
            """One packed [2B] host transfer for a single-step
            dispatch's outputs (token ids exact in f32: vocab < 2^24) —
            each separate device->host read is its own synchronisation,
            so the overlapped pipeline's harvest syncs exactly one
            array per step."""
            return jax.lax.with_sharding_constraint(
                jnp.concatenate(
                    [next_tokens.astype(jnp.float32), logprobs]
                ),
                ns_rep1,
            )

        def spec_step(
            params,
            k_cache,
            v_cache,
            tokens,  # [B, S] carry token + up to S-1 drafts per row
            positions,  # [B, S] contiguous run from each row's base
            slot_mapping,  # [B*S] (pads -> garbage slot 0)
            block_tables,
            context_lens,  # [B] real tokens incl. drafts
            draft_lens,  # [B] valid drafts per row
            sampling,  # SamplingBatch.arrays (base path only)
        ):
            """Speculative verify step: ONE forward over the draft run
            through the paged-KV attention (draft KV is written
            speculatively — rejected positions are overwritten by the
            next real append before they can ever be read or
            content-addressed), then on-device rejection sampling
            (spec/verify.py). Output rides one packed host transfer
            (verify.pack_spec): [B, S out_tokens | S out_lps | 1 n_emit]."""
            from dynamo_tpu.spec.verify import pack_spec, verify_tokens

            logits_all, k_cache, v_cache = forward(
                mc, params, k_cache, v_cache, tokens, positions,
                slot_mapping, block_tables, context_lens,
                jnp.zeros_like(context_lens), bs, logits_all=True,
            )
            out_toks, out_lps, n_emit = verify_tokens(
                logits_all, tokens, draft_lens, sampling
            )
            packed = pack_spec(out_toks, out_lps, n_emit)
            k_cache, v_cache = pin_caches(k_cache, v_cache)
            packed = jax.lax.with_sharding_constraint(packed, ns_rep2)
            return packed, k_cache, v_cache

        def chain_spec(packed, host_tokens, src_idx):
            """Next verify step's [B', S] token rectangle for the
            overlapped spec pipeline: column 0 — each row's CARRY token
            (the in-flight step's LAST emitted token, out_tokens at
            n_emit-1) — gathered on device from the packed verify
            output, columns 1.. the host-proposed drafts. The spec
            twin of ``chain_next``: the carry never round-trips
            host<->device between consecutive verify steps, and the
            gather rebuckets a shrinking population (src_idx maps new
            rows onto the previous step's rows)."""
            S_ = host_tokens.shape[1]
            out_toks = packed[:, :S_].astype(jnp.int32)
            n_emit = packed[:, 2 * S_].astype(jnp.int32)
            carry = jnp.take_along_axis(
                out_toks, jnp.clip(n_emit - 1, 0, S_ - 1)[:, None], axis=1
            )[:, 0]
            col = jnp.take(carry, src_idx)
            return jax.lax.with_sharding_constraint(
                host_tokens.at[:, 0].set(col), ns_rep2
            )

        self._spec_step_fn = (
            jax.jit(spec_step, donate_argnums=(1, 2))
            if self.config.spec_decode
            else None
        )
        self._chain_spec_fn = (
            jax.jit(chain_spec) if self.config.spec_decode else None
        )

        self._multi_step_fn = (
            jax.jit(decode_window, donate_argnums=(1, 2)) if K > 1 else None
        )
        self._mixed_step_fn = (
            jax.jit(mixed_step, donate_argnums=(1, 2)) if K > 1 else None
        )
        self._chain_fn = jax.jit(chain_tokens) if K > 1 else None
        self._chain_pure_fn = jax.jit(chain_tokens_pure) if K > 1 else None
        # overlapped-pipeline glue (both K regimes): on-device token
        # chaining off a single-step/prefill dispatch + packed harvest
        self._chain_next_fn = jax.jit(chain_next)
        self._chain_join_fn = jax.jit(chain_join)
        self._pack_pair_fn = jax.jit(pack_pair)

    def _stage_step_inputs(
        self, arrays: dict[str, np.ndarray], sampling: SamplingBatch
    ) -> tuple[dict, SamplingBatch]:
        """Explicitly stage the host-built step inputs onto the device
        before feeding the jitted step.  Under the armed transfer fence
        (DYN_TRANSFER_FENCE, utils/transfer_fence.py) a raw np.ndarray
        argument would trip the guard as an implicit host->device
        upload; ``jax.device_put`` is the sanctioned spelling of the
        same transfer.  Only ndarray leaves are staged — Python scalars
        keep their weak types (a device_put would change avals and
        recompile every step variant).  Inert when the fence is off:
        the default hot path feeds numpy exactly as before.  The
        fence tests monkeypatch this method to reintroduce the
        implicit upload the fence exists to catch."""
        if not transfer_fence.enabled():
            return arrays, sampling
        staged = {
            k: jax.device_put(v) if isinstance(v, np.ndarray) else v
            for k, v in arrays.items()
        }
        samp = SamplingBatch(arrays={
            k: jax.device_put(v) if isinstance(v, np.ndarray) else v
            for k, v in sampling.arrays.items()
        })
        return staged, samp

    def _dispatch_device_step(
        self,
        arrays: dict[str, np.ndarray],
        sampling: SamplingBatch,
        origin: str = "",
        defer_sync: bool = True,
    ) -> tuple:
        """DISPATCH half of a fused device step: announce (multihost),
        launch the jitted step, swap the donated caches, and return the
        sampled DEVICE outputs — no host sync. The caller harvests via
        ``_harvest_device_step`` when (and only when) it needs values;
        between the two, the host is free to plan/pack the next step
        while the device executes this one (docs/performance.md).

        ``origin`` labels the dispatch for deferred-error forensics: an
        async dispatch's device error only SURFACES at a later synced
        step (_annotate_deferred_error). ``defer_sync=False`` skips that
        registration — for callers that harvest THIS dispatch before
        doing anything else, its error surfaces under its own batch."""
        assert self._step_fn is not None
        if self._mh_broadcast is not None:
            if "extra_embeds" in arrays:
                # embed rectangle broadcasts as its own control kind so
                # followers enter the mm-variant step with real embeds
                self._mh_broadcast.announce_step_mm(arrays, sampling)
            else:
                self._mh_broadcast.announce_step(arrays, sampling)
        # stage AFTER the announce: followers deserialize host numpy
        arrays, sampling = self._stage_step_inputs(arrays, sampling)
        base_args = (
            self.params,
            self.k_cache,
            self.v_cache,
            arrays["tokens"],
            arrays["positions"],
            arrays["slot_mapping"],
            arrays["block_tables"],
            arrays["context_lens"],
            arrays["last_token_idx"],
            sampling.arrays,
        )
        idle_gap_s = self.overlap.note_dispatch()
        if "extra_embeds" in arrays:
            out = self._step_fn_mm(
                *base_args, arrays["extra_embeds"], arrays["embeds_mask"]
            )
        else:
            out = self._step_fn(*base_args)
        self.k_cache, self.v_cache = out[-2], out[-1]
        self._newest_out = out[0]
        # dispatch_ms is the enclosing dyn.step.dispatch phase's wall:
        # the caller adds it when the phase has ended
        self._last_phases = {"idle_gap_ms": round(idle_gap_s * 1e3, 3)}
        if defer_sync:
            self._unsynced_steps.append(
                origin or f"shape={arrays['tokens'].shape}"
            )
            del self._unsynced_steps[:-8]  # bounded forensics window
        return out[:-2]

    def _harvest_device_step(self, outs: tuple) -> tuple:
        """HARVEST half: the designated host-sync point for step
        outputs (dynalint DL010 flags syncs anywhere else in the step
        loop). Blocks until the device result lands on host — under the
        overlapped pipeline that result is already (or nearly) done."""
        from dynamo_tpu.parallel.multihost import host_value

        # (next_tokens, logprobs) base; (+ top_ids, top_lps) on the
        # top-logprobs variant
        res = tuple(host_value(x) for x in outs)
        self.overlap.note_complete(all_prior=True)
        # a successful sync retires every earlier async dispatch
        # (in-order device execution): their deferred errors would have
        # surfaced in this host read
        self._unsynced_steps.clear()
        return res

    def _run_device_step(
        self,
        arrays: dict[str, np.ndarray],
        sampling: SamplingBatch,
        sync: bool = True,
        origin: str = "",
        kind: str = "prefill",
    ):
        """``sync=False`` skips the device->host read of the sampled
        outputs (returns None): a prefill batch with NO last chunks has
        no token anyone needs, and each host read is a synchronisation
        the device would otherwise not wait for — a 3-chunk ISL-3000
        prompt pays it twice for nothing. The dispatch still happens
        (and still broadcasts under multihost); donated caches chain
        the next step regardless."""
        if kind == "prefill":
            self._count_prefill(arrays)
        elif kind == "decode":
            self._count_decode(arrays)
        with self._dispatch_span(kind, arrays["tokens"]) as dispatch:
            outs = self._dispatch_device_step(
                arrays, sampling, origin=origin, defer_sync=not sync
            )
        self._last_phases["dispatch_ms"] = dispatch.ms
        if not sync:
            return None
        with step_span("dyn.step.harvest") as harvest:
            res = self._harvest_device_step(outs)
        self._last_phases["sync_ms"] = harvest.ms
        return res

    def _count_prefill(self, arrays: dict[str, np.ndarray]) -> None:
        """One prefill rectangle about to be dispatched: what its chunks
        hold (pad rows have context 0) against what it pads to."""
        real = arrays["context_lens"] > 0
        self._prefill_tokens[0] += int(
            (arrays["last_token_idx"][real] + 1).sum()
        )
        self._prefill_tokens[1] += arrays["tokens"].size

    def _count_decode(self, arrays: dict[str, np.ndarray]) -> None:
        """One decode bucket about to be dispatched: its rows, and those
        of them that are padding (context 0)."""
        ctx = arrays["context_lens"]
        self._decode_rows[0] += ctx.shape[0]
        self._decode_rows[1] += int(np.count_nonzero(ctx == 0))

    def _dispatch_span(self, kind: str, tokens):
        """Count one device program of ``kind`` (program_counts) and
        return its ``dyn.step.dispatch`` phase; ``tokens`` is the step's
        token array (host or device), read for its shape only. Asks the
        device, without waiting, whether the newest step in flight has
        finished: if so (or nothing is in flight) this dispatch goes to a
        device whose queue had run dry — the host came late."""
        self._steps_dispatched[kind] = self._steps_dispatched.get(kind, 0) + 1
        slots = self.scheduler.state_slots if self.scheduler else None
        if slots is not None:
            self._state_slot_steps[0] += slots.num_used
            self._state_slot_steps[1] += slots.num_slots - 1
        plane = self.scheduler.window_plane if self.scheduler else None
        if plane is not None:
            self._window_page_steps[0] += plane.num_used
            self._window_page_steps[1] += (
                len(self.scheduler.running) + len(self.scheduler.prefilling)
            )
        phase = step_span(
            "dyn.step.dispatch", kind=kind, rows=int(tokens.shape[0]),
            tokens=int(tokens.size),
        )
        newest = self._newest_out
        try:
            phase.drained = newest is None or newest.is_ready()
        except Exception:  # advisory: a count never fails a dispatch
            log.debug("is_ready() of the newest step failed", exc_info=True)
        return phase

    # ------------------------------------------------------------------
    # Engine thread loop
    # ------------------------------------------------------------------
    @affinity.thread_affinity("engine")
    def _step_loop(self) -> None:
        affinity.register_thread("engine")
        self.step_clock.on_tick = self._note_counts
        bind_step_clock(self.step_clock)
        try:
            self._step_loop_body()
        finally:
            bind_step_clock(None)
            # OS thread idents are reused — a stale binding would blame
            # "engine" for a later unrelated thread's writes
            affinity.unregister_thread()

    def _step_loop_body(self) -> None:
        if self._is_follower:
            # follower ranks mirror the leader's device dispatches until
            # the leader broadcasts STOP (parallel/multihost.py)
            from dynamo_tpu.parallel.multihost import StepFollower

            try:
                StepFollower(self).run()
            except Exception:
                log.exception("multihost follower loop failed")
            self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
            return
        assert self.scheduler is not None
        from dynamo_tpu.parallel.multihost import FatalMultihostError

        def pump_kvbm(max_blocks: Optional[int] = None) -> bool:
            """False = fatal multihost failure: the loop must fail all
            requests and stop (a raise here would escape _step_loop and
            leave every request stream hanging on a dead thread)."""
            if self.kvbm is None:
                return True
            try:
                self.kvbm.pump(max_blocks)
            except FatalMultihostError:
                log.exception(
                    "fatal multihost failure inside a mirrored KV op; "
                    "taking the engine down"
                )
                return False
            except Exception:
                log.exception("kv offload pump failed; disabling kvbm")
                self._disable_kvbm()
            return True

        clock = self.step_clock
        while self._running:
            clock.lap()
            # worker-liveness injection point: `kill` rules here model a
            # hard worker death between steps (one-shot by default)
            faults.fire("worker.liveness")
            with step_span("dyn.step.plan"):
                self._drain_incoming()
                if self._draining:
                    # graceful drain: hand off eligible in-flight streams
                    # at this step boundary (every generated token has
                    # already been emitted, so the router's commit log is
                    # exact)
                    self._migrate_eligible()
            if (
                not self.scheduler.running
                and not self.scheduler.prefilling
                and len(self.scheduler.waiting) >= 2
            ):
                # an arrival BURST onto an idle engine: the submitter is
                # still enqueueing (e.g. a gather of N requests, or an
                # HTTP cohort) — planning now would split the burst
                # across prefill steps and desynchronize the decode
                # population for its whole lifetime. Wait out the burst
                # while it is still growing (bounded: ~16 ms worst case
                # vs a multi-hundred-ms prefill dispatch saved).
                # blocking sleep is deliberate: _step_loop runs on the
                # dedicated "jax-engine" thread (launch()), never on the
                # event loop, so this parks only the engine thread
                clock.note_idle()
                with step_span("dyn.step.wait"):
                    for _ in range(8):
                        before = len(self.scheduler.waiting)
                        time.sleep(0.002)
                        self._drain_incoming()
                        if len(self.scheduler.waiting) == before:
                            break
            if not self.scheduler.has_work:
                # idle: drain the offload queue (and run the pump's
                # periodic G4 index refresh) before sleeping. SMALL
                # batches per iteration: each block is a multi-MB
                # device->host transfer, and a request arriving
                # mid-batch must not wait out a 16-block gather.
                clock.note_idle()
                with step_span("dyn.step.wait"):
                    pumped = pump_kvbm(4)
                if not pumped:
                    self._fail_all()
                    self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
                    return
                if self.kvbm is not None and self.kvbm.pending_offloads:
                    continue  # more queued: keep draining
                # no work: the wait for the next request is load, not a
                # device idle gap — drop the overlap tracker's anchor
                self.overlap.note_idle()
                with step_span("dyn.step.wait"):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            try:
                self._one_step()
                self._step_failures = 0
            except FatalMultihostError:
                log.exception(
                    "fatal multihost failure inside a mirrored collective; "
                    "taking the engine down"
                )
                self._fail_all()
                self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
                return
            except Exception as exc:
                if transfer_fence.intercept(exc):
                    # the transfer guard raised at the offending site:
                    # the aborted step may never reach _record_step, so
                    # escalate here. Under fatal mode the fence error
                    # takes the engine down like a fatal multihost
                    # failure — streams get a terminal error, not a
                    # hang on a dead thread.
                    try:
                        self._check_transfer_fence("aborted")
                    except transfer_fence.TransferFenceError:
                        log.exception(
                            "serve-phase implicit transfer under "
                            "DYN_TRANSFER_FENCE=fatal; taking the "
                            "engine down"
                        )
                        self._fail_all()
                        self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
                        return
                self._step_failures += 1
                # queue depth is unknowable after an aborted dispatch
                self.overlap.reset()
                self._annotate_deferred_error(exc)
                if not self._quarantine_step_failure():
                    log.exception(
                        "engine step failed; failing in-flight requests"
                    )
                    self._fail_all()
                continue
            # BUSY path: bounded by the probed copy bandwidth (~20 ms
            # of transfer per step; 0 on slow links). Unbounded
            # write-through offload between serving steps put multi-MB
            # transfers on every window, competing with the serving
            # steps (not measured on the attached chip);
            # pending commits are bounded by G1 size, revalidated at
            # pump time, and drain at idle moments.
            if self.kvbm is None:
                continue
            with step_span("dyn.step.wait"):
                pumped = pump_kvbm(self._kv_busy_pump_cap)
            if not pumped:
                self._fail_all()
                self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
                return

    def _disable_kvbm(self) -> None:
        """Offload tiers are an optimization: on failure, degrade to
        G1-only rather than taking the engine down. Multihost: the
        sharded manager first broadcasts the disable so follower shard
        pools drop in lockstep (runs on the engine thread, while
        followers are still in their receive loop)."""
        if self.kvbm is not None:
            kvbm, self.kvbm = self.kvbm, None
            if self.scheduler is not None:
                self.scheduler.onboard = None
            try:
                getattr(kvbm, "on_disable", lambda: None)()
                kvbm.close()
            except Exception:
                pass

    def _drain_incoming(self) -> None:
        assert self.scheduler is not None
        # control calls first: a KV import enqueued before a submit must be
        # visible to that request's admission (disagg relies on this order)
        while True:
            try:
                fn, fut = self._control.get_nowait()
            except thread_queue.Empty:
                break
            if fut.set_running_or_notify_cancel():
                try:
                    fut.set_result(fn())
                except Exception as exc:
                    fut.set_exception(exc)
        while True:
            try:
                item = self._incoming.get_nowait()
            except thread_queue.Empty:
                return
            self.scheduler.add_request(item)

    # ------------------------------------------------------------------
    # Engine-thread call plane (KV export/import for the transfer agent)
    # ------------------------------------------------------------------
    def call_on_thread(self, fn: Callable[[], Any]) -> "concurrent.futures.Future":
        """Run fn on the engine thread (the only thread allowed to touch
        the donated cache buffers and KVBM pools); returns a Future."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._control.put((fn, fut))
        self._wake.set()
        return fut

    async def acall_on_thread(self, fn: Callable[[], Any]) -> Any:
        return await asyncio.wrap_future(self.call_on_thread(fn))

    def _export_blocks(self, seq_hashes: list[int]) -> tuple[list[int], np.ndarray]:
        """ENGINE THREAD. Gather the longest cached prefix of seq_hashes
        as packed blocks (device tier first, then host tier).

        Multihost (num_nodes > 1): the cache's KV-head axis is sharded
        ACROSS processes, so the export runs as a mirrored replicated
        gather (announce + mirror_gather_full) — the leader ends up with
        whole blocks for the transfer plane. Only the DEVICE-resident
        prefix exports there: the sharded G2 pools hold per-process head
        slices, and assembling those would need a host-side cross-
        process collective the step broadcast channel doesn't carry
        (per-tier design notes: docs/multihost.md)."""
        from dynamo_tpu.kvbm import BlockLayout

        assert self.allocator is not None and self.model_config is not None
        layout = BlockLayout.for_model(
            self.model_config, self.config.block_size,
            self.config.wire_kv_dtype(),
        )
        multihost = self.config.num_nodes > 1
        plan: list[tuple[str, int]] = []  # (tier, device block | hash)
        for h in seq_hashes:
            bid = self.allocator.lookup_block(h)
            if bid is not None:
                plan.append(("dev", bid))
            elif (
                not multihost
                and self.kvbm is not None
                and hasattr(self.kvbm.host, "read")  # not the multihost shard pool
                and self.kvbm.host.contains(h)
            ):
                plan.append(("host", h))
            else:
                break
        if multihost:
            from dynamo_tpu.parallel.multihost import mirror_gather_full

            n = len(plan)
            if n == 0:
                return [], np.zeros((0, *layout.packed_shape), layout.np_dtype)
            ids = [bid for _, bid in plan]
            assert self._mh_broadcast is not None
            self._mh_broadcast.announce_kv_export(ids)
            packed = mirror_gather_full(
                self.k_cache, self.v_cache, np.asarray(ids, np.int32),
                self.config.block_size, self.mesh,
            )
            return seq_hashes[:n], packed
        n = len(plan)
        if n == 0:
            return [], np.zeros((0, *layout.packed_shape), layout.np_dtype)
        packed = np.zeros((n, *layout.packed_shape), layout.np_dtype)
        dev_rows = [i for i, (t, _) in enumerate(plan) if t == "dev"]
        if dev_rows:
            dev_data = self._kv_gather([plan[i][1] for i in dev_rows])
            for j, i in enumerate(dev_rows):
                packed[i] = dev_data[j]
        host_rows = [i for i, (t, _) in enumerate(plan) if t == "host"]
        if host_rows:
            assert self.kvbm is not None
            host_data = self.kvbm.host.read([plan[i][1] for i in host_rows])
            for j, i in enumerate(host_rows):
                packed[i] = host_data[j]
        return seq_hashes[:n], packed

    def _import_blocks(self, seq_hashes: list[int], packed: np.ndarray) -> int:
        """ENGINE THREAD. Land remote KV blocks in the host tier; the
        next admission onboards them into HBM (kvbm onboard()).

        Multihost: the full blocks broadcast to every process and each
        inserts ITS head slice into its shard pool (lockstep kept);
        onboarding then lifts them through the existing mirrored
        scatter."""
        if self.kvbm is None:
            raise RuntimeError("KV import requires host_kv_blocks > 0")
        if len(seq_hashes) > self.kvbm.host.num_blocks:
            # inserting would LRU-evict the delivery's own leading blocks,
            # silently voiding the remote prefill — reject instead
            raise RuntimeError(
                f"KV import of {len(seq_hashes)} blocks exceeds host tier "
                f"capacity {self.kvbm.host.num_blocks}"
            )
        if not hasattr(self.kvbm.host, "read"):
            # ShardedKvOffload: mirrored insert — every process slices
            # its own head range so the pools stay in lockstep
            from dynamo_tpu.parallel.multihost import local_head_rows

            assert self._mh_broadcast is not None
            self._mh_broadcast.announce_kv_import(seq_hashes, packed)
            self.kvbm.host.insert_many(
                seq_hashes, local_head_rows(packed, self.k_cache)
            )
            return len(seq_hashes)
        self.kvbm.host.insert_many(seq_hashes, packed)
        return len(seq_hashes)

    def refuse_kv_transfer(self) -> None:
        if self.model_config is not None and self.model_config.owns_pages:
            raise NotImplementedError(
                "KV block export/import (disaggregated transfer, fleet "
                "fabric) moves K/V pages only: a model whose family lays "
                "its pages out itself (latent rows, recurrent state "
                "beside its pages) is not supported"
            )

    async def export_kv_blocks(
        self, seq_hashes: list[int]
    ) -> tuple[list[int], np.ndarray]:
        self.refuse_kv_transfer()
        return await self.acall_on_thread(
            functools.partial(self._export_blocks, seq_hashes)
        )

    async def import_kv_blocks(self, seq_hashes: list[int], packed: np.ndarray) -> int:
        self.refuse_kv_transfer()
        return await self.acall_on_thread(
            functools.partial(self._import_blocks, seq_hashes, packed)
        )

    def match_cached_prefix(self, seq_hashes: list[int]) -> int:
        """Blocks resolvable without prefill (G1 + offload tiers). Safe to
        call from any thread (read-only dict lookups; advisory only)."""
        n = 0
        for h in seq_hashes:
            if self.allocator is not None and self.allocator.lookup_block(h) is not None:
                n += 1
            elif self.kvbm is not None and (
                self.kvbm.host.contains(h)
                or (self.kvbm.disk is not None and self.kvbm.disk.contains(h))
            ):
                n += 1
            else:
                break
        return n

    # -- step flight recording (telemetry/recorder.py) ---------------------
    _step_counter = 0
    _last_preemptions = 0

    def _update_pool_gauges(self) -> None:
        """KV-pool occupancy gauges from the allocator (refreshed per
        step AND per debug snapshot so /metrics and /debug/state agree
        on the same moment)."""
        alloc = self.allocator
        if alloc is None:
            return
        KV_POOL_BLOCKS_TOTAL.set(alloc.num_blocks - 1)
        KV_POOL_BLOCKS_ACTIVE.set(alloc.num_blocks - 1 - alloc.num_free)
        KV_POOL_CACHED_FREE_BLOCKS.set(alloc.num_cached_free)

    def _record_step(
        self, kind: str, duration_s: float,
        batch: int = 0, prefill_rows: int = 0, use_phases: bool = True,
        **extra,
    ) -> None:
        """One flight-recorder entry per device step: kind, batch
        composition, queue depth, per-phase latency (dispatch/sync from
        ``_last_phases``), preemption delta. Engine-thread only.

        ``use_phases=False`` for records whose dispatch did NOT go
        through ``_run_device_step`` (fused windows, spec) — merging
        ``_last_phases`` there would attribute a stale, unrelated
        dispatch's timings to this step.

        A slow-step/idle-gap watchdog dump triggers the black-box
        bundle."""
        sched = self.scheduler
        self._step_counter += 1
        self._update_pool_gauges()
        if self._step_counter % 32 == 0:
            try:
                self.hbm.refresh()
            except Exception:  # stats are advisory; never fail a step
                log.debug("hbm refresh failed", exc_info=True)
        phases, self._last_phases = self._last_phases, {}
        if sched is None:
            return
        pre = sched.preemptions
        fields = dict(
            batch=batch,
            prefill_rows=prefill_rows,
            running=sched.num_running,
            prefilling=len(sched.prefilling),
            queue_depth=sched.num_waiting,
            kv_free=self.allocator.num_free if self.allocator else 0,
            preemptions=pre - self._last_preemptions,
        )
        self._last_preemptions = pre
        if use_phases:
            fields.update(phases)
        fields.update(extra)
        dump = None
        if self.recorder is not None:
            dump = self.recorder.record(kind, duration_s, **fields)
        if dump is not None:
            # watchdog tripped (slow step or idle gap): preserve the
            # full forensic context, not just the ring
            self.blackbox.trigger(f"watchdog:{kind}")
        self._check_compile_fence(kind)
        self._check_transfer_fence(kind)

    def _check_compile_fence(self, kind: str) -> None:
        """Escalate serve-phase compiles the fence collected since the
        last step (DYN_COMPILE_FENCE, utils/compile_fence.py): ONE
        flight-recorder ``serve_compile`` record per drain — the events
        of a single unprewarmed signature coalesce instead of spamming
        the ring — plus a black-box bundle (its own rate limit applies)
        and a hard error under fatal mode."""
        if not compile_fence.enabled():
            return
        events, n_events = compile_fence.drain()
        if not n_events:
            return
        # n_events is the TRUE count; `events` holds at most the
        # fence's bounded detail window — a retrace storm past the
        # bound still counts in full
        COMPILE_FENCE_EVENTS.inc(n_events)
        total_s = sum(e["duration_ms"] for e in events) / 1e3
        summary = dict(
            compiles=n_events,
            event=events[0]["event"] if events else "<overflowed>",
            step_kind=kind,
        )
        if self.recorder is not None:
            # record() is watchdog-bearing; a mid-serve compile IS the
            # anomaly, so let a long one trip the slow-step dump too
            self.recorder.record("serve_compile", total_s, **summary)
        self.blackbox.trigger("serve_compile")
        log.warning(
            "compile fence: %d serve-phase compile event(s) during a "
            "%s step (first: %s, %.0f ms total) — an unprewarmed jit "
            "signature compiled mid-serve",
            n_events, kind, summary["event"], total_s * 1e3,
        )
        if compile_fence.fatal():
            raise compile_fence.CompileFenceError(
                f"serve-phase compile under DYN_COMPILE_FENCE=fatal: "
                f"{n_events} event(s), first {summary['event']!r} "
                f"during a {kind} step"
            )

    def _check_transfer_fence(self, kind: str) -> None:
        """Escalate serve-phase implicit transfers the fence collected
        (DYN_TRANSFER_FENCE, utils/transfer_fence.py), mirroring the
        compile fence: ONE flight-recorder ``serve_transfer`` record
        per drain, one black-box bundle (its own rate limit applies),
        one counter bump, and a hard error under fatal mode.  Runs from
        ``_record_step`` each step and directly from the step-loop
        handler when the guard's RuntimeError aborts a dispatch (the
        aborted step may never reach ``_record_step``)."""
        if not transfer_fence.enabled():
            return
        events, n_events = transfer_fence.drain()
        if not n_events:
            return
        TRANSFER_FENCE_EVENTS.inc(n_events)
        summary = dict(
            transfers=n_events,
            error=events[0]["error"] if events else "<overflowed>",
            step_kind=kind,
        )
        if self.recorder is not None:
            self.recorder.record("serve_transfer", 0.0, **summary)
        self.blackbox.trigger("serve_transfer")
        log.warning(
            "transfer fence: %d serve-phase implicit transfer(s) "
            "during a %s step (first: %s) — a host<->device sync "
            "outside the dispatch/harvest contract",
            n_events, kind, summary["error"],
        )
        if transfer_fence.fatal():
            raise transfer_fence.TransferFenceError(
                f"serve-phase implicit transfer under "
                f"DYN_TRANSFER_FENCE=fatal: {n_events} event(s), "
                f"first {summary['error']!r} during a {kind} step"
            )

    def _route(self, plan) -> str:
        """Which path steps ``plan`` (called under ``dyn.step.plan``: the
        per-row divert scans are planning, and the device waits them out
        like the rest of it)."""
        kind = plan.kind
        if kind == "idle":
            return "idle"
        if kind == "mixed":
            if self._mixed_step_fn is not None:
                return "mixed"
            plan.kind = kind = "prefill"  # no fused window: prefill this step
        seqs = plan.decode_seqs
        if kind == "decode" and seqs:
            if self._would_speculate(seqs):
                # overlapped speculative decode (the tentpole of
                # docs/speculative_decoding.md's pipelined section):
                # host drafting for step N+1 runs WHILE the device
                # verifies step N
                if self._overlap_ok() and not self._overlap_divert(seqs):
                    return "spec_pipeline"
                return "spec"
            if (
                self._multi_step_fn is None
                and self._overlap_ok()
                and not self._overlap_divert(seqs)
            ):
                # spec-suspended (degradation rung 2) and opted-out
                # batches reach here too: the overlapped plain pipeline IS
                # the literal plain-decode path (bit-identical to serial),
                # so the opt-out contract holds
                # overlapped single-step decode (docs/performance.md):
                # dispatch N+1 before harvesting N so the TPU never idles
                # for the host's plan+unpack time. --no-overlap restores
                # the serial loop.
                return "decode_pipeline"
        if (
            kind == "prefill"
            and self._multi_step_fn is not None
            and self._overlap_ok()
            and plan.prefill_batch
            and all(w.is_last_chunk for w in plan.prefill_batch)
        ):
            # cohort graduation without the hard sync: the prefill
            # dispatch's first tokens chain on device into the first
            # decode window (_window_pipeline prefill-only entry) —
            # multimodal/penalty/top-logprobs batches fall back to the
            # dedicated serial prefill inside the pipeline
            return "prefill_window"
        if kind != "prefill" and not seqs:
            return "none"
        return "serial"

    def _one_step(self) -> None:
        sched = self.scheduler
        assert sched is not None
        # injected device-step faults (docs/robustness.md): a delay here
        # models a straggling dispatch, an error exercises the
        # quarantine path, a kill is a worker death. No-op without a plan.
        faults.fire("engine.step")
        # clear BEFORE plan(): a failure inside planning must not be
        # attributed to the previous step's (healthy) requests
        self._last_plan = None
        with step_span("dyn.step.plan") as planning:
            plan = sched.plan()
            self._last_plan = plan  # step-failure attribution (quarantine)
            route = self._route(plan)
        plan_ms = planning.ms
        # phase stamps from an earlier, never-recorded dispatch (e.g. a
        # dedicated prefill inside the window pipeline) must not leak
        # into this step's record
        self._last_phases = {}
        # per-step load gauges: two locked float stores per step, noise
        # next to a device dispatch
        with step_span("dyn.step.record"):
            ENGINE_BATCH_OCCUPANCY.set(
                sched.num_running / max(1, self.config.max_batch_size)
            )
            ENGINE_QUEUE_DEPTH.set(sched.num_waiting)
        if route == "idle":
            # blocking sleep is deliberate: _one_step executes on the
            # dedicated "jax-engine" thread, never on the event loop
            self.step_clock.note_idle()
            with step_span("dyn.step.wait"):
                time.sleep(0.001)
            return
        if route == "mixed":
            t0 = time.monotonic()
            self._window_pipeline(
                plan.prefill_batch, plan.decode_seqs, rect=plan.rect
            )
            ENGINE_STEP_SECONDS.labels("mixed").observe(
                time.monotonic() - t0
            )
            return
        if route in ("spec_pipeline", "spec"):
            if route == "spec_pipeline":
                ran = self._spec_pipeline(plan.decode_seqs, plan_ms=plan_ms)
            else:
                ran = self._run_spec_step(plan.decode_seqs)
            if ran:
                # per-STEP latency histograms are observed inside the
                # step bodies (_run_spec_step / _finish_spec_record) —
                # one pipeline call drains many steps, so observing the
                # whole drain here would poison the spec p99
                return
            # no drafter had a proposal for any row: fall through to the
            # plain 1-token decode step — the [B, K+1] verify rectangle
            # would spend (K+1)x the attention/lm_head work to emit
            # exactly the same single token per sequence. Take ONE
            # serial step (not the plain pipeline, which would keep
            # speculation off for its whole drain) and retry drafting
            # at the next plan.
            route = "serial"
        if route == "decode_pipeline":
            self._decode_pipeline(plan.decode_seqs, plan_ms=plan_ms)
            return
        if route == "prefill_window":
            t0 = time.monotonic()
            self._window_pipeline(plan.prefill_batch, [])
            ENGINE_STEP_SECONDS.labels("prefill").observe(
                time.monotonic() - t0
            )
            return
        if route == "none":
            return
        with step_span("dyn.step.pack"):
            if plan.kind == "prefill":
                works = plan.prefill_batch
                assert works
                arrays = sched.build_prefill_batch_arrays(works)
                seqs = [w.seq for w in works]
            else:
                seqs = plan.decode_seqs
                arrays = sched.build_decode_arrays(seqs)

            B = arrays["tokens"].shape[0]
            sampling = self._batch_sampling(seqs, B)
            gmask = self._guided_allow_mask(seqs, B)
            if gmask is not None:
                # guided rows constrain the sampled token (prefill's
                # first token and every serial decode step); selects the
                # masked jit variant (prewarmed under
                # config.prewarm_guided)
                sampling.arrays["allow_mask"] = gmask

        if plan.kind == "decode" and self._multi_step_fn is not None:
            t0 = time.monotonic()
            self._window_pipeline([], seqs)
            ENGINE_STEP_SECONDS.labels("decode").observe(
                time.monotonic() - t0
            )
            return

        t_step = time.monotonic()
        need_sync = plan.kind != "prefill" or any(
            w.is_last_chunk for w in plan.prefill_batch
        )
        s_out = self._run_device_step(
            arrays, sampling, sync=need_sync,
            origin="prefill:" + ",".join(
                w.seq.request_id for w in plan.prefill_batch
            ) if plan.kind == "prefill" else "",
            kind=plan.kind,
        )
        if s_out is not None:
            next_tokens, logprobs = s_out[0], s_out[1]
            tops = s_out[2:] if len(s_out) > 2 else None
        else:
            next_tokens = logprobs = tops = None
        dt = time.monotonic() - t_step
        with step_span("dyn.step.record"):
            ENGINE_STEP_SECONDS.labels(plan.kind).observe(dt)
            self._record_step(
                plan.kind, dt,
                batch=len(seqs),
                prefill_rows=len(plan.prefill_batch),
                plan_ms=plan_ms,
                synced=need_sync,
            )

        def top_row(i):
            return (tops[0][i], tops[1][i]) if tops is not None else None

        with step_span("dyn.step.emit"):
            if plan.kind == "prefill":
                for i, work in enumerate(plan.prefill_batch):
                    sched.complete_prefill_chunk(work)
                    if work.is_last_chunk:
                        self._emit_token(
                            work.seq, int(next_tokens[i]),
                            float(logprobs[i]), top=top_row(i),
                        )
            else:
                for i, seq in enumerate(seqs):
                    if seq.state != SeqState.RUNNING:
                        continue
                    self._emit_token(
                        seq, int(next_tokens[i]), float(logprobs[i]),
                        top=top_row(i),
                    )

    # ------------------------------------------------------------------
    # Speculative decoding (dynamo_tpu/spec; docs/speculative_decoding.md)
    # ------------------------------------------------------------------
    def _seq_spec_enabled(self, seq: Sequence) -> bool:
        """Per-request opt-out: PreprocessedRequest.speculative=False
        turns speculation off for one request; None/True follow the
        engine default (a configured drafter)."""
        return (
            self._drafter is not None
            and getattr(seq.request, "speculative", None) is not False
        )

    def _would_speculate(self, seqs: list) -> bool:
        """Does ``_route`` send a decode batch of ``seqs`` down a
        speculative path now? Asked again by the plain decode pipeline
        before every dispatch, since it outlives the plan that chose
        it."""
        return (
            self._drafter is not None
            and not self.spec_suspended
            and not self._spec_divert(seqs)
        )

    def _spec_divert(self, seqs: list) -> bool:
        """Batches that must take the plain decode step instead of the
        verify step: penalty/bias/top-logprobs sampling rides
        separately-compiled step variants the verify path deliberately
        doesn't replicate, and ANY opted-out request diverts its whole
        batch — the opt-out contract is the LITERAL plain-decode path,
        and the verify step computes logits through the T>1 prefill
        attention kernel (different reduction/tiling order than the
        T==1 decode kernel: near-tie argmax can flip on TPU) and draws
        sampled tokens from a different seeded RNG stream than
        sample(). Riding along would approximate, not honor, the
        request."""
        return (
            self._wants_toplp(seqs)
            or any(s.request.sampling.needs_penalties for s in seqs)
            or any(s.request.sampling.logit_bias for s in seqs)
            or any(not self._seq_spec_enabled(s) for s in seqs)
        )

    def _run_spec_step(self, seqs: list, proposals=None) -> bool:
        """One speculative decode step: draft on host, verify on device,
        roll back rejected drafts. Returns False — with NOTHING staged
        and no dispatch made — when no sequence got a proposal, so the
        caller can run the plain decode step instead. ``proposals``
        (aligned with ``seqs``) skips the draft loop — the spec
        pipeline's block-pressure fallback already drafted this batch,
        and re-drafting would double both the host cost and the
        exposed-draft accounting.

        Contract with the rest of the engine (this is the part that
        changes the 1-token/seq/step assumption): each sequence emits
        1..spec_tokens+1 tokens through _emit_window — the SAME
        multi-token append path fused windows use, so stop conditions,
        max_tokens clamping, logprobs emission, prefix-cache block
        commits and SSE multi-token deltas all behave as they do for
        windows. Draft tokens are staged into seq.tokens for array
        building (scheduler.reserve_spec_tokens) and ALWAYS unwound
        after the device sync (TokenBlockSequence.unwind) before the
        verified tokens are appended — so host token state, generated
        counts and block content-addressing only ever see verified
        tokens, and blocks speculatively grown for draft KV stay
        uncommitted until real tokens fill them."""
        sched = self.scheduler
        assert sched is not None and self._spec_step_fn is not None
        assert self._drafter is not None
        S = self.config.spec_tokens + 1
        t_step = time.monotonic()
        draft_s = 0.0
        if proposals is None:
            # drafting is this step's planning: dyn.step.plan
            with step_span("dyn.step.plan") as drafting:
                proposals = []
                for seq in seqs:
                    # budget leaves room for the verify step's guaranteed
                    # +1 token: drafts past it would be discarded by
                    # _emit_window anyway, but their KV writes would still
                    # need blocks the growth reserve never budgeted
                    budget = self._spec_budget(seq)
                    props = (
                        self._draft_tokens(seq, budget)
                        if self._seq_spec_enabled(seq)
                        else []
                    )
                    if props and seq.guided_state is not None:
                        # guided spec: proposals filter through the SAME
                        # automaton the verify masks apply — a draft the
                        # mask would reject can never be proposed, so the
                        # accepted prefix is exactly what serial guided
                        # decode would have committed
                        props = seq.guided_state.filter_drafts(props)
                    proposals.append(props)
            # the draft-phase histogram covers PROPOSAL cost only (the
            # drafter-tuning signal) — staging/array/sampling prep
            # below is fixed per-step engine work, not drafter work
            draft_s = drafting.last_ns / 1e9
            SPEC_STEP_SECONDS.labels("draft").observe(draft_s)
            self.spec_draft_exposed_s_total += draft_s
        if not any(proposals):
            return False  # nothing staged: caller runs plain decode
        with step_span("dyn.step.pack"):
            works: list[tuple] = []
            staged = 0
            for seq, drafts in zip(seqs, proposals):
                # carry read BEFORE staging: reserve_spec_tokens appends
                # the drafts to token state, after which last_token() is
                # a draft
                carry = seq.tokens.last_token()
                k = sched.reserve_spec_tokens(seq, drafts) if drafts else 0
                staged += k
                works.append((seq, [carry] + drafts[:k]))
            if staged == 0:
                # block pressure shrank every row's kept drafts to zero:
                # rows are bare [carry] tokens, nothing was appended to
                # any sequence — bail to plain decode instead of paying
                # the (K+1)x rectangle to emit 1 token per sequence
                return False
            arrays = sched.build_spec_arrays(works, S)
            B = arrays["tokens"].shape[0]
            sampling = self._batch_sampling(seqs, B)
            gmask = self._guided_spec_masks(works, S, B)
            if gmask is not None:
                # [B, S, V] per-position masks: verify applies the
                # identical transform the serial masked path would at
                # each position
                sampling.arrays["allow_mask"] = gmask
        try:
            packed = self._dispatch_spec_step(arrays, sampling)
            # _harvest_spec_step is the spec path's designated harvest
            # point (DL010): the device->host sync happens inside it
            toks, lps, n_emit, sync_s = self._harvest_spec_step(packed, S)
        except Exception:
            # host token state must not keep staged (unverified) drafts
            # when the step dies — the quarantine retry would otherwise
            # replan with drafts baked into every sequence's history
            for seq, row in works:
                if len(row) > 1:
                    seq.tokens.unwind(len(row) - 1)
            raise
        # the verify wall = its dispatch phase + its harvest phase
        verify_s = self._last_phases["dispatch_ms"] / 1e3 + sync_s
        SPEC_STEP_SECONDS.labels("verify").observe(verify_s)
        proposed = sum(len(row) - 1 for _, row in works)
        accepted = int(sum(n_emit[i] - 1 for i in range(len(works))))
        with step_span("dyn.step.record"):
            self._record_step(
                "spec", draft_s + verify_s,
                batch=len(works),
                use_phases=False,  # draft/verify ms below ARE the phases
                draft_ms=round(draft_s * 1e3, 3),
                verify_ms=round(verify_s * 1e3, 3),
                spec_proposed=proposed,
                spec_accepted=accepted,
            )
            if proposed:
                SPEC_PROPOSED_TOKENS.labels(self._drafter.kind).inc(proposed)
                if accepted:
                    SPEC_ACCEPTED_TOKENS.labels(self._drafter.kind).inc(
                        accepted
                    )
                SPEC_ACCEPT_RATE.set(accepted / proposed)
                self.spec_proposed_total += proposed
                self.spec_accepted_total += accepted
        with step_span("dyn.step.emit"):
            for i, (seq, row) in enumerate(works):
                if len(row) > 1:
                    seq.tokens.unwind(len(row) - 1)  # rejected AND accepted
                    # drafts: the accepted prefix re-appends through
                    # append_token below so commits/penalty counts take
                    # the normal path
                if seq.state != SeqState.RUNNING:
                    continue
                n = int(n_emit[i])
                self._emit_window(seq, toks[i, :n], lps[i, :n])
        ENGINE_STEP_SECONDS.labels("spec").observe(time.monotonic() - t_step)
        return True

    def _spec_budget(self, seq: Sequence, lag: int = 0) -> int:
        """Draft budget for one sequence: spec_tokens, clamped to leave
        room for the verify step's guaranteed +1 token. ``lag`` shifts
        the clamp past tokens a harvested-but-not-yet-emitted step will
        add (the pipelined planner's view of ``generated``)."""
        budget = self.config.spec_tokens
        if seq.max_new_tokens is not None:
            budget = min(
                budget, max(0, seq.max_new_tokens - seq.generated - lag - 1)
            )
        return budget

    def _draft_tokens(self, seq: Sequence, budget: int, suffix=()) -> list:
        """Proposals for one sequence — through the per-sequence
        incremental n-gram index when the drafter provides one
        (``seq.drafter_state``; ``NgramDrafter.make_index``), the plain
        windowed ``propose`` otherwise. The index appends committed
        tokens as they arrive and rebuilds only when the sequence
        SHRANK (unwind/truncation) — the from-scratch tail scan was
        O(window) host work per row per step. ``suffix`` = tokens that
        will exist once in-flight emits apply (the pipeline's pre-draft
        and repair contexts) — proposals are computed as if they were
        appended, but token state and the index never see them."""
        d = self._drafter
        assert d is not None
        if budget <= 0:
            return []
        # cap the history the drafter sees (Drafter.window, None = all):
        # a full all_tokens() per sequence per step is O(context) host
        # work on the serialized engine thread
        window = getattr(d, "window", None)
        make = getattr(d, "make_index", None)
        if make is None or not window:
            hist = (
                seq.tokens.tail_tokens(window)
                if window
                else seq.tokens.all_tokens()
            )
            if suffix:
                hist = hist + [int(t) for t in suffix]
                if window:
                    hist = hist[-window:]
            return list(d.propose(hist, budget))[:budget]
        T = len(seq.tokens)
        idx = seq.drafter_state
        if idx is None or idx.seq_len > T:
            # first draft, or the sequence shrank: rebuild from the tail
            idx = make(seq.tokens.tail_tokens(window), T)
            seq.drafter_state = idx
        elif idx.seq_len < T:
            # append what was committed since the last draft (emitted
            # tokens only: the paths that call this never leave staged
            # drafts in token state at draft time)
            idx.extend(seq.tokens.tail_tokens(T - idx.seq_len))
        return list(idx.propose(budget, suffix))[:budget]

    def _dispatch_spec_step(
        self, arrays: dict[str, np.ndarray], sampling: SamplingBatch,
        tokens_dev=None,
    ):
        """DISPATCH half of the speculative verify step (the spec twin
        of ``_dispatch_device_step``): launch the jitted verify, swap
        the donated caches, and return the packed [B, 2S+1] DEVICE
        output — no host sync. ``tokens_dev`` feeds the chain_spec'd
        device token column (the pipelined signature); None feeds the
        host rectangle. Callers harvest via ``_harvest_spec_step``;
        between the two, the host is free to emit the previous step and
        pre-draft the next one while the device verifies this one."""
        assert self._spec_step_fn is not None
        with self._dispatch_span("spec", arrays["tokens"]) as dispatch:
            arrays, sampling = self._stage_step_inputs(arrays, sampling)
            idle_gap_s = self.overlap.note_dispatch()
            packed, self.k_cache, self.v_cache = self._spec_step_fn(
                self.params, self.k_cache, self.v_cache,
                arrays["tokens"] if tokens_dev is None else tokens_dev,
                arrays["positions"], arrays["slot_mapping"],
                arrays["block_tables"], arrays["context_lens"],
                arrays["draft_lens"], sampling.arrays,
            )
        self._newest_out = packed
        self._last_phases = {
            "dispatch_ms": dispatch.ms,
            "idle_gap_ms": round(idle_gap_s * 1e3, 3),
        }
        self._unsynced_steps.append("spec-verify")
        del self._unsynced_steps[:-8]  # bounded forensics window
        return packed

    def _harvest_spec_step(self, packed, S: int) -> tuple:
        """HARVEST half: the spec path's designated host-sync point
        (``harvest_spec_output`` does the one device->host read).
        Returns (toks, lps, n_emit, sync_s)."""
        from dynamo_tpu.spec.verify import harvest_spec_output

        with step_span("dyn.step.harvest") as harvest:
            toks, lps, n_emit = harvest_spec_output(packed, S)
        self.overlap.note_complete(all_prior=True)
        # successful host sync: earlier async dispatches are known-good
        # (in-order execution) — retire deferred-error forensics
        self._unsynced_steps.clear()
        return toks, lps, n_emit, harvest.last_ns / 1e9

    @staticmethod
    def _seq_dead(seq: Sequence) -> bool:
        """Late-detected stop: cancellation or deadline expiry observed
        after a step that includes the row went in flight."""
        return seq_gone(seq, time.monotonic())

    def _spec_predraft(self, works: list) -> list:
        """Optimistic pre-draft for the NEXT verify step, computed
        while the CURRENT one runs on device (the hidden half of the
        spec pipeline's draft cost): for each row, predict the bonus
        token with the drafter itself (suffix = this step's drafts),
        then propose the next draft run from the predicted full-accept
        tail — exactly the context the row realizes IF every draft is
        accepted and the bonus matches the prediction. Returns per-row
        ``(predicted_bonus, proposals)`` or None when the drafter has
        no prediction — those rows re-draft at harvest. Host-only:
        reads token state and the per-sequence index, mutates
        neither."""
        out = []
        for seq, drafts in works:
            pre = None
            if self._seq_spec_enabled(seq):
                guess = self._draft_tokens(seq, 1, suffix=drafts)
                if guess:
                    k = len(drafts)
                    # budget as serial would compute it at the realized
                    # state (generated advances by k+1 on full accept)
                    budget = self._spec_budget(seq, k + 1)
                    pre = (
                        guess[0],
                        self._draft_tokens(
                            seq, budget, suffix=list(drafts) + guess
                        ),
                    )
            out.append(pre)
        return out

    def _dispatch_spec_entry(
        self, nxt: dict, plan_ms: float, draft_ms: float, tokens_dev,
    ) -> dict:
        """Build sampling and dispatch one pipelined verify step from a
        ``plan_pipelined_spec`` result; returns the pipeline entry."""
        works = nxt["works"]
        B = nxt["arrays"]["context_lens"].shape[0]
        with step_span("dyn.step.pack"):
            sampling = self._batch_sampling(
                [s for s, _ in works], B, offset=nxt["offsets"]
            )
        packed = self._dispatch_spec_step(
            nxt["arrays"], sampling, tokens_dev=tokens_dev
        )
        return {
            "packed": packed,
            "works": works,
            "t_disp": time.monotonic(),
            "plan_ms": plan_ms,
            "draft_ms": draft_ms,
            # consumed by _finish_spec_record, not use_phases: at
            # record time _last_phases belongs to a LATER dispatch
            "phases": dict(self._last_phases),
        }

    def _emit_spec_entry(self, entry: dict, toks, lps, n_emit) -> bool:
        """Apply one harvested verify step to host state — the deferred
        emit, running while the NEXT step executes on device. Returns
        True when a late-detected stop DISCARDED a row's tokens (never
        appended, never content-addressed): the pipeline must then
        flush so the serial plan()'s reap frees the blocks with nothing
        in flight. Predicted finishes (max_tokens/model-len/block-cap)
        emit normally and do NOT flush — the next step excludes those
        rows, and nothing allocates until after its harvest, so their
        freed blocks cannot race its writes."""
        late = False
        proposed = accepted = 0
        for i, (seq, drafts) in enumerate(entry["works"]):
            if seq.state != SeqState.RUNNING:
                continue
            if self._seq_dead(seq):
                late = True
                continue
            # proposed counted ONLY for rows that emit: a discarded
            # row's drafts counting as proposals-without-acceptances
            # would bias accept_rate low vs the serial step's books
            n = int(n_emit[i])
            proposed += len(drafts)
            accepted += n - 1
            self._emit_window(seq, toks[i, :n], lps[i, :n])
        entry["proposed"] = proposed
        entry["accepted"] = accepted
        if proposed:
            SPEC_PROPOSED_TOKENS.labels(self._drafter.kind).inc(proposed)
            if accepted:
                SPEC_ACCEPTED_TOKENS.labels(self._drafter.kind).inc(accepted)
            SPEC_ACCEPT_RATE.set(accepted / proposed)
            self.spec_proposed_total += proposed
            self.spec_accepted_total += accepted
        return late

    def _finish_spec_record(self, entry: dict, sync_s: float) -> None:
        """Flight-recorder row for one pipelined spec step (kind
        "spec"): exposed draft/plan time rides ``plan_ms``, the harvest
        block is ``sync_ms``."""
        self.spec_pipeline_steps += 1
        tot = self.spec_draft_hidden_s_total + self.spec_draft_exposed_s_total
        if tot > 0:
            SPEC_DRAFT_HIDDEN_FRAC.set(
                self.spec_draft_hidden_s_total / tot
            )
        dt = time.monotonic() - entry["t_disp"]
        ENGINE_STEP_SECONDS.labels("spec").observe(dt)
        # the harvest block is the pipelined analogue of the serial
        # verify wall (device execution remainder when healthy)
        SPEC_STEP_SECONDS.labels("verify").observe(sync_s)
        self._record_step(
            "spec", dt,
            batch=len(entry["works"]),
            use_phases=False,  # per-entry stamps below
            plan_ms=entry["plan_ms"],
            draft_ms=entry["draft_ms"],
            sync_ms=round(sync_s * 1e3, 3),
            spec_proposed=entry.get("proposed", 0),
            spec_accepted=entry.get("accepted", 0),
            **entry["phases"],
        )

    def _spec_pipeline(self, seqs: list, plan_ms: float = 0.0) -> bool:
        """Overlapped speculative decode — spec (PR 3) composed with
        the decode pipeline's double-buffering (PR 7), ROADMAP item 2's
        biggest unplayed lever. The serial spec loop pays host drafting
        as device idle every step (draft -> dispatch -> harvest ->
        emit, fully serialized); here the host drafts and plans step
        N+1 WHILE the device runs step N's verify:

        - at dispatch of step N the host PRE-DRAFTS step N+1 from the
          *optimistic* all-accepted tail (history + N's drafts + the
          drafter's own prediction of the bonus token — exactly the
          post-N history IF every draft is accepted and the bonus
          matches). At high accept rates most rows realize that tail,
          and their next proposals are already in hand when N's result
          lands;
        - the harvest (the designated sync) reveals each row's realized
          tail; rows that diverged are RE-DRAFTED from the actual tail
          at harvest, so the proposal stream is byte-identical to the
          serial loop's and output stays bit-identical to serial spec —
          greedy AND seeded-sampled (the sampled realization depends on
          the proposals, so a cheaper drop-the-drafts repair would
          break it);
        - ``plan_pipelined_spec`` mirrors every ``should_finish``
          condition using the EXACT emitted counts, reserves blocks for
          the in-flight tokens (up to K+1 per row) plus the next draft
          run with rollback on ``NoBlocksError``, and never
          preempts/admits — any irregularity (arrivals the planner could
          admit: ``Scheduler.admission_work``; opt-outs,
          cancellation, deadline, block pressure, zero proposals)
          flushes back to the serial planner, the same divert
          discipline as ``_overlap_divert``;
        - step N's emit/bookkeeping (append_token, stop checks, block
          commits, SSE deltas) runs AFTER N+1 is dispatched, so the
          device-exposed host span between consecutive verifies is
          repair + plan only — the draft cost is hidden
          (``dynamo_spec_draft_hidden_frac`` reports how much);
        - the carry token chains ON DEVICE (``chain_spec``): column 0
          of N+1's rectangle gathers each row's last emitted token from
          N's packed output, so consecutive verifies exchange no token
          values through the host.

        Late-detected stops DISCARD the in-flight tokens for that row
        at emit and flush the pipeline so plan()'s reap runs with
        nothing in flight. Unlike the serial step, drafts are never
        staged into ``seq.tokens`` (array geometry comes from the
        planner's explicit lags), so a step failure leaves nothing to
        unwind and the quarantine retry replans from clean host state.

        Returns False — with NOTHING dispatched — when no row has a
        proposal, so the caller runs the plain step and retries
        drafting at the next plan."""
        sched = self.scheduler
        assert sched is not None and self._chain_spec_fn is not None
        S = self.config.spec_tokens + 1
        # first step: serial-style (exposed) draft over clean state;
        # drafting is planning (dyn.step.plan)
        with step_span("dyn.step.plan") as drafting:
            entries = []
            for seq in seqs:
                drafts = (
                    self._draft_tokens(seq, self._spec_budget(seq))
                    if self._seq_spec_enabled(seq)
                    else []
                )
                entries.append((seq, 0, drafts))
        draft_s = drafting.last_ns / 1e9
        SPEC_STEP_SECONDS.labels("draft").observe(draft_s)
        if not any(d for _, _, d in entries):
            return False  # nothing to verify: caller runs plain decode
        self.spec_draft_exposed_s_total += draft_s
        with step_span("dyn.step.plan") as planning:
            nxt = sched.plan_pipelined_spec(entries, S)
        if nxt is None:
            # block pressure or another irregularity at entry: the
            # serial spec step handles it (reserve_spec_tokens shrinks
            # draft runs instead of flushing) — identical to what a
            # serial-spec engine does at this state. Hand over the
            # proposals already drafted above rather than paying the
            # host scan twice.
            return self._run_spec_step(
                seqs, proposals=[d for _, _, d in entries]
            )
        if not any(d for _, d in nxt["works"]):
            return False  # clamping dropped every draft: plain step
        # first step chains from nothing: host carry column (the
        # prewarmed serial signature)
        with step_span("dyn.step.pack") as packing:
            arrays = nxt["arrays"]
            for i, (seq, _) in enumerate(nxt["works"]):
                arrays["tokens"][i, 0] = seq.tokens.last_token()
        entry = self._dispatch_spec_entry(
            nxt,
            plan_ms=plan_ms + round(
                (planning.last_ns + packing.last_ns) / 1e6, 3
            ),
            draft_ms=round(draft_s * 1e3, 3),
            tokens_dev=None,
        )
        while True:
            # one logical engine step per turn: the fault point must
            # see it (docs/robustness.md) — fired BEFORE the pre-draft,
            # so an injected error propagates with host state only
            # advanced through the last emit and the quarantine retry
            # recomputes the abandoned in-flight verify bit-identically
            faults.fire("engine.step")
            # ---- device busy: hide the next step's drafting ----
            with step_span("dyn.step.plan") as predrafting:
                pres = self._spec_predraft(entry["works"])
            predraft_s = predrafting.last_ns / 1e9
            SPEC_STEP_SECONDS.labels("predraft").observe(predraft_s)
            self.spec_draft_hidden_s_total += predraft_s
            with step_span("dyn.step.plan"):
                self._drain_incoming_only()
            # ---- harvest step N (the designated sync) ----
            toks, lps, n_emit, sync_s = self._harvest_spec_step(
                entry["packed"], S
            )
            # ---- repair + plan + dispatch N+1 (the exposed span) ----
            # the repair loop is the exposed DRAFT cost (pre-draft
            # misses re-proposing from the realized tail); the plan +
            # chain + dispatch below are exposed PLAN cost. The split
            # matters: draft_hidden_frac compares hidden vs exposed
            # *drafting* only — folding constant per-step plan time
            # into it would understate the hiding at high hit rates.
            with step_span("dyn.step.plan") as repairing:
                entries = []
                for i, (seq, drafts) in enumerate(entry["works"]):
                    n = int(n_emit[i])
                    emitted = [int(t) for t in toks[i, :n]]
                    pre = pres[i]
                    if (
                        pre is not None
                        and n == len(drafts) + 1
                        and emitted
                        and emitted[-1] == pre[0]
                    ):
                        nxt_drafts = pre[1]
                        self.spec_predraft_hits += 1
                    else:
                        # realized tail diverged from the optimistic
                        # one: re-draft from the actual tail so the
                        # proposal stream stays byte-identical to serial
                        # spec
                        nxt_drafts = self._draft_tokens(
                            seq, self._spec_budget(seq, n), suffix=emitted
                        )
                        self.spec_predraft_misses += 1
                    entries.append((seq, n, nxt_drafts))
            # read at once: the next plan phase is the same object
            exposed_ns = repairing.last_ns
            repair_s = exposed_ns / 1e9
            SPEC_STEP_SECONDS.labels("draft").observe(repair_s)
            self.spec_draft_exposed_s_total += repair_s
            with step_span("dyn.step.plan") as planning:
                flush = (
                    sched.admission_work()
                    or not self._running
                    # a drain must reach the serial loop's migrate
                    # sweep: the pipeline would otherwise hold its
                    # streams until they finish naturally, riding out
                    # the whole deadline
                    or self._draining
                    or not self._control.empty()
                    # degradation rung 2 (planner/degradation.py) flips
                    # spec_suspended from the loop thread: the serial
                    # loop honors it every plan, so the pipeline must
                    # not keep paying the verify rectangle for a whole
                    # batch drain
                    or self.spec_suspended
                )
                nxt = None if flush else sched.plan_pipelined_spec(entries, S)
            exposed_ns += planning.last_ns
            if nxt is not None and not any(d for _, d in nxt["works"]):
                # zero proposals across the batch: the [B, S] rectangle
                # would pay (K+1)x the work for 1 token/row — flush and
                # let the next plan take the plain step (no deadlock:
                # emit below still applies this step's tokens)
                nxt = None
            next_entry = None
            if nxt is not None:
                with step_span("dyn.step.pack") as chaining:
                    tokens_dev = self._chain_spec_fn(
                        entry["packed"], nxt["arrays"]["tokens"],
                        nxt["src_idx"],
                    )
                # the record's plan_ms carries the WHOLE exposed host
                # span (repair + plan + chain)
                next_entry = self._dispatch_spec_entry(
                    nxt,
                    plan_ms=round((exposed_ns + chaining.last_ns) / 1e6, 3),
                    draft_ms=round(repair_s * 1e3, 3),
                    tokens_dev=tokens_dev,
                )
            # ---- emit step N under N+1's device time ----
            with step_span("dyn.step.emit"):
                late_stop = self._emit_spec_entry(entry, toks, lps, n_emit)
            with step_span("dyn.step.record"):
                self._finish_spec_record(entry, sync_s)
            if next_entry is None:
                return True
            if late_stop:
                # a stop landed while N+1 was planned: its rows may
                # include the stopped sequence — harvest it, discard
                # dead rows' tokens, and return with nothing in flight
                # so the serial reap frees the blocks safely
                toks, lps, n_emit, sync_s = self._harvest_spec_step(
                    next_entry["packed"], S
                )
                with step_span("dyn.step.emit"):
                    self._emit_spec_entry(next_entry, toks, lps, n_emit)
                with step_span("dyn.step.record"):
                    self._finish_spec_record(next_entry, sync_s)
                return True
            entry = next_entry

    # ------------------------------------------------------------------
    # Overlapped single-step decode (docs/performance.md)
    # ------------------------------------------------------------------
    def _overlap_ok(self) -> bool:
        """The overlapped pipelines run single-host, pp=1, leader-less:
        the chained-dispatch announce protocol doesn't exist for
        followers, and the pp stage rotation keeps its serial step."""
        return (
            self.config.overlap
            and self._mh_broadcast is None
            and not self._is_follower
            and self._pp == 1
        )

    def _overlap_divert(self, seqs: list) -> bool:
        """Batches that must take the SERIAL step instead of the
        overlapped decode pipeline: penalty/bias generated-token counts
        live on host one step behind dispatch (a lagged count would
        change the sampled distribution), top-logprobs rides a
        separately-compiled step variant whose chained-token signature
        is deliberately not prewarmed (mirrors the window pipeline's
        penalties_in gate), and guided sequences FLUSH TO SERIAL by
        construction: step N+1's allow-mask is a function of step N's
        sampled token, so it cannot be known at N+1's dispatch time —
        the pipeline would have to dispatch with a stale mask
        (docs/guided_decoding.md "Divert conditions"). This covers the
        plain decode pipeline AND the overlapped spec pipeline (both
        gate on this predicate)."""
        return (
            self._wants_toplp(seqs)
            or any(s.request.sampling.needs_penalties for s in seqs)
            or any(s.request.sampling.logit_bias for s in seqs)
            or any(s.guided_state is not None for s in seqs)
        )

    # why _decode_pipeline emptied itself (``pipeline_drains.<reason>``)
    DRAIN_REASONS = (
        "unpredicted_finish", "admission", "blocks", "irregular", "control",
        "speculation",
    )

    def _decode_pipeline(self, seqs: list, plan_ms: float = 0.0) -> None:
        """Double-buffered single-step decode — the decode_steps == 1
        serving path restructured so the device never waits out the
        host's plan+unpack+emit time (ROADMAP item 2's host-side lever):

        - while device step N executes, the host plans AND dispatches
          step N+1, its token column chained ON DEVICE from N's sampled
          tokens (``chain_next``): per-step host->device traffic is the
          small position/slot/seed arrays only, and there is no host
          round trip between consecutive steps;
        - step N's packed [2B] output is harvested only after N+1 is in
          flight, so the hot-path sync waits on a result that is
          already (or nearly) done;
        - scheduler state (token appends, stop checks, block frees,
          prefix-cache commits) runs ONE STEP BEHIND dispatch.
          ``plan_pipelined_decode`` predicts every ``should_finish``
          condition a step ahead, so a row that finishes that way is a
          row of no step in flight when the harvest's ``finish()``
          frees its pages and its state slot: the pipeline GOES ON
          (``finishes_inline``). A finish no step in flight foresaw
          (EOS, a stop string) leaves the row in one; a token sampled
          past a late-detected stop (cancellation, deadline) is
          DISCARDED at harvest — never appended, never emitted, never
          content-addressed. Both flush the pipeline, so ``plan()``
          reaps with nothing in flight;
        - admission and prefill run IN LINE. Where the scheduler has
          work of that kind (``Scheduler.admission_work``: not a queue
          whose head the last ``_admit`` could not place — a saturated
          server keeps decoding), ``plan_pipelined_admission`` reaps
          the queue, admits and chooses the chunks as ``plan()`` would,
          and the rectangle is dispatched unsynced BEHIND the step in
          flight, an entry of kind ``prefill`` like any other; chunks
          go first, as in ``plan()``. The decode step after a prompt's
          last chunk is planned over the survivors plus that row, its
          first token joined on the device from the prefill's sampled
          column (``chain_join``; every other row's token is by then
          the host's). The entry's harvest emits the first token and
          moves the row to ``running``. The device runs the programs
          the serial loop would, in its order; only the host-made gaps
          between them go;
        - the pipeline NEVER preempts. What still empties it, each on
          something observed (``pipeline_drains.<reason>``): a finish
          or stop it did not foresee (``unpredicted_finish``); no page
          for the next step (``blocks``: the serial planner preempts
          with nothing in flight); a prefill batch that needs another
          compiled variant — multimodal embeddings, penalties, logit
          bias, top-logprobs, a guided mask (``irregular``); an
          admission that copies pages in from an offload tier
          (``admission``); a control call, shutdown or graceful drain
          (``control``); a batch that ``_route`` would now send down a
          speculative path — a drafter is configured and the row that
          opted out has left the batch, or ``spec_suspended`` went back
          (``speculation``).

        Greedy output is bit-identical to the serial loop (same step
        programs over the same values); sampled output draws the
        identical seed stream (seeds offset by the in-flight lag).
        """
        sched = self.scheduler
        assert sched is not None
        from collections import deque

        from dynamo_tpu.parallel.multihost import host_value

        lag: dict[int, int] = {}
        # the decode population as of the newest dispatch: the newest
        # decode step's rows, then each row whose last chunk a prefill
        # dispatched since was
        rows = seqs

        def dispatch(
            kind: str, seqs_, works, arrays, sampling, p_ms: float
        ) -> dict:
            t0 = time.monotonic()
            if kind == "decode":
                self._decode_dispatches[0] += 1
                self._count_decode(arrays)
            with self._dispatch_span(kind, arrays["tokens"]) as phase:
                outs = self._dispatch_device_step(
                    arrays, sampling,
                    origin="decode-pipeline" if kind == "decode" else
                    "prefill:" + ",".join(w.seq.request_id for w in works),
                )
                packed = self._pack_pair_fn(outs[0], outs[1])
            self._last_phases["dispatch_ms"] = phase.ms
            return {
                "kind": kind,
                "packed": packed,
                "toks": outs[0],  # device column the next step chains off
                "seqs": seqs_,
                "works": works,
                "b": arrays["context_lens"].shape[0],
                # the one token a decode row, or a prompt's last chunk,
                # adds (_lag_add)
                "vmap": {id(s): 1 for s in seqs_} | {
                    id(w.seq): 1 for w in works if w.is_last_chunk
                },
                "t_disp": t0,
                "plan_ms": p_ms,
                # consumed here, not by _record_step's use_phases: at
                # harvest time _last_phases belongs to a LATER dispatch
                "phases": dict(self._last_phases),
            }

        def dispatch_prefill(works, plan_ns: int) -> dict:
            with step_span("dyn.step.pack") as packing:
                arrays = sched.build_prefill_batch_arrays(works)
                sampling = self._batch_sampling(
                    [w.seq for w in works], arrays["tokens"].shape[0]
                )
            # a failure from here on is laid at these prompts' door
            # (_quarantine_step_failure), until their entry is harvested
            self._last_plan = StepPlan(
                kind="prefill", prefill_batch=works, decode_seqs=rows
            )
            self._count_prefill(arrays)
            self._inline["prefill_dispatches_inline"] += 1
            e = dispatch(
                "prefill", [], works, arrays, sampling,
                round((plan_ns + packing.last_ns) / 1e6, 3),
            )
            for w in works:
                if not w.is_last_chunk:
                    # as the serial loop does right after its unsynced
                    # dispatch: the prompt's next chunk is planned from it
                    sched.complete_prefill_chunk(w)
            return e

        def try_extend() -> str:
            """Plan and dispatch one more program behind the newest in
            flight. "" when it did; else why not: a drain reason, or
            "wait" / "done" (Scheduler.plan_pipelined_decode), which
            empty nothing."""
            nonlocal rows
            # each extension is one logical engine step: the fault point
            # (docs/robustness.md) must see it, or a whole decode inside
            # one _one_step call would evade per-step fault plans. Fired
            # BEFORE planning/allocation: an injected error propagates
            # with host state only advanced through the last harvest, so
            # the quarantine retry recomputes the abandoned in-flight
            # step bit-identically (KV slots rewritten with same values)
            faults.fire("engine.step")
            newest = pending[-1]
            works = None
            with step_span("dyn.step.plan") as planning:
                self._drain_incoming_only()
                if self._would_speculate(rows):
                    # the opted-out row ended, or speculation is no
                    # longer suspended: this batch is _route's to send
                    # down the spec path, not this pipeline's to keep
                    return "speculation"
                if sched.admission_work():
                    works, why = sched.plan_pipelined_admission(lag)
                    if works is None:
                        return why
                    batch = [w.seq for w in works]
                    if self._overlap_divert(batch) or any(
                        s.mm_segments for s in batch
                    ):
                        # a separately compiled variant, whose chained
                        # signatures nobody warmed: the serial loop's
                        return "irregular"
                if not works:
                    column = None if newest["kind"] == "decode" else {
                        id(w.seq): r for r, w in enumerate(newest["works"])
                        if w.is_last_chunk
                    }
                    nxt, why = sched.plan_pipelined_decode(rows, lag, column)
                    if nxt is None:
                        return why
            if works:
                e = dispatch_prefill(works, planning.last_ns)
                rows = rows + [w.seq for w in works if w.is_last_chunk]
            else:
                with step_span("dyn.step.pack") as packing:
                    arrays = nxt["arrays"]
                    if column is None:
                        arrays["tokens"] = self._chain_next_fn(
                            newest["toks"], nxt["src_idx"]
                        )
                    else:
                        arrays["tokens"] = self._chain_join_fn(
                            newest["toks"], arrays["tokens"], nxt["src_idx"]
                        )
                    sampling = self._batch_sampling(
                        nxt["seqs"],
                        arrays["context_lens"].shape[0],
                        offset=nxt["offsets"],
                    )
                self._decode_dispatches[1] += 1
                # the exposed host span before this dispatch: plan + pack
                e = dispatch(
                    "decode", nxt["seqs"], (), arrays, sampling,
                    round((planning.last_ns + packing.last_ns) / 1e6, 3),
                )
                rows = nxt["seqs"]
            _lag_add(lag, e)
            pending.append(e)
            return ""

        def harvest(e, depth: int) -> bool:
            """Apply one entry's tokens. True where that ended a row of
            a step still in flight, or found a row dead: flush."""
            t0 = time.monotonic()
            with step_span("dyn.step.harvest") as harvesting:
                packed_h = host_value(e["packed"])
            self.overlap.note_complete()
            self._unsynced_steps.clear()
            sync_ms = harvesting.ms
            B = e["b"]
            kind = e["kind"]
            ended: list = []
            late = False
            with step_span("dyn.step.emit"):
                toks = packed_h[:B].astype(np.int32)
                lps = packed_h[B : 2 * B]
                for i, work in enumerate(e["works"]):
                    seq = work.seq
                    if not work.is_last_chunk or seq.state != SeqState.PREFILL:
                        continue
                    sched.complete_prefill_chunk(work)
                    self._emit_token(seq, int(toks[i]), float(lps[i]))
                    if seq.state != SeqState.RUNNING:
                        ended.append(seq)
                for i, seq in enumerate(e["seqs"]):
                    if seq.state != SeqState.RUNNING:
                        continue
                    if self._seq_dead(seq):
                        # late-detected stop: DISCARD the in-flight token
                        # — nothing appended means nothing emitted and
                        # nothing the prefix cache could ever
                        # content-address
                        late = True
                        continue
                    self._emit_token(seq, int(toks[i]), float(lps[i]))
                    if seq.state != SeqState.RUNNING:
                        ended.append(seq)
                _lag_sub(lag, e)
                # the step's device outputs are freed HERE, under a phase
                # (tens of microseconds a step), not when this frame ends
                e["packed"] = e["toks"] = None
                if kind == "prefill":
                    self._last_plan = StepPlan(kind="decode", decode_seqs=rows)
                held = ended and {id(s) for p in pending for s in p["seqs"]}
                foreseen = not late and not any(id(s) in held for s in ended)
                if foreseen and pending:
                    # freed with steps in flight, none of which holds a
                    # row of them: what plan_pipelined_decode left out
                    self._inline["finishes_inline"] += len(ended)
            dt = time.monotonic() - e["t_disp"]
            n_rows = len(e["seqs"]) or len(e["works"])
            with step_span("dyn.step.record"):
                ENGINE_STEP_SECONDS.labels(kind).observe(dt)
                self._record_step(
                    kind, dt,
                    batch=n_rows,
                    prefill_rows=len(e["works"]),
                    use_phases=False,  # per-entry stamps below
                    plan_ms=e["plan_ms"],
                    sync_ms=sync_ms,
                    pipeline_depth=depth,
                    # host time this step ran UNDER (planning/dispatching
                    # N+1, emitting N-1) — the overlapped span
                    overlap_ms=round((t0 - e["t_disp"]) * 1e3, 3),
                    **e["phases"],
                )
            return not foreseen

        with step_span("dyn.step.pack"):
            arrays = sched.build_decode_arrays(seqs)
            sampling = self._batch_sampling(seqs, arrays["tokens"].shape[0])
        entry = dispatch("decode", seqs, (), arrays, sampling, plan_ms)
        _lag_add(lag, entry)
        pending = deque([entry])
        drain = ""  # why the pipeline stopped growing, once it has
        while pending:
            # extend BEFORE harvesting. What the last harvest freed was
            # a row's of no step in flight (or the pipeline is flushing),
            # so planning here never hands out a page or a state slot an
            # in-flight step writes. _running/_draining/_control:
            # shutdown and engine-thread calls flush rather than starve.
            while not drain and len(pending) < self.PIPELINE_DEPTH:
                if not (
                    self._running
                    and not self._draining
                    and self._control.empty()
                ):
                    drain = "control"
                    break
                why = try_extend()
                if why:
                    # "wait" / "done": nothing to plan until a harvest
                    drain = why if why in self.DRAIN_REASONS else ""
                    break
            if harvest(pending.popleft(), depth=len(pending) + 1) and pending:
                # a stop nobody foresaw, with a step in flight that
                # holds the row: flush, so that plan() reaps and admits
                # with nothing in flight
                drain = drain or "unpredicted_finish"
        if drain:
            self._pipeline_drains[drain] += 1

    def _batch_sampling(
        self, seqs: list, B: int, offset=0
    ) -> SamplingBatch:
        """Per-slot sampling params; ``offset`` (int, or per-seq list)
        advances the per-step seeds past tokens of an in-flight (not
        yet host-applied) window."""
        opts = [s.request.sampling.normalized() for s in seqs]
        pad = B - len(seqs)
        offs = offset if isinstance(offset, list) else [offset] * len(seqs)
        seeds = []
        for s, off in zip(seqs, offs):
            base = s.request.sampling.seed
            if base is None:
                # crc32, NOT hash(): Python's str hash is SipHash-salted
                # per process, and the unseeded base must be identical
                # on whichever worker serves (or RESUMES) the request
                base = zlib.crc32(s.request_id.encode()) & 0x7FFFFFFF
            # resume_offset: a migrated request's RNG stream continues
            # where the dead worker's delivery stopped (the request_id —
            # hence the unseeded base — survives migration unchanged),
            # so the continuation draws the same per-position samples
            # the original stream would have (docs/robustness.md)
            seeds.append(
                base + s.generated + s.request.resume_offset + off
            )
        seeds += [0] * pad
        gen_counts = prompt_ids = None
        if any(o.needs_penalties for o in opts):
            # sparse per-seq token state for the penalty path: generated
            # counts (freq/pres/rep) and distinct prompt ids (rep,
            # cached on the sequence — prompts are immutable)
            gen_counts = [dict(s.gen_counts) for s in seqs]
            for s in seqs:
                if s.prompt_unique is None:
                    # request.token_ids is a host python list; cached
                    # once per sequence, no device array involved
                    s.prompt_unique = np.unique(
                        np.asarray(s.request.token_ids, np.int32)  # dynalint: disable=transitive-host-sync-in-step-loop — host-list conversion
                    )
            prompt_ids = [s.prompt_unique for s in seqs]
            gen_counts += [{} for _ in range(pad)]
            prompt_ids += [np.zeros((0,), np.int32)] * pad
        opts += [opts[-1]] * pad
        top_lp = None
        if self._wants_toplp(seqs):
            top_lp = [
                (s.request.output.logprobs or 0) for s in seqs
            ] + [0] * pad
        return SamplingBatch.from_options(
            opts, seeds, gen_counts, prompt_ids, top_lp
        )

    # ------------------------------------------------------------------
    # Guided decoding (dynamo_tpu/guided; docs/guided_decoding.md)
    # ------------------------------------------------------------------
    def _guided_automaton(self, spec):
        """Resolve a request's guided spec to a TokenAutomaton through
        the process-wide compile LRU (submit thread: a compile or a
        tokenizer load never stalls the step loop)."""
        from dynamo_tpu.guided import automaton_for

        if self._guided_tokenizer is None:
            from dynamo_tpu.tokenizer import Tokenizer

            self._guided_tokenizer = Tokenizer.from_file(
                self.config.model_path
            )
        mc = self.model_config
        assert mc is not None
        eos = set(mc.eos_token_ids) | set(self.eos_token_ids)
        return automaton_for(
            spec,
            self._guided_tokenizer,
            self.config.model_path or self.config.model_name,
            mc.vocab_size,
            eos,
        )

    def _guided_allow_mask(
        self, seqs: list, B: int
    ) -> Optional[np.ndarray]:
        """[B, V_pad] bool allow-mask for a serial prefill/decode batch,
        or None when no sequence is guided. Unguided (and pad) rows are
        all-True — the mask variant constrains only the rows that asked
        for it. Pure host work over cached per-state masks (no device
        arrays; DL010-clean)."""
        if not any(s.guided_state is not None for s in seqs):
            return None
        assert self.model_config is not None
        m = np.ones((B, self.model_config.vocab_size), dtype=bool)
        for i, s in enumerate(seqs):
            if s.guided_state is not None:
                m[i] = s.guided_state.allow_mask()
        return m

    def _guided_spec_masks(
        self, works: list, S: int, B: int
    ) -> Optional[np.ndarray]:
        """[B, S, V_pad] per-position masks for a spec verify batch
        (``works`` rows are (seq, [carry] + kept_drafts)), or None when
        no row is guided. Position j of a guided row is the automaton
        state after its first j drafts commit — the SAME mask sequence
        the serial path would apply step by step, which is what makes
        guided speculative verification exact. Positions past a row's
        kept drafts (never emitted) and unguided rows stay all-True."""
        if not any(seq.guided_state is not None for seq, _ in works):
            return None
        assert self.model_config is not None
        V = self.model_config.vocab_size
        m = np.ones((B, S, V), dtype=bool)
        for i, (seq, row) in enumerate(works):
            gs = seq.guided_state
            if gs is None:
                continue
            m[i, : len(row)] = gs.masks_for_drafts(row[1:])
        return m

    def _dispatch_multi_step(
        self,
        arrays: dict[str, np.ndarray],
        sampling: SamplingBatch,
        tokens_dev=None,
    ):
        """Launch one fused window; returns DEVICE (toks, lps) [B, K] —
        callers sync when they need values, so the next window can be
        dispatched underneath. ``tokens_dev`` chains the previous
        window's device-resident last-token column (no host hop)."""
        assert self._multi_step_fn is not None
        if self._mh_broadcast is not None:
            self._mh_broadcast.announce_multi_step(arrays, sampling)
        # stage AFTER the announce: followers deserialize host numpy
        arrays, sampling = self._stage_step_inputs(arrays, sampling)
        self.overlap.note_dispatch()
        packed, last_tok, self.k_cache, self.v_cache = self._multi_step_fn(
            self.params,
            self.k_cache,
            self.v_cache,
            arrays["tokens"] if tokens_dev is None else tokens_dev,
            arrays["positions"],
            arrays["block_tables"],
            arrays["context_lens"],
            arrays["valid_steps"],
            sampling.arrays,
        )
        self._newest_out = last_tok
        return packed, last_tok

    @staticmethod
    def _unpack_window(
        packed_host: np.ndarray, toplp: bool = False
    ) -> tuple[np.ndarray, ...]:
        """Split a window's packed [B, cols] output: (toks [B,K],
        lps [B,K]) base; with ``toplp`` additionally (top_ids [B,K,N]
        i32, top_lps [B,K,N]) — layout set by decode_window."""
        from dynamo_tpu.engine.sampling import TOPLP_N

        B = packed_host.shape[0]
        if not toplp:
            K = packed_host.shape[1] // 2
            return packed_host[:, :K].astype(np.int32), packed_host[:, K:]
        K = packed_host.shape[1] // (2 + 2 * TOPLP_N)
        toks = packed_host[:, :K].astype(np.int32)
        lps = packed_host[:, K : 2 * K]
        tids = packed_host[:, 2 * K : 2 * K + K * TOPLP_N].reshape(
            B, K, TOPLP_N
        ).astype(np.int32)
        tlps = packed_host[:, 2 * K + K * TOPLP_N :].reshape(B, K, TOPLP_N)
        return toks, lps, tids, tlps

    @staticmethod
    def _wants_toplp(seqs: list) -> bool:
        return any((s.request.output.logprobs or 0) > 0 for s in seqs)

    def _pad_prefill_rect(
        self, arrays: dict[str, np.ndarray], P: int, T: int, width: int
    ) -> dict[str, np.ndarray]:
        """Pad bucketed prefill arrays up to the mixed step's FIXED
        [P, T] rectangle (and ``width``-wide block tables). Pad rows
        write to the reserved garbage slot 0 and have ctx 0, exactly
        like batch-bucket padding."""
        B0, T0 = arrays["tokens"].shape
        w0 = arrays["block_tables"].shape[1]
        out = {
            "tokens": np.zeros((P, T), np.int32),
            "positions": np.zeros((P, T), np.int32),
            "slot_mapping": np.zeros((P * T,), np.int32),
            "block_tables": np.zeros((P, width), np.int32),
            "context_lens": np.zeros((P,), np.int32),
            "last_token_idx": np.zeros((P,), np.int32),
        }
        out["tokens"][:B0, :T0] = arrays["tokens"]
        out["positions"][:B0, :T0] = arrays["positions"]
        out["slot_mapping"].reshape(P, T)[:B0, :T0] = arrays[
            "slot_mapping"
        ].reshape(B0, T0)
        out["block_tables"][:B0] = self.scheduler.widen_tables(
            arrays["block_tables"], width
        )
        out["context_lens"][:B0] = arrays["context_lens"]
        out["last_token_idx"][:B0] = arrays["last_token_idx"]
        return out

    def _dispatch_mixed(
        self,
        works: list,
        seqs: list,
        p_arrays: dict[str, np.ndarray],
        d_arrays: dict[str, np.ndarray],
        sampling_p: SamplingBatch,
        sampling_d: SamplingBatch,
        tokens_dev=None,
        rect: Optional[tuple[int, int]] = None,
    ):
        """Launch one mixed window; returns device (flat, last_tok,
        p_next) — callers sync `flat` when they need values."""
        assert self._mixed_step_fn is not None
        P, T = rect or (
            self.config.mixed_prefill_rows, self.config.mixed_prefill_len
        )
        width = max(
            p_arrays["block_tables"].shape[1],
            d_arrays["block_tables"].shape[1],
        )
        p_pad = self._pad_prefill_rect(p_arrays, P, T, width)
        self._count_prefill(p_pad)
        d_arrays["block_tables"] = self.scheduler.widen_tables(
            d_arrays["block_tables"], width
        )
        if self._mh_broadcast is not None:
            self._mh_broadcast.announce_mixed(
                p_pad, sampling_p, d_arrays, sampling_d
            )
        # stage AFTER the announce: followers deserialize host numpy.
        # d_arrays' row count is read below, so keep the staged copy
        # separate from the host dict the caller may still hold.
        B_d = d_arrays["tokens"].shape[0]
        p_pad, sampling_p = self._stage_step_inputs(p_pad, sampling_p)
        d_staged, sampling_d = self._stage_step_inputs(d_arrays, sampling_d)
        self.overlap.note_dispatch()
        flat, last_tok, p_next, self.k_cache, self.v_cache = (
            self._mixed_step_fn(
                self.params,
                self.k_cache,
                self.v_cache,
                p_pad["tokens"],
                p_pad["positions"],
                p_pad["slot_mapping"],
                p_pad["block_tables"],
                p_pad["context_lens"],
                p_pad["last_token_idx"],
                sampling_p.arrays,
                d_staged["tokens"] if tokens_dev is None else tokens_dev,
                d_staged["positions"],
                d_staged["block_tables"],
                d_staged["context_lens"],
                d_staged["valid_steps"],
                sampling_d.arrays,
            )
        )
        self._newest_out = last_tok
        return flat, last_tok, p_next, B_d, P

    def _emit_mixed(
        self, works: list, seqs: list, flat_h, B: int,
        P: Optional[int] = None,
    ) -> None:
        """Sync-side bookkeeping of one mixed window's flat output.
        ``P`` = the window's prefill-rectangle row count (narrow
        default, or the wide rect's). Mixed windows never carry the
        top-logprobs variant (the window pipeline diverts toplp batches
        to dedicated prefill + pure windows), so the flat layout is
        always the base one."""
        sched = self.scheduler
        assert sched is not None
        assert not (
            self._wants_toplp(seqs)
            or self._wants_toplp([w.seq for w in works])
        ), "top-logprobs batch reached the mixed step"
        K = sched.decode_lookahead
        if P is None:
            P = self.config.mixed_prefill_rows
        tok_m, lp_m = self._unpack_window(
            flat_h[: B * 2 * K].reshape(B, 2 * K)
        )
        p_next_h = flat_h[B * 2 * K : B * 2 * K + P].astype(np.int32)
        p_lp_h = flat_h[B * 2 * K + P :]
        for i, work in enumerate(works):
            sched.complete_prefill_chunk(work)
            if work.is_last_chunk:
                self._emit_token(work.seq, int(p_next_h[i]), float(p_lp_h[i]))
        for i, seq in enumerate(seqs):
            self._emit_window(seq, tok_m[i], lp_m[i])

    def _drain_incoming_only(self) -> None:
        """Drain ONLY the submit queue (not the control queue) — used
        inside the window pipeline, where control calls (KV export /
        import) must NOT run against host state that lags the in-flight
        window by up to K tokens."""
        assert self.scheduler is not None
        while True:
            try:
                item = self._incoming.get_nowait()
            except thread_queue.Empty:
                return
            self.scheduler.add_request(item)

    # in-flight windows: 2 hides each window's host transfer behind
    # the next window's compute; depth 1 trades throughput for one
    # window less first-token latency. Not measured on the attached
    # chip. Set via DYN_PIPELINE_DEPTH
    # (read at engine construction; see __init__).
    PIPELINE_DEPTH = 2

    def _window_pipeline(
        self, works: list, seqs: list,
        rect: Optional[tuple[int, int]] = None,
    ) -> None:
        """THE serving loop: fused decode windows with optional prefill
        rectangles, PIPELINED to depth 2. While windows k and k+1 run
        on device, the host plans window k+2 — last-chunk prefills
        GRADUATE to decode rows of the following window, their first
        token chained on device from that window's outputs
        (scheduler.plan_pipelined_mixed + chain_tokens); new arrivals
        are admitted straight into the next rectangle; sequences
        finishing inside in-flight windows simply aren't rows of later
        ones. Per-sequence ``lag`` (sampled-but-unapplied tokens across
        all in-flight windows) drives positions/budgets. Multihost
        leaders pipeline too: chained windows send a KIND_CHAIN
        pre-announcement so followers derive the token column from
        their own device outputs (parallel/multihost.py). Any
        irregularity (stop-token finishes, cancellations, multimodal,
        penalties, control-plane calls, shutdown) flushes
        the pipeline: in-flight windows are synced in order, surviving
        sequences keep their tokens, finished ones discard theirs
        (their blocks stay allocated until the flush, so no reuse races
        in-flight writes). Multimodal prefill chunks fall back to a
        dedicated step — embedding injection doesn't ride the fixed
        rectangle."""
        sched = self.scheduler
        assert sched is not None
        from collections import deque

        from dynamo_tpu.parallel.multihost import host_value

        # multihost included: pipelined windows broadcast a KIND_CHAIN
        # pre-announcement so followers derive the token column from
        # their own retained device outputs (parallel/multihost.py)
        pipelining = True
        lag: dict[int, int] = {}

        def penalties_in(ws: list, ss: list) -> bool:
            # penalties, top-logprobs and logit-bias all flush/block the
            # pipeline: their windows run separately-compiled variants
            # whose chained-dispatch signatures aren't prewarmed
            return (
                any(w.seq.request.sampling.needs_penalties for w in ws)
                or any(s.request.sampling.needs_penalties for s in ss)
                or any(w.seq.request.sampling.logit_bias for w in ws)
                or any(s.request.sampling.logit_bias for s in ss)
                or self._wants_toplp([w.seq for w in ws])
                or self._wants_toplp(ss)
            )

        def make_entry(out, works_, seqs_, vmap: dict) -> dict:
            """One pipeline entry; the lag invariant (vmap = tokens this
            window adds per sequence, incl. +1 per graduating last
            chunk) lives HERE and nowhere else."""
            if out[0] == "pure":
                e = {"kind": "pure", "flat": out[1], "last": out[2],
                     "b": out[3]}
            elif out[0] == "prefill":
                # prefill-only cohort entry (overlap path): no decode
                # rows; the sampled first tokens chain on device into
                # the NEXT window via chain_next (try_extend)
                e = {"kind": "prefill", "packed": out[1], "p_next": out[2],
                     "p_rows": out[3], "b": 0}
            else:
                e = {"kind": "mixed", "flat": out[1], "last": out[2],
                     "p_next": out[3], "b": out[4], "p_rows": out[5]}
            e["works"] = works_
            e["seqs"] = seqs_
            e["vmap"] = dict(vmap)
            for w in works_:
                if w.is_last_chunk:
                    e["vmap"][id(w.seq)] = e["vmap"].get(id(w.seq), 0) + 1
            # overlap phase stamps for this entry's flight-recorder row
            e["t_disp"] = time.monotonic()
            e["idle_gap_ms"] = round(self.overlap.last_idle_gap_s * 1e3, 3)
            return e

        # dispatch the first window
        if works:
            with step_span("dyn.step.pack"):
                p_arrays = sched.build_prefill_batch_arrays(works, within=rect)
            # Multimodal chunks, top-logprobs AND penalty/bias batches
            # take a dedicated prefill step instead of the mixed
            # rectangle: embedding injection doesn't ride the fixed
            # rectangle, and the mixed jit variants for those sampling
            # features are deliberately NOT part of the prewarm set
            # (the opt-in prewarms cover dedicated prefill + pure
            # windows; an unwarmed variant is a mid-serve compile of the
            # whole step). Decode follows on the next
            # plan.
            if "extra_embeds" in p_arrays or penalties_in(works, seqs):
                with step_span("dyn.step.pack"):
                    sampling = self._batch_sampling(
                        [w.seq for w in works], p_arrays["tokens"].shape[0]
                    )
                s_out = self._run_device_step(
                    p_arrays, sampling,
                    sync=any(w.is_last_chunk for w in works),
                    origin="prefill:" + ",".join(
                        w.seq.request_id for w in works
                    ),
                )
                with step_span("dyn.step.emit"):
                    for i, work in enumerate(works):
                        sched.complete_prefill_chunk(work)
                        if work.is_last_chunk:
                            top = (
                                (s_out[2][i], s_out[3][i])
                                if len(s_out) > 2
                                else None
                            )
                            self._emit_token(
                                work.seq, int(s_out[0][i]),
                                float(s_out[1][i]), top=top,
                            )
                return
            if not seqs:
                # prefill-only first entry (overlapped cohort
                # graduation, _one_step): dispatch the cohort WITHOUT a
                # hard sync — try_extend chains its sampled first
                # tokens on device into the first decode window, so the
                # prefill->decode boundary costs no host round trip
                assert all(w.is_last_chunk for w in works)
                with step_span("dyn.step.pack"):
                    sampling_p = self._batch_sampling(
                        [w.seq for w in works], p_arrays["tokens"].shape[0]
                    )
                self._count_prefill(p_arrays)
                with self._dispatch_span("prefill", p_arrays["tokens"]):
                    outs = self._dispatch_device_step(
                        p_arrays, sampling_p,
                        origin="prefill:" + ",".join(
                            w.seq.request_id for w in works
                        ),
                    )
                    out = (
                        "prefill",
                        self._pack_pair_fn(outs[0], outs[1]),
                        outs[0],
                        p_arrays["tokens"].shape[0],
                    )
                d_arrays = None
            else:
                with step_span("dyn.step.pack"):
                    d_arrays = sched.build_decode_arrays(seqs)
                    p_rows = (rect or (self.config.mixed_prefill_rows, 0))[0]
                    sampling_p = self._batch_sampling(
                        [w.seq for w in works], p_rows
                    )
                    sampling_d = self._batch_sampling(
                        seqs, d_arrays["tokens"].shape[0]
                    )
                pipelining = pipelining and not (
                    sampling_p.has_penalties or sampling_d.has_penalties
                    or sampling_p.has_toplp or sampling_d.has_toplp
                    or sampling_p.has_bias or sampling_d.has_bias
                )
                with self._dispatch_span("mixed", d_arrays["tokens"]):
                    out = ("mixed",) + self._dispatch_mixed(
                        works, seqs, p_arrays, d_arrays, sampling_p,
                        sampling_d, rect=rect,
                    )
        else:
            with step_span("dyn.step.pack"):
                d_arrays = sched.build_decode_arrays(seqs)
                sampling_d = self._batch_sampling(
                    seqs, d_arrays["tokens"].shape[0]
                )
            pipelining = pipelining and not (
                sampling_d.has_penalties or sampling_d.has_toplp
                or sampling_d.has_bias
            )
            with self._dispatch_span("window", d_arrays["tokens"]):
                out = ("pure",) + self._dispatch_multi_step(
                    d_arrays, sampling_d
                ) + (d_arrays["tokens"].shape[0],)
        vmap0 = (
            {id(s): int(d_arrays["valid_steps"][i])
             for i, s in enumerate(seqs)}
            if d_arrays is not None
            else {}
        )
        entry = make_entry(out, works, seqs, vmap0)
        _lag_add(lag, entry)
        pending = deque([entry])

        def harvest_entry(e) -> None:
            t0 = time.monotonic()
            with step_span("dyn.step.harvest") as harvesting:
                host = host_value(
                    e["packed"] if e["kind"] == "prefill" else e["flat"]
                )
            with step_span("dyn.step.emit") as emitting:
                if e["kind"] == "prefill":
                    # cohort-graduation entry: one packed [2P] transfer
                    # carrying first tokens + logprobs; the decode window
                    # chained off them is already in flight behind it
                    P = e["p_rows"]
                    p_next_h = host[:P].astype(np.int32)
                    p_lp_h = host[P : 2 * P]
                    for i, work in enumerate(e["works"]):
                        sched.complete_prefill_chunk(work)
                        if work.is_last_chunk:
                            self._emit_token(
                                work.seq, int(p_next_h[i]), float(p_lp_h[i])
                            )
                elif e["kind"] == "mixed":
                    self._emit_mixed(
                        e["works"], e["seqs"], host, e["b"], P=e["p_rows"],
                    )
                else:
                    tlp = self._wants_toplp(e["seqs"])
                    win = self._unpack_window(host, tlp)
                    for i, seq in enumerate(e["seqs"]):
                        tops = (win[2][i], win[3][i]) if tlp else None
                        self._emit_window(
                            seq, win[0][i], win[1][i], tops=tops
                        )
                # the window's device outputs are freed HERE, under a
                # phase, not when this frame ends
                for key in ("flat", "last", "p_next", "packed"):
                    e.pop(key, None)
            self.overlap.note_complete()
            # window sync succeeded: earlier async dispatches are
            # known-good (in-order execution) — retire deferred-error
            # forensics
            self._unsynced_steps.clear()
            _lag_sub(lag, e)
            win_s = (harvesting.last_ns + emitting.last_ns) / 1e9
            # one flight-recorder entry per WINDOW (the serving-path
            # unit of work): duration is the host-side sync+emit wait —
            # the dispatch overlapped earlier windows by design
            with step_span("dyn.step.record"):
                self._record_step(
                    "window_" + e["kind"], win_s,
                    batch=len(e["seqs"]),
                    prefill_rows=len(e["works"]),
                    pipeline_depth=len(pending),
                    use_phases=False,  # dispatched via the window fns,
                    # not _run_device_step — its phase stamps belong
                    # elsewhere. Overlap phase stamps
                    # (telemetry/overlap.py): the span this window ran
                    # under other host work, and the device idle gap that
                    # preceded its dispatch
                    overlap_ms=round((t0 - e["t_disp"]) * 1e3, 3),
                    idle_gap_ms=e["idle_gap_ms"],
                )

        def try_extend() -> bool:
            """Plan + dispatch one more window chained off the newest
            in-flight one. False = the pipeline can't grow further."""
            newest = pending[-1]
            with step_span("dyn.step.plan"):
                self._drain_incoming_only()
                nxt = sched.plan_pipelined_mixed(
                    newest["seqs"], newest["works"], lag,
                    # a prefill-only entry's token vector is the prefill
                    # rows alone — graduated row r chains from index r
                    grad_base=0 if newest["kind"] == "prefill" else None,
                )
            if nxt is None or penalties_in(nxt["works2"], nxt["seqs"]):
                return False
            p2 = None
            if nxt["works2"]:
                with step_span("dyn.step.pack"):
                    p2 = sched.build_prefill_batch_arrays(
                        nxt["works2"], within=nxt["rect"]
                    )
                if "extra_embeds" in p2:
                    return False  # multimodal never rides the pipeline
            if self._mh_broadcast is not None:
                # multihost pipelining: followers chain the SAME token
                # column from their own retained device outputs — the
                # next announce's host token values are placeholders.
                # (prefill-only entries exist only single-host:
                # _one_step gates them on _overlap_ok)
                assert newest["kind"] != "prefill"
                self._mh_broadcast.announce_chain(
                    nxt["src_idx"], newest["kind"] == "mixed"
                )
            with step_span("dyn.step.pack"):
                if newest["kind"] == "prefill":
                    chained = self._chain_next_fn(
                        newest["p_next"], nxt["src_idx"]
                    )
                elif newest["kind"] == "mixed":
                    chained = self._chain_fn(
                        newest["last"], newest["p_next"], nxt["src_idx"]
                    )
                else:
                    chained = self._chain_pure_fn(
                        newest["last"], nxt["src_idx"]
                    )
                s_d2 = self._batch_sampling(
                    nxt["seqs"],
                    nxt["arrays"]["tokens"].shape[0],
                    offset=nxt["offsets"],
                )
                if p2 is not None:
                    s_p2 = self._batch_sampling(
                        [w.seq for w in nxt["works2"]], nxt["rect"][0]
                    )
            if p2 is not None:
                with self._dispatch_span("mixed", nxt["arrays"]["tokens"]):
                    out = ("mixed",) + self._dispatch_mixed(
                        nxt["works2"], nxt["seqs"], p2, nxt["arrays"],
                        s_p2, s_d2, tokens_dev=chained, rect=nxt["rect"],
                    )
            else:
                with self._dispatch_span("window", nxt["arrays"]["tokens"]):
                    out = ("pure",) + self._dispatch_multi_step(
                        nxt["arrays"], s_d2, tokens_dev=chained
                    ) + (nxt["arrays"]["tokens"].shape[0],)
            e = make_entry(out, nxt["works2"], nxt["seqs"], nxt["vmap"])
            _lag_add(lag, e)
            pending.append(e)
            return True

        while pending:
            # fill the pipeline BEFORE syncing (nothing has been freed
            # yet, so planning here can never reallocate blocks an
            # in-flight window still writes).
            # _running: a shutdown() mid-stream must flush and return,
            # not keep dispatching until the batch drains
            while (
                len(pending) < self.PIPELINE_DEPTH
                and pipelining
                and self._running
                and self._control.empty()
            ):
                if not try_extend():
                    break
            harvest_entry(pending.popleft())
            if any(
                s.state != SeqState.RUNNING for e in pending for s in e["seqs"]
            ) or any(
                w.seq.state != SeqState.PREFILL
                for e in pending
                for w in e["works"]
            ):
                # composition changed under in-flight windows: flush
                while pending:
                    harvest_entry(pending.popleft())
                return

    @staticmethod
    def _top_entry(seq: Sequence, ids, lps) -> dict[int, float]:
        """One token's top-logprob alternatives, trimmed to the count
        the request asked for ({} when this seq only wants the chosen
        logprob but rode a top-lp batch)."""
        k = seq.request.output.logprobs or 0
        return {int(i): float(l) for i, l in zip(ids[:k], lps[:k])}

    def _emit_token(
        self, seq: Sequence, token: int, logprob: float, top=None
    ) -> None:
        sched = self.scheduler
        assert sched is not None
        sched.append_token(seq, token)
        ENGINE_TOKENS_GENERATED.inc()
        self.tokens_generated_total += 1
        reason = sched.should_finish(seq)
        if reason is not None:
            # finalize SLO + autopsy BEFORE the last token item hits the
            # output queue: the serving layer ships the autopsy payload
            # ahead of each item, and consumers abandon the stream at
            # this token (max_tokens), never reaching the finish item
            self._finalize_observability(seq, reason)
        if seq.emit is not None:
            tl = None
            if top is not None and (seq.request.output.logprobs or 0) > 0:
                tl = [self._top_entry(seq, top[0], top[1])]
            seq.emit(
                LLMEngineOutput(
                    request_id=seq.request_id,
                    token_ids=[token],
                    log_probs=[logprob],
                    top_logprobs=tl,
                )
            )
        if reason is not None:
            sched.finish(seq, reason)

    def _emit_window(self, seq: Sequence, tokens, logprobs, tops=None) -> None:
        """Append a fused-decode window's tokens, stopping at the first
        finish condition (the rest of the window is discarded), and emit
        ONE output carrying all kept tokens — the backend consumes
        multi-token deltas, so there's no per-token queue hop.
        ``tops`` = (top_ids [K, N], top_lps [K, N]) on the top-logprobs
        variant."""
        sched = self.scheduler
        assert sched is not None
        kept_toks: list[int] = []
        kept_lps: list[float] = []
        kept_tops: list[dict[int, float]] = []
        want_tl = tops is not None and (seq.request.output.logprobs or 0) > 0
        finish: Optional[FinishReason] = None
        for j in range(len(tokens)):
            if seq.state != SeqState.RUNNING:
                break
            sched.append_token(seq, int(tokens[j]))
            kept_toks.append(int(tokens[j]))
            kept_lps.append(float(logprobs[j]))
            if want_tl:
                kept_tops.append(self._top_entry(seq, tops[0][j], tops[1][j]))
            finish = sched.should_finish(seq)
            if finish is not None:
                break
        if kept_toks:
            ENGINE_TOKENS_GENERATED.inc(len(kept_toks))
            self.tokens_generated_total += len(kept_toks)
        if finish is not None:
            # see _emit_token: the autopsy payload must be pending
            # before the last token item is queued
            self._finalize_observability(seq, finish)
        if kept_toks and seq.emit is not None:
            seq.emit(
                LLMEngineOutput(
                    request_id=seq.request_id,
                    token_ids=kept_toks,
                    log_probs=kept_lps,
                    top_logprobs=kept_tops if want_tl else None,
                )
            )
        if finish is not None:
            sched.finish(seq, finish)

    def _emit_finish(self, seq: Sequence, reason: FinishReason) -> None:
        """Scheduler on_finish hook: close the request's output stream,
        bump finish counters, evaluate the request against the SLO
        targets, and emit the request's engine-side span tree (queue
        wait → prefill → decode) from the lifecycle stamps the
        scheduler recorded."""
        ENGINE_REQUESTS_FINISHED.labels(str(reason.value)).inc()
        self._finalize_observability(seq, reason)
        self._emit_lifecycle_spans(seq, reason)
        if seq.emit is not None:
            seq.emit(
                LLMEngineOutput(
                    request_id=seq.request_id,
                    finish_reason=reason,
                    prompt_tokens=len(seq.request.token_ids),
                    completion_tokens=seq.generated,
                )
            )
            seq.emit(None)  # sentinel: stream closed

    def _finalize_observability(
        self, seq: Sequence, reason: FinishReason
    ) -> None:
        """SLO verdict + autopsy segment, exactly once per request.

        Called EARLY — before the last token item is emitted — from the
        decode paths (consumers abandon the stream at max_tokens, so a
        payload published at the finish item would never ship), and
        again from the on_finish hook for paths that end without a
        trailing token (aborts, deadline kills, prefill-only finishes);
        the guard makes the second call a no-op."""
        if seq.observability_done:
            return
        seq.observability_done = True
        slo_met = self._observe_slo(seq, reason)
        self._publish_autopsy(seq, reason, slo_met)

    def _observe_slo(
        self, seq: Sequence, reason: FinishReason
    ) -> Optional[bool]:
        """Per-request TTFT/ITL vs the configured targets (telemetry/
        slo.py). Engine-side TTFT = submit → first appended token; ITL
        = mean decode inter-token latency. Requests that never produced
        a token (errors/cancellations before first emit) don't score —
        they'd poison attainment with infrastructure failures the SLO
        targets don't describe. An SLO miss trips the flight recorder's
        request watchdog so the steps that served the slow request are
        preserved on disk. Returns the verdict (None = unscored) so the
        autopsy segment can carry the slo_miss flag."""
        if reason in (
            FinishReason.ERROR, FinishReason.CANCELLED, FinishReason.TIMEOUT,
            # a drain handoff is a planned partial segment, not a served
            # request: the resumed continuation scores on the peer
            FinishReason.MIGRATE,
        ):
            # infrastructure failures and client disconnects don't
            # score: counting an errored request's fast partial tokens
            # as 'met' goodput would report a fleet in an error loop as
            # HEALTHY — the opposite of what the Planner signal means
            return None
        if not seq.t_submit or not seq.t_first_token:
            return None
        ttft_s = seq.t_first_token - seq.t_submit
        itl_s = None
        if seq.generated > 1:
            itl_s = (time.monotonic() - seq.t_first_token) / (
                seq.generated - 1
            )
        met = self.slo.observe(ttft_s, itl_s, completion_tokens=seq.generated)
        if not met and self.recorder is not None:
            dump = self.recorder.note_slow_request(
                seq.request_id,
                ttft_ms=round(ttft_s * 1e3, 3),
                itl_ms=round(itl_s * 1e3, 3) if itl_s is not None else None,
                tokens=seq.generated,
                finish_reason=str(reason.value),
            )
            if dump is not None:
                # the ring dump fired: preserve the rest of the state
                # too (both limiters gate independently — a suppressed
                # ring dump means a recent bundle already exists)
                self.blackbox.trigger(f"slo_miss:{seq.request_id}")
        return met

    def _publish_autopsy(
        self, seq: Sequence, reason: FinishReason, slo_met: Optional[bool]
    ) -> None:
        """Publish the request's engine-side autopsy segment under its
        rid (telemetry/autopsy.py). In the frontend's process it lands
        straight on the active record; on a remote worker it parks in
        the pending table and the endpoint server ships it on the
        ``seg`` wire frame before fin. One bounded dict per request —
        the per-step decode summary comes from the flight recorder's
        ring tail and only for requests that missed their SLO, so the
        happy path stays O(1)."""
        try:
            now = time.monotonic()
            seg: dict = {
                "source": "engine",
                "pid": os.getpid(),
                "finish_reason": str(reason.value),
                "prompt_tokens": len(seq.request.token_ids),
                "cached_prompt_tokens": seq.num_cached_prompt,
                "tokens": seq.generated,
                "resume_offset": int(
                    getattr(seq.request, "resume_offset", 0) or 0
                ),
                "guided": seq.guided_state is not None,
                "slo_miss": slo_met is False,
            }
            if seq.t_submit:
                if seq.t_admit:
                    seg["queue_wait_ms"] = round(
                        (seq.t_admit - seq.t_submit) * 1e3, 3
                    )
                if seq.t_admit and seq.t_prefill_done:
                    seg["prefill_ms"] = round(
                        (seq.t_prefill_done - seq.t_admit) * 1e3, 3
                    )
                if seq.t_prefill_done:
                    seg["decode_ms"] = round(
                        (now - seq.t_prefill_done) * 1e3, 3
                    )
                if seq.t_first_token:
                    seg["ttft_ms"] = round(
                        (seq.t_first_token - seq.t_submit) * 1e3, 3
                    )
            sched = self.scheduler
            if sched is not None:
                seg["preemptions_total"] = sched.preemptions
            if self.spec_proposed_total:
                seg["spec"] = {
                    "proposed_total": self.spec_proposed_total,
                    "accepted_total": self.spec_accepted_total,
                    "accept_rate": round(
                        self.spec_accepted_total
                        / max(1, self.spec_proposed_total),
                        4,
                    ),
                }
            if slo_met is False and self.recorder is not None:
                steps = [
                    r for r in self.recorder.snapshot(32)
                    if r.get("kind") in ("decode", "mixed", "spec")
                ]
                if steps:
                    durs = [
                        float(r.get("duration_ms") or 0.0) for r in steps
                    ]
                    seg["decode_window"] = {
                        "steps": len(steps),
                        "mean_ms": round(sum(durs) / len(durs), 3),
                        "max_ms": round(max(durs), 3),
                        "slow_steps": sum(
                            1 for r in steps if r.get("slow")
                        ),
                    }
            autopsy.publish_segment(
                seq.autopsy_rid or seq.request_id, seg
            )
        except Exception:
            # the autopsy plane must never take down a finishing request
            log.exception("autopsy segment publish failed")

    def _emit_lifecycle_spans(self, seq: Sequence, reason: FinishReason) -> None:
        """Record the engine's per-request spans at finish time, from
        the scheduler's monotonic stamps as they are: queue_wait, prefill
        and decode tile submit -> now. No-op (two attribute reads) when
        tracing is disabled."""
        tracer = get_tracer()
        if not tracer.enabled or not seq.t_submit:
            return
        parent = seq.trace
        if parent is None:
            # untraced caller: WE are the trace head — one sampling
            # decision and ONE minted trace for the request, so its
            # three spans stay correlated (three independent record()
            # calls would each sample separately and root a separate
            # trace)
            if tracer.sample < 1.0 and random.random() >= tracer.sample:
                return
            parent = {"trace_id": new_trace_id(), "span_id": None}
        if not seq.t_admit:
            return
        tracer.record(
            "engine.queue_wait", start_mono=seq.t_submit,
            duration_s=seq.t_admit - seq.t_submit, parent=parent,
            attrs={"service": "engine",
                   "waiting": seq.waiting_at_intake},
        )
        if not seq.t_prefill_done:
            return
        prefill = {"service": "engine",
                   "prompt_tokens": len(seq.request.token_ids),
                   "cached_tokens": seq.num_cached_prompt,
                   "chunks": seq.prefill_chunks,
                   # window-plane pages its chunks handed back (0 for
                   # a model without that plane)
                   "window_pages_released": seq.window_pages_released}
        if getattr(self.model_config, "index_topk", 0):
            # learned sparse attention: the keys its chunks' tokens were
            # candidates among — token p scores p + 1 — a layer; cached
            # tokens are not scored again
            n, c = len(seq.request.token_ids), seq.num_cached_prompt
            prefill["candidate_keys"] = (n * (n + 1) - c * (c + 1)) // 2
        tracer.record(
            "engine.prefill", start_mono=seq.t_admit,
            duration_s=seq.t_prefill_done - seq.t_admit, parent=parent,
            attrs=prefill,
        )
        decode = {"service": "engine", "tokens": seq.generated,
                  "finish_reason": str(reason.value)}
        if seq.t_first_token:
            # the server's own TTFT, beside the client's
            decode["ttft_ms"] = round(
                (seq.t_first_token - seq.t_submit) * 1e3, 3
            )
        tracer.record(
            "engine.decode", start_mono=seq.t_prefill_done,
            duration_s=time.monotonic() - seq.t_prefill_done, parent=parent,
            attrs=decode,
        )

    def _annotate_deferred_error(self, exc: BaseException) -> None:
        """A device error from an earlier ``sync=False`` prefill dispatch
        only SURFACES at the next synced step (async dispatch defers
        device-side failures to the first host read). Annotate the
        raised error so quarantine forensics don't blame the batch the
        exception happened to be raised under (ADVICE r5)."""
        if not self._unsynced_steps:
            return
        note = (
            f"{len(self._unsynced_steps)} earlier sync=False prefill "
            f"dispatch(es) were never synced "
            f"[{'; '.join(self._unsynced_steps)}]; a deferred device "
            "error from those chunks can surface at this later synced "
            "step — the current batch may not be the origin"
        )
        log.warning("step failure may be deferred: %s", note)
        add_note = getattr(exc, "add_note", None)  # PEP 678, 3.11+
        if add_note is not None:
            add_note(note)
        else:
            exc.args = exc.args + (note,)
        self._unsynced_steps.clear()

    def _quarantine_step_failure(self) -> bool:
        """Try to contain a step failure to the requests most likely to
        have caused it instead of killing every in-flight stream
        (VERDICT r2 weak #6: one poisoned request must not fail all).

        Heuristic: the FIRST failure is retried outright — host state is
        untouched (emission happens after the device sync, which never
        completed), so a transient fault (device hiccup, allocator
        pressure) costs one replanned step instead of innocent requests'
        lives (ADVICE r3: don't terminate requests on transient faults).
        A repeat failure in a step that was PREFILLING new requests is
        attributed to those requests — their data is the new input.
        Further repeats (or repeat failures in pure-decode steps, where
        no single culprit is identifiable) fall back to _fail_all.
        Returns True when contained."""
        sched = self.scheduler
        plan = self._last_plan
        self._last_plan = None
        if self._step_failures == 1 and sched is not None and plan is not None:
            log.exception(
                "engine step failed (kind=%s); retrying once before "
                "quarantining", plan.kind,
            )
            return True
        if (
            sched is None
            or plan is None
            or not plan.prefill_batch
            or self._step_failures > 3
        ):
            return False
        ids = [w.seq.request_id for w in plan.prefill_batch]
        log.exception(
            "engine step failed while prefilling %s; quarantining those "
            "requests and keeping %d decode streams alive",
            ids, len(plan.decode_seqs),
        )
        for w in plan.prefill_batch:
            seq = w.seq
            if seq in sched.prefilling:
                sched.prefilling.remove(seq)
            sched.finish(seq, FinishReason.ERROR)
        return True

    def _fail_all(self) -> None:
        assert self.scheduler is not None
        for seq in list(self.scheduler.running) + list(
            self.scheduler.prefilling
        ) + list(self.scheduler.waiting):
            self.scheduler.finish(seq, FinishReason.ERROR)
        self.scheduler.running.clear()
        self.scheduler.prefilling.clear()
        self.scheduler.waiting.clear()

    # ------------------------------------------------------------------
    # Graceful drain (runtime/drain.py; docs/robustness.md)
    # ------------------------------------------------------------------
    def begin_drain(self) -> None:
        """Thread-safe: stop admitting and hand off in-flight streams.

        submit() rejects from the next call; the step loop finishes
        every MIGRATABLE sequence with ``FinishReason.MIGRATE`` at the
        next step boundary, which the routers turn into a proactive
        resume on a healthy peer. Ineligible streams (guided,
        penalty-sampling, opted out — the same set migration.resumable
        refuses) keep running until they complete or the drain
        deadline's reactive fallback ends them."""
        self._draining = True
        self._wake.set()

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drain_migrated(self) -> int:
        """Streams handed off with MIGRATE since begin_drain() (feeds
        dynamo_drain_streams_migrated_total)."""
        return self._drain_migrated

    def active_streams(self) -> int:
        """Sequences still attached to a client stream (advisory; the
        drain coordinator polls this toward zero)."""
        sched = self.scheduler
        if sched is None:
            return 0
        return sched.num_running + sched.num_waiting

    @staticmethod
    def _drain_migratable(request) -> bool:
        """Engine-side mirror of migration.resumable()'s *request*
        eligibility: only streams the router could actually resume get
        the MIGRATE handoff — the rest finish naturally or ride the
        deadline fallback."""
        if getattr(request, "migration", None) is False:
            return False
        if getattr(request, "guided", None) is not None:
            return False
        sampling = getattr(request, "sampling", None)
        if sampling is not None and getattr(sampling, "needs_penalties", False):
            return False
        return True

    def _migrate_eligible(self) -> None:
        """Engine thread: finish every migratable sequence with MIGRATE.
        Runs each loop iteration while draining, so a submit that raced
        the flag is swept on the next boundary too."""
        assert self.scheduler is not None
        sched = self.scheduler
        for pool in (sched.running, sched.prefilling, sched.waiting):
            for seq in list(pool):
                if not self._drain_migratable(seq.request):
                    continue
                try:
                    if seq in pool:
                        pool.remove(seq)
                    sched.finish(seq, FinishReason.MIGRATE)
                    self._drain_migrated += 1
                except Exception:
                    # a failed handoff must not take the engine thread
                    # down mid-drain: this stream rides the deadline and
                    # the reactive resume path instead
                    log.exception(
                        "drain handoff failed for %s", seq.request_id
                    )

    # ------------------------------------------------------------------
    # Async interface
    # ------------------------------------------------------------------
    def submit(
        self, request: PreprocessedRequest, context: Context
    ) -> asyncio.Queue:
        """Thread-safe submit; returns the asyncio output queue."""
        assert self._loop is not None
        if self._draining:
            # routers stop placing here the moment the DRAINING flag
            # lands in discovery; a submit that still arrives (flag
            # propagation race) must fail fast so the caller's failover
            # re-dispatches it to a healthy peer
            raise RuntimeError("engine is draining; not admitting new requests")
        out: asyncio.Queue = asyncio.Queue()
        loop = self._loop

        def emit(item) -> None:
            loop.call_soon_threadsafe(out.put_nowait, item)

        # Validate HERE, where a bad request errors on its own: garbage
        # reaching the jitted step would fail or corrupt the whole batch
        # (out-of-range ids silently clamp in the embedding gather).
        assert self.model_config is not None
        if not request.token_ids:
            raise ValueError("empty token_ids")
        V = self.model_config.vocab_size
        ids = np.asarray(request.token_ids)
        if not np.issubdtype(ids.dtype, np.integer):
            raise ValueError("token_ids must be integers")
        if ids.min() < 0 or ids.max() >= V:
            raise ValueError(
                f"token id out of range [0, {V}): "
                f"{int(ids.min())}..{int(ids.max())}"
            )
        mm_segments = []
        salt = DEFAULT_SALT
        if request.mm_embeds:
            from dynamo_tpu.multimodal.embeds import unpack_segments

            if self._pp > 1:
                raise ValueError(
                    "multimodal embedding injection is not supported with "
                    "pipeline parallelism yet"
                )

            # Validate HERE, where a bad request errors on its own — a
            # malformed shape surfacing inside the jitted step would
            # fail every in-flight request (_fail_all).
            mm_segments = unpack_segments(request.mm_embeds)
            assert self.model_config is not None
            D = self.model_config.hidden_size
            for offset, arr in mm_segments:
                if arr.shape[1] != D:
                    raise ValueError(
                        f"mm embedding dim {arr.shape[1]} != model hidden {D}"
                    )
                if not (0 <= offset and offset + arr.shape[0] <= len(request.token_ids)):
                    raise ValueError(
                        f"mm segment [{offset},+{arr.shape[0]}) outside prompt "
                        f"of {len(request.token_ids)} tokens"
                    )
            # Salt the block hashes with the embedding content: two
            # prompts with identical placeholder tokens but different
            # images must NOT share prefix-cache KV (and must not match
            # text-only requests either).
            h = hashlib.blake2b(digest_size=8)
            for offset, arr in mm_segments:
                h.update(offset.to_bytes(8, "little"))
                h.update(np.ascontiguousarray(arr).tobytes())
            salt = DEFAULT_SALT ^ int.from_bytes(h.digest(), "little")
        guided_automaton = None
        if request.guided is not None:
            # guided decoding (docs/guided_decoding.md): compile (or
            # LRU-fetch) the token automaton HERE, on the submit thread
            # — a bad schema fails this request alone, and a compile
            # never stalls the engine thread mid-step
            if self.config.decode_steps != 1:
                raise ValueError(
                    "guided decoding requires decode_steps == 1 (the "
                    "allow-mask advances on host per committed token; "
                    "fused windows sample K tokens per dispatch)"
                )
            if request.resume_offset:
                # a migrated request's generated tokens are folded into
                # token_ids with no boundary marker — the automaton
                # cursor cannot be reconstructed (the router refuses to
                # resume guided requests for the same reason)
                raise ValueError(
                    "guided requests cannot resume mid-stream"
                )
            guided_automaton = self._guided_automaton(request.guided)
            GUIDED_REQUESTS.labels(guided_automaton.kind).inc()
        seq = Sequence(
            request=request,
            tokens=TokenBlockSequence(
                request.token_ids, block_size=self.config.block_size, salt=salt
            ),
            emit=emit,
            is_cancelled=lambda: context.is_stopped,
            mm_segments=mm_segments,
            autopsy_rid=getattr(context, "id", "") or "",
        )
        if guided_automaton is not None:
            from dynamo_tpu.guided import GuidedState

            seq.guided_state = GuidedState(guided_automaton)
        # lifecycle stamps + trace link: _emit_finish turns these into
        # engine.{queue_wait,prefill,decode} spans (cheap plain fields
        # when tracing is off)
        seq.t_submit = time.monotonic()
        seq.trace = context.trace_context()
        if context.deadline is not None:
            # same-process monotonic instant: the scheduler reaps the
            # sequence (and frees its KV blocks) once this passes
            seq.deadline = context.deadline
        self._incoming.put(seq)
        self._wake.set()
        return out

    def as_async_engine(self) -> "JaxEngineAdapter":
        return JaxEngineAdapter(self)

    def stats(self) -> ForwardPassMetrics:
        sched, alloc = self.scheduler, self.allocator
        assert sched is not None and alloc is not None
        return ForwardPassMetrics(
            request_active_slots=sched.num_running,
            request_total_slots=self.config.max_batch_size,
            kv_active_blocks=alloc.num_blocks - 1 - alloc.num_free,
            kv_total_blocks=alloc.num_blocks - 1,
            num_requests_waiting=sched.num_waiting,
            gpu_cache_usage_perc=alloc.usage,
            gpu_prefix_cache_hit_rate=(
                sched.prefix_hits / sched.prefix_queries
                if sched.prefix_queries
                else 0.0
            ),
            slo_enabled=self.slo.config.enabled,
            slo_attainment=self.slo.attainment,
            goodput_tokens_total=self.slo.goodput_tokens,
        )

    def program_counts(self) -> dict:
        """Cumulative counts a profiler capture reads at its two edges
        (telemetry/debug.py ``program_spans.json``): the host's, and the
        family's on-device ones."""
        out = self._host_counts()
        out.update(self._read_family_counts())
        return out

    def _host_counts(self) -> dict:
        """The cumulative counts the host keeps: plain ints, no device
        read and no lock, so the engine thread can note them between two
        steps (the count history) as well as a capture's edges can."""
        sched = self.scheduler
        out: dict = {"steps": dict(self._steps_dispatched)}
        out.update(self.step_clock.counts())
        if sched is not None:
            out.update(
                prompt_tokens=sched.prompt_tokens_admitted,
                cached_prompt_tokens=sched.prompt_tokens_cached,
                preemptions=sched.preemptions,
                admit_blocked_reserve=sched.admit_blocked_reserve,
                admit_reserve_peak_pages=sched.admit_reserve_peak_pages,
                admit_reserve_sum_pages=sched.admit_reserve_sum_pages,
                decode_dispatches=self._decode_dispatches[0],
                decode_dispatches_chained=self._decode_dispatches[1],
                decode_rows_dispatched=self._decode_rows[0],
                decode_rows_padded=self._decode_rows[1],
                **self._inline,
                pipeline_drains=dict(self._pipeline_drains),
                prefill_tokens_real=self._prefill_tokens[0],
                prefill_tokens_padded=self._prefill_tokens[1],
            )
            if sched.state_slots is not None:
                out.update(
                    state_slot_steps_used=self._state_slot_steps[0],
                    state_slot_steps_total=self._state_slot_steps[1],
                )
            if sched.window_plane is not None:
                plane = sched.window_plane
                out.update(
                    window_page_steps=self._window_page_steps[0],
                    window_row_steps=self._window_page_steps[1],
                    window_pages_released_total=plane.released_total,
                    admit_blocked_window=sched.admit_blocked_window,
                    # as they stand (not cumulative): both planes
                    window_pages_in_use=plane.num_used,
                    window_pages_total=plane.num_blocks - 1,
                    full_pages_in_use=(
                        self.allocator.num_blocks - 1 - self.allocator.num_free
                    ),
                    full_pages_total=self.allocator.num_blocks - 1,
                )
        return out

    def _note_counts(self, now_ns: int) -> None:
        """The step clock's once-a-second tick (engine thread, at the end
        of a ``record`` or ``wait`` phase): one entry of the count
        history that ``program_spans.json`` carries."""
        # host-side numbers, and the family's device counts AS LAST READ
        # (a capture's edge, ``program_counts``): the tick reads no
        # device array and waits for no step
        counts = self._host_counts()
        counts.update(self._family_counts)
        note_counts(self._debug_name or "engine", counts, now_ns)

    def _read_family_counts(self) -> dict:
        """The counts a family keeps ON THE DEVICE in its state pytree
        (``state["counts"]``, int32, cumulative): read on the engine
        thread — the only one that may touch the donated caches — and
        added up in Python ints, so the device's wrap-around after 2**32
        never shows. Nothing new if the engine thread does not answer."""
        names = getattr(model_family(self.model_config), "COUNT_NAMES", ())
        if not names or not isinstance(self.v_cache, dict):
            return {}

        def read() -> np.ndarray:
            with transfer_fence.allow():
                return np.asarray(jax.device_get(self.v_cache["counts"]))

        try:
            if threading.current_thread() is self._thread or not self._running:
                now = read()
            else:
                now = self.call_on_thread(read).result(timeout=5.0)
        except Exception:
            return dict(self._family_counts)
        seen = self._family_counts_seen
        delta = now.astype(np.uint32) - (
            np.zeros_like(now) if seen is None else seen
        ).astype(np.uint32)
        self._family_counts_seen = now
        for name, d in zip(names, delta.tolist()):
            self._family_counts[name] = self._family_counts.get(name, 0) + int(d)
        return dict(self._family_counts)

    def debug_state(self) -> dict:
        """Live snapshot for ``/debug/state`` (telemetry/debug.py):
        scheduler slots, KV block pool occupancy/fragmentation, prefill
        queue depth, in-flight requests, recent flight-recorder steps,
        SLO attainment, HBM accounting.

        Reads live structures WITHOUT stopping the engine thread — a
        snapshot that waited for the step loop would hang exactly when
        the loop is stuck, which is when you need it. Values may be a
        step apart from each other; every field is advisory."""
        sched, alloc = self.scheduler, self.allocator
        out: dict = {
            "model": self.config.model_name,
            "running": self._running,
            "max_batch_size": self.config.max_batch_size,
            "decode_steps": self.config.decode_steps,
            "block_size": self.config.block_size,
            "tokens_generated_total": self.tokens_generated_total,
            # graceful drain flag ("top" renders the DRAIN state from
            # this; absent on older builds → the '-' rule)
            "draining": self._draining,
            # the step loop's phases and what else its one clock counts,
            # cumulative (docs/observability.md "Step phases")
            **self.step_clock.counts(),
        }
        if sched is not None:
            def req_row(seq) -> dict:
                return {
                    "request_id": seq.request_id,
                    "state": str(seq.state.value),
                    "prompt_tokens": len(seq.request.token_ids),
                    "generated": seq.generated,
                    "computed": seq.num_computed,
                    "blocks": len(seq.block_table),
                }

            running = list(sched.running)
            prefilling = list(sched.prefilling)
            waiting = list(sched.waiting)
            out["scheduler"] = {
                "running": len(running),
                "prefilling": len(prefilling),
                "waiting": len(waiting),
                "queue_depth": len(waiting) + len(prefilling),
                "preemptions": sched.preemptions,
                "prefix_queries": sched.prefix_queries,
                "prefix_hits": sched.prefix_hits,
                # the prefill shapes there are, and what the dispatched
                # ones held against what they padded to
                "prefill_rects": [f"{b}x{t}" for b, t in sched.prefill_rects],
                "prefill_tokens_real": self._prefill_tokens[0],
                "prefill_tokens_padded": self._prefill_tokens[1],
                # bounded: the fleet view needs the shape of the batch,
                # not one row per request at max_batch_size=256
                "requests": [
                    req_row(s) for s in (running + prefilling + waiting)[:64]
                ],
            }
        if alloc is not None:
            self._update_pool_gauges()
            usable = alloc.num_blocks - 1
            free = alloc.num_free
            cached_free = alloc.num_cached_free
            out["kv_pool"] = {
                "total_blocks": usable,
                "active_blocks": usable - free,
                "free_blocks": free,
                "cached_free_blocks": cached_free,
                "usage": alloc.usage,
                # fraction of the free pool still holding reusable
                # content-addressed KV (the prefix cache's evictable
                # working set — high is GOOD until allocation pressure
                # starts evicting it)
                "cached_free_fraction": (cached_free / free) if free else 0.0,
            }
        if sched is not None and sched.state_slots is not None:
            # models with recurrent layers: the per-sequence state plane
            # beside the pages (the family's own: latent rows, or the
            # K and V of its attention layers)
            slots = sched.state_slots
            out["state_plane"] = {
                "total_slots": slots.num_slots - 1,
                "used_slots": slots.num_used,
                "bytes": self._plane_bytes[1],
                "page_pool_bytes": self._plane_bytes[0],
            }
        elif sched is not None and sched.window_plane is not None:
            # two page planes: the full layers' pages live as long as
            # the row, the window layers' are released behind the window
            pool, plane = out["kv_pool"], sched.window_plane
            out["page_planes"] = {
                "full": {
                    "bytes": self._page_plane_bytes["full"],
                    "pages_total": pool["total_blocks"],
                    "pages_in_use": pool["active_blocks"],
                },
                "window": {
                    "bytes": self._page_plane_bytes["window"],
                    "pages_total": plane.num_blocks - 1,
                    "pages_in_use": plane.num_used,
                    "window": plane.window,
                },
                "window_pages_released_total": plane.released_total,
            }
        elif (sched is not None and self.model_config is not None
                and self.model_config.owns_pages):
            # a family's own pages with no state beside them (latent
            # rows): the plane's bytes beside kv_pool's own counts, the
            # prefix cache keeping what is free and reusable
            pool = out["kv_pool"]
            out["page_plane"] = {
                "page_pool_bytes": self._plane_bytes[0],
                "pages_total": pool["total_blocks"],
                "pages_in_use": pool["active_blocks"],
                "pages_cached_reusable": pool["cached_free_blocks"],
            }
            if isinstance(self.k_cache, dict) and len(self.k_cache) > 1:
                # several per-token planes under ONE page id and table
                # (models/glm_moe_dsa.py: latent rows and indexer keys):
                # a page in use is in use in each of them
                out["page_plane"]["bytes_by_plane"] = {
                    name: tree_bytes(plane)
                    for name, plane in self.k_cache.items()
                }
        out["hbm"] = self.hbm.refresh()
        # the device this engine actually runs on, the kernel impls that
        # resolved there, and what start-up cost (chip_smoke.py reads it)
        out["device"] = dict(self.device_report)
        # a sharded model must sit on every device of its mesh, not on
        # the first one (None where the backend reports no stats)
        out["device"]["bytes_in_use_per_device"] = [
            (d.memory_stats() or {}).get("bytes_in_use")
            for d in self.mesh.devices.flat
        ]
        out["slo"] = self.slo.stats()
        # overlapped-pipeline health (docs/performance.md): device
        # idle-gap accounting — read device_idle_frac as
        # idle_gap_s_total growth over wall time under load
        out["overlap"] = {
            "enabled": self.config.overlap,
            **self.overlap.stats(),
            # the decode pipeline's dispatches, and those chained onto a
            # step in flight: the share says how often it overlaps
            "decode_dispatches": self._decode_dispatches[0],
            "decode_dispatches_chained": self._decode_dispatches[1],
            # what it did in line (prefill dispatches behind a step in
            # flight, finishes no step in flight held a row of), and
            # why it emptied itself when it did
            **self._inline,
            "pipeline_drains": dict(self._pipeline_drains),
        }
        # serve-phase compile fence (DYN_COMPILE_FENCE): mode + lifetime
        # escalation count, so `top`//debug/state show whether a fenced
        # worker has compiled anything mid-serve
        out["compile_fence"] = compile_fence.stats()
        out["transfer_fence"] = transfer_fence.stats()
        out["blackbox"] = self.blackbox.stats()
        if self.recorder is not None:
            out["flight_recorder"] = self.recorder.stats()
            out["recent_steps"] = self.recorder.snapshot(32)
        if self._drafter is not None:
            hid = self.spec_draft_hidden_s_total
            exp = self.spec_draft_exposed_s_total
            out["spec"] = {
                "drafter": getattr(self._drafter, "kind", "?"),
                "proposed_total": self.spec_proposed_total,
                "accepted_total": self.spec_accepted_total,
                # overlapped spec pipeline health (docs/
                # speculative_decoding.md): how much host draft wall
                # time the pipeline hid under device execution, and how
                # often the optimistic pre-draft matched the realized
                # tail (a miss re-drafts on the exposed critical path)
                "pipelined": self._overlap_ok(),
                "pipeline_steps": self.spec_pipeline_steps,
                "draft_hidden_s": round(hid, 6),
                "draft_exposed_s": round(exp, 6),
                "draft_hidden_frac": (
                    round(hid / (hid + exp), 4) if (hid + exp) > 0 else 0.0
                ),
                "predraft_hits": self.spec_predraft_hits,
                "predraft_misses": self.spec_predraft_misses,
            }
        if sched is not None and alloc is not None:
            out["load"] = self.stats().to_dict()
        return out

    async def wait_for_state(
        self, predicate: Callable[["JaxEngine"], bool],
        timeout: float = 30.0, poll_s: float = 0.005,
    ) -> None:
        """Await an engine-state condition (e.g. ``lambda e:
        e.scheduler.num_running >= 3``) instead of sleeping a guessed
        wall-clock interval — the injectable-event replacement for
        timing-based test choreography. Raises asyncio.TimeoutError."""
        deadline = time.monotonic() + timeout
        last_exc: Optional[BaseException] = None
        while True:
            try:
                if predicate(self):
                    return
                last_exc = None
            except Exception as exc:
                # tolerated (scheduler mid-mutation races) but REMEMBERED:
                # a predicate that raises every poll (typo'd attribute)
                # must surface its error, not a bare timeout
                last_exc = exc
            if time.monotonic() >= deadline:
                detail = (
                    f"; predicate raised every poll: {last_exc!r}"
                    if last_exc is not None else ""
                )
                raise asyncio.TimeoutError(
                    f"engine state predicate not met within {timeout}s"
                    + detail
                )
            await asyncio.sleep(poll_s)

    async def shutdown(self) -> None:
        self._running = False  # dynalint: handoff=stop-flag — one-way bool, each side only ever writes False; readers poll per step/await
        self._wake.set()
        if self._debug_name is not None:
            unregister_debug_provider(self._debug_name, self.debug_state)
            unregister_count_provider(self._debug_name, self.program_counts)
            self._debug_name = None
        from dynamo_tpu.models.llama import (
            get_attention_mesh,
            set_attention_mesh,
        )

        if get_attention_mesh() is self.mesh:
            set_attention_mesh(None)  # don't leak into later engines
        if self._thread is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, functools.partial(self._thread.join, timeout=10)
            )
        # let an in-flight black-box bundle finish writing — its
        # forensics are the reason the process is probably going down
        await asyncio.get_running_loop().run_in_executor(
            None, functools.partial(self.blackbox.flush, 5.0)
        )
        if self._mh_broadcast is not None:
            # release follower ranks blocked on the next control
            # broadcast (strictly after the step thread has joined, so
            # STOP orders after every step announcement)
            await asyncio.get_running_loop().run_in_executor(
                None, self._mh_broadcast.announce_stop
            )
        if self.kvbm is not None:
            self.kvbm.close()
        if self._thread is None or not self._thread.is_alive():
            # give the device memory back NOW, not whenever the last
            # reference to this engine dies: a second engine in the same
            # process (A/B tests) must be able to allocate
            # its weights and cache on the same chip. The cache is this
            # engine's alone and is deleted; the weights may be shared
            # with the caller, so only the reference is dropped.
            for leaf in jax.tree_util.tree_leaves((self.k_cache, self.v_cache)):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()
            self.params = self.k_cache = self.v_cache = None


class JaxEngineAdapter(AsyncEngine):
    """AsyncEngine facade: PreprocessedRequest in → LLMEngineOutput stream."""

    def __init__(self, engine: JaxEngine):
        self.engine = engine

    async def _gen(self, request: Any, context: Context) -> AsyncIterator[Any]:
        if not isinstance(request, PreprocessedRequest):
            request = PreprocessedRequest.model_validate(request)
        out = self.engine.submit(request, context)
        while True:
            item = await out.get()
            if item is None:
                return
            yield item
            if isinstance(item, LLMEngineOutput) and item.is_final:
                return

    def generate(self, request: Any, context: Context) -> EngineStream:
        return self._gen(request, context)
