"""The serving stack's metric catalog — every instrument in one place.

One module so the surface is auditable (docs/observability.md mirrors
this file) and so the cardinality gate (tests/test_metric_cardinality.py)
can walk the whole registry by importing one module. Layers import their
instruments from here; nothing else registers process-global metrics.

Naming: ``dynamo_<layer>_<what>_<unit>`` with Prometheus suffix
conventions (``_total`` counters, ``_seconds`` histograms). The http
family keeps the seed's prometheus_client names so dashboards survive
the migration.
"""

from __future__ import annotations

from dynamo_tpu.telemetry.metrics import REGISTRY

# -- HTTP frontend (names unchanged from the seed's prometheus_client) ------
HTTP_REQUESTS = REGISTRY.counter(
    "dynamo_http_requests_total",
    "Total HTTP LLM requests",
    labels=("model", "endpoint", "status"),
)
HTTP_INFLIGHT = REGISTRY.gauge(
    "dynamo_http_inflight_requests",
    "In-flight HTTP LLM requests",
    labels=("model",),
)
HTTP_DURATION = REGISTRY.histogram(
    "dynamo_http_request_duration_seconds",
    "HTTP LLM request duration",
    labels=("model", "endpoint"),
)
HTTP_TTFT = REGISTRY.histogram(
    "dynamo_http_time_to_first_token_seconds",
    "Time to first streamed token",
    labels=("model",),
)

# -- host data plane (telemetry/hostplane.py; docs/observability.md
# "Host data plane") — the frontend's event-loop lag monitor and the
# per-stream host-cost ledger. Lag buckets are scheduling-tax shaped
# (sub-ms healthy loop up to the multi-second stall a watchdog dump
# should already have explained).
_LAG_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, float("inf"),
)
HTTP_LOOP_LAG = REGISTRY.histogram(
    "dynamo_http_loop_lag_seconds",
    "Event-loop scheduling lag measured by the hostplane heartbeat "
    "(how late the loop ran a task that asked to wake on a fixed "
    "interval — every concurrent stream waits at least this long)",
    buckets=_LAG_BUCKETS,
)
HTTP_LOOP_LAG_P99 = REGISTRY.gauge(
    "dynamo_http_loop_lag_p99_seconds",
    "p99 event-loop lag over the heartbeat's rolling window",
)
HTTP_LOOP_LAG_MAX = REGISTRY.gauge(
    "dynamo_http_loop_lag_max_seconds",
    "Max event-loop lag over the heartbeat's rolling window",
)
HTTP_LOOP_STALLS = REGISTRY.counter(
    "dynamo_http_loop_stalls_total",
    "Heartbeat wakes later than the stall threshold — some callback "
    "held the loop synchronously; each (rate-limited) stall also dumps "
    "the flight recorder and a black-box bundle with reason loop_stall",
)
HTTP_OPEN_STREAMS = REGISTRY.gauge(
    "dynamo_http_open_streams",
    "SSE streams currently open on this frontend",
)
HTTP_HOST_STAGE = REGISTRY.histogram(
    "dynamo_http_host_stage_seconds",
    "Per-request host-plane stage cost stamped by the cost ledger "
    "(preprocess = parse/validate/tokenize, admission, dispatch = "
    "router/engine handoff, prime = wait for the engine's first "
    "chunk, tool_parser = streaming tool-call delta parsing)",
    labels=("stage",),  # preprocess | admission | dispatch | prime | tool_parser
    buckets=_LAG_BUCKETS,
)
HTTP_FIRST_CHUNK_WAIT = REGISTRY.histogram(
    "dynamo_http_first_chunk_wait_seconds",
    "Frontend's wait for the engine's FIRST chunk (first-chunk "
    "priming): the engine-side share of TTFB — compare with "
    "dynamo_http_time_to_first_token_seconds to split host stall "
    "from chip stall",
    buckets=(
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        1.0, 2.5, 5.0, 15.0, 60.0, float("inf"),
    ),
)
HTTP_SSE_WRITE_EMA = REGISTRY.gauge(
    "dynamo_http_sse_write_ema_seconds",
    "EMA of per-chunk SSE serialize+write cost across all streams "
    "(an EMA, not a per-chunk series: thousands of streams x hundreds "
    "of chunks must not mint histogram samples)",
)
HTTP_DRAIN_WAIT = REGISTRY.histogram(
    "dynamo_http_drain_wait_seconds",
    "Per-stream total time resp.write() spent awaiting transport "
    "drain (write backpressure: slow clients eating loop time)",
    buckets=(
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        1.0, 2.5, 5.0, 15.0, 60.0, float("inf"),
    ),
)

# -- engine (scheduler + step loop; the instruments ISSUE 2 calls out) ------
_STEP_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 15.0, 60.0, float("inf"),
)
ENGINE_STEP_SECONDS = REGISTRY.histogram(
    "dynamo_engine_step_seconds",
    "Engine device-step wall time by step kind",
    labels=("kind",),  # prefill | decode | mixed | window | spec
    buckets=_STEP_BUCKETS,
)
ENGINE_BATCH_OCCUPANCY = REGISTRY.gauge(
    "dynamo_engine_batch_occupancy",
    "Running sequences / max_batch_size (sampled each step)",
)
ENGINE_QUEUE_DEPTH = REGISTRY.gauge(
    "dynamo_engine_queue_depth",
    "Requests waiting or prefilling (not yet decoding)",
)
ENGINE_QUEUE_WAIT = REGISTRY.histogram(
    "dynamo_engine_queue_wait_seconds",
    "Submit-to-admission wait (time in the scheduler's waiting queue)",
    buckets=_STEP_BUCKETS,
)
ENGINE_PREEMPTIONS = REGISTRY.counter(
    "dynamo_engine_preemptions_total",
    "Recompute preemptions (healthy serving sits at ~0)",
)
ENGINE_COMPILE_EVENTS = REGISTRY.counter(
    "dynamo_engine_compile_events_total",
    "Step-shape compilations by phase (prewarm vs mid-serve lazy)",
    labels=("phase",),  # prewarm | serve
)
ENGINE_PREWARM_SECONDS = REGISTRY.gauge(
    "dynamo_engine_prewarm_seconds",
    "Wall time of the startup AOT prewarm pass",
)
COMPILE_FENCE_EVENTS = REGISTRY.counter(
    "dynamo_compile_fence_events_total",
    "Serve-phase XLA compile events escalated by the compile fence "
    "(nonzero only under DYN_COMPILE_FENCE; each one is an unprewarmed "
    "jit signature compiling mid-serve)",
)
TRANSFER_FENCE_EVENTS = REGISTRY.counter(
    "dynamo_transfer_fence_events_total",
    "Serve-phase implicit host<->device transfers escalated by the "
    "transfer fence (nonzero only under DYN_TRANSFER_FENCE; each one "
    "is a device sync or upload outside the dispatch/harvest contract)",
)
ENGINE_REQUESTS_FINISHED = REGISTRY.counter(
    "dynamo_engine_requests_finished_total",
    "Sequences finished by reason",
    labels=("reason",),  # stop | length | cancelled | error | ...
)
ENGINE_TOKENS_GENERATED = REGISTRY.counter(
    "dynamo_engine_tokens_generated_total",
    "Decoded tokens emitted to request streams",
)

# -- speculative decoding (engine spec step; dynamo_tpu/spec) ---------------
SPEC_PROPOSED_TOKENS = REGISTRY.counter(
    "dynamo_spec_proposed_tokens_total",
    "Draft tokens proposed to the speculative verify step",
    labels=("drafter",),  # ngram | bigram
)
SPEC_ACCEPTED_TOKENS = REGISTRY.counter(
    "dynamo_spec_accepted_tokens_total",
    "Draft tokens accepted by rejection sampling",
    labels=("drafter",),
)
SPEC_ACCEPT_RATE = REGISTRY.gauge(
    "dynamo_spec_accept_rate",
    "Accepted/proposed draft tokens of the last speculative step",
)
SPEC_STEP_SECONDS = REGISTRY.histogram(
    "dynamo_spec_step_seconds",
    "Speculative step latency by phase (host drafting vs device verify; "
    "the overlapped pipeline adds predraft = optimistic drafting hidden "
    "under device time)",
    labels=("phase",),  # draft | verify | predraft
    buckets=_STEP_BUCKETS,
)
SPEC_DRAFT_HIDDEN_FRAC = REGISTRY.gauge(
    "dynamo_spec_draft_hidden_frac",
    "Fraction of host draft wall time the overlapped spec pipeline hid "
    "under device execution (hidden predraft / (hidden + exposed); "
    "exposed = first-step drafts + harvest-time repairs)",
)

# -- guided decoding (dynamo_tpu/guided; docs/guided_decoding.md) -----------
GUIDED_COMPILE_SECONDS = REGISTRY.histogram(
    "dynamo_guided_compile_seconds",
    "Schema/regex -> token-automaton compile time (one compile per "
    "(spec, tokenizer) pair; repeats hit the process-wide LRU)",
    labels=("kind",),  # json_schema | regex | json_object
    buckets=(0.001, 0.005, 0.025, 0.1, 0.5, 2.5, 10.0, float("inf")),
)
GUIDED_CACHE_EVENTS = REGISTRY.counter(
    "dynamo_guided_cache_events_total",
    "Guided-automaton compile-cache lookups by result",
    labels=("result",),  # hit | miss
)
GUIDED_REQUESTS = REGISTRY.counter(
    "dynamo_guided_requests_total",
    "Requests admitted with a guided-decoding constraint",
    labels=("kind",),  # json_schema | regex | json_object
)
TOOL_CALL_STREAMS = REGISTRY.counter(
    "dynamo_tool_call_streams_total",
    "Responses emitted as OpenAI tool_calls deltas",
    labels=("mode",),  # forced | auto
)

# -- KV block manager / transfer plane --------------------------------------
KV_TRANSFER_BYTES = REGISTRY.counter(
    "dynamo_kv_transfer_bytes_total",
    "KV block bytes moved over the disagg transfer plane",
    labels=("direction",),  # send | recv
)
KV_TRANSFER_SECONDS = REGISTRY.histogram(
    "dynamo_kv_transfer_seconds",
    "Wall time of one KV transfer put (connect to ack)",
    labels=("direction",),
    buckets=_STEP_BUCKETS,
)
KV_TRANSFER_BLOCKS = REGISTRY.counter(
    "dynamo_kv_transfer_blocks_total",
    "KV blocks moved over the disagg transfer plane",
    labels=("direction",),
)
KVBM_OFFLOADED_BLOCKS = REGISTRY.counter(
    "dynamo_kvbm_offloaded_blocks_total",
    "Blocks demoted from device HBM into the host tier",
)
KVBM_ONBOARDED_BLOCKS = REGISTRY.counter(
    "dynamo_kvbm_onboarded_blocks_total",
    "Blocks promoted from offload tiers back into device HBM",
)
KVBM_REMOTE_TIMEOUTS = REGISTRY.counter(
    "dynamo_kvbm_remote_timeout_total",
    "Blocking store round trips from the engine thread that hit their "
    "deadline (G4 object plane + fleet catalog), by operation — each "
    "one also books a flight-recorder record instead of killing the "
    "offload pump",
    labels=("op",),  # put | get | get_many | list | catalog.*
)

# -- fleet KV fabric (kvbm/fabric.py; docs/kvbm.md "Fleet fabric") -----------
KVBM_FLEET_HITS = REGISTRY.counter(
    "dynamo_kvbm_fleet_hits_total",
    "Prompt blocks missing every local tier but onboarded from the "
    "fleet instead of recomputed, by source (peer = another worker's "
    "host tier over the wire plane, bucket = the shared G4 object "
    "bucket adopted via the catalog)",
    labels=("source",),  # peer | bucket
)
KVBM_FLEET_FETCHED_BLOCKS = REGISTRY.counter(
    "dynamo_kvbm_fleet_fetched_blocks_total",
    "Blocks landed in local tiers by fleet prefetch at admission",
)
KVBM_FLEET_FETCH_SECONDS = REGISTRY.histogram(
    "dynamo_kvbm_fleet_fetch_seconds",
    "Wall time of one peer host-tier fetch round trip (connect to "
    "last block byte)",
    buckets=(
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
        0.5, 1.0, 2.5, float("inf"),
    ),
)
KVBM_FLEET_DEMOTED_BLOCKS = REGISTRY.counter(
    "dynamo_kvbm_fleet_demoted_blocks_total",
    "G2 blocks demoted by the watermark pressure lifecycle, by "
    "destination (shared = hot shared prefixes to the G4 bucket, disk "
    "= cold private blocks to local G3, dropped = no lower tier)",
    labels=("dest",),  # shared | disk | dropped
)
KVBM_FLEET_CATALOG_ENTRIES = REGISTRY.gauge(
    "dynamo_kvbm_fleet_catalog_entries",
    "Distinct block hashes in this participant's fleet-catalog view "
    "after the last snapshot refresh",
)
KVBM_FLEET_DANGLING = REGISTRY.counter(
    "dynamo_kvbm_fleet_dangling_total",
    "Catalog entries pruned because every advertised location failed "
    "to produce the block (the request falls back to recompute)",
)

# -- SLO / goodput (telemetry/slo.py; targets via --slo-ttft-ms/--slo-itl-ms)
# latency-target-shaped buckets: TTFT targets live in the tens-of-ms to
# tens-of-seconds range, ITL targets in the ms to hundreds-of-ms range
_TTFT_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    float("inf"),
)
_ITL_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    float("inf"),
)
REQUEST_TTFT_SECONDS = REGISTRY.histogram(
    "dynamo_request_ttft_seconds",
    "Per-request time to first token, measured at the engine "
    "(submit to first emitted token)",
    buckets=_TTFT_BUCKETS,
)
REQUEST_ITL_SECONDS = REGISTRY.histogram(
    "dynamo_request_itl_seconds",
    "Per-request mean inter-token latency over the decode phase",
    buckets=_ITL_BUCKETS,
)
SLO_ATTAINMENT = REGISTRY.gauge(
    "dynamo_slo_attainment",
    "Rolling fraction of recent requests meeting the configured "
    "TTFT/ITL targets (1.0 when no targets are set)",
)
GOODPUT_TOKENS = REGISTRY.counter(
    "dynamo_goodput_tokens_total",
    "Completion tokens from requests that met their SLO targets",
)
SLO_REQUESTS = REGISTRY.counter(
    "dynamo_slo_requests_total",
    "Requests evaluated against the SLO targets, by outcome",
    labels=("outcome",),  # met | missed
)

# -- black-box capture (telemetry/blackbox.py; docs/observability.md) ------
BLACKBOX_DUMPS = REGISTRY.counter(
    "dynamo_blackbox_dumps_total",
    "Anomaly-triggered black-box forensic bundles written, by trigger "
    "(watchdog / serve_compile / serve_transfer / slo_miss / loop_stall)",
    labels=("reason",),
)

# -- request autopsy (telemetry/autopsy.py; docs/observability.md
# "Request autopsy") — request-bounded only: one counter bump per
# request at finish plus one per attached segment, NEVER per chunk
AUTOPSY_REQUESTS = REGISTRY.counter(
    "dynamo_autopsy_requests_total",
    "Requests closed by the autopsy collector, by retention outcome "
    "(retained = kept as an exemplar: flagged slow/migrated/faulted/"
    "shed/rejected or at the rolling p99 tail; dropped = finished "
    "clean and fast, record discarded)",
    labels=("outcome",),  # retained | dropped
)
AUTOPSY_EXEMPLARS = REGISTRY.gauge(
    "dynamo_autopsy_exemplars",
    "Exemplar records currently held in the autopsy ring "
    "(bounded; serves /debug/requests and the top SLOW column)",
)
AUTOPSY_SEGMENTS = REGISTRY.counter(
    "dynamo_autopsy_segments_total",
    "Execution segments attached to autopsy records, by source "
    "(engine = an engine's finish summary, remote_prefill = the "
    "disagg decode-side wait, worker_died = the synthesized stub "
    "for a worker that was lost mid-stream)",
    labels=("source",),  # engine | remote_prefill | worker_died
)

# -- flight recorder + slow-step watchdog (telemetry/recorder.py) -----------
SLOW_STEPS = REGISTRY.counter(
    "dynamo_engine_slow_steps_total",
    "Engine steps that breached the slow-step watchdog threshold",
    labels=("kind",),
)
FLIGHT_DUMPS = REGISTRY.counter(
    "dynamo_flight_recorder_dumps_total",
    "Flight-recorder ring dumps written, by trigger",
    labels=("reason",),  # slow_step | slow_request | manual
)

# -- KV pool occupancy (allocator view; refreshed per step + per snapshot) --
KV_POOL_BLOCKS_ACTIVE = REGISTRY.gauge(
    "dynamo_kv_pool_blocks_active",
    "KV blocks currently referenced by sequences (excludes the "
    "reserved garbage block)",
)
KV_POOL_BLOCKS_TOTAL = REGISTRY.gauge(
    "dynamo_kv_pool_blocks_total",
    "Usable KV blocks in the device pool (excludes the reserved "
    "garbage block)",
)
KV_POOL_CACHED_FREE_BLOCKS = REGISTRY.gauge(
    "dynamo_kv_pool_cached_free_blocks",
    "Free blocks still holding content-addressed (reusable) KV — the "
    "prefix cache's evictable working set",
)

# -- HBM accounting (telemetry/hbm.py) --------------------------------------
HBM_WEIGHT_BYTES = REGISTRY.gauge(
    "dynamo_hbm_weight_bytes",
    "Bytes held by model parameters (logical, across shards)",
)
HBM_KV_POOL_BYTES = REGISTRY.gauge(
    "dynamo_hbm_kv_pool_bytes",
    "Bytes held by the device KV cache pool (logical, across shards)",
)
HBM_BYTES_IN_USE = REGISTRY.gauge(
    "dynamo_hbm_bytes_in_use",
    "Live device memory in use (device.memory_stats when available; "
    "accounted weights+KV fallback otherwise)",
)
HBM_BYTES_LIMIT = REGISTRY.gauge(
    "dynamo_hbm_bytes_limit",
    "Device memory capacity reported by the runtime (0 = unknown)",
)
HBM_PEAK_BYTES = REGISTRY.gauge(
    "dynamo_hbm_peak_bytes",
    "Peak live-buffer watermark (device-reported peak, or the "
    "accounted maximum on backends without memory stats)",
)

# -- robustness (docs/robustness.md: faults, deadlines, shedding, failover) -
FAULTS_FIRED = REGISTRY.counter(
    "dynamo_faults_fired_total",
    "Injected faults fired, by injection point and fault kind "
    "(nonzero only when a DYN_FAULTS plan is active)",
    labels=("point", "kind"),
)
WATCH_RESTARTS = REGISTRY.counter(
    "dynamo_watch_restarts_total",
    "Store watch streams resubscribed after dying (discovery watchers "
    "recover instead of freezing their registry)",
    labels=("watcher",),  # models | instances
)
STORE_RECONNECTS = REGISTRY.counter(
    "dynamo_store_reconnects_total",
    "Coordinator-store client redials after a lost connection",
)
DEADLINE_EXPIRED = REGISTRY.counter(
    "dynamo_deadline_expired_total",
    "Requests cancelled because their deadline budget expired, by the "
    "lifecycle stage that caught the expiry",
    labels=("stage",),  # admission | queue | prefill | decode | prefill_queue
)
REQUESTS_SHED = REGISTRY.counter(
    "dynamo_requests_shed_total",
    "Requests rejected 429 by admission control, by overload signal",
    labels=("reason",),  # queue_depth | kv_pressure
)
FAILOVER_RETRIES = REGISTRY.counter(
    "dynamo_failover_retries_total",
    "Requests re-dispatched to another worker after a dispatch or "
    "pre-first-token stream failure",
)
MIDSTREAM_ABORTS = REGISTRY.counter(
    "dynamo_midstream_aborts_total",
    "Streams terminated with a clean error after their worker died "
    "mid-generation AND migration could not save them (disabled, "
    "opted out, penalty-ineligible, or every resume attempt failed)",
)
MIDSTREAM_RESUMES = REGISTRY.counter(
    "dynamo_midstream_resumes_total",
    "Mid-stream migration outcomes: result=ok counts successful "
    "splices (the resumed worker's first continuation token reached "
    "the client), result=failed counts resume attempts that died "
    "before splicing a token (dispatch failure or pre-splice stream "
    "loss; the stream then retries or falls back to the abort)",
    labels=("result",),  # ok | failed
)
RESUME_SECONDS = REGISTRY.histogram(
    "dynamo_midstream_resume_seconds",
    "Mid-stream migration latency: worker-death detection to the first "
    "spliced continuation token (covers re-schedule, re-dispatch, and "
    "the resume re-prefill — cache-hot placements sit in the low "
    "buckets)",
    buckets=_STEP_BUCKETS,
)
WORKER_DRAINS = REGISTRY.counter(
    "dynamo_worker_drains_total",
    "Graceful drains run by this worker (runtime/drain.py), by result: "
    "completed = every eligible stream handed off inside the deadline, "
    "deadline = the --drain-timeout-s budget expired and leftover "
    "streams fell back to the reactive abort/resume path, no_peer = no "
    "healthy peer existed so the worker served until done or deadline "
    "instead of migrating",
    labels=("result",),  # completed | deadline | no_peer
)
DRAIN_HANDOFF_SECONDS = REGISTRY.histogram(
    "dynamo_drain_handoff_seconds",
    "Wall time of one graceful drain's handoff phase: DRAINING flag "
    "published to the moment the last eligible stream left the engine "
    "(deadline-capped; docs/robustness.md 'Graceful drain')",
    buckets=_STEP_BUCKETS,
)
DRAIN_STREAMS_MIGRATED = REGISTRY.counter(
    "dynamo_drain_streams_migrated_total",
    "Active streams a graceful drain proactively handed off with the "
    "MIGRATE marker (each becomes a reason=drain resume splice on its "
    "router)",
)

# -- autoscaling planner (planner/planner.py; docs/autoscaling.md) ----------
PLANNER_SCALE_EVENTS = REGISTRY.counter(
    "dynamo_planner_scale_events_total",
    "Successful planner scaling actions, by component and direction",
    # direction: up | down (policy) | drain (reconciliation removing a
    # surplus worker the fleet gained without the planner asking)
    labels=("component", "direction"),
)
PLANNER_REPLACEMENTS = REGISTRY.counter(
    "dynamo_planner_replacements_total",
    "Workers replaced by the planner's self-healing reconciliation "
    "(intent said N, the fleet reported fewer for reconcile_cycles)",
    labels=("component",),
)
PLANNER_DEGRADATION_LEVEL = REGISTRY.gauge(
    "dynamo_planner_degradation_level",
    "Graceful-degradation ladder position (0 normal, 1 tighten "
    "admission, 2 disable spec decode, 3 shed aggressively)",
)
PLANNER_CONNECTOR_FAILURES = REGISTRY.counter(
    "dynamo_planner_connector_failures_total",
    "Planner add/remove commands the connector refused or failed",
    labels=("op",),  # add | remove
)

# -- disaggregation (decode-side routing + prefill queue) -------------------
DISAGG_REMOTE_PREFILLS = REGISTRY.counter(
    "dynamo_disagg_remote_prefills_total",
    "Requests routed to a remote prefill worker",
)
DISAGG_LOCAL_FALLBACKS = REGISTRY.counter(
    "dynamo_disagg_local_fallbacks_total",
    "Remote prefills that timed out and fell back to local prefill",
)
PREFILL_QUEUE_DEPTH = REGISTRY.gauge(
    "dynamo_prefill_queue_depth",
    "Prefill queue depth observed at the last routing decision",
)
PREFILL_QUEUE_WAIT = REGISTRY.histogram(
    "dynamo_prefill_queue_wait_seconds",
    "Enqueue-to-KV-landed wait for remote prefills (decode side)",
    buckets=_STEP_BUCKETS,
)
