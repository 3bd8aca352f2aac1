"""KV router wire types (reference: lib/llm/src/kv_router/protocols.rs)."""

from __future__ import annotations

from typing import Literal, Optional

from pydantic import BaseModel, ConfigDict, Field


class KvCacheEvent(BaseModel):
    """One cache mutation on a worker: blocks stored or removed.

    ``block_hashes`` are chained sequence hashes (position-sensitive), so
    the radix tree can attach stored blocks under their parents.
    """

    op: Literal["stored", "removed", "cleared"]
    block_hashes: list[int] = Field(default_factory=list)
    parent_hash: Optional[int] = None  # for stored: hash chain parent
    token_block_size: int = 16


class RouterEvent(BaseModel):
    """KvCacheEvent tagged with its source worker + monotonic id."""

    worker_id: int
    event_id: int = 0
    event: KvCacheEvent


class ForwardPassMetrics(BaseModel):
    """Worker load snapshot (reference: protocols.rs ForwardPassMetrics)."""

    # a rolling restart mixes worker versions on one feed: a key this
    # version does not know is dropped, never an error
    model_config = ConfigDict(extra="ignore")

    worker_id: int = 0
    request_active_slots: int = 0
    request_total_slots: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0
    gpu_prefix_cache_hit_rate: float = 0.0
    # SLO/goodput signals (telemetry/slo.py; defaults keep the wire
    # compatible with workers that predate them). slo_enabled marks a
    # worker that actually evaluates targets — aggregators average
    # attainment over only those (a target-less worker's constant 1.0
    # would dilute the fleet signal).
    slo_enabled: bool = False
    slo_attainment: float = 1.0
    goodput_tokens_total: int = 0


class KvHitRateEvent(BaseModel):
    """Emitted by the router per scheduling decision
    (reference: scheduler.rs KVHitRateEvent)."""

    worker_id: int
    isl_blocks: int
    overlap_blocks: int
