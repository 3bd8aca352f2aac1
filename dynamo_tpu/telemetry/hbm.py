"""HBM memory accounting: what device memory is actually holding.

The engine's KV sizing (``_auto_num_blocks``) reasons about free HBM
once, at startup; this module keeps the answer LIVE — weight bytes, KV
pool bytes, current/peak device usage — as gauges and as a
``/debug/state`` snapshot, so "is the cache sized right" and "what ate
the headroom" are scrape-able questions instead of archaeology.

Sources, in preference order:

- ``device.memory_stats()`` (TPU runtimes report ``bytes_in_use`` /
  ``bytes_limit`` / ``peak_bytes_in_use``);
- a portable fallback that sums the tracked buffers (params + KV pool)
  when the backend reports nothing (CPU test backends)
  — the gauges then carry the *accounted* footprint with
  ``source="accounted"`` so dashboards can tell the difference.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Optional

from dynamo_tpu.telemetry.instruments import (
    HBM_BYTES_IN_USE,
    HBM_BYTES_LIMIT,
    HBM_KV_POOL_BYTES,
    HBM_PEAK_BYTES,
    HBM_WEIGHT_BYTES,
)

log = logging.getLogger("dynamo_tpu.telemetry.hbm")


def tree_bytes(tree: Any) -> int:
    """Total nbytes across a pytree of arrays (int8 KV caches are
    (values, scales) tuples — tree_leaves flattens those too)."""
    try:
        import jax

        return int(sum(
            getattr(x, "nbytes", 0) for x in jax.tree_util.tree_leaves(tree)
        ))
    except Exception:
        return 0


class HbmAccountant:
    """Per-engine memory bookkeeping feeding the ``dynamo_hbm_*`` gauges.

    ``set_static()`` records the long-lived allocations (weights, KV
    pool) once after engine init; ``refresh()`` re-reads live device
    stats (cheap — one runtime call) and returns the snapshot dict the
    debug endpoint embeds.
    """

    def __init__(self, device: Optional[Any] = None):
        self._device = device
        self._lock = threading.Lock()
        self.weight_bytes = 0
        self.kv_pool_bytes = 0
        self._peak_accounted = 0

    def set_device(self, device: Optional[Any]) -> None:
        """Bind the device whose memory_stats() refresh() reads (the
        engine learns its devices after the accountant is built)."""
        self._device = device

    def set_static(self, weight_bytes: int, kv_pool_bytes: int) -> None:
        with self._lock:
            self.weight_bytes = int(weight_bytes)
            self.kv_pool_bytes = int(kv_pool_bytes)
        HBM_WEIGHT_BYTES.set(self.weight_bytes)
        HBM_KV_POOL_BYTES.set(self.kv_pool_bytes)

    def refresh(self) -> dict:
        """Update the live gauges and return the snapshot dict."""
        with self._lock:
            weight, kv = self.weight_bytes, self.kv_pool_bytes
        stats: dict = {}
        if self._device is not None:
            try:
                stats = dict(self._device.memory_stats() or {})
            except Exception:
                stats = {}
        if stats.get("bytes_in_use") is not None:
            in_use = int(stats["bytes_in_use"])
            limit = int(stats.get("bytes_limit") or 0)
            peak = int(stats.get("peak_bytes_in_use") or in_use)
            source = "device"
        else:
            # portable fallback: the accounted footprint (weights + KV
            # pool); step transients are invisible here, so peak tracks
            # the accounted max only
            in_use = weight + kv
            limit = 0
            with self._lock:
                self._peak_accounted = max(self._peak_accounted, in_use)
                peak = self._peak_accounted
            source = "accounted"
        HBM_BYTES_IN_USE.set(in_use)
        HBM_BYTES_LIMIT.set(limit)
        HBM_PEAK_BYTES.set(peak)
        return {
            "source": source,
            "weight_bytes": weight,
            "kv_pool_bytes": kv,
            "bytes_in_use": in_use,
            "bytes_limit": limit,
            "peak_bytes_in_use": peak,
            "headroom_bytes": max(0, limit - in_use) if limit else None,
        }
