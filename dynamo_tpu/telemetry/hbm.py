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
from dataclasses import dataclass
from typing import Any, Optional

from dynamo_tpu.telemetry.instruments import (
    HBM_BYTES_IN_USE,
    HBM_BYTES_LIMIT,
    HBM_KV_POOL_BYTES,
    HBM_PEAK_BYTES,
    HBM_WEIGHT_BYTES,
)

log = logging.getLogger("dynamo_tpu.telemetry.hbm")


@dataclass(frozen=True)
class DevicePeaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    hbm_bytes: float


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. JAX reports a v5e
# chip as "TPU v5 lite". An accelerator whose kind is not here is an
# error (device_peaks), never a silent v5e.
_V5E = DevicePeaks(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                   hbm_bytes=16e9)
DEVICE_PEAKS: dict[str, DevicePeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}

# CPU backends (tests, dev runs) have no peaks of their own: they keep
# the v5e row as a NAMED default; nothing computed from it there is a
# device metric.
CPU_DEFAULT_KIND = "TPU v5 lite"


def device_peaks(device) -> DevicePeaks:
    """Peaks of ``device`` (a ``jax.Device``); raises for an
    accelerator the table does not know."""
    if device.platform == "cpu":
        return DEVICE_PEAKS[CPU_DEFAULT_KIND]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add its datasheet row to "
            f"telemetry.hbm.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})"
        ) from None


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
