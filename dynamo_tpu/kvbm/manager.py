"""KvBlockManager: multi-tier KV cache orchestration.

Composes the engine's device allocator (G1, HBM) with host (G2) and disk
(G3) tier pools (reference: lib/llm/src/block_manager.rs:60-166 +
offload.rs:43-751). Responsibilities:

- **offload** (G1→G2): device blocks that become content-addressed are
  queued; ``pump()`` — called from the engine thread between steps —
  batches them through one jitted gather and inserts into the host pool.
  Single-threaded by design: the engine donates its cache buffers every
  step, so only the engine thread may touch them (the reference gets the
  same serialization from its progress-engine actor, block_manager/pool.rs).
- **demotion** (G2→G3): host-pool eviction writes through to disk.
- **onboarding** (G2/G3→G1): at admission, prompt blocks that miss in G1
  but hit in lower tiers are copied into freshly allocated device blocks
  via one jitted scatter, extending the prefix-cache hit (reference:
  offload.rs onboarding + docs/architecture.md:91-96 — the +40% TTFT
  system-memory-tier win this tier structure exists for).
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from dynamo_tpu.kvbm.layout import BlockLayout
from dynamo_tpu.kvbm.pool import TierPool
from dynamo_tpu.kvbm.storage import DiskBlockStorage, HostBlockStorage
from dynamo_tpu.telemetry.instruments import (
    KVBM_OFFLOADED_BLOCKS,
    KVBM_ONBOARDED_BLOCKS,
)
from dynamo_tpu.utils.clock import SYSTEM, Clock

log = logging.getLogger("dynamo_tpu.kvbm")

GatherFn = Callable[[list[int]], np.ndarray]  # device block ids -> packed
ScatterFn = Callable[[list[int], np.ndarray], None]  # packed -> device blocks
ResolveFn = Callable[[int], Optional[int]]  # seq_hash -> device block id


@dataclass
class KvbmConfig:
    host_num_blocks: int = 0
    disk_num_blocks: int = 0
    disk_path: str = ""
    offload_batch: int = 16  # max blocks gathered per pump
    # G4: remote object-storage tier (bucket in the coordinator store's
    # object plane; "" disables). Shared across workers — blocks another
    # worker demoted are onboardable here after refresh_remote_index().
    remote_bucket: str = ""


@dataclass
class KvbmStats:
    offloaded_blocks: int = 0
    onboarded_blocks: int = 0
    demoted_blocks: int = 0
    host_cached_blocks: int = 0
    disk_cached_blocks: int = 0
    remote_put_blocks: int = 0
    remote_got_blocks: int = 0


class SyncObjectStore:
    """Blocking object-plane facade the G4 tier runs on (the engine
    thread has no event loop; the coordinator client is async — see
    StoreObjectAdapter in dynamo_tpu/kvbm/remote.py for the bridge)."""

    def put(self, key: str, data: bytes) -> None:  # pragma: no cover
        raise NotImplementedError

    def get(self, key: str) -> Optional[bytes]:  # pragma: no cover
        raise NotImplementedError

    def get_many(self, keys: list[str]) -> list[Optional[bytes]]:
        """Batched fetch; backends override to overlap the round trips
        (one blocking wait instead of one per block)."""
        return [self.get(k) for k in keys]

    def list_keys(self) -> list[str]:  # pragma: no cover
        raise NotImplementedError


class RemoteTier:
    """G4: content-addressed KV blocks in remote object storage
    (reference: block_manager.rs CacheLevel::G4 — remote storage behind
    NIXL; here the coordinator store's object plane, so the tier is
    shared by every worker of the model).

    Unlike G2/G3 the capacity is remote and unbounded from the worker's
    view, so there is no LRU/slot pool — keys ARE the sequence hashes.
    ``contains`` consults a local index only (no network on the
    admission path); ``refresh_remote_index`` pulls the bucket's key
    list to discover blocks other workers demoted."""

    def __init__(self, objects: SyncObjectStore, layout: BlockLayout):
        self.objects = objects
        self.layout = layout
        self._known: set[int] = set()

    @staticmethod
    def _key(seq_hash: int) -> str:
        return f"{seq_hash:016x}"

    def contains(self, seq_hash: int) -> bool:
        return seq_hash in self._known

    @property
    def num_known(self) -> int:
        return len(self._known)

    def insert(self, seq_hash: int, data: np.ndarray) -> None:
        if seq_hash in self._known:
            return
        self.objects.put(self._key(seq_hash), np.ascontiguousarray(data).tobytes())
        self._known.add(seq_hash)

    def read(self, seq_hashes: list[int]) -> Optional[np.ndarray]:
        """All-or-nothing batched read (a half-onboarded prefix is not
        usable past the first gap anyway). NEVER raises: a flaky remote
        reads as a miss — the caller truncates, it must not take the
        whole kvbm down (engine._safe_onboard disables tiers on error)."""
        try:
            raws = self.objects.get_many([self._key(h) for h in seq_hashes])
        except Exception:
            import logging

            logging.getLogger("dynamo_tpu.kvbm").exception("G4 read failed")
            return None
        out = np.zeros((len(seq_hashes), *self.layout.packed_shape),
                       self.layout.np_dtype)
        for i, (h, raw) in enumerate(zip(seq_hashes, raws)):
            if raw is None or len(raw) != self.layout.block_bytes:
                self._known.discard(h)
                return None
            out[i] = np.frombuffer(raw, self.layout.np_dtype).reshape(
                self.layout.packed_shape
            )
        return out

    def refresh_remote_index(self) -> int:
        """Adopt keys other workers wrote; returns newly-known count."""
        before = len(self._known)
        for key in self.objects.list_keys():
            try:
                self._known.add(int(key, 16))
            except ValueError:
                continue
        return len(self._known) - before


class KvBlockManager:
    def __init__(
        self,
        config: KvbmConfig,
        layout: BlockLayout,
        gather_fn: GatherFn,
        scatter_fn: ScatterFn,
        resolve_fn: ResolveFn,
        remote_objects: Optional[SyncObjectStore] = None,
        clock: Optional[Clock] = None,
    ):
        # injectable clock (utils/clock.py; DL009 vocabulary): pump()'s
        # G4 refresh throttle reads time through this seam, so tests and
        # the fleet simulator can drive the refresh deterministically
        self.clock = clock or SYSTEM
        self.config = config
        if config.host_num_blocks <= 0:
            raise ValueError("host_num_blocks must be positive")
        if config.offload_batch <= 0:
            raise ValueError("offload_batch must be positive")
        # an offload batch larger than the host tier would just thrash it
        # (clamped copy: never mutate the caller's config)
        self._offload_batch = min(config.offload_batch, config.host_num_blocks)
        self.layout = layout
        self._gather = gather_fn
        self._scatter = scatter_fn
        self._resolve = resolve_fn
        self.remote: Optional[RemoteTier] = None
        if config.remote_bucket and remote_objects is not None:
            self.remote = RemoteTier(remote_objects, layout)
        self.disk: Optional[TierPool] = None
        if config.disk_num_blocks > 0:
            self.disk = TierPool(
                DiskBlockStorage(layout, config.disk_num_blocks, config.disk_path),
                on_evict=self._on_disk_evict,
            )
        self.host = TierPool(
            HostBlockStorage(layout, config.host_num_blocks),
            on_evict=self._demote,
        )
        # offload candidates: seq_hash -> device block id at commit time
        self._pending: OrderedDict[int, int] = OrderedDict()
        self._last_remote_refresh = 0.0
        self.stats = KvbmStats()
        # fleet KV fabric (kvbm/fabric.py FleetKvFabric), late-bound via
        # attach_fabric(). The host-tier lock exists for it: the peer
        # block server exports G2 blocks from the event loop while the
        # engine thread mutates the pool, so every host-pool touch that
        # moves data goes through this lock (uncontended when no fabric
        # is attached — a few ns per pump, not per step).
        self.fabric: Any = None
        self._host_lock = threading.Lock()

    def attach_fabric(self, fabric: Any) -> None:
        """Bind the fleet fabric (idempotent; engine thread or setup
        thread, before serving). The fabric's hooks then run inside
        pump()/onboard() on the engine thread."""
        self.fabric = fabric

    def attach_remote(self, objects: SyncObjectStore) -> None:
        """Late-bind the G4 tier (the worker's store connection usually
        comes up after the engine). Idempotent. MUST NOT be called on
        the event loop a StoreObjectAdapter schedules onto — the initial
        index refresh blocks on that loop (the CLI calls this via
        run_in_executor)."""
        if self.remote is None and self.config.remote_bucket:
            self.remote = RemoteTier(objects, self.layout)
            try:
                self.remote.refresh_remote_index()
            except Exception:
                log.exception("initial G4 index refresh failed")

    # -- event intake (engine thread) -------------------------------------
    def on_block_committed(self, seq_hash: int, device_block: int) -> None:
        if self.host.contains(seq_hash):
            return
        self._pending[seq_hash] = device_block

    REMOTE_REFRESH_S = 5.0

    # -- offload pump (engine thread, between steps) -----------------------
    def pump(self, max_blocks: Optional[int] = None) -> int:
        """Offload up to ``max_blocks`` (default ``offload_batch``)
        pending blocks; returns count. ``max_blocks=0`` runs only the
        periodic G4 index refresh — the engine uses it to keep the
        refresh alive while serving is busy (each offloaded block is a
        multi-MB device->host transfer on the engine thread, so
        unthrottled write-through offload competes with serving steps;
        its cost is not measured on the attached chip)."""
        if self.remote is not None:
            # periodic G4 index refresh: discover blocks OTHER workers
            # demoted since we attached (the cross-worker tier benefit)
            now = self.clock.monotonic()
            if now - self._last_remote_refresh >= self.REMOTE_REFRESH_S:
                self._last_remote_refresh = now
                try:
                    self.remote.refresh_remote_index()
                except Exception:
                    log.exception("G4 index refresh failed")
        if self.fabric is not None:
            # catalog snapshot refresh rides the same pump cadence as
            # the G4 index (throttled inside the fabric)
            self.fabric.maybe_refresh()
        if not self._pending or max_blocks == 0:
            self._enforce_fabric_pressure()
            return 0
        cap = self._offload_batch if max_blocks is None else min(
            max_blocks, self._offload_batch
        )
        batch: list[tuple[int, int]] = []
        while self._pending and len(batch) < cap:
            h, bid = self._pending.popitem(last=False)
            # the device block may have been evicted/reassigned since commit
            if self._resolve(h) == bid and not self.host.contains(h):
                batch.append((h, bid))
        if not batch:
            self._enforce_fabric_pressure()
            return 0
        hashes = [h for h, _ in batch]
        ids = [b for _, b in batch]
        packed = self._gather(ids)
        with self._host_lock:
            self.host.insert_many(hashes, packed)
        if self.fabric is not None:
            # publish the landed blocks to the fleet catalog (batched:
            # one store round trip per pump, not per block)
            self.fabric.on_host_insert_many(hashes, self.layout.block_bytes)
        self.stats.offloaded_blocks += len(batch)
        KVBM_OFFLOADED_BLOCKS.inc(len(batch))
        self._enforce_fabric_pressure()
        self._refresh_gauges()
        return len(batch)

    def _enforce_fabric_pressure(self) -> None:
        """Watermark-driven G2 demotion, once per pump (the fabric
        no-ops below the high watermark). A broken fabric must degrade
        to single-worker behavior, not kill the offload pump."""
        if self.fabric is None:
            return
        try:
            self.fabric.enforce_pressure()
        except Exception:
            log.exception("fleet pressure enforcement failed")

    @property
    def pending_offloads(self) -> int:
        return len(self._pending)

    def _demote(self, seq_hash: int, data: np.ndarray) -> None:
        # destination strings are the catalog tier names
        # (fabric.TIER_DISK / TIER_SHARED): the fabric retiers or prunes
        # the hash's catalog entry so it is never dangling
        dest: Optional[str] = None
        if self.disk is not None:
            self.disk.insert(seq_hash, data)
            self.stats.demoted_blocks += 1
            dest = "g3"
        elif self.remote is not None:
            # no G3: the cascade skips straight to remote
            if self._demote_remote(seq_hash, data):
                dest = "g4"
        if self.fabric is not None:
            self.fabric.on_host_evict(seq_hash, dest)

    def _demote_remote(self, seq_hash: int, data: np.ndarray) -> bool:
        if self.remote is None:
            return False
        try:
            self.remote.insert(seq_hash, data)
            self.stats.demoted_blocks += 1
            self.stats.remote_put_blocks += 1
            return True
        except Exception:
            # remote tier is best-effort cache: a flaky store must not
            # take the engine's offload pump down
            log.exception("G4 remote put failed for %x", seq_hash)
            return False

    def _on_disk_evict(self, seq_hash: int, data: np.ndarray) -> None:
        """G3's eviction cascade (disk LRU overflow -> remote)."""
        landed = self._demote_remote(seq_hash, data)
        if self.fabric is not None:
            if landed:
                self.fabric.on_tier_move(seq_hash, "g4")
            else:
                self.fabric.on_block_dropped(seq_hash)

    # -- onboarding (engine thread, at admission) --------------------------
    def match_offloaded(self, seq_hashes: list[int]) -> int:
        """Leading consecutive blocks available in G2/G3/G4 (no copies,
        no network — G4 membership is the local index)."""
        n = 0
        for h in seq_hashes:
            if (
                self.host.contains(h)
                or (self.disk is not None and self.disk.contains(h))
                or (self.remote is not None and self.remote.contains(h))
            ):
                n += 1
            else:
                break
        return n

    def onboard(self, seq_hashes: list[int], device_blocks: list[int]) -> int:
        """Copy the longest available prefix of ``seq_hashes`` from lower
        tiers into the given (freshly allocated) device blocks. Returns the
        number of blocks onboarded."""
        if self.fabric is not None:
            # fleet prefetch: blocks missing every local tier but hitting
            # the fleet catalog are pulled from the owning peer's host
            # tier / adopted from the shared bucket FIRST, so the plan
            # below sees them as local hits (a fetch replaces a whole
            # re-prefill; failures degrade to recompute, never raise)
            try:
                self.fabric.prefetch(seq_hashes[: len(device_blocks)])
            except Exception:
                log.exception("fleet prefetch failed")
        # plan first (membership only — no reads, no promotions yet, so the
        # plan can't be invalidated by eviction cascades mid-loop)
        host_rows: list[tuple[int, int]] = []  # (row index, hash)
        disk_rows: list[tuple[int, int]] = []
        remote_rows: list[tuple[int, int]] = []
        limit = min(len(seq_hashes), len(device_blocks))
        n = 0
        for i in range(limit):
            h = seq_hashes[i]
            if self.host.contains(h):
                host_rows.append((i, h))
            elif self.disk is not None and self.disk.contains(h):
                disk_rows.append((i, h))
            elif self.remote is not None and self.remote.contains(h):
                remote_rows.append((i, h))
            else:
                break
            n += 1
        # G4 reads can fail (remote eviction, another namespace's GC):
        # fetch BEFORE committing to n so a miss just truncates the
        # onboarded prefix at the first remote row
        remote_data = None
        if remote_rows:
            assert self.remote is not None
            remote_data = self.remote.read([h for _, h in remote_rows])
            if remote_data is None:
                if self.fabric is not None:
                    # the G4 read dropped whatever keys the bucket lost
                    # from the local index; prune their catalog claims so
                    # the fleet stops advertising them (never dangling)
                    for _, h in remote_rows:
                        if not self.remote.contains(h):
                            self.fabric.on_block_dropped(h)
                n = remote_rows[0][0]
                remote_rows = []
        if n == 0:
            return 0
        host_rows = [(i, h) for i, h in host_rows if i < n]
        disk_rows = [(i, h) for i, h in disk_rows if i < n]
        rows = np.zeros((n, *self.layout.packed_shape), self.layout.np_dtype)
        if host_rows:
            with self._host_lock:
                data = self.host.read([h for _, h in host_rows])  # one batched read
            for j, (i, _) in enumerate(host_rows):
                rows[i] = data[j]
        disk_data = None
        if disk_rows:
            assert self.disk is not None
            disk_data = self.disk.read([h for _, h in disk_rows])
            for j, (i, _) in enumerate(disk_rows):
                rows[i] = disk_data[j]
        for j, (i, _) in enumerate(remote_rows):
            rows[i] = remote_data[j]
        self._scatter(device_blocks[:n], rows)
        # promote lower-tier hits into the host tier AFTER all reads and
        # the scatter: promotion may trigger demotion-eviction cascades
        promoted: list[int] = []
        with self._host_lock:
            for j, (_, h) in enumerate(disk_rows):
                self.host.insert(h, disk_data[j])
                promoted.append(h)
            for j, (_, h) in enumerate(remote_rows):
                self.host.insert(h, remote_data[j])
                self.stats.remote_got_blocks += 1
                promoted.append(h)
        if self.fabric is not None:
            if promoted:
                self.fabric.on_host_insert_many(
                    promoted, self.layout.block_bytes
                )
            # popularity signal for the pressure lifecycle's
            # victim selection: every onboarded block was just used
            self.fabric.note_touch(seq_hashes[:n])
        self.stats.onboarded_blocks += n
        KVBM_ONBOARDED_BLOCKS.inc(n)
        self._refresh_gauges()
        return n

    # -- fleet fabric surface (kvbm/fabric.py) ------------------------------
    def contains_local(self, seq_hash: int) -> bool:
        """Membership across every locally readable tier (G2/G3/G4
        index) — what the fleet prefetch skips past."""
        return (
            self.host.contains(seq_hash)
            or (self.disk is not None and self.disk.contains(seq_hash))
            or (self.remote is not None and self.remote.contains(seq_hash))
        )

    def adopt_remote(self, seq_hash: int) -> bool:
        """Adopt a catalog-advertised shared-bucket block into the local
        G4 index without waiting for the periodic list refresh; the
        existing onboard path then reads it through RemoteTier (and
        un-adopts on a failed read)."""
        if self.remote is None:
            return False
        self.remote._known.add(seq_hash)
        return True

    def insert_host_bytes(self, seq_hash: int, raw: bytes) -> None:
        """Land one peer-fetched packed block in the host tier (engine
        thread; the fleet prefetch path). Publishes to the catalog like
        any other G2 landing."""
        block = np.frombuffer(raw, self.layout.np_dtype).reshape(
            self.layout.packed_shape
        )
        with self._host_lock:
            self.host.insert(seq_hash, block)
        if self.fabric is not None:
            self.fabric.on_host_insert(seq_hash, self.layout.block_bytes)

    def export_host_blocks(self, seq_hashes: list[int]) -> list[Optional[bytes]]:
        """Read G2 blocks as raw bytes for a peer (called from the peer
        block server's executor thread — the host lock is the handoff
        with the engine thread's mutation paths). Misses are None."""
        out: list[Optional[bytes]] = []
        with self._host_lock:
            for h in seq_hashes:
                if self.host.contains(h):
                    out.append(
                        np.ascontiguousarray(self.host.read([h])[0]).tobytes()
                    )
                else:
                    out.append(None)
        return out

    def demote_block(self, seq_hash: int, dest: str) -> Optional[str]:
        """Explicitly demote one G2 block (the pressure lifecycle's
        routed eviction — bypasses the LRU cascade so hot shared blocks
        can go to the shared bucket while cold ones go to disk).
        Returns where the block actually landed ("g3"/"g4") or None when
        it was dropped; the caller owns the catalog update."""
        with self._host_lock:
            if not self.host.contains(seq_hash):
                return None
            data = self.host.read([seq_hash])[0]
            self.host.evict(seq_hash)  # index-only: no on_evict cascade
        if dest == "g4" and self.remote is not None:
            if self._demote_remote(seq_hash, data):
                return "g4"
            dest = "g3"  # remote refused: fall back to disk
        if dest == "g3" and self.disk is not None:
            self.disk.insert(seq_hash, data)
            self.stats.demoted_blocks += 1
            return "g3"
        return None

    def _refresh_gauges(self) -> None:
        self.stats.host_cached_blocks = self.host.num_cached
        self.stats.disk_cached_blocks = self.disk.num_cached if self.disk else 0

    def close(self) -> None:
        if self.fabric is not None:
            try:
                self.fabric.close()
            except Exception:  # pragma: no cover - shutdown is best-effort
                log.exception("fleet fabric close failed")
        if self.disk is not None:
            self.disk.storage.close()
