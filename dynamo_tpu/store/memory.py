"""In-process control-plane implementation with full semantics.

Single source of truth for store behavior: the TCP server wraps one of
these; tests and single-process deployments use it directly.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import AsyncIterator, Optional

from dynamo_tpu.store.base import (
    NO_LEASE,
    KvEntry,
    QueueMessage,
    Store,
    Subscription,
    Watch,
    WatchEvent,
    subject_matches,
)

log = logging.getLogger("dynamo_tpu.store.memory")


class _MemWatch(Watch):
    def __init__(self, store: "MemoryStore", prefix: str, snapshot: list[KvEntry]):
        self._store = store
        self.prefix = prefix
        self._snapshot = snapshot
        self._queue: asyncio.Queue[WatchEvent | None] = asyncio.Queue()
        self._closed = False

    def snapshot(self) -> list[KvEntry]:
        return list(self._snapshot)

    def _notify(self, event: WatchEvent) -> None:
        if not self._closed:
            self._queue.put_nowait(event)

    def __aiter__(self) -> AsyncIterator[WatchEvent]:
        return self._iter()

    async def _iter(self) -> AsyncIterator[WatchEvent]:
        while True:
            ev = await self._queue.get()
            if ev is None:
                return
            yield ev

    async def close(self) -> None:
        self._closed = True
        self._queue.put_nowait(None)
        self._store._watches.discard(self)


class _MemSubscription(Subscription):
    def __init__(self, store: "MemoryStore", pattern: str):
        self._store = store
        self.pattern = pattern
        self._queue: asyncio.Queue[tuple[str, bytes] | None] = asyncio.Queue()
        self._closed = False

    def _deliver(self, subject: str, payload: bytes) -> None:
        if not self._closed:
            self._queue.put_nowait((subject, payload))

    def __aiter__(self) -> AsyncIterator[tuple[str, bytes]]:
        return self._iter()

    async def _iter(self) -> AsyncIterator[tuple[str, bytes]]:
        while True:
            item = await self._queue.get()
            if item is None:
                return
            yield item

    async def close(self) -> None:
        self._closed = True
        self._queue.put_nowait(None)
        self._store._subs.discard(self)


@dataclass
class _Lease:
    id: int
    ttl_s: float
    expires_at: float
    keys: set[str] = field(default_factory=set)


@dataclass
class _QueueState:
    next_id: itertools.count = field(default_factory=lambda: itertools.count(1))
    ready: deque[QueueMessage] = field(default_factory=deque)
    # msg_id -> (message, redelivery deadline)
    in_flight: dict[int, tuple[QueueMessage, float]] = field(default_factory=dict)
    cond: asyncio.Condition = field(default_factory=asyncio.Condition)


class MemoryStore(Store):
    """Full-semantics in-process store. All methods are asyncio-safe within
    one event loop (the store is not thread-safe by design; cross-thread use
    goes through the TCP client)."""

    def __init__(
        self,
        lease_sweep_interval_s: float = 0.5,
        persist_path: Optional[str] = None,
    ):
        self._kv: dict[str, KvEntry] = {}
        self._version = itertools.count(1)
        self._watches: set[_MemWatch] = set()
        self._subs: set[_MemSubscription] = set()
        self._leases: dict[int, _Lease] = {}
        self._lease_ids = itertools.count(1)
        self._queues: dict[str, _QueueState] = defaultdict(_QueueState)
        self._objects: dict[str, dict[str, bytes]] = defaultdict(dict)
        self._sweep_interval = lease_sweep_interval_s
        self._sweeper: Optional[asyncio.Task] = None
        self._closed = False
        # durability (store/persist.py): WAL + snapshot replay. Without
        # it a coordinator restart loses model registrations, deployment
        # specs, prefill queues, and the whole G4 object tier.
        self._wal = None
        if persist_path:
            from dynamo_tpu.store.persist import WriteAheadLog

            self._wal = WriteAheadLog(persist_path)
            self._restore()

    # -- durability -------------------------------------------------------
    def _restore(self) -> None:
        from dynamo_tpu.store.persist import decode_value

        snap, records = self._wal.replay()
        max_ver = 0
        snap_next: dict[str, int] = {}
        if snap:
            max_ver = int(snap.get("version", 0))
            for e in snap.get("kv", []):
                self._kv[e["k"]] = KvEntry(
                    key=e["k"], value=decode_value(e["v"]),
                    version=int(e["ver"]), lease_id=NO_LEASE,
                )
            for name, qs in snap.get("queues", {}).items():
                q = self._queues[name]
                snap_next[name] = int(qs["next_id"])
                q.next_id = itertools.count(int(qs["next_id"]))
                for m in qs.get("msgs", []):
                    q.ready.append(
                        QueueMessage(id=int(m["id"]), payload=decode_value(m["p"]))
                    )
            for bucket, objs in snap.get("objects", {}).items():
                for name, data in objs.items():
                    self._objects[bucket][name] = decode_value(data)
        acked: dict[str, set[int]] = defaultdict(set)
        pushes: dict[str, list[QueueMessage]] = defaultdict(list)
        q_next: dict[str, int] = {}
        for rec in records:
            op = rec["op"]
            if op == "kv_put":
                max_ver = max(max_ver, int(rec["ver"]))
                self._kv[rec["k"]] = KvEntry(
                    key=rec["k"], value=decode_value(rec["v"]),
                    version=int(rec["ver"]), lease_id=NO_LEASE,
                )
            elif op == "kv_del":
                self._kv.pop(rec["k"], None)
            elif op == "q_push":
                # a crash between snapshot replace and log truncation
                # leaves pre-compaction records behind: anything the
                # snapshot already folded in (id < its next_id) must
                # not replay, or queued work would deliver twice
                if int(rec["id"]) < snap_next.get(rec["q"], 0):
                    continue
                pushes[rec["q"]].append(
                    QueueMessage(id=int(rec["id"]), payload=decode_value(rec["p"]))
                )
                q_next[rec["q"]] = max(
                    q_next.get(rec["q"], 1), int(rec["id"]) + 1
                )
            elif op == "q_ack":
                acked[rec["q"]].add(int(rec["id"]))
            elif op == "obj_put":
                self._objects[rec["b"]][rec["n"]] = decode_value(rec["v"])
            elif op == "obj_del":
                self._objects.get(rec["b"], {}).pop(rec["n"], None)
        for name, msgs in pushes.items():
            q = self._queues[name]
            for m in msgs:
                if m.id not in acked[name]:
                    q.ready.append(m)
        for name, nid in q_next.items():
            self._queues[name].next_id = itertools.count(nid)
        # acks for messages restored from the SNAPSHOT
        for name, ids in acked.items():
            q = self._queues[name]
            if ids:
                q.ready = deque(m for m in q.ready if m.id not in ids)
        self._version = itertools.count(max_ver + 1)

    def _snapshot(self) -> dict:
        from dynamo_tpu.store.persist import snapshot_from_state

        queues = {
            name: (
                next(q.next_id),  # consumes one id: monotonicity kept
                list(q.ready) + [m for m, _ in q.in_flight.values()],
            )
            for name, q in self._queues.items()
        }
        # itertools.count was advanced by the peek above; rebuild
        for name, (nid, _) in queues.items():
            self._queues[name].next_id = itertools.count(nid + 1)
        ver = next(self._version)
        self._version = itertools.count(ver + 1)
        return snapshot_from_state(self._kv, queues, self._objects, ver)

    def _maybe_compact(self) -> None:
        if self._wal is not None and self._wal.needs_compaction():
            self._wal.compact(self._snapshot())

    def _ensure_sweeper(self) -> None:
        if self._sweeper is None or self._sweeper.done():
            self._sweeper = asyncio.get_running_loop().create_task(self._sweep_loop())

    async def _sweep_loop(self) -> None:
        swept_at = time.monotonic()
        while not self._closed:
            await asyncio.sleep(self._sweep_interval)
            now = time.monotonic()
            # A sweep that wakes late means this loop was blocked, or the
            # host starved or frozen: renewals sent meanwhile are still
            # unread in their sockets. That deaf time is not charged to
            # the leases (as etcd extends leases over a leader change).
            deaf = now - swept_at - self._sweep_interval
            swept_at = now
            if deaf > self._sweep_interval:
                if deaf > 1.0 and self._leases:
                    log.warning(
                        "lease sweep woke %.1fs late; %d lease(s) extended "
                        "by as much", deaf, len(self._leases),
                    )
                for lease in self._leases.values():
                    lease.expires_at += deaf
            expired = [l.id for l in self._leases.values() if l.expires_at <= now]
            for lid in expired:
                await self.lease_revoke(lid)
            # redeliver timed-out in-flight queue messages
            for q in self._queues.values():
                timed_out = [
                    mid for mid, (_, ddl) in q.in_flight.items() if ddl <= now
                ]
                if timed_out:
                    async with q.cond:
                        for mid in timed_out:
                            msg, _ = q.in_flight.pop(mid)
                            q.ready.appendleft(msg)
                        q.cond.notify_all()

    # -- kv ---------------------------------------------------------------
    def _emit(self, event: WatchEvent) -> None:
        for w in list(self._watches):
            if event.entry.key.startswith(w.prefix):
                w._notify(event)

    async def kv_put(self, key: str, value: bytes, lease_id: int = NO_LEASE) -> int:
        # detach from a previous owner lease so a stale lease's expiry can't
        # delete a key that has since been re-registered by a live process
        prev = self._kv.get(key)
        if prev is not None and prev.lease_id != lease_id:
            old = self._leases.get(prev.lease_id)
            if old is not None:
                old.keys.discard(key)
        if lease_id != NO_LEASE:
            lease = self._leases.get(lease_id)
            if lease is None:
                raise KeyError(f"lease {lease_id} does not exist")
            lease.keys.add(key)
        version = next(self._version)
        entry = KvEntry(key=key, value=value, version=version, lease_id=lease_id)
        durable_prev = prev is not None and prev.lease_id == NO_LEASE
        self._kv[key] = entry
        if self._wal is not None:
            from dynamo_tpu.store.persist import encode_value

            if lease_id == NO_LEASE:
                self._wal.append(
                    "kv_put", k=key, v=encode_value(value), ver=version
                )
                self._maybe_compact()
            elif durable_prev:
                # a leased put SHADOWS a previously durable key: tombstone
                # it, or a restart would resurrect the stale value
                # (leased keys themselves are ephemeral by design)
                self._wal.append("kv_del", k=key)
        self._emit(WatchEvent("put", entry))
        return version

    async def kv_create(self, key: str, value: bytes, lease_id: int = NO_LEASE) -> bool:
        if key in self._kv:
            return False
        await self.kv_put(key, value, lease_id)
        return True

    async def kv_get(self, key: str) -> Optional[KvEntry]:
        return self._kv.get(key)

    async def kv_get_prefix(self, prefix: str) -> list[KvEntry]:
        return sorted(
            (e for k, e in self._kv.items() if k.startswith(prefix)),
            key=lambda e: e.key,
        )

    async def kv_delete(self, key: str) -> bool:
        entry = self._kv.pop(key, None)
        if entry is None:
            return False
        if entry.lease_id != NO_LEASE and entry.lease_id in self._leases:
            self._leases[entry.lease_id].keys.discard(key)
        if self._wal is not None and entry.lease_id == NO_LEASE:
            self._wal.append("kv_del", k=key)
        self._emit(WatchEvent("delete", entry))
        return True

    async def kv_delete_prefix(self, prefix: str) -> int:
        keys = [k for k in self._kv if k.startswith(prefix)]
        for k in keys:
            await self.kv_delete(k)
        return len(keys)

    async def watch_prefix(self, prefix: str) -> Watch:
        snapshot = await self.kv_get_prefix(prefix)
        w = _MemWatch(self, prefix, snapshot)
        self._watches.add(w)
        return w

    # -- leases -----------------------------------------------------------
    async def lease_grant(self, ttl_s: float) -> int:
        self._ensure_sweeper()
        lid = next(self._lease_ids)
        self._leases[lid] = _Lease(
            id=lid, ttl_s=ttl_s, expires_at=time.monotonic() + ttl_s
        )
        return lid

    async def lease_keepalive(self, lease_id: int) -> bool:
        lease = self._leases.get(lease_id)
        if lease is None:
            return False
        lease.expires_at = time.monotonic() + lease.ttl_s
        return True

    async def lease_revoke(self, lease_id: int) -> None:
        lease = self._leases.pop(lease_id, None)
        if lease is None:
            return
        for key in list(lease.keys):
            await self.kv_delete(key)

    # -- pub/sub ----------------------------------------------------------
    async def publish(self, subject: str, payload: bytes) -> None:
        for sub in list(self._subs):
            if subject_matches(sub.pattern, subject):
                sub._deliver(subject, payload)

    async def subscribe(self, pattern: str) -> Subscription:
        sub = _MemSubscription(self, pattern)
        self._subs.add(sub)
        return sub

    # -- queues -----------------------------------------------------------
    async def queue_push(self, queue: str, payload: bytes) -> int:
        self._ensure_sweeper()
        q = self._queues[queue]
        msg = QueueMessage(id=next(q.next_id), payload=payload)
        async with q.cond:
            q.ready.append(msg)
            q.cond.notify()
        # log AFTER the state mutation (like kv_put/obj_put): a
        # compaction triggered by this very append snapshots state that
        # already CONTAINS the message — logging first would let the
        # compaction truncate the push record while the snapshot misses it
        if self._wal is not None:
            from dynamo_tpu.store.persist import encode_value

            self._wal.append(
                "q_push", q=queue, id=msg.id, p=encode_value(payload)
            )
            self._maybe_compact()
        return msg.id

    async def queue_pop(
        self, queue: str, timeout_s: Optional[float] = None, visibility_s: float = 30.0
    ) -> Optional[QueueMessage]:
        q = self._queues[queue]
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        async with q.cond:
            while not q.ready:
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                try:
                    await asyncio.wait_for(q.cond.wait(), timeout=remaining)
                except asyncio.TimeoutError:
                    return None
            msg = q.ready.popleft()
            q.in_flight[msg.id] = (msg, time.monotonic() + visibility_s)
            return msg

    async def queue_ack(self, queue: str, msg_id: int) -> bool:
        q = self._queues[queue]
        acked = q.in_flight.pop(msg_id, None) is not None
        if acked and self._wal is not None:
            self._wal.append("q_ack", q=queue, id=msg_id)
        return acked

    async def queue_len(self, queue: str) -> int:
        q = self._queues[queue]
        return len(q.ready) + len(q.in_flight)

    # -- object store -----------------------------------------------------
    async def obj_put(self, bucket: str, name: str, data: bytes) -> None:
        self._objects[bucket][name] = bytes(data)
        if self._wal is not None:
            from dynamo_tpu.store.persist import encode_value

            self._wal.append("obj_put", b=bucket, n=name, v=encode_value(data))
            self._maybe_compact()

    async def obj_get(self, bucket: str, name: str) -> Optional[bytes]:
        return self._objects.get(bucket, {}).get(name)

    async def obj_delete(self, bucket: str, name: str) -> bool:
        deleted = self._objects.get(bucket, {}).pop(name, None) is not None
        if deleted and self._wal is not None:
            self._wal.append("obj_del", b=bucket, n=name)
        return deleted

    async def obj_list(self, bucket: str) -> list[str]:
        return sorted(self._objects.get(bucket, {}).keys())

    # -- lifecycle --------------------------------------------------------
    async def close(self) -> None:
        self._closed = True
        if self._sweeper is not None:
            self._sweeper.cancel()
        for w in list(self._watches):
            await w.close()
        for s in list(self._subs):
            await s.close()
        if self._wal is not None:
            # fold the log into a snapshot: clean restarts replay O(1)
            self._wal.compact(self._snapshot())
            self._wal.close()
