"""Runtime and DistributedRuntime: process-level handles.

Analogue of the reference's Runtime/DistributedRuntime/Worker
(reference: lib/runtime/src/{lib.rs:62-91, distributed.rs:32-176,
worker.rs:61-117}). A ``DistributedRuntime`` owns:

- the store connection (coordinator client, or in-process MemoryStore in
  "static" single-process mode),
- the primary lease + background keepalive (liveness primitive: if this
  process dies, everything it registered vanishes from discovery). Against
  a coordinator the renewals run on a thread with its own loop and store
  connection, so work that blocks the main loop (imports, tokenizer and
  pipeline builds at start-up) cannot cost the process its lease,
- one shared TCP EndpointServer for all endpoints served by this process.
"""

from __future__ import annotations

import asyncio
import logging
import signal
import threading
from typing import Optional

from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.service import ConnectionPool, EndpointServer
from dynamo_tpu.store.base import Store
from dynamo_tpu.store.client import StoreClient
from dynamo_tpu.store.memory import MemoryStore

log = logging.getLogger("dynamo_tpu.runtime")


class Runtime:
    """Process-level runtime: the event loop + shutdown signal."""

    def __init__(self) -> None:
        self._shutdown = asyncio.Event()
        # why the runtime shut ITSELF down (lease or store lost); None
        # for a planned stop. A failed runtime exits non-zero.
        self.failure: Optional[str] = None

    def shutdown(self) -> None:
        self._shutdown.set()

    def fail(self, reason: str) -> None:
        if self.failure is None:
            self.failure = reason
        self._shutdown.set()

    @property
    def is_shutdown(self) -> bool:
        return self._shutdown.is_set()

    async def wait_shutdown(self) -> None:
        await self._shutdown.wait()

    def install_signal_handlers(self) -> None:
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, self.shutdown)
            except NotImplementedError:  # pragma: no cover
                pass


class DistributedRuntime:
    def __init__(
        self,
        runtime: Runtime,
        store: Store,
        config: RuntimeConfig,
        primary_lease_id: int,
    ):
        self.runtime = runtime
        self.store = store
        self.config = config
        self.primary_lease_id = primary_lease_id
        self.endpoint_server = EndpointServer(
            host=config.worker_host, port=config.worker_port
        )
        self.connection_pool = ConnectionPool()
        self._keepalive_task: Optional[asyncio.Task] = None
        self._keeper: Optional[_LeaseKeeper] = None
        self._server_started = False

    @classmethod
    async def create(
        cls,
        config: Optional[RuntimeConfig] = None,
        runtime: Optional[Runtime] = None,
        store: Optional[Store] = None,
    ) -> "DistributedRuntime":
        """Connect to the coordinator (or spin an in-process store in static
        mode), grant the primary lease, start keepalive."""
        config = config or RuntimeConfig.from_settings()
        runtime = runtime or Runtime()
        coordinator = store is None and not config.static
        if store is None:
            if config.static:
                store = MemoryStore()
            else:
                # reconnect: a coordinator blip redials on backoff instead
                # of bricking the client (docs/robustness.md); the lease
                # keepalive below decides whether the process survives it
                store = await StoreClient.connect(
                    config.store_host, config.store_port, reconnect=True
                )
        lease_id = await store.lease_grant(config.lease_ttl_s)
        drt = cls(runtime, store, config, lease_id)
        loop = asyncio.get_running_loop()
        if coordinator:
            drt._keeper = _LeaseKeeper(drt, loop)
            drt._keeper.start()
        else:
            # an in-process store lives on this loop and is not
            # thread-safe; its sweeper forgives the time the loop was
            # blocked (store/memory.py), so renewing here is safe too
            drt._keepalive_task = loop.create_task(
                drt._keepalive_loop(store, runtime.fail)
            )
        return drt

    async def _keepalive_loop(self, store: Store, fail) -> None:
        # transient store disconnects are tolerated for up to the lease
        # TTL (the client is redialing on backoff underneath); once the
        # TTL has certainly lapsed the lease is gone server-side anyway,
        # so the process shuts down rather than serve unregistered
        cfg = self.config
        clock = asyncio.get_running_loop().time
        down_since: Optional[float] = None
        renewed_at = clock()
        while not self.runtime.is_shutdown:
            await asyncio.sleep(cfg.lease_keepalive_s)
            try:
                ok = await store.lease_keepalive(self.primary_lease_id)
            except ConnectionError:
                now = clock()
                if down_since is None:
                    down_since = now
                    log.warning(
                        "store unreachable; retrying keepalive within the "
                        "lease TTL (%.0fs)", cfg.lease_ttl_s,
                    )
                if now - down_since >= cfg.lease_ttl_s:
                    log.error("store connection lost; shutting down")
                    fail("store connection lost")
                    return
                continue
            down_since = None
            now = clock()
            gap, renewed_at = now - renewed_at, now
            if not ok:
                log.error(
                    "primary lease lost (%.1fs since the last renewal, TTL "
                    "%.0fs); shutting down", gap, cfg.lease_ttl_s,
                )
                fail("primary lease lost")
                return
            if gap > 2 * cfg.lease_keepalive_s:
                # evidence for the next lost lease: who was starved
                log.warning(
                    "lease renewed %.1fs after the previous renewal (asked "
                    "every %.1fs, TTL %.0fs): this process or the store "
                    "was starved", gap, cfg.lease_keepalive_s,
                    cfg.lease_ttl_s,
                )

    async def ensure_endpoint_server(self) -> EndpointServer:
        if not self._server_started:
            await self.endpoint_server.start()
            self._server_started = True
        return self.endpoint_server

    def namespace(self, name: str):
        from dynamo_tpu.runtime.component import Namespace

        return Namespace(self, name)

    async def shutdown(self) -> None:
        self.runtime.shutdown()
        if self._keepalive_task is not None:
            self._keepalive_task.cancel()
        if self._keeper is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self._keeper.stop
            )
        try:
            await self.store.lease_revoke(self.primary_lease_id)
        except (ConnectionError, RuntimeError):
            pass
        await self.endpoint_server.stop()
        await self.connection_pool.close()
        await self.store.close()
        if self.runtime.failure is not None:
            # a process that lost its lease or its store must not report
            # success to whoever supervises it
            raise SystemExit(f"runtime failed: {self.runtime.failure}")


class _LeaseKeeper(threading.Thread):
    """Renews a runtime's primary lease from a thread of its own.

    The thread runs its own event loop and its own connection to the
    coordinator, so a main loop that is blocked for seconds (imports and
    pipeline builds at start-up, a worker registering a large tokenizer)
    delays no renewal. The verdict (lease or store lost) is handed back
    to the main loop, which shuts the process down."""

    def __init__(self, drt: DistributedRuntime, main_loop: asyncio.AbstractEventLoop):
        super().__init__(name="lease-keepalive", daemon=True)
        self._drt = drt
        self._main_loop = main_loop
        self._lock = threading.Lock()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._task: Optional[asyncio.Task] = None
        self._stopped = False

    def run(self) -> None:
        try:
            asyncio.run(self._renew())
        except asyncio.CancelledError:
            pass  # stop()

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            if self._task is not None and self._loop is not None:
                try:
                    self._loop.call_soon_threadsafe(self._task.cancel)
                except RuntimeError:
                    pass  # its loop already closed: the thread is done
        self.join(timeout=5.0)

    def _fail(self, reason: str) -> None:
        try:
            self._main_loop.call_soon_threadsafe(self._drt.runtime.fail, reason)
        except RuntimeError:
            pass  # the main loop is gone: the process is exiting anyway

    async def _renew(self) -> None:
        with self._lock:
            if self._stopped:
                return
            self._loop = asyncio.get_running_loop()
            self._task = asyncio.current_task()
        cfg = self._drt.config
        try:
            store = await StoreClient.connect(
                cfg.store_host, cfg.store_port, reconnect=True
            )
        except OSError as e:
            log.error("lease keepalive cannot reach the store: %s", e)
            self._fail("store connection lost")
            return
        try:
            await self._drt._keepalive_loop(store, self._fail)
        finally:
            await store.close()
