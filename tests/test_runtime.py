"""Distributed runtime tests (≈ reference lib/runtime/tests/{pipeline,lifecycle}.rs).

Two deployment shapes are exercised:
- static: one process, in-memory store
- distributed: coordinator on TCP + two DistributedRuntimes ("processes")
  in one event loop, talking over real sockets.
"""

import asyncio
from typing import Any, AsyncIterator

import pytest

from dynamo_tpu.runtime.component import Instance
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.engine import AsyncEngine, Context, FnEngine, collect
from dynamo_tpu.runtime.pipeline import Operator, build_pipeline
from dynamo_tpu.runtime.push_router import PushRouter, RouterMode
from dynamo_tpu.runtime.runtime import DistributedRuntime
from dynamo_tpu.store.memory import MemoryStore
from dynamo_tpu.store.server import StoreServer


async def echo_stream(request: Any, ctx: Context) -> AsyncIterator[Any]:
    """Stream each token of the request back (≈ reference EchoEngineCore)."""
    for tok in request["tokens"]:
        if ctx.is_stopped:
            return
        yield {"token": tok}


def make_static_config() -> RuntimeConfig:
    return RuntimeConfig(static=True, worker_host="127.0.0.1", lease_ttl_s=2.0,
                         lease_keepalive_s=0.5)


async def test_static_serve_and_call():
    drt = await DistributedRuntime.create(config=make_static_config())
    try:
        ep = drt.namespace("test").component("echo").endpoint("generate")
        await ep.serve(FnEngine(echo_stream))
        client = await ep.client()
        ids = await client.wait_for_instances(timeout_s=5)
        assert len(ids) == 1
        stream = await client.generate_direct(ids[0], {"tokens": [1, 2, 3]})
        items = [i async for i in stream]
        assert items == [{"token": 1}, {"token": 2}, {"token": 3}]
        await client.close()
    finally:
        await drt.shutdown()


async def test_push_router_round_robin_and_failover():
    """Two workers; round-robin spreads load; killing one fails over."""
    server = StoreServer(MemoryStore(lease_sweep_interval_s=0.1), port=0)
    await server.start()
    cfg = lambda: RuntimeConfig(  # noqa: E731
        store_port=server.port, worker_host="127.0.0.1",
        lease_ttl_s=1.0, lease_keepalive_s=0.2,
    )
    w1 = await DistributedRuntime.create(config=cfg())
    w2 = await DistributedRuntime.create(config=cfg())
    frontend = await DistributedRuntime.create(config=cfg())

    async def worker_engine(tag: str):
        async def gen(request: Any, ctx: Context) -> AsyncIterator[Any]:
            yield {"worker": tag, "echo": request}

        return FnEngine(gen)

    try:
        for drt, tag in ((w1, "w1"), (w2, "w2")):
            ep = drt.namespace("ns").component("gen").endpoint("generate")
            await ep.serve(await worker_engine(tag))

        ep = frontend.namespace("ns").component("gen").endpoint("generate")
        client = await ep.client()
        await client.wait_for_instances(timeout_s=5)
        # wait until both instances are discovered
        for _ in range(50):
            if len(client.instance_ids()) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 2

        router = PushRouter(client, RouterMode.ROUND_ROBIN)
        seen = set()
        for i in range(4):
            items = await collect(router.generate({"n": i}, Context()))
            seen.add(items[0]["worker"])
        assert seen == {"w1", "w2"}

        # kill w1: lease revoked => discovery prunes it; router fails over
        await w1.shutdown()
        for _ in range(100):
            if len(client.instance_ids()) == 1:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 1
        for i in range(3):
            items = await collect(router.generate({"n": i}, Context()))
            assert items[0]["worker"] == "w2"
        await client.close()
    finally:
        for drt in (w2, frontend):
            await drt.shutdown()
        await server.stop()


async def test_cancellation_stops_worker_stream():
    """Client-side kill propagates to the worker's Context."""
    drt = await DistributedRuntime.create(config=make_static_config())
    try:
        produced = []

        async def slow(request: Any, ctx: Context) -> AsyncIterator[Any]:
            for i in range(1000):
                if ctx.is_stopped:
                    return
                produced.append(i)
                yield {"i": i}
                await asyncio.sleep(0.01)

        ep = drt.namespace("ns").component("slow").endpoint("generate")
        await ep.serve(FnEngine(slow))
        client = await ep.client()
        (iid,) = await client.wait_for_instances(5)
        ctx = Context()
        stream = await client.generate_direct(iid, {}, ctx)
        got = []
        async for item in stream:
            got.append(item)
            if len(got) == 3:
                ctx.kill()
                break
        await asyncio.sleep(0.3)
        n = len(produced)
        await asyncio.sleep(0.3)
        assert len(produced) == n, "worker kept producing after kill"
        assert n < 1000
        await client.close()
    finally:
        await drt.shutdown()


async def test_pipeline_operators():
    """Forward/backward edges compose (≈ reference pipeline.rs tests)."""

    class TokenizeOp(Operator):
        async def forward(self, request: str, context: Context):
            return {"tokens": [ord(c) for c in request]}, {"n": len(request)}

        async def backward(self, stream, state, context):
            async for item in stream:
                yield chr(item["token"] + 1)

    engine = build_pipeline(TokenizeOp(), FnEngine(echo_stream))
    out = await collect(engine.generate("abc", Context()))
    assert out == ["b", "c", "d"]


async def test_pipeline_type_errors():
    with pytest.raises(TypeError):
        build_pipeline(FnEngine(echo_stream), FnEngine(echo_stream))
    with pytest.raises(ValueError):
        build_pipeline()


async def test_component_events_pubsub():
    drt = await DistributedRuntime.create(config=make_static_config())
    try:
        comp = drt.namespace("ns").component("worker")
        sub = await comp.subscribe("kv_events")
        await comp.publish("kv_events", {"block_hash": 42, "op": "stored"})
        it = sub.__aiter__()
        subject, payload = await asyncio.wait_for(it.__anext__(), 5)
        assert subject == "ns.worker.kv_events"
        assert payload == {"block_hash": 42, "op": "stored"}
        await sub.close()
    finally:
        await drt.shutdown()


async def test_static_client_without_discovery():
    """Static mode: direct instance without store watch
    (≈ reference static client, component.rs:294-300)."""
    drt = await DistributedRuntime.create(config=make_static_config())
    try:
        ep = drt.namespace("ns").component("echo").endpoint("generate")
        inst = await ep.serve(FnEngine(echo_stream))
        static = Instance(
            instance_id=inst.instance_id, host="127.0.0.1", port=inst.port,
            namespace="ns", component="echo", endpoint="generate",
        )
        client = await ep.client(static_instance=static)
        stream = await client.generate_direct(inst.instance_id, {"tokens": [9]})
        assert [i async for i in stream] == [{"token": 9}]
        await client.close()
    finally:
        await drt.shutdown()


# ---------------------------------------------------------------------------
# Lease keepalive: what may and may not cost a process its lease
# ---------------------------------------------------------------------------


class _StoreInItsOwnThread:
    """A coordinator on a loop of its own, as a separate process would
    be: blocking the test's loop does not block the store."""

    def __enter__(self) -> int:
        import threading

        self._loop = asyncio.new_event_loop()
        self._server = StoreServer(
            MemoryStore(lease_sweep_interval_s=0.1), port=0
        )
        self._thread = threading.Thread(
            target=self._loop.run_forever, daemon=True
        )
        self._thread.start()
        asyncio.run_coroutine_threadsafe(
            self._server.start(), self._loop
        ).result(10)
        return self._server.port

    def call(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(10)

    def __exit__(self, *exc) -> None:
        self.call(self._server.stop())
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(10)
        assert not self._thread.is_alive()
        self._loop.close()


async def test_blocked_main_loop_does_not_cost_the_lease():
    """Start-up work that blocks the event loop for longer than the TTL
    (imports, a tokenizer build, a first compile) used to starve the
    renewals on that loop: the store revoked the lease and the process
    shut itself down ("primary lease lost"). The renewals now run on a
    thread with its own loop and connection."""
    import time

    store = _StoreInItsOwnThread()
    with store as port:
        drt = await DistributedRuntime.create(config=RuntimeConfig(
            store_port=port, worker_host="127.0.0.1",
            lease_ttl_s=1.0, lease_keepalive_s=0.2,
        ))
        try:
            await drt.store.kv_put("instances/x", b"v", drt.primary_lease_id)
            time.sleep(2.5)  # the loop is blocked for 2.5 TTLs
            await asyncio.sleep(0.5)
            assert not drt.runtime.is_shutdown
            assert drt.runtime.failure is None
            assert store.call(
                store._server.store.kv_get("instances/x")
            ) is not None
        finally:
            await drt.shutdown()


async def test_store_does_not_charge_leases_for_its_own_deaf_time(caplog):
    """An in-process store shares the loop: while the loop is blocked
    it can hear no renewal, so the sweep that wakes late extends the
    leases by the time it was deaf instead of revoking them."""
    import time

    drt = await DistributedRuntime.create(
        config=RuntimeConfig(static=True, worker_host="127.0.0.1",
                             lease_ttl_s=1.0, lease_keepalive_s=0.2),
        store=MemoryStore(lease_sweep_interval_s=0.1),
    )
    try:
        await asyncio.sleep(0.3)  # the sweeper is running
        time.sleep(2.5)
        await asyncio.sleep(0.5)
        assert not drt.runtime.is_shutdown
        assert await drt.store.lease_keepalive(drt.primary_lease_id) is True
        assert any("lease sweep woke" in r.getMessage()
                   for r in caplog.records)  # and it says so
        # a lease nobody renews still expires on time
        orphan = await drt.store.lease_grant(0.3)
        await asyncio.sleep(0.8)
        assert await drt.store.lease_keepalive(orphan) is False
    finally:
        await drt.shutdown()


async def test_lost_lease_fails_the_runtime_and_exits_nonzero():
    store = _StoreInItsOwnThread()
    with store as port:
        drt = await DistributedRuntime.create(config=RuntimeConfig(
            store_port=port, worker_host="127.0.0.1",
            lease_ttl_s=2.0, lease_keepalive_s=0.2,
        ))
        store.call(store._server.store.lease_revoke(drt.primary_lease_id))
        await asyncio.wait_for(drt.runtime.wait_shutdown(), timeout=5)
        assert drt.runtime.failure == "primary lease lost"
        with pytest.raises(SystemExit, match="primary lease lost"):
            await drt.shutdown()


async def test_planned_shutdown_exits_clean_and_stops_the_keeper():
    store = _StoreInItsOwnThread()
    with store as port:
        drt = await DistributedRuntime.create(config=RuntimeConfig(
            store_port=port, worker_host="127.0.0.1",
            lease_ttl_s=2.0, lease_keepalive_s=0.2,
        ))
        keeper = drt._keeper
        assert keeper is not None and keeper.is_alive()
        await drt.shutdown()  # no SystemExit
        assert drt.runtime.failure is None
        assert not keeper.is_alive()
        # a shutdown that races the keeper's own start-up (its loop and
        # task may not exist yet when stop() comes) leaves no thread
        keepers = []
        for _ in range(20):
            drt = await DistributedRuntime.create(config=RuntimeConfig(
                store_port=port, worker_host="127.0.0.1",
                lease_ttl_s=2.0, lease_keepalive_s=0.2,
            ))
            keepers.append(drt._keeper)
            await drt.shutdown()
        assert not any(k.is_alive() for k in keepers)


def test_frontend_that_loses_its_lease_exits_nonzero(tmp_path):
    """The real processes: a discovery frontend frozen (SIGSTOP) for
    longer than its lease comes back to a revoked lease, shuts itself
    down — and says so with its exit code."""
    import os
    import signal
    import socket
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    store_port, http_port = free_port(), free_port()
    env = dict(os.environ, PYTHONPATH=repo, DYN_JAX_PLATFORM="cpu",
               DYN_LEASE_TTL_S="1.0", DYN_LEASE_KEEPALIVE_S="0.2")
    cli = [sys.executable, "-m", "dynamo_tpu.cli.main"]
    store = subprocess.Popen(
        [*cli, "store", "--host", "127.0.0.1", "--port", str(store_port)],
        env=env, cwd=tmp_path,
    )
    front = None
    try:
        time.sleep(1.5)
        with open(tmp_path / "front.log", "w") as log:
            front = subprocess.Popen(
                [*cli, "run", "--in", "http", "--out", "auto",
                 "--http-host", "127.0.0.1", "--http-port", str(http_port),
                 "--store-host", "127.0.0.1",
                 "--store-port", str(store_port)],
                env=env, cwd=tmp_path, stdout=log, stderr=subprocess.STDOUT,
            )
        deadline = time.monotonic() + 60
        while "listening on" not in (tmp_path / "front.log").read_text():
            assert front.poll() is None and time.monotonic() < deadline
            time.sleep(0.2)
        front.send_signal(signal.SIGSTOP)
        time.sleep(2.5)
        front.send_signal(signal.SIGCONT)
        assert front.wait(timeout=30) == 1
        text = (tmp_path / "front.log").read_text()
        assert "primary lease lost" in text
        assert "runtime failed: primary lease lost" in text
    finally:
        for p in (front, store):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
