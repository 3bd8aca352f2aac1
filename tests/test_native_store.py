"""Native (C++) coordinator parity: the python StoreClient and the full
distributed runtime must behave identically over native/store/
store_server.cc as over the python StoreServer (which is the semantic
reference)."""

import asyncio
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BINARY = os.path.join(REPO, "dynamo_tpu", "native", "dynamo_store")


@pytest.fixture(scope="module")
def native_store_binary():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "native", "build.py")],
        capture_output=True, text=True,
    )
    if not os.path.exists(BINARY):
        pytest.skip(f"native store build unavailable: {r.stderr[-200:]}")
    return BINARY  # build.py also produced libdynamo_kv.so


@pytest.fixture
def native_store(native_store_binary):
    # sync fixture: the conftest's asyncio shim only handles async TESTS
    proc = subprocess.Popen(
        [native_store_binary, "--host", "127.0.0.1", "--port", "0"],
        stdout=subprocess.PIPE,
    )
    line = proc.stdout.readline()
    assert line.startswith(b"LISTENING"), line
    port = int(line.split()[1])
    yield port
    proc.kill()
    proc.wait()


async def test_native_store_full_parity(native_store):
    from dynamo_tpu.store.client import StoreClient

    c = await StoreClient.connect("127.0.0.1", native_store)
    try:
        # kv: versions, create, prefix order, delete
        v1 = await c.kv_put("a/x", b"1")
        v2 = await c.kv_put("a/y", b"2")
        assert v2 > v1
        assert not await c.kv_create("a/x", b"dupe")
        assert await c.kv_create("a/new", b"n")
        got = await c.kv_get_prefix("a/")
        assert [e.key for e in got] == ["a/new", "a/x", "a/y"]
        assert await c.kv_delete("a/new")
        assert not await c.kv_delete("a/new")
        assert await c.kv_delete_prefix("a/") == 2

        # lease against a missing id errors like the python server
        with pytest.raises(Exception):
            await c.kv_put("k", b"v", lease_id=424242)

        # watch: snapshot + put/delete events
        await c.kv_put("w/1", b"a")
        w = await c.watch_prefix("w/")
        assert [e.key for e in w.snapshot()] == ["w/1"]
        await c.kv_put("w/2", b"b")
        await c.kv_delete("w/1")
        it = w.__aiter__()
        ev1 = await asyncio.wait_for(it.__anext__(), 3)
        ev2 = await asyncio.wait_for(it.__anext__(), 3)
        assert (ev1.type, ev1.entry.key) == ("put", "w/2")
        assert (ev2.type, ev2.entry.key) == ("delete", "w/1")
        await w.close()

        # lease expiry deletes attached keys
        lid = await c.lease_grant(0.3)
        await c.kv_put("lease/me", b"x", lease_id=lid)
        await asyncio.sleep(0.8)
        assert await c.kv_get("lease/me") is None

        # re-put under a new lease detaches from the old one
        l1 = await c.lease_grant(0.3)
        l2 = await c.lease_grant(30)
        await c.kv_put("stable", b"1", lease_id=l1)
        await c.kv_put("stable", b"2", lease_id=l2)
        await asyncio.sleep(0.8)  # l1 expires: must NOT delete "stable"
        e = await c.kv_get("stable")
        assert e is not None and e.value == b"2"

        # pub/sub wildcards
        sub = await c.subscribe("ns.*.ev")
        subj_all = await c.subscribe("ns.>")
        await c.publish("ns.w1.ev", b"p1")
        await c.publish("other.w1.ev", b"nope")
        s, p = await asyncio.wait_for(sub.__aiter__().__anext__(), 3)
        assert (s, p) == ("ns.w1.ev", b"p1")
        s2, _ = await asyncio.wait_for(subj_all.__aiter__().__anext__(), 3)
        assert s2 == "ns.w1.ev"
        await sub.close()
        await subj_all.close()

        # queues: fifo, blocking pop, visibility redelivery, ack, len
        await c.queue_push("q", b"m1")
        await c.queue_push("q", b"m2")
        m1 = await c.queue_pop("q", timeout_s=1, visibility_s=30)
        m2 = await c.queue_pop("q", timeout_s=1, visibility_s=0.3)
        assert (m1.payload, m2.payload) == (b"m1", b"m2")
        assert await c.queue_ack("q", m1.id)
        await asyncio.sleep(0.8)  # m2 visibility expires -> redelivered
        m2b = await c.queue_pop("q", timeout_s=2)
        assert m2b.payload == b"m2"
        assert await c.queue_ack("q", m2b.id)
        assert not await c.queue_ack("q", m2b.id)
        assert await c.queue_len("q") == 0
        assert await c.queue_pop("q", timeout_s=0.1) is None

        # object plane (binary-safe)
        blob = bytes(range(256)) * 10
        await c.obj_put("bkt", "blob", blob)
        assert await c.obj_get("bkt", "blob") == blob
        assert await c.obj_list("bkt") == ["blob"]
        assert await c.obj_delete("bkt", "blob")
        assert await c.obj_get("bkt", "blob") is None
    finally:
        await c.close()


async def test_runtime_e2e_over_native_store(native_store):
    """The full distributed runtime (serve + discovery + streaming call +
    lease liveness) over the C++ coordinator."""
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.engine import Context, FnEngine, collect
    from dynamo_tpu.runtime.push_router import PushRouter, RouterMode
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    cfg = lambda: RuntimeConfig(  # noqa: E731
        store_host="127.0.0.1", store_port=native_store,
        worker_host="127.0.0.1", lease_ttl_s=1.0, lease_keepalive_s=0.3,
    )

    async def echo(request, ctx):
        for tok in request["tokens"]:
            yield {"token": tok}

    worker = await DistributedRuntime.create(config=cfg())
    frontend = await DistributedRuntime.create(config=cfg())
    try:
        ep = worker.namespace("cns").component("w").endpoint("gen")
        await ep.serve(FnEngine(echo))
        client = await (
            frontend.namespace("cns").component("w").endpoint("gen").client()
        )
        await client.wait_for_instances()
        router = PushRouter(client, RouterMode.ROUND_ROBIN)
        items = await collect(router.generate({"tokens": [1, 2, 3]}, Context()))
        assert [i["token"] for i in items] == [1, 2, 3]

        # worker death (connection drop) revokes its lease: the instance
        # disappears from discovery within the sweep interval
        await worker.shutdown()
        for _ in range(40):
            if not client.instance_ids():
                break
            await asyncio.sleep(0.1)
        assert not client.instance_ids()
    finally:
        await frontend.shutdown()


KV_LIB = os.path.join(REPO, "dynamo_tpu", "native", "libdynamo_kv.so")


async def _drive_c_publisher(port: int) -> None:
    """Publish from the C ABI to the given coordinator port and assert
    the python subscriber receives valid RouterEvents — including hashes
    with the top bit set (must arrive as UNSIGNED ints, matching the
    radix tree's xxh3 keys)."""
    import ctypes

    import msgpack

    from dynamo_tpu.kv_router.protocols import RouterEvent
    from dynamo_tpu.store.client import StoreClient

    big = 0x9000000000000001  # >= 2^63: a signed-int64 encoding would corrupt it
    client = await StoreClient.connect("127.0.0.1", port)
    sub = await client.subscribe("ns.backend.kv_events")
    try:
        lib = ctypes.CDLL(KV_LIB)
        lib.dynamo_kv_publisher_connect.restype = ctypes.c_void_p
        lib.dynamo_kv_publisher_connect.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_longlong, ctypes.c_int,
        ]
        lib.dynamo_kv_publisher_publish.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_ulonglong), ctypes.c_int,
        ]

        def publish():
            h = lib.dynamo_kv_publisher_connect(
                b"127.0.0.1", port, b"ns.backend.kv_events", 42, 16
            )
            assert h
            arr = (ctypes.c_ulonglong * 3)(111, big, 333)
            assert lib.dynamo_kv_publisher_publish(h, b"stored", arr, 3) == 0
            assert lib.dynamo_kv_publisher_publish(h, b"removed", arr, 1) == 0
            assert lib.dynamo_kv_publisher_publish(h, b"stored", None, 1) == -1
            lib.dynamo_kv_publisher_close(ctypes.c_void_p(h))

        await asyncio.get_running_loop().run_in_executor(None, publish)
        events = []

        async def consume():
            async for _subj, payload in sub:
                events.append(
                    RouterEvent.model_validate(msgpack.unpackb(payload, raw=False))
                )
                if len(events) == 2:
                    return

        await asyncio.wait_for(consume(), 5)
        assert events[0].worker_id == 42
        assert events[0].event.op == "stored"
        assert events[0].event.block_hashes == [111, big, 333]
        assert events[0].event.token_block_size == 16
        assert events[1].event.op == "removed"
        assert [e.event_id for e in events] == [1, 2]
    finally:
        await sub.close()
        await client.close()


async def test_c_abi_kv_publisher_python_server(native_store_binary):
    """C publisher against the python StoreServer."""
    from dynamo_tpu.store.memory import MemoryStore
    from dynamo_tpu.store.server import StoreServer

    if not os.path.exists(KV_LIB):
        pytest.skip("kv publisher lib unavailable")
    server = StoreServer(MemoryStore(), port=0)
    await server.start()
    try:
        await _drive_c_publisher(server.port)
    finally:
        await server.stop()


async def test_c_abi_kv_publisher_native_server(native_store):
    """The no-python-in-the-path pairing: C publisher -> C++ coordinator."""
    if not os.path.exists(KV_LIB):
        pytest.skip("kv publisher lib unavailable")
    await _drive_c_publisher(native_store)


async def test_parked_pop_survives_client_disconnect(native_store):
    """A client that parks a blocking queue_pop and then disconnects must
    not leave the server holding a dangling Conn*: the next queue_push (and
    the sweep tick) previously dereferenced the freed connection. The
    message must be redelivered intact to a live consumer."""
    from dynamo_tpu.store.client import StoreClient

    victim = await StoreClient.connect("127.0.0.1", native_store)
    await victim.queue_len("uaf")  # ensure the queue exists server-side
    # park a long blocking pop, then drop the connection without unparking
    pop_task = asyncio.ensure_future(victim.queue_pop("uaf", timeout_s=30))
    await asyncio.sleep(0.3)  # let the pop reach the server and park
    await victim.close()
    with pytest.raises((ConnectionError, asyncio.CancelledError)):
        await pop_task

    c = await StoreClient.connect("127.0.0.1", native_store)
    try:
        # push triggers serve_parked() over the dead conn's parked entry
        await c.queue_push("uaf", b"survivor")
        await asyncio.sleep(0.3)  # span at least one sweep tick as well
        # the server must still be alive and must not have delivered the
        # message into the void: a live pop gets it
        m = await c.queue_pop("uaf", timeout_s=3)
        assert m is not None and m.payload == b"survivor"
        assert await c.queue_ack("uaf", m.id)
        # plain liveness probe after the dust settles
        assert await c.kv_put("uaf/alive", b"1") > 0
    finally:
        await c.close()


async def test_native_codec_randomized_roundtrip(native_store):
    """Property-style cross-implementation check (≈ the reference's
    proptest protocol validation): random keys/values — every bin length
    0..1KB, embedded NULs, high-bit bytes, unicode keys — must round-trip
    python-msgpack -> C++ decoder -> C++ encoder -> python-msgpack
    byte-identically through the native server's kv plane."""
    import random

    from dynamo_tpu.store.client import StoreClient

    rng = random.Random(0xD1CE)
    c = await StoreClient.connect("127.0.0.1", native_store)
    try:
        cases = []
        for i in range(120):
            key = f"fz/{i:03d}-" + "".join(
                rng.choice("abcxyz日本語🙂/._-") for _ in range(rng.randrange(0, 12))
            )
            value = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 1024)))
            cases.append((key, value))
        versions = {}
        for key, value in cases:
            versions[key] = await c.kv_put(key, value)
        for key, value in cases:
            e = await c.kv_get(key)
            assert e is not None and e.value == value, key
            assert e.version == versions[key]
        listed = await c.kv_get_prefix("fz/")
        assert len(listed) == len({k for k, _ in cases})
        assert [e.key for e in listed] == sorted({k for k, _ in cases})
        # object plane: a large binary blob with every byte value
        blob = bytes(range(256)) * 512  # 128 KiB
        await c.obj_put("fz", "blob", blob)
        assert await c.obj_get("fz", "blob") == blob
    finally:
        await c.close()


async def test_native_store_wal_survives_kill9(native_store_binary, tmp_path):
    """Durability parity with the python store (VERDICT r3 item 7): the
    native server WALs every acked mutation, so a kill -9 UNDER TRAFFIC
    (no SIGTERM snapshot, no 2s tick grace) loses nothing acked — KV,
    unacked queue messages (including in-flight, redelivered as ready),
    and the object plane all survive; acked messages never redeliver.
    Reference role: etcd raft log / JetStream file store
    (lib/runtime/src/transports/{etcd,nats}.rs)."""
    import signal

    from dynamo_tpu.store.client import StoreClient

    persist = str(tmp_path / "store.bin")

    def start():
        proc = subprocess.Popen(
            [native_store_binary, "--host", "127.0.0.1", "--port", "0",
             "--persist-path", persist],
            stdout=subprocess.PIPE,
        )
        line = proc.stdout.readline()
        assert line.startswith(b"LISTENING"), line
        return proc, int(line.split()[1])

    proc, port = start()
    try:
        c = await StoreClient.connect("127.0.0.1", port)
        await c.kv_put("model/reg", b"card-v1")
        await c.kv_put("model/other", b"x")
        await c.kv_delete("model/other")
        lid = await c.lease_grant(30.0)
        await c.kv_put("live/worker", b"ephemeral", lease_id=lid)
        for i in range(4):
            await c.queue_push("prefill", f"job-{i}".encode())
        # job-0 popped+acked (must NOT come back), job-1 popped but
        # UNACKED (in-flight at the kill: must come back ready)
        m0 = await c.queue_pop("prefill", timeout_s=1)
        assert m0.payload == b"job-0"
        assert await c.queue_ack("prefill", m0.id)
        m1 = await c.queue_pop("prefill", timeout_s=1)
        assert m1.payload == b"job-1"
        await c.obj_put("artifacts", "tok.json", b"{}")
        await c.close()
    finally:
        # hard kill: no SIGTERM handler, no final snapshot
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    proc, port = start()
    try:
        c = await StoreClient.connect("127.0.0.1", port)
        e = await c.kv_get("model/reg")
        assert e is not None and e.value == b"card-v1"
        assert await c.kv_get("model/other") is None
        # leased liveness key is ephemeral by design
        assert await c.kv_get("live/worker") is None
        # in-flight job-1 redelivers; job-2/3 still queued; job-0 never
        seen = []
        for _ in range(3):
            m = await c.queue_pop("prefill", timeout_s=1)
            assert m is not None
            seen.append(m.payload)
            await c.queue_ack("prefill", m.id)
        assert sorted(seen) == [b"job-1", b"job-2", b"job-3"]
        assert await c.queue_pop("prefill", timeout_s=0) is None
        assert await c.obj_get("artifacts", "tok.json") == b"{}"
        # new pushes must not collide with pre-crash message ids
        nid = await c.queue_push("prefill", b"post-crash")
        assert nid > m1.id
        await c.close()
    finally:
        proc.kill()
        proc.wait()


async def test_native_store_wal_compaction_no_double_delivery(
    native_store_binary, tmp_path
):
    """A snapshot (2s tick) folds WAL records in and truncates the log;
    messages folded into the snapshot must not ALSO replay from any
    surviving WAL records after a later crash."""
    import signal

    from dynamo_tpu.store.client import StoreClient

    persist = str(tmp_path / "store.bin")
    proc = subprocess.Popen(
        [native_store_binary, "--host", "127.0.0.1", "--port", "0",
         "--persist-path", persist],
        stdout=subprocess.PIPE,
    )
    line = proc.stdout.readline()
    port = int(line.split()[1])
    try:
        c = await StoreClient.connect("127.0.0.1", port)
        await c.queue_push("q", b"early")
        await asyncio.sleep(2.5)  # let the snapshot tick fold + truncate
        await c.queue_push("q", b"late")  # lands in the fresh WAL
        await c.close()
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()

    proc = subprocess.Popen(
        [native_store_binary, "--host", "127.0.0.1", "--port", "0",
         "--persist-path", persist],
        stdout=subprocess.PIPE,
    )
    line = proc.stdout.readline()
    port = int(line.split()[1])
    try:
        c = await StoreClient.connect("127.0.0.1", port)
        got = []
        while True:
            m = await c.queue_pop("q", timeout_s=0)
            if m is None:
                break
            got.append(m.payload)
            await c.queue_ack("q", m.id)
        assert sorted(got) == [b"early", b"late"]
        await c.close()
    finally:
        proc.kill()
        proc.wait()


async def test_native_store_does_not_charge_leases_for_its_frozen_time(
    native_store_binary,
):
    """Parity with store/memory.py's sweeper: a store that was frozen
    (its host stalled) extends the leases by the time it was deaf."""
    import signal

    from dynamo_tpu.store.client import StoreClient

    proc = subprocess.Popen(
        [native_store_binary, "--host", "127.0.0.1", "--port", "0"],
        stdout=subprocess.PIPE,
    )
    try:
        port = int(proc.stdout.readline().split()[1])
        c = await StoreClient.connect("127.0.0.1", port)
        lid = await c.lease_grant(1.0)
        await asyncio.sleep(0.3)  # the sweep has ticked
        proc.send_signal(signal.SIGSTOP)
        await asyncio.sleep(2.0)
        proc.send_signal(signal.SIGCONT)
        await asyncio.sleep(0.3)  # several sweeps since it woke
        assert await c.lease_keepalive(lid) is True
        await asyncio.sleep(1.6)  # nobody renews: it expires on time
        assert await c.lease_keepalive(lid) is False
        await c.close()
    finally:
        proc.kill()
        proc.wait()
