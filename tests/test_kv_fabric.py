"""Fleet KV fabric tests (docs/kvbm.md "Fleet fabric").

Ladder: catalog semantics on the dict backend, the pressure-driven G2
lifecycle on a virtual clock, never-dangling catalog invariants across
failed fetches, two-worker onboarding (in-process peer plane, then the
real store wire plane over loopback sockets), the router's discounted
fleet scoring (incl. the resume-racing-a-demotion regression), the
remote-bridge timeout surfacing, and the simulator's fabric off / on A/B.
"""

import asyncio
import threading

import numpy as np
import pytest

from dynamo_tpu.kvbm import (
    BlockLayout,
    DictCatalogBackend,
    FleetKvFabric,
    FleetPrefixCatalog,
    KvbmConfig,
    KvBlockManager,
    LocalPeerRegistry,
    PeerBlockServer,
    PressureConfig,
    StoreCatalogBackend,
    TcpPeerClient,
)
from dynamo_tpu.kvbm.fabric import TIER_DISK, TIER_HOST, TIER_SHARED
from dynamo_tpu.kvbm.remote import DictObjectStore

LAYOUT = BlockLayout(num_layers=2, block_size=4, num_kv_heads=2, head_dim=8)


def _block(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal(LAYOUT.packed_shape).astype(LAYOUT.np_dtype)


class FakeDevice:
    """Numpy 'device' cache + allocator hash index (test_kvbm.py)."""

    def __init__(self, num_blocks):
        self.blocks = np.zeros(
            (num_blocks, *LAYOUT.packed_shape), LAYOUT.np_dtype
        )
        self.hash_index: dict[int, int] = {}

    def gather(self, ids):
        return self.blocks[np.asarray(ids)]

    def scatter(self, ids, data):
        self.blocks[np.asarray(ids)] = data

    def resolve(self, h):
        return self.hash_index.get(h)


class TickClock:
    """Virtual time: the fabric's refresh throttle, touch recency, and
    catalog timestamps all read through this seam (DL009 vocabulary)."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now

    def time(self):
        return self.now

    async def sleep(self, seconds):
        self.now += seconds


def _manager(dev, host_blocks=8, disk_blocks=0, tmp=None, objects=None,
             clock=None):
    return KvBlockManager(
        KvbmConfig(
            host_num_blocks=host_blocks,
            disk_num_blocks=disk_blocks,
            disk_path=str(tmp / "kv.bin") if tmp else "",
            offload_batch=64,
            remote_bucket="kvg4" if objects is not None else "",
        ),
        LAYOUT,
        gather_fn=dev.gather,
        scatter_fn=dev.scatter,
        resolve_fn=dev.resolve,
        remote_objects=objects,
        clock=clock,
    )


def _fabric(backend, worker_id, clock=None, fetcher=None, addr="",
            pressure=None):
    cat = FleetPrefixCatalog(backend, worker_id=worker_id, clock=clock)
    return FleetKvFabric(
        cat, fetcher=fetcher, pressure=pressure, clock=clock, addr=addr,
        name=f"w{worker_id}",
    )


def _commit(dev, m, hashes, base_slot=0):
    """Commit blocks on the device and pump them into G2 (the offload
    batch is clamped to the host-tier size, so drain in a loop)."""
    for i, h in enumerate(hashes):
        dev.blocks[base_slot + i] = _block(h)
        dev.hash_index[h] = base_slot + i
        m.on_block_committed(h, base_slot + i)
    m.pump()
    while m.pending_offloads:
        m.pump()


# ---------------------------------------------------------------------------
# Catalog semantics
# ---------------------------------------------------------------------------


def test_catalog_publish_match_and_tier_preference():
    backend = DictCatalogBackend()
    clock = TickClock()
    a = FleetPrefixCatalog(backend, worker_id=1, clock=clock)
    b = FleetPrefixCatalog(backend, worker_id=2, clock=clock)
    a.publish(11, TIER_HOST, 64, addr="a:1")
    clock.now += 1.0
    b.publish(11, TIER_SHARED, 64)
    a.publish(12, TIER_HOST, 64, addr="a:1")
    a.publish(13, TIER_DISK, 64)  # g3 is private: not fleet-fetchable
    b.refresh()
    # shared-bucket copies sort first (no peer round trip needed)
    locs = b.locations(11)
    assert [e["tier"] for _, e in locs] == [TIER_SHARED, TIER_HOST]
    # leading-run semantics: 11, 12 fetchable; 13 only has a g3 copy
    assert b.match_prefix([11, 12, 13]) == 2
    # a worker's own copies don't count as fleet-fetchable for itself
    assert b.match_prefix([11], exclude_worker=2) == 1
    a.refresh()
    assert a.match_prefix([11], exclude_worker=1) == 1  # b's g4 copy
    # prune-on-evict: a's retier to g3 leaves only b's g4 claim
    a.retier(11, TIER_DISK)
    b.refresh()
    assert [e["tier"] for _, e in b.locations(11)] == [TIER_SHARED]
    b.prune(11)
    b.refresh()
    assert b.match_prefix([11]) == 0


def test_pump_publishes_and_evictions_never_dangle(tmp_path):
    """Every G2 landing publishes; every eviction retiers (g3/g4) or
    prunes — after arbitrary churn, every catalog entry names a tier
    that really holds the block."""
    backend = DictCatalogBackend()
    clock = TickClock()
    dev = FakeDevice(16)
    objects = DictObjectStore()
    m = _manager(dev, host_blocks=2, disk_blocks=2, tmp=tmp_path,
                 objects=objects, clock=clock)
    fab = _fabric(backend, worker_id=1, clock=clock)
    fab.attach(m)
    try:
        _commit(dev, m, [101, 102, 103, 104, 105])  # churn 5 through 2+2
        view = backend.snapshot()
        for h in (101, 102, 103, 104, 105):
            entry = view[h][1]
            tier = entry["tier"]
            if tier == TIER_HOST:
                assert m.host.contains(h)
            elif tier == TIER_DISK:
                assert m.disk.contains(h)
            elif tier == TIER_SHARED:
                assert m.remote.contains(h)
            else:  # pragma: no cover - would be the dangling bug
                pytest.fail(f"unknown tier {tier!r} for {h:x}")
        assert fab.stats.published_blocks >= 5
    finally:
        m.close()


def test_host_evict_without_lower_tier_prunes():
    backend = DictCatalogBackend()
    dev = FakeDevice(8)
    m = _manager(dev, host_blocks=1)  # no disk, no remote: evict = drop
    # watermarks above 1.0 disable pressure so the LRU path is isolated
    fab = _fabric(backend, worker_id=1,
                  pressure=PressureConfig(high_watermark=2.0,
                                          low_watermark=1.5))
    fab.attach(m)
    _commit(dev, m, [21])
    assert backend.snapshot()[21][1]["tier"] == TIER_HOST
    _commit(dev, m, [22], base_slot=2)  # LRU-evicts 21 with nowhere to go
    assert 21 not in backend.snapshot()  # pruned, not dangling
    assert backend.snapshot()[22][1]["tier"] == TIER_HOST
    assert fab.stats.pruned_blocks >= 1


# ---------------------------------------------------------------------------
# Pressure-driven lifecycle (virtual clock)
# ---------------------------------------------------------------------------


def test_pressure_demotes_popularity_weighted_victims(tmp_path):
    """Fill G2 past the high watermark on virtual time: cold blocks go
    to private disk, hot (touched) ones to the shared bucket, and
    occupancy lands at the low watermark."""
    backend = DictCatalogBackend()
    clock = TickClock()
    dev = FakeDevice(16)
    objects = DictObjectStore()
    m = _manager(dev, host_blocks=10, disk_blocks=8, tmp=tmp_path,
                 objects=objects, clock=clock)
    pressure = PressureConfig(high_watermark=0.85, low_watermark=0.5,
                              hot_min_touches=2)
    fab = _fabric(backend, worker_id=1, clock=clock, pressure=pressure)
    fab.attach(m)
    try:
        hashes = list(range(201, 209))  # 8 of 10: below the watermark
        _commit(dev, m, hashes)
        assert m.host.num_cached == 8
        assert fab.stats.demoted_shared == fab.stats.demoted_disk == 0
        # popularity: the first two blocks are hot (2 touches)
        fab.note_touch([201, 202])
        clock.now += 1.0
        fab.note_touch([201, 202])
        # two more landings push occupancy to 10 > 8.5: demote to 5
        _commit(dev, m, [209, 210], base_slot=10)
        assert m.host.num_cached == 5
        # hot survivors stay in G2 (cold blocks were better victims)
        assert m.host.contains(201) and m.host.contains(202)
        demoted = [h for h in range(201, 211) if not m.host.contains(h)]
        view = backend.snapshot()
        for h in demoted:
            tier = view[h][1]["tier"]
            assert tier in (TIER_DISK, TIER_SHARED)
            # cold victims are private-disk bound in this config
            assert tier == TIER_DISK
            assert m.disk.contains(h)
        assert fab.stats.demoted_disk == 5
    finally:
        m.close()


def test_pressure_routes_hot_victims_to_shared_bucket():
    """With a tiny low watermark even hot blocks demote — and they land
    in the shared G4 bucket (fleet-fetchable), not private disk."""
    backend = DictCatalogBackend()
    clock = TickClock()
    dev = FakeDevice(16)
    objects = DictObjectStore()
    m = _manager(dev, host_blocks=4, objects=objects, clock=clock)
    pressure = PressureConfig(high_watermark=0.6, low_watermark=0.2,
                              hot_min_touches=2)
    fab = _fabric(backend, worker_id=1, clock=clock, pressure=pressure)
    fab.attach(m)
    _commit(dev, m, [301, 302])
    for _ in range(2):
        fab.note_touch([301, 302])
        clock.now += 1.0
    _commit(dev, m, [303], base_slot=4)  # 3 > 2.4: demote to <= 0.8
    view = backend.snapshot()
    shared = [h for h in (301, 302, 303)
              if view.get(h, {}).get(1, {}).get("tier") == TIER_SHARED]
    assert shared and all(m.remote.contains(h) for h in shared)
    assert fab.stats.demoted_shared == len(shared) > 0


def test_degradation_rung_tightens_watermarks():
    """The planner ladder's "demote cold KV" rung scales the fabric's
    watermarks down — rung N makes the same occupancy demote earlier."""
    from dynamo_tpu.planner.degradation import LadderPolicy, ServingDegradation

    policy = LadderPolicy()
    assert policy.fabric_pressure_scale(0) == 1.0
    assert policy.fabric_pressure_scale(1) == pytest.approx(0.75)
    assert policy.fabric_pressure_scale(2) == pytest.approx(0.5625)
    assert policy.fabric_pressure_scale(9) == pytest.approx(
        max(0.25, 0.75 ** 3)
    )

    backend = DictCatalogBackend()
    clock = TickClock()
    dev = FakeDevice(16)
    m = _manager(dev, host_blocks=10, clock=clock)
    fab = _fabric(backend, worker_id=1, clock=clock,
                  pressure=PressureConfig(high_watermark=0.9,
                                          low_watermark=0.6))
    fab.attach(m)
    _commit(dev, m, list(range(401, 409)))  # 8 of 10: below 9.0
    assert m.host.num_cached == 8
    hooks = ServingDegradation(policy=policy, fabric=fab)
    hooks.set_level(2)  # scale 0.5625: high watermark now 5.06 blocks
    assert fab._pressure_scale == pytest.approx(0.5625)
    m.pump()  # no new offloads; the pressure pass runs anyway
    assert m.host.num_cached <= int(0.6 * 0.5625 * 10)
    hooks.set_level(0)
    assert fab._pressure_scale == 1.0


# ---------------------------------------------------------------------------
# Two-worker onboarding (the tentpole's acceptance path)
# ---------------------------------------------------------------------------


def test_two_workers_share_prefix_via_peer_plane():
    """Worker A prefills a prefix; worker B onboards it from A's host
    tier through the peer plane — B never recomputes, and the bytes are
    bit-identical."""
    backend = DictCatalogBackend()
    clock = TickClock()
    peers = LocalPeerRegistry()

    dev_a = FakeDevice(8)
    a = _manager(dev_a, host_blocks=8, clock=clock)
    fab_a = _fabric(backend, worker_id=1, clock=clock, fetcher=peers)
    fab_a.addr = peers.register("a", a.export_host_blocks)
    fab_a.attach(a)
    hashes = [501, 502, 503]
    _commit(dev_a, a, hashes)  # A prefilled: blocks live in A's G2

    dev_b = FakeDevice(8)
    b = _manager(dev_b, host_blocks=8, clock=clock)
    fab_b = _fabric(backend, worker_id=2, clock=clock, fetcher=peers)
    fab_b.attach(b)
    fab_b.catalog.refresh()
    assert fab_b.catalog.match_prefix(hashes, exclude_worker=2) == 3
    assert b.match_offloaded(hashes) == 0  # nothing local yet

    n = b.onboard(hashes, [3, 4, 5])
    assert n == 3  # onboarded, not recomputed
    for slot, h in zip((3, 4, 5), hashes):
        np.testing.assert_array_equal(dev_b.blocks[slot], _block(h))
    assert fab_b.stats.fleet_hits_peer == 3
    assert b.host.contains(501)  # fetched blocks now serve B's repeats
    # and B now advertises its own G2 copies
    assert len(backend.snapshot()[501]) == 2


def test_two_workers_share_via_bucket_adoption():
    """A catalog g4 entry onboards through bucket adoption (no peer
    round trip): worker B learns the key exists without waiting for the
    periodic G4 list refresh."""
    backend = DictCatalogBackend()
    clock = TickClock()
    objects = DictObjectStore()

    dev_a = FakeDevice(8)
    a = _manager(dev_a, host_blocks=1, objects=objects, clock=clock)
    fab_a = _fabric(backend, worker_id=1, clock=clock)
    fab_a.attach(a)
    _commit(dev_a, a, [601])
    _commit(dev_a, a, [602], base_slot=2)  # evicts 601 -> shared bucket
    assert backend.snapshot()[601][1]["tier"] == TIER_SHARED

    dev_b = FakeDevice(8)
    b = _manager(dev_b, host_blocks=4, objects=DictObjectStore(),
                 clock=clock)
    # B's own bucket is EMPTY; share A's object plane like production
    b.remote.objects = objects
    b.remote._known.clear()
    fab_b = _fabric(backend, worker_id=2, clock=clock)
    fab_b.attach(b)
    fab_b.catalog.refresh()
    assert b.onboard([601], [3]) == 1
    np.testing.assert_array_equal(dev_b.blocks[3], _block(601))
    assert fab_b.stats.fleet_hits_bucket == 1


def test_two_workers_over_store_wire_plane():
    """The full store-plane path: catalog in a real (in-memory) store
    reached through the blocking bridge, blocks served over loopback
    sockets with store/wire.py framing."""
    from dynamo_tpu.store.memory import MemoryStore

    loop = asyncio.new_event_loop()
    ready = threading.Event()

    def _run_loop():
        asyncio.set_event_loop(loop)
        ready.set()
        loop.run_forever()

    t = threading.Thread(target=_run_loop, name="store-loop", daemon=True)
    t.start()
    ready.wait(5)

    async def _mkstore():
        return MemoryStore()

    store = asyncio.run_coroutine_threadsafe(_mkstore(), loop).result(5)
    try:
        backend_a = StoreCatalogBackend(store, "testns", loop, timeout_s=5.0)
        backend_b = StoreCatalogBackend(store, "testns", loop, timeout_s=5.0)

        dev_a = FakeDevice(8)
        a = _manager(dev_a, host_blocks=8)
        server = PeerBlockServer(a.export_host_blocks)
        addr = asyncio.run_coroutine_threadsafe(server.start(), loop).result(5)
        fab_a = _fabric(backend_a, worker_id=1, addr=addr)
        fab_a.attach(a)
        hashes = [701, 702]
        _commit(dev_a, a, hashes)

        dev_b = FakeDevice(8)
        b = _manager(dev_b, host_blocks=8)
        fab_b = _fabric(backend_b, worker_id=2, fetcher=TcpPeerClient())
        fab_b.attach(b)
        fab_b.catalog.refresh()  # snapshot over the store plane
        assert fab_b.catalog.match_prefix(hashes, exclude_worker=2) == 2
        assert b.onboard(hashes, [3, 4]) == 2
        for slot, h in zip((3, 4), hashes):
            np.testing.assert_array_equal(dev_b.blocks[slot], _block(h))
        assert fab_b.stats.fleet_hits_peer == 2
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(5)
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)


def test_failed_fetch_prunes_and_falls_back_to_recompute():
    """A catalog hit whose every advertised copy is gone must prune the
    entries and read as a clean miss — the engine recomputes, nothing
    raises, nothing dangles."""

    class DeadPeer(LocalPeerRegistry):
        def fetch(self, addr, seq_hashes):
            return None  # peer unreachable

    backend = DictCatalogBackend()
    clock = TickClock()
    backend.put(801, 9, {"tier": TIER_HOST, "bytes": 64, "t": 0.0,
                         "addr": "dead:1"})
    dev = FakeDevice(8)
    m = _manager(dev, host_blocks=4, clock=clock)
    fab = _fabric(backend, worker_id=2, clock=clock, fetcher=DeadPeer())
    fab.attach(m)
    fab.catalog.refresh()
    assert fab.catalog.match_prefix([801], exclude_worker=2) == 1
    assert m.onboard([801], [3]) == 0  # clean miss: engine recomputes
    assert fab.stats.dangling_pruned == 1
    assert 801 not in backend.snapshot()  # advertised owner pruned
    fab.catalog.refresh()
    assert fab.catalog.match_prefix([801]) == 0


def test_fetch_length_mismatch_is_a_miss():
    class ShortPeer(LocalPeerRegistry):
        def fetch(self, addr, seq_hashes):
            return [b"\x00" * 7 for _ in seq_hashes]  # wrong size

    backend = DictCatalogBackend()
    backend.put(811, 9, {"tier": TIER_HOST, "bytes": 64, "t": 0.0,
                         "addr": "short:1"})
    dev = FakeDevice(8)
    m = _manager(dev, host_blocks=4)
    fab = _fabric(backend, worker_id=2, fetcher=ShortPeer())
    fab.attach(m)
    fab.catalog.refresh()
    assert m.onboard([811], [3]) == 0
    assert fab.stats.fetch_failures >= 1
    assert not m.host.contains(811)  # corrupt bytes never land


# ---------------------------------------------------------------------------
# Router: discounted fleet scoring + the resume/demotion race
# ---------------------------------------------------------------------------


class _FixedCatalog:
    def __init__(self, blocks):
        self.blocks = blocks

    def match_prefix(self, seq_hashes):
        return min(self.blocks, len(seq_hashes))


def _scheduler(catalog=None):
    from dynamo_tpu.kv_router.indexer import KvIndexer
    from dynamo_tpu.kv_router.scheduler import KvMetricsAggregator, KvScheduler

    indexer = KvIndexer(block_size=4)
    agg = KvMetricsAggregator()
    captured = {}

    def selector(overlaps, metrics, candidates):
        captured["scores"] = dict(overlaps.scores)
        return sorted(candidates)[0]

    sched = KvScheduler(indexer, agg, selector=selector,
                        fleet_catalog=catalog)
    return sched, indexer, captured


def test_fleet_blocks_score_at_discounted_weight():
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

    sched, indexer, captured = _scheduler(_FixedCatalog(blocks=4))
    sched.aggregator.update(ForwardPassMetrics(worker_id=1))
    sched.aggregator.update(ForwardPassMetrics(worker_id=2))
    prompt = list(range(32))  # 8 blocks
    decision = sched.schedule(prompt, [1, 2])
    w = sched.fleet_hit_weight
    # no local overlap anywhere: both candidates score w*fleet
    assert captured["scores"][1] == pytest.approx(w * 4)
    assert captured["scores"][2] == pytest.approx(w * 4)
    assert decision.fleet_blocks == 4
    assert decision.overlap_blocks == 0  # decision reports TRUE overlap


def test_local_overlap_dominates_fleet_extension():
    """A worker's local blocks count at full weight; the fleet term only
    tops up the REMAINDER at the discount — local copies never get
    double-counted and fleet blocks never reach local weight."""
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
    from tests.test_kv_router import _seq_hashes, _stored

    sched, indexer, captured = _scheduler(_FixedCatalog(blocks=6))
    prompt = list(range(32))  # 8 blocks
    indexer.apply(_stored(1, _seq_hashes(prompt)[:6]))
    indexer.apply(_stored(2, _seq_hashes(prompt)[:2]))
    sched.aggregator.update(ForwardPassMetrics(worker_id=1))
    sched.aggregator.update(ForwardPassMetrics(worker_id=2))
    sched.schedule(prompt, [1, 2])
    w = sched.fleet_hit_weight
    assert captured["scores"][1] == pytest.approx(6)  # local covers fleet
    assert captured["scores"][2] == pytest.approx(2 + w * 4)
    assert captured["scores"][2] < captured["scores"][1]


def test_resume_racing_demotion_keeps_fleet_discount():
    """The satellite regression: a resume whose prefix was JUST demoted
    off every device (local overlap gone, catalog still hits) must score
    boost*weight*fleet — never boost*fleet as if still resident."""
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
    from tests.test_kv_router import _seq_hashes, _stored

    sched, indexer, captured = _scheduler(_FixedCatalog(blocks=8))
    prompt = list(range(32))  # 8 blocks
    sched.aggregator.update(ForwardPassMetrics(worker_id=1))
    sched.aggregator.update(ForwardPassMetrics(worker_id=2))
    # the demotion race: NO worker has local overlap anymore
    decision = sched.schedule(prompt, [1, 2], resume=True)
    boost = sched.resume_overlap_boost
    w = sched.fleet_hit_weight
    assert captured["scores"][1] == pytest.approx(boost * w * 8)
    assert captured["scores"][1] < boost * 8  # never local weight
    assert decision.fleet_blocks == 8

    # contrast: a resume onto a still-resident prefix boosts LOCAL weight
    indexer.apply(_stored(1, _seq_hashes(prompt)))
    sched.schedule(prompt, [1, 2], resume=True)
    assert captured["scores"][1] == pytest.approx(boost * 8)
    # the fleet-only candidate stays discounted under the same boost
    assert captured["scores"][2] == pytest.approx(boost * w * 8)


def test_catalog_failure_never_breaks_routing():
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

    class Exploding:
        def match_prefix(self, seq_hashes):
            raise RuntimeError("store down")

    sched, _, captured = _scheduler(Exploding())
    sched.aggregator.update(ForwardPassMetrics(worker_id=1))
    decision = sched.schedule(list(range(8)), [1])
    assert decision.worker_id == 1 and decision.fleet_blocks == 0


# ---------------------------------------------------------------------------
# Remote-bridge timeout surfacing (satellite: remote.py _run)
# ---------------------------------------------------------------------------


def test_store_timeout_surfaces_op_and_books_counter():
    from dynamo_tpu.kvbm.remote import StoreRoundTripTimeout, run_on_loop
    from dynamo_tpu.telemetry.instruments import KVBM_REMOTE_TIMEOUTS

    records = []

    class Recorder:
        def record(self, kind, **kw):
            records.append((kind, kw))

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    async def hang():
        await asyncio.sleep(60)

    before = KVBM_REMOTE_TIMEOUTS.labels("get_many").value
    try:
        with pytest.raises(StoreRoundTripTimeout) as exc:
            run_on_loop(hang(), loop, timeout_s=0.05, op="get_many",
                        recorder=Recorder())
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
    # the exception carries WHICH plane stalled, not a bare TimeoutError
    assert exc.value.op == "get_many"
    assert exc.value.timeout_s == pytest.approx(0.05)
    assert "get_many" in str(exc.value)
    assert isinstance(exc.value, TimeoutError)  # callers' except clauses
    assert KVBM_REMOTE_TIMEOUTS.labels("get_many").value == before + 1
    assert records and records[0][0] == "kvbm_remote_timeout"
    assert records[0][1]["op"] == "get_many"


def test_catalog_timeout_degrades_not_raises_into_routing():
    """A StoreCatalogBackend timeout surfaces as StoreRoundTripTimeout
    with op=catalog.*; the fabric's refresh path swallows it (the pump
    must degrade to single-worker behavior, not die)."""
    from dynamo_tpu.kvbm.remote import StoreRoundTripTimeout

    class HangingStore:
        async def kv_get_prefix(self, prefix):
            await asyncio.sleep(60)

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    try:
        backend = StoreCatalogBackend(HangingStore(), "ns", loop,
                                      timeout_s=0.05)
        cat = FleetPrefixCatalog(backend, worker_id=1)
        with pytest.raises(StoreRoundTripTimeout) as exc:
            cat.refresh()
        assert exc.value.op == "catalog.snapshot"
        fab = FleetKvFabric(cat)
        fab._last_refresh = -1e9
        fab.maybe_refresh()  # swallowed: logged, not raised
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# Simulator A/B
# ---------------------------------------------------------------------------


def _sim_ab(duration=120.0, seed=7):
    from dynamo_tpu.sim import FleetSim, SimConfig, diurnal_trace
    from dynamo_tpu.sim.traces import PrefixModel

    trace = diurnal_trace(duration, seed, base_rps=8.0, peak_rps=24.0,
                          period_s=duration, prefixes=PrefixModel())
    out = {}
    for fabric in (False, True):
        cfg = SimConfig(initial_decode=4, initial_prefill=1,
                        max_queue_depth=200, fabric=fabric)
        out[fabric] = FleetSim(trace, cfg).run()["fabric"]
    return out


def test_sim_fabric_ab_fewer_reprefill_tokens():
    """The acceptance A/B: fabric on shows a positive fleet hit rate and
    STRICTLY fewer prefilled (recomputed) tokens than fabric off."""
    res = _sim_ab()
    off, on = res[False], res[True]
    assert off["enabled"] is False and on["enabled"] is True
    assert on["fleet_hit_rate"] > 0
    assert on["reprefill_tokens_avoided"] > 0
    assert on["prefilled_tokens"] < off["prefilled_tokens"]
    # conservation: every prompt token is either recomputed or fetched
    assert (on["prefilled_tokens"] + on["fleet_fetched_tokens"]
            == off["prefilled_tokens"])


def test_sim_fabric_ab_deterministic():
    a, b = _sim_ab(duration=60.0), _sim_ab(duration=60.0)
    assert a == b


def test_fabric_debug_stanza_registered():
    from dynamo_tpu.telemetry.debug import collect_debug_state

    backend = DictCatalogBackend()
    dev = FakeDevice(8)
    m = _manager(dev, host_blocks=4)
    fab = _fabric(backend, worker_id=3)
    fab.attach(m)
    try:
        _commit(dev, m, [901, 902])
        state = collect_debug_state()
        stanza = state["kvfleet:w3"]
        assert stanza["catalog"]["entries"] == 2
        assert stanza["watermarks"]["high"] == pytest.approx(0.90)
        assert stanza["resident_tracked"] == 2
    finally:
        m.close()  # unregisters the provider
    assert "kvfleet:w3" not in collect_debug_state()
