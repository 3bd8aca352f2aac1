"""Graceful drain & rolling restarts (ISSUE 20, docs/robustness.md
"Graceful drain & rolling restarts"): the discovery-level DRAINING
flag and Client filtering, the KV scheduler's drain-aware scoring, the
engine's migrate-eligibility mirror, the fabric's hot-prefix handoff,
the DrainCoordinator state machine, the worker-control subject
round-trip, the planner's rolling_restart, and the sim's kill-vs-drain
A/B. The live SIGTERM-mid-stream proof is
tests/test_cli_drain_e2e.py; the fault-point seams are covered in
tests/test_faults.py."""

import asyncio
import json
from types import SimpleNamespace

import msgpack
import pytest

from dynamo_tpu.runtime.component import Client, Instance, _decode_instance
from dynamo_tpu.runtime.drain import (
    DEFAULT_DRAIN_TIMEOUT_S,
    DrainCoordinator,
    DrainResult,
    drain_timeout_from_env,
    request_drain,
    serve_drain_control,
    worker_control_subject,
)


def _inst(iid: int, draining: bool = False) -> Instance:
    return Instance(
        instance_id=iid, host="127.0.0.1", port=9000 + iid,
        namespace="ns", component="backend", endpoint="generate",
        draining=draining,
    )


# ---------------------------------------------------------------------------
# Discovery: the DRAINING flag through decode + Client filtering
# ---------------------------------------------------------------------------


def test_decode_instance_reads_draining_flag():
    key = "instances/ns/backend/generate:a1"
    plain = msgpack.packb({"host": "h", "port": 1}, use_bin_type=True)
    flagged = msgpack.packb(
        {"host": "h", "port": 1, "draining": True}, use_bin_type=True
    )
    assert _decode_instance(key, plain).draining is False
    assert _decode_instance(key, flagged).draining is True
    # the flag rides the SAME key: a re-put flips the existing entry
    assert _decode_instance(key, flagged).instance_id == 0xA1


def test_client_excludes_draining_from_fresh_placement():
    """The satellite bugfix in one seam: BOTH routers and the resume
    path pick from instance_ids(), so filtering here keeps resumes off
    workers that are themselves on the way out."""
    c = Client(endpoint=None, static_instance=_inst(1))
    c.instances[2] = _inst(2, draining=True)
    c.instances[3] = _inst(3)
    assert c.instance_ids() == [1, 3]
    assert c.instance_ids(include_draining=True) == [1, 2, 3]
    assert c.draining_ids() == {2}


def test_client_two_draining_workers_leave_only_third():
    """Regression (ISSUE 20): with two of three workers draining, fresh
    placement AND resumes must land on the third — previously a resume
    could re-dial a draining worker and bounce."""
    c = Client(endpoint=None, static_instance=_inst(1, draining=True))
    c.instances[2] = _inst(2, draining=True)
    c.instances[3] = _inst(3)
    assert c.instance_ids() == [3]


async def test_client_wait_event_tracks_routable_instances_only():
    """wait_for_instances must not unblock onto an all-draining fleet."""
    c = Client(endpoint=None, static_instance=_inst(1))
    c.instances[1] = _inst(1, draining=True)
    c._refresh_event()
    assert not c._instances_event.is_set()
    with pytest.raises(asyncio.TimeoutError):
        await c.wait_for_instances(timeout_s=0.05)
    c.instances[2] = _inst(2)
    c._refresh_event()
    assert await c.wait_for_instances(timeout_s=1.0) == [2]


# ---------------------------------------------------------------------------
# KV scheduler: drain-aware candidate filtering + overlap reclassification
# ---------------------------------------------------------------------------


def _scheduler(fleet_catalog=None):
    from dynamo_tpu.kv_router.indexer import KvIndexer
    from dynamo_tpu.kv_router.scheduler import KvMetricsAggregator, KvScheduler

    indexer = KvIndexer(block_size=4)
    captured = {}

    def selector(overlaps, metrics, candidates):
        captured["scores"] = dict(overlaps.scores)
        captured["candidates"] = list(candidates)
        return sorted(candidates)[0]

    sched = KvScheduler(
        indexer, KvMetricsAggregator(), selector=selector,
        fleet_catalog=fleet_catalog,
    )
    return sched, indexer, captured


def test_scheduler_excludes_draining_candidates():
    sched, _, captured = _scheduler()
    d = sched.schedule(list(range(8)), [1, 2, 3], draining={1, 2})
    assert captured["candidates"] == [3]
    assert d.worker_id == 3


def test_scheduler_all_draining_falls_back_to_full_set():
    """Defensive: if filtering would empty the candidate set, serve
    SOMEWHERE rather than erroring — the draining worker still answers
    in-flight dials for its drain window."""
    sched, _, captured = _scheduler()
    sched.schedule(list(range(8)), [1, 2], draining={1, 2})
    assert captured["candidates"] == [1, 2]


def test_scheduler_counts_draining_overlap_as_fleet():
    """A draining worker's indexed prefix doesn't vanish: the drain
    retiers it into the shared bucket, so every surviving candidate
    scores it at fleet_hit_weight — not local weight, and not zero."""
    from tests.test_kv_router import _seq_hashes, _stored

    sched, indexer, captured = _scheduler()
    prompt = list(range(32))  # 8 blocks
    indexer.apply(_stored(1, _seq_hashes(prompt)[:6]))  # draining holds 6
    sched.schedule(prompt, [1, 2, 3], draining={1})
    w = sched.fleet_hit_weight
    assert captured["candidates"] == [2, 3]
    assert captured["scores"][2] == pytest.approx(w * 6)
    assert captured["scores"][3] == pytest.approx(w * 6)


# ---------------------------------------------------------------------------
# Engine: migrate-eligibility mirror of migration.resumable()
# ---------------------------------------------------------------------------


def test_engine_drain_migratable_mirrors_resume_eligibility():
    from dynamo_tpu.engine.engine import JaxEngine

    ok = SimpleNamespace(migration=None, guided=None, sampling=None)
    opted_out = SimpleNamespace(migration=False, guided=None, sampling=None)
    guided = SimpleNamespace(migration=None, guided=object(), sampling=None)
    penalties = SimpleNamespace(
        migration=None, guided=None,
        sampling=SimpleNamespace(needs_penalties=True),
    )
    plain_sampling = SimpleNamespace(
        migration=None, guided=None,
        sampling=SimpleNamespace(needs_penalties=False),
    )
    mig = JaxEngine._drain_migratable
    assert mig(ok) and mig(plain_sampling)
    assert not mig(opted_out)
    assert not mig(guided)
    assert not mig(penalties)


# ---------------------------------------------------------------------------
# Fabric: on_drain pushes hot G2 prefixes into the shared bucket
# ---------------------------------------------------------------------------


def test_fabric_on_drain_demotes_hot_blocks_to_shared(tmp_path):
    from dynamo_tpu.kvbm import DictCatalogBackend
    from dynamo_tpu.kvbm.fabric import TIER_SHARED
    from dynamo_tpu.kvbm.remote import DictObjectStore
    from tests.test_kv_fabric import (
        FakeDevice, TickClock, _commit, _fabric, _manager,
    )

    clock = TickClock()
    dev = FakeDevice(16)
    objects = DictObjectStore()
    m = _manager(dev, host_blocks=8, tmp=tmp_path, objects=objects,
                 clock=clock)
    backend = DictCatalogBackend()
    fab = _fabric(backend, worker_id=1, clock=clock)
    fab.attach(m)
    try:
        _commit(dev, m, [201, 202, 203])
        # 201/202 are hot (>= hot_min_touches); 203 is cold
        fab._resident[201].touches = 2
        fab._resident[202].touches = 3
        demoted = fab.on_drain()
        assert demoted == 2
        view = backend.snapshot()
        assert view[201][1]["tier"] == TIER_SHARED
        assert view[202][1]["tier"] == TIER_SHARED
        # the cold block keeps its host-tier claim: peer-fetchable for
        # the drain window, gone with the lease after exit
        assert view[203][1]["tier"] != TIER_SHARED
        assert not m.host.contains(201) and not m.host.contains(202)
        assert m.remote.contains(201) and m.remote.contains(202)
    finally:
        fab.close()


def test_fabric_on_drain_respects_max_blocks_and_needs_remote(tmp_path):
    from dynamo_tpu.kvbm import DictCatalogBackend
    from dynamo_tpu.kvbm.remote import DictObjectStore
    from tests.test_kv_fabric import (
        FakeDevice, TickClock, _commit, _fabric, _manager,
    )

    clock = TickClock()
    dev = FakeDevice(16)
    m = _manager(dev, host_blocks=8, tmp=tmp_path,
                 objects=DictObjectStore(), clock=clock)
    fab = _fabric(DictCatalogBackend(), worker_id=1, clock=clock)
    fab.attach(m)
    try:
        _commit(dev, m, [301, 302, 303])
        for h in (301, 302, 303):
            fab._resident[h].touches = 5
        assert fab.on_drain(max_blocks=1) == 1  # deadline-bounded sweep
    finally:
        fab.close()

    # no shared bucket attached: nothing to hand off, clean no-op
    dev2 = FakeDevice(16)
    m2 = _manager(dev2, host_blocks=8)
    fab2 = _fabric(DictCatalogBackend(), worker_id=2)
    fab2.attach(m2)
    try:
        _commit(dev2, m2, [401])
        fab2._resident[401].touches = 5
        assert fab2.on_drain() == 0
    finally:
        fab2.close()


# ---------------------------------------------------------------------------
# DrainCoordinator state machine (fault-seam paths live in test_faults.py)
# ---------------------------------------------------------------------------


class _Store:
    def __init__(self):
        self.deleted = []

    async def kv_delete(self, key):
        self.deleted.append(key)
        return True


class _Endpoint:
    def __init__(self):
        self.drained = []

    async def set_draining(self, instance):
        self.drained.append(instance)


class _Component:
    def __init__(self, instances):
        self._instances = instances

    async def list_instances(self):
        return self._instances


class _Engine:
    def __init__(self, active=0, fabric=None, migrate_on_drain=True):
        self._active = active
        self.drain_begun = False
        self.drain_migrated = 0
        self._migrate = migrate_on_drain
        self.kvbm = (
            SimpleNamespace(fabric=fabric) if fabric is not None else None
        )

    def active_streams(self):
        return self._active

    def begin_drain(self):
        self.drain_begun = True
        if self._migrate:
            self.drain_migrated += self._active
            self._active = 0

    async def acall_on_thread(self, fn, *args):
        return fn(*args)


def _coord(engine, peers="healthy", **kw):
    me = _inst(0xAA)
    if peers == "healthy":
        instances = [me, _inst(0xBB)]
    elif peers == "draining":
        instances = [me, _inst(0xBB, draining=True)]
    else:
        instances = [me]
    kw.setdefault("timeout_s", 0.2)
    return DrainCoordinator(
        SimpleNamespace(store=_Store()), _Component(instances),
        _Endpoint(), me, engine=engine, poll_interval_s=0.01, **kw,
    )


async def test_coordinator_completed_path_publishes_and_deregisters():
    eng = _Engine(active=3)
    coord = _coord(eng)
    res = await coord.drain()
    assert res == DrainResult(
        result="completed", streams_migrated=3,
        elapsed_s=res.elapsed_s, fabric_blocks_shared=0,
    )
    assert eng.drain_begun
    assert len(coord.endpoint.drained) == 1
    assert coord.drt.store.deleted == [coord.instance.path]


async def test_coordinator_fabric_handoff_counts_blocks():
    fabric = SimpleNamespace(on_drain=lambda max_blocks=None: 7)
    coord = _coord(_Engine(active=0, fabric=fabric))
    res = await coord.drain()
    assert res.fabric_blocks_shared == 7
    assert res.result == "completed"


async def test_coordinator_deadline_when_streams_cannot_migrate():
    """Ineligible streams (guided / penalties / opted out) get the
    window; past the deadline the worker leaves anyway and the reactive
    machinery owns the rest."""
    eng = _Engine(active=2, migrate_on_drain=False)
    coord = _coord(eng, timeout_s=0.1)
    res = await coord.drain()
    assert res.result == "deadline"
    assert eng.drain_begun  # proactive sweep WAS attempted
    assert coord.drt.store.deleted  # deregistration is unconditional


async def test_coordinator_no_peer_serves_out_the_window():
    """A draining-only fleet counts as no peer: MIGRATE handoffs would
    only bounce, so the engine keeps serving and the distinct no_peer
    outcome reaches the operator."""
    eng = _Engine(active=1)
    coord = _coord(eng, peers="draining", timeout_s=0.1)
    res = await coord.drain()
    assert res.result == "no_peer"
    assert not eng.drain_begun
    assert res.streams_migrated == 0


async def test_coordinator_idle_worker_with_no_peer_is_still_clean():
    coord = _coord(_Engine(active=0), peers="none")
    res = await coord.drain()
    assert res.result == "completed"


def test_drain_timeout_env_parsing(monkeypatch):
    monkeypatch.delenv("DYN_DRAIN_TIMEOUT_S", raising=False)
    assert drain_timeout_from_env() == DEFAULT_DRAIN_TIMEOUT_S
    monkeypatch.setenv("DYN_DRAIN_TIMEOUT_S", "7.5")
    assert drain_timeout_from_env() == 7.5
    monkeypatch.setenv("DYN_DRAIN_TIMEOUT_S", "not-a-number")
    assert drain_timeout_from_env() == DEFAULT_DRAIN_TIMEOUT_S


# ---------------------------------------------------------------------------
# Worker-control subject: serve_drain_control / request_drain round-trip
# ---------------------------------------------------------------------------


class _PubSubStore:
    """In-memory publish/subscribe + kv_get_prefix, shaped like the
    coordinator store client (store/base.py)."""

    def __init__(self):
        self.queues = {}
        self.instances = {}
        self.published = []

    async def subscribe(self, subject):
        q = asyncio.Queue()
        self.queues.setdefault(subject, []).append(q)

        async def _iter():
            while True:
                yield subject, await q.get()

        return _iter()

    async def publish(self, subject, payload):
        self.published.append((subject, payload))
        for q in self.queues.get(subject, []):
            q.put_nowait(payload)

    async def kv_get_prefix(self, prefix):
        return [
            SimpleNamespace(key=k, value=v)
            for k, v in self.instances.items()
            if k.startswith(prefix)
        ]


async def test_control_call_converges_onto_shutdown_and_acks():
    store = _PubSubStore()
    drt = SimpleNamespace(store=store)
    me = _inst(0xAA)
    shutdowns = []
    runtime = SimpleNamespace(shutdown=lambda: shutdowns.append(True))
    task = asyncio.ensure_future(
        serve_drain_control(drt, "ns", me, runtime)
    )
    await asyncio.sleep(0.01)
    ack_sub = await store.subscribe("_ack")
    # wrong instance: ignored; garbage: ignored; match: shutdown + ack
    subject = worker_control_subject("ns")
    await store.publish(subject, b"not json")
    await store.publish(
        subject, json.dumps({"op": "drain", "instance": "bb"}).encode()
    )
    await store.publish(
        subject,
        json.dumps(
            {"op": "drain", "instance": "aa", "reply_to": "_ack"}
        ).encode(),
    )
    _, ack = await asyncio.wait_for(ack_sub.__anext__(), 1.0)
    assert json.loads(ack.decode()) == {"ok": True, "instance": "aa"}
    assert shutdowns == [True]
    task.cancel()


async def test_request_drain_polls_until_instance_departs():
    store = _PubSubStore()
    me = _inst(0xAA)
    store.instances[me.path] = b"{}"

    async def _depart():
        await asyncio.sleep(0.05)
        del store.instances[me.path]

    asyncio.ensure_future(_depart())
    ok = await request_drain(
        store, "ns", "aa", timeout_s=2.0, poll_interval_s=0.01
    )
    assert ok
    subject, payload = store.published[0]
    assert subject == worker_control_subject("ns")
    assert json.loads(payload.decode()) == {"op": "drain", "instance": "aa"}


async def test_request_drain_times_out_when_worker_stays():
    store = _PubSubStore()
    store.instances[_inst(0xAA).path] = b"{}"
    assert not await request_drain(
        store, "ns", "aa", timeout_s=0.05, poll_interval_s=0.01
    )


# ---------------------------------------------------------------------------
# Planner: drain-preferring scale-down + rolling_restart
# ---------------------------------------------------------------------------


class _FastClock:
    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        return self.now

    async def sleep(self, seconds):
        self.now += seconds


class _Connector:
    def __init__(self, replicas=3, drain_refusals=0, add_refusals=0,
                 recover=True):
        self.n = replicas
        self.drains = 0
        self.adds = 0
        self._drain_refusals = drain_refusals
        self._add_refusals = add_refusals
        self._recover = recover

    async def replicas(self, component):
        return self.n

    async def drain_component(self, component):
        if self._drain_refusals > 0:
            self._drain_refusals -= 1
            return False
        self.drains += 1
        self.n -= 1
        return True

    async def add_component(self, component):
        if self._add_refusals > 0:
            self._add_refusals -= 1
            return False
        self.adds += 1
        if self._recover:
            self.n += 1
        return True


async def test_drain_or_remove_prefers_drain_and_falls_back():
    from dynamo_tpu.planner.planner import _drain_or_remove

    c = _Connector(replicas=2)
    assert await _drain_or_remove(c, "backend")
    assert c.drains == 1

    class _Legacy:
        removed = 0

        async def remove_component(self, component):
            self.removed += 1
            return True

    legacy = _Legacy()
    assert await _drain_or_remove(legacy, "backend")
    assert legacy.removed == 1


async def test_rolling_restart_cycles_every_replica():
    from dynamo_tpu.planner.planner import rolling_restart

    c = _Connector(replicas=3)
    cycled = await rolling_restart(
        c, "backend", max_unavailable=1, health_timeout_s=5.0,
        poll_interval_s=0.01, clock=_FastClock(),
    )
    assert cycled == 3
    assert c.drains == 3 and c.adds == 3
    assert c.n == 3  # fleet back at baseline


async def test_rolling_restart_batches_by_max_unavailable():
    from dynamo_tpu.planner.planner import rolling_restart

    c = _Connector(replicas=5)
    cycled = await rolling_restart(
        c, "backend", max_unavailable=2, health_timeout_s=5.0,
        poll_interval_s=0.01, clock=_FastClock(),
    )
    assert cycled == 5
    assert c.drains == 5 and c.adds == 5


async def test_rolling_restart_aborts_on_refused_drain():
    from dynamo_tpu.planner.planner import rolling_restart

    c = _Connector(replicas=3, drain_refusals=1)
    cycled = await rolling_restart(
        c, "backend", max_unavailable=1, health_timeout_s=5.0,
        poll_interval_s=0.01, clock=_FastClock(),
    )
    assert cycled == 0
    assert c.adds == 0  # no replacement for a drain that never happened


async def test_rolling_restart_aborts_when_fleet_never_recovers():
    from dynamo_tpu.planner.planner import rolling_restart

    c = _Connector(replicas=3, recover=False)
    cycled = await rolling_restart(
        c, "backend", max_unavailable=1, health_timeout_s=0.5,
        poll_interval_s=0.01, clock=_FastClock(),
    )
    assert cycled == 0  # health gate stopped the rollout at batch one
    assert c.drains == 1 and c.adds == 1


async def test_rolling_restart_empty_fleet_is_a_noop():
    from dynamo_tpu.planner.planner import rolling_restart

    c = _Connector(replicas=0)
    assert await rolling_restart(c, "backend", clock=_FastClock()) == 0


# ---------------------------------------------------------------------------
# Simulator: drain modeling + the kill-vs-drain A/B
# ---------------------------------------------------------------------------


def _ab_run(point):
    from dynamo_tpu.faults.plan import parse_plan
    from dynamo_tpu.sim import FleetSim, SimConfig, bursty_trace

    trace = bursty_trace(
        600.0, seed=2026, calm_rps=30.0, burst_rps=60.0,
        mean_calm_s=90.0, mean_burst_s=30.0,
    )
    return FleetSim(
        trace, SimConfig(initial_decode=3, kill_detect_s=2.0),
        plan=parse_plan(f"seed=42;{point}:kill@after=240"),
    ).run()


def _dip(res):
    att = [s["slo_attainment_mean"] for s in res["timeline"]]
    return 1.0 - min(att) if att else 0.0


def test_sim_drain_migrates_inflight_and_conserves_requests():
    from dynamo_tpu.faults.plan import parse_plan
    from dynamo_tpu.sim import FleetSim, SimConfig, diurnal_trace

    trace = diurnal_trace(
        120.0, seed=4, base_rps=10.0, peak_rps=10.0, period_s=120.0
    )
    plan = parse_plan("seed=2;worker.drain:kill@after=30")
    res = FleetSim(trace, SimConfig(initial_decode=2), plan=plan).run()
    assert res["workers_drained"] == 1
    assert res["workers_killed"] == 0
    assert res["drained_inflight"] > 0
    # planned departure: every in-flight stream hands off, none lost
    assert res["lost_inflight"] == 0
    assert res["resumed"] + res["refailed"] == res["drained_inflight"]
    assert res["decode_workers_final"] == 1
    assert res["completed"] + res["shed"] + res["unfinished"] == res["requests"]


def test_sim_kill_vs_drain_ab_is_deterministic_and_shallower():
    """The acceptance A/B on one seeded trace and fault plan: the
    drain's SLO-attainment dip must be STRICTLY shallower than the
    kill's, and replays bit-identical."""
    kill = _ab_run("worker.liveness")
    drain = _ab_run("worker.drain")
    assert _ab_run("worker.drain") == drain  # bit-identical replay
    assert drain["workers_drained"] == 1 and kill["workers_killed"] == 1
    assert _dip(drain) < _dip(kill)
    assert drain["lost_inflight"] == 0
    assert drain["goodput_tokens"] >= kill["goodput_tokens"]


def test_sim_connector_drain_component_routes_by_config():
    """drain_proactive=False (the default) preserves the legacy remove
    semantics bit-for-bit; True routes scale-downs through the drain."""
    from dynamo_tpu.sim import FleetSim, SimConfig
    from dynamo_tpu.sim.fleet import SimConnector

    async def scale_down(proactive):
        fleet = FleetSim([], SimConfig(
            initial_decode=2, drain_proactive=proactive,
        ))
        fleet.run()  # spawns the initial workers; empty trace, returns
        assert await SimConnector(fleet).drain_component("backend")
        return fleet.result()

    res = asyncio.run(scale_down(False))
    assert res["workers_drained"] == 0
    res = asyncio.run(scale_down(True))
    assert res["workers_drained"] == 1
