"""Metrics aggregation service: worker load → Prometheus text endpoint.

Reference: components/metrics/src/lib.rs:145-612 — scrape worker
ForwardPassMetrics, aggregate (avg/std load, active blocks/slots),
serve Prometheus ``/metrics``, and watch KV hit-rate events. Transport
here: subscribe to the component's ``load_metrics`` subject (same feed
as router and planner) and the frontend's KV hit-rate events.

Exposition rides the unified telemetry registry (telemetry/metrics.py):
the gauges below are declared once on a per-service Registry and
re-populated from a fresh aggregator snapshot at each scrape, so the
text format (HELP/TYPE pairs, label escaping, series dedup) is produced
by one implementation shared with the HTTP frontend.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import math
from typing import Optional

from aiohttp import web

from dynamo_tpu.kv_router.scheduler import KvMetricsAggregator
from dynamo_tpu.runtime.component import Component
from dynamo_tpu.telemetry.debug import capture_profile, collect_debug_state
from dynamo_tpu.telemetry.metrics import Registry
from dynamo_tpu.telemetry.slo import aggregate_slo
from dynamo_tpu.utils.tasks import spawn

log = logging.getLogger("dynamo_tpu.metrics")

KV_HIT_RATE_SUBJECT = "kv-hit-rate"


class MetricsService:
    def __init__(
        self,
        component: Component,
        host: str = "0.0.0.0",
        port: int = 9091,
    ):
        self.component = component
        self.host = host
        self.port = port
        self.aggregator = KvMetricsAggregator()
        self._hit_events = 0
        self._isl_sum = 0.0
        self._overlap_sum = 0.0
        self._runner: Optional[web.AppRunner] = None
        self._hit_task: Optional[asyncio.Task] = None
        # per-service registry (gauge names ≈ reference
        # components/metrics/src/lib.rs:339-545)
        self.registry = Registry()
        r = self.registry
        self._g_load_avg = r.gauge(
            "llm_kv_load_avg", "mean KV cache usage across workers")
        self._g_load_std = r.gauge(
            "llm_kv_load_std", "stddev of KV cache usage")
        self._g_blocks_active = r.gauge(
            "llm_kv_blocks_active", "total active KV blocks")
        self._g_blocks_total = r.gauge(
            "llm_kv_blocks_total", "total KV blocks")
        self._g_active_slots = r.gauge(
            "llm_requests_active_slots", "busy request slots")
        self._g_total_slots = r.gauge(
            "llm_requests_total_slots", "total request slots")
        self._g_waiting = r.gauge(
            "llm_requests_waiting", "queued requests")
        self._g_workers = r.gauge(
            "llm_workers_reporting", "workers with fresh metrics")
        self._g_worker_usage = r.gauge(
            "llm_worker_kv_cache_usage", "per-worker KV cache usage",
            labels=("worker",),
        )
        self._g_hit_events = r.gauge(
            "llm_kv_hit_rate_events", "KV hit rate events seen")
        self._g_avg_hit = r.gauge(
            "llm_kv_avg_hit_rate", "mean prefix overlap fraction")
        # SLO/goodput rollup (telemetry/slo.py signals riding the same
        # load_metrics feed — the Planner scales on these)
        self._g_slo_attainment = r.gauge(
            "llm_slo_attainment", "mean rolling SLO attainment across "
            "workers reporting targets")
        self._g_goodput = r.gauge(
            "llm_goodput_tokens", "total goodput tokens (SLO-met "
            "completion tokens) across workers")

    def build_app(self) -> web.Application:
        """The debug/metrics route table, separable from ``start()`` so
        the endpoint-parity test can compare it against the HTTP
        frontend's without binding a socket. The ``/debug/*`` surface
        mirrors the frontend: an operator mid-incident must not have to
        remember which port grew which endpoint."""
        app = web.Application()
        app.router.add_get("/metrics", self._handle_metrics)
        app.router.add_get("/debug/state", self._handle_debug_state)
        app.router.add_get("/debug/hostplane", self._handle_debug_hostplane)
        app.router.add_get("/debug/kvfleet", self._handle_debug_kvfleet)
        app.router.add_get("/debug/requests", self._handle_debug_requests)
        app.router.add_get("/debug/request/{rid}", self._handle_debug_request)
        app.router.add_get("/debug/profile", self._handle_debug_profile)
        return app

    async def start(self) -> None:
        sub = await self.component.subscribe("load_metrics")
        self.aggregator.start_consuming(sub)
        hit_sub = await self.component.namespace.subscribe(KV_HIT_RATE_SUBJECT)

        async def pump_hits() -> None:
            async for _subject, payload in hit_sub:
                try:
                    self._hit_events += 1
                    self._isl_sum += float(payload.get("isl_blocks", 0))
                    self._overlap_sum += float(payload.get("overlap_blocks", 0))
                except Exception:
                    log.exception("bad kv-hit-rate payload")

        # spawn (not bare create_task): a crash in the hit-rate pump is
        # logged instead of dying silently with hit-rate gauges frozen
        self._hit_task = spawn(pump_hits(), name="metrics-hit-pump")
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.port == 0:
            # public API (no aiohttp private internals): the runner
            # exposes every site's bound (host, port)
            self.port = self._runner.addresses[0][1]
        log.info("metrics service on :%d/metrics", self.port)

    def render(self) -> str:
        """Prometheus text exposition from a fresh aggregator snapshot."""
        fresh = self.aggregator.fresh_metrics()
        loads = [m.gpu_cache_usage_perc for m in fresh.values()]
        mean = sum(loads) / len(loads) if loads else 0.0
        std = (
            math.sqrt(sum((x - mean) ** 2 for x in loads) / len(loads))
            if loads
            else 0.0
        )
        self._g_load_avg.set(mean)
        self._g_load_std.set(std)
        self._g_blocks_active.set(
            float(sum(m.kv_active_blocks for m in fresh.values()))
        )
        self._g_blocks_total.set(
            float(sum(m.kv_total_blocks for m in fresh.values()))
        )
        self._g_active_slots.set(
            float(sum(m.request_active_slots for m in fresh.values()))
        )
        self._g_total_slots.set(
            float(sum(m.request_total_slots for m in fresh.values()))
        )
        self._g_waiting.set(
            float(sum(m.num_requests_waiting for m in fresh.values()))
        )
        self._g_workers.set(float(len(fresh)))
        # per-worker series re-seed from the snapshot: a worker that
        # stopped reporting must drop out of the payload, not go stale
        self._g_worker_usage.clear()
        for wid, m in sorted(fresh.items()):
            self._g_worker_usage.labels(f"{wid:x}").set(m.gpu_cache_usage_perc)
        avg_hit = (
            self._overlap_sum / self._isl_sum if self._isl_sum > 0 else 0.0
        )
        self._g_hit_events.set(float(self._hit_events))
        self._g_avg_hit.set(avg_hit)
        attainment, goodput = aggregate_slo(fresh.values())
        self._g_slo_attainment.set(attainment)
        self._g_goodput.set(goodput)
        return self.registry.render()

    async def _handle_metrics(self, _req: web.Request) -> web.Response:
        return web.Response(text=self.render(), content_type="text/plain")

    async def _handle_debug_state(self, _req: web.Request) -> web.Response:
        """Fleet-side /debug/state: the aggregator's per-worker load
        view plus any local debug providers (an in-process engine's
        snapshot shows up here when the metrics server shares the
        worker process)."""
        state = collect_debug_state()
        fresh = self.aggregator.fresh_metrics()
        state["workers"] = {
            f"{wid:x}": m.model_dump() if hasattr(m, "model_dump")
            else dict(m.__dict__)
            for wid, m in sorted(fresh.items())
        }
        return web.json_response(state)

    async def _handle_debug_hostplane(
        self, _req: web.Request
    ) -> web.Response:
        """Host data-plane view (telemetry/hostplane.py): event-loop
        lag, asyncio task census, and the per-stream cost ledger of
        whatever co-located services registered a provider."""
        from dynamo_tpu.telemetry.hostplane import collect_hostplane

        return web.json_response(collect_hostplane())

    async def _handle_debug_kvfleet(self, _req: web.Request) -> web.Response:
        """Fleet KV fabric introspection (docs/kvbm.md "Fleet fabric"):
        the ``kvfleet:*`` provider stanzas only — mirrors the HTTP
        frontend's endpoint for processes that co-locate a fabric with
        the metrics server (a worker). Empty when no fabric is attached
        here."""
        state = collect_debug_state()
        fleet = {
            k: v for k, v in state.items() if k.startswith("kvfleet")
        }
        return web.json_response(fleet)

    async def _handle_debug_requests(self, _req: web.Request) -> web.Response:
        """Request-autopsy exemplar index for THIS process (docs/
        observability.md "Request autopsy") — on a worker that is the
        pending engine-side segments plus any records finished here."""
        from dynamo_tpu.telemetry import autopsy

        return web.json_response(autopsy.collect_autopsy())

    async def _handle_debug_request(self, req: web.Request) -> web.Response:
        """One request's autopsy record, mirroring the frontend route."""
        from dynamo_tpu.telemetry import autopsy

        rid = req.match_info["rid"]
        rec = autopsy.get_record(rid)
        if rec is None:
            return web.json_response(
                {"error": f"no autopsy record for {rid!r} (never seen, "
                          "or dropped at finish by tail retention)"},
                status=404,
            )
        return web.json_response(rec)

    async def _handle_debug_profile(self, req: web.Request) -> web.Response:
        try:
            ms = int(req.query.get("ms", "1000"))
        except ValueError:
            return web.json_response(
                {"error": "ms must be an integer"}, status=400
            )
        try:
            return web.json_response(await capture_profile(ms))
        except RuntimeError as exc:
            return web.json_response({"error": str(exc)}, status=409)
        except Exception as exc:
            log.exception("profile capture failed")
            return web.json_response(
                {"error": f"{type(exc).__name__}: {exc}"}, status=500
            )

    async def close(self) -> None:
        if self._hit_task is not None:
            self._hit_task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._hit_task
        await self.aggregator.close()
        if self._runner is not None:
            await self._runner.cleanup()
