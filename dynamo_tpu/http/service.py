"""OpenAI-compatible HTTP frontend.

Analogue of the reference's axum HTTP service (reference:
lib/llm/src/http/service/{openai.rs:133-560, service_v2.rs:26-151,
metrics.rs:36-311}): /v1/chat/completions, /v1/completions, /v1/models,
SSE streaming, Prometheus middleware, model add/remove at runtime via the
ModelManager (fed either programmatically or by the store-driven
ModelWatcher in discovery.py).

aiohttp replaces axum (fastapi/uvicorn are unavailable in this image and
aiohttp's raw StreamResponse is lower overhead for SSE anyway).

Observability (ISSUE 2): requests carry an ``X-Request-Id`` (client's,
or generated) echoed on every response and stamped into log records
(runtime/logging.py RequestIdFilter) and the request's root span, so
logs, traces, and client reports join on one id. Metrics moved from
prometheus_client onto the unified registry (telemetry/instruments.py
— same metric names); ``/metrics`` renders the whole process registry,
engine instruments included.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import math
import time
import uuid
from typing import Optional

from aiohttp import web

from dynamo_tpu import faults
from dynamo_tpu.protocols.aggregators import ChatAggregator, CompletionAggregator
from dynamo_tpu.protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    ModelInfo,
    ModelList,
)
from dynamo_tpu.protocols.sse import encode_done, encode_sse
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.logging import set_log_request_id
from dynamo_tpu.telemetry import (
    REGISTRY,
    capture_profile,
    collect_debug_state,
    get_tracer,
    propagation_context,
)
from dynamo_tpu.telemetry import autopsy
from dynamo_tpu.telemetry.hostplane import (
    LEDGER,
    LoopLagMonitor,
    collect_hostplane,
    register_hostplane_provider,
    unregister_hostplane_provider,
)
from dynamo_tpu.telemetry.instruments import (
    HTTP_DURATION,
    HTTP_INFLIGHT,
    HTTP_REQUESTS,
    HTTP_TTFT,
)
from dynamo_tpu.telemetry.spans import note_loop_thread

log = logging.getLogger("dynamo_tpu.http")

REQUEST_ID_HEADER = "X-Request-Id"
# per-request deadline budget in milliseconds (docs/robustness.md);
# --default-deadline-ms applies when the header is absent
REQUEST_TIMEOUT_HEADER = "X-Request-Timeout-Ms"
# per-request fault rules (only honored when the active DYN_FAULTS plan
# opted in with `header`; see dynamo_tpu/faults)
FAULT_HEADER = "X-Dyn-Fault"


async def _chain_first(first, rest):
    """Re-prepend the primed first chunk to the rest of the stream."""
    if first is not None:
        yield first
    async for chunk in rest:
        yield chunk


def _request_id_from(request: web.Request) -> str:
    """The client's X-Request-Id (sanitized) or a fresh one."""
    rid = request.headers.get(REQUEST_ID_HEADER, "").strip()
    if rid:
        # bounded + printable: the id lands in logs/headers verbatim
        rid = "".join(c for c in rid[:128] if c.isprintable())
    return rid or uuid.uuid4().hex


class ModelManager:
    """Live model registry: name → chat/completion pipeline engines.

    (reference: http/service/discovery.rs ModelManager — models are added
    and removed while the service runs.)
    """

    def __init__(self) -> None:
        self.chat_engines: dict[str, AsyncEngine] = {}
        self.completion_engines: dict[str, AsyncEngine] = {}
        self._created: dict[str, int] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self.chat_engines[name] = engine
        self._created.setdefault(name, int(time.time()))

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self.completion_engines[name] = engine
        self._created.setdefault(name, int(time.time()))

    def remove_model(self, name: str) -> None:
        self.chat_engines.pop(name, None)
        self.completion_engines.pop(name, None)
        self._created.pop(name, None)

    def list_models(self) -> ModelList:
        names = sorted(set(self.chat_engines) | set(self.completion_engines))
        return ModelList(
            data=[
                ModelInfo(id=n, created=self._created.get(n, 0)) for n in names
            ]
        )


class HttpService:
    def __init__(
        self,
        model_manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8000,
        admission=None,
        default_deadline_ms: Optional[float] = None,
        lag_monitor: Optional[LoopLagMonitor] = None,
    ):
        self.models = model_manager or ModelManager()
        self.host = host
        self.port = port
        # load shedding (http/admission.py AdmissionController); None =
        # every request admitted (zero-change default)
        self.admission = admission
        # deadline budget applied when X-Request-Timeout-Ms is absent
        self.default_deadline_ms = default_deadline_ms
        # host data plane (telemetry/hostplane.py): the per-stream cost
        # ledger is process-global (downstream stages stamp it by
        # request id); the loop-lag monitor is per-service — a stall
        # dumps its own flight ring + black-box bundle (loop_stall)
        self.hostplane = LEDGER
        if lag_monitor is None:
            from dynamo_tpu.telemetry.blackbox import BlackBox
            from dynamo_tpu.telemetry.recorder import FlightRecorder

            rec = FlightRecorder(capacity=256)
            lag_monitor = LoopLagMonitor(
                recorder=rec, blackbox=BlackBox(recorder=rec)
            )
        self.lag_monitor = lag_monitor
        self.app = web.Application(client_max_size=64 * 1024 * 1024)
        self.app.add_routes(
            [
                web.get("/health", self._health),
                web.get("/live", self._health),
                web.get("/metrics", self._metrics),
                web.get("/debug/state", self._debug_state),
                web.get("/debug/hostplane", self._debug_hostplane),
                web.get("/debug/kvfleet", self._debug_kvfleet),
                web.get("/debug/requests", self._debug_requests),
                web.get("/debug/request/{rid}", self._debug_request),
                web.get("/debug/profile", self._debug_profile),
                web.get("/v1/models", self._models),
                web.post("/v1/chat/completions", self._chat),
                web.post("/v1/completions", self._completions),
            ]
        )
        self._runner: Optional[web.AppRunner] = None

    # -- lifecycle --------------------------------------------------------
    async def start(self) -> None:
        # handler_cancellation: client disconnect cancels the handler task so
        # in-flight generation is killed promptly (off by default in aiohttp 3.9+)
        self._runner = web.AppRunner(self.app, handler_cancellation=True)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        if self.port == 0:
            self.port = self._runner.addresses[0][1]
        # host data plane: heartbeat on THIS loop + the /debug/hostplane
        # provider stanza (lag window, task census, ledger rollup)
        self.lag_monitor.start()
        register_hostplane_provider("frontend", self._hostplane_stanza)
        # THIS loop's thread serialises every token of every stream: an
        # engine in the process counts its CPU beside its own thread's
        note_loop_thread()
        log.info("OpenAI HTTP service on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        unregister_hostplane_provider("frontend", self._hostplane_stanza)
        await self.lag_monitor.stop()
        if self._runner is not None:
            await self._runner.cleanup()

    async def run_forever(self) -> None:
        await self.start()
        await asyncio.Event().wait()

    # -- handlers ---------------------------------------------------------
    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "healthy", "models": [m.id for m in self.models.list_models().data]}
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=REGISTRY.render(), content_type="text/plain")

    async def _debug_state(self, request: web.Request) -> web.Response:
        """Live introspection (docs/observability.md): a JSON snapshot
        from every registered debug provider — scheduler slots, KV pool
        occupancy, flight-recorder tail, SLO attainment, HBM — plus the
        frontend's own model table. `dynamo-tpu top` polls this."""
        state = collect_debug_state()
        state["frontend"] = {
            "models": [m.id for m in self.models.list_models().data],
            "host": self.host,
            "port": self.port,
        }
        return web.json_response(state)

    def _hostplane_stanza(self) -> dict:
        """The frontend's /debug/hostplane provider: loop-lag window +
        task census from the monitor, per-stream cost rollup from the
        ledger (docs/observability.md "Host data plane")."""
        out = {
            "loop": self.lag_monitor.snapshot(),
            "ledger": self.hostplane.snapshot(recent=8),
        }
        if self.admission is not None:
            out["admission"] = self.admission.stats()
        return out

    async def _debug_hostplane(self, request: web.Request) -> web.Response:
        """Host data-plane introspection (docs/observability.md "Host
        data plane"): event-loop lag p50/p99/max + stall count, the
        asyncio task census, and the per-stream host-cost ledger's
        rolling window — the 'is the HOST the bottleneck' endpoint.
        The provider refreshes the loop-lag gauges, so a /metrics
        scrape next to this endpoint describes the same window."""
        return web.json_response(collect_hostplane())

    async def _debug_kvfleet(self, request: web.Request) -> web.Response:
        """Fleet KV fabric introspection (docs/kvbm.md "Fleet fabric"):
        the ``kvfleet:*`` provider stanzas only — per-fabric catalog
        view size, fleet hit/fetch/demotion counters, current pressure
        scale and host-tier residency. Empty when no fabric is attached
        in this process (e.g. a pure frontend)."""
        state = collect_debug_state()
        fleet = {
            k: v for k, v in state.items() if k.startswith("kvfleet")
        }
        return web.json_response(fleet)

    async def _debug_requests(self, request: web.Request) -> web.Response:
        """Request-autopsy exemplar index (docs/observability.md
        "Request autopsy"): retention counters + one summary line per
        retained tail exemplar, via the autopsy provider registry."""
        return web.json_response(autopsy.collect_autopsy())

    async def _debug_request(self, request: web.Request) -> web.Response:
        """One request's full autopsy record: in-flight (partial) or a
        retained exemplar. 404 = never seen here, or finished fast and
        clean and was dropped by tail retention."""
        rid = request.match_info["rid"]
        rec = autopsy.get_record(rid)
        if rec is None:
            return web.json_response(
                {"error": f"no autopsy record for {rid!r} (never seen, "
                          "or dropped at finish by tail retention)"},
                status=404,
            )
        return web.json_response(rec)

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """On-demand ``jax.profiler`` capture: ``/debug/profile?ms=N``
        records N ms and returns the Perfetto-loadable trace dir."""
        try:
            ms = int(request.query.get("ms", "1000"))
        except ValueError:
            return web.json_response(
                {"error": "ms must be an integer"}, status=400
            )
        try:
            result = await capture_profile(ms)
        except RuntimeError as exc:  # capture already running
            return web.json_response({"error": str(exc)}, status=409)
        except Exception as exc:
            log.exception("profile capture failed")
            return web.json_response(
                {"error": f"{type(exc).__name__}: {exc}"}, status=500
            )
        return web.json_response(result)

    async def _models(self, request: web.Request) -> web.Response:
        return web.json_response(self.models.list_models().model_dump())

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_llm(request, kind="chat")

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_llm(request, kind="completion")

    async def _handle_llm(self, request: web.Request, kind: str) -> web.StreamResponse:
        endpoint = "chat_completions" if kind == "chat" else "completions"
        rid = _request_id_from(request)
        # root span of the request's trace: every downstream span
        # (preprocess, router dispatch, worker, engine, disagg) nests
        # under this one via the Context's trace ids
        span = get_tracer().span(
            "http.request",
            attrs={"service": "frontend", "endpoint": endpoint,
                   "request_id": rid},
        )
        set_log_request_id(rid, span.trace_id or None)
        # host-cost ledger record (telemetry/hostplane.py): stamped by
        # every stage below; downstream stages (preprocessor tool
        # parser, router dispatch) stamp by request id via note_stage
        self.hostplane.begin(rid, endpoint)
        # autopsy record (telemetry/autopsy.py): the per-request join
        # layer — router dials, engine segments, and fleet events land
        # on this rid; the hostplane row is adopted at finish
        autopsy.begin_request(rid, endpoint)
        autopsy.set_trace(rid, span.trace_id or None)
        try:
            if faults.ACTIVE is not None:
                # per-request chaos: the X-Dyn-Fault header arms rules
                # scoped to this request id (no-op unless the active
                # plan opted in), then the frontend's own injection
                # point fires
                hdr = request.headers.get(FAULT_HEADER)
                if hdr:
                    try:
                        faults.ACTIVE.arm_request(hdr, rid)
                    except ValueError as exc:
                        return self._error(
                            400, f"bad {FAULT_HEADER}: {exc}", "",
                            endpoint, rid,
                        )
                await faults.ACTIVE.fire_async("http.request", request_id=rid)
            # admission control (docs/robustness.md): consult live load
            # BEFORE any expensive work; shed with 429 + Retry-After
            # instead of queueing unboundedly
            if self.admission is not None:
                t_adm = time.monotonic()
                rejection = self.admission.check()
                self.hostplane.stage(
                    rid, "admission", time.monotonic() - t_adm
                )
                if rejection is not None:
                    log.warning(
                        "shedding request %s: %s", rid, rejection.detail
                    )
                    span.set_attr("shed", rejection.reason)
                    autopsy.note_event(
                        rid, "shed", flag="shed",
                        reason=rejection.reason,
                        retry_after_s=round(rejection.retry_after_s, 3),
                    )
                    return self._error(
                        429,
                        f"server overloaded ({rejection.detail}); retry "
                        "after the indicated delay",
                        "", endpoint, rid,
                        headers={
                            "Retry-After": str(
                                max(1, int(rejection.retry_after_s))
                            )
                        },
                    )
            # per-request deadline budget: header beats the configured
            # default; invalid values are a client error, not a guess
            deadline_ms: Optional[float] = self.default_deadline_ms
            raw_timeout = request.headers.get(REQUEST_TIMEOUT_HEADER)
            if raw_timeout:
                try:
                    deadline_ms = float(raw_timeout)
                    # not (x > 0) also rejects NaN, which would mint a
                    # never-expiring local deadline but ship a 0 ms
                    # budget over the wire
                    if not (deadline_ms > 0) or math.isinf(deadline_ms):
                        raise ValueError
                except ValueError:
                    return self._error(
                        400,
                        f"{REQUEST_TIMEOUT_HEADER} must be a positive "
                        "number of milliseconds",
                        "", endpoint, rid,
                    )
            t_pre = time.monotonic()
            try:
                body = await request.json()
            except json.JSONDecodeError:
                return self._error(400, "invalid JSON body", "", endpoint, rid)
            try:
                if kind == "chat":
                    req = ChatCompletionRequest.model_validate(body)
                else:
                    req = CompletionRequest.model_validate(body)
            except Exception as exc:
                return self._error(
                    400, f"invalid request: {exc}", "", endpoint, rid
                )
            # frontend share of preprocess: body read + pydantic
            # validation (the pipeline's tokenize/template forward adds
            # its share to the same stamp via note_stage)
            self.hostplane.stage(rid, "preprocess", time.monotonic() - t_pre)

            model = req.model
            span.set_attr("model", model)
            # per-request speculative-decoding opt-in/out rides the ext
            # field straight through to PreprocessedRequest.speculative
            # (the engine resolves None to its configured default);
            # stamp explicit choices on the root span so traces show
            # which requests ran speculatively
            spec_opt = req.extension().speculative
            if spec_opt is not None:
                span.set_attr("speculative", bool(spec_opt))
            # guided decoding / tool calling (docs/guided_decoding.md):
            # stamp the constraint kind and tool surface on the root
            # span so traces show which requests ran masked
            rf = getattr(req, "response_format", None)
            if isinstance(rf, dict) and rf.get("type"):
                span.set_attr("response_format", str(rf["type"]))
            tools = getattr(req, "tools", None)
            if tools:
                span.set_attr("tools", len(tools))
            engines = (
                self.models.chat_engines if kind == "chat" else self.models.completion_engines
            )
            engine = engines.get(model)
            if engine is None:
                return self._error(
                    404, f"model {model!r} not found", model, endpoint, rid
                )

            ctx = Context(id=rid)
            if deadline_ms is not None:
                # the budget starts at admission; it propagates with the
                # context (and over the worker wire) so every stage —
                # queue wait, prefill dispatch, decode — can cancel the
                # request instead of burning steps past its deadline
                ctx.set_deadline_ms(deadline_ms)
                span.set_attr("deadline_ms", deadline_ms)
                autopsy.note_event(rid, "deadline_budget", ms=deadline_ms)
            # the head's decision governs the WHOLE trace: a sampled-out
            # root propagates {"sampled": False} so downstream processes
            # don't start orphan root traces of their own
            ctx.set_trace(propagation_context(span) or {})
            start = time.monotonic()
            HTTP_INFLIGHT.labels(model).inc()
            try:
                stream = engine.generate(req, ctx)
                # dispatch stamp: building the generator is the local
                # handoff cost (routed pipelines add the instance-pick
                # share via note_stage inside the router)
                self.hostplane.stage(
                    rid, "dispatch", time.monotonic() - start
                )
                if req.stream:
                    # prime the FIRST chunk before committing to an SSE
                    # response: generation pipelines run lazily, so
                    # request-shaped failures (uncompilable guided
                    # schemas, bad token ids) surface on the first
                    # __anext__ — they must return the 400 below, not a
                    # 200 stream carrying an error event
                    aiter = stream.__aiter__()
                    t_prime = time.monotonic()
                    try:
                        first = await aiter.__anext__()
                    except StopAsyncIteration:
                        first = None
                    # first-chunk priming = the engine-side share of
                    # TTFB (the frontend TTFB-vs-engine-TTFT split)
                    self.hostplane.stage(
                        rid, "prime", time.monotonic() - t_prime
                    )
                    return await self._stream_sse(
                        request, _chain_first(first, aiter), ctx, model,
                        endpoint, start, rid,
                    )
                # aggregate to a single response object
                agg = ChatAggregator() if kind == "chat" else CompletionAggregator()
                async for chunk in stream:
                    agg.push(chunk)
                HTTP_REQUESTS.labels(model, endpoint, "200").inc()
                HTTP_DURATION.labels(model, endpoint).observe(
                    time.monotonic() - start
                )
                autopsy.finish_request(
                    rid, "200", host=self.hostplane.finish(rid, "200")
                )
                return web.json_response(
                    agg.response().model_dump(exclude_none=True),
                    headers={REQUEST_ID_HEADER: rid},
                )
            except asyncio.CancelledError:
                ctx.kill()
                span.set_attr("status", "499")
                raise
            except ValueError as exc:
                # request-shaped failures surfacing past pydantic —
                # uncompilable guided schemas, bad token ids — are the
                # CLIENT's error, not an engine failure. Logged with the
                # traceback anyway: if an internal defect ever surfaces
                # as ValueError, the 400 must not hide it from operators
                log.warning(
                    "rejecting request %s as invalid: %s", rid, exc,
                    exc_info=True,
                )
                # covers guided-rejects (uncompilable schemas): flagged
                # so the autopsy exemplar survives tail retention
                autopsy.note_event(
                    rid, "request_rejected", flag="rejected",
                    error=str(exc)[:200],
                )
                return self._error(
                    400, f"invalid request: {exc}", model, endpoint, rid
                )
            except Exception as exc:
                ctx.kill()  # whatever failed, nothing keeps generating for it
                log.exception("engine failure for %s", model)
                return self._error(
                    500, f"engine error: {exc}", model, endpoint, rid
                )
            finally:
                HTTP_INFLIGHT.labels(model).dec()
        finally:
            # error/shed/4xx paths return before their stage reached a
            # finish() call — close the ledger record so the active
            # table can't grow (finish is idempotent: happy paths
            # already popped theirs; the autopsy close mirrors it)
            autopsy.finish_request(
                rid, "error", host=self.hostplane.finish(rid, "error")
            )
            span.end()
            set_log_request_id(None)

    async def _stream_sse(
        self,
        request: web.Request,
        stream,
        ctx: Context,
        model: str,
        endpoint: str,
        start: float,
        rid: str = "",
    ) -> web.StreamResponse:
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        }
        if rid:
            headers[REQUEST_ID_HEADER] = rid
        resp = web.StreamResponse(status=200, headers=headers)
        first = True
        status = "200"
        try:
            # inside the try: a client that left while its first chunk
            # was primed makes prepare() raise on the closing transport,
            # and the generation it leaves behind is killed like any other
            await resp.prepare(request)
            self.hostplane.mark_stream(rid)
            async for chunk in stream:
                if first:
                    HTTP_TTFT.labels(model).observe(time.monotonic() - start)
                    first = False
                # per-chunk cost feeds the ledger's EMA (serialize vs
                # write split: a long write is transport backpressure)
                t0 = time.monotonic()
                payload = chunk.model_dump(exclude_none=True) if hasattr(chunk, "model_dump") else chunk
                data = encode_sse(payload).encode()
                t1 = time.monotonic()
                await resp.write(data)
                self.hostplane.chunk(
                    rid, t1 - t0, time.monotonic() - t1, len(data)
                )
            await resp.write(encode_done().encode())
        except asyncio.CancelledError:
            # client went away: kill the in-flight generation, let the
            # cancellation propagate (aiohttp expects it); finally still
            # records the 499
            ctx.kill()
            status = "499"
            raise
        except ConnectionResetError:
            ctx.kill()
            status = "499"
        except Exception as exc:
            log.exception("stream failure for %s", model)
            await resp.write(
                encode_sse({"error": str(exc)}, event="error").encode()
            )
            status = "500"
        finally:
            HTTP_REQUESTS.labels(model, endpoint, status).inc()
            HTTP_DURATION.labels(model, endpoint).observe(time.monotonic() - start)
            autopsy.finish_request(
                rid, status, host=self.hostplane.finish(rid, status)
            )
        with contextlib.suppress(ConnectionResetError):
            await resp.write_eof()
        return resp

    def _error(
        self, status: int, message: str, model: str, endpoint: str,
        rid: str = "", headers: Optional[dict] = None,
    ) -> web.Response:
        HTTP_REQUESTS.labels(model, endpoint, str(status)).inc()
        all_headers = dict(headers or {})
        if rid:
            all_headers[REQUEST_ID_HEADER] = rid
        err_type = (
            "overloaded_error" if status == 429 else "invalid_request_error"
        )
        return web.json_response(
            {"error": {"message": message, "type": err_type}},
            status=status,
            headers=all_headers or None,
        )


