"""``dynamo-tpu top`` — live fleet view over ``/debug/state``.

Polls one or more debug endpoints (HTTP frontends and/or worker
metrics servers) and renders a terminal table: batch occupancy, queue
depth, KV-pool usage, token throughput (derived from successive
snapshots), SLO attainment, and HBM in use — the operator's "what is
this worker doing RIGHT NOW" answer without attaching a profiler.

Plumbing notes: snapshots come from ``/debug/state`` verbatim (the
engine's provider, telemetry/debug.py); token rates are derived
client-side from ``engine.tokens_generated_total`` deltas between
polls, so the first frame shows ``-``. ``--once`` renders a single
frame and exits (scriptable / testable); ``--raw`` prints the JSON
instead of the table.

Host-plane columns (telemetry/hostplane.py, polled best-effort from
``/debug/hostplane``): LAG99 = the frontend event loop's lag p99 in
ms, STRM = open SSE streams, RPS = finished requests/sec derived from
``ledger.requests_total`` deltas (same ``-`` rule as TOK/S: first
poll, zero poll gap, and counter rewinds render absence, not 0.0).

SLOW counts the endpoint's retained autopsy exemplars (telemetry/
autopsy.py, best-effort from ``/debug/requests``): requests kept by
tail sampling because they were flagged (SLO miss, migrated, faulted,
shed, …) or landed in the p99 latency tail — a rising SLOW with a flat
SSTEP (flight-recorder slow steps) points the operator at the host/
fleet path rather than the device loop.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Any, Optional, TextIO

import aiohttp

POLL_TIMEOUT_S = 5.0


def _fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "-"  # 0 is real data ("0B"); only absence renders "-"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return "-"


def _pct(v: Optional[float]) -> str:
    return f"{v * 100:5.1f}%" if isinstance(v, (int, float)) else "    -"


async def fetch_state(
    session: aiohttp.ClientSession, base_url: str
) -> dict[str, Any]:
    url = base_url.rstrip("/") + "/debug/state"
    async with session.get(url, timeout=aiohttp.ClientTimeout(
        total=POLL_TIMEOUT_S
    )) as resp:
        resp.raise_for_status()
        return await resp.json()


async def fetch_hostplane(
    session: aiohttp.ClientSession, base_url: str
) -> Optional[dict[str, Any]]:
    """Best-effort /debug/hostplane poll: an endpoint without the host
    data plane (worker-only metrics server from an older build) is not
    an error — its host columns just render ``-``."""
    url = base_url.rstrip("/") + "/debug/hostplane"
    try:
        async with session.get(url, timeout=aiohttp.ClientTimeout(
            total=POLL_TIMEOUT_S
        )) as resp:
            if resp.status != 200:
                return None
            return await resp.json()
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
        return None


async def fetch_requests(
    session: aiohttp.ClientSession, base_url: str
) -> Optional[dict[str, Any]]:
    """Best-effort /debug/requests poll (request-autopsy exemplar
    index). An endpoint predating the autopsy plane renders ``-`` in
    the SLOW column rather than erroring the row."""
    url = base_url.rstrip("/") + "/debug/requests"
    try:
        async with session.get(url, timeout=aiohttp.ClientTimeout(
            total=POLL_TIMEOUT_S
        )) as resp:
            if resp.status != 200:
                return None
            return await resp.json()
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
        return None


def _autopsy_cols(ap: Optional[dict]) -> dict:
    """SLOW column from a /debug/requests payload: the count of
    retained exemplars. Absence (no autopsy plane, error stanza, or a
    malformed payload) renders ``-``; an empty exemplar ring is real
    data and renders 0."""
    cols: dict[str, Any] = {"slow_requests": None}
    coll = (ap or {}).get("collector")
    if isinstance(coll, dict):
        ex = coll.get("exemplars")
        if isinstance(ex, list):
            cols["slow_requests"] = len(ex)
    return cols


def _hostplane_cols(
    hp: Optional[dict], prev_hp: Optional[dict],
    now: float, prev_ts: Optional[float],
) -> dict:
    """Host-plane columns (LAG99 / STRM / RPS) from a /debug/hostplane
    payload. RPS derives from ``ledger.requests_total`` deltas under
    the same rule as TOK/S: no prior poll, a zero/negative poll gap, or
    a counter that went backwards (frontend restart) all render the
    absence marker, never a fabricated 0.0."""
    cols: dict[str, Any] = {
        "loop_lag_p99_ms": None, "streams_open": None, "rps": None,
    }
    fe = (hp or {}).get("frontend") or {}
    lag = (fe.get("loop") or {}).get("lag") or {}
    ledger = fe.get("ledger") or {}
    if "p99_ms" in lag:
        cols["loop_lag_p99_ms"] = lag["p99_ms"]
    if "streams_open" in ledger:
        cols["streams_open"] = ledger["streams_open"]
    total = ledger.get("requests_total")
    if prev_hp is not None and prev_ts is not None and total is not None:
        prev_total = (
            ((prev_hp.get("frontend") or {}).get("ledger") or {})
            .get("requests_total")
        )
        dt = now - prev_ts
        if prev_total is not None and dt > 0 and total >= prev_total:
            cols["rps"] = (total - prev_total) / dt
    return cols


def _engine_row(url: str, state: dict, prev: Optional[dict],
                now: float, prev_ts: Optional[float]) -> dict:
    """Flatten one /debug/state payload into the table row."""
    eng = state.get("engine") or {}
    sched = eng.get("scheduler") or {}
    pool = eng.get("kv_pool") or {}
    slo = eng.get("slo") or {}
    hbm = eng.get("hbm") or {}
    load = eng.get("load") or {}
    rec = eng.get("flight_recorder") or {}
    tok_rate: Optional[float] = None
    # tokens_generated_total counts ALL generated tokens (goodput only
    # counts SLO-met ones and stays 0 when no targets are configured).
    # No prior snapshot, a zero/negative poll gap, or a counter that
    # went BACKWARDS (worker restart) all mean "no delta yet" — render
    # the absence marker, never a fabricated 0.0 rate.
    toks = eng.get("tokens_generated_total")
    if prev is not None and prev_ts is not None and toks is not None:
        prev_toks = (prev.get("engine") or {}).get("tokens_generated_total")
        dt = now - prev_ts
        if prev_toks is not None and dt > 0 and toks >= prev_toks:
            tok_rate = (toks - prev_toks) / dt
    return {
        "url": url,
        "model": eng.get("model") or "-",
        # lifecycle state from the engine's drain flag; workers
        # predating the field (or frontends) report None → '-'
        "draining": eng.get("draining"),
        "running": sched.get("running"),
        "waiting": sched.get("queue_depth"),
        "max_batch": eng.get("max_batch_size"),
        "kv_usage": pool.get("usage"),
        "kv_active": pool.get("active_blocks"),
        "kv_total": pool.get("total_blocks"),
        "tok_s": tok_rate,
        "slo": slo.get("attainment") if slo.get("enabled") else None,
        "hbm": hbm.get("bytes_in_use"),
        "slow_steps": rec.get("slow_steps"),
        "preemptions": sched.get("preemptions"),
        "error": None,
    }


HEADER = (
    f"{'WORKER':<28} {'MODEL':<12} {'STATE':>5} {'RUN':>5} {'WAIT':>5} "
    f"{'KV%':>7} {'TOK/S':>8} {'SLO%':>7} "
    f"{'HBM':>9} {'SSTEP':>5} {'SLOW':>5} {'PREEMPT':>7} "
    f"{'LAG99':>7} {'STRM':>6} {'RPS':>7}"
)


def render_frame(rows: list[dict], out: TextIO) -> None:
    out.write(HEADER + "\n")
    for r in rows:
        if r.get("error"):
            out.write(f"{r['url']:<28} !! {r['error']}\n")
            continue
        run = r["running"]
        mb = r["max_batch"]
        run_s = f"{run}/{mb}" if run is not None and mb else (
            str(run) if run is not None else "-"
        )
        tok = f"{r['tok_s']:8.1f}" if r["tok_s"] is not None else "       -"
        lag = r.get("loop_lag_p99_ms")
        lag_s = f"{lag:7.1f}" if lag is not None else "      -"
        strm = r.get("streams_open")
        rps = r.get("rps")
        rps_s = f"{rps:7.1f}" if rps is not None else "      -"
        dr = r.get("draining")
        state_s = "-" if dr is None else ("DRAIN" if dr else "up")
        out.write(
            f"{r['url']:<28} {str(r['model'])[:12]:<12} {state_s:>5} "
            f"{run_s:>5} "
            f"{str(r['waiting'] if r['waiting'] is not None else '-'):>5} "
            f"{_pct(r['kv_usage']):>7} {tok} "
            f"{_pct(r['slo']):>7} "
            f"{_fmt_bytes(r['hbm']):>9} "
            f"{str(r['slow_steps'] if r['slow_steps'] is not None else '-'):>5} "
            f"{str(r['slow_requests'] if r.get('slow_requests') is not None else '-'):>5} "
            f"{str(r['preemptions'] if r['preemptions'] is not None else '-'):>7} "
            f"{lag_s} {str(strm if strm is not None else '-'):>6} {rps_s}\n"
        )
    out.flush()


async def run_top(
    urls: list[str],
    interval: float = 2.0,
    iterations: Optional[int] = None,
    raw: bool = False,
    clear: bool = True,
    out: TextIO = sys.stdout,
) -> int:
    """Poll ``urls`` and render frames until ``iterations`` runs out
    (None = forever). Returns an exit code (1 when EVERY worker errored
    on the final frame — a dead fleet should fail scripts)."""
    prev: dict[str, tuple[dict, float]] = {}
    prev_hp: dict[str, Optional[dict]] = {}
    n = 0
    all_failed = False
    async with aiohttp.ClientSession() as session:
        while True:
            now = time.monotonic()
            results = await asyncio.gather(
                *[fetch_state(session, u) for u in urls],
                return_exceptions=True,
            )
            hp_results = await asyncio.gather(
                *[fetch_hostplane(session, u) for u in urls]
            )
            ap_results = await asyncio.gather(
                *[fetch_requests(session, u) for u in urls]
            )
            rows: list[dict] = []
            all_failed = True
            for url, res, hp, ap in zip(
                urls, results, hp_results, ap_results
            ):
                if isinstance(res, BaseException):
                    rows.append({"url": url, "error": str(res) or
                                 type(res).__name__})
                    continue
                all_failed = False
                p = prev.get(url)
                row = _engine_row(
                    url, res, p[0] if p else None, now,
                    p[1] if p else None,
                )
                row.update(_hostplane_cols(
                    hp, prev_hp.get(url), now, p[1] if p else None,
                ))
                row.update(_autopsy_cols(ap))
                rows.append(row)
                prev[url] = (res, now)
                prev_hp[url] = hp
            if raw:
                payload = {
                    r["url"] if "url" in r else urls[i]: r
                    for i, r in enumerate(rows)
                }
                out.write(json.dumps(payload) + "\n")
                out.flush()
            else:
                if clear and n > 0:
                    out.write("\x1b[2J\x1b[H")
                out.write(time.strftime("dynamo-tpu top  %H:%M:%S\n"))
                render_frame(rows, out)
            n += 1
            if iterations is not None and n >= iterations:
                break
            await asyncio.sleep(interval)
    return 1 if all_failed else 0


def cmd_top(args: Any) -> int:
    urls = args.urls or ["http://127.0.0.1:8000"]
    try:
        return asyncio.run(run_top(
            urls,
            interval=args.interval,
            iterations=1 if args.once else args.iterations,
            raw=args.raw,
            clear=not args.no_clear,
        ))
    except KeyboardInterrupt:
        return 0
