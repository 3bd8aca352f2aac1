"""The load generator: one asyncio loop, one process, no threads of its own.

A traffic kind's ``drive(load)`` coroutine (``perf/traffic/kinds/``) turns
its schedule into calls of ``Load.request``; everything about HTTP, the
clock and the records lives here. Every request streams
``/v1/completions`` with its scheduled ``max_tokens``, ``ignore_eos`` and
greedy sampling, so an answer has exactly the scheduled length whatever
the weights are. Latency is timed from the request's DUE time, and how
late the generator sent it is recorded beside it.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Optional

import aiohttp

from perf.server import MODEL_NAME
from perf.traffic.schedule import token_ids, words


@dataclass
class Record:
    key: tuple
    due: float            # monotonic seconds
    prompt_tokens: int
    out_tokens: int       # scheduled
    sent: float = 0.0
    first: float = 0.0
    last: float = 0.0
    received: int = 0
    chunks: list = field(default_factory=list)   # (time, tokens)
    usage: Optional[dict] = None
    error: str = ""
    cancelled: bool = False
    text: str = ""

    @property
    def failed(self) -> bool:
        """Errored, refused, or a stream shorter (or longer) than
        scheduled; a request the generator itself cancelled at the
        window's end is neither failed nor complete."""
        if self.cancelled:
            return False
        return bool(self.error) or self.received != self.out_tokens


class Load:
    """What a kind's driver sees: the window, the seed's token ids, and
    ``request``."""

    def __init__(self, url: str, schedule: dict, mix: dict, seed: int,
                 vocab: int):
        self.url, self.schedule, self.mix = url, schedule, mix
        self.seed, self.vocab = seed, vocab
        self.records: list[Record] = []
        self.t0 = 0.0          # the window's first instant (monotonic)
        self.end = 0.0         # its last
        self._session: Optional[aiohttp.ClientSession] = None
        self.cancelling = False  # set by a driver that ends requests itself

    # -- what kinds use ----------------------------------------------------
    def ids(self, key: tuple, n: int) -> list[int]:
        return token_ids(self.seed, key, n, self.vocab)

    async def sleep_until(self, t: float) -> None:
        delay = t - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)

    async def request(self, key: tuple, due: float, prompt_ids: list[int],
                      out_tokens: int, keep_text: bool = False) -> Record:
        """Send one request (now; the caller slept until ``due``) and
        stream its answer to the end."""
        rec = Record(key=key, due=due, prompt_tokens=len(prompt_ids),
                     out_tokens=out_tokens)
        self.records.append(rec)
        await self._stream(rec, words(prompt_ids), keep_text)
        return rec

    # -- the wire ------------------------------------------------------------
    def _body(self, prompt: str, out_tokens: int, **extra) -> dict:
        return {
            "model": MODEL_NAME, "prompt": prompt, "max_tokens": out_tokens,
            "temperature": 0.0, "stream": True,
            "stream_options": {"include_usage": True},
            "ext": {"ignore_eos": True, "greedy_sampling": True}, **extra,
        }

    async def _stream(self, rec: Record, prompt: str, keep_text: bool,
                      **extra) -> None:
        assert self._session is not None
        body = json.dumps(self._body(prompt, rec.out_tokens, **extra))
        rec.sent = time.monotonic()
        lps: list[float] = []
        try:
            async with self._session.post(
                self.url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"},
            ) as resp:
                if resp.status != 200:
                    rec.error = f"http {resp.status}: {(await resp.text())[:200]}"
                    return
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    data = raw[5:].strip()
                    if data == b"[DONE]":
                        break
                    ev = json.loads(data)
                    rec.usage = ev.get("usage") or rec.usage
                    for choice in ev.get("choices") or []:
                        text = choice.get("text") or ""
                        n = len(text.split())
                        if n:
                            now = time.monotonic()
                            if not rec.received:
                                rec.first = now
                            rec.last = now
                            rec.received += n
                            rec.chunks.append((now, n))
                            if keep_text:
                                rec.text += " " + text
                        lp = (choice.get("logprobs") or {}).get("token_logprobs")
                        if lp:
                            lps.extend(lp)
        except asyncio.CancelledError:
            # the closed loop ends its in-flight requests on purpose at
            # the window's end; any other cancellation is the drain limit
            if self.cancelling:
                rec.cancelled = True
            else:
                rec.error = "not finished at the drain limit"
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:200]
        if lps:
            rec.usage = dict(rec.usage or {}, token_logprobs=lps)

    # -- running a schedule --------------------------------------------------
    async def run(self, drive, on_window=None) -> dict:
        """Ramp, window, drain. ``drive(self)`` issues the requests;
        ``on_window(self)`` (the traced run's sampler and capture) runs
        beside it for the length of the window."""
        ramp = float(self.schedule["ramp_s"])
        seconds = float(self.schedule["seconds"])
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout, connector=conn) as s:
            self._session = s
            ramp_start = time.monotonic() + 0.05
            self.t0 = ramp_start + ramp
            self.end = self.t0 + seconds
            driver = asyncio.ensure_future(drive(self))
            side = (asyncio.ensure_future(on_window(self))
                    if on_window is not None else None)
            await self.sleep_until(self.end)
            drain_t0 = time.monotonic()
            limit = float(self.mix.get("drain_limit_s", 60))
            try:
                await asyncio.wait_for(driver, timeout=limit)
                timed_out = False
            except asyncio.TimeoutError:
                timed_out = True  # wait_for cancelled the driver
            if side is not None:
                await side
            self._session = None
        return {"ramp_s": ramp, "drain_s": time.monotonic() - drain_t0,
                "drain_timed_out": timed_out}

    async def check_requests(self, jobs: list[dict]) -> list[dict]:
        """The correctness probe's requests (outside the window): each
        job ``{ids, out, ...}`` comes back with ``chosen`` ids and their
        ``logprobs``."""
        timeout = aiohttp.ClientTimeout(total=300)
        async with aiohttp.ClientSession(timeout=timeout) as s:
            self._session = s

            async def one(job: dict) -> dict:
                rec = Record(key=("check",), due=time.monotonic(),
                             prompt_tokens=len(job["ids"]), out_tokens=job["out"])
                await self._stream(rec, words(job["ids"]), True, logprobs=0)
                if rec.failed:
                    raise RuntimeError(f"check request failed: {rec.error or rec.received}")
                chosen = [int(w[1:]) for w in rec.text.split()]
                lps = (rec.usage or {}).get("token_logprobs") or []
                if not len(chosen) == len(lps) == job["out"]:
                    raise RuntimeError(
                        f"check request: {len(chosen)} ids and {len(lps)} "
                        f"logprobs for {job['out']} tokens")
                return dict(job, chosen=chosen, logprobs=lps, prompt_tokens_served=(
                    rec.usage or {}).get("prompt_tokens"))

            out = await asyncio.gather(*(one(j) for j in jobs))
            self._session = None
        return list(out)
