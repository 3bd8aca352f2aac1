"""HTTP service tests with a counting mock engine over real sockets
(≈ reference lib/llm/tests/http-service.rs CounterEngine)."""

import asyncio
import json
from typing import Any, AsyncIterator

import aiohttp

from dynamo_tpu.http.service import HttpService, ModelManager
from dynamo_tpu.protocols.common import FinishReason
from dynamo_tpu.protocols.openai import ChatCompletionRequest, ChatDeltaGenerator
from dynamo_tpu.protocols.sse import SseDecoder
from dynamo_tpu.runtime.engine import AsyncEngine, Context, EngineStream


class CounterEngine(AsyncEngine):
    """Streams N words; counts requests and cancellations."""

    def __init__(self, n: int = 5, delay: float = 0.0):
        self.n = n
        self.delay = delay
        self.requests = 0
        self.cancelled = 0

    async def _gen(self, request: Any, ctx: Context) -> AsyncIterator[Any]:
        self.requests += 1
        self.produced = 0
        self.ctx = ctx
        assert isinstance(request, ChatCompletionRequest)
        gen = ChatDeltaGenerator(model=request.model)
        for i in range(self.n):
            if ctx.is_stopped:
                self.cancelled += 1
                return
            if self.delay:
                await asyncio.sleep(self.delay)
            self.produced += 1
            yield gen.text_chunk(f"w{i} ")
        yield gen.finish_chunk(FinishReason.STOP)

    def generate(self, request: Any, context: Context) -> EngineStream:
        return self._gen(request, context)


async def _start_service(engine) -> tuple[HttpService, str]:
    manager = ModelManager()
    manager.add_chat_model("foo", engine)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    return service, f"http://127.0.0.1:{service.port}"


async def test_models_and_health():
    service, base = await _start_service(CounterEngine())
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/models") as r:
                assert r.status == 200
                body = await r.json()
                assert [m["id"] for m in body["data"]] == ["foo"]
            async with s.get(f"{base}/health") as r:
                assert (await r.json())["status"] == "healthy"
    finally:
        await service.stop()


async def test_chat_streaming_sse():
    service, base = await _start_service(CounterEngine(n=3))
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
            }
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/event-stream")
                dec = SseDecoder()
                msgs = []
                async for chunk, _ in r.content.iter_chunks():
                    msgs.extend(dec.feed(chunk))
        assert msgs[-1].is_done
        chunks = [m.json() for m in msgs[:-1]]
        text = "".join(
            c["choices"][0]["delta"].get("content", "")
            for c in chunks
            if c["choices"]
        )
        assert text == "w0 w1 w2 "
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
    finally:
        await service.stop()


async def test_chat_non_streaming_aggregates():
    service, base = await _start_service(CounterEngine(n=4))
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "hi"}],
            }
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200
                body = await r.json()
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["content"] == "w0 w1 w2 w3 "
        assert body["choices"][0]["finish_reason"] == "stop"
    finally:
        await service.stop()


async def test_unknown_model_404_and_bad_json_400():
    service, base = await _start_service(CounterEngine())
    try:
        async with aiohttp.ClientSession() as s:
            payload = {"model": "nope", "messages": [{"role": "user", "content": "x"}]}
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 404
                assert "not found" in (await r.json())["error"]["message"]
            async with s.post(
                f"{base}/v1/chat/completions",
                data=b"{not json",
                headers={"Content-Type": "application/json"},
            ) as r:
                assert r.status == 400
            # missing required field
            async with s.post(f"{base}/v1/chat/completions", json={"model": "foo"}) as r:
                assert r.status == 400
    finally:
        await service.stop()


async def test_client_disconnect_cancels_engine():
    engine = CounterEngine(n=1000, delay=0.01)
    service, base = await _start_service(engine)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "x"}],
                "stream": True,
            }
            resp = await s.post(f"{base}/v1/chat/completions", json=payload)
            # read a few chunks then slam the connection shut
            await resp.content.read(64)
            resp.close()
        await asyncio.sleep(0.5)
        n = engine.produced
        assert n < 1000, "engine was not interrupted"
        await asyncio.sleep(0.3)
        assert engine.produced == n, "engine kept producing after disconnect"
    finally:
        await service.stop()


async def test_disconnect_before_the_sse_headers_kills_the_generation(monkeypatch):
    """A client that leaves while its first chunk is being primed makes
    the response's headers fail on the closing transport. Seen on the
    chip: the handler answered 500 to nobody and the sequence ran on to
    max_tokens. The generation has to be killed like any other whose
    client went away."""
    from aiohttp import web

    async def closing(self):
        if self.content_type == "text/event-stream":
            raise ConnectionResetError("Cannot write to closing transport")
        return await real(self)

    real = web.StreamResponse._write_headers
    monkeypatch.setattr(web.StreamResponse, "_write_headers", closing)
    engine = CounterEngine(n=1000, delay=0.01)
    service, base = await _start_service(engine)
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "x"}],
                "stream": True,
            }
            try:
                async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                    await r.read()
            except aiohttp.ClientError:
                pass  # no headers ever arrive: the client's side of it
        assert engine.requests == 1
        assert engine.ctx.is_killed
    finally:
        await service.stop()


async def test_metrics_endpoint():
    service, base = await _start_service(CounterEngine(n=1))
    try:
        async with aiohttp.ClientSession() as s:
            payload = {"model": "foo", "messages": [{"role": "user", "content": "x"}]}
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                await r.json()
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
        assert "dynamo_http_requests_total" in text
        assert 'model="foo"' in text
    finally:
        await service.stop()


# ---------------------------------------------------------------------------
# OpenAI wire-schema conformance for logprobs / top_logprobs / n>1 over the
# REAL pipeline (preprocessor -> fanout -> backend -> JaxEngine), asserted
# from raw SSE — the serialization layer the engine-level tests in
# test_logprobs_n.py never cross (reference schema:
# lib/llm/src/protocols/common.rs:323-372 ChatCompletionLogprobs/TopLogprob).
# ---------------------------------------------------------------------------

import os

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


async def _real_pipeline_service():
    """HttpService over the full serving pipeline on the tiny model."""
    from dynamo_tpu.backend import Backend
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.preprocessor import OpenAIPreprocessor, PromptFormatter
    from dynamo_tpu.preprocessor.fanout import ChoiceFanout
    from dynamo_tpu.runtime.pipeline import build_pipeline
    from dynamo_tpu.tokenizer import Tokenizer

    engine = await JaxEngine.launch(EngineConfig(
        model_path=MODEL_DIR, model_name="tiny", random_weights=True,
        num_blocks=64, block_size=8, max_batch_size=4,
        prefill_chunk_size=32, max_model_len=128,
    ))
    tokenizer = Tokenizer.from_file(MODEL_DIR)
    formatter = PromptFormatter.from_model_dir(MODEL_DIR)
    pre = OpenAIPreprocessor(tokenizer, formatter, model_name="tiny")
    pipeline = build_pipeline(
        pre,
        ChoiceFanout(build_pipeline(
            Backend(tokenizer, eos_token_ids=engine.eos_token_ids),
            engine.as_async_engine(),
        )),
    )
    manager = ModelManager()
    manager.add_chat_model("tiny", pipeline)
    manager.add_completion_model("tiny", pipeline)
    service = HttpService(manager, host="127.0.0.1", port=0)
    await service.start()
    return service, f"http://127.0.0.1:{service.port}", engine


async def _sse_json_events(resp) -> list:
    dec = SseDecoder()
    events = []
    async for chunk, _ in resp.content.iter_chunks():
        for msg in dec.feed(chunk.decode()):
            if msg.data and msg.data != "[DONE]":
                events.append(json.loads(msg.data))
    return events


async def test_http_chat_sse_logprobs_wire_schema():
    """Raw SSE chat stream with logprobs+top_logprobs: every content
    delta carries OpenAI's nested logprob schema — content[] entries of
    {token, logprob, bytes, top_logprobs[{token, logprob, bytes}]} —
    with exactly one finish-reason chunk and one trailing usage chunk."""
    service, base, engine = await _real_pipeline_service()
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "tiny",
                "messages": [{"role": "user", "content": "hello world"}],
                "stream": True,
                "stream_options": {"include_usage": True},
                "max_tokens": 4,
                "logprobs": True,
                "top_logprobs": 2,
                "temperature": 0,
                "ignore_eos": True,
            }
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200
                events = await _sse_json_events(r)

        lp_entries = []
        finish_chunks = []
        usage_chunks = [e for e in events if e.get("usage")]
        for e in events:
            assert e["object"] == "chat.completion.chunk"
            for ch in e.get("choices", []):
                assert ch["index"] == 0
                if ch.get("finish_reason"):
                    finish_chunks.append(ch["finish_reason"])
                lp = ch.get("logprobs")
                if lp:
                    lp_entries.extend(lp["content"])
        assert len(lp_entries) == 4  # one per generated token
        for entry in lp_entries:
            assert set(entry) >= {"token", "logprob", "bytes", "top_logprobs"}
            assert isinstance(entry["logprob"], float) and entry["logprob"] <= 0
            assert isinstance(entry["bytes"], list)
            assert len(entry["top_logprobs"]) == 2
            for alt in entry["top_logprobs"]:
                assert set(alt) >= {"token", "logprob", "bytes"}
            # greedy: chosen token must be the argmax alternative
            assert entry["logprob"] == max(
                a["logprob"] for a in entry["top_logprobs"]
            )
        assert finish_chunks == ["length"]
        # exactly ONE trailing usage chunk, after all choice chunks
        assert len(usage_chunks) == 1
        assert usage_chunks[0]["choices"] == []
        assert usage_chunks[0]["usage"]["completion_tokens"] == 4
        assert events[-1].get("usage") is not None
    finally:
        await service.stop()
        await engine.shutdown()


async def test_http_chat_sse_n2_wire_schema():
    """n=2 over raw SSE: per-choice index/role/finish_reason and a
    single usage accounting BOTH choices' completion tokens."""
    service, base, engine = await _real_pipeline_service()
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "tiny",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
                "stream_options": {"include_usage": True},
                "max_tokens": 3,
                "n": 2,
                "temperature": 0.9,
                "seed": 7,
                "ignore_eos": True,
            }
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200
                events = await _sse_json_events(r)
        finishes = {}
        roles = set()
        for e in events:
            for ch in e.get("choices", []):
                assert ch["index"] in (0, 1)
                if ch.get("delta", {}).get("role"):
                    roles.add(ch["index"])
                if ch.get("finish_reason"):
                    finishes[ch["index"]] = ch["finish_reason"]
        assert roles == {0, 1}
        assert finishes == {0: "length", 1: "length"}
        usage_chunks = [e for e in events if e.get("usage")]
        assert len(usage_chunks) == 1
        assert usage_chunks[0]["usage"]["completion_tokens"] == 6
    finally:
        await service.stop()
        await engine.shutdown()


async def test_http_completions_logprobs_wire_schema():
    """Non-streaming /v1/completions with logprobs=2: OpenAI completions
    schema — parallel tokens/token_logprobs/top_logprobs/text_offset
    arrays, offsets indexing into the returned text."""
    service, base, engine = await _real_pipeline_service()
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "tiny", "prompt": "one two three",
                "max_tokens": 4, "logprobs": 2, "temperature": 0,
                "ignore_eos": True,
            }
            async with s.post(f"{base}/v1/completions", json=payload) as r:
                assert r.status == 200
                body = await r.json()
        choice = body["choices"][0]
        assert choice["finish_reason"] == "length"
        lp = choice["logprobs"]
        assert set(lp) >= {"tokens", "token_logprobs", "top_logprobs", "text_offset"}
        assert len(lp["tokens"]) == 4
        assert len(lp["token_logprobs"]) == 4
        assert len(lp["top_logprobs"]) == 4
        assert len(lp["text_offset"]) == 4
        # offsets are monotonically non-decreasing and start at 0
        assert lp["text_offset"][0] == 0
        assert lp["text_offset"] == sorted(lp["text_offset"])
        for t_lp, tops in zip(lp["token_logprobs"], lp["top_logprobs"]):
            assert t_lp <= 0
            # the dict is keyed by token STRING: distinct ids decoding to
            # the same text collapse (keep-max), so 1 <= len <= 2
            assert 1 <= len(tops) <= 2 and all(v <= 0 for v in tops.values())
            assert t_lp == max(tops.values())  # greedy pick is the argmax
        assert body["usage"]["completion_tokens"] == 4
    finally:
        await service.stop()
        await engine.shutdown()
