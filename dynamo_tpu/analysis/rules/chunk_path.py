"""DL013 blocking-work-in-chunk-path: heavyweight per-chunk work inside
an SSE writer loop.

The frontend's chunk path (http/service.py ``_stream_sse``) runs once
per delta for EVERY open stream on ONE event loop — at the fan-out
ceiling a microsecond of per-chunk work is multiplied by thousands of
streams times hundreds of chunks, and a MILLISECOND of synchronous work is a loop stall every stream observes
(telemetry/hostplane.py measures exactly this). Three families of work
do not belong inside the chunk loop:

- ``json.dumps``/``json.dump`` of whole aggregates — serializing a
  growing object per delta is O(stream²) host work; serialize the
  DELTA (protocols/sse.py ``encode_sse``) and keep aggregates out of
  the loop;
- tokenizer decode of accumulated history (``*.tokenizer.decode`` /
  ``.detokenize`` / ``.batch_decode``) — the preprocessor already
  detokenized the delta once; re-decoding the full history per chunk is
  the classic quadratic-TTFT bug;
- synchronous file/socket ops (``open``, ``os.read``/``os.write``,
  ``socket.sendall``/``recv``, ``time.sleep``) — any of these parks the
  WHOLE loop, not just this stream (DL002 catches generic blocking
  calls in async defs; DL013 scopes tighter and fires even in the sync
  helpers the writer loop calls).

Scope is name-structural like DL010: a function is a chunk path when
its name contains ``stream_sse`` or ``sse_write``, or appears in the
``sse-writer-functions`` config list ([tool.dynalint] — seeded with the
frontend's writer entry points). Only code inside a loop body
(``for``/``async for``/``while``, nested defs included) is flagged:
one-shot work before the stream starts is priming, not per-chunk cost.
"""

from __future__ import annotations

import ast

from dynamo_tpu.analysis.registry import LintModule, rule
from dynamo_tpu.analysis.rules.common import dotted_name

# whole-aggregate serializers (the delta path uses encode_sse once per
# chunk — that call lives OUTSIDE these functions and stays legal)
_JSON_CALLS = {"json.dumps", "json.dump"}

# blocking file/socket primitives by dotted name or bare call
_SYNC_CALLS = {
    "open", "os.open", "os.read", "os.write", "os.fsync", "time.sleep",
}
# blocking socket methods by attribute (receiver-agnostic: a socket in
# an SSE writer loop is wrong whatever it is called)
_SYNC_ATTRS = {"sendall", "recv", "recv_into"}

_DECODE_ATTRS = {"decode", "detokenize", "batch_decode"}


def _in_scope(name: str, extra: set[str]) -> bool:
    return "stream_sse" in name or "sse_write" in name or name in extra


def _flag(call: ast.Call) -> str | None:
    """The violation message for ``call``, or None."""
    name = dotted_name(call.func) or ""
    if name in _JSON_CALLS:
        return (
            f"`{name}(...)` inside the SSE chunk loop — serializing "
            "whole aggregates per delta is quadratic host work; "
            "serialize only the delta (protocols/sse.py encode_sse) "
            "and keep aggregates out of the loop"
        )
    if name in _SYNC_CALLS:
        return (
            f"`{name}(...)` inside the SSE chunk loop blocks the whole "
            "event loop once per chunk per stream — every concurrent "
            "stream observes the stall (loop-lag p99, "
            "telemetry/hostplane.py)"
        )
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        if attr in _SYNC_ATTRS:
            return (
                f"`.{attr}(...)` (sync socket op) inside the SSE chunk "
                "loop blocks the event loop — use the response's async "
                "write path"
            )
        if attr in _DECODE_ATTRS:
            recv = dotted_name(call.func.value) or ""
            if "tokenizer" in recv or "detok" in recv:
                return (
                    f"`{recv}.{attr}(...)` inside the SSE chunk loop — "
                    "re-decoding token history per chunk is quadratic; "
                    "the preprocessor already detokenized the delta "
                    "once"
                )
    return None


@rule(
    "blocking-work-in-chunk-path",
    "DL013",
    "heavyweight per-chunk work (whole-aggregate json.dumps, tokenizer "
    "decode of history, sync file/socket ops) inside an SSE writer "
    "loop — multiplied by streams × chunks on one event loop",
)
def check(module: LintModule):
    findings: list[tuple[ast.AST, str]] = []
    extra = set(module.config.get("sse-writer-functions", []))

    def scan_loop(loop: ast.AST) -> None:
        """Flag offending calls anywhere under a loop body, nested defs
        included (a helper defined in the loop runs per chunk too)."""
        for child in ast.walk(loop):
            if isinstance(child, ast.Call):
                msg = _flag(child)
                if msg is not None:
                    findings.append((child, msg))

    def scan_fn(fn: ast.AST) -> None:
        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    scan_loop(child)
                    continue  # scan_loop covered the whole subtree
                walk(child)

        walk(fn)

    for node in ast.walk(module.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _in_scope(node.name, extra):
            scan_fn(node)
    return findings
