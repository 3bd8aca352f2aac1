"""Operations and bytes of the ``kimi_linear`` family's own kernels, per
call from its shapes — the yardstick of ``kda_decode_roofline``,
``mla_decode_roofline`` and ``moe_roofline`` (no metric of this name:
the readers beside it import it). Peaks, least time and the share that
raises over 100% are ``perf/roofline.py``'s.

Also ``count_deltas``: what the program's cumulative counts grew by over
the profiler capture (``program_spans.json``: ``start`` / ``stop``).
"""

from __future__ import annotations

from perf import roofline
from perf.trace import program_spans

F32, BF16, INT8 = 4, 2, 1


def kda_decode_cost(rows: int, heads: int, head_dim: int) -> tuple[float, float]:
    """One recurrent update of ``rows`` sequences, one layer: each row's
    ``[heads, d, d]`` float32 state crosses HBM once in and once out;
    q, k, v, the log-decay (float32 ``[heads, d]`` each) and beta in, the
    output out. Per state element: decay multiply, k-product and its sum,
    the rank-one update (multiply, add), q-product and its sum."""
    state = heads * head_dim * head_dim
    ops = 8.0 * rows * state
    byts = rows * (2 * state * F32 + 5 * heads * head_dim * F32 + heads * F32)
    return ops, float(byts)


def mla_decode_cost(contexts: list[int], heads: int, rank: int, rope: int,
                    row_bytes: int = BF16) -> tuple[float, float]:
    """One latent-attention decode call (one layer) over rows with
    ``contexts`` cached tokens: every cached row of ``rank + rope`` values
    is read once (it is key and value at once); scores over rank + rope,
    values over rank, for each of ``heads`` query heads; the absorbed
    query in and the latent-space output out."""
    width = rank + rope
    tokens = float(sum(contexts))
    ops = 2.0 * heads * tokens * (width + rank)
    byts = tokens * width * row_bytes \
        + len(contexts) * heads * (width + rank) * BF16
    return ops, byts


def moe_cost(assignments: float, touched: float, rows: float, hidden: int,
             width: int) -> tuple[float, float]:
    """The routed experts of one expert layer: ``assignments`` (token,
    expert) pairs each through gate, up and down (``[hidden, width]``
    twice, ``[width, hidden]`` once); the int8 weights and float32 scales
    of the ``touched`` experts cross HBM once; the rows' activations in
    and the combined result out."""
    ops = 2.0 * assignments * 3 * hidden * width
    per_expert = 3 * hidden * width * INT8 + (2 * width + hidden) * F32
    byts = touched * per_expert + 2 * rows * hidden * BF16
    return ops, float(byts)


def count_deltas(run) -> dict | None:
    """``{flat count name: growth over the capture}`` of the program's
    counts, or None where the program wrote none."""
    doc = program_spans.spans_doc(run)
    return None if doc is None else program_spans._count_deltas(doc)


def engine_count(deltas: dict, name: str):
    """The engine's count ``name`` (``engine.<name>``), or None."""
    for key, value in deltas.items():
        if key == name or key.endswith("." + name):
            return value
    return None


def decode_samples(run) -> list[list[int]]:
    """The running rows' contexts at each ``/debug/state`` sample taken
    during the capture."""
    lo, hi = run.trace_span
    return [s["contexts"] for s in run.samples
            if lo - 0.5 <= s["t"] <= hi + 0.5 and s["contexts"]]


def decode_kernel_share(run, name: str, prefix: str, cost_of_rows) -> float | None:
    """Roofline share (%) of a decode kernel found by its label's
    ``prefix``: ``cost_of_rows(contexts) -> (ops, bytes)`` of ONE call
    (one layer) at each sample's running rows, averaged over the samples,
    times the calls the trace shows, over the time it shows. None where
    the trace has no such kernel or no sample fell into the capture."""
    ops = {k: v for k, v in (run.trace or {}).get("ops", {}).items()
           if k.startswith(prefix)}
    samples = decode_samples(run)
    if not ops or not samples:
        return None
    pk = roofline.peaks(run.device["kind"])
    per_call = [roofline.least_seconds(*cost_of_rows(ctx), pk) for ctx in samples]
    least_call = sum(t for t, _ in per_call) / len(per_call)
    calls = sum(v["calls"] for v in ops.values())
    measured = sum(v["total_s"] for v in ops.values())
    run.notes.append({name: {
        "bound": per_call[0][1], "least_s_per_call": least_call, "calls": calls,
        "measured_s": measured, "labels": sorted(ops),
        "rows_mean": sum(map(len, samples)) / len(samples),
        "context_mean": sum(map(sum, samples)) / max(1, sum(map(len, samples)))}})
    return roofline.share_pct(least_call * calls, measured)
