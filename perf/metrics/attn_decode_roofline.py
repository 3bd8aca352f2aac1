"""Roofline share (%) of the paged-attention decode kernel over the
traced interval. Bytes are the KV pages ACTUALLY read at the batch's
context lengths (whole 128-token pages of K and V per running row, from
the ``/debug/state`` samples taken during the capture), per call = per
layer: the count is multiplied by the calls the trace shows, never by a
layer count, so it holds for a model in which only some layers attend.
Time is the trace's total for ``paged_attention_decode*``. Heads and
head size come from ``geometry`` of the configuration's family module
(``perf/reference/family.py``)."""

from perf import roofline
from perf.reference.family import family_of


def read(run, variant=""):
    ops = {k: v for k, v in (run.trace or {}).get("ops", {}).items()
           if k.startswith("paged_attention_decode")}
    lo, hi = run.trace_span
    samples = [s["contexts"] for s in run.samples
               if lo - 0.5 <= s["t"] <= hi + 0.5 and s["contexts"]]
    if not ops or not samples:
        return None
    g = family_of(run.config).geometry(run.config)
    pk = roofline.peaks(run.device["kind"])
    per_call = [roofline.least_seconds(*roofline.attn_decode_cost(
        ctx, g["H"], g["Hk"], g["Dh"], run.block_size), pk) for ctx in samples]
    least_call = sum(t for t, _ in per_call) / len(per_call)
    calls = sum(v["calls"] for v in ops.values())
    measured = sum(v["total_s"] for v in ops.values())
    run.notes.append({"attn_decode_roofline": {
        "bound": per_call[0][1], "least_s_per_call": least_call, "calls": calls,
        "measured_s": measured, "rows_mean": sum(map(len, samples)) / len(samples),
        "context_mean": sum(map(sum, samples)) / max(1, sum(map(len, samples)))}})
    return roofline.share_pct(least_call * calls, measured)
