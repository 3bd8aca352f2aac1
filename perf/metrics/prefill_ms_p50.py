"""Median time from a request's admission to its last prompt chunk
computed (``engine.prefill`` spans); the mean chunks and cached tokens of
those spans go to the notes."""
from perf import measure
from perf.trace import program_spans


def read(run, variant=""):
    reqs = program_spans.requests(run)
    spans = [t["engine.prefill"] for t in reqs or [] if "engine.prefill" in t]
    if not spans:
        return None
    run.notes.append({"prefill_ms_p50": {
        "spans": len(spans),
        "chunks_mean": sum(s["attrs"].get("chunks", 0) for s in spans) / len(spans),
        "prompt_tokens": sum(s["attrs"]["prompt_tokens"] for s in spans)}})
    return measure.percentile([s["duration_s"] * 1e3 for s in spans], 50)
