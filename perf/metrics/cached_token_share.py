"""Share (%) of the window's prompt tokens that the prefix cache already
held at admission: sum of ``cached_tokens`` over sum of ``prompt_tokens``
of the ``engine.prefill`` spans. Unlike ``prefix_hit_share`` (requests
that found ANY cached prefix) it halves when half as many tokens are
cached per turn."""
from perf.trace import program_spans


def read(run, variant=""):
    reqs = program_spans.requests(run)
    spans = [t["engine.prefill"]["attrs"] for t in reqs or []
             if "engine.prefill" in t]
    prompt = sum(a["prompt_tokens"] for a in spans)
    if not prompt:
        return None
    return 100.0 * sum(a["cached_tokens"] for a in spans) / prompt
