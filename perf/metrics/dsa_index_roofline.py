"""Roofline share (%) of the indexer's score in PREFILL over the traced
interval. Least work, from the program's own counts at the capture's
edges: ``dsa_index_pairs`` ((query, key) pairs its prefill calls scored —
a token at position p scores p + 1 — summed over layers, in units of
1 024) x 32 heads x a 128-wide dot product x 2 FLOP, at the published
sizes (``glm_moe_dsa_costs.index_cost``), against the bf16 peak. Measured:
the device seconds of the index kernel in prefill programs, found by its
name (``dsa_index_prefill*``: the scope ``dsa_index``), and of what that
scope runs beside it — the indexer's projections and the XLA gather of
the row's ``index_k`` pages (``glm_moe_dsa_costs.index_side_ops``;
``side_s`` in the note). The kernel scores
whole tiles, keys past a query's own position among them, so the share is
under 100 by construction. A program without these counts or this kernel,
and a capture whose edge a call straddles, read nothing."""
from perf import roofline
from perf.metrics import glm_moe_dsa_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    ops = costs.kernel_ops(run, ("dsa_index_prefill",))
    got = costs.counted(run) if ops else None
    g = family_of(run.config).geometry(run.config)
    if not got or not got["dsa_index_pairs"] or "dI" not in g:
        return None
    side_s = sum(v["total_s"] for v in costs.index_side_ops(
        run, "dsa_index_prefill", g["dI"]).values())
    measured = sum(v["total_s"] for v in ops.values()) + side_s
    least, bound = roofline.least_seconds(*costs.index_cost(
        got["dsa_index_pairs"] * costs.PAIR_UNIT, g["G"], g["dI"]),
        roofline.peaks(run.device["kind"]))
    run.notes.append({"dsa_index_roofline": {
        "pairs": got["dsa_index_pairs"] * costs.PAIR_UNIT, "bound": bound,
        "least_s": least, "measured_s": measured, "side_s": side_s,
        "labels": sorted(ops),
        "calls_counted": got["dsa_calls"], "calls_traced": got["calls_traced"]}})
    return roofline.share_pct(least, measured) if measured > 0 else None
