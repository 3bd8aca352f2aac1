"""Roofline share (%) of latent attention in PREFILL over the traced
interval. Least work, from the program's own counts at the capture's
edges: ``mla_prefill_pairs`` (valid (query, key) pairs its prefill calls
attended, summed over layers, in units of 1 024) x heads x the published
non-absorbed 640 FLOP a pair and head
(``deepseek_v3_costs.mla_prefill_cost``). Measured: the device seconds of
the prefill attention kernel, found by its name
(``mla_prefill_attention*``).

The same calls on both sides: the capture reads the counts AFTER
``start_trace`` and BEFORE ``stop_trace``, each time once every step
dispatched so far has finished on the device — so every call the counts'
growth holds ran inside the trace whole, and a prefill in flight at the
capture's first edge is traced but not counted: the share can err low by
that one program, never high. Where the counts hold MORE calls than the
trace shows (an edge this reasoning missed), the reader says nothing. A
program without these counts or this kernel reads nothing."""
from perf import roofline
from perf.metrics import deepseek_v3_costs as costs
from perf.metrics.kimi_linear_costs import count_deltas, engine_count
from perf.reference.family import family_of


def read(run, variant=""):
    ops = {k: v for k, v in (run.trace or {}).get("ops", {}).items()
           if k.startswith("mla_prefill_attention")}
    deltas = count_deltas(run)
    if not ops or not deltas:
        return None
    pairs = engine_count(deltas, "mla_prefill_pairs")
    calls = engine_count(deltas, "mla_prefill_calls")
    tokens = engine_count(deltas, "mla_prefill_query_tokens")
    g = family_of(run.config).geometry(run.config)
    if not pairs or not calls or "vd" not in g:
        return None
    traced = sum(v["calls"] for v in ops.values())
    measured = sum(v["total_s"] for v in ops.values())
    note = {"calls_counted": calls, "calls_traced": traced,
            "pairs": pairs * costs.PAIR_UNIT, "query_tokens": tokens,
            "measured_s": measured, "labels": sorted(ops)}
    run.notes.append({"mla_prefill_roofline": note})
    if calls > traced or measured <= 0:
        return None
    least, bound = roofline.least_seconds(*costs.mla_prefill_cost(
        pairs * costs.PAIR_UNIT, g["H"], g["nope"], g["rope"], g["vd"]),
        roofline.peaks(run.device["kind"]))
    note.update(bound=bound, least_s=least,
                keys_per_query=pairs * costs.PAIR_UNIT / max(1, tokens))
    return roofline.share_pct(least, measured)
