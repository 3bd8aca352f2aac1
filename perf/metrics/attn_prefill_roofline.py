"""Roofline share (%) of paged attention in PREFILL over the traced
interval, both layer kinds of ``mimo_v2_flash`` together
(``paged_attention_prefill_stacked_{full,window}*``). Least work: the
valid (query, key) pairs the prefill calls attended, from the program's
own counts ``attn_full_pairs`` + ``attn_window_pairs`` (units of 1 024,
summed over layers) x 64 heads x the PUBLISHED 2 x (192 + 128) = 640 FLOP
a pair and head. A window layer's kernel computes whole pages of which
the mask keeps 128 keys a query, so its share of this number is low by
construction: ``pairs_window_share`` in the note says how much of the
least work is its. What is compared and when the reader says nothing:
``mimo_v2_flash_costs.py``."""
from perf import roofline
from perf.metrics import mimo_v2_flash_costs as costs


def read(run, variant=""):
    ops = {**costs.kind_ops(run, "prefill", "full"),
           **costs.kind_ops(run, "prefill", "window")}
    deltas = costs.count_deltas(run)
    if not ops or not deltas:
        return None
    count = {n: costs.engine_count(deltas, n) for n in (
        "attn_full_pairs", "attn_window_pairs",
        "attn_full_prefill_calls", "attn_window_prefill_calls")}
    g = costs.geometry_of(run)
    if None in count.values() or g is None:
        return None
    pairs = (count["attn_full_pairs"] + count["attn_window_pairs"]) * costs.PAIR_UNIT
    calls = count["attn_full_prefill_calls"] + count["attn_window_prefill_calls"]
    if not pairs or not calls:
        return None
    traced = sum(v["calls"] for v in ops.values())
    measured = sum(v["total_s"] for v in ops.values())
    note = {"calls_counted": calls, "calls_traced": traced, "pairs": pairs,
            "pairs_window_share": count["attn_window_pairs"] * costs.PAIR_UNIT / pairs,
            "measured_s": measured, "labels": sorted(ops)}
    run.notes.append({"attn_prefill_roofline": note})
    if calls > traced or measured <= 0:
        return None
    least, bound = roofline.least_seconds(
        *costs.attn_prefill_cost(pairs, g["H"], g["Dk"], g["Dv"]),
        roofline.peaks(run.device["kind"]))
    note.update(bound=bound, least_s=least)
    return roofline.share_pct(least, measured)
