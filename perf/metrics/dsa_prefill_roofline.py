"""Roofline share (%) of sparse latent attention in PREFILL over the
traced interval. Least work, from the program's own counts at the
capture's edges: ``dsa_prefill_selected`` (keys attended: the sum of
min(2 048, p + 1) over the prefilled tokens and layers, in units of 1 024)
x 64 heads x the published non-absorbed 2 x (256 + 256) FLOP a (query,
selected key) pair and head (``glm_moe_dsa_costs.prefill_attend_cost``),
against the bf16 peak. Measured: the device seconds of the attend kernel
in prefill programs, found by its name (``dsa_prefill_attention*``: the
scope ``dsa_attend``). The least work is the SELECTED keys whatever the
kernel does: the masked walk multiplies every live key in the absorbed
form, so it reads its true low share. A program without these counts or
this kernel, and a capture whose edge a call straddles, read nothing."""
from perf import roofline
from perf.metrics import glm_moe_dsa_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    ops = costs.kernel_ops(run, ("dsa_prefill_attention",))
    got = costs.counted(run) if ops else None
    g = family_of(run.config).geometry(run.config)
    if not got or not got["dsa_prefill_selected"] or "topk" not in g:
        return None
    measured = sum(v["total_s"] for v in ops.values())
    selected = got["dsa_prefill_selected"] * costs.PAIR_UNIT
    least, bound = roofline.least_seconds(*costs.prefill_attend_cost(
        selected, g["H"], g["nope"], g["rope"], g["vd"]),
        roofline.peaks(run.device["kind"]))
    run.notes.append({"dsa_prefill_roofline": {
        "selected_pairs": selected,
        "scored_pairs": got["dsa_index_pairs"] * costs.PAIR_UNIT,
        "bound": bound, "least_s": least, "measured_s": measured,
        "labels": sorted(ops)}})
    return roofline.share_pct(least, measured) if measured > 0 else None
