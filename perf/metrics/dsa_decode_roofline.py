"""Roofline share (%) of a decode step's selection and sparse attention
over the traced interval. Least bytes, from the program's own counts at
the capture's edges: ``dsa_decode_selected`` (keys attended by decode
calls, summed over rows and layers) x 576 published latent values x 2 B
+ ``dsa_decode_scored`` (keys scored) x 128 indexer values x 2 B
(``glm_moe_dsa_costs.decode_cost``), against the HBM peak. Measured: the
device seconds of the three kernels in decode programs, found by their
names (``dsa_index_decode*``, ``dsa_select_decode*``,
``dsa_decode_attention*``: the scopes ``dsa_index``, ``dsa_select``,
``dsa_attend``), and of what the scope ``dsa_index`` runs beside its
kernel — the indexer's projections and the XLA gather of the rows'
``index_k`` pages (``glm_moe_dsa_costs.index_side_ops``; ``side_s`` in the
note). The masked walk reads every live 640-lane row where the
least work reads the 2 048 selected of 576 values: its share says how far
a gather of the selected rows could go. A program without these counts or
kernels, and a capture whose edge a call straddles, read nothing."""
from perf import roofline
from perf.metrics import glm_moe_dsa_costs as costs
from perf.reference.family import family_of

KERNELS = ("dsa_index_decode", "dsa_select_decode", "dsa_decode_attention")


def read(run, variant=""):
    ops = costs.kernel_ops(run, KERNELS)
    got = costs.counted(run) if ops else None
    g = family_of(run.config).geometry(run.config)
    if not got or not got["dsa_decode_scored"] or "dI" not in g:
        return None
    side = costs.index_side_ops(run, KERNELS[0], g["dI"])
    side_s = sum(v["total_s"] for v in side.values())
    measured = sum(v["total_s"] for v in ops.values()) + side_s
    least, bound = roofline.least_seconds(*costs.decode_cost(
        got["dsa_decode_selected"], got["dsa_decode_scored"], g["rank"],
        g["rope"], g["dI"]), roofline.peaks(run.device["kind"]))
    by_kernel = {k: sum(v["total_s"] for name, v in ops.items()
                        if name.startswith(k)) for k in KERNELS}
    run.notes.append({"dsa_decode_roofline": {
        "selected": got["dsa_decode_selected"], "scored": got["dsa_decode_scored"],
        "bound": bound, "least_s": least, "measured_s": measured,
        "seconds_by_kernel": by_kernel, "side_s": side_s,
        "side_calls": sum(v["calls"] for v in side.values())}})
    return roofline.share_pct(least, measured) if measured > 0 else None
