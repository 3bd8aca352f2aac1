"""Roofline share (%) of the fused-dequant int8 matmul kernels (``qmm``)
over the traced interval: the least time their calls could take (larger
of ops / 197 TFLOP/s and bytes / 819 GB/s; int8 weights 1 byte each plus
f32 scales, activations and results at bf16) over the device time the
trace measured for them AND for the int8 weight-slice fusions that feed
them: today XLA copies each layer's weights out of the stacked
``[layers, K, N]`` array into on-chip memory (``dynamic-slice_bitcast_fusion``
with an ``s8[K, N]`` result) before the kernel runs, so the kernel alone
reads its weights faster than HBM could deliver them (measured: 121% of
the HBM floor at 8 rows) and the copy is where the HBM read is paid.
Leaving the copies out would leave out part of the work.

The kernels have no stable name yet: they are the Mosaic custom calls
``closed_call.<n>`` with a bf16 ``[rows, N]`` result. N alone does not say
which matmul a call is (wq, wo and w_down all give the hidden size), and
two programs can use one op name for different matmuls, so nothing is
classified call by call: a layer runs each of its matmuls exactly once,
so among the calls of one row count those with result width N are
``calls / (matmuls of that width per layer)`` layer passes, each costing
the SUM of that width's matmuls. Rows are the padded rows of the result.
The head (N = vocabulary) is counted where it shows up as such a call."""

import re

from perf import roofline
from perf.reference.model import geometry

_QMM = re.compile(r"^closed_call\.\d+_bf16_(\d+)_(\d+)__custom-call$")
_SLICE = re.compile(r"^dynamic-slice[\w.\-]*_s8_\d+_\d+__fusion$")


def layer_matmuls(g: dict) -> dict:
    """Result width -> the ``qmm_cost`` arguments of a layer's matmuls of
    that width: (K, weights in the call, residual fused)."""
    D, F, q, kv = g["D"], g["F"], g["H"] * g["Dh"], g["Hk"] * g["Dh"]
    by_n: dict = {}
    for n, k, weights, residual in ((q, D, 1, False), (kv, D, 1, False),
                                    (kv, D, 1, False), (D, q, 1, True),
                                    (F, D, 2, False), (D, F, 1, True)):
        by_n.setdefault(n, []).append((k, weights, residual))
    return by_n


def read(run, variant=""):
    ops = (run.trace or {}).get("ops", {})
    calls: dict = {}      # (rows, N) -> [calls, seconds]
    for label, row in ops.items():
        m = _QMM.match(label)
        if m:
            acc = calls.setdefault((int(m.group(1)), int(m.group(2))), [0, 0.0])
            acc[0] += row["calls"]
            acc[1] += row["total_s"]
    if not calls:
        return None
    slices_s = sum(row["total_s"] for label, row in ops.items()
                   if _SLICE.match(label))
    g = geometry(run.config)
    pk = roofline.peaks(run.device["kind"])
    per_layer = layer_matmuls(g)
    least = measured = 0.0
    bound_s = {"bytes": 0.0, "compute": 0.0}
    for (rows, n), (count, seconds) in calls.items():
        if n in per_layer:
            shapes = per_layer[n]
        elif n == g["V"]:
            shapes = [(g["D"], 1, False)]
        else:
            return None  # a matmul this reader does not know: say nothing
        passes = count / len(shapes)
        for k, weights, residual in shapes:
            t, bound = roofline.least_seconds(
                *roofline.qmm_cost(rows, k, n, weights, residual), pk)
            least += t * passes
            bound_s[bound] += t * passes
        measured += seconds
    run.notes.append({"qmm_roofline": {
        "bound": max(bound_s, key=bound_s.get), "least_s": least,
        "kernels_s": measured, "weight_slices_s": slices_s,
        "row_counts": sorted({rows for rows, _ in calls})}})
    return roofline.share_pct(least, measured + slices_s)
