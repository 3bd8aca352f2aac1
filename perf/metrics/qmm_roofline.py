"""Roofline share (%) of the fused-dequant int8 matmul kernels (``qmm``)
over the traced interval: the least time their calls could take (larger
of ops / 197 TFLOP/s and bytes / 819 GB/s; int8 weights 1 byte each plus
f32 scales, activations and results at bf16) over the device time the
trace measured for them. Since PR 26 the kernels read each layer's
weights out of the stacked ``[layers, K, N]`` array themselves, so their
own time holds the whole HBM read. An int8 weight-slice copy
(``dynamic-slice*`` with an ``s8[K, N]`` result) would be part of the
work again, so its time is still added to the measured side and printed
in the note as ``weight_slices_s``: it must read 0.

Two kinds of call are counted, both Mosaic custom calls with a bf16
``[rows, N]`` result and neither with a stable name yet:

- the layers' matmuls, ``closed_call.<n>`` inside the scan over layers.
  N alone does not say which matmul a call is (wq, wo and w_down all give
  the hidden size), and two programs can use one op name for different
  matmuls, so nothing is classified call by call: a layer runs each of
  its matmuls exactly once, so among the calls of one row count those
  with result width N are ``calls / (matmuls of that width per layer)``
  layer passes, each costing the SUM of that width's matmuls. Which
  matmuls a layer has is its family's knowledge: ``layer_matmuls`` of the
  configuration's family module (``perf/reference/family.py``). A family
  that gives none, or a width it does not list, reads nothing here —
  never another family's count;
- the head, which runs outside the scan as ``step.<n>`` with N = the
  vocabulary: one ``[rows, D] x [D, V]`` call each (``head_calls`` and
  ``head_s`` in the note). A ``step.<n>`` custom call of another width is
  not a matmul this reader knows and is left alone.

Rows are the padded rows of the result."""

import re

from perf import roofline
from perf.reference.family import family_of

_QMM = re.compile(r"^closed_call\.\d+_bf16_(\d+)_(\d+)__custom-call$")
_HEAD = re.compile(r"^step\.\d+_bf16_(\d+)_(\d+)__custom-call$")
_SLICE = re.compile(r"^dynamic-slice[\w.\-]*_s8_\d+_\d+__fusion$")


def _calls(ops: dict, pattern) -> dict:
    """(rows, N) -> [calls, seconds] of the ops whose label matches."""
    found: dict = {}
    for label, row in ops.items():
        m = pattern.match(label)
        if m:
            acc = found.setdefault((int(m.group(1)), int(m.group(2))), [0, 0.0])
            acc[0] += row["calls"]
            acc[1] += row["total_s"]
    return found


def read(run, variant=""):
    ops = (run.trace or {}).get("ops", {})
    in_layers, outside = _calls(ops, _QMM), _calls(ops, _HEAD)
    if not in_layers and not outside:
        return None
    family = family_of(run.config)
    if not hasattr(family, "layer_matmuls"):
        return None  # which matmuls a layer has, only its family knows
    g = family.geometry(run.config)
    per_layer = family.layer_matmuls(g)
    head = [(g["D"], 1, False)]
    if any(n not in per_layer and n != g["V"] for _, n in in_layers):
        return None  # a matmul this reader does not know: say nothing
    heads = {key: got for key, got in outside.items() if key[1] == g["V"]}
    if not in_layers and not heads:
        return None
    pk = roofline.peaks(run.device["kind"])
    least = measured = 0.0
    bound_s = {"bytes": 0.0, "compute": 0.0}
    for (rows, n), (count, seconds) in [*in_layers.items(), *heads.items()]:
        shapes = per_layer.get(n, head)   # what is no layer's width is V
        passes = count / len(shapes)
        for k, weights, residual in shapes:
            t, bound = roofline.least_seconds(
                *roofline.qmm_cost(rows, k, n, weights, residual), pk)
            least += t * passes
            bound_s[bound] += t * passes
        measured += seconds
    slices_s = sum(row["total_s"] for label, row in ops.items()
                   if _SLICE.match(label))
    run.notes.append({"qmm_roofline": {
        "bound": max(bound_s, key=bound_s.get), "least_s": least,
        "kernels_s": measured, "weight_slices_s": slices_s,
        "head_calls": sum(c for c, _ in heads.values()),
        "head_s": sum(t for _, t in heads.values()),
        "row_counts": sorted({rows for rows, _ in [*in_layers, *heads]})}})
    return roofline.share_pct(least, measured + slices_s)
