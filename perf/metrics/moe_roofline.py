"""Roofline share (%) of the held experts' matmuls over the traced
interval. Least work, from the program's own counts at the capture's
edges (``moe_local_assignments``, ``moe_experts_touched``: real tokens
only, summed over the expert layers of every program in the capture):
each (token, expert) pair through gate, up and down, and the int8 weights
and scales of every expert a layer's tokens TOUCHED across HBM once
(``kimi_linear_costs.moe_cost``). Measured: the device time of the ops
that do that work, found by the shapes only they have (``E`` experts held,
expert width ``Fe``, hidden ``D`` from the family's ``geometry``):

- decode, every held expert over the rows: the fusion whose result is
  ``f32[E, rows, Fe]`` (gate) and the one whose result is ``f32[rows, D]``
  (up, activation, down and the weighted sum in one);
- prefill, rows sorted by expert: the ``ragged-dot`` ops, their
  ``f32[rows * k, Fe | D]`` fusions, and the upcast copies of a layer's
  experts, ``bf16[E, D, Fe]`` / ``bf16[E, Fe, D]``.

``f32[rows, D]`` is also the shape of the residual stream's small
fusions: they are counted in (``light_s`` in the note: a few microseconds
each), so the share errs low, never high."""
import re

from perf import roofline
from perf.metrics import kimi_linear_costs as costs
from perf.reference.family import family_of

LIGHT_S = 50e-6   # a fusion this short moves no expert's weights


def read(run, variant=""):
    ops = (run.trace or {}).get("ops", {})
    deltas = costs.count_deltas(run)
    if not ops or not deltas:
        return None
    g = family_of(run.config).geometry(run.config)
    if "Fe" not in g:
        return None
    assigned = costs.engine_count(deltas, "moe_local_assignments")
    touched = costs.engine_count(deltas, "moe_experts_touched")
    calls = costs.engine_count(deltas, "moe_layer_calls")
    if not assigned or not touched or not calls:
        return None
    E, Fe, D = g["E"], g["Fe"], g["D"]
    mine = re.compile(
        rf"(_f32_{E}_\d+_{Fe}__fusion|_f32_\d+_({D}|{Fe})__fusion"
        rf"|_bf16_{E}_({D}_{Fe}|{Fe}_{D})__fusion|ragged-dot)")
    found = {k: v for k, v in ops.items() if mine.search(k)}
    measured = sum(v["total_s"] for v in found.values())
    if not found or measured <= 0:
        return None
    pk = roofline.peaks(run.device["kind"])
    least, bound = roofline.least_seconds(
        *costs.moe_cost(assigned, touched, assigned / g["k"], D, Fe), pk)
    heavy = sorted(found.items(), key=lambda kv: -kv[1]["total_s"])[:6]
    run.notes.append({"moe_roofline": {
        "bound": bound, "least_s": least, "measured_s": measured,
        "layer_calls": calls, "assignments_per_call": assigned / calls,
        "experts_touched_per_call": touched / calls, "experts_held": E,
        "light_s": sum(v["total_s"] for v in found.values()
                       if v["median_s"] < LIGHT_S),
        "top": [[k, v["calls"], v["total_s"]] for k, v in heavy]}})
    return roofline.share_pct(least, measured)
