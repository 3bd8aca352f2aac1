"""Roofline share (%) of the held two-matrix experts' matmuls over the
traced interval: ``moe_roofline``'s method for an expert of ``up`` and
``down`` alone. Least work, from the program's own counts at the
capture's edges (``moe_local_assignments``, ``moe_experts_touched``: real
tokens only, summed over the expert layers of every program in the
capture): each (token, expert) pair through up and down, and the int8
weights and scales of every expert a layer's tokens TOUCHED across HBM
once (``nemotron_h_costs.moe_updown_cost``). Measured: the device time of
the ops that do that work, found by the shapes only they have (``E``
experts held, expert width ``Fe``, hidden ``D`` from the family's
``geometry``):

- every held expert over a block of rows (decode, and prefill in blocks of
  512 tokens): the fusion whose result is ``[E, rows, Fe]`` or ``[E, Fe,
  rows]`` (up and the activation, float32 or rounded to bf16) and the one
  whose result is ``f32[rows, D]`` (down and the weighted sum; at decode
  rows the compiler makes ONE fusion of the whole layer, of this shape);
- what a step of several blocks does once a layer for all of them: the
  layer's experts sliced out of their stacks (``s8`` / ``bf16[(1,) E, D,
  Fe]`` and ``[(1,) E, Fe, D]``) and their scales (``f32[E, Fe | D]``).

``f32[rows, D]`` is also the shape of the residual stream's small
fusions: they are counted in (``light_s`` in the note), so the share errs
low, never high. A family whose experts have a gate matrix too is
``moe_roofline``'s: this reader reads where the family's ``geometry``
gives ``expert_form`` as ``"updown"``, and nothing elsewhere."""
import re

from perf import roofline
from perf.metrics import kimi_linear_costs, nemotron_h_costs as costs
from perf.reference.family import family_of

LIGHT_S = 50e-6   # a fusion this short moves no expert's weights


def read(run, variant=""):
    ops = (run.trace or {}).get("ops", {})
    deltas = kimi_linear_costs.count_deltas(run)
    if not ops or not deltas:
        return None
    g = family_of(run.config).geometry(run.config)
    if g.get("expert_form") != "updown":
        return None
    assigned = kimi_linear_costs.engine_count(deltas, "moe_local_assignments")
    touched = kimi_linear_costs.engine_count(deltas, "moe_experts_touched")
    calls = kimi_linear_costs.engine_count(deltas, "moe_layer_calls")
    if not assigned or not touched or not calls:
        return None
    E, Fe, D = g["E"], g["Fe"], g["D"]
    mine = re.compile(
        rf"(_(f32|bf16)_{E}_(\d+_{Fe}|{Fe}_\d+)__fusion|_f32_\d+_({D}|{Fe})__fusion"
        rf"|_(bf16|s8)_(1_)?{E}_({D}_{Fe}|{Fe}_{D})__)")
    found = {k: v for k, v in ops.items() if mine.search(k)}
    measured = sum(v["total_s"] for v in found.values())
    if not found or measured <= 0:
        return None
    pk = roofline.peaks(run.device["kind"])
    least, bound = roofline.least_seconds(
        *costs.moe_updown_cost(assigned, touched, assigned / g["k"], D, Fe), pk)
    heavy = sorted(found.items(), key=lambda kv: -kv[1]["total_s"])[:6]
    run.notes.append({"moe_updown_roofline": {
        "bound": bound, "least_s": least, "measured_s": measured,
        "layer_calls": calls, "assignments_per_call": assigned / calls,
        "experts_touched_per_call": touched / calls, "experts_held": E,
        "light_s": sum(v["total_s"] for v in found.values()
                       if v["median_s"] < LIGHT_S),
        "top": [[k, v["calls"], v["total_s"]] for k, v in heavy]}})
    return roofline.share_pct(least, measured)
