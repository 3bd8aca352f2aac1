"""Share (%) of the held experts that a step's real tokens chose, over
the expert layers of every program in the capture: the growth of
``moe_experts_touched`` over that of ``moe_layer_calls`` times the
experts held (``E`` of the family's ``geometry``). It says how much of
what the every-expert form reads is wanted: at 100 that form reads
nothing in vain, at 10 a grouped kernel would read a tenth. A program
without these counts reads nothing."""
from perf.metrics import kimi_linear_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    deltas = costs.count_deltas(run)
    if not deltas:
        return None
    touched = costs.engine_count(deltas, "moe_experts_touched")
    calls = costs.engine_count(deltas, "moe_layer_calls")
    g = family_of(run.config).geometry(run.config)
    if not calls or touched is None or not g.get("E"):
        return None
    return 100.0 * touched / (calls * g["E"])
