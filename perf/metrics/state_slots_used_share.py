"""Share (%) of the state plane's slots that held a sequence, over the
programs dispatched during the capture: the growth of
``state_slot_steps_used`` over that of ``state_slot_steps_total``
(``program_spans.json`` counts; the engine adds the slots held and the
slots there are at every dispatch). A model without recurrent state gives
no such counts: nothing is read."""
from perf.metrics import kimi_linear_costs as costs


def read(run, variant=""):
    deltas = costs.count_deltas(run)
    if not deltas:
        return None
    used = costs.engine_count(deltas, "state_slot_steps_used")
    total = costs.engine_count(deltas, "state_slot_steps_total")
    if not total or used is None:
        return None
    return 100.0 * used / total
