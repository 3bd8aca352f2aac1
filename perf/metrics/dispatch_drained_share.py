"""Share (%) of the dispatches, of every kind, issued to a device whose
queue had ALREADY run dry, in the UNTRACED window: just before each
dispatch the engine asks the newest step in flight, without waiting,
whether it has finished (``is_ready()``); if it has, or nothing is in
flight, the device had nothing to do until this dispatch — the host came
late (``dispatches_device_drained`` over ``dispatches.*``;
``program_spans.json`` ``history``; perf/trace/count_history.py). The
first dispatch after the loop found no work is not counted: no work is
not starvation. This is the device's own answer to what
``decode_chained_share`` reads from the host's bookkeeping."""
from perf.trace import count_history as ch


def read(run, variant=""):
    return ch.per(run, lambda g: g.get("dispatches_device_drained", 0),
                  ch.dispatches, 100.0)
