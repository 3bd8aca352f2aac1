"""Time a dispatch that the engine thread stood in host work WITHOUT
running, ms, in the UNTRACED window: the wall of ``dyn.step.plan`` +
``pack`` + ``dispatch`` + ``emit`` + ``record`` less the thread's own CPU
in them (``offcpu_ns`` over the dispatches of every kind;
``program_spans.json`` ``history``; perf/trace/count_history.py). In those
phases the thread neither waits for the device nor for work, so what is
left is the interpreter lock held by another thread (the event loop
serialising tokens), the scheduler, or a blocking call inside a phase
(a device_put that waits). The note splits it by phase."""
from perf.trace import count_history as ch


def read(run, variant=""):
    value = ch.per(run, lambda g: g.get("offcpu_ns", 0), ch.dispatches, 1e-6)
    if value is None:
        return None
    g = ch.growth(run)
    n = ch.dispatches(g)
    run.notes.append({"step_host_offcpu_ms": {"per_dispatch_ms": {
        p: round((g.get(f"step_phases.{p}.wall_ns", 0)
                  - g.get(f"step_phases.{p}.cpu_ns", 0)) / n / 1e6, 4)
        for p in ch.HOST_WORK}}})
    return value
