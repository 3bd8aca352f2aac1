"""Host work a dispatch in the UNTRACED window, ms (a mean): the wall of
``dyn.step.plan`` + ``pack`` + ``dispatch`` + ``emit`` + ``record`` as the
engine's own clock counts them always, over the dispatches of every kind
(``program_spans.json`` ``history``; perf/trace/count_history.py). It is
``step_host_ms_p50``'s sum without the python tracer that the capture
runs under; the note gives each phase's ms a dispatch (``record`` is
telemetry's own cost; ``harvest`` waits for the device, ``wait`` for
work, ``unphased`` is the loop's wall under no phase, also as a share of
that wall) and the ratio of the capture's median to this mean."""
from perf import measure
from perf.trace import count_history as ch
from perf.trace import program_spans


def read(run, variant=""):
    value = ch.per(run, lambda g: sum(
        g.get(f"step_phases.{p}.wall_ns", 0) for p in ch.HOST_WORK),
        ch.dispatches, 1e-6)
    if value is None:
        return None
    g = ch.growth(run)
    note = {"per_dispatch_ms": ch.per_dispatch_ms(
        g, [f"step_phases.{p}.wall_ns" for p in ch.PHASES] + ["unphased_ns"]),
        "dispatches": ch.dispatches(g)}
    if g.get("loop_wall_ns"):
        note["unphased_share_pct"] = round(
            100.0 * g.get("unphased_ns", 0) / g["loop_wall_ns"], 4)
    steps = program_spans.steps(run)
    traced = measure.percentile(steps["host_step_ms"], 50) if steps else None
    if traced is not None and value:
        note["captures_p50_over_this"] = round(traced / value, 3)
    run.notes.append({"step_host_wall_ms": note})
    return value
