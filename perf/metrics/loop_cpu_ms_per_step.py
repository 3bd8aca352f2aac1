"""CPU the asyncio loop's thread used a dispatch, ms, in the UNTRACED
window: the thread that serialises every token of every stream (a
pydantic model, a ``json`` dump and a socket write each) and shares one
interpreter lock with the engine thread (``cpu_ns.loop`` over the
dispatches of every kind; ``program_spans.json`` ``history``;
perf/trace/count_history.py). The note gives the engine thread's, the
process's and every other thread's CPU a dispatch, and how full the
interpreter is: (engine + loop CPU) over the wall the history covers.
Nothing where the platform has no per-thread CPU clock, or the program
no history."""
from perf.trace import count_history as ch


def read(run, variant=""):
    g = ch.growth(run)
    if g is None or "cpu_ns.loop" not in g:
        return None
    n = ch.dispatches(g)
    if not n:
        return None
    loop, engine = g["cpu_ns.loop"], g.get("cpu_ns.engine", 0)
    process = g.get("cpu_ns.process", 0)
    run.notes.append({"loop_cpu_ms_per_step": {
        "engine_cpu_ms": round(engine / n / 1e6, 4),
        "process_cpu_ms": round(process / n / 1e6, 4),
        "other_threads_cpu_ms": round((process - engine - loop) / n / 1e6, 4),
        "interpreter_fill": round((engine + loop) / (g["seconds"] * 1e9), 4),
        "seconds": g["seconds"], "dispatches": n}})
    return loop / n / 1e6
