"""Median device duration (ms) of the decode step program in the traced
interval: the ``jit_step*`` program that ran most often (programs have no
stable names yet — PERF.md, Open questions)."""


def read(run, variant=""):
    mods = {k: v for k, v in (run.trace or {}).get("modules", {}).items()
            if k.startswith("jit_step")}
    if not mods:
        return None
    name, most = max(mods.items(), key=lambda kv: kv[1]["calls"])
    run.notes.append({"step_device_ms_p50": name, "calls": most["calls"],
                      "programs": {k: [v["calls"], round(v["median_s"] * 1e3, 3)]
                                   for k, v in mods.items()}})
    return most["median_s"] * 1000.0
