"""Multi-turn sessions. ``live_sessions`` slots each hold one session at
a time; groups of ``group_size`` slots share one system prompt. A turn
resends the whole history plus a new user message and asks for ``out``
tokens; its due time is the end of the previous answer plus ``think``
seconds. A session retires when its next turn would pass
``max_history_tokens``, and the slot's next session opens after the
think time. Slots open on a fixed schedule inside the ramp, each at a
scheduled age (``start_turn``: the earlier turns are sent as history),
so the window sees sessions of every age from its first second."""

import math

from perf.traffic.schedule import uniform_int


def _session(mix: dict, rng) -> list[dict]:
    u, o, th = mix["user_tokens"], mix["output_tokens"], mix["think_s"]
    history, turns = int(mix["system_prompt_tokens"]), []
    while True:
        turn = {"user": uniform_int(rng, u["min"], u["max"]),
                "out": uniform_int(rng, o["min"], o["max"]),
                "think": round(float(rng.uniform(th["min"], th["max"])), 3)}
        if history + turn["user"] + turn["out"] > int(mix["max_history_tokens"]):
            return turns
        turns.append(turn)
        history += turn["user"] + turn["out"]


def build(mix: dict, seconds: float, rng) -> dict:
    n, ramp = int(mix["live_sessions"]), float(mix["ramp_s"])
    span = ramp + seconds
    # a session lasts at least turns x (shortest think + fastest answer)
    shortest = 6 * (mix["think_s"]["min"]
                    + mix["output_tokens"]["min"] * float(mix["fastest_tpot_s"]))
    per_slot = 2 + math.ceil(span / shortest)
    slots = []
    for s in range(n):
        sessions = [_session(mix, rng) for _ in range(per_slot)]
        slots.append({
            "slot": s, "group": s // int(mix["group_size"]),
            "open_at": round(-ramp + float(mix["open_span_s"]) * s / n, 6),
            # the slot's first session begins at a scheduled age
            "start_turn": (s * 3) % max(1, len(sessions[0])),
            "sessions": sessions,
        })
    return {"slots": slots}


def totals(schedule: dict) -> dict:
    turns = [t for sl in schedule["slots"] for se in sl["sessions"] for t in se]
    return {
        "slots": len(schedule["slots"]),
        "sessions": sum(len(sl["sessions"]) for sl in schedule["slots"]),
        "listed_turns": len(turns),
        "listed_user_tokens": sum(t["user"] for t in turns),
        "listed_output_tokens": sum(t["out"] for t in turns),
    }


def probe(mix: dict, rng) -> list[list[dict]]:
    """The output check's requests, from the mix alone: a row per live
    session. Wave 1 is a turn at a scheduled age (0, 3, 6, ... turns of
    history behind the group's shared system prompt, the ids of which are
    the window's own, so the oldest rows hold the longest contexts the
    mix reaches); wave 2 is the same session's next turn, which resends
    wave 1's prompt and answer — the prefix cache serves those pages."""
    first, second = [], []
    for s in range(int(mix["live_sessions"])):
        turns = _session(mix, rng)
        age = (s * 3) % len(turns)
        history = sum(t["user"] + t["out"] for t in turns[:age])
        group = s // int(mix["group_size"])
        first.append({"row": s, "shared": [1, group],
                      "shared_tokens": int(mix["system_prompt_tokens"]),
                      "new": history + turns[age]["user"]})
        second.append({"row": s, "after": s,
                       "new": turns[(age + 1) % len(turns)]["user"]})
    return [first, second]


async def drive(load) -> None:
    """One task per slot. The history a turn resends is the system
    prompt, every earlier user message and every earlier ANSWER AS
    RECEIVED (a real client's behaviour, and what lets the server find
    its cached pages); turns scheduled before a slot's ``start_turn``
    were never sent, so their answers are drawn from the seed."""
    import asyncio

    sys_ids = {}

    def system(group: int) -> list[int]:
        if group not in sys_ids:
            sys_ids[group] = load.ids(
                (1, group), int(load.mix["system_prompt_tokens"]))
        return sys_ids[group]

    async def slot(sl: dict) -> None:
        s, due = sl["slot"], load.t0 + sl["open_at"]
        for n, turns in enumerate(sl["sessions"]):
            history = list(system(sl["group"]))
            first = sl["start_turn"] if n == 0 else 0
            for k, turn in enumerate(turns):
                user = load.ids((2, s, n, k), turn["user"])
                if k < first:
                    history += user + load.ids((3, s, n, k), turn["out"])
                    continue
                if k > first or n > 0:
                    due += turn["think"]
                if due >= load.end:
                    return
                await load.sleep_until(due)
                rec = await load.request(
                    ("session", s, n, k), due, history + user, turn["out"],
                    keep_text=True)
                if rec.failed:
                    return
                history += user + [int(w[1:]) for w in rec.text.split()]
                rec.text = ""
                due = rec.last
        raise RuntimeError(f"slot {s} ran out of scheduled sessions")

    await asyncio.gather(*(slot(sl) for sl in load.schedule["slots"]))
