"""Operations and bytes of the ``mimo_v2_flash`` family's attention calls,
the yardstick of ``window_decode_roofline``, ``full_decode_roofline`` and
``attn_prefill_roofline`` (no metric of this name: the readers beside it
import it), always at the PUBLISHED widths — K 192, V 128, bf16 —
whatever the program stores (its K rows lie in 256 lanes) or runs. Peaks,
least time and the share that raises over 100% are ``perf/roofline.py``'s;
the counts' growth over the capture is ``kimi_linear_costs.count_deltas``'s.

The calls of the two layer kinds are told apart by the device op's name:
``paged_attention_{decode,prefill}_stacked_{full,window}*``
(``models/mimo_v2_flash.py`` passes the kernel wrappers that ``name``). The work is
the program's own count at the capture's two edges (``attn_*`` of its
``COUNT_NAMES``), read once every step dispatched so far has finished: a
call the counts hold ran inside the trace whole, one in flight at the
first edge is traced and not counted — a share can err low by that one
program, never high. Where the counts hold MORE calls of a kind than the
trace shows, a call straddled an edge this reasoning missed and the
reader says nothing.
"""

from __future__ import annotations

from perf import roofline
from perf.metrics.kimi_linear_costs import count_deltas, engine_count
from perf.reference.family import family_of

PAIR_UNIT = 1024   # models/mimo_v2_flash.py counts attn_*_pairs in these
BF16 = 2
KINDS = {"full": "Hk_full", "window": "Hk_window"}


def attn_decode_cost(keys: float, heads: int, kv_heads: int, dk: int,
                     dv: int) -> tuple[float, float]:
    """Decode attention that read ``keys`` cached keys (summed over rows
    and layers): every key's K and V rows of each KV head cross HBM once
    — ``kv_heads x (dk + dv) x 2 B`` a key — and every query head takes
    one ``dk`` wide score and one ``dv`` wide value product of it."""
    return (2.0 * keys * heads * (dk + dv),
            float(keys * kv_heads * (dk + dv) * BF16))


def attn_prefill_cost(pairs: float, heads: int, dk: int,
                      dv: int) -> tuple[float, float]:
    """Prefill attention over ``pairs`` valid (query, key) pairs (summed
    over layers): each pair and head one ``dk`` wide score and one ``dv``
    wide value product, 2 FLOP a multiply-add — 640 FLOP at 192 / 128.
    No bytes: a prefill tile re-reads its pages far below what it
    computes on them."""
    return 2.0 * pairs * heads * (dk + dv), 0.0


def kind_ops(run, phase: str, kind: str) -> dict:
    prefix = f"paged_attention_{phase}_stacked_{kind}"
    return {k: v for k, v in (run.trace or {}).get("ops", {}).items()
            if k.startswith(prefix)}


def geometry_of(run) -> dict | None:
    g = family_of(run.config).geometry(run.config)
    return g if "Hk_window" in g and "Dv" in g else None


def decode_share(run, kind: str) -> float | None:
    """Roofline share (%) of the decode attention calls of ``kind``
    (``full`` | ``window``): least bytes of the counted keys over the
    device seconds of that kind's decode ops."""
    name = f"{kind}_decode_roofline"
    ops = kind_ops(run, "decode", kind)
    deltas = count_deltas(run)
    if not ops or not deltas:
        return None
    keys = engine_count(deltas, f"attn_{kind}_decode_keys")
    calls = engine_count(deltas, f"attn_{kind}_decode_calls")
    g = geometry_of(run)
    if not keys or not calls or g is None:
        return None
    traced = sum(v["calls"] for v in ops.values())
    measured = sum(v["total_s"] for v in ops.values())
    note = {"calls_counted": calls, "calls_traced": traced, "keys": keys,
            "keys_per_call": keys / calls, "measured_s": measured,
            "labels": sorted(ops)}
    run.notes.append({name: note})
    if calls > traced or measured <= 0:
        return None
    least, bound = roofline.least_seconds(
        *attn_decode_cost(keys, g["H"], g[KINDS[kind]], g["Dk"], g["Dv"]),
        roofline.peaks(run.device["kind"]))
    note.update(bound=bound, least_s=least)
    return roofline.share_pct(least, measured)
