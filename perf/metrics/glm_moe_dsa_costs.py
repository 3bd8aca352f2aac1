"""Operations and bytes of the ``glm_moe_dsa`` family's own kernels — the
yardstick of ``dsa_index_roofline``, ``dsa_prefill_roofline`` and
``dsa_decode_roofline`` (no metric of this name: the readers beside it
import it) — and what the three readers share. Every count is at the
PUBLISHED widths and of the LEAST work, the SELECTED keys, whatever the
program stores (640 lanes for 576 values) or runs (a masked walk reads
and multiplies every live key): a form that does more reads its true low
share. Peaks, least time and the share that raises over 100% are
``perf/roofline.py``'s; the counts' growth over the capture is
``kimi_linear_costs.count_deltas``'s.

The trace reduction keeps device ops by their HLO names, not by
``jax.named_scope``, so the three stages are found by the names of their
Pallas kernels: ``dsa_index_{prefill,decode}`` (the scope ``dsa_index``),
``dsa_select_{prefill,decode}`` (``dsa_select``), ``dsa_{prefill,decode}_
attention`` (``dsa_attend``). What the scope ``dsa_index`` runs beside its
kernel is in the measured time too (in PR 49's first traced run the
gather alone took 1.5 x the three decode kernels' seconds), found
by ``index_side_ops`` from the shapes the kernel's own label states: the
indexer's projections, which the compiler names after the scope
(``dsa_index.<n>``), and the XLA gather of the row's ``index_k`` pages (a
bfloat16 op of [rows x table columns, block, index width]). A later change
that moves the gather into the kernel then raises the share as the step
gets faster, not the other way round.
"""

from __future__ import annotations

import re

from perf.metrics.kimi_linear_costs import BF16, count_deltas, engine_count

PAIR_UNIT = 1024   # models/glm_moe_dsa.py counts its two prefill counts in these


def index_cost(pairs: float, heads: int, width: int) -> tuple[float, float]:
    """The index score of ``pairs`` (query, key) pairs: a ``width`` wide
    dot product a pair and indexer head, 2 FLOP a multiply-add. No bytes:
    a tile of queries re-reads the keys far below what it computes."""
    return 2.0 * pairs * heads * width, 0.0


def prefill_attend_cost(selected: float, heads: int, nope: int, rope: int,
                        vd: int) -> tuple[float, float]:
    """Attention over ``selected`` (query, selected key) pairs by the
    published, non-absorbed count: a ``nope + rope`` wide score and a
    ``vd`` wide value product a pair and head."""
    return 2.0 * selected * heads * (nope + rope + vd), 0.0


def decode_cost(selected: float, scored: float, rank: int, rope: int,
                index_width: int) -> tuple[float, float]:
    """A decode step's selection and attention: every SCORED key's
    ``index_width`` indexer values and every SELECTED key's ``rank +
    rope`` latent values cross HBM once, in bfloat16. No operations
    counted: at one query a row the bytes bound it."""
    return 0.0, selected * (rank + rope) * BF16 + scored * index_width * BF16


def kernel_ops(run, prefixes: tuple[str, ...]) -> dict:
    """The traced device ops whose label starts with one of ``prefixes``."""
    return {k: v for k, v in (run.trace or {}).get("ops", {}).items()
            if k.startswith(prefixes)}


_LABEL = re.compile(r"_[a-z]+\d+_(\d+(?:_\d+)*)__[a-z-]+$")


def label_dims(label: str) -> tuple[int, ...]:
    """The result's shape as the reduction prints it at a label's end
    (``<name>_<dtype>_<d0>_<d1>...__<kind>``), or () where it prints none."""
    m = _LABEL.search(label)
    return tuple(int(x) for x in m.group(1).split("_")) if m else ()


def index_side_ops(run, kernel: str, index_width: int) -> dict:
    """The traced ops that the scope ``dsa_index`` runs beside the index
    kernel whose label starts with ``kernel``, told from other programs'
    by the [rows, query tokens, keys] that kernel's labels state: the
    projections under the scope's name whose rows are its queries (the
    matmul kernel pads a batch to 8 rows), and the gather of its keys'
    pages — bfloat16 [pages, block, ``index_width``] with pages x block =
    rows x keys. A program that gathers inside the kernel has no such op
    and adds nothing."""
    ops = (run.trace or {}).get("ops", {})
    shapes = {d for d in map(label_dims, kernel_ops(run, (kernel,))) if len(d) == 3}
    rows = {max(8, b * t) for b, t, _ in shapes}
    keys = {b * s for b, _, s in shapes}
    side = {}
    for label, v in ops.items():
        d = label_dims(label)
        if label.startswith("dsa_index."):
            if len(d) == 2 and d[0] in rows:
                side[label] = v
        elif (not label.startswith("dsa_") and "_bf16_" in label and len(d) == 3
              and d[2] == index_width and d[0] * d[1] in keys):
            side[label] = v
    return side


def counted(run, against_trace: bool = True) -> dict | None:
    """The family's counts' growth over the capture by their plain names,
    or None where the program keeps none of them (an older commit, another
    family) or — ``against_trace``: for a reader that divides by traced
    time — the counts hold MORE calls than the trace shows: a call then
    straddles the capture's edge and the two sides are not the same
    calls."""
    deltas = count_deltas(run)
    if not deltas:
        return None
    names = ("dsa_index_pairs", "dsa_prefill_selected", "dsa_decode_scored",
             "dsa_decode_selected", "dsa_calls")
    got = {n: engine_count(deltas, n) for n in names}
    if any(v is None for v in got.values()) or not got["dsa_calls"]:
        return None
    traced = sum(v["calls"] for v in kernel_ops(run, ("dsa_index_",)).values())
    got["calls_traced"] = traced
    return None if against_trace and got["dsa_calls"] > traced else got
