"""Operations of the ``deepseek_v3`` family's own kernel, the yardstick
of ``mla_prefill_roofline`` (no metric of this name: the reader beside it
imports it). Peaks, least time and the share that raises over 100% are
``perf/roofline.py``'s; the counts' growth over the capture is
``kimi_linear_costs.count_deltas``'s.
"""

from __future__ import annotations

PAIR_UNIT = 1024   # models/deepseek_v3.py counts mla_prefill_pairs in these


def mla_prefill_cost(pairs: float, heads: int, nope: int, rope: int,
                     vd: int) -> tuple[float, float]:
    """Causal latent attention over ``pairs`` valid (query, key) pairs
    (summed over layers), by the PUBLISHED, non-absorbed count: each pair
    and head one ``nope + rope`` wide score and one ``vd`` wide value
    product, 2 FLOP a multiply-add — 640 FLOP at Kanana-2's 192 / 128 —
    whatever form the program runs (absorbed, it spends 2 x (576 + 512)),
    so the share is a true floor. No bytes: a prefill tile re-reads the
    pages from HBM far below what it computes on them."""
    return 2.0 * pairs * heads * (nope + rope + vd), 0.0
