"""Operations and bytes of the ``qwen3_next`` family's own kernel, per
call from its shapes — the yardstick of ``gdn_decode_roofline`` (no
metric of this name: the reader beside it imports it). Peaks, least time
and the share that raises over 100% are ``perf/roofline.py``'s; the
samples of running rows and the counts at a capture's edges are read as
``kimi_linear_costs`` reads them.
"""

from __future__ import annotations

F32 = 4
# labels the state update's kernel may carry: the shared one today, a
# sibling of this family's own if a later PR writes one
UPDATE_KERNELS = ("gdn_decode_update", "kda_decode_update")


def gdn_decode_cost(rows: int, value_heads: int, key_heads: int,
                    head_dim: int) -> tuple[float, float]:
    """One Gated DeltaNet update of ``rows`` sequences, one layer: each
    row's ``[value_heads, d, d]`` float32 state crosses HBM once in and
    once out; the operands in — q and k a KEY head, v a value head
    (float32 ``[heads, d]``), the decay and beta one scalar a value head
    — and the output out. Per state element: decay multiply, k-product
    and its sum, the rank-one update (multiply, add), q-product and its
    sum (``kimi_linear_costs.kda_decode_cost``'s count)."""
    state = value_heads * head_dim * head_dim
    ops = 8.0 * rows * state
    operands = (2 * key_heads + 2 * value_heads) * head_dim * F32 \
        + 2 * value_heads * F32
    return ops, float(rows * (2 * state * F32 + operands))
