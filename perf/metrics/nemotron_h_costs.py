"""Operations and bytes of the ``nemotron_h`` family's own kernels, per
call from its shapes — the yardstick of ``ssm_decode_roofline`` and
``moe_updown_roofline`` (no metric of this name: the readers beside it
import it). Peaks, least time and the share that raises over 100% are
``perf/roofline.py``'s; the samples of running rows and the counts at a
capture's edges are read as ``kimi_linear_costs`` reads them.
"""

from __future__ import annotations

F32, BF16, INT8 = 4, 2, 1


def ssm_decode_cost(rows: int, heads: int, head_dim: int, state_dim: int,
                    groups: int) -> tuple[float, float]:
    """One Mamba-2 update of ``rows`` sequences, one layer: each row's
    ``[heads, head_dim, state_dim]`` float32 state crosses HBM once in and
    once out; the operands in — x a head (``[heads, head_dim]``), dt and
    the decay one scalar a head, B and C a GROUP (``[groups, state_dim]``
    each), all float32 — and the output out. Per state element: the decay
    multiply, the outer product and its add, the C product and its sum."""
    state = heads * head_dim * state_dim
    ops = 5.0 * rows * state
    operands = (2 * heads * head_dim + 2 * heads + 2 * groups * state_dim) * F32
    return ops, float(rows * (2 * state * F32 + operands))


def moe_updown_cost(assignments: float, touched: float, rows: float,
                    hidden: int, width: int) -> tuple[float, float]:
    """The routed experts of one two-matrix expert layer: ``assignments``
    (token, expert) pairs each through up (``[hidden, width]``) and down
    (``[width, hidden]``); the int8 weights and float32 scales of the
    ``touched`` experts cross HBM once; the rows' activations in and the
    combined result out."""
    ops = 2.0 * assignments * 2 * hidden * width
    per_expert = 2 * hidden * width * INT8 + (width + hidden) * F32
    return ops, float(touched * per_expert + 2 * rows * hidden * BF16)
