"""Peaks, and the operations and bytes a kernel call needs — the yardstick
for every ``*_roofline`` metric. Arithmetic copied in spirit from
``dynamo_tpu/telemetry/roofline.py`` (sound; the original is listed for
deletion in PERF.md), but per KERNEL CALL from its shapes, not per step.

A kernel's least time is the larger of ops / peak ops/s and bytes / peak
bytes/s; its roofline share is that least time over the time the trace
measured. A share over 100% means the count is too high or the time
leaves out part of the work: ``share_pct`` raises, it never clips.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SIZEOF = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4}


class RooflineError(ValueError):
    pass


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise RooflineError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def qmm_cost(m: int, k: int, n: int, weights: int = 1, residual: bool = False,
             act_bytes: int = 2) -> tuple[float, float]:
    """One fused-dequant int8 matmul call, ``[m, k] x [k, n]`` for each of
    ``weights`` int8 matrices (2 = gate and up in one call): operations,
    and the bytes that must cross HBM: int8 weights at 1 byte each plus
    their f32 scales, activations in, result out (and the residual in),
    at the activation width."""
    ops = 2.0 * m * k * n * weights
    byts = (weights * (k * n * 1 + n * 4)      # int8 weights + f32 scales
            + m * k * act_bytes                # activations in
            + m * n * act_bytes                # result out
            + (m * n * act_bytes if residual else 0))
    return ops, float(byts)


def attn_decode_cost(contexts: list[int], heads: int, kv_heads: int,
                     head_dim: int, block_size: int,
                     kv_bytes: int = 2) -> tuple[float, float]:
    """One paged-attention decode call (one layer) over a batch whose
    rows have ``contexts`` cached tokens: the KV pages ACTUALLY read are
    whole pages, so each row costs ceil(ctx / block) pages of K and V."""
    ops = byts = 0.0
    for ctx in contexts:
        pages = -(-max(1, ctx) // block_size)
        byts += 2 * pages * block_size * kv_heads * head_dim * kv_bytes
        ops += 2 * 2 * heads * head_dim * ctx            # QK^T and PV
    byts += 2 * len(contexts) * heads * head_dim * 2     # q in, out
    return ops, byts


def least_seconds(ops: float, byts: float, pk: dict) -> tuple[float, str]:
    t_ops = ops / pk["bf16_flops_per_s"]
    t_bytes = byts / pk["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")


def share_pct(least_s: float, measured_s: float) -> float:
    """Roofline share in percent. Over 100 is a fault in the count or in
    the time, and is raised, not clipped."""
    if measured_s <= 0:
        raise RooflineError("no measured time")
    pct = 100.0 * least_s / measured_s
    if pct > 100.0:
        raise RooflineError(
            f"roofline share {pct:.1f}% > 100%: the operations or bytes are "
            f"counted too high, or the time leaves out part of the work "
            f"(least {least_s:.6g} s, measured {measured_s:.6g} s)")
    return pct
