"""Worker service-time model, with assumed (not chip-measured) defaults.

One ``SimWorker`` stands in for a single-chip decode worker running the
native engine. Three resources bound it, mirroring the real scheduler:

- **batch slots** (``batch_slots``, the engine's max_batch_size);
- **KV blocks** (``kv_blocks`` × ``block_size`` tokens of paged KV);
- **decode bandwidth**: total token throughput follows the measured
  saturating curve — per-sequence inter-token latency grows linearly
  with occupancy, ``itl(n) = (n + n_half) / decode_tok_s_max``, which
  makes fleet ITL the load signal SLO scaling reacts to. The defaults
  (2000 tok/s ceiling, n_half 16) are an assumed single-chip batch
  ladder: not measured on the attached chip.

Speculative decoding is modeled as a throughput/KV trade: when enabled
it multiplies decode speed by ``spec_speedup`` but charges
``spec_kv_overhead_blocks`` extra blocks per sequence (draft staging),
so the degradation ladder's "disable spec" rung genuinely frees KV
under saturation at an ITL cost — the same trade the real engine makes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class WorkerProfile:
    prefill_tok_s: float = 20_000.0   # pooled prefill server speed
    # cache-hot resume onboarding (mid-stream migration, docs/
    # robustness.md): when the target already holds the request's
    # prefix KV, the "re-prefill" is a block onboard, not a forward
    # pass — an order of magnitude cheaper than prefill_tok_s
    onboard_tok_s: float = 200_000.0
    decode_tok_s_max: float = 2_000.0  # saturated per-worker ceiling
    n_half: int = 16                   # occupancy at half-ceiling
    batch_slots: int = 64
    # sized so KV binds just before the slot budget at the default
    # length mix (~4 blocks/seq incl. spec overhead), like a real 16 GB
    # chip after 8B int8 weights: the KV watermark is the planner's
    # primary signal, exactly as in live serving
    kv_blocks: int = 192
    block_size: int = 128
    first_step_s: float = 0.02         # dispatch + first decode step
    spawn_delay_s: float = 30.0        # provisioning latency on scale-up
    spec_speedup: float = 1.25
    spec_kv_overhead_blocks: int = 1

    def blocks_for(self, prompt_tokens: int, output_tokens: int,
                   spec_on: bool) -> int:
        blocks = math.ceil((prompt_tokens + output_tokens) / self.block_size)
        return blocks + (self.spec_kv_overhead_blocks if spec_on else 0)


class SimWorker:
    def __init__(self, wid: int, profile: WorkerProfile):
        self.wid = wid
        self.profile = profile
        self.active: dict[int, int] = {}  # rid -> kv blocks held
        self.kv_used = 0
        self.draining = False
        self.slow_until = 0.0  # injected stall horizon (sim time)
        self.slow_factor = 1.0

    @property
    def occupancy(self) -> int:
        return len(self.active)

    @property
    def kv_usage(self) -> float:
        return self.kv_used / max(1, self.profile.kv_blocks)

    def can_admit(self, blocks: int) -> bool:
        return (
            not self.draining
            and self.occupancy < self.profile.batch_slots
            and self.kv_used + blocks <= self.profile.kv_blocks
        )

    def admit(self, rid: int, blocks: int) -> None:
        self.active[rid] = blocks
        self.kv_used += blocks

    def release(self, rid: int) -> int:
        blocks = self.active.pop(rid)
        self.kv_used -= blocks
        return blocks

    def itl_s(self, now: float, spec_on: bool) -> float:
        """Per-sequence inter-token latency at the CURRENT occupancy
        (evaluated at admission — the model's one approximation: a
        request keeps the ITL of the load it was admitted into)."""
        n = max(1, self.occupancy)
        itl = (n + self.profile.n_half) / self.profile.decode_tok_s_max
        if spec_on:
            itl /= self.profile.spec_speedup
        if now < self.slow_until:
            itl *= self.slow_factor
        return itl
