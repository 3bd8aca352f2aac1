"""TPU resource allocator for local serving.

Reference: deploy/sdk/src/dynamo/sdk/cli/allocator.py:54-255 (GPU
assignment per @service resources). TPU twist: the schedulable unit is a
*chip set* — a worker that wants tp=N needs N chips wired as one mesh.

A chip belongs to one process at a time, and a process that loads libtpu
takes the whole host unless the environment confines it. What confines
it (established on a four-chip v5e host with libtpu 0.0.34):
``TPU_VISIBLE_CHIPS`` names the chips, and
``TPU_CHIPS_PER_PROCESS_BOUNDS`` + ``TPU_PROCESS_BOUNDS`` declare the
process a sub-host slice — without the bounds libtpu still takes the
host-wide lock and every worker after the first dies with "The TPU is
already in use by process ...". Inside a confined process the chips are
renumbered from 0 (``jax.devices()[0].id == 0`` in every one-chip
worker), so a worker reports its placement as ``TPU_VISIBLE_CHIPS``.
Control-plane components that request no TPU are pinned to the CPU.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

# chips per process -> the x,y,z box libtpu must be told. Host chip ids
# run x-fastest over a 2-wide host (v5e 2x2: 0=(0,0) 1=(1,0) 2=(0,1)
# 3=(1,1)), so an aligned run of 2 ids is a 2x1 box and 4 a 2x2 box.
# Only the one-chip box has been run on the chip.
CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "2,4,1"}


class AllocationError(RuntimeError):
    pass


def chip_env(chip_ids: list[int]) -> dict[str, str]:
    """Env vars that confine a process to ``chip_ids`` of its host."""
    bounds = CHIP_BOUNDS.get(len(chip_ids))
    if bounds is None:
        raise AllocationError(
            f"a process can be confined to {sorted(CHIP_BOUNDS)} chips, "
            f"not {len(chip_ids)}"
        )
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chip_ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@dataclass
class Allocation:
    chip_ids: list[int] = field(default_factory=list)

    def env(self) -> dict[str, str]:
        """Env vars that scope a child process to its chips."""
        if not self.chip_ids:
            # control-plane component: keep it off the TPU entirely
            return {"DYN_JAX_PLATFORM": "cpu"}
        return chip_env(self.chip_ids)


class TpuAllocator:
    def __init__(self, total_chips: int | None = None):
        if total_chips is None:
            total_chips = int(os.environ.get("DYN_TPU_CHIPS", "1"))
        self.total = total_chips
        self._free: list[int] = list(range(total_chips))
        self._held: dict[str, list[int]] = {}

    @property
    def free_chips(self) -> int:
        return len(self._free)

    def allocate(self, owner: str, resources: dict) -> Allocation:
        want = int(resources.get("tpu", 0))
        if want == 0:
            return Allocation([])
        if want not in CHIP_BOUNDS:
            raise AllocationError(
                f"{owner}: wants {want} chips; a process can be confined "
                f"to {sorted(CHIP_BOUNDS)}"
            )
        # an ALIGNED run of ids: the chips must form the box the bounds
        # declare (ids 1,2 are not neighbours on a 2-wide host)
        free = set(self._free)
        for start in range(0, self.total - want + 1, want):
            chips = list(range(start, start + want))
            if free.issuperset(chips):
                break
        else:
            raise AllocationError(
                f"{owner}: wants {want} chips, {len(self._free)} free of "
                f"{self.total} and no aligned run of {want}"
            )
        self._free = [c for c in self._free if c not in chips]
        self._held.setdefault(owner, []).extend(chips)
        return Allocation(chips)

    def release(self, owner: str) -> None:
        self._free.extend(self._held.pop(owner, []))
        self._free.sort()
