"""TPU resource allocator for local serving.

Reference: deploy/sdk/src/dynamo/sdk/cli/allocator.py:54-255 (GPU
assignment per @service resources). TPU twist: the schedulable unit is a
*chip set* — a worker that wants tp=N needs N chips wired as one mesh.

A chip belongs to one process at a time, and a process that loads libtpu
takes the whole host unless the environment confines it. What confines
it to ONE chip (established on a four-chip v5e host with libtpu 0.0.34):
``TPU_VISIBLE_CHIPS`` names the chip, and
``TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1`` + ``TPU_PROCESS_BOUNDS=1,1,1``
declare the process a one-chip slice — without the bounds libtpu still
takes the host-wide lock and every worker after the first dies with "The
TPU is already in use by process ...". Inside a confined process the chip
is renumbered to 0 (``jax.devices()[0].id == 0`` in every one-chip
worker), so a worker reports its placement as ``TPU_VISIBLE_CHIPS``.

Two placements have run on chips and are all this allocator hands out:
one chip (confined as above) and the whole host (no confinement: the
process takes every chip, as a tp=4 worker on a 2x2 host does). The
bounds that would confine a process to 2 chips of 4 have never met the
installed libtpu, so such a request is refused rather than guessed at.
Control-plane components that request no TPU are pinned to the CPU.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


class AllocationError(RuntimeError):
    pass


def chip_env(chip_id: int) -> dict[str, str]:
    """Env vars that confine a process to one chip of its host."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@dataclass
class Allocation:
    chip_ids: list[int] = field(default_factory=list)
    whole_host: bool = False

    def env(self) -> dict[str, str]:
        """Env vars that scope a child process to its chips."""
        if not self.chip_ids:
            # control-plane component: keep it off the TPU entirely
            return {"DYN_JAX_PLATFORM": "cpu"}
        if self.whole_host:
            return {}  # nothing to confine: the process takes every chip
        (chip,) = self.chip_ids
        return chip_env(chip)


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
        whole_host = want == self.total and len(self._free) == self.total
        if whole_host:
            chips = list(self._free)
        elif want == 1 and self._free:
            chips = self._free[:1]
        elif want in (1, self.total):
            raise AllocationError(
                f"{owner}: wants {want} chips, {len(self._free)} free of "
                f"{self.total}"
            )
        else:
            raise AllocationError(
                f"{owner}: wants {want} chips of {self.total}; a process "
                f"gets one chip or the whole host — confining it to a "
                f"part of the host is unverified with the installed libtpu"
            )
        self._free = [c for c in self._free if c not in chips]
        self._held.setdefault(owner, []).extend(chips)
        return Allocation(chips, whole_host)

    def release(self, owner: str) -> None:
        self._free.extend(self._held.pop(owner, []))
        self._free.sort()
