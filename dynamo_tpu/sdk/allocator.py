"""Per-service TPU chip allocator.

Reference: cli/allocator.py:28-120 — the serve CLI reads each service's
``resources={gpu: n}`` and assigns disjoint ``CUDA_VISIBLE_DEVICES`` ranges
to its workers. TPU-native analog: assign chip indices and export
``TPU_VISIBLE_CHIPS`` (+ ``TPU_PROCESS_BOUNDS``-friendly count) so multiple
engine processes on one TPU-VM host split the local chips; CPU/dry-run
deployments get the same accounting with no env effect."""

from __future__ import annotations

import dataclasses
import glob
import logging
import os
from typing import Dict, List, Optional

logger = logging.getLogger("dynamo_tpu.sdk.allocator")

__all__ = ["TpuAllocator"]


def _detect_chip_count() -> int:
    """Chips on this host, counted from the kernel's device nodes and
    nothing else: the supervisor must never initialise a JAX backend —
    a process that has loaded libtpu holds the chips, and the workers it
    spawns next could not open them. TPU-VM hosts expose one node per
    chip, ``/dev/accel<N>`` or ``/dev/vfio/<N>`` by generation: the accel
    nodes are counted where there are any, the vfio groups only where
    there are none, so a host that shows both kinds for the same chips
    counts each chip once. Any other device bound to vfio on such a host
    (a passed-through NIC) would count as a chip; ``--total-chips``
    overrides. A host with neither has no chips (0): a service that asks
    for one then fails in ``allocate`` unless ``--total-chips`` says
    otherwise."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return len([p for p in glob.glob("/dev/vfio/[0-9]*")
                if os.path.basename(p).isdigit()])


@dataclasses.dataclass
class Allocation:
    service: str
    chips: List[int]

    def env(self) -> Dict[str, str]:
        if not self.chips:
            return {}
        return {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in self.chips),
                "TPU_CHIPS_PER_PROCESS_BOUNDS":
                    f"1,1,{len(self.chips)}"}


class TpuAllocator:
    """Free-list allocator (was bump-pointer): the dynamic planner scales
    replicas up AND down, so released chips must be reusable."""

    def __init__(self, total_chips: Optional[int] = None):
        self.total = (_detect_chip_count() if total_chips is None
                      else total_chips)
        self._free: List[int] = list(range(self.total))
        self.allocations: Dict[str, Allocation] = {}

    @property
    def free_chips(self) -> int:
        return len(self._free)

    def allocate(self, service: str, n_chips: int) -> Allocation:
        if n_chips == 0:
            alloc = Allocation(service, [])
        else:
            if n_chips > len(self._free):
                raise RuntimeError(
                    f"service {service!r} wants {n_chips} chips but only "
                    f"{len(self._free)}/{self.total} remain")
            alloc = Allocation(service, self._free[:n_chips])
            del self._free[:n_chips]
            logger.info("allocated chips %s → %s", alloc.chips, service)
        self.allocations[service] = alloc
        return alloc

    def release(self, alloc: Allocation) -> None:
        """Return a replica's chips to the pool (planner scale-down)."""
        if alloc.chips:
            self._free = sorted(set(self._free) | set(alloc.chips))
            logger.info("released chips %s ← %s", alloc.chips,
                        alloc.service)
        if self.allocations.get(alloc.service) is alloc:
            self.allocations.pop(alloc.service, None)
