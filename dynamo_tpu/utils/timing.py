"""Chained-dispatch slope timing for device-truth measurements.

Per-step device time is measured as a SLOPE: time a short chain of m1
dispatches and a long chain of m2, each ending in ONE value fetch as the
barrier; (t2 - t1) / (m2 - m1) cancels the fetch cost and every constant
overhead (dispatch, host loop). Host jitter is one-sided (stalls only), so
each point takes the min over `reps` runs. On the local chip
`block_until_ready` waits for the device and a plain timed window works
too; the slope stays because it needs no steady-state assumption. The
single home of this protocol — bench.py and tools/decode_profile.py both
use it.
"""

from __future__ import annotations

import time
from typing import Callable


def slope_per_unit(run: Callable[[int], float], m1: int, m2: int,
                   *, reps: int = 2, warmup: bool = True) -> float:
    """run(m) executes a chain of m units (ending in its own barrier fetch)
    and returns elapsed seconds. Returns per-unit seconds, clamped >= 0."""
    if warmup:
        run(m1)                       # settle compiles / queue state
    t1 = min(run(m1) for _ in range(reps))
    t2 = min(run(m2) for _ in range(reps))
    return max((t2 - t1) / (m2 - m1), 0.0)


def timed(fn: Callable[[], None]) -> float:
    """Elapsed seconds of fn() — the building block for run(m) closures."""
    t0 = time.monotonic()
    fn()
    return time.monotonic() - t0
