"""The benchmark's own selftest cases (``benchmark/test_selftest.py``: the
yardstick's arithmetic on known inputs, and every fixture's engine held to
its plain reference with each listed breakage outside the tolerance), run by
tier 1. PR 30 could not place them under ``tests/``; here they are imported,
not copied, so a fixture or a reference that a later PR adds under
``benchmark/`` is held by the driver's run with no edit to this file.

About 70 s in one process (the three reference checks are most of it); all
cases of this file go to one xdist worker (``--dist loadfile``).
"""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
try:
    from test_selftest import (limit,  # noqa: E402,F401
                               test_engine_within_tolerance_of_its_reference,
                               test_yardstick)
finally:
    sys.path.remove(BENCH)
