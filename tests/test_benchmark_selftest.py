"""The benchmark's own selftest cases (``benchmark/test_selftest.py``: the
yardstick's arithmetic on known inputs, and every fixture's engine held to
its plain reference with each listed breakage outside the tolerance), run by
tier 1. PR 30 could not place them under ``tests/``; here they are imported,
not copied, so a fixture or a reference that a later PR adds under
``benchmark/`` is held by the driver's run with no edit to this file.

About three minutes in one process (the seven reference checks are most of
it: 18-36 s each alone, nearly all of it XLA compiling tiny programs); all
cases of this file go to one xdist worker (``--dist loadfile``).

The limit a case runs under is this file's, not the 60 s of
``benchmark/test_selftest.py``: that is for the file run alone, and under
tier 1 five other workers compile beside this one, so a 36 s case ran over
it in the driver's run of PR 42's first tree (``tiny-dots3-note``;
``tiny-deepseek-v32``, 31 s alone, in a builder's). A case that hangs still
ends.
"""

import os
import signal
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
try:
    from test_selftest import (  # noqa: E402,F401
        test_engine_within_tolerance_of_its_reference, test_yardstick)
finally:
    sys.path.remove(BENCH)

LIMIT_S = 240


@pytest.fixture(autouse=True)
def limit():
    def over(*_):
        raise TimeoutError(f"the case ran over its {LIMIT_S} s")
    before = signal.signal(signal.SIGALRM, over)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)
