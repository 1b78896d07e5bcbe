"""The cheap parts of ``selftest.py`` as pytest cases, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_selftest.py -q -p no:cacheprovider

One case per check, one per reader that takes its costs module from its
``ctx`` and, for the reference, one per fixture, so that each counts. The
served rehearsal (``selftest.end_to_end``, minutes) stays in ``selftest.py``.
Each case has a limit of its own (no pytest-timeout here: an alarm). Tier 1
runs ``tests/`` only: ``tests/test_benchmark_selftest.py`` imports both test
functions, so a case added to either is tier 1's too.
"""

import functools
import os
import signal
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import selftest  # noqa: E402

LIMIT_S = 60
CHECKS = (selftest.arithmetic, selftest.generator, selftest.window_arithmetic,
          selftest.trace_reduction, selftest.reader_check, selftest.layout,
          selftest.reference_lookup)
CASES = [pytest.param(check, id=check.__name__) for check in CHECKS] + [
    pytest.param(functools.partial(selftest.merged_reader, name),
                 id=f"merged_reader-{name}")
    for name in selftest.MERGED_READERS]


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


@pytest.mark.parametrize("check", CASES)
def test_yardstick(check):
    check()


@pytest.mark.parametrize("fixture", selftest.fixtures())
def test_engine_within_tolerance_of_its_reference(fixture):
    selftest.reference_check(fixture)
