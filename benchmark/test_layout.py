"""``BENCHMARK.json``'s ``per_layer`` table against ``layer_metrics/``, one
entry and one reader file a case, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_layout.py -q -p no:cacheprovider

The rules are ``selftest.py``'s (``layout``, which tier 1 runs whole as
``test_yardstick[layout]``): an entry resolves to a reader file by
``run.metric_reader``'s own rule, lists cells that exist and that report the
end-to-end metric it moves (``ttft_p50_ms`` / ``itl_p95_ms``: open loops
alone); every reader file is named by an entry; no (reader file, cell) pair
is reported under two entries with the same ``moves``; the table stays
inside the contract's limit. What keeps the table from growing copies again
(PR 45 folded 47). Tier 1 runs ``tests/`` only: until a PR that may touch
``tests/`` imports these cases there, as ``tests/test_benchmark_selftest.py``
imports ``test_selftest.py``'s, they run by the command above (PERF.md §7).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import selftest  # noqa: E402

BENCH = selftest.bench_run.load_benchmark()
NAMED = selftest.named_reader_files(BENCH)


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_entry_has_a_reader_and_cells_that_report_what_it_moves(entry):
    assert not selftest.entry_faults(BENCH, entry)


@pytest.mark.parametrize("file", selftest.reader_files())
def test_reader_file_is_named_by_an_entry(file):
    assert file in NAMED


def test_no_reader_is_reported_twice_in_a_cell():
    assert not selftest.double_reports(BENCH)


def test_per_layer_is_inside_the_limit():
    assert len(BENCH["per_layer"]) <= selftest.PER_LAYER_LIMIT
