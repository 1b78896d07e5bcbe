"""Test configuration: force JAX onto a virtual 8-device CPU mesh so every
sharding/collective path runs without TPU hardware (SURVEY.md §4: the
reference tests multi-node with mock transports + no-GPU fixtures; our analog
is XLA's forced host platform device count)."""

import os
import sys

# Tests run on a virtual 8-device CPU mesh, never on a chip: a chip
# belongs to one process (xdist workers would fight over it) and MXU bf16
# matmul numerics break float32 reference comparisons. The forcing recipe
# lives in __graft_entry__.force_cpu_devices (shared with the driver's
# multi-chip dryrun so the two can't drift).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import jax  # noqa: E402

import pytest  # noqa: E402

# One persistent compile cache for the tests, shared by the xdist workers and
# kept from one run to the next: most of tier 1 is XLA's CPU compiler, and
# the same tiny programs are built again by every test that makes an engine
# of its own and by every worker that serves the same fixture (a whole run
# on an empty cache: 8,238 -> 7,552 s of test time, PR 60; a run on the
# cache the run before it left finds nearly every program built). The
# entries are keyed by the program's text, XLA's version and its flags, so
# an old one is never wrong, only unused; the directory is dropped when it
# passes 1 GiB (a whole run writes ~140 MiB). It is under the temporary
# directory and not in the checkout, which chiprun copies whole, and it is
# set through jax.config and not as JAX_COMPILATION_CACHE_DIR, so the child
# processes the tests start (launchers, benches, the multi-rank CLIs) keep
# the cache they had: .jax_cache/, where two ranks must not find the
# one-process programs of these tests. A directory placed from outside
# wins, as in utils/compile_cache.py.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import tempfile
    _cache_dir = os.path.join(tempfile.gettempdir(),
                              f"dynamo-tpu-tier1-jax-cache-{os.getuid()}")
    if "PYTEST_XDIST_WORKER" not in os.environ:
        try:
            _held = sum(e.stat().st_size for e in os.scandir(_cache_dir))
        except OSError:
            _held = 0
        if _held > 1 << 30:
            import shutil
            shutil.rmtree(_cache_dir, ignore_errors=True)
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# Under ``--dist loadfile`` a file is one worker's, and xdist hands the files
# out by their COUNT of cases, most first: a file of few long cases (the
# deviceless builds of one family: 5 cases, 144 s) starts last and runs with
# nothing left beside it (PR 60: the last three files to start were two
# minutes each). Here the workers put the files in order of the seconds they
# took, longest first, and xdist is told to keep that order; six workers
# then end within seconds of each other. tier1_seconds.json holds the files
# of 20 s and more from a whole run's junit file (sum of ``time`` by
# ``classname``); a file it does not name is taken at 4 s a case, and a
# stale entry costs balance, never a case.
def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    if not hasattr(config, "workerinput"):
        return                          # a plain run keeps pytest's order
    import json
    with open(os.path.join(os.path.dirname(__file__),
                           "tier1_seconds.json")) as f:
        seconds = json.load(f)
    by_file = {}
    for item in items:
        by_file.setdefault(item.nodeid.split("::", 1)[0], []).append(item)
    items[:] = [item for _, group in sorted(
        by_file.items(), key=lambda kv: -seconds.get(
            os.path.basename(kv[0]), 4 * len(kv[1]))) for item in group]

# The image has no pytest-asyncio; anyio (a httpx dependency) auto-registers
# its pytest plugin, which runs coroutine tests and async fixtures. Auto-mark
# every async test below so `@pytest.mark.asyncio` works as authored.


@pytest.fixture
def anyio_backend():
    return "asyncio"


@pytest.fixture(scope="session")
def tiny_model_dir(tmp_path_factory):
    """HF-style tiny model directory: trained byte-level BPE tokenizer +
    config.json + chat template (the test-fixture analog of the reference's
    lib/llm/tests/data/ pinned repos)."""
    from tests.fixtures import build_tiny_model_dir
    path = tmp_path_factory.mktemp("tiny-model")
    build_tiny_model_dir(str(path))
    return str(path)


@pytest.fixture(scope="session")
def tiny_weighted_model_dir(tmp_path_factory):
    """tiny_model_dir + random-init safetensors — for paths that load real
    weights from disk (JaxEngine.from_model_dir, the example graphs'
    ``engine: jax`` mode)."""
    from tests.fixtures import build_tiny_weighted_model_dir
    path = tmp_path_factory.mktemp("tiny-weighted-model")
    build_tiny_weighted_model_dir(str(path))
    return str(path)
