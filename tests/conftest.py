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
