"""Bandwidth-bound models (tools/bandwidth_model.py) + the offload pump's
injectable simulated d2h link (model-backed bounds at stated link
speeds)."""

import asyncio
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bm():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib
        return importlib.import_module("bandwidth_model")
    finally:
        sys.path.pop(0)


def test_bandwidth_model_tables():
    bm = _bm()
    assert bm.kv_bytes_per_token("1b") == 2 * 16 * 8 * 64 * 2
    assert bm.kv_bytes_per_token("70b") == 2 * 80 * 8 * 128 * 2
    host = bm.host_tier_table("1b")
    assert [r["d2h_gbps"] for r in host] == [10.0, 30.0, 100.0]
    # restore time strictly shrinks with bandwidth; recompute is constant
    restores = [r["restore_ms_2k_hit"] for r in host]
    assert restores == sorted(restores, reverse=True)
    assert len({r["recompute_ms_2k_hit"] for r in host}) == 1
    # at TPU-VM link speeds the model says the tier pays for every
    # geometry
    assert all(r["tier_pays"] for r in host)
    wire = bm.wire_plane_table("1b", isl=1024)
    assert wire[0]["transfer_ms"] > wire[1]["transfer_ms"]
    assert wire[0]["kv_mb"] == round(1024 * bm.kv_bytes_per_token("1b")
                                     / 1e6, 1)
    assert wire[0]["serialize_ms_measured"] > 0


@pytest.mark.asyncio
async def test_offload_pump_simulated_link():
    """EngineConfig.offload_simulated_gbps paces write-backs to the
    modeled d2h link: a throttled pump accumulates simulated wait."""
    import numpy as np

    from dynamo_tpu.llm.kv.offload import (HostKvPool, KvOffloadEngine,
                                           OffloadJob)

    L, H, BS, D = 2, 2, 4, 8
    pool = HostKvPool(8, L, H, BS, D, dtype=np.float32)
    import jax.numpy as jnp
    kv = {"k": jnp.zeros((L, 16 * BS, H * D), jnp.float32),
          "v": jnp.zeros((L, 16 * BS, H * D), jnp.float32)}

    # block bytes = 2(kv) * L * BS * H * D * 4B = 2048; at 1e-6 GB/s the
    # pace target is ~2s per block — far above the real copy time
    eng = KvOffloadEngine(pool, BS, get_kv=lambda: kv,
                          simulated_gbps=1e-6)
    eng.enqueue(OffloadJob(block_ids=[1], seq_hashes=[111]))
    t0 = asyncio.get_running_loop().time()
    await asyncio.wait_for(eng.drain(), 30)
    waited = asyncio.get_running_loop().time() - t0
    await eng.stop()
    assert eng.simulated_wait_s > 0.5, (
        f"pump did not pace to the simulated link ({eng.simulated_wait_s})")
    assert waited >= 0.5
    assert pool.contains(111)
