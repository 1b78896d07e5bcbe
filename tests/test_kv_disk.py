"""Persistent disk (G3) KV tier (llm/kv/diskstore.py): the
content-addressed store's durability contract (kill -9, torn manifest),
the spill → evict → promote cycle through EngineCore, cross-restart
prefix reuse with bit-exact continuations, the loop-stall guard for
spill/promote, follower mirror equivalence, tier-tagged router events,
and the llmctl kv admin surface."""

import asyncio
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dynamo_tpu.llm.kv.diskstore import DiskKvStore, DiskSpillEngine, SpillJob

pytestmark = pytest.mark.kvdisk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

L, H, BS, D = 2, 2, 4, 8


def _blk(x: float) -> dict:
    return {"k": np.full((L, H, BS, D), x, np.float32),
            "v": np.full((L, H, BS, D), 10 + x, np.float32)}


# ------------------------------------------------------------------ store


def test_diskstore_put_match_fetch_roundtrip(tmp_path):
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    assert store.put(101, _blk(1.0), tokens_hash=11, parent_hash=None) == []
    assert store.put(102, _blk(2.0), tokens_hash=12, parent_hash=101) == []
    # duplicate put is a no-op (content addressing)
    assert store.put(101, _blk(9.0)) is None
    assert store.match_prefix([101, 102, 999]) == [101, 102]
    assert store.match_prefix([999]) == []
    out = store.fetch([101, 102])
    assert out["k"].shape == (L, H, 2, BS, D)
    np.testing.assert_allclose(out["k"][:, :, 0], 1.0)
    np.testing.assert_allclose(out["v"][:, :, 1], 12.0)
    assert store.registered_entries() == [(101, 11, None), (102, 12, 101)]
    assert store.hit_rate() > 0


def test_prepare_prefill_asserts_disk_pin_coverage(tmp_path):
    """ISSUE 5 satellite: prepare_prefill must verify the allocation can
    cover the host+disk tier hits before building the plan — a
    disk store whose match_prefix over-returns (more pinned hashes than
    the prompt has unmatched full blocks) would otherwise scatter past
    new_blocks silently. The loud failure must also release the device
    holds and the disk pins it took."""
    from dynamo_tpu.llm.kv.pool import KvBlockManager

    store = DiskKvStore(str(tmp_path), capacity_blocks=16)
    mgr = KvBlockManager(num_blocks=32, block_size=4, disk_store=store,
                         prefer_native=False)
    prompt = list(range(10))               # 2 full blocks + 2 tokens

    class OverReturningStore:
        def __init__(self, inner):
            self.inner = inner
            self.pinned = []
            self.unpinned = []

        def match_prefix(self, hashes, pin=False):
            # over-return: more "hits" than the unmatched full blocks
            fake = list(range(900, 908))
            self.pinned.extend(fake)
            return fake

        def unpin(self, hashes):
            self.unpinned.extend(hashes)

    mgr.disk_store = OverReturningStore(store)
    free_before = mgr.pool.free_blocks
    with pytest.raises(RuntimeError, match="invariant"):
        mgr.prepare_prefill(prompt)
    # holds and pins released by the failure path
    assert mgr.pool.free_blocks == free_before
    assert mgr.disk_store.unpinned == mgr.disk_store.pinned

    # the honest store path still plans cleanly (invariant holds)
    mgr.disk_store = store
    plan = mgr.prepare_prefill(prompt)
    assert plan is not None
    assert len(plan.new_blocks) >= len(plan.host_slots) + len(
        plan.disk_hashes)
    mgr.abort_plan(plan)


def test_diskstore_capacity_lru_eviction_and_pins(tmp_path):
    store = DiskKvStore(str(tmp_path), capacity_blocks=3)
    for i in range(3):
        store.put(100 + i, _blk(float(i)))
    store.match_prefix([100])             # freshen: 101 becomes LRU
    evicted = store.put(200, _blk(9.0))
    assert evicted == [101]
    assert not store.contains(101) and store.contains(200)
    # pinned entries are skipped (requeued), the next LRU goes instead
    store.pin([102])
    store.match_prefix([100, 200])        # LRU order now: 102, 100, 200
    evicted = store.put(201, _blk(8.0))
    assert evicted == [100]
    assert store.contains(102)
    store.unpin([102])
    assert store.evicted_blocks_total == 2


def test_diskstore_survives_kill9_mid_spill(tmp_path):
    """THE durability gate (the test_control_plane_durability pattern
    applied to the disk tier): a subprocess writes blocks in a loop and
    prints each hash AFTER put() returns (= acknowledged); SIGKILL lands
    mid-write; recovery must serve every acknowledged block with whole
    bytes and must not surface any partially-written one."""
    d = str(tmp_path / "kv")
    code = (
        "import sys, numpy as np\n"
        "from dynamo_tpu.llm.kv.diskstore import DiskKvStore\n"
        "store = DiskKvStore(sys.argv[1], capacity_blocks=100000)\n"
        "i = 0\n"
        "print('ready', flush=True)\n"
        "while True:\n"
        "    vals = {'k': np.full((4, 2, 16, 64), float(i), np.float32)}\n"
        "    store.put(i + 1, vals, tokens_hash=i, parent_hash=None)\n"
        "    print(i + 1, flush=True)\n"
        "    i += 1\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    proc = subprocess.Popen([sys.executable, "-c", code, d], env=env,
                            cwd=REPO, stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        acked = []
        deadline = time.monotonic() + 30
        while len(acked) < 5 and time.monotonic() < deadline:
            acked.append(int(proc.stdout.readline()))
        assert len(acked) >= 5, "writer made no progress"
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
    store = DiskKvStore(d, capacity_blocks=100000)
    # every acknowledged block is resident with correct whole bytes
    for h in acked:
        assert store.contains(h), f"acknowledged block {h} lost"
        out = store.fetch([h])
        np.testing.assert_allclose(out["k"][:, :, 0], float(h - 1))
    # anything else resident (the in-flight put may or may not have been
    # acknowledged) must still read back whole — no corrupt entries
    for h, _th, _ph in store.registered_entries():
        store.fetch([h])
    # no tmp- droppings survive recovery
    assert not [f for f in os.listdir(d) if f.startswith("tmp-")]


def test_diskstore_torn_manifest_and_orphans(tmp_path):
    d = str(tmp_path / "kv")
    store = DiskKvStore(d, capacity_blocks=8)
    store.put(1, _blk(1.0))
    store.put(2, _blk(2.0))
    store.close()
    # torn manifest tail (crash mid-append): must be skipped
    with open(os.path.join(d, "manifest.jsonl"), "a") as f:
        f.write('{"op": "put", "h": 3, "f"')
    # orphan data file (renamed but never acknowledged): must be removed
    orphan = os.path.join(d, "blk-00000000000000ff.npz")
    np.savez(open(orphan, "wb"), k=np.zeros((1,)))
    # manifest entry whose file vanished: must be dropped
    with open(os.path.join(d, "manifest.jsonl"), "a") as f:
        f.write(json.dumps({"op": "put", "h": 77,
                            "f": "blk-gone.npz", "n": 1}) + "\n")
    store2 = DiskKvStore(d, capacity_blocks=8)
    assert sorted(h for h, _t, _p in store2.registered_entries()) == [1, 2]
    assert not os.path.exists(orphan)
    np.testing.assert_allclose(store2.fetch([2])["k"][:, :, 0], 2.0)


def test_diskstore_recovery_reaps_truncated_payload(tmp_path):
    """ISSUE 6 satellite (kill-during-put regression alongside the torn
    manifest case): recovery must skip manifest entries whose npz
    payload is missing or TRUNCATED — a short file can't serve reads and
    must be reaped + counted, never surfaced. Our own writes are atomic
    (tmp → fsync → rename), so truncation models external damage (fs
    corruption, a cache dir copied mid-write)."""
    d = str(tmp_path / "kv")
    store = DiskKvStore(d, capacity_blocks=8)
    store.put(1, _blk(1.0), tokens_hash=11)
    store.put(2, _blk(2.0), tokens_hash=22)
    store.put(3, _blk(3.0), tokens_hash=33)
    fname2 = next(e.fname for e in store._entries.values()
                  if e.seq_hash == 2)
    store.close()
    # block 2's payload is cut short; block 3's vanishes entirely
    with open(os.path.join(d, fname2), "r+b") as f:
        f.truncate(16)
    os.unlink(os.path.join(d, fname2.replace(
        fname2, next(e.fname for e in store._entries.values()
                     if e.seq_hash == 3))))
    store2 = DiskKvStore(d, capacity_blocks=8)
    assert [h for h, _t, _p in store2.registered_entries()] == [1]
    assert store2.reaped_corrupt_blocks == 1       # truncated (3 = missing)
    np.testing.assert_allclose(store2.fetch([1])["k"][:, :, 0], 1.0)
    # the truncated file is gone (orphan sweep) and a re-put re-admits
    assert not os.path.exists(os.path.join(d, fname2))
    assert store2.put(2, _blk(2.0)) == []
    np.testing.assert_allclose(store2.fetch([2])["k"][:, :, 0], 2.0)


def test_diskstore_roundtrips_bfloat16_and_int8(tmp_path):
    """Production pools are bfloat16 (and int8 opaque rows) — np.savez
    alone round-trips ml_dtypes arrays as anonymous void '|V2', which
    the device scatter rejects (caught live: a warm bf16 engine failed
    every disk promote). The store must give back the exact dtype and
    bytes across a reopen."""
    import ml_dtypes
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    rng = np.random.default_rng(3)
    bf = rng.normal(size=(L, H, BS, D)).astype(ml_dtypes.bfloat16)
    i8 = rng.integers(-128, 127, size=(L, 1, BS, 64)).astype(np.int8)
    store.put(1, {"k": bf, "v": bf + 1})
    store.close()
    store2 = DiskKvStore(str(tmp_path), capacity_blocks=8)
    out = store2.fetch([1])
    assert out["k"].dtype == bf.dtype
    np.testing.assert_array_equal(out["k"][:, :, 0], bf)
    np.testing.assert_array_equal(out["v"][:, :, 0], bf + 1)
    # int8 opaque rows (kv_quantization / MLA latent pools)
    store3 = DiskKvStore(str(tmp_path / "i8"), capacity_blocks=8)
    store3.put(2, {"kv": i8})
    got = store3.fetch([2])["kv"]
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got[:, :, 0], i8)


def test_diskstore_block_size_mismatch_starts_cold(tmp_path):
    d = str(tmp_path / "kv")
    store = DiskKvStore(d, capacity_blocks=8, expect_block_size=4)
    store.put(1, _blk(1.0))
    store.close()
    store2 = DiskKvStore(d, capacity_blocks=8, expect_block_size=16)
    assert len(store2) == 0


# ----------------------------------------------------------- spill engine


@pytest.mark.asyncio
async def test_spill_engine_backpressure_drops_with_counter(tmp_path):
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    eng = DiskSpillEngine(store, max_queue_jobs=0)
    assert not eng.offer(SpillJob(1, None, None, _blk(1.0)))
    assert eng.dropped_jobs_total == 1
    eng2 = DiskSpillEngine(store, max_queue_jobs=8)
    assert eng2.offer(SpillJob(2, 22, None, _blk(2.0)))
    await eng2.drain()
    assert store.contains(2)
    # duplicate offers are refused without counting as backpressure
    assert not eng2.offer(SpillJob(2, 22, None, _blk(2.0)))
    assert eng2.dropped_jobs_total == 0
    await eng2.stop()


# --------------------------------------------------------------- EngineCore


def _mcfg():
    from dynamo_tpu.engine.config import ModelConfig
    return ModelConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       max_position_embeddings=256)


def _make_core(disk_dir, host_blocks=16, disk_blocks=32, **kw):
    import jax.numpy as jnp
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    ecfg = EngineConfig(max_model_len=64, kv_block_size=4,
                        num_kv_blocks=32, max_num_seqs=2,
                        prefill_buckets=[32, 64],
                        host_kv_blocks=host_blocks,
                        kv_disk_dir=str(disk_dir),
                        kv_disk_blocks=disk_blocks, **kw)
    return EngineCore(_mcfg(), ecfg, attn_impl="xla",
                      param_dtype=jnp.float32)


async def _serve(core, prompt, rid, max_new=4):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=max_new, eos_ids=frozenset())
    await core.submit(req)
    toks = []
    while True:
        item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
        if item is FINISH_SENTINEL:
            return toks, req.prefix_hit_tokens
        toks.append(item)


async def test_warm_restart_serves_prefix_from_disk(tmp_path):
    """ISSUE 3 acceptance: a restarted engine pointed at the same
    --kv-disk-dir serves a previously-cached prefix with onboarded (not
    recomputed) KV, and the token stream is bit-exact vs the uncontended
    reference run."""
    prompt = list(range(1, 13))        # 3 full blocks
    core1 = _make_core(tmp_path / "kv")
    ref_toks, hit1 = await _serve(core1, prompt, "cold")
    assert hit1 == 0
    await core1.stop()                 # graceful stop flushes host → disk
    assert len(core1.disk_store) >= 2

    core2 = _make_core(tmp_path / "kv")
    # warm start: the new store recovered the previous run's blocks
    assert core2.disk_store.restored_blocks >= 2
    warm_toks, hit2 = await _serve(core2, prompt, "warm")
    assert hit2 >= 8                   # prefix onboarded, not recomputed
    assert core2.disk_onboards == 1    # through the async onboard path
    assert warm_toks == ref_toks       # bit-exact continuation
    # the restored blocks re-registered on device and host-offload on
    # release skips re-spilling them
    await core2.stop()


async def test_host_eviction_spills_to_disk_write_behind(tmp_path):
    """The write-behind trigger itself: a tiny host pool evicts under
    multi-prompt load and the evicted blocks land on disk (no flush
    involved), then promote back on a later request."""
    core = _make_core(tmp_path / "kv", host_blocks=3)
    pa = list(range(1, 13))
    pb = list(range(40, 52))
    toks_a, _ = await _serve(core, pa, "a")
    await core.offload_engine.drain()
    # B's offload evicts A's host blocks → write-behind spill
    await _serve(core, pb, "b")
    await core.offload_engine.drain()
    await core.spill_engine.drain()
    assert core.disk_store.used_blocks >= 1
    assert core.spill_engine.spilled_blocks_total >= 1
    # wipe the device tier; A's prefix must come back via disk (host
    # pool now holds B's blocks)
    core.kv_manager.pool.reset()
    toks_a2, hit = await _serve(core, pa, "a2")
    assert hit >= 4
    assert toks_a2 == toks_a
    assert core.disk_onboards >= 1
    await core.stop()


async def test_spill_and_promote_never_block_engine_loop(tmp_path,
                                                         monkeypatch):
    """Loop-stall guard (the host-tier overlap contract one tier down):
    with disk I/O artificially slowed to 1 s per operation, a
    decode-active engine doing spills AND a disk promote must never gap
    the event loop anywhere near that long — the file I/O runs
    off-thread (DiskSpillEngine → to_thread; onboard prep thread)."""
    core = _make_core(tmp_path / "kv", host_blocks=3)
    pa = list(range(1, 15))
    pb = list(range(40, 52))
    # seed: B on disk (via host eviction pressure from A)
    await _serve(core, pb, "seed")
    await core.offload_engine.drain()
    await _serve(core, pa, "pressure")
    await core.offload_engine.drain()
    await core.spill_engine.drain()
    assert core.disk_store.contains(
        next(iter(h for h, _t, _p in core.disk_store.registered_entries())))
    core.kv_manager.pool.reset()
    # pre-compile the promote path (onboard scatter + suffix prefill):
    # first-time XLA compiles legitimately run on the loop and would
    # alias as stalls in the measured window below
    _, warm_hit = await _serve(core, pb, "warmcompile")
    assert warm_hit >= 4
    await core.offload_engine.drain()
    await _serve(core, pa, "pressure2", 16)     # evict pb's host rows
    await core.offload_engine.drain()
    await core.spill_engine.drain()
    core.kv_manager.pool.reset()

    # 1 s per disk op: far above anything legitimately on the loop (the
    # one-time XLA compile of the onboard scatter measured ~180 ms on this
    # CPU; under six busy test workers the loop has stalled 720 ms with
    # nothing of the disk on it) — if put/fetch ran on the loop thread, or
    # the loop waited for one, the max gap would exceed it
    slow = 1.0
    loop_thread = threading.get_ident()
    ran_on = []
    real_put, real_fetch = DiskKvStore.put, DiskKvStore.fetch

    def slowed(real):
        def op(self, *a, **k):
            ran_on.append(threading.get_ident())
            time.sleep(slow)
            return real(self, *a, **k)
        return op

    monkeypatch.setattr(DiskKvStore, "put", slowed(real_put))
    monkeypatch.setattr(DiskKvStore, "fetch", slowed(real_fetch))

    gaps = []
    done = asyncio.Event()

    async def heartbeat():
        while not done.is_set():
            t0 = time.monotonic()
            await asyncio.sleep(0.005)
            gaps.append(time.monotonic() - t0 - 0.005)

    hb = asyncio.ensure_future(heartbeat())
    # A decodes (spilling its own evictions through the slowed store)
    # while B's promote reads from the slowed disk
    got_a, got_b = await asyncio.gather(_serve(core, pa, "a2", 16),
                                        _serve(core, pb, "b2", 4))
    done.set()
    await hb
    assert got_b[1] >= 4               # B really promoted from a tier
    # whatever the machine's load: no slowed operation on the loop's thread
    assert ran_on and loop_thread not in ran_on
    assert max(gaps) < slow * 0.8, (
        f"engine loop stalled {max(gaps) * 1e3:.0f} ms — disk I/O ran on "
        f"the loop thread")
    await core.stop()


async def test_follower_mirror_bit_identical_spill_evict_promote(tmp_path):
    """ISSUE 3 acceptance: a follower mirror stays bit-identical through
    a spill → evict → promote cycle. The leader records its schedule
    (Recorder) including kv_store spills, kv_disk_store commits, and the
    disk-restored hit_transfer; replay() applies them to mirror tiers
    exactly like engine/multihost.run_follower, and the mirrors' bytes
    must equal the leader's pools."""
    from dynamo_tpu.engine.replay import Recorder, replay

    core = _make_core(tmp_path / "kv", host_blocks=3,
                      decode_steps_per_dispatch=2)
    core.recorder = Recorder()
    pa = list(range(1, 13))
    pb = list(range(40, 52))
    await _serve(core, pa, "a")
    await core.offload_engine.drain()
    await _serve(core, pb, "b")         # evicts A's host rows → spill
    await core.offload_engine.drain()
    await core.spill_engine.drain()
    assert core.spill_engine.spilled_blocks_total >= 1
    core.kv_manager.pool.reset()
    _toks, hit = await _serve(core, pa, "a2")   # promote from disk
    assert hit >= 4 and core.disk_onboards >= 1
    await core.offload_engine.drain()
    await core.spill_engine.drain()

    out = replay(core, core.recorder.events)
    mirror, disk_mirror = out["host_mirror"], out["disk_mirror"]
    assert disk_mirror is not None
    # disk mirror: every leader-resident block byte-identical
    leader_disk = core.disk_store.registered_entries()
    assert leader_disk
    for h, _th, _ph in leader_disk:
        assert disk_mirror.contains(h)
        want = core.disk_store.fetch([h])
        got = disk_mirror.fetch([h])
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    # host mirror: same hash→slot map, same arena bytes at those slots
    host = core.kv_manager.host_pool
    assert mirror._by_hash == host._by_hash
    for h, slot in host._by_hash.items():
        for k in host._arena:
            np.testing.assert_array_equal(mirror._arena[k][slot],
                                          host._arena[k][slot])
    await core.stop()


# ---------------------------------------------------- router / kv events


@pytest.mark.asyncio
async def test_disk_tier_events_and_reannounce(tmp_path):
    """Spill commits publish tier-tagged stored events; a warm-started
    engine re-announces disk-resident prefixes; the router's radix index
    discounts colder tiers' depth (scoring.TIER_WEIGHTS)."""
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer
    from dynamo_tpu.llm.kv_router.protocols import RouterEvent
    from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher

    events = []

    class Pub(KvEventPublisher):
        def _enqueue(self, ev: RouterEvent) -> None:
            events.append(ev)

    core = _make_core(tmp_path / "kv", host_blocks=3)
    core.kv_event_publisher = Pub(worker_id=7)
    await _serve(core, list(range(1, 13)), "a")
    await core.offload_engine.drain()
    await _serve(core, list(range(40, 52)), "b")
    await core.offload_engine.drain()
    await core.spill_engine.drain()
    # while the device copy stays registered the disk announce is
    # suppressed (the device announce stands at full weight) ...
    assert not [e for e in events
                if e.stored is not None and e.stored.tier == "disk"]
    # ... and a device eviction DEMOTES the announce to the coldest tier
    # still holding the hash instead of removing it
    core.kv_manager.pool.reset()
    disk_stored = [e for e in events
                   if e.stored is not None and e.stored.tier == "disk"]
    assert disk_stored, "device eviction published no disk-tier demotion"
    assert any(e.stored is not None and e.stored.tier == "host"
               for e in events)
    await core.stop()

    # warm restart: reannounce surfaces the disk-resident prefixes
    events.clear()
    core2 = _make_core(tmp_path / "kv")
    core2.kv_event_publisher = Pub(worker_id=7)
    n = core2.reannounce_kv()
    assert n >= 1
    assert any(e.stored is not None and e.stored.tier == "disk"
               for e in events)

    # the indexer discounts disk-resident depth
    idx = KvIndexer(block_size=4, prefer_native=False)
    for e in events:
        idx.apply_event(e)
    hashes = [h for h, _t, _p in core2.disk_store.registered_entries()]
    scores = idx.find_matches([hashes[0]])
    assert scores.scores.get(7) == 1
    assert 0 < scores.weighted[7] < 1          # TIER_WEIGHTS["disk"]
    await core2.stop()


def test_tier_weighted_depth_helper():
    from dynamo_tpu.llm.kv_router.scoring import (TIER_WEIGHTS,
                                                  tier_weighted_depth)
    assert tier_weighted_depth(3, []) == 3.0
    assert tier_weighted_depth(2, ["device", "disk"]) == pytest.approx(
        1.0 + TIER_WEIGHTS["disk"])
    assert tier_weighted_depth(2, ["host"]) == pytest.approx(
        TIER_WEIGHTS["host"] + 1.0)


def test_tier_metrics_exported_as_gauges(tmp_path):
    """Satellite: host-tier counters + disk gauges ride ForwardPassMetrics
    into the nv_llm_kv_host_* / nv_llm_kv_disk_* families."""
    from prometheus_client import CollectorRegistry

    from dynamo_tpu.components.metrics import MetricsAggregatorService

    class _EP:
        component, name = "worker", "generate"
        runtime = None

    svc = MetricsAggregatorService(_EP(), registry=CollectorRegistry())
    m = {"kv_active_blocks": 1, "host_stored_total": 5,
         "host_hit_rate": 0.5, "disk_used_blocks": 3,
         "disk_spill_dropped_total": 2,
         "offload_dropped_jobs_total": 1}
    svc._apply_stats({9: m})
    text = svc.render().decode()
    assert "nv_llm_kv_host_stored_blocks_total" in text
    assert "nv_llm_kv_disk_used_blocks" in text
    assert 'nv_llm_kv_disk_spill_dropped_jobs_total{component="worker"' \
        in text


# --------------------------------------------------------------- llmctl kv


@pytest.fixture
async def daemon():
    from dynamo_tpu.runtime.server import DiscoveryServer
    srv = DiscoveryServer(host="127.0.0.1")
    await srv.start()
    yield srv
    await srv.close()


@pytest.mark.asyncio
async def test_llmctl_kv_status_and_flush(tmp_path, daemon, capsys):
    """llmctl kv {status,flush}: the worker publishes tier snapshots
    under kvtier/status/{ns} and acts on the control key — flush
    persists host-resident blocks to disk without a restart."""
    from dynamo_tpu.launch.llmctl import amain as llmctl_amain
    from dynamo_tpu.llm.kv.admin import (publish_status_loop,
                                         watch_control_loop)
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    addr = daemon.address
    assert await llmctl_amain(["--runtime-server", addr, "kv",
                               "status"]) == 1     # nothing published yet

    core = _make_core(tmp_path / "kv")
    await _serve(core, list(range(1, 13)), "a")
    await core.offload_engine.drain()
    assert len(core.kv_manager.host_pool) >= 2
    assert len(core.disk_store) == 0               # nothing evicted yet

    rt = await DistributedRuntime.connect(addr)
    tasks = [asyncio.ensure_future(
                 publish_status_loop(core, rt, "nsA", interval=0.1)),
             asyncio.ensure_future(watch_control_loop(core, rt, "nsA"))]
    try:
        await asyncio.sleep(0.3)
        assert await llmctl_amain(["--runtime-server", addr, "kv",
                                   "status"]) == 0
        out = capsys.readouterr().out
        assert "namespace nsA" in out and "disk:" in out
        # flush: host-resident blocks persist to disk NOW
        assert await llmctl_amain(["--runtime-server", addr, "kv",
                                   "flush", "nsA"]) == 0
        for _ in range(100):
            if len(core.disk_store) >= 2:
                break
            await asyncio.sleep(0.05)
        assert len(core.disk_store) >= 2, "flush never reached the worker"
        # clear drops the disk cache
        assert await llmctl_amain(["--runtime-server", addr, "kv",
                                   "flush", "nsA", "--clear"]) == 0
        for _ in range(100):
            if len(core.disk_store) == 0:
                break
            await asyncio.sleep(0.05)
        assert len(core.disk_store) == 0
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        await rt.shutdown()
        await core.stop()
