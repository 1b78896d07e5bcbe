"""Chaos-hardening suite (docs/chaos.md): every registered failpoint
site is armed, fired, and its RECOVERY asserted — fallback taken,
counters bumped, no leaked holds/pins/slots, no hung awaits. The
coverage gate at the end fails the suite if a registered site is never
armed here (an uninstrumented failure mode is an untested one)."""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.runtime import faults
from dynamo_tpu.runtime.faults import SITES, FaultInjected

pytestmark = [pytest.mark.anyio, pytest.mark.chaos]


@pytest.fixture(autouse=True)
def _disarm_after():
    """Per-test isolation: no armed site outlives its test."""
    yield
    faults.disarm_all()


# ------------------------------------------------------------ the registry


def test_spec_parsing_and_deterministic_1_in_n():
    faults.arm("engine.harvest", "1-in-3,error")
    fired = []
    for i in range(9):
        try:
            faults.hit("engine.harvest")
            fired.append(False)
        except FaultInjected:
            fired.append(True)
    # counter-based: fires on exactly every 3rd hit, run after run
    assert fired == [False, False, True] * 3
    assert faults.fired_count("engine.harvest") >= 3
    # off disarms
    faults.arm("engine.harvest", "off")
    assert "engine.harvest" not in faults.armed()


def test_unknown_site_and_bad_spec_raise():
    with pytest.raises(KeyError):
        faults.arm("no.such.site", "error")
    with pytest.raises(ValueError):
        faults.arm("wal.append", "explode")
    faults.arm("wal.append", "enospc")
    with pytest.raises(KeyError):
        faults.hit("not.registered")


def test_env_arming_roundtrip():
    n = faults.arm_from_env("wal.append=enospc;netstore.call=1-in-2,error")
    assert n == 2
    assert faults.armed() == {"netstore.call": "1-in-2,error",
                              "wal.append": "enospc"}
    with pytest.raises(KeyError):
        faults.arm_from_env("typo.site=error")


def test_custom_exception_class_and_enospc_errno():
    import errno
    faults.arm("request.egress", "error")
    with pytest.raises(ConnectionError):
        faults.hit("request.egress", exc=ConnectionError)
    faults.arm("request.egress", "enospc")
    with pytest.raises(OSError) as ei:
        faults.hit("request.egress")
    assert ei.value.errno == errno.ENOSPC


def test_mangle_truncates_payload():
    data = bytes(range(100))
    assert faults.mangle("dataplane.frame", data) == data  # disarmed
    faults.arm("dataplane.frame", "torn")
    assert faults.mangle("dataplane.frame", data) == data[:50]
    faults.arm("dataplane.frame", "torn:0.1")
    assert faults.mangle("dataplane.frame", data) == data[:10]


# --------------------------------------------------------------- netstore


@pytest.fixture
async def daemon():
    from dynamo_tpu.runtime.server import DiscoveryServer
    srv = DiscoveryServer(host="127.0.0.1")
    await srv.start()
    yield srv
    await srv.close()


async def test_netstore_call_retry_absorbs_flaps(daemon):
    """A 1-in-3 request-plane flap rides the bounded jittered retry
    ladder: every call still succeeds, retries are counted."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    rt = await DistributedRuntime.connect(daemon.address)
    try:
        faults.arm("netstore.call", "1-in-3,error")
        for i in range(6):
            await rt.store.kv_put(f"chaos/k{i}", b"v")
        faults.disarm("netstore.call")
        assert rt.store._conn.retries_total >= 2
        assert (await rt.store.kv_get("chaos/k0")).value == b"v"
    finally:
        faults.disarm_all()
        await rt.shutdown()


async def test_netstore_call_deadline_exceeded_typed_and_counted(daemon):
    """Satellite: the TOTAL per-call deadline fails a partitioned-daemon
    call in bounded time with the typed error + counter, instead of
    holding the caller for the whole retry ladder."""
    from dynamo_tpu.runtime import netstore
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    rt = await DistributedRuntime.connect(daemon.address)
    try:
        conn = rt.store._conn
        conn.CALL_DEADLINE = 0.25
        conn.MAX_CALL_RETRIES = 10_000     # deadline, not attempts, binds
        before = netstore.deadline_exceeded_total()
        faults.arm("netstore.call", "error")   # every attempt "flaps"
        t0 = asyncio.get_running_loop().time()
        with pytest.raises(netstore.NetstoreDeadlineExceeded):
            await rt.store.kv_put("chaos/never", b"v")
        elapsed = asyncio.get_running_loop().time() - t0
        assert elapsed < 5.0                   # bounded, not the ladder
        assert netstore.deadline_exceeded_total() == before + 1
        # typed error degrades like any connection failure for callers
        assert issubclass(netstore.NetstoreDeadlineExceeded,
                          ConnectionError)
        faults.disarm("netstore.call")
        await rt.store.kv_put("chaos/after", b"v")   # recovered
    finally:
        faults.disarm_all()
        await rt.shutdown()


# ---------------------------------------------------------- request plane


async def test_request_egress_flap_retried_and_ingress_delay_served():
    from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
    from dynamo_tpu.runtime.engine import (Context, ResponseStream,
                                           engine_from_fn)

    async def gen(request):
        async def stream():
            yield {"echo": request.data}
        return ResponseStream(stream(), request.ctx)

    rt = DistributedRuntime.in_process()
    ep = Endpoint(rt, "ns", "comp", "gen")
    await ep.serve(engine_from_fn(gen))
    client = await ep.client().start()
    await client.wait_for_instances(10)
    try:
        faults.arm("request.egress", "1-in-2,error")
        faults.arm("request.ingress", "delay:20")
        for q in (1, 2):                 # the 2nd dispatch hits the flap
            got = [x async for x in await asyncio.wait_for(
                client.random(Context({"q": q})), 60)]
            assert got == [{"echo": {"q": q}}]
        assert faults.fired_count("request.egress") >= 1
        assert faults.fired_count("request.ingress") >= 1
    finally:
        await client.close()
        await rt.shutdown()


async def test_request_ingress_error_is_loud_not_hung():
    from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
    from dynamo_tpu.runtime.engine import Context, ResponseStream, \
        engine_from_fn

    async def gen(request):
        async def stream():
            yield {"ok": True}
        return ResponseStream(stream(), request.ctx)

    rt = DistributedRuntime.in_process()
    ep = Endpoint(rt, "ns", "comp", "gen")
    await ep.serve(engine_from_fn(gen))
    client = await ep.client().start()
    await client.wait_for_instances(10)
    try:
        faults.arm("request.ingress", "error")
        with pytest.raises(RuntimeError, match="remote rejected"):
            await asyncio.wait_for(client.random(Context({"q": 1})), 30)
        faults.disarm("request.ingress")
        got = [x async for x in await client.random(Context({"q": 2}))]
        assert got == [{"ok": True}]           # recovered
    finally:
        await client.close()
        await rt.shutdown()


# ----------------------------------------------------------------- leases


async def test_lease_keepalive_flap_tolerated():
    """One dropped refresh RPC must not tear down a healthy worker: the
    keepalive retries inside the TTL window before declaring loss."""
    from dynamo_tpu.runtime.kvstore import MemoryKvStore
    store = MemoryKvStore()
    lease = await store.lease_create(ttl=0.6)
    lost = []
    lease.on_lost = lambda: lost.append(True)
    lease.start_keepalive()
    faults.arm("kvstore.lease.keepalive", "1-in-2,error")
    await asyncio.sleep(1.2)                    # several refresh cycles
    assert not lost                             # flaps absorbed
    assert faults.fired_count("kvstore.lease.keepalive") >= 1
    await lease.revoke()
    await store.close()


async def test_lease_keepalive_sustained_loss_fires_on_lost():
    from dynamo_tpu.runtime.kvstore import MemoryKvStore
    store = MemoryKvStore()
    lease = await store.lease_create(ttl=0.4)
    lost = asyncio.Event()
    lease.on_lost = lost.set
    lease.start_keepalive()
    faults.arm("kvstore.lease.keepalive", "error")   # every refresh
    await asyncio.wait_for(lost.wait(), 15)     # bounded give-up
    await lease.revoke()
    await store.close()


# -------------------------------------------------------------------- WAL


async def test_wal_append_enospc_fails_op_daemon_survives(tmp_path):
    """A full disk fails the ONE op whose durability could not be
    acknowledged; the daemon keeps serving (and later ops are durable)."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.server import DiscoveryServer
    srv = DiscoveryServer(host="127.0.0.1", data_dir=str(tmp_path),
                          wal_fsync=False)
    await srv.start()
    rt = await DistributedRuntime.connect(srv.address)
    try:
        faults.arm("wal.append", "enospc")
        with pytest.raises(Exception):
            await rt.store.kv_put("chaos/full", b"v")
        faults.disarm("wal.append")
        await rt.store.kv_put("chaos/ok", b"v")    # daemon survived
        assert (await rt.store.kv_get("chaos/ok")).value == b"v"
    finally:
        faults.disarm_all()
        await rt.shutdown()
        await srv.close()
    # the acknowledged op survives a restart (durable); recovery works
    srv2 = DiscoveryServer(host="127.0.0.1", data_dir=str(tmp_path),
                           wal_fsync=False)
    await srv2.start()
    rt2 = await DistributedRuntime.connect(srv2.address)
    try:
        e = await rt2.store.kv_get("chaos/ok")
        assert e is not None and e.value == b"v"
    finally:
        await rt2.shutdown()
        await srv2.close()


# ------------------------------------------------------------ disk tier


def _blk(x: float):
    return {"k": np.full((2, 2, 4, 8), x, np.float32),
            "v": np.full((2, 2, 4, 8), -x, np.float32)}


def test_diskstore_write_enospc_raises_and_recovers(tmp_path):
    from dynamo_tpu.llm.kv.diskstore import DiskKvStore
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    faults.arm("diskstore.write", "enospc")
    with pytest.raises(OSError):
        store.put(1, _blk(1.0))
    assert not store.contains(1)               # nothing half-acknowledged
    faults.disarm("diskstore.write")
    assert store.put(1, _blk(1.0)) == []
    assert store.contains(1)
    store.close()


def test_diskstore_torn_write_reaped_at_recovery(tmp_path):
    from dynamo_tpu.llm.kv.diskstore import DiskKvStore
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    store.put(1, _blk(1.0))
    faults.arm("diskstore.write", "torn")
    store.put(2, _blk(2.0))                    # acknowledged, bytes torn
    faults.disarm("diskstore.write")
    store.close()
    warm = DiskKvStore(str(tmp_path), capacity_blocks=8)
    assert warm.contains(1)                    # whole block survives
    assert not warm.contains(2)                # torn payload reaped
    assert warm.reaped_corrupt_blocks == 1
    warm.close()


def test_diskstore_recovery_failure_starts_cold(tmp_path):
    from dynamo_tpu.llm.kv.diskstore import DiskKvStore
    store = DiskKvStore(str(tmp_path), capacity_blocks=8)
    store.put(1, _blk(1.0))
    store.close()
    faults.arm("diskstore.recovery", "error")
    cold = DiskKvStore(str(tmp_path), capacity_blocks=8)  # no raise
    assert cold.restored_blocks == 0           # degraded to a cold start
    cold.close()
    faults.disarm("diskstore.recovery")
    warm = DiskKvStore(str(tmp_path), capacity_blocks=8)
    assert warm.restored_blocks >= 0           # recovered path works
    warm.close()


async def test_disk_spill_sheds_on_enospc_and_keeps_pumping(tmp_path):
    from dynamo_tpu.llm.kv.diskstore import (DiskKvStore, DiskSpillEngine,
                                             SpillJob)
    store = DiskKvStore(str(tmp_path), capacity_blocks=32)
    pump = DiskSpillEngine(store)
    faults.arm("diskstore.spill", "enospc")
    for h in (1, 2, 3):
        assert pump.offer(SpillJob(h, None, None, _blk(float(h))))
    await pump.drain()
    assert pump.shed_writes_total == 3         # shed, not crashed
    assert store.used_blocks == 0
    faults.disarm("diskstore.spill")
    assert pump.offer(SpillJob(4, None, None, _blk(4.0)))
    await pump.drain()
    assert store.contains(4)                   # pump recovered
    await pump.stop()
    store.close()


def test_remotestore_put_enospc_and_torn_object(tmp_path):
    from dynamo_tpu.llm.kv.remotestore import ObjectKvBackend, RemoteKvStore
    rs = RemoteKvStore(ObjectKvBackend(str(tmp_path)))
    faults.arm("remotestore.put", "enospc")
    with pytest.raises(OSError):
        rs.put(1, _blk(1.0))
    faults.arm("remotestore.put", "torn")
    rs.put(2, _blk(2.0))                       # lands, but truncated
    faults.disarm("remotestore.put")
    with pytest.raises(KeyError):
        rs.object.fetch_blocks([2])            # torn object is a miss…
    assert rs.object.reaped_corrupt_total == 1  # …and is reaped
    rs.put(3, _blk(3.0))
    assert rs.object.fetch_blocks([3])[0]["k"][0, 0, 0, 0] == 3.0


# ------------------------------------------------------ fabric + breaker


async def test_fabric_fetch_failpoint_trips_breaker():
    """fabric.fetch errors feed the peer's circuit breaker: after the
    failure budget the peer is OPEN — fetches short-circuit (no RPC, no
    waiting) and its holdings vanish from the store's holder view."""
    from dynamo_tpu.llm.kv.fabric import (AdmissionGate, KvFabric,
                                          PeerLinkTable)
    from dynamo_tpu.llm.kv.remotestore import RemoteKvStore
    links = PeerLinkTable(breaker_failure_threshold=3,
                          breaker_cooldown_s=30.0)
    store = RemoteKvStore()
    fab = KvFabric(store, links, AdmissionGate(1, 1, 1.0))
    store.note_peer_stored(7, [101, 102])
    assert store.holders_of(101) == [7]
    faults.arm("fabric.fetch", "error")
    for _ in range(3):
        with pytest.raises(KeyError):
            await fab.fetch_async(7, [101])
    assert links.breaker(7).state == "open"
    assert links.breaker_trips_total() == 1
    assert links.open_breaker_count() == 1
    # open short-circuits BEFORE the failpoint/RPC
    fired = faults.fired_count("fabric.fetch")
    with pytest.raises(KeyError, match="circuit breaker"):
        await fab.fetch_async(7, [101])
    assert faults.fired_count("fabric.fetch") == fired
    # NetKV/admission credit withdrawn: holders gone, link prices dead
    assert store.holders_of(101) == []
    assert links.link_for_holders([[7]]).gbps == 0.0
    assert not AdmissionGate(1 << 20, 32, 1000.0).admit(
        4, links.link_for_holders([[7]]))


def test_breaker_half_open_recovery_and_hysteresis():
    """Both directions (acceptance criterion): a browning-out peer trips
    within its failure budget AND a recovered peer is re-admitted via
    the half-open trial — no permanent exile, no flapping."""
    from dynamo_tpu.llm.kv.fabric import CircuitBreaker
    t = [0.0]
    b = CircuitBreaker(failure_threshold=3, cooldown_s=10.0,
                       latency_slo_s=1.0, now=lambda: t[0])
    # hysteresis: alternating success/failure never trips (consecutive
    # counter resets) — no flapping on a noisy-but-working link
    for _ in range(10):
        b.record_failure()
        b.record_success(0.1)
    assert b.state == "closed" and b.trips_total == 0
    # consecutive failures trip within the budget
    for _ in range(3):
        b.record_failure()
    assert b.state == "open" and not b.would_allow()
    # cooldown not elapsed: still exiled
    t[0] = 5.0
    assert not b.would_allow()
    # cooldown elapsed: exactly ONE half-open trial
    t[0] = 11.0
    assert b.allow()
    assert not b.allow()                        # second trial refused
    b.record_failure()                          # trial failed → re-open
    assert b.state == "open" and b.trips_total == 2
    t[0] = 22.0
    assert b.allow()
    b.record_success(0.1)                       # trial passed → closed
    assert b.state == "closed" and b.would_allow()
    # latency-SLO brownout: slow "successes" trip exactly like failures
    for _ in range(3):
        b.record_success(5.0)                   # 5s >> 1s SLO
    assert b.state == "open" and b.trips_total == 3


async def test_fabric_dialback_and_torn_frame(monkeypatch, tmp_path):
    """Serving-peer chaos: a failed dial-back declines to the JSON path
    (return False, never an error); a torn streamed frame surfaces on
    the fetching side as an unpackable block (→ recompute)."""
    from dynamo_tpu.llm.kv.fabric import KvFabricServer
    from dynamo_tpu.llm.kv.remotestore import (pack_block_bytes,
                                               unpack_block_bytes)
    from dynamo_tpu.runtime.codec import FrameKind
    from dynamo_tpu.runtime.tcp import TcpStreamServer
    monkeypatch.setenv("DYN_NATIVE_DATAPLANE", "0")   # asyncio sender
    server = KvFabricServer(core=None)
    tcp = TcpStreamServer("127.0.0.1")
    await tcp.start()
    blocks = {5: pack_block_bytes(_blk(5.0))}

    # dial-back failure → graceful decline
    faults.arm("fabric.dialback", "error")
    rx = tcp.register()
    ok = await server._stream_native(
        tcp.connection_info(rx).to_dict(), [5], blocks)
    assert ok is False                          # caller rides JSON
    tcp.unregister(rx.stream_id)
    faults.disarm("fabric.dialback")

    # torn frame → unpack fails on the fetching side
    faults.arm("dataplane.frame", "torn")
    rx = tcp.register()
    ok = await server._stream_native(
        tcp.connection_info(rx).to_dict(), [5], blocks)
    assert ok is True
    f = await rx.next_frame(timeout=10)
    assert f is not None and f.kind == FrameKind.DATA
    with pytest.raises(ValueError):
        unpack_block_bytes(f.data)              # torn npz is a miss
    rx.close()
    tcp.unregister(rx.stream_id)
    await tcp.close()


# ------------------------------------------------------------- the engine


def _tiny_core(**kw):
    import jax.numpy as jnp
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    mcfg = ModelConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16,
                       max_position_embeddings=256)
    kw = {"max_model_len": 64, "kv_block_size": 4, "num_kv_blocks": 32,
          "max_num_seqs": 2, "prefill_buckets": [32, 64], **kw}
    return EngineCore(mcfg, EngineConfig(**kw), attn_impl="xla",
                      param_dtype=jnp.float32)


async def _serve(core, prompt, rid="r", max_new=4):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=max_new, eos_ids=frozenset())
    await core.submit(req)
    toks = []
    while True:
        item, payload = await asyncio.wait_for(req.out_queue.get(), 120)
        if item is FINISH_SENTINEL:
            return toks, payload, req
        toks.append(item)


async def test_engine_onboard_failpoint_falls_back_to_cold_recompute():
    """A failing tier-hit onboard degrades to a COLD admission (full
    recompute) with identical output — never a failed request, never a
    leaked hold/pin."""
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    from dynamo_tpu.llm.protocols.common import FinishReason
    core = _tiny_core(host_kv_blocks=16)
    try:
        prompt = list(range(1, 13))

        async def run():
            req = EngineRequest(rid="r", prompt=list(prompt),
                                sampling=SlotSampling(temperature=0.0),
                                max_new_tokens=4, eos_ids=frozenset())
            await core.submit(req)
            toks = []
            while True:
                item, payload = await asyncio.wait_for(
                    req.out_queue.get(), 120)
                if item is FINISH_SENTINEL:
                    return toks, payload, req
                toks.append(item)

        toks1, r1, _ = await run()
        assert r1 == FinishReason.LENGTH
        await core.offload_engine.drain()
        core.kv_manager.pool.reset()            # force the host-tier path
        faults.arm("engine.onboard", "error")
        toks2, r2, req2 = await run()
        faults.disarm("engine.onboard")
        assert r2 == FinishReason.LENGTH        # served, not errored
        assert toks2 == toks1                   # cold recompute, same math
        assert req2.cold_admission and core.onboard_cold_retries == 1
        assert req2.prefix_hit_tokens == 0      # tiers skipped
        # nothing leaked: pool drains back to empty, host pins clear.
        # A release with a host tier takes one more hold on the prompt's
        # registered blocks until their write-back lands (_release_slot);
        # drain it as after the first request, or the pool reads that
        # hold (3 blocks), not a leak
        await core.offload_engine.drain()
        assert core.kv_manager.pool.used_blocks == 0
        assert not core.kv_manager.host_pool._pins
    finally:
        await core.stop()


async def test_engine_harvest_failpoint_fails_loudly_and_releases_all():
    """An error at the harvest boundary is LOUD: the loop dies, every
    pending request gets an ERROR finish, every KV block is released —
    the opposite of a hang (round-5 postmortem contract under chaos)."""
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    from dynamo_tpu.llm.protocols.common import FinishReason
    core = _tiny_core(decode_steps_per_dispatch=4)
    req = EngineRequest(rid="r", prompt=list(range(1, 10)),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=8, eos_ids=frozenset())
    faults.arm("engine.harvest", "error")
    await core.submit(req)
    while True:      # the prefill's first token may land before the kill
        item, payload = await asyncio.wait_for(req.out_queue.get(), 120)
        if item is FINISH_SENTINEL:
            break
    assert payload == FinishReason.ERROR
    assert core.kv_manager.pool.used_blocks == 0   # _fail_pending swept
    assert core._dead is not None                  # loud, not wedged
    faults.disarm("engine.harvest")
    await core.stop()


async def test_prefill_publish_failpoint_sheds_blocks(tmp_path):
    """A refusing object tier forfeits individual block publishes and
    keeps going — publish is an optimization, never a failure."""
    core = _tiny_core(host_kv_blocks=16,
                      kv_disk_dir=str(tmp_path / "disk"),
                      kv_disk_blocks=16,
                      kv_remote_dir=str(tmp_path / "obj"))
    try:
        _toks, _r, req = await _serve(core, list(range(1, 13)))
        faults.arm("prefill.publish", "enospc")
        n = await core.publish_prefix_to_remote(req.seq)
        assert n == 0                           # every put shed, no raise
        assert faults.fired_count("prefill.publish") >= 1
        faults.disarm("prefill.publish")
        n2 = await core.publish_prefix_to_remote(req.seq)
        assert n2 >= 2                          # recovered: prefix lands
        assert core.kv_manager.pool.used_blocks == 0   # holds released
    finally:
        await core.stop()


async def test_layer_stream_torn_frame_degrades_to_monolithic():
    """A torn per-layer frame mid-stream ("disagg.layer_stream", rung 1
    of the fallback ladder) degrades to the monolithic payload ON THE
    SAME STREAM: the decode side fills the remaining layers from it and
    the served tokens are byte-identical to an untorn run — never an
    error, never a cold recompute."""
    from dynamo_tpu.llm.disagg import (DisaggEngine, DisaggregatedRouter,
                                       PrefillWorker)
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from tests.test_disagg import (collect_tokens,
                                   hold_stream_until_admitted, make_core,
                                   make_request)

    rng = np.random.default_rng(31)
    prompt = [int(t) for t in rng.integers(2, 120, size=37)]

    async def wire_run(rid):
        rt = DistributedRuntime.in_process()
        prefill_core = make_core()
        decode_core = make_core()
        router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=0,
                                     conditional=False)
        engine = DisaggEngine(decode_core, rt, router, device_plane=False,
                              layer_stream=True)
        hold_stream_until_admitted(engine, decode_core)
        worker = await PrefillWorker(prefill_core, rt).start()
        try:
            got = await collect_tokens(
                await engine.generate(make_request(prompt, rid=rid)))
            assert engine.remote_failures == 0
            return got, worker, decode_core
        finally:
            await worker.stop()
            await prefill_core.stop()
            await decode_core.stop()
            await rt.shutdown()

    want, _w, _c = await wire_run("untorn")
    faults.arm("disagg.layer_stream", "1-in-2,torn")
    try:
        got, worker, decode_core = await wire_run("torn")
    finally:
        faults.disarm("disagg.layer_stream")
    assert got == want                      # byte-identical degradation
    assert faults.fired_count("disagg.layer_stream") >= 1
    assert worker.stream_fallbacks >= 1     # producer took rung 1
    assert worker.prefills_done == 1        # served, not retried
    # the consumer saw the monolithic tail and counted the fallback —
    # the request was NOT re-admitted cold
    assert decode_core.disagg_stream_fallbacks >= 1
    assert decode_core.total_prefill_tokens == 0


# -------------------------------------------------------- fleet-ops plumbing


async def test_llmctl_faults_table_applies_live():
    """The faults/control/{ns} table is declarative: watching processes
    converge to it (arm + disarm), and bad entries are skipped."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.faults import (faults_control_key,
                                           watch_faults_loop)
    rt = DistributedRuntime.in_process()
    task = asyncio.get_running_loop().create_task(
        watch_faults_loop(rt, "chaosns"))
    try:
        import json
        await rt.store.kv_put(
            faults_control_key("chaosns"),
            json.dumps({"wal.append": "enospc",
                        "bogus.site": "error"}).encode())
        for _ in range(100):
            if faults.armed().get("wal.append") == "enospc":
                break
            await asyncio.sleep(0.02)
        assert faults.armed() == {"wal.append": "enospc"}
        await rt.store.kv_put(faults_control_key("chaosns"), b"{}")
        for _ in range(100):
            if not faults.armed():
                break
            await asyncio.sleep(0.02)
        assert faults.armed() == {}
    finally:
        task.cancel()
        await rt.shutdown()


# ---------------------------------------------------------- coverage gate


def test_failpoint_coverage_gate():
    """Every registered site must be (a) armed by name somewhere in this
    suite and (b) live in the registry: armed here, one hit raises and
    counts once. That a site's own test really drove the program through
    it is that test's finding (each asserts a recovery only the injected
    fault produces), and that the program places a hit on it is dynalint
    DL009's; so this gate reads no counter a sibling test left behind:
    it passes alone, and reports no other test's failure a second time.
    An unreferenced site fails the suite — instrumentation without a
    recovery test is a false sense of coverage."""
    with open(__file__, encoding="utf-8") as f:
        src = f.read()
    unreferenced = [s for s in SITES if f'arm("{s}"' not in src]
    assert not unreferenced, (
        f"failpoint sites never armed by the chaos suite: "
        f"{unreferenced} — add an arm/fire/recover test per site")
    for s in SITES:
        before = faults.fired_count(s)
        faults.arm(s, "error")
        with pytest.raises(FaultInjected):
            faults.hit(s)
        faults.disarm(s)
        assert faults.fired_count(s) == before + 1
        faults.hit(s)                          # disarmed: a no-op again
