"""PD disaggregation: remote prefill round trip, KV handoff correctness,
conditional routing, live threshold reconfig, and fallback.

Reference test strategy analog: the disagg path is exercised fully
in-process with real transports (memory bus + real TCP sockets) and tiny
random models — SURVEY.md §4's "single-machine distributed tests" tier."""

import asyncio
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.llm.disagg import (DisaggEngine, DisaggregatedRouter,
                                   PrefillQueue, PrefillWorker)
from dynamo_tpu.llm.engines.jax_engine import JaxEngine
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.llm.protocols.disagg import (KvPayload, RemotePrefillRequest,
                                             decode_kv_payload,
                                             encode_kv_payload)
from dynamo_tpu.runtime import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import EngineContext
from tests.fixtures import wait_until

pytestmark = pytest.mark.asyncio

TINY = ModelConfig(
    model_type="llama", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=16, max_position_embeddings=256, tie_word_embeddings=False)

ECFG = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=48,
            max_num_seqs=2, prefill_buckets=[16, 32, 64, 128])


def make_core(**over) -> EngineCore:
    cfg = EngineConfig(**{**ECFG, **over})
    return EngineCore(TINY, cfg, attn_impl="xla", param_dtype=jnp.float32)


def make_request(prompt, max_tokens=8, rid="r1") -> Context:
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(greedy=True))
    return Context(pre, ctx=EngineContext(rid))


async def collect_tokens(stream):
    toks = []
    async for a in stream:
        if a.data is not None and a.data.token_ids:
            toks.extend(a.data.token_ids)
    return toks


# ---------------------------------------------------------------- protocols

def test_kv_payload_roundtrip():
    rng = np.random.default_rng(0)
    vals = {"k": rng.standard_normal((2, 2, 3, 8, 16)).astype(np.float32),
            "v": rng.standard_normal((2, 2, 3, 8, 16)).astype(np.float32)}
    p = KvPayload(request_id="x", first_token=7, first_logprob=-0.5,
                  seq_hashes=[11, 22, 33], values=vals)
    hdr, data = encode_kv_payload(p)
    q = decode_kv_payload(hdr, data)
    assert q.request_id == "x" and q.first_token == 7
    assert q.seq_hashes == [11, 22, 33]
    np.testing.assert_array_equal(q.values["k"], vals["k"])
    np.testing.assert_array_equal(q.values["v"], vals["v"])


def test_kv_payload_bfloat16_roundtrip():
    x = jnp.arange(2 * 1 * 1 * 4 * 2, dtype=jnp.bfloat16).reshape(
        2, 1, 1, 4, 2)
    vals = {"k": np.asarray(x), "v": np.asarray(x + 1)}
    p = KvPayload("y", 1, 0.0, [5], vals)
    hdr, data = encode_kv_payload(p)
    q = decode_kv_payload(hdr, data)
    assert q.values["k"].dtype == vals["k"].dtype
    np.testing.assert_array_equal(q.values["v"], vals["v"])


def test_remote_prefill_request_roundtrip():
    r = RemotePrefillRequest(
        request_id="a", token_ids=[1, 2, 3], sampling={"temperature": 0.0},
        connection_info={"address": "1.2.3.4:5", "stream_id": "s"},
        engine_id="e", prefix_hit_tokens=8)
    assert RemotePrefillRequest.from_json(r.to_json()) == r


# ------------------------------------------------------------------- router

def test_disagg_router_threshold():
    rt = DistributedRuntime.in_process()
    r = DisaggregatedRouter(rt, "m", max_local_prefill_length=100)
    assert not r.prefill_remote(100, 0)
    assert r.prefill_remote(101, 0)
    assert not r.prefill_remote(200, 100)   # prefix hit discounts
    r2 = DisaggregatedRouter(rt, "m", max_local_prefill_length=100,
                             conditional=False)
    assert r2.prefill_remote(1, 0)          # unconditional disagg


async def test_disagg_router_live_reconfig():
    rt = DistributedRuntime.in_process()
    r = await DisaggregatedRouter(rt, "m", max_local_prefill_length=100).start()
    await r.publish_threshold(7)
    for _ in range(50):
        if r.max_local_prefill_length == 7:
            break
        await asyncio.sleep(0.02)
    assert r.max_local_prefill_length == 7
    await r.stop()
    await rt.shutdown()


async def test_prefill_queue_ack_nack():
    rt = DistributedRuntime.in_process()
    q = PrefillQueue(rt)
    r = RemotePrefillRequest("a", [1], {}, {"address": "x:1", "stream_id": "s"})
    await q.enqueue(r)
    item = await q.dequeue(timeout=1)
    assert item is not None
    await q.nack(item.id)
    item2 = await q.dequeue(timeout=1)
    assert item2.deliveries == 2
    await q.ack(item2.id)
    assert await q.depth() == 0
    await rt.shutdown()


# ----------------------------------------------------- end-to-end handoff

@pytest.fixture
def prompt():
    rng = np.random.default_rng(42)
    return [int(t) for t in rng.integers(2, 120, size=37)]


@pytest.mark.parametrize("plane", ["device", "wire"])
async def test_remote_prefill_matches_local(prompt, plane):
    """Disagg (prefill engine → KV handoff → decode engine) must produce
    exactly the greedy tokens of a single aggregated engine — on both the
    in-process device bulk plane (ICI analog of the reference's NIXL
    `read_blocks`/`write_blocks`) and the TCP wire fallback."""
    local_core = make_core()
    try:
        local = JaxEngine(local_core)
        want = await collect_tokens(
            await local.generate(make_request(prompt, rid="want")))
    finally:
        await local_core.stop()
    assert len(want) == 8

    rt = DistributedRuntime.in_process()
    prefill_core = make_core()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=0,
                                 conditional=False)
    engine = DisaggEngine(decode_core, rt, router,
                          device_plane=(plane == "device"))
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        got = await collect_tokens(
            await engine.generate(make_request(prompt, rid=f"got-{plane}")))
        assert got == want
        assert engine.remote_prefills == 1 and engine.remote_failures == 0
        assert worker.prefills_done == 1
        # prefill engine computed the prompt; decode engine never prefilled
        assert prefill_core.total_prefill_tokens == len(prompt)
        assert decode_core.total_prefill_tokens == 0
        assert decode_core.total_decode_tokens >= 7
        if plane == "device":
            # the bulk bytes rode the in-process device plane, not TCP
            assert engine.device_transfers == 1
            assert worker.device_handoffs == 1
        else:
            assert engine.device_transfers == 0
            assert worker.device_handoffs == 0
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()


async def test_remote_prefill_chunked_transfer(prompt, monkeypatch):
    """KV payloads larger than one chunk stream across multiple frames
    (guards the MAX_FRAME bound for long-prompt handoffs)."""
    import dynamo_tpu.llm.protocols.disagg as dproto
    monkeypatch.setattr(dproto, "KV_CHUNK_BYTES", 1024)

    local_core = make_core()
    try:
        want = await collect_tokens(await JaxEngine(local_core).generate(
            make_request(prompt, rid="want")))
    finally:
        await local_core.stop()

    rt = DistributedRuntime.in_process()
    prefill_core = make_core()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", conditional=False)
    # wire plane forced: chunked framing is a TCP-path concern
    engine = DisaggEngine(decode_core, rt, router, device_plane=False)
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        got = await collect_tokens(
            await engine.generate(make_request(prompt, rid="got")))
        assert got == want
        assert engine.remote_prefills == 1
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()


async def test_disagg_fallback_without_prefill_worker(prompt):
    """No prefill workers → the decode engine falls back to local prefill
    and still serves the request correctly."""
    local_core = make_core()
    try:
        want = await collect_tokens(await JaxEngine(local_core).generate(
            make_request(prompt, rid="want")))
    finally:
        await local_core.stop()

    rt = DistributedRuntime.in_process()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", conditional=False)
    engine = DisaggEngine(decode_core, rt, router, prefill_timeout=0.5)
    try:
        got = await collect_tokens(
            await engine.generate(make_request(prompt, rid="got")))
        assert got == want
        assert engine.remote_failures == 1
        assert decode_core.total_prefill_tokens == len(prompt)
    finally:
        await decode_core.stop()
        await rt.shutdown()


async def test_conditional_disagg_short_prompt_stays_local(prompt):
    """Under the threshold → no queue traffic, local prefill."""
    rt = DistributedRuntime.in_process()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=1000)
    engine = DisaggEngine(decode_core, rt, router)
    try:
        toks = await collect_tokens(
            await engine.generate(make_request(prompt, rid="short")))
        assert len(toks) == 8
        assert engine.local_prefills == 1 and engine.remote_prefills == 0
        assert await PrefillQueue(rt).depth() == 0
    finally:
        await decode_core.stop()
        await rt.shutdown()


# ------------------------------------------------- TP-reshard on handoff

def make_mesh_core(tp: int, **over) -> EngineCore:
    """EngineCore sharded over a tp-wide mesh of CPU devices."""
    from dynamo_tpu.parallel.sharding import make_mesh
    cfg = EngineConfig(**{**ECFG, **over})
    return EngineCore(TINY, cfg, attn_impl="xla", param_dtype=jnp.float32,
                      mesh=make_mesh(dp=1, tp=tp))


async def _disagg_pair_run(prefill_core, decode_core, prompt, rid, plane):
    rt = DistributedRuntime.in_process()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=0,
                                 conditional=False)
    engine = DisaggEngine(decode_core, rt, router,
                          device_plane=(plane == "device"))
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        got = await collect_tokens(
            await engine.generate(make_request(prompt, rid=rid)))
        assert engine.remote_prefills == 1 and engine.remote_failures == 0
        return got, engine, worker
    finally:
        await worker.stop()
        await rt.shutdown()


@pytest.mark.parametrize("src_tp,dst_tp,plane", [
    (1, 2, "device"),   # unsharded prefill → TP-2 decode, ICI plane
    (2, 4, "device"),   # TP-2 prefill → TP-4 decode, ICI plane
    (1, 2, "wire"),     # same reshard through the TCP fallback
])
async def test_tp_reshard_on_handoff(prompt, src_tp, dst_tp, plane):
    """Prefill engine TP=src → decode engine TP=dst: the handoff reshards
    the KV blocks under the decode mesh (device plane: `jax.device_put`
    with the decode KV sharding — the reference's permute_scatter_memcpy
    semantics, block_copy.cu:558-728) and decode must match a same-mesh
    run that prefilled locally."""
    # reference: the DECODE-side mesh serving the request alone (local
    # prefill on the same tp=dst mesh — greedy tokens to compare against)
    ref_core = make_mesh_core(dst_tp)
    try:
        want = await collect_tokens(await JaxEngine(ref_core).generate(
            make_request(prompt, rid="want")))
    finally:
        await ref_core.stop()
    assert len(want) == 8

    prefill_core = (make_core() if src_tp == 1
                    else make_mesh_core(src_tp))
    decode_core = make_mesh_core(dst_tp)
    try:
        got, engine, worker = await _disagg_pair_run(
            prefill_core, decode_core, prompt,
            f"reshard-{src_tp}-{dst_tp}-{plane}", plane)
        assert decode_core.total_prefill_tokens == 0   # KV arrived sharded
        if plane == "device":
            assert engine.device_transfers == 1
            assert worker.device_handoffs == 1
        # bit-identical decode: the resharded blocks must hold exactly the
        # values a local same-mesh prefill would have written (the decode
        # program's math is identical from there on; the first token comes
        # from the prefill mesh whose matmul partial-sum order can differ,
        # so near-tie flips there would be legitimate — flag them apart)
        assert got[1:] == want[1:], (
            f"decode diverged after handoff (src_tp={src_tp}, "
            f"dst_tp={dst_tp}, plane={plane})")
        assert got[0] == want[0], (
            "first token flipped across meshes — near-tie numerics or a "
            "real handoff bug; investigate before loosening")
    finally:
        await prefill_core.stop()
        await decode_core.stop()


async def test_decode_prefix_reuse_after_remote_prefill(prompt):
    """After one remote prefill, the decode engine's pool holds the prompt's
    blocks — a repeat of the same prompt gets a device-tier prefix hit and
    the router keeps it local (the conditional-disagg interplay)."""
    rt = DistributedRuntime.in_process()
    prefill_core = make_core()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=16)
    engine = DisaggEngine(decode_core, rt, router)
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        first = await collect_tokens(
            await engine.generate(make_request(prompt, rid="one")))
        assert engine.remote_prefills == 1
        second = await collect_tokens(
            await engine.generate(make_request(prompt, rid="two")))
        assert first == second
        # 37-token prompt, 32 tokens of it in reused blocks → 5 uncached
        # tokens < threshold 16 → local
        assert engine.local_prefills == 1
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()


@pytest.mark.parametrize("plane", ["device", "wire"])
async def test_remote_prefill_int8_pools_match_local(prompt, plane):
    """Disagg with int8 KV pools on BOTH engines (the former refusal,
    now closed): the handoff ships whole int8 rows — values plus in-row
    scales — bit-exactly on either plane, so the disagg pair reproduces
    an aggregated int8 engine's greedy tokens exactly."""
    local_core = make_core(kv_quantization="int8")
    try:
        local = JaxEngine(local_core)
        want = await collect_tokens(
            await local.generate(make_request(prompt, rid="want8")))
    finally:
        await local_core.stop()
    assert len(want) == 8

    prefill_core = make_core(kv_quantization="int8")
    decode_core = make_core(kv_quantization="int8")
    got, engine, worker = await _disagg_pair_run(
        prefill_core, decode_core, prompt, f"got8-{plane}", plane)
    try:
        assert got == want
        assert prefill_core.total_prefill_tokens == len(prompt)
        assert decode_core.total_prefill_tokens == 0
        if plane == "device":
            assert engine.device_transfers == 1
    finally:
        await prefill_core.stop()
        await decode_core.stop()


async def test_disagg_kv_layout_mismatch_fails_loudly():
    """A decode engine rejects KV payloads whose layout it cannot
    serve: the WIRE plane never repacks, and int8 rows from a different
    tp (whose width bundles a different scale-group count) refuse on
    either plane. (Device-plane cross-quant repacks instead — see
    test_remote_prefill_cross_quant_repack.)"""
    core8 = make_core(kv_quantization="int8")
    core_f = make_core()
    try:
        lanes8 = core8.kv["k"].shape[-1]          # C + 128
        lanes_f = core_f.kv["k"].shape[-1]        # C
        with pytest.raises(ValueError, match="layout mismatch"):
            core_f._check_kv_payload_layout(lanes8, np.int8, "wire")
        with pytest.raises(ValueError, match="layout mismatch"):
            core8._check_kv_payload_layout(lanes_f, np.float32, "wire")
        # same width, wrong dtype must not pass either
        with pytest.raises(ValueError, match="layout mismatch"):
            core8._check_kv_payload_layout(lanes8, np.float32, "device")
        # int8 rows from a tp=2 prefill carry 2 scale groups → wider
        with pytest.raises(ValueError, match="layout mismatch"):
            core8._check_kv_payload_layout(
                lanes8 + 128, np.int8, "device")
        core8._check_kv_payload_layout(lanes8, np.int8, "wire")  # ok
        core_f._check_kv_payload_layout(lanes_f, np.float32, "wire")

        # end-to-end: submit() delivers the error SYNCHRONOUSLY to the
        # caller (a raise inside the engine loop would kill it and hang
        # every in-flight request), and the engine keeps serving after
        from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
        from dynamo_tpu.engine.sampling import SlotSampling
        bad = KvPayload(
            request_id="bad", first_token=3, first_logprob=0.0,
            seq_hashes=[1],
            values={"k": np.zeros((2, 1, 1, 8, lanes8), np.int8),
                    "v": np.zeros((2, 1, 1, 8, lanes8), np.int8)})
        req = EngineRequest(rid="bad", prompt=list(range(2, 12)),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=2, eos_ids=frozenset(),
                            precomputed=bad)
        with pytest.raises(ValueError, match="layout mismatch"):
            await core_f.submit(req)
        ok = EngineRequest(rid="ok", prompt=list(range(2, 12)),
                           sampling=SlotSampling(temperature=0.0),
                           max_new_tokens=2, eos_ids=frozenset())
        await core_f.submit(ok)
        toks = []
        while True:
            item, _ = await ok.out_queue.get()
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        assert len(toks) == 2
    finally:
        await core8.stop()
        await core_f.stop()


# ------------------------------------------- layer-wise streaming handoff

def make_seeded_request(prompt, rid) -> Context:
    """Seeded stochastic sampling: the bit-exactness bar for the layer
    stream covers the sampled path too (same seed → same key stream →
    same tokens, streamed or monolithic)."""
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.8, top_k=20,
                                         seed=1234))
    return Context(pre, ctx=EngineContext(rid))


def hold_stream_until_admitted(engine, core):
    """Which of two legitimate paths a layer stream admits through is a
    race between the frames' arrival and the engine loop's admission
    tick: a payload that is whole by then takes the monolithic
    precomputed path (by design, `LayerStreamPayload.values`) and the
    per-layer counters these tests read stay 0 — six busy xdist workers
    made that happen now and then. Hold the drain (the frames queue in
    the receiver) until the core has admitted against the manifest, so
    that the streamed path is the one that runs."""
    spawn = engine._spawn_stream_drain

    def held(rid, rx, payload):
        async def later():
            await wait_until(lambda: core.disagg_stream_admits >= 1,
                             "admission against the stream's manifest")
            spawn(rid, rx, payload)
        task = asyncio.get_running_loop().create_task(later())
        engine._drain_tasks.add(task)
        task.add_done_callback(engine._drain_tasks.discard)

    engine._spawn_stream_drain = held


async def _wire_disagg_run(prompt, rid, layer_stream, seeded=False):
    rt = DistributedRuntime.in_process()
    prefill_core = make_core()
    decode_core = make_core()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=0,
                                 conditional=False)
    engine = DisaggEngine(decode_core, rt, router, device_plane=False,
                          layer_stream=layer_stream)
    if layer_stream:
        hold_stream_until_admitted(engine, decode_core)
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        req = (make_seeded_request(prompt, rid) if seeded
               else make_request(prompt, rid=rid))
        got = await collect_tokens(await engine.generate(req))
        assert engine.remote_prefills == 1 and engine.remote_failures == 0
        return got, engine, worker, decode_core
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()


@pytest.mark.parametrize("seeded", [False, True], ids=["greedy", "seeded"])
async def test_layer_stream_matches_monolithic(prompt, seeded):
    """ISSUE 18 tentpole: the layer-streamed wire handoff must produce
    BIT-exactly the tokens of the monolithic handoff (and, under greedy,
    of a local aggregated run) — the overlap is a latency optimisation,
    never a numerics change. Covers both greedy and seeded sampling."""
    if not seeded:
        local_core = make_core()
        try:
            want = await collect_tokens(await JaxEngine(local_core).generate(
                make_request(prompt, rid="ls")))
        finally:
            await local_core.stop()

    mono, eng_m, _w, core_m = await _wire_disagg_run(
        prompt, "ls", layer_stream=False, seeded=seeded)
    streamed, eng_s, wrk_s, core_s = await _wire_disagg_run(
        prompt, "ls", layer_stream=True, seeded=seeded)
    assert streamed == mono
    if not seeded:
        assert streamed == want
    assert len(streamed) == 8
    # the streamed leg really took the per-layer path end to end
    assert core_s.disagg_stream_admits == 1
    assert core_s.disagg_stream_fallbacks == 0
    assert core_s.disagg_stream_layers_scattered == TINY.num_layers
    assert wrk_s.stream_handoffs == 1 and wrk_s.stream_fallbacks == 0
    assert eng_s.stream_transfers == 1
    # and the monolithic leg never touched it
    assert core_m.disagg_stream_admits == 0
    assert eng_m.stream_transfers == 0
    # decode engine never prefilled on either leg — the KV came over the
    # wire both times
    assert core_s.total_prefill_tokens == 0
    assert core_m.total_prefill_tokens == 0


async def test_layer_stream_recorded_replay(prompt):
    """kv_layer_stream is a first-class wire event: a recorded streamed
    handoff passes the schedule checkers and replays bit-exactly (the
    replayer re-applies each per-layer scatter from the logged values —
    the same arm the multihost follower runs)."""
    from dynamo_tpu.engine.replay import (Recorder, check_log,
                                          compare_replay, replay)
    rt = DistributedRuntime.in_process()
    prefill_core = make_core()
    decode_core = make_core()
    decode_core.recorder = Recorder()
    router = DisaggregatedRouter(rt, "tiny", max_local_prefill_length=0,
                                 conditional=False)
    engine = DisaggEngine(decode_core, rt, router, device_plane=False,
                          layer_stream=True)
    hold_stream_until_admitted(engine, decode_core)
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        got = await collect_tokens(
            await engine.generate(make_request(prompt, rid="rec")))
        assert len(got) == 8
        assert decode_core.disagg_stream_admits == 1
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()

    events = decode_core.recorder.events
    ls = [e for e in events if e["ev"] == "kv_layer_stream"]
    assert sorted(e["layer"] for e in ls) == list(range(TINY.num_layers)), (
        "streamed admit must record one kv_layer_stream event per layer")
    assert all(e["num_layers"] == TINY.num_layers for e in ls)
    assert all(e["rid"] == "rec" and e["targets"] for e in ls)
    assert check_log(events, block_size=ECFG["kv_block_size"]) == []
    rep = replay(decode_core, events)
    assert compare_replay(events, rep) == []


async def test_layer_stream_peer_death_recovers_cold(prompt):
    """Rung 2 of the fallback ladder: the producer dies mid-stream (one
    layer landed, the rest never will) — the decode engine releases the
    half-onboarded slot and re-admits COLD, serving exactly the tokens an
    uncontended local run produces, with no leaked blocks or pins."""
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    from dynamo_tpu.llm.kv.stream import (LayerStreamManifest,
                                          LayerStreamPayload)
    from tests.test_cancellation import assert_pool_baseline

    ref_core = make_core()
    try:
        want = await collect_tokens(await JaxEngine(ref_core).generate(
            make_request(prompt, rid="want")))
    finally:
        await ref_core.stop()
    assert len(want) == 8

    core = make_core()
    try:
        n_blocks = -(-len(prompt) // ECFG["kv_block_size"])
        manifest = LayerStreamManifest(
            request_id="dead", first_token=0, first_logprob=0.0,
            seq_hashes=[1, 2, 3, 4], num_layers=TINY.num_layers,
            shape=[TINY.num_kv_heads, n_blocks, ECFG["kv_block_size"],
                   TINY.head_dim],
            dtype="float32", keys=["k", "v"])
        payload = LayerStreamPayload(manifest)
        req = EngineRequest(rid="dead", prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=8, eos_ids=frozenset(),
                            precomputed=payload)
        await core.submit(req)
        # layer 0 lands and scatters; layer 1 never arrives — peer died
        rng = np.random.default_rng(7)
        payload.put_layer(0, {
            k: rng.standard_normal(manifest.shape).astype(np.float32)
            for k in ("k", "v")})
        for _ in range(100):
            if core.disagg_stream_layers_scattered >= 1:
                break
            await asyncio.sleep(0.02)
        assert core.disagg_stream_admits == 1
        payload.fail("peer died mid-stream")

        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        # the cold recompute reproduces the uncontended run exactly: the
        # producer's first token was never emitted and no sampling key
        # was consumed by the dead stream
        assert toks == want
        assert core.disagg_stream_fallbacks == 1
        assert core.total_prefill_tokens == len(prompt)   # really recomputed
        # wait out the request's own release, then: nothing leaked
        for _ in range(100):
            if all(s is None for s in core.slots):
                break
            await asyncio.sleep(0.02)
        assert_pool_baseline(core)
    finally:
        await core.stop()


@pytest.mark.parametrize("src_q,dst_q", [("none", "int8"),
                                         ("int8", "none")])
async def test_remote_prefill_cross_quant_repack(prompt, src_q, dst_q):
    """Scale-aware repack on the DEVICE plane (round 5, VERDICT r4 item
    4): prefill and decode engines may differ in kv_quantization — the
    decode engine dequantizes/requantizes the payload rows into its own
    pool layout at admission. Accuracy-bounded equality: the stream
    must match an aggregated engine running with the DECODE side's
    quantization (the pool the tokens actually decode from), exactly
    under greedy sampling at this tiny geometry."""
    local_core = make_core(kv_quantization=dst_q)
    try:
        local = JaxEngine(local_core)
        want = await collect_tokens(await local.generate(
            make_request(prompt, rid=f"want-{src_q}-{dst_q}")))
    finally:
        await local_core.stop()
    assert len(want) == 8

    prefill_core = make_core(kv_quantization=src_q)
    decode_core = make_core(kv_quantization=dst_q)
    got, engine, worker = await _disagg_pair_run(
        prefill_core, decode_core, prompt, f"xq-{src_q}-{dst_q}",
        "device")
    try:
        assert decode_core.total_prefill_tokens == 0   # really remote
        assert engine.device_transfers == 1
        # the cross-quant hop quantizes once more than the aggregated
        # reference (src bf16 -> int8 pool, or src int8 -> dequant);
        # at this geometry greedy decoding absorbs it — token-exact.
        # A real deployment gate would bound argmax agreement instead.
        assert got == want
    finally:
        await prefill_core.stop()
        await decode_core.stop()
