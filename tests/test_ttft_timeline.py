"""The first token's timeline (ISSUE 39): the request's trace starts at the
first byte the connection's protocol saw, its spans tile the way to the
dispatch, the engine writes one ``first_token`` flight record a request whose
four stages tile ``server_ms``, and each benchmark reader of the record
returns what a small hand-made list says."""

import asyncio
import importlib.util
import json
import os
import sys

import aiohttp
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from dynamo_tpu.engine.sampling import SlotSampling
from dynamo_tpu.llm.http import service as http_service
from dynamo_tpu.llm.http import HttpService
from dynamo_tpu.runtime.tracing import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)
STAGES = ("ingest_ms", "queue_wait_ms", "prefill_ms", "first_token_wait_ms")


# ------------------------------------------------------- over a real socket

@pytest.fixture
def stamps(monkeypatch):
    """Every stamp a connection's protocol took: (protocol id, stamp)."""
    taken = []
    plain = http_service._StampingHandler.data_received

    def data_received(self, data):
        pending = self.received_at
        plain(self, data)
        if pending is None:
            taken.append((id(self), self.received_at))

    monkeypatch.setattr(http_service._StampingHandler, "data_received",
                        data_received)
    return taken


def traces_of(rid):
    return [t for t in tracer._recent if t.request_id == rid]


async def echo_service(tiny_model_dir):
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.engines.echo import EchoEngineCore
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import link
    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir,
                                              display_name="tiny")
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model(
        "tiny", link(OpenAIPreprocessor(mdc), Backend(mdc), EchoEngineCore()))
    await svc.start()
    return svc


@pytest.mark.asyncio
async def test_trace_starts_at_the_first_byte_and_tiles_ttft(tiny_model_dir,
                                                             stamps):
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.engines.jax_engine import JaxEngine
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import link
    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir,
                                              display_name="tiny")
    core = EngineCore(ModelConfig.from_model_dir(tiny_model_dir), EngineConfig(
        max_model_len=256, kv_block_size=8, num_kv_blocks=64,
        max_num_seqs=4, prefill_buckets=[32, 64, 128, 256]),
        attn_impl="xla", param_dtype=jnp.float32)
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model(
        "tiny", link(OpenAIPreprocessor(mdc), Backend(mdc), JaxEngine(core)))
    await svc.start()
    body = {"model": "tiny", "stream": True, "max_tokens": 6,
            "temperature": 0.0, "nvext": {"ignore_eos": True},
            "messages": [{"role": "user", "content": "hello world"}]}
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"http://127.0.0.1:{svc.port}"
                              "/v1/chat/completions", json=body) as r:
                assert r.status == 200
                rid = r.headers["X-Request-Id"]
                await r.read()
    finally:
        await svc.stop()
        await core.stop()
    (trace,) = traces_of(rid)
    # the trace starts at the stamp the connection's protocol took, and its
    # wall-clock anchors lie that far back too
    assert [t for _, t in stamps] == [trace.start]
    assert trace.origin_ts == trace.start_epoch
    d = trace.to_dict()
    spans = d["spans"]
    assert [s["name"] for s in spans[:4]] == [
        "http.wire", "http.read_body", "http.validate", "dispatch"]
    assert spans[0]["at_ms"] == 0.0
    for a, b in zip(trace.spans[:3], trace.spans[1:4]):
        assert 0.0 <= b.start - a.end < 1e-4, (a.name, b.name)
    # pydantic's validation runs inside the preprocess span, which the
    # dispatch holds
    by_name = {s["name"]: s for s in spans}
    assert by_name["dispatch"]["at_ms"] <= by_name["preprocess"]["at_ms"]
    records = [r for r in core.flight.dump() if r["kind"] == "first_token"]
    assert [r["rid"] for r in records] == [rid]
    rec = records[0]
    assert rec["chunks"] == 1 and rec["hit"] == 0 and rec["prompt"] > 0
    assert all(rec[s] >= 0.0 for s in STAGES)
    # five values rounded to a microsecond each
    assert abs(rec["server_ms"] - sum(rec[s] for s in STAGES)) < 0.005
    # the same stamps as the trace's spans: the record's last stage is the
    # engine.first_token span, and server_ms ends where that span ends
    first = by_name["engine.first_token"]
    assert rec["first_token_wait_ms"] == pytest.approx(first["ms"], abs=0.02)
    assert rec["server_ms"] == pytest.approx(first["at_ms"] + first["ms"],
                                             abs=0.02)
    assert rec["ingest_ms"] >= (by_name["preprocess"]["at_ms"]
                                + by_name["preprocess"]["ms"] - 0.02)


@pytest.mark.asyncio
async def test_each_request_of_a_kept_alive_connection_gets_its_stamp(
        tiny_model_dir, stamps):
    """Fails loudly if an aiohttp upgrade drops the handler subclass (the
    ``AppRunner._make_server`` seam): no stamp is taken then. A body of
    many segments stamps once, and a GET between two requests leaves no
    stale stamp behind."""
    svc = await echo_service(tiny_model_dir)
    base = f"http://127.0.0.1:{svc.port}"
    body = {"model": "tiny", "max_tokens": 4, "user": "x" * 600_000,
            "messages": [{"role": "user", "content": "a b c"}]}
    rids = []
    try:
        async with aiohttp.ClientSession(
                connector=aiohttp.TCPConnector(limit=1)) as s:
            for _ in range(2):
                async with s.post(f"{base}/v1/chat/completions",
                                  json=body) as r:
                    assert r.status == 200
                    rids.append(r.headers["X-Request-Id"])
                    await r.read()
                async with s.get(f"{base}/health") as r:
                    assert r.status == 200
                await asyncio.sleep(0.05)
    finally:
        await svc.stop()
    assert len({pid for pid, _ in stamps}) == 1     # one connection
    assert len(stamps) == 4                         # one a request
    first, second = (traces_of(rid)[0] for rid in rids)
    assert [first.start, second.start] == [stamps[0][1], stamps[2][1]]
    assert second.start > first.finished
    # http.wire is short on an idle loop: no stamp was left from before
    assert second.spans[0].name == "http.wire" and second.spans[0].ms < 50.0


@pytest.mark.asyncio
@pytest.mark.parametrize("payload,status,said", [
    (b"{nope", 400, "invalid JSON body"),
    (json.dumps({"messages": []}).encode(), 400, "missing 'model'"),
    (json.dumps({"model": "absent", "messages": []}).encode(), 404,
     "not found"),
    (json.dumps({"model": "tiny", "n": 2.9, "messages": []}).encode(), 400,
     "'n' must be an integer"),
    (json.dumps({"model": "tiny", "nvext": {"deadline_ms": "soon"},
                 "messages": []}).encode(), 400, "invalid deadline_ms"),
    (json.dumps({"model": "tiny", "messages": "no list"}).encode(), 400,
     "messages"),
], ids=["json", "model-missing", "model-unknown", "n", "deadline",
        "pydantic"])
async def test_a_refusal_before_the_engine_finishes_its_trace_with_the_error(
        tiny_model_dir, payload, status, said):
    svc = await echo_service(tiny_model_dir)
    before = tracer.completed
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    f"http://127.0.0.1:{svc.port}/v1/chat/completions",
                    data=payload,
                    headers={"Content-Type": "application/json"}) as r:
                assert r.status == status
                message = (await r.json())["error"]["message"]
        gauge = svc.metrics.render().decode()
    finally:
        await svc.stop()
    assert tracer.completed == before + 1
    trace = tracer._recent[-1]
    assert trace.role == "frontend" and trace.finished is not None
    assert said in trace.error and trace.error == message[:512]
    assert [s.name for s in trace.spans[:2]] == ["http.wire",
                                                 "http.read_body"]
    # nothing stays counted in flight
    assert not [line for line in gauge.splitlines()
                if line.startswith("nv_llm_http_service_inflight_requests{")
                and not line.endswith(" 0.0")]


# --------------------------------------------------------- the engine alone

def make_core(num_kv_blocks=64, **kw) -> EngineCore:
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8,
                        num_kv_blocks=num_kv_blocks, max_num_seqs=2,
                        prefill_buckets=[32, 64, 128], **kw)
    return EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)


async def run_req(core, prompt, max_new, rid):
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=max_new, eos_ids=frozenset())
    await core.submit(req)
    n = 0
    while True:
        item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
        if item is FINISH_SENTINEL:
            return n
        n += 1


@pytest.mark.asyncio
@pytest.mark.parametrize("kw,chunks", [
    ({}, 1), ({"prefill_chunk": 32}, 3), ({"ragged_dispatch": True}, 0),
], ids=["one-prefill", "chunked", "lane"])
async def test_a_request_with_no_trace_records_the_engine_stages(kw, chunks):
    rng = np.random.default_rng(5)
    core = make_core(**kw)
    try:
        n = await run_req(core, rng.integers(1, 256, size=70).tolist(), 5,
                          "alone")
    finally:
        await core.stop()
    assert n == 5
    (rec,) = [r for r in core.flight.dump() if r["kind"] == "first_token"]
    assert "server_ms" not in rec and "ingest_ms" not in rec
    assert set(rec) == {"kind", "t", "rid", "prompt", "hit", "chunks",
                        "queue_wait_ms", "prefill_ms",
                        "first_token_wait_ms"}
    assert (rec["rid"], rec["prompt"], rec["chunks"]) == ("alone", 70, chunks)
    assert all(rec[s] >= 0.0 for s in STAGES[1:])
    assert rec["first_token_wait_ms"] > 0.0
    # every prompt token a prefill dispatch took is in some cycle's count
    cycles = [r for r in core.flight.dump() if "admit_tokens" in r]
    assert sum(r["admit_tokens"] for r in cycles) == (70 if chunks else 0)
    assert all(r["admit_tokens"] == 0 for r in cycles if not r["admits"])


@pytest.mark.asyncio
async def test_a_preempted_and_recomputed_request_records_once():
    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    # a pool for either sequence alone at full length, not for both
    core = make_core(num_kv_blocks=16)
    try:
        counts = await asyncio.gather(run_req(core, p1, 40, "a"),
                                      run_req(core, p2, 40, "b"))
    finally:
        await core.stop()
    assert counts == [40, 40] and core.preemptions > 0
    records = core.flight.dump()
    assert sum(r["kind"] == "prefill" for r in records) > 2  # recomputed
    firsts = [r for r in records if r["kind"] == "first_token"]
    assert sorted(r["rid"] for r in firsts) == ["a", "b"]
    assert all(r["prompt"] == 30 for r in firsts)   # as first admitted


# ------------------------------------------------------ the benchmark's readers

def reader(name):
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)       # a reader may import stats
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(bench, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def first_token(server, ingest, queue, prefill, wait):
    return {"kind": "first_token", "t": 0.0, "rid": "r", "prompt": 100,
            "hit": 0, "chunks": 1, "server_ms": server, "ingest_ms": ingest,
            "queue_wait_ms": queue, "prefill_ms": prefill,
            "first_token_wait_ms": wait}


def cycle(ms, admits=0, tokens=0, kind="decode", iters=1):
    return {"kind": kind, "t": 0.0, "device_ms": 1.0, "host_gap_ms": ms - 1.0,
            "admits": admits, "admit_tokens": tokens, "yield_iters": iters}


UNTRACED = {k: v for k, v in first_token(0, 0, 2.0, 8.0, 30.0).items()
            if k not in ("server_ms", "ingest_ms")}
FLIGHT = [
    first_token(60.0, 10.0, 5.0, 11.0, 34.0),
    first_token(100.0, 40.0, 6.0, 12.0, 40.0),      # 2 of it in no stage
    first_token(80.0, 20.0, 7.0, 13.0, 40.0),
    UNTRACED,
    {"kind": "prefill", "t": 0.0, "host_ms": 30.0, "queue_wait_ms": 1.0},
    # nineteen quiet cycles of 16 ms, one that dispatched two prefills of
    # 1,500 tokens in all (40 ms) and the one after it, which waited the
    # prefills out (66 ms) and whose yield drained a new connection's seven
    # iterations; a verify cycle is not a decode cycle
    *[cycle(16.0) for _ in range(19)],
    cycle(40.0, admits=2, tokens=1500), cycle(66.0, iters=7),
    cycle(500.0, kind="verify", iters=16),
]
SPANS = [
    {"request_id": "a", "role": "frontend", "spans": [
        {"name": "http.wire", "ms": 3.0, "at_ms": 0.0},
        {"name": "http.read_body", "ms": 1.0, "at_ms": 3.0}]},
    {"request_id": "b", "role": "frontend", "spans": [
        {"name": "http.wire", "ms": 17.0, "at_ms": 0.0}]},
    {"request_id": "b", "role": "worker", "spans": [
        {"name": "engine.first_token", "ms": 60.0, "at_ms": 20.0}]},
]
CTX = {"flight": FLIGHT, "spans": SPANS,
       "load": {"ttft_ms": [150.0, 210.0, 190.0, 400.0]}}
# a program from before this record, span and counter: nothing to read
PARENT = {"flight": [{"kind": "decode", "t": 0.0, "device_ms": 0.0,
                      "host_gap_ms": 40.0, "admits": 1},
                     {"kind": "prefill", "t": 0.0, "host_ms": 30.0}],
          "spans": [{"request_id": "c", "role": "frontend", "spans": [
              {"name": "preprocess", "ms": 1.0, "at_ms": 0.5}]}],
          "load": {"ttft_ms": [150.0]}}
EMPTY = {"flight": [], "spans": [], "load": {"ttft_ms": []}}


@pytest.mark.parametrize("name,want", [
    ("ttft.server_ms", 80.0),
    ("ttft.ingest_ms", 20.0),
    ("ttft.prefill_ms", 11.5),              # the untraced request's too
    ("ttft.unaccounted_pct", 100.0 * 2.0 / 240.0),
    ("ttft.unseen_ms", 200.0 - 80.0),
    ("http.wire_ms", 10.0),
    ("step.cycle_p95_ms", 40.0),            # the 20th of 21 by rank
    ("step.admit_excess_ms_per_ktok",
     (19 * 16.0 + 40.0 + 66.0 - 21 * 16.0) / 1.5),
    ("loop.yield_iters", (20 * 1 + 7) / 21),    # a mean: the median reads 1
])
def test_benchmark_reader(name, want):
    read = reader(name)
    assert read(CTX) == pytest.approx(want, abs=1e-9)
    assert read(EMPTY) is None
    assert read(PARENT) is None or name == "step.cycle_p95_ms"


def test_readers_of_a_window_without_their_subject():
    # no request had an origin: the engine's stages still read
    ctx = dict(CTX, flight=[UNTRACED])
    assert reader("ttft.prefill_ms")(ctx) == 8.0
    for name in ("ttft.server_ms", "ttft.ingest_ms", "ttft.unaccounted_pct",
                 "ttft.unseen_ms"):
        assert reader(name)(ctx) is None
    # no admission in the window, or no cycle free of one: no cost to read
    cost = reader("step.admit_excess_ms_per_ktok")
    assert cost(dict(CTX, flight=[cycle(16.0), cycle(17.0)])) is None
    assert cost(dict(CTX, flight=[cycle(40.0, 1, 800)])) is None
    # the parent's cycle records read as they always did
    assert reader("step.cycle_p95_ms")(PARENT) == 40.0


PR39_CLOSED = ("qwen15-moe-a2.7b.prefill-closed",
               "qwen15-moe-a2.7b.decode-closed",
               "deepseek-v3.2.docqa-closed",
               "phi4-mini-flash.reasoning-closed",
               "kimi-k2.7-code.repo-closed")


def test_benchmark_lists_every_reader_in_both_groups():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    closed = sorted(cells - {"mistral-7b.chat-open"})
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("ttft.server_ms", "ttft.ingest_ms", "ttft.prefill_ms",
                 "ttft.unaccounted_pct", "ttft.unseen_ms", "http.wire_ms",
                 "step.cycle_p95_ms", "step.admit_excess_ms_per_ktok",
                 "loop.yield_iters"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
        assert entries[f"{name}.open"]["workloads"] == ["mistral-7b.chat-open"]
        assert entries[f"{name}.open"]["moves"] == (
            "itl_p95_ms" if name.startswith("step.") else "ttft_p50_ms")
        listed = entries[f"{name}.closed"]["workloads"]
        assert entries[f"{name}.closed"]["moves"] == "out_tokens_per_s"
        # a span reader stays out of the cells whose requests outlast the
        # window before the profiler (PERF.md §7 (l)); a cell that a later
        # model_config PR adds is not in these lists until a benchmark PR
        # extends them (benchmark/README.md): dots3-note-prev.notes-closed
        if name == "http.wire_ms":
            assert sorted(listed) == [c for c in closed
                                      if c.startswith("qwen15")]
        else:
            assert set(PR39_CLOSED) <= set(listed) <= set(closed)
