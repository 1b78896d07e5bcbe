"""Per-request tracing spans (reference egress/push.rs:134-151): stage
latencies from HTTP ingress through router egress to worker ingress —
now with ON-WIRE context propagation (ISSUE 7): the worker side opens a
CHILD trace of the frontend's via the TraceContext riding the control
message, log lines are sampled at fleet QPS, and finished traces flow
to publication hooks."""

import asyncio
import json

import pytest

from dynamo_tpu.runtime.tracing import (Trace, TraceContext, Tracer,
                                        current_trace, span, tracer,
                                        use_trace)

pytestmark = pytest.mark.asyncio


async def test_trace_spans_and_contextvar():
    t = Trace("req-1", role="test")
    with use_trace(t, finish=False):
        assert current_trace() is t
        with span("a", k=1):
            await asyncio.sleep(0.01)
        with span("b"):
            pass
        t.event("marker")
    assert current_trace() is None
    d = t.to_dict()
    names = [s["name"] for s in d["spans"]]
    assert names == ["a", "b", "marker"]
    assert d["spans"][0]["ms"] >= 10
    assert d["spans"][0]["attrs"] == {"k": 1}


async def test_span_without_trace_is_noop():
    with span("orphan") as s:
        assert s is None


async def test_wire_context_opens_child_trace():
    """The propagation contract: wire_context → from_wire yields a child
    sharing the trace id and origin timestamp, parented on the sender's
    span id; a malformed/absent context falls back to a fresh root."""
    root = Trace("req-x", role="frontend")
    ctx = root.wire_context()
    assert ctx == {"trace_id": root.trace_id, "parent_span": root.span_id,
                   "origin_ts": root.origin_ts}
    child = Trace.from_wire(ctx, "req-x", role="worker")
    assert child.trace_id == root.trace_id
    assert child.parent_span == root.span_id
    assert child.origin_ts == root.origin_ts
    assert child.span_id != root.span_id
    # grandchild chains through the child, not the root
    grand = Trace.from_wire(child.wire_context(), "req-x", role="kv_peer")
    assert grand.trace_id == root.trace_id
    assert grand.parent_span == child.span_id
    # serialization carries the stitch fields + origin offset
    d = child.to_dict()
    assert d["trace_id"] == root.trace_id
    assert d["parent_span"] == root.span_id
    assert d["origin_offset_ms"] >= 0
    # degenerate inputs never fail a request
    assert Trace.from_wire(None, "r").parent_span is None
    assert Trace.from_wire({}, "r").parent_span is None
    assert TraceContext.from_dict({"parent_span": "zz"}) is None


async def test_trace_started_in_the_past_anchors_its_wire_context_there():
    """ISSUE 39: ``start`` (monotonic) is the HTTP front end's stamp of
    the request's first byte; the wall-clock anchors are derived from it,
    so a downstream child's offset counts from the first byte too."""
    import time
    stamp = time.monotonic() - 0.25
    wall = time.time()
    root = Trace("r", role="frontend", start=stamp)
    assert root.start == stamp
    assert root.start_epoch == pytest.approx(wall - 0.25, abs=0.01)
    assert root.origin_ts == root.start_epoch
    root.add_span("http.wire", stamp, stamp + 0.2)
    d = root.to_dict()
    assert d["spans"][0]["at_ms"] == 0.0 and d["total_ms"] >= 250.0
    child = Trace.from_wire(root.wire_context(), "r", role="worker")
    assert child.origin_ts == root.origin_ts
    assert child.to_dict()["origin_offset_ms"] == pytest.approx(250.0, abs=15)
    # with no start a trace begins now, as it always did
    now = Trace("n")
    assert now.start_epoch == pytest.approx(time.time(), abs=0.01)
    assert time.monotonic() - now.start < 0.01


async def test_log_sampling_counts_dropped_lines(caplog):
    """Satellite: at fleet QPS one INFO line per request is log-spam.
    log_every=N logs every Nth; slow/errored traces ALWAYS log; skips
    feed the dropped_log_lines counter behind
    nv_llm_trace_dropped_log_lines_total."""
    import logging
    t = Tracer(keep=16, log_every=3, slow_ms=1000.0)
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.trace"):
        for i in range(6):
            t.finish(Trace(f"s-{i}"))
    lines = [r for r in caplog.records if "trace s-" in r.message]
    assert len(lines) == 2              # every 3rd of 6
    assert t.dropped_log_lines == 4
    # errored traces bypass sampling
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.trace"):
        err = Trace("s-err")
        err.set_error("boom")
        t.finish(err)
    assert any("s-err" in r.message for r in caplog.records)
    assert t.dropped_log_lines == 4     # unchanged
    # a slow trace bypasses sampling too
    caplog.clear()
    slow = Trace("s-slow")
    slow.start -= 2.0                   # fake 2s of latency
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.trace"):
        t.finish(slow)
    assert any("s-slow" in r.message for r in caplog.records)
    # live retune (the --trace-log-every path)
    t.configure(log_every=1)
    assert t.log_every == 1


async def test_finish_hooks_receive_trace_dicts():
    """on_finish hooks are the publication path (TracePublisher); a
    failing hook must not break finish."""
    t = Tracer(keep=4)
    got = []
    t.on_finish.append(got.append)
    t.on_finish.append(lambda d: 1 / 0)      # hostile hook
    tr = Trace("hooked")
    tr.event("mark")
    t.finish(tr)
    assert len(got) == 1 and got[0]["request_id"] == "hooked"
    assert got[0]["spans"][0]["name"] == "mark"


async def test_http_request_produces_trace(tiny_model_dir, aiohttp_client=None):
    """End-to-end over the echo HTTP stack: one chat request leaves a
    frontend trace with dispatch/preprocess/engine markers and total
    latency, visible on /traces."""
    import aiohttp

    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.engines.echo import EchoEngineCore
    from dynamo_tpu.llm.http import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import link

    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir,
                                              display_name="tiny")
    pipe = link(OpenAIPreprocessor(mdc), Backend(mdc), EchoEngineCore())
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model("tiny", pipe)
    await svc.start()
    before = tracer.completed
    try:
        url = f"http://127.0.0.1:{svc.port}"
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{url}/v1/chat/completions", json={
                    "model": "tiny", "max_tokens": 4,
                    "messages": [{"role": "user", "content": "hi"}]}) as r:
                assert r.status == 200
            async with s.get(f"{url}/traces") as r:
                traces = (await r.json())["traces"]
        assert tracer.completed == before + 1
        mine = [t for t in traces if t["role"] == "frontend"][-1]
        names = [sp["name"] for sp in mine["spans"]]
        assert "dispatch" in names and "aggregate" in names
        assert "preprocess" in names        # operator span joined the trace
        assert mine["total_ms"] > 0
        for sp in mine["spans"]:
            assert sp["ms"] >= 0 and sp["at_ms"] >= 0
    finally:
        await svc.stop()


async def test_distributed_roundtrip_traces_both_sides(caplog):
    """Frontend egress span + worker ingress trace under the SAME request
    id across a real served endpoint."""
    import logging

    from dynamo_tpu.components.mock_worker import MockTokenWorker
    from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
    from dynamo_tpu.runtime.engine import EngineContext
    from dynamo_tpu.runtime import Context
    from dynamo_tpu.runtime.server import DiscoveryServer

    PATH = "dyn://tracens/worker/generate"
    srv = DiscoveryServer(host="127.0.0.1")
    await srv.start()
    rt_w = await DistributedRuntime.connect(srv.address)
    rt_c = await DistributedRuntime.connect(srv.address)
    worker = await MockTokenWorker(rt_w, PATH, block_size=4).start()
    try:
        endpoint = Endpoint.parse_path(rt_c, PATH)
        client = endpoint.client()
        await client.start()
        await client.wait_for_instances(10)

        rid = "traced-req-7"
        payload = {"token_ids": [1, 2, 3],
                   "stop_conditions": {"max_tokens": 3, "ignore_eos": True},
                   "sampling_options": {"greedy": True}}
        with caplog.at_level(logging.INFO, logger="dynamo_tpu.trace"):
            with use_trace(Trace(rid, role="frontend")):
                stream = await client.generate(
                    Context(payload, ctx=EngineContext(rid)))
                outs = [x async for x in stream]
            assert outs
            await asyncio.sleep(0.2)    # worker-side trace finishes async

        sides = {t["role"] for t in tracer.find(rid)}
        assert sides == {"frontend", "worker"}
        front = [t for t in tracer.find(rid) if t["role"] == "frontend"][0]
        work = [t for t in tracer.find(rid) if t["role"] == "worker"][0]
        # ISSUE 7 tentpole: the control message carried the TraceContext,
        # so the worker trace is a CHILD of the frontend trace — same
        # trace id, parented on the frontend's span — not a disjoint root
        assert work["trace_id"] == front["trace_id"]
        assert work["parent_span"] == front["span_id"]
        assert work["origin_ts"] == front["origin_ts"]
        assert work["origin_offset_ms"] >= 0
        assert any(s["name"] == "egress" for s in front["spans"])
        wnames = [s["name"] for s in work["spans"]]
        assert {"engine.accept", "dial_back", "respond",
                "first_response"} <= set(wnames)
        # the trace is in the LOGS too (the VERDICT's "visible in logs
        # with stage latencies" gate)
        lines = [r.message for r in caplog.records
                 if rid in r.message and "trace" in r.message]
        assert any("egress=" in ln for ln in lines)
        assert any("respond=" in ln for ln in lines)
    finally:
        await worker.stop()
        await rt_w.shutdown()
        await rt_c.shutdown()
        await srv.close()


async def test_late_events_visible_in_ring_buffer():
    """ADVICE r2: events appended AFTER use_trace exits (by code holding a
    captured Trace reference, e.g. the engine's stream loop) must still
    appear in the ring buffer — traces serialize lazily, and total_ms is
    frozen at finish time."""
    t = Trace("late-req", role="test")
    with use_trace(t):
        t.event("early")
    total_at_finish = t.to_dict()["total_ms"]
    await asyncio.sleep(0.02)
    t.event("late_first_token")
    found = tracer.find("late-req")
    assert found, "finished trace missing from ring buffer"
    names = [s["name"] for s in found[-1]["spans"]]
    assert "early" in names and "late_first_token" in names
    # total_ms does not grow with wall time after finish
    assert found[-1]["total_ms"] == pytest.approx(total_at_finish, abs=1.0)
