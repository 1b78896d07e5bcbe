"""Fleet trace collector (components/trace_collector.py): tree
stitching on propagated span edges, Chrome-trace-event/Perfetto export,
tail-based retention (slow/errored/preempted trees survive), latency
histograms with trace_id exemplars, the event-plane publication path
through the metrics service, and ``llmctl trace dump``."""

import asyncio
import json
import time

import pytest

from dynamo_tpu.components.trace_collector import TraceCollector
from dynamo_tpu.runtime.tracing import Trace

pytestmark = [pytest.mark.asyncio, pytest.mark.tracing]


def _trace_dict(rid, role, trace_id=None, parent=None, total_ms=10.0,
                spans=(), error=None, origin_ts=None):
    t = Trace(rid, role=role, trace_id=trace_id, parent_span=parent,
              origin_ts=origin_ts)
    for name, at_ms, ms in spans:
        t.add_span(name, t.start + at_ms / 1e3, t.start + (at_ms + ms) / 1e3)
    if error:
        t.set_error(error)
    t.finished = t.start + total_ms / 1e3
    return t.to_dict()


# ------------------------------------------------------------- tree stitch


async def test_collector_stitches_parent_child_tree():
    c = TraceCollector()
    front = _trace_dict("r1", "frontend", spans=[("dispatch", 0, 8)])
    tid = front["trace_id"]
    work = _trace_dict("r1", "worker", trace_id=tid,
                       parent=front["span_id"],
                       spans=[("engine.accept", 0, 1),
                              ("first_response", 3, 0), ("respond", 1, 7)],
                       origin_ts=front["origin_ts"])
    peer = _trace_dict("r1", "kv_peer", trace_id=tid,
                       parent=work["span_id"],
                       spans=[("fabric.fetch", 0, 2)],
                       origin_ts=front["origin_ts"])
    # out-of-order arrival must not matter
    for d in (peer, front, work):
        c.feed(d)
    tree = c.tree(tid)
    assert tree["request_id"] == "r1"
    assert tree["n_processes"] == 3
    assert tree["roles"] == ["frontend", "kv_peer", "worker"]
    root = tree["root"]
    assert root["role"] == "frontend" and root["parent_span"] is None
    assert len(root["children"]) == 1
    child = root["children"][0]
    assert child["role"] == "worker"
    assert child["parent_span"] == root["span_id"]
    assert child["children"][0]["role"] == "kv_peer"
    # lookup by request id resolves too (the X-Request-Id join)
    assert c.find("r1") == tid
    assert c.find("nope") is None
    # re-delivery dedupes on span_id
    c.feed(work)
    assert c.tree(tid)["n_processes"] == 3


async def test_collector_orphans_attach_under_root():
    """A member whose parent trace never arrived (lost event) must stay
    visible in the tree, not vanish."""
    c = TraceCollector()
    front = _trace_dict("r2", "frontend")
    orphan = _trace_dict("r2", "prefill", trace_id=front["trace_id"],
                         parent="missing-span",
                         origin_ts=front["origin_ts"])
    c.feed(front)
    c.feed(orphan)
    tree = c.tree(front["trace_id"])
    assert {n["role"] for n in tree["root"]["children"]} == {"prefill"}


# ---------------------------------------------------------------- perfetto


async def test_perfetto_export_is_loadable_chrome_trace_json():
    """Chrome-trace-event shape (the format ui.perfetto.dev and
    chrome://tracing load): traceEvents list, every slice a complete
    event with name/ph/ts/dur/pid/tid, process-name metadata present,
    and child-process slices offset monotonically on the origin
    timeline."""
    c = TraceCollector()
    front = _trace_dict("r3", "frontend", spans=[("dispatch", 0, 5)])
    tid = front["trace_id"]
    work = _trace_dict("r3", "worker", trace_id=tid,
                       parent=front["span_id"],
                       spans=[("respond", 1, 4)],
                       origin_ts=front["origin_ts"])
    c.feed(front)
    c.feed(work)
    out = c.perfetto(tid)
    # valid JSON round-trip (the loadable-shape gate)
    out = json.loads(json.dumps(out))
    assert isinstance(out["traceEvents"], list) and out["traceEvents"]
    slices = [e for e in out["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert metas and all(e["name"] == "process_name" for e in metas)
    for e in slices:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # two processes, stable pid per role
    assert {e["pid"] for e in slices} == {1, 2}
    # span slices carry their names
    names = {e["name"] for e in slices}
    assert "dispatch" in names and "respond" in names
    assert c.perfetto("unknown") is None


# --------------------------------------------------------------- retention


async def test_tail_based_retention_protects_slow_and_errored():
    """Over capacity the boring majority is evicted first; errored and
    slow-tail trees survive, plus an every-Nth baseline sample."""
    c = TraceCollector(keep_trees=10, sample_every=5, slow_fraction=0.05)
    err = _trace_dict("r-err", "worker", error="exploded")
    c.feed(err)
    slow = _trace_dict("r-slow", "frontend", total_ms=10_000.0)
    c.feed(slow)
    for i in range(40):
        c.feed(_trace_dict(f"r-{i}", "frontend", total_ms=5.0))
    assert len(c._trees) <= 10
    assert c.tree(err["trace_id"]) is not None, "errored tree evicted"
    assert c.tree(slow["trace_id"]) is not None, "slow-tail tree evicted"
    assert c.evicted > 0
    # preempted traces are protected the same way
    pre = _trace_dict("r-pre", "worker",
                      spans=[("engine.preempted", 1, 0)])
    c.feed(pre)
    for i in range(40):
        c.feed(_trace_dict(f"r2-{i}", "frontend", total_ms=5.0))
    assert c.tree(pre["trace_id"]) is not None, "preempted tree evicted"
    s = c.stats()
    assert s["received"] == 83 and s["protected"] >= 3


# ------------------------------------------------- histograms + exemplars


async def test_latency_histograms_carry_trace_id_exemplars():
    """TTFT/ITL/queue-wait are HISTOGRAMS (not gauges) and every bucket
    observation carries the trace id as an exemplar — the OpenMetrics
    exposition shows `# {trace_id="..."}` so a Grafana spike clicks
    through to the exact trace."""
    from prometheus_client import CollectorRegistry
    from prometheus_client.openmetrics.exposition import (
        generate_latest as om_latest)

    reg = CollectorRegistry()
    c = TraceCollector(registry=reg)
    d = _trace_dict("r-ex", "worker",
                    spans=[("engine.queue_wait", 0, 2),
                           ("first_response", 30, 0),
                           ("respond", 5, 80)])
    c.feed(d)
    text = om_latest(reg).decode()
    assert "nv_llm_trace_ttft_seconds_bucket" in text
    assert "nv_llm_trace_itl_seconds_bucket" in text
    assert "nv_llm_trace_queue_wait_seconds_bucket" in text
    assert f'trace_id="{d["trace_id"]}"' in text
    # percentile source for the planner reads the same window
    lat = c.latency_percentiles(90.0)
    assert lat["n_traces"] == 1
    assert lat["ttft_p_ms"] == pytest.approx(30.0, abs=1.0)


async def test_slo_latency_percentiles_prefers_collector_with_fallback():
    """Satellite: the planner's SLO input goes fleet-wide — collector
    window preferred, frontend-local ring as the fallback."""
    from dynamo_tpu.llm.slo import latency_percentiles

    c = TraceCollector()
    local = [{"role": "worker", "spans": [
        {"name": "first_response", "at_ms": 111.0, "ms": 0.0}]}]
    # empty collector → local ring wins
    lat = latency_percentiles(collector=c, traces=local)
    assert lat["ttft_p_ms"] == pytest.approx(111.0)
    # fed collector wins over the local ring
    c.feed(_trace_dict("r", "worker", spans=[("first_response", 44, 0)]))
    lat = latency_percentiles(collector=c, traces=local)
    assert lat["ttft_p_ms"] == pytest.approx(44.0, abs=1.0)
    # no collector at all → pure local behavior (the old path)
    lat = latency_percentiles(traces=local)
    assert lat["ttft_p_ms"] == pytest.approx(111.0)


# ------------------------------------------- event plane + metrics service


@pytest.fixture
async def daemon():
    from dynamo_tpu.runtime.server import DiscoveryServer
    srv = DiscoveryServer(host="127.0.0.1")
    await srv.start()
    yield srv
    await srv.close()


async def test_mock_worker_traces_reach_collector_over_event_plane(daemon):
    """Satellite: mock_worker publishes traces (real per-request ones
    from ingress AND synthetic fabricated ones) over trace_events; the
    metrics service's collector assembles them and serves /traces —
    the whole Grafana 'Tracing' feed with zero engines."""
    import aiohttp

    from dynamo_tpu.components.metrics import MetricsAggregatorService
    from dynamo_tpu.components.mock_worker import MockTokenWorker
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
    from dynamo_tpu.runtime.engine import EngineContext

    PATH = "dyn://tracecolns/worker/generate"
    rt_w = await DistributedRuntime.connect(daemon.address)
    rt_m = await DistributedRuntime.connect(daemon.address)
    rt_c = await DistributedRuntime.connect(daemon.address)
    worker = await MockTokenWorker(
        rt_w, PATH, block_size=4,
        synthetic_trace_interval=0.05).start()
    svc = runner = None
    try:
        svc = await MetricsAggregatorService(
            Endpoint.parse_path(rt_m, PATH), scrape_interval=0.1).start()
        client = Endpoint.parse_path(rt_c, PATH).client()
        await client.start()
        await client.wait_for_instances(10)
        # one REAL request → a real worker trace through the publisher
        pre = PreprocessedRequest(
            token_ids=list(range(8)),
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True))
        stream = await client.generate(
            Context(pre, ctx=EngineContext("traced-mock-req")))
        _ = [x async for x in stream]
        for _ in range(100):
            if (svc.collector.received >= 3
                    and svc.collector.find("traced-mock-req")):
                break
            await asyncio.sleep(0.05)
        assert worker.synthetic_traces_emitted >= 1
        # the real request's trace tree arrived
        tid = svc.collector.find("traced-mock-req")
        assert tid is not None
        tree = svc.collector.tree(tid)
        assert "worker" in tree["roles"]
        # synthetic traces fed the histograms (exemplars present)
        text = svc.render_openmetrics().decode()
        assert "nv_llm_trace_ttft_seconds_bucket" in text
        assert "trace_id=" in text
        # /traces + /traces/{id} routes serve the stitched data
        runner = await svc.serve_http("127.0.0.1", 0)
        port = runner.addresses[0][1]
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/traces") as r:
                assert r.status == 200
                listing = await r.json()
            assert listing["traces"] and listing["received"] >= 3
            async with s.get(f"http://127.0.0.1:{port}/traces/{tid}") as r:
                assert r.status == 200
                assert (await r.json())["trace_id"] == tid
            async with s.get(f"http://127.0.0.1:{port}/traces/{tid}"
                             f"?format=perfetto") as r:
                assert r.status == 200
                pf = await r.json()
                assert pf["traceEvents"]
            async with s.get(f"http://127.0.0.1:{port}/traces/zzz") as r:
                assert r.status == 404
            # Accept-negotiated OpenMetrics /metrics carries exemplars
            async with s.get(
                    f"http://127.0.0.1:{port}/metrics",
                    headers={"Accept":
                             "application/openmetrics-text"}) as r:
                body = await r.text()
                assert "# EOF" in body
    finally:
        if runner is not None:
            await runner.cleanup()
        if svc is not None:
            await svc.close()
        await worker.stop()
        for rt in (rt_w, rt_m, rt_c):
            await rt.shutdown()


# ------------------------------------------------------- llmctl trace dump


async def test_llmctl_trace_dump_collects_flight_recorder(daemon, capsys):
    """The on-demand dump protocol: llmctl writes trace/control/{ns},
    the worker-side watch loop answers with its flight-recorder ring
    under its lease, llmctl prints it."""
    import types

    from dynamo_tpu.engine.flight_recorder import (FlightRecorder,
                                                   watch_trace_dump_loop)
    from dynamo_tpu.launch import llmctl
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.connect(daemon.address)
    fr = FlightRecorder(capacity=8)
    fr.record("decode", K=4, batch_fill=2, device_ms=1.5, host_gap_ms=0.4)
    fr.record("prefill", rid="r1", prompt=64, hit_remote=8,
              queue_wait_ms=2.0)
    core = types.SimpleNamespace(flight=fr)
    task = asyncio.get_running_loop().create_task(
        watch_trace_dump_loop(core, rt, "dumptest"))
    try:
        await asyncio.sleep(0.1)        # watcher subscribes
        rc = await llmctl.amain(["--runtime-server", daemon.address,
                                 "trace", "dump", "dumptest"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "decode" in out and "prefill" in out
        assert "loop_lag" in out and "built=" in out
        # a namespace nobody serves times out politely
        rc = await llmctl.amain(["--runtime-server", daemon.address,
                                 "trace", "dump", "nobody",
                                 "--timeout", "0.5"])
        assert rc == 1
    finally:
        task.cancel()
        await rt.shutdown()


async def test_flight_recorder_ring_and_lag_probe():
    """Unit: bounded ring, kind counting, and the loop-lag probe
    measuring a deliberately blocked loop."""
    from dynamo_tpu.engine.flight_recorder import (FlightRecorder,
                                                   all_recorders,
                                                   register_recorder)

    fr = FlightRecorder(capacity=4, lag_probe_interval=0.05)
    for i in range(10):
        fr.record("decode", K=1, i=i)
    assert len(fr.dump()) == 4                    # bounded
    assert fr.dump()[-1]["i"] == 9                # newest kept
    assert fr.dump(last=2)[0]["i"] == 8
    assert fr.records_total == 10
    assert fr.stats()["kinds"] == {"decode": 4}
    name = register_recorder(fr, name="t-rec")
    assert all_recorders()[name] is fr
    # lag probe: block the loop synchronously and the probe sees it
    fr.start_lag_probe()
    fr.start_lag_probe()                          # idempotent
    await asyncio.sleep(0.08)
    time.sleep(0.15)                              # block the event loop
    await asyncio.sleep(0.08)
    assert fr.loop_lag_max_ms >= 50.0
    fr.stop_lag_probe()
