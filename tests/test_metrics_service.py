"""Metrics aggregation service against mock workers (zero hardware).

Reference: components/metrics (main.rs:26-210) + its mock_worker fixture —
scraped ForwardPassMetrics become per-worker Prometheus gauges, router
KV-hit-rate events become counters, and dead workers' series are dropped.
"""

import asyncio

import pytest

from dynamo_tpu.components.metrics import MetricsAggregatorService
from dynamo_tpu.components.mock_worker import MockTokenWorker
from dynamo_tpu.llm.engines.kv_routed import KvRoutedEngine
from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.runtime import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
from dynamo_tpu.runtime.engine import EngineContext
from dynamo_tpu.runtime.server import DiscoveryServer

pytestmark = pytest.mark.asyncio

PATH = "dyn://metricsns/worker/generate"


@pytest.fixture
async def daemon():
    srv = DiscoveryServer(host="127.0.0.1")
    await srv.start()
    yield srv
    await srv.close()


async def test_aggregator_scrapes_and_counts_hit_rate(daemon):
    addr = daemon.address
    rt_w = await DistributedRuntime.connect(addr)
    rt_router = await DistributedRuntime.connect(addr)
    rt_metrics = await DistributedRuntime.connect(addr)
    metrics = ForwardPassMetrics(request_active_slots=2,
                                 request_total_slots=8,
                                 kv_active_blocks=5, kv_total_blocks=64)
    worker = await MockTokenWorker(rt_w, PATH, block_size=4,
                                   metrics=metrics).start()
    engine = svc = None
    try:
        svc = await MetricsAggregatorService(
            Endpoint.parse_path(rt_metrics, PATH),
            scrape_interval=0.1).start()
        engine = await KvRoutedEngine.start(
            Endpoint.parse_path(rt_router, PATH), block_size=4,
            scrape_interval=0.1)
        await engine.client.wait_for_instances(15)

        # wait for a scrape to land
        for _ in range(100):
            if worker.worker_id in svc.latest:
                break
            await asyncio.sleep(0.05)
        assert svc.latest[worker.worker_id].kv_active_blocks == 5
        text = svc.render().decode()
        wid_hex = f"{worker.worker_id:x}"
        assert (f'nv_llm_kv_kv_active_blocks{{component="worker",'
                f'endpoint="generate",worker_id="{wid_hex}"}} 5.0') in text
        assert 'nv_llm_kv_request_total_slots' in text

        # a routed request emits a KVHitRateEvent → counter increments
        for _ in range(100):
            if engine.router.schedule([1, 2, 3, 4]) is not None:
                break
            await asyncio.sleep(0.05)
        pre = PreprocessedRequest(
            token_ids=list(range(10, 22)),
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True))
        stream = await engine.generate(
            Context(pre, ctx=EngineContext("r1")))
        _ = [a async for a in stream]
        for _ in range(100):
            if svc.events_received >= 1:
                break
            await asyncio.sleep(0.05)
        assert svc.events_received >= 1
        text = svc.render().decode()
        assert "nv_llm_kv_hit_rate_isl_blocks_total" in text

        # worker dies → its gauge series disappears on the next scrape
        await worker.stop()
        for _ in range(100):
            if worker.worker_id not in svc.latest:
                break
            await asyncio.sleep(0.05)
        assert worker.worker_id not in svc.latest
        text = svc.render().decode()
        assert f'worker_id="{wid_hex}"}} 5.0' not in text
    finally:
        if engine is not None:
            await engine.close()
        if svc is not None:
            await svc.close()
        for rt in (rt_w, rt_router, rt_metrics):
            await rt.shutdown()


async def test_http_exposition(daemon):
    import aiohttp
    addr = daemon.address
    rt_w = await DistributedRuntime.connect(addr)
    rt_metrics = await DistributedRuntime.connect(addr)
    worker = await MockTokenWorker(rt_w, PATH, block_size=4).start()
    svc = runner = None
    try:
        svc = await MetricsAggregatorService(
            Endpoint.parse_path(rt_metrics, PATH),
            scrape_interval=0.1).start()
        runner = await svc.serve_http("127.0.0.1", 0)
        port = runner.addresses[0][1] if runner.addresses else \
            runner.sites[0]._server.sockets[0].getsockname()[1]
        for _ in range(100):
            if worker.worker_id in svc.latest:
                break
            await asyncio.sleep(0.05)
        async with aiohttp.ClientSession() as sess:
            async with sess.get(f"http://127.0.0.1:{port}/metrics") as resp:
                assert resp.status == 200
                body = await resp.text()
        assert "nv_llm_kv_kv_total_blocks" in body
        # fleet-tracing observability rides the same scrape: the
        # log-sampling drop counter and the engine loop-lag probe
        # (per-worker gauges), plus the collector's latency histograms
        assert "nv_llm_trace_dropped_log_lines_total" in body
        assert "nv_llm_engine_loop_lag_ms" in body
        # ... and the build log's totals (a rise while serving: a recompile)
        assert "nv_llm_engine_programs_built_total" in body
        assert "nv_llm_engine_program_build_seconds_total" in body
        assert "nv_llm_trace_ttft_seconds" in body
    finally:
        if runner is not None:
            await runner.cleanup()
        if svc is not None:
            await svc.close()
        await worker.stop()
        for rt in (rt_w, rt_metrics):
            await rt.shutdown()


async def test_push_mode_to_fake_gateway(daemon):
    """Push collection (reference MetricsMode::Push,
    components/metrics/src/lib.rs:104-296): the aggregator periodically
    PUTs its registry to a PushGateway; a fake gateway captures the body."""
    from aiohttp import web

    received = []

    async def capture(request):
        received.append((request.method, request.path,
                         await request.read()))
        return web.Response(status=200)

    app = web.Application()
    app.router.add_route("*", "/metrics/job/{job}", capture)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    gw_port = runner.addresses[0][1]

    addr = daemon.address
    rt_w = await DistributedRuntime.connect(addr)
    rt_metrics = await DistributedRuntime.connect(addr)
    worker = await MockTokenWorker(rt_w, PATH, block_size=4).start()
    svc = None
    try:
        svc = await MetricsAggregatorService(
            Endpoint.parse_path(rt_metrics, PATH),
            scrape_interval=0.1).start()
        await svc.serve_push(f"127.0.0.1:{gw_port}", job="testjob",
                             interval=0.1)
        for _ in range(100):
            if svc.pushes >= 2 and worker.worker_id in svc.latest:
                break
            await asyncio.sleep(0.05)
        assert svc.pushes >= 2, "no pushes reached the fake gateway"
        assert received, "gateway captured nothing"
        method, path, body = received[-1]
        assert path == "/metrics/job/testjob"
        assert b"nv_llm_kv_kv_total_blocks" in body
    finally:
        if svc is not None:
            await svc.close()
        await worker.stop()
        for rt in (rt_w, rt_metrics):
            await rt.shutdown()
        await runner.cleanup()

async def test_tenant_labeled_gauges_from_mock_worker(daemon):
    """ISSUE 14 satellite: mock_worker --tenants publishes synthetic
    per-tenant stats; the aggregator exports one nv_llm_tenant_* series
    per (worker, tenant) and prunes them with the worker."""
    addr = daemon.address
    rt_w = await DistributedRuntime.connect(addr)
    rt_metrics = await DistributedRuntime.connect(addr)
    worker = await MockTokenWorker(rt_w, PATH, block_size=4,
                                   tenants=3).start()
    svc = None
    try:
        svc = await MetricsAggregatorService(
            Endpoint.parse_path(rt_metrics, PATH),
            scrape_interval=0.1).start()
        for _ in range(100):
            if worker.worker_id in svc.latest:
                break
            await asyncio.sleep(0.05)
        m = svc.latest[worker.worker_id]
        assert set(m.tenant_stats) == {"t00", "t01", "t02"}
        # the synthetic story: t00 floods (throttled), others hold
        assert m.tenant_stats["t00"]["throttled"] >= 0
        assert m.tenant_stats["t01"]["hit_rate"] == 0.6
        text = svc.render().decode()
        wid_hex = f"{worker.worker_id:x}"
        assert (f'nv_llm_tenant_hit_rate{{component="worker",'
                f'endpoint="generate",tenant="t01",'
                f'worker_id="{wid_hex}"}} 0.6') in text
        assert 'nv_llm_tenant_admitted_total' in text
        assert 'nv_llm_tenant_kv_blocks' in text
        # worker death prunes every tenant series
        await worker.stop()
        for _ in range(100):
            if worker.worker_id not in svc.latest:
                break
            await asyncio.sleep(0.05)
        text = svc.render().decode()
        assert f'tenant="t01",worker_id="{wid_hex}"' not in text
    finally:
        if svc is not None:
            await svc.close()
        for rt in (rt_w, rt_metrics):
            await rt.shutdown()
