"""Mock worker: the zero-hardware routing/metrics test fixture.

Reference: components/metrics/src/bin/mock_worker.rs — a worker publishing
synthetic ForwardPassMetrics and KV events so the router/metrics stack runs
with no GPUs (SURVEY.md §4 "mock worker" tier). Ours additionally *serves*
the token protocol with an echo engine and publishes stored-block events for
every prompt it sees, so a KV-aware router's radix tree fills exactly as it
would against a real engine's prefix cache."""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
from typing import Optional

from ..llm.engines.echo import EchoEngineCore
from ..llm.kv.blocks import TokenBlockSequence
from ..llm.kv_router.protocols import ForwardPassMetrics
from ..llm.kv_router.publisher import KvEventPublisher
from ..llm.protocols.annotated import encode_annotated_json
from ..llm.protocols.common import PreprocessedRequest
from ..runtime.distributed import DistributedRuntime, Endpoint
from ..runtime.engine import AsyncEngine, ManyOut, SingleIn

logger = logging.getLogger("dynamo_tpu.components.mock_worker")

__all__ = ["MockTokenWorker"]


class _EchoWithKvEvents(AsyncEngine):
    """Echo engine that mimics a paged engine's prefix-cache events: each
    prompt's full blocks are published as stored (chained hashes). Tracks
    live in-flight streams so the worker's scraped ForwardPassMetrics show
    real occupancy — the planner's drain-wait and scale signals read it."""

    def __init__(self, publisher: KvEventPublisher, block_size: int,
                 spec_k: int = 0, spec_acceptance: float = 0.75,
                 delay_fn=None):
        # optional per-request service delay (BehaviorProfile slow-start
        # / latency inflation — sim/profiles.py, shared with the fleet
        # simulator's worker model)
        self.delay_fn = delay_fn
        self.inner = EchoEngineCore()
        self.publisher = publisher
        self.block_size = block_size
        self.requests_served = 0
        self.active = 0
        # synthetic speculative-decoding counters: each request "drafts"
        # spec_k tokens and "accepts" the configured fraction, so the
        # nv_llm_spec_* metrics path (engine/spec/ → stats payload →
        # MetricsAggregatorService) is exercisable with zero hardware
        self.spec_k = spec_k
        self.spec_acceptance = spec_acceptance
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        # every (seq_hash, tokens_hash, parent) ever announced, in parent
        # order — replayed by reannounce() after a transient lease expiry
        # (KNOWN_ISSUES kv-router staleness fix)
        self._announced: dict = {}

    def reannounce(self) -> int:
        """Re-publish every stored block (pool-side re-announce hook)."""
        for sh, (bid, th, parent) in self._announced.items():
            self.publisher.publish_stored(bid, sh, th, parent)
        return len(self._announced)

    async def generate(self, request: SingleIn) -> ManyOut:
        pre: PreprocessedRequest = request.data
        self.requests_served += 1
        if self.delay_fn is not None:
            d = self.delay_fn()
            if d > 0:
                await asyncio.sleep(d)
        if self.spec_k > 0:
            self.spec_steps += 1
            self.spec_drafted += self.spec_k
            self.spec_accepted += round(self.spec_k * self.spec_acceptance)
        seq = TokenBlockSequence(self.block_size, pre.token_ids)
        parent = None
        for i, (sh, bh) in enumerate(zip(seq.sequence_hashes,
                                         seq.block_hashes)):
            self.publisher.publish_stored(i, sh, bh, parent)
            self._announced[sh] = (i, bh, parent)
            parent = seq.sequence_hashes[i]
        stream = await self.inner.generate(request)
        self.active += 1

        async def tracked():
            try:
                async for item in stream:
                    yield item
            finally:
                self.active -= 1

        from ..runtime.engine import ResponseStream
        return ResponseStream(tracked(), request.ctx)


class MockTokenWorker:
    """Embeddable fixture: serve a token-protocol endpoint with synthetic
    metrics + KV events."""

    # class-level defaults so partially-constructed fixtures (the
    # __new__-then-assign shape some stats tests use) still have a
    # coherent profile/_stats surface
    block_size = 16
    _started_mono = 0.0

    def __init__(self, runtime: DistributedRuntime, endpoint_path: str,
                 block_size: int = 16,
                 metrics: Optional[ForwardPassMetrics] = None,
                 spec_k: int = 0, spec_acceptance: float = 0.75,
                 publish_traces: bool = True,
                 synthetic_trace_interval: float = 0.0,
                 profile=None, tenants: int = 0):
        self.runtime = runtime
        self.endpoint = Endpoint.parse_path(runtime, endpoint_path)
        self.block_size = block_size
        # synthetic behavior profile (sim/profiles.py — the SAME
        # vocabulary the fleet simulator's worker model runs, so a
        # scenario rehearsed in simulation replays against this live
        # fixture): slow-start/latency inflate service delays,
        # crash-at-T stops the worker cold, drain-ignore makes it deaf
        # to the planner's drain key (the drain-timeout path).
        from ..sim.profiles import BehaviorProfile
        if isinstance(profile, str):
            profile = BehaviorProfile.parse(profile)
        self.profile = profile or BehaviorProfile()
        self._started_mono: float = 0.0
        self._crash_task = None
        self.crashed = False
        self.metrics = metrics or ForwardPassMetrics(
            request_active_slots=0, request_total_slots=8,
            kv_active_blocks=0, kv_total_blocks=1024)
        self.spec_k = spec_k
        self.spec_acceptance = spec_acceptance
        self.engine: Optional[_EchoWithKvEvents] = None
        self.server = None
        # fleet tracing fixture (components/trace_collector.py): served
        # requests already produce REAL worker traces (ingress opens
        # one per request); publish_traces ships them over the
        # trace_events subject like a real worker would, and
        # synthetic_trace_interval > 0 additionally fabricates plausible
        # traces on a timer — collector + Grafana "Tracing" panels are
        # testable with zero engines AND zero traffic
        # synthetic multi-tenant feed (--tenants N): per-tenant
        # admitted/throttled/kv_blocks/hit_rate stats shaped exactly
        # like a tenancy-enabled EngineCore's tenant_stats payload, so
        # the nv_llm_tenant_* labeled-gauge path and the Grafana
        # "Tenants" row run with zero engines
        self.tenants = tenants
        self.publish_traces = publish_traces
        self.synthetic_trace_interval = synthetic_trace_interval
        self._trace_pub = None
        self._synth_task = None
        self.synthetic_traces_emitted = 0

    @property
    def worker_id(self) -> int:
        return self.server.lease_id

    async def start(self) -> "MockTokenWorker":
        component = self.runtime.namespace(
            self.endpoint.namespace).component(self.endpoint.component)
        lease = await self.runtime.primary_lease()

        async def sink(ev) -> None:
            await component.publish_event("kv_events", ev)

        publisher = KvEventPublisher(worker_id=lease.id, sink=sink)
        import time as _time
        self._started_mono = _time.monotonic()

        def _delay() -> float:
            return self.profile.service_delay_s(
                _time.monotonic() - self._started_mono)

        self.engine = _EchoWithKvEvents(publisher, self.block_size,
                                        spec_k=self.spec_k,
                                        spec_acceptance=self.spec_acceptance,
                                        delay_fn=_delay)
        # transient lease reclaim (daemon blip) → replay the radix index
        # for this worker (KNOWN_ISSUES kv-router staleness fix)
        prev = getattr(self.runtime.store, "on_lease_reclaimed", None)

        def reclaimed(lease_id: int) -> None:
            if prev is not None:
                prev(lease_id)
            if lease_id == lease.id and self.engine is not None:
                n = self.engine.reannounce()
                logger.info("mock worker %x re-announced %d blocks after "
                            "lease reclaim", lease_id, n)

        if hasattr(self.runtime.store, "on_lease_reclaimed"):
            self.runtime.store.on_lease_reclaimed = reclaimed
        self.server = await self.endpoint.serve(
            self.engine,
            decode_req=lambda raw: PreprocessedRequest.from_dict(
                json.loads(raw)),
            encode_resp=encode_annotated_json,
            stats_handler=self._stats,
            stats_interval=0.2)
        if self.publish_traces:
            from .trace_collector import wire_trace_publisher
            self._trace_pub = wire_trace_publisher(component)
        if self.synthetic_trace_interval > 0:
            self._synth_task = asyncio.get_running_loop().create_task(
                self._synthetic_trace_loop(), name="mock-synth-traces")
        if self.profile.drain_ignore:
            # deaf to the planner's drain key: kill the server's drain
            # watch so only the planner's drain-timeout path can retire
            # this worker
            if self.server._drain_task is not None:
                self.server._drain_task.cancel()
                self.server._drain_task = None
        if self.profile.crash_at_s > 0:
            self._crash_task = asyncio.get_running_loop().create_task(
                self._crash_after(self.profile.crash_at_s),
                name="mock-crash-at")
        return self

    async def _crash_after(self, delay_s: float) -> None:
        await asyncio.sleep(delay_s)
        self.crashed = True
        self._crash_task = None     # don't self-cancel inside stop()
        logger.info("mock worker %x crashing (profile crash-at:%g)",
                    self.worker_id, delay_s)
        await self.stop()

    async def _synthetic_trace_loop(self) -> None:
        """Fabricate plausible finished worker traces on a timer — they
        flow through the REAL tracer (ring, sampling, publisher), so the
        whole collector/histogram/Grafana path exercises without any
        traffic at all."""
        import random
        import time as _time

        from ..runtime.tracing import Trace, tracer
        while True:
            await asyncio.sleep(self.synthetic_trace_interval)
            self.synthetic_traces_emitted += 1
            t = Trace(f"synthetic-{self.worker_id:x}-"
                      f"{self.synthetic_traces_emitted}", role="worker")
            now = _time.monotonic()
            queue_ms = random.uniform(0.1, 3.0)
            ttft_ms = queue_ms + random.uniform(5.0, 60.0)
            total_ms = ttft_ms + random.uniform(20.0, 400.0)
            t.start = now - total_ms / 1e3
            t.start_epoch = _time.time() - total_ms / 1e3
            t.origin_ts = t.start_epoch
            t.add_span("engine.queue_wait", t.start,
                       t.start + queue_ms / 1e3)
            t.add_span("engine.accept", t.start, t.start + 1e-3)
            first = t.start + ttft_ms / 1e3
            t.add_span("first_response", first, first)
            t.add_span("respond", t.start + 2e-3, now,
                       synthetic=True)
            tracer.finish(t)

    def _stats(self) -> dict:
        """Base synthetic metrics overlaid with LIVE occupancy, so the
        planner's signals (queue depth, slot pressure, drain-idle) are
        real even against the echo engine."""
        d = self.metrics.to_dict()
        # server._inflight outlives engine.active by the response tail
        # (sentinel + finish), so a drain-wait on these stats can't retire
        # the worker with a stream mid-delivery
        live = max(self.engine.active,
                   len(self.server._inflight) if self.server else 0)
        d["request_active_slots"] = (self.metrics.request_active_slots
                                     + live)
        eng = self.engine
        if eng is not None and eng.spec_drafted > 0:
            # live synthetic speculation counters (see _EchoWithKvEvents)
            # — shaped exactly like a real EngineCore.metrics() payload
            d["spec_drafted_total"] = eng.spec_drafted
            d["spec_accepted_total"] = eng.spec_accepted
            # every draft not accepted is a scored row rolled back
            d["spec_rewound_rows_total"] = (eng.spec_drafted
                                            - eng.spec_accepted)
            d["spec_acceptance_rate"] = eng.spec_accepted / eng.spec_drafted
            d["spec_accepted_per_step"] = (eng.spec_accepted
                                           / max(eng.spec_steps, 1))
        if eng is not None and not d.get("kv_contiguity_ratio"):
            # synthetic KV-layout gauges (docs/kv_layout.md): a healthy
            # contiguous pool — one free run, every alloc one run, two
            # DMA copies per wave (k + v) — so the nv_llm_kv_frag_* /
            # _attn_dma_* scrape path runs with zero hardware
            d["kv_frag_ratio"] = 0.0
            d["kv_contig_runs"] = 1
            d["kv_contiguity_ratio"] = 1.0
            d["attn_dma_copies_per_wave"] = 2.0
        if eng is not None and not d.get("ragged_fill_ratio"):
            # synthetic ragged-dispatch gauges (docs/ragged_attention.md):
            # a healthy unified-dispatch engine — ~70% token fill, a
            # third of dispatches mixing prefill chunks into the decode
            # batch, saved dispatches growing with served requests — so
            # the nv_llm_ragged_* scrape path and the Grafana "Ragged
            # dispatch" panels run with zero hardware
            d["ragged_fill_ratio"] = 0.7
            d["ragged_mixed_ratio"] = 0.33
            d["ragged_dispatches_saved_total"] = eng.requests_served
            # round 11: a healthy prefetch chain (most first waves
            # covered by a predecessor) and spec draft rows riding the
            # ragged batch, growing with traffic
            d["ragged_prefetch_hit_ratio"] = 0.8
            d["ragged_spec_rows_total"] = 3 * eng.requests_served
        if eng is not None and not d.get("remote_link_gbps"):
            # synthetic KV-fabric gauges (docs/kv_fabric.md): a healthy
            # fabric — some object-tier residency, a ~10 GB/s / 1 ms
            # measured link, zero failures — so the nv_llm_kv_remote_*
            # scrape path and the router's NetKV scoring inputs
            # (kv_bytes_per_block / prefill_tok_per_s) are exercisable
            # with zero hardware
            d["remote_used_blocks"] = eng.requests_served
            d["remote_peer_blocks"] = 4 * eng.requests_served
            d["remote_hit_rate"] = 0.5
            d["remote_link_gbps"] = 10.0
            d["remote_link_rtt_s"] = 1e-3
            d["kv_bytes_per_block"] = 1 << 20
            d["kv_block_size"] = self.block_size
            # a stateful model's per-slot group: 4 slots of 2 MiB
            d["kv_state_bytes"] = 4 << 21
            d["prefill_tok_per_s"] = 5e4
            # round 12: a healthy native dataplane (every fetch rides
            # it, zero JSON fallbacks) and a prefill-publish worker
            # steadily feeding the object tier
            d["remote_dataplane_fetches_total"] = 2 * eng.requests_served
            d["remote_dataplane_fallbacks_total"] = 0
            d["prefill_published_blocks_total"] = 3 * eng.requests_served
        if eng is not None and not d.get("requests_cancelled_total"):
            # round 13: synthetic graceful-degradation counters
            # (docs/chaos.md) — a lightly-chaotic fleet: a few cancels
            # and deadline misses growing with traffic, one tripped peer
            # that recovered (trips > open), a handful of shed spill
            # writes — so the nv_llm_requests_cancelled_total /
            # nv_llm_kv_remote_breaker_* / nv_llm_kv_disk_spill_shed_*
            # scrape path and the Grafana "Degradation" row run with
            # zero engines
            d["requests_cancelled_total"] = max(eng.requests_served // 4,
                                                1)
            d["requests_deadline_exceeded_total"] = \
                eng.requests_served // 8
            d["netstore_deadline_exceeded_total"] = 0
            d["remote_breaker_open_peers"] = 0
            d["remote_breaker_trips_total"] = 1
            d["disk_spill_shed_total"] = eng.requests_served // 6
        if eng is not None and not d.get("disk_capacity_blocks"):
            # synthetic tier-ladder + worker-health gauges: a healthy
            # host/disk ladder (steady stores, warm hit rates, no
            # dropped jobs), a quiet loop-lag probe, and cost-model
            # inputs — every remaining gauge-table field fed so the
            # zero-TPU fixture lights EVERY Grafana panel (the DL010
            # closure: a field the mock can't feed is a panel no
            # no-hardware test can ever prove works)
            served = eng.requests_served
            # base + live, as request_active_slots above: a test that
            # sets queue pressure on self.metrics must reach the planner
            d["num_requests_waiting"] = (self.metrics.num_requests_waiting
                                         + max(live - 4, 0))
            d["gpu_cache_usage_perc"] = min(0.1 + 0.01 * live, 0.9)
            d["gpu_prefix_cache_hit_rate"] = 0.45
            d["host_stored_total"] = 2 * served
            d["host_evicted_total"] = served // 2
            d["host_hit_rate"] = 0.55
            d["offload_dropped_jobs_total"] = 0
            d["disk_used_blocks"] = served
            d["disk_capacity_blocks"] = 4096
            d["disk_stored_total"] = served
            d["disk_evicted_total"] = served // 4
            d["disk_hit_rate"] = 0.35
            d["disk_bytes_used"] = served * (1 << 20)
            d["disk_spill_dropped_total"] = 0
            d["remote_capacity_blocks"] = 1 << 16
            d["remote_stored_total"] = 3 * served
            d["remote_fetch_failures_total"] = 0
            d["remote_admission_rejects_total"] = served // 10
            d["kv_defrag_moves_total"] = served // 8
            # a mildly-interleaved pipeline profile (pp=2, K=4 →
            # utilization K·pp/(K·pp+pp-1) = 8/9)
            d["pp_stages"] = 2
            d["pp_microbatch"] = 4
            d["pp_utilization"] = 8 / 9
            d["pp_bubble_fraction"] = 1 / 9
            d["trace_dropped_log_lines_total"] = served // 3
            d["loop_lag_ms"] = 0.4
            d["loop_lag_max_ms"] = 2.5
            # a warm start: 140 programs read from the cache in 48 s
            d["programs_built_total"] = 140
            d["program_build_seconds_total"] = 48.0
            d["netstore_retries_total"] = 0
        if eng is not None and not d.get("disagg_stream_layers_total"):
            # round 15: synthetic streaming-handoff gauges (docs/
            # kv_fabric.md "Streaming handoff") — a healthy plane: a
            # 32-layer measured pipeline depth, layers growing with
            # traffic, the occasional degraded stream, transfer mostly
            # hidden — so the nv_llm_disagg_stream_* scrape path and
            # the Grafana "Disagg streaming" panels run with zero
            # hardware
            d["disagg_stream_layers_total"] = 32 * eng.requests_served
            d["disagg_stream_fallbacks_total"] = eng.requests_served // 16
            d["disagg_stream_overlap_ratio"] = 0.85
            d["disagg_stream_layers"] = 32
        tenants = getattr(self, "tenants", 0)
        if eng is not None and tenants > 0:
            # round 14: synthetic per-tenant stats — a Zipf-ish spread
            # where tenant 0 floods (and is the only one throttled),
            # everyone else's hit rate holds (the fair-share story the
            # Grafana "Tenants" row should show)
            served = max(eng.requests_served, 1)
            d["tenant_stats"] = {
                f"t{i:02d}": {
                    "admitted": max(served // (i + 1), 1),
                    "throttled": served // 2 if i == 0 else 0,
                    "kv_blocks": 64 // (i + 1),
                    "hit_rate": 0.3 if i == 0 else 0.6,
                } for i in range(tenants)}
        profile = getattr(self, "profile", None)
        if profile is not None and (profile.slow_start_s > 0
                                    or profile.latency_factor != 1.0):
            # young/slow worker: the published prefill rate tracks the
            # profile's speed factor, so the router's NetKV recompute
            # model and the planner's crossover stats see the ramp
            import time as _time
            f = profile.speed_factor(
                _time.monotonic() - self._started_mono)
            if d.get("prefill_tok_per_s"):
                d["prefill_tok_per_s"] = d["prefill_tok_per_s"] * f
        return d

    @property
    def draining(self) -> bool:
        return self.server is not None and self.server.draining

    async def drain(self) -> None:
        await self.server.set_draining(True)

    async def stop(self) -> None:
        if self._crash_task is not None:
            self._crash_task.cancel()
            self._crash_task = None
        if self._synth_task is not None:
            self._synth_task.cancel()
            self._synth_task = None
        if self._trace_pub is not None:
            # detach from the process tracer (it is a singleton; a
            # dangling hook would publish other fixtures' traces)
            self._trace_pub.close()
            self._trace_pub = None
        if self.server is not None:
            await self.server.stop()


async def amain(argv=None) -> None:
    p = argparse.ArgumentParser(prog="dynamo-tpu-mock-worker")
    p.add_argument("--runtime-server", required=True)
    p.add_argument("--endpoint", default="dyn://dynamo/worker/generate")
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--spec-k", type=int, default=0,
                   help="synthetic speculation: drafts per request "
                        "(exercises the nv_llm_spec_* metrics path)")
    p.add_argument("--spec-acceptance", type=float, default=0.75)
    p.add_argument("--synthetic-trace-interval", type=float, default=0.0,
                   help="emit a fabricated worker trace every N seconds "
                        "(exercises the trace collector + Grafana "
                        "'Tracing' row with zero traffic)")
    p.add_argument("--profile", default="",
                   help="synthetic behavior profile (sim/profiles.py), "
                        "e.g. 'slow-start:30', 'crash-at:120', "
                        "'drain-ignore', 'latency:2.5' — comma-joined")
    p.add_argument("--tenants", type=int, default=0,
                   help="publish synthetic per-tenant stats for N "
                        "tenants (exercises the nv_llm_tenant_* "
                        "labeled gauges + Grafana 'Tenants' row with "
                        "zero engines)")
    args = p.parse_args(argv)
    from ..runtime.log import setup_logging
    setup_logging()
    runtime = await DistributedRuntime.connect(args.runtime_server)
    worker = await MockTokenWorker(
        runtime, args.endpoint, block_size=args.kv_block_size,
        spec_k=args.spec_k, spec_acceptance=args.spec_acceptance,
        synthetic_trace_interval=args.synthetic_trace_interval,
        profile=args.profile, tenants=args.tenants).start()
    logger.info("mock worker %x serving %s", worker.worker_id, args.endpoint)
    try:
        await asyncio.Event().wait()
    finally:
        await worker.stop()
        await runtime.shutdown()


def main() -> None:
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
