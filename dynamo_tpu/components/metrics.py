"""Standalone metrics aggregation service.

Reference: components/metrics (components/metrics/src/main.rs:26-210,
src/lib.rs) — a service that (a) subscribes the routers' KV-hit-rate event
subject, (b) scrapes every worker instance's ForwardPassMetrics stats, and
(c) exposes the merged picture as Prometheus text for Grafana/alerting
(deploy/metrics/{grafana.json,prometheus.yml}). Runs with zero TPUs against
the mock worker (SURVEY.md §4's no-GPU fixture).

Usage (module CLI)::

    python -m dynamo_tpu.components.metrics dyn://ns/component/endpoint \
        --daemon 127.0.0.1:5600 --port 9091
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Dict, Optional, Set

from prometheus_client import (CollectorRegistry, Counter, Gauge,
                               generate_latest)

from ..llm.kv_router.protocols import (KV_HIT_RATE_SUBJECT,
                                       ForwardPassMetrics)
from ..runtime.distributed import DistributedRuntime, Endpoint

logger = logging.getLogger("dynamo_tpu.components.metrics")

PREFIX = "nv_llm_kv"

_GAUGE_FIELDS = (
    "request_active_slots", "request_total_slots", "kv_active_blocks",
    "kv_total_blocks", "num_requests_waiting", "gpu_cache_usage_perc",
    "gpu_prefix_cache_hit_rate",
)

# speculative decoding (engine/spec/): ForwardPassMetrics field →
# exported metric name (the nv_llm_spec_* family the planner and the
# Grafana speculation panel scrape)
_SPEC_GAUGES = {
    "spec_acceptance_rate": "nv_llm_spec_acceptance_rate",
    "spec_accepted_per_step": "nv_llm_spec_accepted_per_step",
    "spec_drafted_total": "nv_llm_spec_drafted_tokens",
    "spec_accepted_total": "nv_llm_spec_accepted_tokens",
    "spec_rewound_rows_total": "nv_llm_spec_rewound_rows_total",
}

# contiguity-aware KV layout (llm/kv/pool.py run-tracking allocator +
# engine/attention.py run-coalesced DMA; docs/kv_layout.md):
# ForwardPassMetrics field → exported metric name. The Grafana "KV
# layout" row plots frag_ratio against dma-copies-per-wave so a
# fragmenting pool (rising copies, coalescing losing its DMA win) is
# visible before it costs step time; defrag_moves_total confirms the
# compaction pass is actually reclaiming contiguity.
_LAYOUT_GAUGES = {
    "kv_frag_ratio": "nv_llm_kv_frag_ratio",
    "kv_contig_runs": "nv_llm_kv_contig_runs",
    "kv_contiguity_ratio": "nv_llm_kv_contiguity_ratio",
    "kv_defrag_moves_total": "nv_llm_kv_defrag_moves_total",
    "attn_dma_copies_per_wave": "nv_llm_kv_attn_dma_copies_per_wave",
}

# pipeline parallelism (parallel/pipeline_parallel.py):
# ForwardPassMetrics field → exported metric name. Stage count and
# microbatch slots are topology facts; utilization/bubble are the
# dispatch-level interleave model (K·pp/(K·pp+pp-1) and complement) —
# the Grafana "Pipeline" row plots them so a misconfigured K (deep
# bubble) is visible at a glance.
_PP_GAUGES = {
    "pp_stages": "nv_llm_pp_stages",
    "pp_microbatch": "nv_llm_pp_microbatch_slots",
    "pp_utilization": "nv_llm_pp_steady_state_utilization",
    "pp_bubble_fraction": "nv_llm_pp_bubble_fraction",
}

# KV tier ladder (host DRAM tier + persistent disk G3 tier):
# ForwardPassMetrics field → exported metric name. The host counters
# were previously module-local only (llm/kv/offload.py stats); now they
# ride the same scrape as everything else, next to the disk gauges and
# the two backpressure drop counters (offload write-back queue + disk
# spill queue) the Grafana "KV tiers" row alerts on.
_TIER_GAUGES = {
    "host_stored_total": "nv_llm_kv_host_stored_blocks_total",
    "host_evicted_total": "nv_llm_kv_host_evicted_blocks_total",
    "host_hit_rate": "nv_llm_kv_host_hit_rate",
    "offload_dropped_jobs_total": "nv_llm_kv_host_offload_dropped_jobs_total",
    "disk_used_blocks": "nv_llm_kv_disk_used_blocks",
    "disk_capacity_blocks": "nv_llm_kv_disk_capacity_blocks",
    "disk_stored_total": "nv_llm_kv_disk_stored_blocks_total",
    "disk_evicted_total": "nv_llm_kv_disk_evicted_blocks_total",
    "disk_hit_rate": "nv_llm_kv_disk_hit_rate",
    "disk_bytes_used": "nv_llm_kv_disk_bytes_used",
    "disk_spill_dropped_total": "nv_llm_kv_disk_spill_dropped_jobs_total",
}

# unified ragged dispatch (engine/ragged.py + docs/ragged_attention.md):
# ForwardPassMetrics field → exported metric name. The Grafana "Ragged
# dispatch" panel plots fill ratio (how close each unified dispatch
# runs to its compiled token capacity — LOW fill under load means the
# capacity is oversized or admissions are starving) next to the
# mixed-batch ratio (prefill chunks actually riding decode dispatches —
# the batch-boundary bubbles being eliminated) and the cumulative
# split-path dispatches the packing saved. Round 11 adds the
# cross-sequence wave-prefetch hit ratio (first waves a predecessor's
# last wave already covered — LOW under load means dispatches carry too
# few concurrent spans to chain) and the cumulative draft rows that
# rode ragged dispatches as speculative spans.
_RAGGED_GAUGES = {
    "ragged_fill_ratio": "nv_llm_ragged_fill_ratio",
    "ragged_mixed_ratio": "nv_llm_ragged_mixed_batch_ratio",
    "ragged_dispatches_saved_total": "nv_llm_ragged_dispatches_saved_total",
    "ragged_prefetch_hit_ratio": "nv_llm_ragged_prefetch_hit_ratio",
    "ragged_spec_rows_total": "nv_llm_ragged_spec_rows_total",
}

# fleet tracing + engine flight recorder (runtime/tracing.py sampling
# counter + engine/flight_recorder.py loop-lag probe): dropped log
# lines rise by design when sampling is on; loop lag rising means the
# ENGINE loop is being blocked (sync I/O, long host glue) — the most
# actionable single gauge on a slow worker. The latency HISTOGRAMS
# (TTFT/ITL/queue-wait with trace_id exemplars) live on the trace
# collector, not here — they are fed per trace, not per scrape.
_TRACE_GAUGES = {
    "trace_dropped_log_lines_total": "nv_llm_trace_dropped_log_lines_total",
    "loop_lag_ms": "nv_llm_engine_loop_lag_ms",
    "loop_lag_max_ms": "nv_llm_engine_loop_lag_max_ms",
    # the build log's totals: programs_built_total rising on a worker that
    # is serving means a step recompiled, and the stall is the rise of the
    # second
    "programs_built_total": "nv_llm_engine_programs_built_total",
    "program_build_seconds_total":
        "nv_llm_engine_program_build_seconds_total",
}

# remote (G4) fleet KV fabric (llm/kv/remotestore.py + fabric.py):
# ForwardPassMetrics field → exported metric name. The Grafana "KV
# fabric" row plots tier occupancy and hit rate next to the MEASURED
# link model (decay-averaged peer gbps/rtt) and the two health signals
# worth alerting on: fetch failures (peers vanishing mid-fetch — the
# engine recomputes, but rising failures mean churn) and admission
# rejects (the latency gate refusing hits — expected on slow links,
# suspicious on fast ones). netstore retries ride along: the same
# daemon link the fabric's discovery depends on.
_REMOTE_GAUGES = {
    "remote_used_blocks": "nv_llm_kv_remote_used_blocks",
    "remote_capacity_blocks": "nv_llm_kv_remote_capacity_blocks",
    "remote_peer_blocks": "nv_llm_kv_remote_peer_blocks",
    "remote_stored_total": "nv_llm_kv_remote_stored_blocks_total",
    "remote_hit_rate": "nv_llm_kv_remote_hit_rate",
    "remote_fetch_failures_total": "nv_llm_kv_remote_fetch_failures_total",
    "remote_admission_rejects_total":
        "nv_llm_kv_remote_admission_rejects_total",
    "remote_link_gbps": "nv_llm_kv_remote_link_gbps",
    "remote_link_rtt_s": "nv_llm_kv_remote_link_rtt_seconds",
    # native KV dataplane + prefill-as-a-service (round 12): fetches
    # riding the C++ data plane vs the base64-over-JSON fallback, and
    # prefix blocks published to the object tier by prefill-publish
    # workers (components/prefill_service.py)
    "remote_dataplane_fetches_total":
        "nv_llm_kv_remote_dataplane_fetches_total",
    "remote_dataplane_fallbacks_total":
        "nv_llm_kv_remote_dataplane_fallbacks_total",
    "prefill_published_blocks_total":
        "nv_llm_kv_remote_prefill_published_blocks_total",
    "netstore_retries_total": "nv_llm_netstore_retries_total",
}

# chaos-hardening / graceful degradation (runtime/faults.py failpoints,
# end-to-end deadlines/cancellation, fabric circuit breaker —
# docs/chaos.md): ForwardPassMetrics field → exported metric name. The
# Grafana "Degradation" row plots cancelled + deadline-exceeded next to
# the breaker state (open peers / cumulative trips) and the two "the
# fleet is shedding instead of hanging" signals: spill writes shed on
# disk pressure and netstore calls that burned their whole deadline
# against a partitioned daemon.
_DEGRADE_GAUGES = {
    "requests_cancelled_total": "nv_llm_requests_cancelled_total",
    "requests_deadline_exceeded_total":
        "nv_llm_requests_deadline_exceeded_total",
    "netstore_deadline_exceeded_total":
        "nv_llm_netstore_deadline_exceeded_total",
    "remote_breaker_open_peers": "nv_llm_kv_remote_breaker_open_peers",
    "remote_breaker_trips_total":
        "nv_llm_kv_remote_breaker_trips_total",
    "disk_spill_shed_total": "nv_llm_kv_disk_spill_shed_writes_total",
}


# fetch-vs-recompute cost model (kv_router/scoring.py
# network_adjusted_overlap / crossover_tokens): the three fields the
# router and planner price candidates with. Exporting them closes the
# metrics plane (DL010): the crossover inputs are debuggable per worker
# next to the link gauges instead of living only inside routing
# decisions — a worker advertising kv_block_size=0 (old payload) or a
# wildly-off prefill rate is visible at a glance.
_COST_GAUGES = {
    "kv_bytes_per_block": "nv_llm_kv_bytes_per_block",
    "prefill_tok_per_s": "nv_llm_prefill_tok_per_s",
    "kv_block_size": "nv_llm_kv_block_size_tokens",
    # the per-slot state group's device bytes (0: a cache of rows alone)
    "kv_state_bytes": "nv_llm_kv_state_bytes",
}


# streaming layer-wise KV handoff (llm/kv/stream.py; docs/kv_fabric.md
# "Streaming handoff"): ForwardPassMetrics field → exported metric name.
# The Grafana "Disagg streaming" panels plot the cumulative layers this
# decode worker progressively scattered and the degradations (torn frame
# → monolithic fill, dead stream → cold recompute; rising fallbacks mean
# a flaky handoff plane) next to the two pricing inputs: the measured
# overlap ratio (fraction of stream-onboard wall time spent on hidden
# prep/scatter work rather than exposed wire waiting — near 1.0 means
# the transfer is fully hidden behind compute) and the measured
# streaming depth the router's overlap credit divides by.
_DISAGG_STREAM_GAUGES = {
    "disagg_stream_layers_total": "nv_llm_disagg_stream_layers_total",
    "disagg_stream_fallbacks_total":
        "nv_llm_disagg_stream_fallbacks_total",
    "disagg_stream_overlap_ratio": "nv_llm_disagg_stream_overlap_ratio",
    "disagg_stream_layers": "nv_llm_disagg_stream_layers",
}


# multi-tenant serving plane (llm/tenancy.py; docs/multi_tenant.md):
# ForwardPassMetrics.tenant_stats {tenant: {field: value}} → one series
# per (worker, tenant). The Grafana "Tenants" row plots per-tenant
# admitted vs throttled (a flooding tenant shows throttles rising while
# everyone else's admissions hold — the fair-share contract visualized)
# next to per-tenant resident KV blocks (quota headroom) and prefix hit
# rate (the isolation guarantee: one tenant's eviction storm must not
# crater another's curve).
_TENANT_GAUGES = {
    "admitted": "nv_llm_tenant_admitted_total",
    "throttled": "nv_llm_tenant_throttled_total",
    "kv_blocks": "nv_llm_tenant_kv_blocks",
    "hit_rate": "nv_llm_tenant_hit_rate",
}


class MetricsAggregatorService:
    """Aggregates worker load + router hit-rate into one Prometheus registry.

    One instance watches one logical endpoint (namespace/component/endpoint);
    workers appear/disappear with their leases and their gauge series follow.
    """

    def __init__(self, endpoint: Endpoint, scrape_interval: float = 1.0,
                 registry: Optional[CollectorRegistry] = None,
                 collector=None):
        self.endpoint = endpoint
        self.scrape_interval = scrape_interval
        self.registry = registry or CollectorRegistry()
        # fleet trace collector (components/trace_collector.py): fed by
        # the trace_events subscription, serves /traces/{id} (stitched
        # tree + Perfetto export) and owns the TTFT/ITL/queue-wait
        # histograms whose buckets carry trace_id exemplars
        if collector is None:
            from .trace_collector import TraceCollector
            collector = TraceCollector(registry=self.registry)
        self.collector = collector
        labels = ["component", "endpoint", "worker_id"]
        self._gauges: Dict[str, Gauge] = {
            f: Gauge(f"{PREFIX}_{f}", f"worker {f} (scraped stats)",
                     labels, registry=self.registry)
            for f in _GAUGE_FIELDS}
        self._spec_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"speculative decoding: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _SPEC_GAUGES.items()}
        self._pp_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"pipeline parallelism: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _PP_GAUGES.items()}
        self._tier_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"KV tier ladder: worker {f} (scraped stats)",
                     labels, registry=self.registry)
            for f, name in _TIER_GAUGES.items()}
        self._layout_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"KV layout/contiguity: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _LAYOUT_GAUGES.items()}
        self._remote_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"KV fabric (remote tier): worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _REMOTE_GAUGES.items()}
        self._ragged_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"ragged dispatch: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _RAGGED_GAUGES.items()}
        self._trace_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"fleet tracing: worker {f} (scraped stats)",
                     labels, registry=self.registry)
            for f, name in _TRACE_GAUGES.items()}
        self._degrade_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"graceful degradation: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _DEGRADE_GAUGES.items()}
        self._cost_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"fetch-vs-recompute cost model: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _COST_GAUGES.items()}
        self._disagg_stream_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"streaming KV handoff: worker {f} "
                     "(scraped stats)", labels, registry=self.registry)
            for f, name in _DISAGG_STREAM_GAUGES.items()}
        self._tenant_gauges: Dict[str, Gauge] = {
            f: Gauge(name, f"multi-tenant serving: per-tenant {f} "
                     "(scraped stats)", labels + ["tenant"],
                     registry=self.registry)
            for f, name in _TENANT_GAUGES.items()}
        self._seen_tenants: Dict[int, Set[str]] = {}
        self.hit_isl_blocks = Counter(
            f"{PREFIX}_hit_rate_isl_blocks_total",
            "Routing decisions: total request blocks (ISL)",
            labels, registry=self.registry)
        self.hit_overlap_blocks = Counter(
            f"{PREFIX}_hit_rate_overlap_blocks_total",
            "Routing decisions: blocks already held by the chosen worker",
            labels, registry=self.registry)
        self._seen_workers: Set[int] = set()
        self._client = None
        self._sub = None
        self._trace_sub = None
        self._tasks: list = []
        self.events_received = 0
        self.pushes = 0
        self.latest: Dict[int, ForwardPassMetrics] = {}
        # planner observability (components/planner.py): decision counters
        # + live signals scraped from the planner/status/* keys, exported
        # per namespace; /planner serves the raw snapshots
        self.planner_status: Dict[str, dict] = {}
        self._planner_decisions = Gauge(
            f"{PREFIX}_planner_decisions", "Planner decision counters "
            "(scraped from planner status)", ["namespace", "action"],
            registry=self.registry)
        self._planner_signal = Gauge(
            f"{PREFIX}_planner_signal", "Planner fleet signals",
            ["namespace", "signal"], registry=self.registry)
        self._planner_workers = Gauge(
            f"{PREFIX}_planner_workers", "Planner worker counts",
            ["namespace", "state"], registry=self.registry)
        self._planner_paused = Gauge(
            f"{PREFIX}_planner_paused", "1 when the planner is paused",
            ["namespace"], registry=self.registry)

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> "MetricsAggregatorService":
        from .trace_collector import TRACE_EVENTS_SUBJECT
        ep = self.endpoint
        self._client = ep.client()
        await self._client.start()
        self._sub = await ep.parent_component().subscribe_event(
            KV_HIT_RATE_SUBJECT)
        self._trace_sub = await ep.parent_component().subscribe_event(
            TRACE_EVENTS_SUBJECT)
        loop = asyncio.get_running_loop()
        self._tasks = [
            loop.create_task(self._scrape_loop(), name="metrics-scrape"),
            loop.create_task(self._hit_rate_loop(), name="metrics-hitrate"),
            loop.create_task(self._trace_loop(), name="metrics-traces"),
        ]
        return self

    async def close(self) -> None:
        if self._sub is not None:
            self._sub.close()
        if self._trace_sub is not None:
            self._trace_sub.close()
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        if self._client is not None:
            await self._client.close()

    # ----------------------------------------------------------------- feeds
    def _labels(self, worker_id: int):
        return (self.endpoint.component, self.endpoint.name,
                f"{worker_id:x}")

    async def _scrape_loop(self) -> None:
        # long-lived task: shed whatever ambient trace the spawning
        # context carried (runtime/tracing.py detach_trace contract)
        from ..runtime.tracing import detach_trace
        detach_trace()
        while True:
            try:
                stats = await self._client.collect_stats()
                self._apply_stats(stats)
            except Exception:  # noqa: BLE001
                logger.exception("stats scrape failed")
            try:
                await self._scrape_planner()
            except Exception:  # noqa: BLE001
                logger.exception("planner status scrape failed")
            await asyncio.sleep(self.scrape_interval)

    async def _scrape_planner(self) -> None:
        from ..llm.slo import PLANNER_PREFIX
        rt = self.endpoint.runtime
        prefix = f"{PLANNER_PREFIX}status/"
        snapshot: Dict[str, dict] = {}
        for e in await rt.store.kv_get_prefix(prefix):
            try:
                snapshot[e.key[len(prefix):]] = json.loads(e.value)
            except Exception:  # noqa: BLE001
                continue
        self.planner_status = snapshot
        for ns, s in snapshot.items():
            for action, n in (s.get("counters") or {}).items():
                self._planner_decisions.labels(ns, action).set(n)
            sig = s.get("signals") or {}
            for name in ("queue_depth", "slot_util", "kv_util",
                         "prefill_queue_depth"):
                if sig.get(name) is not None:
                    self._planner_signal.labels(ns, name).set(sig[name])
            if sig.get("ttft_p90_ms") is not None:
                self._planner_signal.labels(ns, "ttft_p90_ms").set(
                    sig["ttft_p90_ms"])
            self._planner_signal.labels(ns, "disagg_threshold").set(
                s.get("disagg_threshold", 0))
            workers = s.get("workers") or {}
            self._planner_workers.labels(ns, "live").set(
                len(workers.get("live", [])))
            self._planner_workers.labels(ns, "draining").set(
                len(workers.get("draining", [])))
            self._planner_paused.labels(ns).set(
                1 if s.get("paused") else 0)

    def _apply_stats(self, stats: Dict[int, dict]) -> None:
        present = set(stats)
        for wid, raw in stats.items():
            m = (raw if isinstance(raw, ForwardPassMetrics)
                 else ForwardPassMetrics.from_dict(raw))
            self.latest[wid] = m
            lbl = self._labels(wid)
            for f in _GAUGE_FIELDS:
                self._gauges[f].labels(*lbl).set(getattr(m, f))
            for f, g in self._spec_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._pp_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._tier_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._layout_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._remote_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._ragged_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._trace_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._degrade_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._cost_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            for f, g in self._disagg_stream_gauges.items():
                g.labels(*lbl).set(getattr(m, f))
            # per-tenant labeled series (llm/tenancy.py tenant_stats)
            tenants = m.tenant_stats or {}
            for t, stats in tenants.items():
                if not isinstance(stats, dict):
                    continue
                for f, g in self._tenant_gauges.items():
                    g.labels(*lbl, t).set(stats.get(f, 0))
            for gone_t in self._seen_tenants.get(wid, set()) - set(tenants):
                for g in self._tenant_gauges.values():
                    try:
                        g.remove(*lbl, gone_t)
                    except KeyError:
                        pass
            self._seen_tenants[wid] = set(tenants)
        # drop series for workers whose leases died (the watcher pruned them)
        for gone in self._seen_workers - present:
            self.latest.pop(gone, None)
            lbl = self._labels(gone)
            for gone_t in self._seen_tenants.pop(gone, set()):
                for g in self._tenant_gauges.values():
                    try:
                        g.remove(*lbl, gone_t)
                    except KeyError:
                        pass
            for g in (list(self._gauges.values())
                      + list(self._spec_gauges.values())
                      + list(self._pp_gauges.values())
                      + list(self._tier_gauges.values())
                      + list(self._layout_gauges.values())
                      + list(self._remote_gauges.values())
                      + list(self._ragged_gauges.values())
                      + list(self._trace_gauges.values())
                      + list(self._degrade_gauges.values())
                      + list(self._cost_gauges.values())
                      + list(self._disagg_stream_gauges.values())):
                try:
                    g.remove(*lbl)
                except KeyError:
                    pass
        self._seen_workers = present

    async def _hit_rate_loop(self) -> None:
        async for msg in self._sub:
            try:
                d = json.loads(msg.payload)
                lbl = self._labels(int(d["worker_id"]))
                self.hit_isl_blocks.labels(*lbl).inc(int(d["isl_blocks"]))
                self.hit_overlap_blocks.labels(*lbl).inc(
                    int(d["overlap_blocks"]))
                self.events_received += 1
            except Exception:  # noqa: BLE001
                logger.exception("bad hit-rate event dropped")

    async def _trace_loop(self) -> None:
        """Completed trace dicts published by workers/frontends
        (trace_events subject) → the collector's tree store + latency
        histograms (components/trace_collector.py)."""
        from ..runtime.tracing import detach_trace
        detach_trace()
        async for msg in self._trace_sub:
            try:
                self.collector.feed(json.loads(msg.payload))
            except Exception:  # noqa: BLE001
                logger.exception("bad trace event dropped")

    # ----------------------------------------------------------------- serve
    def render(self) -> bytes:
        return generate_latest(self.registry)

    def render_openmetrics(self) -> bytes:
        """OpenMetrics exposition — the format that CARRIES exemplars
        (classic Prometheus text silently drops them). Grafana's
        exemplar-click-through needs this negotiated via the Accept
        header, which serve_http honors."""
        from prometheus_client.openmetrics.exposition import (
            generate_latest as generate_openmetrics)
        return generate_openmetrics(self.registry)

    async def serve_push(self, gateway: str,
                         job: str = "dynamo_tpu_metrics",
                         interval: float = 2.0) -> asyncio.Task:
        """Push mode (reference MetricsMode::Push,
        components/metrics/src/lib.rs:104-296): periodically PUT the whole
        registry to a Prometheus PushGateway instead of — or alongside —
        pull exposition. Returns the pushing task (cancelled by close())."""
        from prometheus_client import push_to_gateway

        async def push_loop() -> None:
            while True:
                try:
                    await asyncio.to_thread(push_to_gateway, gateway,
                                            job=job, registry=self.registry)
                    self.pushes += 1
                except Exception:  # noqa: BLE001 — gateway may flap
                    logger.exception("metrics push to %s failed", gateway)
                await asyncio.sleep(interval)

        task = asyncio.get_running_loop().create_task(
            push_loop(), name="metrics-push")
        self._tasks.append(task)
        logger.info("pushing metrics to gateway %s every %.1fs (job=%s)",
                    gateway, interval, job)
        return task

    async def serve_http(self, host: str = "0.0.0.0",
                         port: int = 9091):
        """Expose GET /metrics (Prometheus text); returns the aiohttp
        runner (caller owns cleanup)."""
        from aiohttp import web

        async def metrics(request):
            # OpenMetrics when asked for (the exemplar-carrying format
            # Grafana's trace click-through scrapes); classic text else
            if "application/openmetrics-text" in request.headers.get(
                    "Accept", ""):
                return web.Response(
                    body=self.render_openmetrics(),
                    content_type="application/openmetrics-text")
            return web.Response(body=self.render(),
                                content_type="text/plain")

        async def planner(_request):
            # introspection: the latest planner/status/* snapshots
            # (SLOs, last decision, per-actuator counters) as JSON
            return web.json_response(self.planner_status)

        async def traces(_request):
            return web.json_response(
                {"traces": self.collector.summaries(),
                 **self.collector.stats()})

        async def trace_by_id(request):
            key = request.match_info["trace_id"]
            tid = self.collector.find(key)
            if tid is None:
                return web.json_response(
                    {"error": f"unknown trace {key!r}"}, status=404)
            if request.query.get("format") == "perfetto":
                return web.json_response(self.collector.perfetto(tid))
            return web.json_response(self.collector.tree(tid))

        app = web.Application()
        app.router.add_get("/metrics", metrics)
        app.router.add_get("/planner", planner)
        app.router.add_get("/traces", traces)
        app.router.add_get("/traces/{trace_id}", trace_by_id)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, host, port)
        await site.start()
        logger.info("metrics exposition on http://%s:%d/metrics", host, port)
        return runner


async def amain(argv=None) -> None:
    import argparse
    p = argparse.ArgumentParser(
        description="KV metrics aggregation service (Prometheus exposition)")
    p.add_argument("endpoint", help="dyn://ns/component/endpoint to watch")
    p.add_argument("--daemon", default="127.0.0.1:5600",
                   help="discovery daemon host:port")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9091)
    p.add_argument("--scrape-interval", type=float, default=1.0)
    p.add_argument("--push-gateway",
                   help="Prometheus PushGateway address (host:port or URL); "
                        "enables push mode alongside pull exposition "
                        "(reference MetricsMode::Push)")
    p.add_argument("--push-job", default="dynamo_tpu_metrics")
    p.add_argument("--push-interval", type=float, default=2.0)
    p.add_argument("--no-pull", action="store_true",
                   help="push mode only: skip the /metrics HTTP listener")
    args = p.parse_args(argv)
    if args.no_pull and not args.push_gateway:
        raise SystemExit("--no-pull requires --push-gateway")

    rt = await DistributedRuntime.connect(args.daemon)
    ep = Endpoint.parse_path(rt, args.endpoint)
    svc = await MetricsAggregatorService(
        ep, scrape_interval=args.scrape_interval).start()
    runner = None
    if not args.no_pull:
        runner = await svc.serve_http(args.host, args.port)
    if args.push_gateway:
        await svc.serve_push(args.push_gateway, job=args.push_job,
                             interval=args.push_interval)
    try:
        await asyncio.Event().wait()
    finally:
        if runner is not None:
            await runner.cleanup()
        await svc.close()
        await rt.shutdown()


def main() -> None:
    from ..runtime.log import setup_logging
    setup_logging()
    asyncio.run(amain())


if __name__ == "__main__":
    main()
