"""SimFleet: the real control plane over a simulated fleet.

What is REAL here (imported production code, not reimplementation):

- :class:`~dynamo_tpu.components.planner.Planner` — the standing SLO
  loop with hysteresis/cooldown, graceful drain, disagg retune — started
  exactly as in production against a real ``MemoryKvStore`` + real
  ``Client`` (sim workers write real discovery/stats/drain records);
- :class:`~dynamo_tpu.llm.kv_router.indexer.KvIndexer` — the radix
  prefix index, fed tier-tagged RouterEvents by the sim workers;
- :class:`~dynamo_tpu.llm.kv_router.scheduler.KvScheduler` — the cost
  model picking a worker per request (NetKV network-adjusted overlap,
  draining exclusion, optimistic accounting);
- :class:`~dynamo_tpu.llm.disagg.DisaggregatedRouter` — the local-vs-
  remote prefill decision, live-rewatched as the planner retunes it;
- :class:`~dynamo_tpu.llm.kv.fabric.AdmissionGate` /
  ``PeerLinkTable`` / ``PrefillRateEstimator`` — fetch-vs-recompute
  pricing per worker over measured-shaped links.

What is SIMULATED: request service times (sim/worker.py over the
measured device models), the network links' parameters, and the traffic
(sim/workload.py). Stats flow to the planner shaped exactly like
``ForwardPassMetrics`` — because they are built with that dataclass.
"""

from __future__ import annotations

import asyncio
import dataclasses
import random
import struct
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import xxhash

from ..components.planner import Planner, PlannerActuator, PlannerConfig
from ..llm.disagg import DisaggregatedRouter, disagg_config_key
from ..llm.kv.blocks import HASH_SEED, chain_hash
from ..llm.kv.fabric import PeerLinkTable
from ..llm.kv_router.indexer import KvIndexer
from ..llm.kv_router.scheduler import KvScheduler
from ..llm.kv_router.scoring import Endpoint as ScoringEndpoint
from ..llm.kv_router.scoring import ProcessedEndpoints
from ..llm.slo import ServiceLevelObjective, percentile
from ..runtime.bus import MemoryBus
from ..runtime.distributed import DistributedRuntime, Endpoint
from ..runtime.kvstore import MemoryKvStore, WatchEventType
from .models import WorkerPerfModel
from .report import EventLog
from .worker import SimRequest, SimWorker
from .workload import RequestSpec, Workload

__all__ = ["FleetConfig", "SimFleet", "SimActuator"]


@dataclasses.dataclass
class FleetConfig:
    namespace: str = "sim"
    replicas: int = 8
    prefill_replicas: int = 0
    slots: int = 4
    kv_blocks: int = 512
    host_blocks: int = 256
    block_size: int = 32
    tenant_prefix_blocks: int = 4      # per-tenant shared system prefix
    model_name: str = "sim-model"
    perf: Optional[WorkerPerfModel] = None
    link_gbps: float = 8.0
    link_rtt_s: float = 2e-3
    link_jitter: float = 0.25          # ± fraction, per-worker (seeded)
    admission: str = "auto"
    provision_delay_s: float = 20.0
    stats_interval_s: float = 5.0
    scrape_interval_s: float = 2.0
    retry_backoff_s: float = 0.5
    max_retries: int = 3
    drainout_s: float = 300.0
    planner_enabled: bool = True
    slo: Optional[ServiceLevelObjective] = None
    planner_cfg: Optional[PlannerConfig] = None
    new_worker_profile: str = "slow-start:20"
    initial_profiles: Tuple[str, ...] = ()   # cycled over initial workers
    # multi-tenant serving plane (llm/tenancy.py): {tenant: {weight,
    # kv_quota_blocks, qos}} policies. Non-None turns on fair-share
    # waiting queues (WDRR + QoS) and per-worker quota-preferred
    # eviction — the REAL policy classes under the determinism gate.
    tenant_policies: Optional[Dict[str, dict]] = None
    # streaming layer-wise KV handoff (llm/kv/stream.py): > 0 prices the
    # disagg P→D handoff at the EXPOSED overlapped transfer for that
    # pipeline depth (AdmissionGate.modeled_fetch_overlap_s) instead of
    # the serial cost — the sim's lever for predicting what streaming
    # buys a fleet before turning it on. 0 = monolithic (unchanged).
    stream_layers: int = 0


class SimLatencyCollector:
    """Collector-shaped latency source (the planner consumes it through
    llm/slo.latency_percentiles exactly like the fleet trace
    collector): sliding window of completed-request TTFT/ITL."""

    def __init__(self, clock, window_s: float = 180.0):
        self.clock = clock
        self.window_s = window_s
        self._ttft: deque = deque()
        self._itl: deque = deque()

    def record(self, ttft_ms: float, itl_ms: Optional[float]) -> None:
        now = self.clock.now
        self._ttft.append((now, ttft_ms))
        if itl_ms is not None:
            self._itl.append((now, itl_ms))

    def _prune(self) -> None:
        cut = self.clock.now - self.window_s
        for dq in (self._ttft, self._itl):
            while dq and dq[0][0] < cut:
                dq.popleft()

    def latency_percentiles(self, p: float = 90.0) -> dict:
        self._prune()
        return {"ttft_p_ms": percentile([v for _, v in self._ttft], p),
                "itl_p_ms": percentile([v for _, v in self._itl], p),
                "n_traces": float(len(self._ttft))}


class HashCatalog:
    """Deterministic per-session block-hash chains without materializing
    token ids: block i's local hash is xxh3 over (seed, scope, i) and
    the sequence hashes chain through the REAL chain_hash — the first
    ``tenant_prefix_blocks`` blocks are scoped to the TENANT (the shared
    system prompt every session of that tenant reuses)."""

    def __init__(self, seed: int, block_size: int, tenant_prefix_blocks: int):
        self.seed = seed
        self.block_size = block_size
        self.tenant_prefix_blocks = tenant_prefix_blocks
        self._chains: Dict[str, List[int]] = {}

    def chain(self, tenant: str, session: str, n_blocks: int) -> List[int]:
        chain = self._chains.get(session)
        if chain is None:
            chain = self._chains[session] = []
        while len(chain) < n_blocks:
            i = len(chain)
            scope = tenant if i < self.tenant_prefix_blocks else session
            local = xxhash.xxh3_64_intdigest(
                struct.pack("<q", self.seed) + scope.encode()
                + struct.pack("<q", i), seed=HASH_SEED)
            parent = chain[-1] if chain else None
            chain.append(chain_hash(parent, local))
        return chain[:n_blocks]


class SimActuator(PlannerActuator):
    """The planner's substrate: scale-up provisions new sim workers after
    the configured provision delay (with the scenario's new-worker
    profile — typically slow-start); retire force-exits a worker the
    planner gave up draining (the drain-timeout path; a cleanly drained
    worker already exited on its own)."""

    def __init__(self, fleet: "SimFleet"):
        self.fleet = fleet

    async def scale_up(self, role: str, count: int) -> None:
        self.fleet.log.log("planner_scale_up", role=role, count=count)
        for _ in range(count):
            self.fleet.schedule_spawn(self.fleet.cfg.new_worker_profile,
                                      prefill=(role == "prefill"))

    async def retire(self, role: str, worker_id: int) -> None:
        self.fleet.log.log("planner_retire", role=role, worker=worker_id)
        w = (self.fleet.workers.get(worker_id)
             or self.fleet.prefill_workers.get(worker_id))
        if w is not None and not w.dead:
            w.exit(clean=False)


class SimPrefillQueue:
    """Planner-visible prefill backlog (the ``prefill_queue.depth()``
    signal driving the disagg retune)."""

    def __init__(self):
        self.items: deque = deque()
        self.inflight = 0

    async def depth(self) -> int:
        return len(self.items) + self.inflight


class SimFleet:
    def __init__(self, cfg: FleetConfig, seed: int = 0):
        self.cfg = cfg
        self.seed = seed
        self.rng = random.Random(seed ^ 0x51AFEED)
        self.perf = cfg.perf or WorkerPerfModel()
        self.clock = None              # bound at start() from the loop
        self.log: Optional[EventLog] = None
        self.runtime: Optional[DistributedRuntime] = None
        self.endpoint: Optional[Endpoint] = None
        self.prefill_endpoint: Optional[Endpoint] = None
        self.workers: Dict[int, SimWorker] = {}
        self.prefill_workers: Dict[int, SimWorker] = {}
        self.draining: set = set()
        self.links = PeerLinkTable(default_gbps=cfg.link_gbps,
                                   default_rtt_s=cfg.link_rtt_s)
        self.indexer = KvIndexer(cfg.block_size, prefer_native=False)
        self.scheduler = KvScheduler(cfg.block_size,
                                     rng=random.Random(seed ^ 0x5C3D))
        # instance-local tenant table (NOT the process-global one: two
        # fleets in one test must not share policy state)
        self.tenant_table = None
        if cfg.tenant_policies is not None:
            from ..llm.tenancy import TenantPolicy, TenantTable
            self.tenant_table = TenantTable(
                {t: TenantPolicy(**p)
                 for t, p in cfg.tenant_policies.items()})
        self.catalog = HashCatalog(seed, cfg.block_size,
                                   cfg.tenant_prefix_blocks)
        self.prefill_queue = SimPrefillQueue()
        self.collector = None
        self.planner: Optional[Planner] = None
        self.disagg_router: Optional[DisaggregatedRouter] = None
        self._next_wid = 0x51A0001
        self._tasks: List[asyncio.Task] = []
        self._watchers: list = []
        self._spawned: List[asyncio.Task] = []
        self._t0 = 0.0
        self._specs: List[RequestSpec] = []
        self._next_spec = 0
        self.counters: Dict[str, int] = {
            "arrived": 0, "completed": 0, "dropped": 0, "lost": 0,
            "retried": 0, "no_capacity": 0, "remote_prefills": 0,
            "fabric_fetch_blocks": 0, "hit_blocks": 0, "isl_blocks": 0,
            "crashes": 0, "clean_exits": 0, "forced_exits": 0,
            "spawned": 0, "shed_writes": 0, "tenant_evictions": 0,
        }
        self.ttft_ms: List[float] = []
        self.itl_ms: List[float] = []
        self.kv_events = 0
        self.replica_peak = 0
        self.prefill_peak = 0

    # ------------------------------------------------------------ wiring
    def spawn(self, coro) -> asyncio.Task:
        t = asyncio.get_running_loop().create_task(coro)
        self._spawned.append(t)
        return t

    def log_event(self, kind: str, **fields) -> None:
        self.log.log(kind, **fields)

    async def start(self) -> "SimFleet":
        loop = asyncio.get_running_loop()
        self.clock = loop.clock      # VirtualTimeLoop
        self.log = EventLog(self.clock)
        self.collector = SimLatencyCollector(self.clock)
        store = MemoryKvStore(now=self.clock.monotonic)
        self.runtime = DistributedRuntime(store, MemoryBus())
        ns = self.cfg.namespace
        self.endpoint = Endpoint(self.runtime, ns, "worker", "generate")
        self.prefill_endpoint = Endpoint(self.runtime, ns, "prefill",
                                         "generate")
        for i in range(self.cfg.replicas):
            prof = ""
            if self.cfg.initial_profiles:
                prof = self.cfg.initial_profiles[
                    i % len(self.cfg.initial_profiles)]
            await self._spawn_worker(profile=prof)
        for _ in range(self.cfg.prefill_replicas):
            await self._spawn_worker(prefill=True)
        # the REAL disagg router, watching the REAL retune key
        self.disagg_router = DisaggregatedRouter(
            self.runtime, self.cfg.model_name,
            max_local_prefill_length=(
                self.cfg.slo.max_local_prefill_length
                if self.cfg.slo else 512))
        await self.disagg_router.start()
        # drain watch: ONE fleet-level watcher per tier dispatching to
        # workers (the worker-side half of the planner's drain protocol;
        # the prefill tier drains through its own endpoint's keys)
        w = await store.watch_prefix(self.endpoint.drain_prefix())
        self._watchers.append(w)
        self._tasks.append(loop.create_task(
            self._drain_watch(w, self.workers), name="sim-drain-watch"))
        wp = await store.watch_prefix(self.prefill_endpoint.drain_prefix())
        self._watchers.append(wp)
        self._tasks.append(loop.create_task(
            self._drain_watch(wp, self.prefill_workers),
            name="sim-prefill-drain-watch"))
        # retune observability: log threshold changes into the event log
        w2 = await store.watch_prefix(disagg_config_key(self.cfg.model_name))
        self._watchers.append(w2)
        self._tasks.append(loop.create_task(self._retune_watch(w2),
                                            name="sim-retune-watch"))
        self._tasks.append(loop.create_task(self._stats_loop(),
                                            name="sim-stats"))
        self._tasks.append(loop.create_task(self._scrape_loop(),
                                            name="sim-scrape"))
        self._scrape_once()
        if self.cfg.planner_enabled:
            self.planner = Planner(
                self.runtime, self.endpoint, SimActuator(self),
                slo=self.cfg.slo, config=self.cfg.planner_cfg,
                prefill_queue=(self.prefill_queue
                               if self.cfg.prefill_replicas > 0 else None),
                prefill_endpoint=(self.prefill_endpoint
                                  if self.cfg.prefill_replicas > 0
                                  else None),
                model_name=(self.cfg.model_name
                            if self.cfg.prefill_replicas > 0 else None),
                traces=lambda: [], collector=self.collector)
            await self.planner.start()
        return self

    async def stop(self) -> None:
        if self.planner is not None:
            await self.planner.stop()
        if self.disagg_router is not None:
            await self.disagg_router.stop()
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        for w in self._watchers:
            w.close()
        for w in list(self.workers.values()) + list(
                self.prefill_workers.values()):
            w._cancel_timers()
        if self._spawned:
            await asyncio.gather(*self._spawned, return_exceptions=True)
        await self.runtime.shutdown()

    # ----------------------------------------------------------- workers
    def _jitter(self, base: float) -> float:
        j = self.cfg.link_jitter
        return base * (1.0 + self.rng.uniform(-j, j))

    async def _spawn_worker(self, profile: str = "",
                            prefill: bool = False) -> SimWorker:
        wid = self._next_wid
        self._next_wid += 1
        w = SimWorker(self, wid, perf=self.perf, profile=profile,
                      slots=self.cfg.slots, kv_blocks=self.cfg.kv_blocks,
                      host_blocks=self.cfg.host_blocks,
                      block_size=self.cfg.block_size, prefill_only=prefill)
        # seed the measured link table (jittered per worker, then
        # decay-averaged exactly like live probes would refine it)
        self.links.observe_rtt(wid, self._jitter(self.cfg.link_rtt_s))
        self.links.observe_transfer(
            wid, int(self._jitter(self.cfg.link_gbps) * 1e9), 1.0)
        await w.register()
        (self.prefill_workers if prefill else self.workers)[wid] = w
        self.counters["spawned"] += 1
        self.replica_peak = max(self.replica_peak, self.live_decode_count())
        self.prefill_peak = max(self.prefill_peak,
                                self.live_prefill_count())
        self.log.log("worker_up", worker=wid, prefill=prefill,
                     profile=w.profile.name)
        if prefill:
            self._pump_prefill_queue()
        return w

    def schedule_spawn(self, profile: str = "",
                       prefill: bool = False) -> None:
        asyncio.get_running_loop().call_later(
            self.cfg.provision_delay_s,
            lambda: self.spawn(self._spawn_worker(profile=profile,
                                                  prefill=prefill)))

    def live_decode_count(self) -> int:
        return sum(1 for w in self.workers.values() if not w.dead)

    def live_prefill_count(self) -> int:
        return sum(1 for w in self.prefill_workers.values() if not w.dead)

    def on_worker_exit(self, w: SimWorker, clean: bool) -> None:
        self.draining.discard(w.worker_id)
        self.counters["clean_exits" if clean else "forced_exits"] += 1
        self.log.log("worker_exit", worker=w.worker_id, clean=clean)
        self.indexer.remove_worker(w.worker_id)
        self.links.drop(w.worker_id)
        ep = w.endpoint
        store = self.runtime.store
        self.spawn(store.kv_delete(ep.discovery_key(w.worker_id)))
        self.spawn(store.kv_delete(ep.stats_key(w.worker_id)))
        self._scrape_once()

    def on_worker_crash(self, w: SimWorker) -> None:
        self.draining.discard(w.worker_id)
        self.counters["crashes"] += 1
        self.log.log("worker_crash", worker=w.worker_id)
        self.indexer.remove_worker(w.worker_id)
        self.links.drop(w.worker_id)
        ep = w.endpoint
        store = self.runtime.store
        self.spawn(store.kv_delete(ep.discovery_key(w.worker_id)))
        self.spawn(store.kv_delete(ep.stats_key(w.worker_id)))
        self._scrape_once()

    def on_drain_begin(self, w: SimWorker) -> None:
        self.draining.add(w.worker_id)
        self.log.log("drain_begin", worker=w.worker_id)

    def on_shed_writes(self, w: SimWorker, n: int) -> None:
        """Disk-pressure fault: a demote the colder tier refused — the
        write-behind sheds and serving continues (disk_pressure
        scenario's asserted behavior)."""
        self.counters["shed_writes"] += n

    async def _drain_watch(self, watcher, pool: Dict[int, SimWorker]
                           ) -> None:
        from ..runtime.tracing import detach_trace
        detach_trace()
        async for ev in watcher:
            if ev.type != WatchEventType.PUT:
                continue
            try:
                wid = int(ev.entry.key.rsplit(":", 1)[-1], 16)
            except ValueError:
                continue
            w = pool.get(wid)
            if w is not None:
                w.begin_drain()

    async def _retune_watch(self, watcher) -> None:
        import json as _json
        async for ev in watcher:
            if ev.type != WatchEventType.PUT:
                continue
            try:
                d = _json.loads(ev.entry.value)
            except ValueError:
                continue
            self.log.log("retune",
                         threshold=d.get("max_local_prefill_length"))

    # ------------------------------------------------------- stats plane
    async def _stats_loop(self) -> None:
        from ..runtime.tracing import detach_trace
        detach_trace()
        store = self.runtime.store
        while True:
            for w in list(self.workers.values()) + list(
                    self.prefill_workers.values()):
                if not w.dead and not w.partitioned:
                    # a partitioned worker's stats plane is dark: its
                    # last-published record goes stale — the planner's
                    # view of the brownout (sim/scenarios.py
                    # partition_brownout)
                    await store.kv_put(
                        w.endpoint.stats_key(w.worker_id), w.stats_json())
            await asyncio.sleep(self.cfg.stats_interval_s)

    def _scrape_once(self, sample: bool = False) -> None:
        eps = [ScoringEndpoint(w.worker_id, w.scraped_metrics())
               for w in self.workers.values() if not w.dead]
        self.scheduler.update_endpoints(ProcessedEndpoints(eps))
        if sample and eps:
            n = len(eps)
            self.log.log(
                "load_sample", n=n,
                queue_depth=round(sum(e.metrics.num_requests_waiting
                                      for e in eps) / n, 3),
                slot_util=round(sum(e.metrics.request_active_slots
                                    for e in eps)
                                / max(sum(e.metrics.request_total_slots
                                          for e in eps), 1), 4))

    async def _scrape_loop(self) -> None:
        while True:
            self._scrape_once(sample=True)
            await asyncio.sleep(self.cfg.scrape_interval_s)

    # ------------------------------------------------------- request flow
    def apply_kv_event(self, ev) -> None:
        self.kv_events += 1
        self.indexer.apply_event(ev)

    def _start_frontend(self, workload: Workload) -> None:
        self._specs = list(workload)
        self._next_spec = 0
        self._dispatch_due()

    def _dispatch_due(self) -> None:
        loop = asyncio.get_running_loop()
        now = loop.time()
        while (self._next_spec < len(self._specs)
               and self._t0 + self._specs[self._next_spec].at <= now + 1e-9):
            spec = self._specs[self._next_spec]
            self._next_spec += 1
            self.counters["arrived"] += 1
            self.log.log("arrive", rid=spec.rid, tenant=spec.tenant,
                         isl=spec.isl, osl=spec.osl, turn=spec.turn)
            self._admit(spec)
        if self._next_spec < len(self._specs):
            loop.call_at(self._t0 + self._specs[self._next_spec].at,
                         self._dispatch_due)

    def _route(self, spec: RequestSpec):
        """One pass of the REAL router: radix overlap + KvScheduler."""
        isl_blocks = max(spec.isl // self.cfg.block_size, 1)
        hashes = self.catalog.chain(spec.tenant, spec.session, isl_blocks)
        overlap = self.indexer.find_matches(hashes)
        exclude = set(self.draining)
        wid = self.scheduler.schedule(spec.isl, overlap, exclude=exclude,
                                      tenant=spec.tenant)
        if wid is not None and wid in self.workers \
                and not self.workers[wid].dead:
            return wid, hashes, overlap
        # Every worker slot-full (or only draining workers left): fall
        # back to least-backlogged so pressure lands in worker queues —
        # the num_requests_waiting signal the planner scales on — and a
        # full fleet NEVER drops a request.
        #
        # With tenancy on, the fallback keeps CACHE AFFINITY instead:
        # the per-tenant WDRR waiting queues guarantee a victim tenant's
        # request is popped at its fair share no matter how deep the
        # flooding tenant's backlog on that worker is — so routing into
        # a backlogged affinity worker is safe, and a flood can no
        # longer strip everyone else's hit rate by saturating the fleet
        # (backlog-blind affinity is exactly what fair-share queues buy).
        if self.tenant_table is not None:
            best = [(-overlap.weighted.get(wid_, 0.0),
                     len(w.waiting) + w.active_slots, wid_)
                    for wid_, w in self.workers.items()
                    if not w.dead and wid_ not in exclude]
            if best:
                best.sort()
                return best[0][2], hashes, overlap
        live = [(len(w.waiting) + w.active_slots, wid_)
                for wid_, w in self.workers.items()
                if not w.dead and wid_ not in exclude]
        if not live:
            live = [(len(w.waiting) + w.active_slots, wid_)
                    for wid_, w in self.workers.items() if not w.dead]
        if not live:
            return None, hashes, overlap
        live.sort()
        return live[0][1], hashes, overlap

    def _admit(self, spec: RequestSpec, retries: int = 0) -> None:
        wid, hashes, overlap = self._route(spec)
        if wid is None:
            # no live decode workers at all — the planner's
            # "no_workers" verdict is already scaling; retry shortly
            self.counters["no_capacity"] += 1
            if retries == 0:
                self.log.log("no_capacity", rid=spec.rid)
            asyncio.get_running_loop().call_later(
                self.cfg.retry_backoff_s,
                lambda: self._admit(spec, retries + 1))
            return
        bs = self.cfg.block_size
        isl_blocks = len(hashes)
        hit = min(overlap.scores.get(wid, 0), isl_blocks)
        self.counters["hit_blocks"] += hit
        self.counters["isl_blocks"] += isl_blocks
        w = self.workers[wid]
        remote = (self.disagg_router.prefill_remote(spec.isl, hit * bs)
                  and any(not p.dead
                          for p in self.prefill_workers.values()))
        if remote:
            self.counters["remote_prefills"] += 1
            req = SimRequest(spec, hashes, new_tokens=spec.isl,
                             fetch_s=0.0, fetched_blocks=0, hit_blocks=hit,
                             arrive_t=self.clock.now, kind="prefill",
                             target_wid=wid)
            req.retries = retries
            self.log.log("route", rid=spec.rid, worker=wid, hit=hit,
                         blocks=isl_blocks, remote=True)
            self.prefill_queue.items.append(req)
            self._pump_prefill_queue()
            return
        # fabric credit: blocks some OTHER worker holds are fetched over
        # the chosen worker's measured link iff ITS real AdmissionGate
        # prices the fetch under the recompute
        fetched = 0
        fetch_s = 0.0
        extra = min(overlap.fleet_depth, isl_blocks) - hit
        if extra > 0 and w.gate.admit(extra, w.link):
            fetched = extra
            fetch_s = w.gate.modeled_fetch_s(extra, w.link)
            self.counters["fabric_fetch_blocks"] += fetched
        new_tokens = max(spec.isl - (hit + fetched) * bs, 0)
        req = SimRequest(spec, hashes, new_tokens=new_tokens,
                         fetch_s=fetch_s, fetched_blocks=fetched,
                         hit_blocks=hit, arrive_t=self.clock.now)
        req.retries = retries
        self.log.log("route", rid=spec.rid, tenant=spec.tenant,
                     worker=wid, hit=hit, fetched=fetched,
                     blocks=isl_blocks, remote=False)
        w.submit(req)

    # ------------------------------------------------- disagg prefill leg
    def _pump_prefill_queue(self) -> None:
        q = self.prefill_queue
        while q.items:
            idle = [w for w in self.prefill_workers.values()
                    if not w.dead and w.prefill is None and not w.waiting]
            if not idle:
                return
            req = q.items.popleft()
            q.inflight += 1
            # the prefill worker's own prefix cache shortens its work
            p_overlap = self.indexer.find_matches(req.hashes)
            p_hit = min(p_overlap.scores.get(idle[0].worker_id, 0),
                        len(req.hashes))
            req.new_tokens = max(req.spec.isl
                                 - p_hit * self.cfg.block_size, 0)
            idle[0].submit(req)

    def on_prefill_handoff(self, req: SimRequest, pw: SimWorker) -> None:
        """Remote prefill finished: price the KV handoff to the decode
        worker over its measured link, then admit decode with the KV
        already shipped (new_tokens=0)."""
        self.prefill_queue.inflight -= 1
        wid = req.target_wid
        w = self.workers.get(wid)
        if w is None or w.dead or w.draining:
            live = sorted(wid_ for wid_, w_ in self.workers.items()
                          if not w_.dead and wid_ not in self.draining)
            if not live:
                self.on_requests_lost([req])
                self._pump_prefill_queue()
                return
            wid = live[0]
            w = self.workers[wid]
        n_blocks = len(req.hashes)
        handoff_s = (w.gate.modeled_fetch_overlap_s(
            n_blocks, w.link, self.cfg.stream_layers)
            if self.cfg.stream_layers > 0
            else w.gate.modeled_fetch_s(n_blocks, w.link))
        dreq = SimRequest(req.spec, req.hashes, new_tokens=0,
                          fetch_s=handoff_s, fetched_blocks=n_blocks,
                          hit_blocks=req.hit_blocks,
                          arrive_t=req.arrive_t)
        dreq.retries = req.retries
        self.log.log("prefill_handoff", rid=req.spec.rid,
                     prefill_worker=pw.worker_id, worker=wid,
                     blocks=n_blocks)
        w.submit(dreq)
        self._pump_prefill_queue()

    # -------------------------------------------------------- completions
    def on_first_token(self, req: SimRequest, w: SimWorker) -> None:
        ttft_ms = (req.first_t - req.arrive_t) * 1e3
        self.log.log("first_token", rid=req.spec.rid, worker=w.worker_id,
                     ttft_ms=round(ttft_ms, 3))

    def on_complete(self, req: SimRequest, w: SimWorker) -> None:
        now = self.clock.now
        ttft_ms = (req.first_t - req.arrive_t) * 1e3
        itl_ms = None
        if req.spec.osl > 1:
            itl_ms = (now - req.first_t) * 1e3 / (req.spec.osl - 1)
        self.counters["completed"] += 1
        self.ttft_ms.append(ttft_ms)
        if itl_ms is not None:
            self.itl_ms.append(itl_ms)
        self.collector.record(ttft_ms, itl_ms)
        self.log.log("complete", rid=req.spec.rid, tenant=req.spec.tenant,
                     worker=w.worker_id, ttft_ms=round(ttft_ms, 3),
                     itl_ms=round(itl_ms, 3) if itl_ms is not None else None)

    def on_requests_lost(self, reqs: List[SimRequest]) -> None:
        """A crash or forced retire cut these in-flight requests: the
        frontend retries them (bounded), exactly as production clients
        re-dispatch on a vanished instance."""
        for req in reqs:
            self.counters["lost"] += 1
            if req.retries >= self.cfg.max_retries:
                self.counters["dropped"] += 1
                self.log.log("drop", rid=req.spec.rid,
                             retries=req.retries)
                continue
            self.counters["retried"] += 1
            self.log.log("retry", rid=req.spec.rid, retries=req.retries + 1)
            spec = req.spec
            nxt = req.retries + 1
            asyncio.get_running_loop().call_later(
                self.cfg.retry_backoff_s,
                lambda s=spec, r=nxt: self._admit(s, r))

    # -------------------------------------------------------------- drive
    @property
    def inflight(self) -> int:
        done = (self.counters["completed"] + self.counters["dropped"])
        return self.counters["arrived"] - done

    async def run(self, workload: Workload,
                  faults: Tuple[Tuple[float, str, Callable], ...] = (),
                  duration_s: Optional[float] = None) -> None:
        loop = asyncio.get_running_loop()
        self._t0 = loop.time()
        duration = duration_s or (workload.duration_s + 1.0)
        for at, name, fn in faults:
            loop.call_at(self._t0 + at,
                         lambda n=name, f=fn: (self.log.log("fault", name=n),
                                               f(self)))
        self._start_frontend(workload)
        end = self._t0 + duration
        while loop.time() < end:
            await asyncio.sleep(min(5.0, end - loop.time()))
        grace = end + self.cfg.drainout_s
        while self.inflight > 0 and loop.time() < grace:
            await asyncio.sleep(1.0)
        self.log.log("sim_end", inflight=self.inflight)

    # ------------------------------------------------------------- report
    def report(self, wall_s: Optional[float] = None) -> dict:
        slo = self.cfg.slo or ServiceLevelObjective()
        attained = (sum(1 for v in self.ttft_ms if v <= slo.ttft_p90_ms)
                    / max(len(self.ttft_ms), 1))
        r = {
            "seed": self.seed,
            "virtual_s": round(self.clock.now, 3),
            "requests": dict(self.counters),
            "replicas": {"start": self.cfg.replicas,
                         "end": self.live_decode_count(),
                         "peak": self.replica_peak},
            "prefill_replicas": {"start": self.cfg.prefill_replicas,
                                 "end": self.live_prefill_count(),
                                 "peak": self.prefill_peak},
            "latency_ms": {
                "ttft_p50": percentile(self.ttft_ms, 50),
                "ttft_p90": percentile(self.ttft_ms, 90),
                "ttft_p99": percentile(self.ttft_ms, 99),
                "itl_p50": percentile(self.itl_ms, 50),
                "itl_p90": percentile(self.itl_ms, 90),
            },
            "slo": {"ttft_target_ms": slo.ttft_p90_ms,
                    "ttft_attainment": round(attained, 4)},
            "router": {
                "kv_events": self.kv_events,
                "hit_rate_blocks": round(
                    self.counters["hit_blocks"]
                    / max(self.counters["isl_blocks"], 1), 4),
                "fabric_fetch_blocks": self.counters["fabric_fetch_blocks"],
            },
            "events": len(self.log),
            "event_log_digest": self.log.digest(),
        }
        if self.tenant_table is not None:
            # per-tenant serving summary (noisy_neighbor's check input):
            # routed decisions + residual residency per live worker
            r["tenants"] = {
                "admitted": self.scheduler.tenant_counters(),
                "kv_blocks": {
                    t: sum(sum(w.ledger.snapshot().get(t, {}).values())
                           for w in self.workers.values()
                           if not w.dead and w.ledger is not None)
                    for t in sorted(self.tenant_table.policies)},
            }
        if self.planner is not None:
            r["planner"] = {
                "counters": dict(self.planner.counters),
                "disagg_threshold": self.planner.disagg_threshold,
            }
        if wall_s is not None:
            r["wall_s"] = round(wall_s, 3)
        return r
