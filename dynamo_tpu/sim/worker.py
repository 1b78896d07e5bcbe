"""Simulated replica: a discrete-event model of one serving worker.

The engine model is deliberately coarse — the control plane under test
(planner, router, disagg retune) consumes QUEUE/SLOT/KV/LATENCY signals,
not kernel microstructure:

- prefill runs serially at the perf model's token rate
  (sim/models.py), scaled by the behavior
  profile's slow-start/latency factors;
- decode is continuous batching as processor sharing: all active
  sequences advance one token per step, and the step time grows with
  batch size along the measured device-step fit (+ modeled TP/PP
  collective cost from parallel/ici_model);
- fabric fetches and disagg KV handoffs are priced by the REAL
  ``AdmissionGate.modeled_fetch_s`` over the fleet's ``PeerLinkTable``
  links — the same arithmetic a live worker's gate runs;
- the worker's measured prefill rate feeds a REAL
  ``PrefillRateEstimator`` (llm/kv/fabric.py), so a slow-starting
  replica's compile-inflated early samples are age-weighted out of the
  admission pricing exactly as on hardware.

Workers register REAL discovery + stats records in the fleet's
MemoryKvStore, so the unmodified production ``Client`` — and therefore
the unmodified ``Planner`` — watches, scrapes, and drains them through
the production code paths (drain key → draining re-announce →
drain-to-exit).
"""

from __future__ import annotations

import asyncio
import json
from collections import OrderedDict, deque
from typing import Deque, List, Optional

from ..llm.kv.fabric import AdmissionGate, PrefillRateEstimator
from ..llm.kv_router.protocols import (ForwardPassMetrics, KvRemovedEvent,
                                       KvStoredEvent, RouterEvent)
from ..runtime.component import ComponentEndpointInfo
from .profiles import BehaviorProfile

__all__ = ["SimRequest", "SimWorker"]

_EPS = 1e-9
_MIN_DT = 1e-6


class SimRequest:
    """One request in flight through the simulated fleet."""

    __slots__ = ("spec", "hashes", "new_tokens", "fetch_s", "fetched_blocks",
                 "hit_blocks", "kind", "arrive_t", "first_t", "worker_id",
                 "retries", "target_wid")

    def __init__(self, spec, hashes: List[int], new_tokens: int,
                 fetch_s: float, fetched_blocks: int, hit_blocks: int,
                 arrive_t: float, kind: str = "decode",
                 target_wid: Optional[int] = None):
        self.spec = spec
        self.hashes = hashes
        self.new_tokens = int(new_tokens)
        self.fetch_s = float(fetch_s)
        self.fetched_blocks = int(fetched_blocks)
        self.hit_blocks = int(hit_blocks)
        self.kind = kind                 # "decode" | "prefill" (disagg leg)
        self.arrive_t = arrive_t
        self.first_t: Optional[float] = None
        self.worker_id: Optional[int] = None
        self.retries = 0
        self.target_wid = target_wid     # disagg: the decode worker


class _Prefill:
    __slots__ = ("req", "fetch_left", "tokens_left", "started_t")

    def __init__(self, req: SimRequest, now: float):
        self.req = req
        self.fetch_left = req.fetch_s
        self.tokens_left = float(max(req.new_tokens, 0))
        self.started_t = now


class _Decode:
    __slots__ = ("req", "tokens_left")

    def __init__(self, req: SimRequest):
        self.req = req
        self.tokens_left = float(max(req.spec.osl - 1, 0))


class SimWorker:
    def __init__(self, fleet, worker_id: int, *, perf, profile: str = "",
                 slots: int = 4, kv_blocks: int = 512, host_blocks: int = 256,
                 block_size: int = 32, prefill_only: bool = False):
        self.fleet = fleet
        self.worker_id = worker_id
        self.perf = perf
        self.profile = (profile if isinstance(profile, BehaviorProfile)
                        else BehaviorProfile.parse(profile))
        self.slots = slots
        self.kv_blocks = kv_blocks
        self.host_blocks = host_blocks
        self.block_size = block_size
        self.prefill_only = prefill_only

        # multi-tenant serving (llm/tenancy.py — the REAL policy
        # machinery, not a sim reimplementation): with fleet tenancy on,
        # the waiting queue drains in weighted-deficit-round-robin order
        # with QoS classes (a flooding tenant's backlog sits in ITS
        # queue) and a per-worker TenantBlockLedger quota-prefers the
        # over-quota tenant's blocks at eviction time.
        self.tenant_table = getattr(fleet, "tenant_table", None)
        self.ledger = None
        if self.tenant_table is not None:
            from ..llm.tenancy import FairShareQueue, TenantBlockLedger
            self.waiting = FairShareQueue(self.tenant_table)
            self.ledger = TenantBlockLedger(self.tenant_table)
        else:
            self.waiting: Deque[SimRequest] = deque()
        self.prefill: Optional[_Prefill] = None
        self.decoding: List[_Decode] = []
        # device-tier LRU of resident block seq-hashes; evictions demote
        # to a host-tier LRU (re-announced tier="host"), whose own
        # evictions are removed-announced — the tier ladder the router's
        # weighted scoring consumes, and the eviction-storm substrate.
        self.resident: "OrderedDict[int, None]" = OrderedDict()
        self.host_resident: "OrderedDict[int, None]" = OrderedDict()

        self.estimator = PrefillRateEstimator()
        self.gate = AdmissionGate(
            bytes_per_block=perf.kv_bytes_per_block, block_size=block_size,
            prefill_tok_per_s=self.estimator.rate,
            mode=fleet.cfg.admission)
        # the router-facing metrics object, mutated in place on scrape
        self.metrics = ForwardPassMetrics(request_total_slots=slots,
                                          kv_total_blocks=kv_blocks)

        self.started_at = 0.0
        self.draining = False
        self.dead = False
        self.exited_clean = False
        self.prefills_done = 0
        self.decodes_done = 0
        # chaos-scenario state (docs/chaos.md): a PARTITIONED worker
        # keeps serving but its stats/scrape view freezes (the
        # kvstore-partition shape — routers/planner see stale numbers);
        # a worker under disk pressure SHEDS demote writes instead of
        # landing them in the colder tier (the ENOSPC write-behind
        # shape), counted in shed_writes.
        self.partitioned = False
        self.frozen_metrics: Optional[ForwardPassMetrics] = None
        self.disk_full = False
        self.shed_writes = 0
        self._timer: Optional[asyncio.TimerHandle] = None
        self._crash_timer: Optional[asyncio.TimerHandle] = None
        self._last_t = 0.0

    # ------------------------------------------------------------ lifecycle
    @property
    def link(self):
        return self.fleet.links.get(self.worker_id)

    @property
    def endpoint(self):
        return (self.fleet.prefill_endpoint if self.prefill_only
                else self.fleet.endpoint)

    async def register(self) -> None:
        """Write the REAL discovery + stats records the production Client
        watches/scrapes."""
        loop = asyncio.get_running_loop()
        self.started_at = self._last_t = loop.time()
        ep = self.endpoint
        info = ComponentEndpointInfo(
            subject=ep.subject(self.worker_id), worker_id=self.worker_id,
            component=ep.component, endpoint=ep.name,
            namespace=ep.namespace, draining=False)
        store = self.fleet.runtime.store
        await store.kv_put(ep.discovery_key(self.worker_id), info.to_json())
        await store.kv_put(ep.stats_key(self.worker_id), self.stats_json())
        if self.profile.crash_at_s > 0:
            self._crash_timer = loop.call_later(self.profile.crash_at_s,
                                                self.crash)
        if self.profile.slow_start_s > 0:
            # ramp-end boundary: re-evaluate event times at full speed
            loop.call_later(self.profile.slow_start_s, self._fire)

    # -------------------------------------------------------------- engine
    @property
    def active_slots(self) -> int:
        return len(self.decoding) + (1 if self.prefill is not None else 0)

    @property
    def idle(self) -> bool:
        return (not self.waiting and self.prefill is None
                and not self.decoding)

    def submit(self, req: SimRequest) -> None:
        if self.dead:
            self.fleet.on_requests_lost([req])
            return
        req.worker_id = self.worker_id
        if self.tenant_table is not None:
            # fair-share order (WDRR + QoS): cost = the request's new
            # prefill blocks, so a flooding tenant's LONG prompts spend
            # its deficit faster, exactly like its flood rate does
            self.waiting.push(
                req, tenant=req.spec.tenant,
                cost=max(req.new_tokens / self.block_size, 1.0))
        else:
            self.waiting.append(req)
        self._fire()

    def _speed(self, now: float) -> float:
        return self.profile.speed_factor(now - self.started_at)

    def _advance(self, now: float) -> None:
        dt = now - self._last_t
        self._last_t = now
        if dt <= 0:
            return
        speed = self._speed(now)
        if self.prefill is not None:
            p = self.prefill
            left = dt
            if p.fetch_left > 0:
                used = min(p.fetch_left, left)
                p.fetch_left -= used
                left -= used
            if left > 0:
                p.tokens_left -= left * self.perf.prefill_tok_per_s * speed
        if self.decoding:
            step = self.perf.step_time_s(len(self.decoding)) / speed
            adv = dt / step
            for d in self.decoding:
                d.tokens_left -= adv

    def _fire(self) -> None:
        if self.dead:
            return
        loop = asyncio.get_running_loop()
        now = loop.time()
        self._advance(now)
        if (self.prefill is not None and self.prefill.fetch_left <= _EPS
                and self.prefill.tokens_left <= _EPS):
            p, self.prefill = self.prefill, None
            self._prefill_done(p, now)
        if self.decoding:
            done = [d for d in self.decoding if d.tokens_left <= _EPS]
            if done:
                self.decoding = [d for d in self.decoding
                                 if d.tokens_left > _EPS]
                for d in done:
                    self.decodes_done += 1
                    self.fleet.on_complete(d.req, self)
        while (self.prefill is None and self.waiting
               and (self.prefill_only
                    or len(self.decoding) < self.slots)):
            req = self.waiting.popleft()
            self.prefill = _Prefill(req, now)
            if self.prefill.fetch_left <= _EPS \
                    and self.prefill.tokens_left <= _EPS:
                p, self.prefill = self.prefill, None
                self._prefill_done(p, now)
            else:
                break
        if self.draining and self.idle and not self.dead:
            self.exit(clean=True)
            return
        self._reschedule(now)

    def _prefill_done(self, p: _Prefill, now: float) -> None:
        req = p.req
        self.prefills_done += 1
        wall = now - p.started_t
        if req.new_tokens > 0 and wall > 0:
            # the REAL age-weighted estimator: slow-start (compile-
            # shaped) samples are excluded/decayed exactly as on a
            # young live engine
            self.estimator.observe(req.new_tokens, wall)
        self._register_blocks(req.hashes, tenant=req.spec.tenant)
        if req.kind == "prefill":
            self.fleet.on_prefill_handoff(req, self)
            return
        req.first_t = now
        self.fleet.on_first_token(req, self)
        if req.spec.osl <= 1:
            self.fleet.on_complete(req, self)
        else:
            self.decoding.append(_Decode(req))

    def _next_dt(self, now: float) -> Optional[float]:
        speed = self._speed(now)
        cands = []
        if self.prefill is not None:
            p = self.prefill
            cands.append(max(p.fetch_left, 0.0)
                         + max(p.tokens_left, 0.0)
                         / (self.perf.prefill_tok_per_s * speed))
        if self.decoding:
            step = self.perf.step_time_s(len(self.decoding)) / speed
            rem = min(d.tokens_left for d in self.decoding)
            cands.append(max(rem, 0.0) * step)
        if not cands:
            return None
        dt = min(cands)
        if self.profile.slow_start_s > 0:
            ramp_left = (self.started_at + self.profile.slow_start_s) - now
            if 0 < ramp_left < dt:
                dt = ramp_left
        return max(dt, _MIN_DT)

    def _reschedule(self, now: float) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        dt = self._next_dt(now)
        if dt is not None:
            self._timer = asyncio.get_running_loop().call_at(
                now + dt, self._fire)

    # ----------------------------------------------------------- KV model
    def _register_blocks(self, hashes: List[int],
                         tenant: Optional[str] = None) -> None:
        """Device-tier residency with chained stored-announces: the
        longest already-resident prefix is touched (LRU), the suffix is
        announced tier=device off its parent — feeding the REAL radix
        indexer the router queries. With tenancy on, new blocks are
        noted in the worker's ledger and eviction victims come from an
        OVER-QUOTA tenant first (bounded LRU-front scan — the device
        pool's quota preference, llm/tenancy.py)."""
        resident = self.resident
        i = 0
        for h in hashes:
            if h in resident:
                resident.move_to_end(h)
                i += 1
            else:
                break
        new = hashes[i:]
        if new:
            parent = hashes[i - 1] if i > 0 else None
            for h in new:
                resident[h] = None
                self.host_resident.pop(h, None)
                if self.ledger is not None:
                    self.ledger.forget(h, "host")
                    self.ledger.note(h, tenant, "device")
            self.fleet.apply_kv_event(RouterEvent(
                worker_id=self.worker_id,
                stored=KvStoredEvent(parent_hash=parent, block_hashes=new)))
        evicted = []
        while len(resident) > self.kv_blocks:
            victim = None
            if self.ledger is not None:
                for j, h in enumerate(resident):
                    if j >= 64:
                        break
                    if self.ledger.is_over_quota_hash(h, "device"):
                        victim = h
                        break
            if victim is None:
                victim, _ = resident.popitem(last=False)
            else:
                resident.pop(victim)
                self.fleet.counters["tenant_evictions"] += 1
            if self.ledger is not None:
                self.ledger.forget(victim, "device")
            evicted.append(victim)
        if evicted:
            self._demote(evicted)

    # ----------------------------------------------------- chaos controls
    def set_brownout(self, latency_factor: float,
                     partition: bool = True) -> None:
        """Slow-not-dead: inflate every service time ``latency_factor``×
        and (optionally) freeze the worker's published stats — the
        router/planner keep seeing the pre-brownout numbers, exactly the
        stale-view a kvstore partition produces."""
        self.profile = BehaviorProfile(
            name=f"brownout:{latency_factor:g}",
            latency_factor=latency_factor)
        self.partitioned = partition
        if partition and self.frozen_metrics is None:
            self.frozen_metrics = ForwardPassMetrics.from_dict(
                self.refresh_metrics().to_dict())
        if not partition:
            self.frozen_metrics = None
        self._fire()                     # reschedule at the new speed

    def clear_brownout(self) -> None:
        self.profile = BehaviorProfile(name="steady")
        self.partitioned = False
        self.frozen_metrics = None
        self._fire()

    def scraped_metrics(self) -> ForwardPassMetrics:
        """What the router/planner see: live numbers, or the frozen
        pre-partition snapshot while the stats plane is dark."""
        if self.partitioned and self.frozen_metrics is not None:
            return self.frozen_metrics
        return self.refresh_metrics()

    def _demote(self, hashes: List[int]) -> None:
        """Device eviction → host-tier demote announce; host overflow →
        removed announce (the router's tier-weighted view tracks both).
        Under disk pressure (``disk_full``) the demote is SHED: the
        blocks leave the ladder immediately (removed announce) and the
        shed is counted — the sim analog of the spill pump's
        ENOSPC-shedding (diskstore.DiskSpillEngine.shed_writes_total)."""
        if self.disk_full:
            self.shed_writes += len(hashes)
            self.fleet.on_shed_writes(self, len(hashes))
            self.fleet.apply_kv_event(RouterEvent(
                worker_id=self.worker_id,
                removed=KvRemovedEvent(block_hashes=list(hashes))))
            return
        host = self.host_resident
        for h in hashes:
            host[h] = None
            if self.ledger is not None:
                self.ledger.note(h, None, "host")   # owner from ledger memory
        self.fleet.apply_kv_event(RouterEvent(
            worker_id=self.worker_id,
            stored=KvStoredEvent(parent_hash=None, block_hashes=hashes,
                                 tier="host")))
        removed = []
        while len(host) > self.host_blocks:
            victim = None
            if self.ledger is not None:
                for j, h in enumerate(host):
                    if j >= 64:
                        break
                    if self.ledger.is_over_quota_hash(h, "host"):
                        victim = h
                        break
            if victim is None:
                victim, _ = host.popitem(last=False)
            else:
                host.pop(victim)
                self.fleet.counters["tenant_evictions"] += 1
            if self.ledger is not None:
                self.ledger.forget(victim, "host")
            removed.append(victim)
        if removed:
            self.fleet.apply_kv_event(RouterEvent(
                worker_id=self.worker_id,
                removed=KvRemovedEvent(block_hashes=removed)))

    def flush_kv(self) -> int:
        """The fleet-wide prefix-cache-flush fault: drop every resident
        block and announce the removals (an eviction storm for the
        router index)."""
        hashes = list(self.resident) + list(self.host_resident)
        if self.ledger is not None:
            for h in self.resident:
                self.ledger.forget(h, "device")
            for h in self.host_resident:
                self.ledger.forget(h, "host")
        self.resident.clear()
        self.host_resident.clear()
        if hashes:
            self.fleet.apply_kv_event(RouterEvent(
                worker_id=self.worker_id,
                removed=KvRemovedEvent(block_hashes=hashes)))
        return len(hashes)

    # -------------------------------------------------------------- stats
    def refresh_metrics(self) -> ForwardPassMetrics:
        m = self.metrics
        m.request_active_slots = self.active_slots
        m.request_total_slots = self.slots
        m.num_requests_waiting = len(self.waiting)
        # ACTIVE usage = blocks pinned by in-flight requests (the
        # planner's kv_util pressure signal and the scheduler's load
        # metric) — NOT the resident cache, which like any LRU sits at
        # capacity forever once warm
        pinned = sum(len(d.req.hashes) for d in self.decoding)
        if self.prefill is not None:
            pinned += len(self.prefill.req.hashes)
        m.kv_active_blocks = pinned
        m.kv_total_blocks = self.kv_blocks
        m.gpu_cache_usage_perc = min(pinned / max(self.kv_blocks, 1), 1.0)
        link = self.link
        m.remote_link_gbps = link.gbps
        m.remote_link_rtt_s = link.rtt_s
        m.kv_bytes_per_block = self.perf.kv_bytes_per_block
        m.kv_block_size = self.block_size
        m.prefill_tok_per_s = self.estimator.rate()
        m.remote_admission_rejects_total = self.gate.rejects_total
        if self.fleet.cfg.stream_layers > 0:
            # streaming handoff plane on: publish the pipeline depth so
            # the REAL scoring path (network_adjusted_overlap /
            # crossover_tokens) prices this worker's fetches overlapped
            m.disagg_stream_layers = self.fleet.cfg.stream_layers
        if self.ledger is not None:
            # per-tenant residency (the nv_llm_tenant_kv_blocks shape);
            # admission/throttle counters live fleet-side in the sim
            m.tenant_stats = {
                t: {"admitted": 0, "throttled": 0,
                    "kv_blocks": sum(tiers.values()), "hit_rate": 0.0}
                for t, tiers in sorted(self.ledger.snapshot().items())}
        return m

    def stats_json(self) -> bytes:
        return json.dumps(self.refresh_metrics().to_dict()).encode()

    # --------------------------------------------------------------- drain
    def begin_drain(self) -> None:
        if self.dead or self.draining:
            return
        if self.profile.drain_ignore:
            self.fleet.log_event("drain_ignored", worker=self.worker_id)
            return
        self.draining = True
        self.fleet.on_drain_begin(self)
        ep = self.endpoint
        info = ComponentEndpointInfo(
            subject=ep.subject(self.worker_id), worker_id=self.worker_id,
            component=ep.component, endpoint=ep.name,
            namespace=ep.namespace, draining=True)
        self.fleet.spawn(self.fleet.runtime.store.kv_put(
            ep.discovery_key(self.worker_id), info.to_json()))
        if self.idle:
            self.exit(clean=True)

    # ---------------------------------------------------------------- exit
    def _cancel_timers(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._crash_timer is not None:
            self._crash_timer.cancel()
            self._crash_timer = None

    def exit(self, clean: bool) -> None:
        """Drain-to-exit (clean) or planner force-retire after a drain
        timeout (in-flight work is CUT — reported as lost)."""
        if self.dead:
            return
        self.dead = True
        self.exited_clean = clean
        self._cancel_timers()
        cut = ([p.req for p in ([self.prefill] if self.prefill else [])]
               + [d.req for d in self.decoding] + list(self.waiting))
        self.prefill = None
        self.decoding = []
        self.waiting.clear()
        self.fleet.on_worker_exit(self, clean=clean and not cut)
        if cut:
            self.fleet.on_requests_lost(cut)

    def crash(self) -> None:
        """The crash-at-T fault: in-flight requests lost, discovery entry
        gone (the router/planner see a vanished instance)."""
        if self.dead:
            return
        self.dead = True
        self._cancel_timers()
        lost = ([p.req for p in ([self.prefill] if self.prefill else [])]
                + [d.req for d in self.decoding] + list(self.waiting))
        self.prefill = None
        self.decoding = []
        self.waiting.clear()
        self.fleet.on_worker_crash(self)
        if lost:
            self.fleet.on_requests_lost(lost)
