"""Scenario library: the named fleet storms the ROADMAP asks the control
plane to survive, each returning a report with its violated expectations
(empty = pass). ``fleetsim`` (tools/fleetsim.py) and the tier-1 sim
tests are thin wrappers over :func:`run_scenario`.

Every scenario is deterministic under its seed: the report carries the
event-log digest, and running the same (scenario, seed) twice must
produce byte-identical logs — the gate in tests/test_fleet_sim.py.
"""

from __future__ import annotations

import asyncio
import dataclasses
from typing import Callable, Dict, List, Tuple

from ..components.planner import PlannerConfig
from ..llm.slo import ServiceLevelObjective
from .clock import REAL_PERF_COUNTER, run_simulation
from .fleet import FleetConfig, SimFleet
from .models import WorkerPerfModel
from .workload import Workload, generate_workload

__all__ = ["SCENARIOS", "Scenario", "run_scenario", "check_report"]


# Fleet-class perf points (measured-fit shapes scaled to model class;
# sim/models.py pulls the llama8b device-step fit when the bench ledger
# is present):
def _perf_small() -> WorkerPerfModel:
    return WorkerPerfModel(prefill_tok_per_s=3000.0,
                                      step_base_s=0.03,
                                      step_per_seq_s=0.005)


def _perf_large() -> WorkerPerfModel:
    # a 70B-class replica: slow steps, slow prefill — 200 of these are
    # meaningfully loaded by tens of rps
    return WorkerPerfModel(prefill_tok_per_s=800.0, step_base_s=0.12,
                           step_per_seq_s=0.02, tp=8, hidden=8192,
                           num_layers=80, kv_bytes_per_block=1 << 21)


@dataclasses.dataclass
class Scenario:
    name: str
    description: str
    build: Callable[..., Tuple[FleetConfig, Workload, tuple, float]]
    check: Callable[[SimFleet, dict], List[str]]


def _fault_mass_drain(count: int):
    """Ops-driven storm: drain keys written for ``count`` workers AT
    ONCE through the real drain protocol (store key → fleet watch →
    worker re-announce draining → drain-to-exit). Like real node-pool
    rotation tooling, it respects the fleet's min_decode_workers floor
    — the planner may already have shrunk the fleet by the time the
    rotation fires."""

    def fault(fleet: SimFleet) -> None:
        live = sorted(w for w, x in fleet.workers.items()
                      if not x.dead and w not in fleet.draining)
        floor = fleet.cfg.slo.min_decode_workers if fleet.cfg.slo else 1
        n = min(count, max(len(live) - floor, 0))
        for wid in live[-n:] if n else []:
            fleet.spawn(fleet.runtime.store.kv_put(
                fleet.endpoint.drain_key(wid), b"{}"))
    return fault


def _fault_crash(count: int, stagger_s: float = 3.0):
    def fault(fleet: SimFleet) -> None:
        loop = asyncio.get_running_loop()
        live = sorted(w for w, x in fleet.workers.items() if not x.dead)
        for i, wid in enumerate(live[-count:]):
            loop.call_later(i * stagger_s, fleet.workers[wid].crash)
    return fault


def _fault_flush(fleet: SimFleet) -> None:
    n = sum(w.flush_kv() for w in fleet.workers.values() if not w.dead)
    fleet.log.log("prefix_flush", blocks=n)


def _fault_brownout(count: int, latency_factor: float = 8.0):
    """Slow-not-dead + stats partition: ``count`` workers keep serving
    at latency_factor× while their published stats freeze — the router
    and planner keep seeing the healthy pre-brownout numbers (the
    kvstore-partition shape). Deterministic victim choice (sorted)."""

    def fault(fleet: SimFleet) -> None:
        live = sorted(w for w, x in fleet.workers.items() if not x.dead)
        for wid in live[-count:]:
            fleet.workers[wid].set_brownout(latency_factor,
                                            partition=True)
            fleet.log.log("brownout", worker=wid,
                          factor=latency_factor)
    return fault


def _fault_brownout_recover(fleet: SimFleet) -> None:
    for wid, w in sorted(fleet.workers.items()):
        if not w.dead and w.partitioned:
            w.clear_brownout()
            fleet.log.log("brownout_recover", worker=wid)


def _fault_disk_pressure(full: bool):
    """ENOSPC mid-spill fleet-wide: every worker's demote tier refuses
    writes; the write-behind SHEDS (counted) and serving continues."""

    def fault(fleet: SimFleet) -> None:
        for wid, w in sorted(fleet.workers.items()):
            if not w.dead:
                w.disk_full = full
        fleet.log.log("disk_pressure", full=full)
    return fault


# --------------------------------------------------------------- builders
def _baseline_hour(seed: int, replicas: int = 200,
                   duration_s: float = 3600.0):
    slo = ServiceLevelObjective(
        ttft_p90_ms=6000.0, itl_p90_ms=400.0, max_queue_depth=3.0,
        min_decode_workers=max(replicas - 10, 1),
        max_decode_workers=replicas + 30)
    cfg = FleetConfig(
        replicas=replicas, slots=2, kv_blocks=384, host_blocks=192,
        perf=_perf_large(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=5.0, cooldown_s=60.0,
                                  breach_cycles=3, scale_step=4,
                                  drain_timeout_s=240.0, drain_poll_s=1.0,
                                  status_interval_s=30.0),
        stats_interval_s=5.0, scrape_interval_s=2.0,
        provision_delay_s=30.0, drainout_s=600.0)
    # bursty diurnal mix sized to ~30% mean utilization of the 200-
    # replica fleet (capacity ≈ replicas·slots/service_s ≈ 19 rps) —
    # request count is the sim's wall-clock driver, so the load sits
    # where the planner still sees real pressure at peak without
    # burning tier-1 budget on idle-ish requests
    wl = generate_workload(duration_s, seed, base_rps=2.5, peak_rps=8.0,
                           agentic_frac=0.4, long_tail_frac=0.03,
                           osl_base=64, osl_spread=128)
    return cfg, wl, (), duration_s


def _check_baseline(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if r["requests"]["completed"] < 0.98 * r["requests"]["arrived"]:
        v.append("fewer than 98% of requests completed")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} requests")
    if r["slo"]["ttft_attainment"] < 0.9:
        v.append(f"TTFT attainment {r['slo']['ttft_attainment']} < 0.9")
    if r["planner"]["counters"]["evaluations"] < 100:
        v.append("planner barely ran")
    if r["router"]["hit_rate_blocks"] <= 0.05:
        v.append("prefix reuse never materialized")
    return v


def _scale_storm(seed: int, replicas: int = 12,
                 duration_s: float = 1500.0):
    slo = ServiceLevelObjective(
        ttft_p90_ms=4000.0, itl_p90_ms=400.0, max_queue_depth=2.0,
        min_decode_workers=max(replicas // 2, 2),
        max_decode_workers=replicas + 16)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=20.0,
                                  breach_cycles=3, scale_step=2,
                                  drain_timeout_s=120.0, drain_poll_s=0.5,
                                  status_interval_s=10.0),
        stats_interval_s=2.0, scrape_interval_s=1.0,
        provision_delay_s=15.0, new_worker_profile="slow-start:20",
        drainout_s=600.0)
    wl = generate_workload(duration_s, seed, base_rps=1.0, peak_rps=1.8,
                           burst_at=240.0, burst_len_s=600.0,
                           burst_factor=6.0, osl_base=64, osl_spread=128)
    return cfg, wl, (), duration_s


def _check_scale_storm(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if r["planner"]["counters"]["scale_up"] < 2:
        v.append("planner never scaled into the storm")
    if r["replicas"]["peak"] < r["replicas"]["start"] + 4:
        v.append("fleet did not grow under the burst")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} requests")
    if r["requests"]["completed"] < 0.99 * r["requests"]["arrived"]:
        v.append("storm lost requests")
    # SLO attainment once the scale-out landed (late window)
    if r["slo"]["late_attainment"] < 0.85:
        v.append(f"late-window TTFT attainment "
                 f"{r['slo']['late_attainment']} < 0.85")
    return v


def _drain_storm(seed: int, replicas: int = 24,
                 duration_s: float = 1400.0):
    slo = ServiceLevelObjective(
        ttft_p90_ms=4000.0, itl_p90_ms=400.0, max_queue_depth=3.0,
        min_decode_workers=6, max_decode_workers=replicas + 4)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=15.0,
                                  breach_cycles=3, scale_step=2,
                                  drain_timeout_s=200.0, drain_poll_s=0.5,
                                  status_interval_s=10.0),
        stats_interval_s=2.0, scrape_interval_s=1.0, drainout_s=600.0)
    # heavy first third, then the load collapses — the planner should
    # drain the excess; at t=500 ops additionally mass-drains 8 workers
    wl = generate_workload(duration_s / 3.0, seed, base_rps=3.0,
                           peak_rps=6.0, osl_base=64, osl_spread=128)
    faults = ((duration_s / 3.0 + 60.0, "mass_drain",
               _fault_mass_drain(8)),)
    return cfg, wl, faults, duration_s


def _check_drain_storm(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} in-flight requests")
    if r["requests"]["completed"] != r["requests"]["arrived"]:
        v.append("not every admitted request completed")
    if r["requests"]["forced_exits"]:
        v.append("a drain was forced (in-flight work cut)")
    if r["requests"]["clean_exits"] < 8:
        v.append("mass drain did not retire 8 workers cleanly")
    if r["planner"]["counters"]["drains_completed"] < 1:
        v.append("planner never drained the idle excess")
    if r["replicas"]["end"] >= r["replicas"]["start"]:
        v.append("fleet did not shrink after the load collapsed")
    return v


def _crash_cascade(seed: int, replicas: int = 16,
                   duration_s: float = 1000.0):
    slo = ServiceLevelObjective(
        ttft_p90_ms=5000.0, itl_p90_ms=400.0, max_queue_depth=2.0,
        min_decode_workers=replicas - 2, max_decode_workers=replicas + 8)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=20.0,
                                  breach_cycles=3, scale_step=2,
                                  drain_timeout_s=120.0, drain_poll_s=0.5,
                                  status_interval_s=10.0),
        stats_interval_s=2.0, scrape_interval_s=1.0,
        provision_delay_s=15.0, max_retries=5, drainout_s=600.0)
    wl = generate_workload(duration_s * 0.6, seed, base_rps=5.0,
                           peak_rps=8.0, osl_base=64, osl_spread=128)
    faults = ((300.0, "crash_cascade", _fault_crash(5, stagger_s=3.0)),)
    return cfg, wl, faults, duration_s


def _check_crash_cascade(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if r["requests"]["crashes"] != 5:
        v.append("expected exactly 5 crashes")
    if r["requests"]["dropped"]:
        v.append(f"retries did not absorb the cascade: "
                 f"{r['requests']['dropped']} dropped")
    if r["requests"]["completed"] != r["requests"]["arrived"]:
        v.append("not every request completed after the cascade")
    if r["planner"]["counters"]["scale_up"] < 1:
        v.append("planner never responded to the crash-induced pressure")
    if r["replicas"]["end"] < r["replicas"]["start"] - 4:
        v.append("planner never replaced the crashed replicas")
    return v


def _prefix_flush(seed: int, replicas: int = 10,
                  duration_s: float = 1200.0):
    slo = ServiceLevelObjective(
        ttft_p90_ms=6000.0, itl_p90_ms=400.0, max_queue_depth=4.0,
        min_decode_workers=replicas, max_decode_workers=replicas + 6)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=4096, host_blocks=1024,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=5.0, cooldown_s=30.0,
                                  status_interval_s=20.0),
        stats_interval_s=5.0, scrape_interval_s=2.0, drainout_s=600.0)
    # agentic-heavy: deep prefix reuse builds up, then the flush storm
    wl = generate_workload(duration_s, seed, base_rps=3.0, peak_rps=6.0,
                           tenants=4, agentic_frac=0.7,
                           osl_base=48, osl_spread=96)
    faults = ((600.0, "prefix_flush", _fault_flush),)
    return cfg, wl, faults, duration_s


def _check_prefix_flush(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    flush_t = next((t for t, f in fleet.log.of_kind("fault")
                    if f.get("name") == "prefix_flush"), None)
    if flush_t is None:
        return ["flush fault never fired"]
    flushed = next((f["blocks"] for _, f in
                    fleet.log.of_kind("prefix_flush")), 0)
    if flushed < 500:
        v.append(f"flush removed only {flushed} blocks — no storm")
    pre, post = [], []
    for t, f in fleet.log.of_kind("route"):
        frac = f["hit"] / max(f["blocks"], 1)
        if flush_t - 300 <= t < flush_t:
            pre.append(frac)
        elif flush_t <= t < flush_t + 15:
            post.append(frac)
    if not pre or not post:
        return ["no routed traffic around the flush"]
    pre_hit = sum(pre) / len(pre)
    post_hit = sum(post) / len(post)
    if pre_hit < 0.2:
        v.append(f"prefix reuse never warmed up (pre-flush hit {pre_hit:.2f})")
    # the crater is short — in-flight prefills re-register hot chains
    # within seconds — so measure right after the flush
    if post_hit > 0.85 * pre_hit:
        v.append(f"flush did not cool the prefix cache "
                 f"(hit {pre_hit:.2f} → {post_hit:.2f})")
    # the recompute storm must show up as a TTFT spike after the flush
    from ..llm.slo import percentile
    pre_ttft = percentile([f["ttft_ms"] for t, f in
                           fleet.log.of_kind("complete")
                           if flush_t - 300 <= t < flush_t], 90)
    post_ttft = percentile([f["ttft_ms"] for t, f in
                            fleet.log.of_kind("complete")
                            if flush_t <= t < flush_t + 120], 90)
    if pre_ttft is not None and post_ttft is not None \
            and post_ttft <= pre_ttft:
        v.append("flush produced no recompute-storm TTFT spike")
    if r["requests"]["completed"] < 0.98 * r["requests"]["arrived"]:
        v.append("fleet did not keep serving through the flush")
    if r["requests"]["dropped"]:
        v.append("flush dropped requests")
    return v


def _oscillate(seed: int, replicas: int = 6, duration_s: float = 900.0):
    """Anti-thrash: load oscillating across the scale-up boundary FASTER
    than the hysteresis window — the planner must hold, not flap."""
    # latency SLOs are deliberately loose: TTFT rides a 180s collector
    # window (a LAGGING indicator by design), so an oscillation test on
    # the hysteresis boundary drives the INSTANT signals — queue depth
    # and slot utilization — across their thresholds instead
    slo = ServiceLevelObjective(
        ttft_p90_ms=60000.0, itl_p90_ms=5000.0, max_queue_depth=2.0,
        min_decode_workers=replicas - 2, max_decode_workers=replicas + 6,
        slot_util_low=0.05)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        # breach must persist 6 consecutive 5s evaluations = 30s; the
        # 20s-period load breaches for only ~5-10s per crest before the
        # trough drains the backlog — hysteresis must hold through it
        planner_cfg=PlannerConfig(interval_s=5.0, cooldown_s=30.0,
                                  breach_cycles=6, status_interval_s=15.0),
        stats_interval_s=2.0, scrape_interval_s=1.0, drainout_s=300.0)
    wl = generate_workload(duration_s, seed, base_rps=0.3, peak_rps=5.0,
                           period_s=20.0, osl_base=48, osl_spread=96)
    return cfg, wl, (), duration_s


def _check_oscillate(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    c = r["planner"]["counters"]
    if c["evaluations"] < 100:
        v.append("planner barely evaluated")
    # the load must actually CROSS the scale-up boundary (instantaneous
    # fleet queue depth above the SLO threshold at some samples)...
    slo = fleet.cfg.slo
    peaks = sum(1 for _, f in fleet.log.of_kind("load_sample")
                if f["queue_depth"] > slo.max_queue_depth
                or f["slot_util"] > slo.slot_util_high)
    if peaks < 3:
        v.append("load never crossed the scale-up boundary — "
                 "the anti-thrash case was not exercised")
    # ...while breach-cycle hysteresis keeps the planner from flapping
    flaps = c["scale_up"] + c["drains_started"]
    if flaps > 1:
        v.append(f"planner flapped under oscillating load "
                 f"({flaps} actions)")
    if r["requests"]["dropped"]:
        v.append("oscillation dropped requests")
    return v


def _disagg_retune(seed: int, replicas: int = 8,
                   duration_s: float = 1000.0,
                   link_gbps: float = 10.0, link_rtt_s: float = 1e-3):
    slo = ServiceLevelObjective(
        ttft_p90_ms=1500.0, itl_p90_ms=500.0, max_queue_depth=2.0,
        min_decode_workers=replicas, max_decode_workers=replicas,
        # pin the prefill tier: this scenario proves the RETUNE lever,
        # so the (round-12) prefill-fleet actuator is held at its start
        # size rather than absorbing the backlog the retune should see
        min_prefill_workers=2, max_prefill_workers=2,
        max_local_prefill_length=512)
    cfg = FleetConfig(
        replicas=replicas, prefill_replicas=2, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo, link_gbps=link_gbps,
        link_rtt_s=link_rtt_s,
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=20.0,
                                  status_interval_s=10.0),
        stats_interval_s=2.0, scrape_interval_s=1.0, drainout_s=400.0)
    # long-prompt traffic: most prefills cross the 512-token threshold,
    # the 2-replica prefill tier backs up, the planner retunes UP; when
    # the queue clears under TTFT pressure it retunes back DOWN —
    # floored at the fleet's fetch-vs-recompute crossover
    wl = generate_workload(duration_s * 0.7, seed, base_rps=2.0,
                           peak_rps=6.0, isl_base=1024, isl_spread=2048,
                           agentic_frac=0.1, long_tail_frac=0.0,
                           osl_base=32, osl_spread=64)
    return cfg, wl, (), duration_s


def _prefill_storm(seed: int, replicas: int = 10,
                   duration_s: float = 1400.0):
    """Prefill-as-a-Service proving ground (ISSUE 12 rung (c)): a
    prefix-MISS surge — long fresh-session prompts with no reuse —
    drives the prefill queue while the decode tier stays comfortable;
    the planner must scale the PREFILL tier out (the new actuator, not
    the decode one or the retune) and late-window SLO must recover."""
    slo = ServiceLevelObjective(
        ttft_p90_ms=4000.0, itl_p90_ms=400.0, max_queue_depth=2.0,
        # decode pinned: the storm is a prefill-capacity problem
        min_decode_workers=replicas, max_decode_workers=replicas,
        min_prefill_workers=2, max_prefill_workers=12,
        max_local_prefill_length=256)
    cfg = FleetConfig(
        replicas=replicas, prefill_replicas=2, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        # retune_max == the threshold itself: the disagg-retune lever is
        # deliberately out of headroom, so only the prefill-fleet
        # actuator can absorb the storm
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=20.0,
                                  breach_cycles=3, scale_step=2,
                                  drain_timeout_s=120.0, drain_poll_s=0.5,
                                  status_interval_s=10.0,
                                  retune_max=256),
        stats_interval_s=2.0, scrape_interval_s=1.0,
        provision_delay_s=15.0, new_worker_profile="slow-start:20",
        drainout_s=600.0)
    # fresh long prompts (agentic_frac=0: every session is new, so the
    # prefix indexes miss) crossing the 256-token disagg threshold; the
    # surge quadruples arrivals for ~8 minutes
    wl = generate_workload(duration_s * 0.7, seed, base_rps=1.0,
                           peak_rps=1.6, burst_at=240.0, burst_len_s=480.0,
                           burst_factor=5.0, tenants=16,
                           agentic_frac=0.0, long_tail_frac=0.0,
                           isl_base=768, isl_spread=1024,
                           osl_base=32, osl_spread=64)
    return cfg, wl, (), duration_s


def _check_prefill_storm(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    c = r["planner"]["counters"]
    if r["requests"]["remote_prefills"] < 50:
        v.append("prefill queue barely exercised — storm never formed")
    if c.get("prefill_scale_up", 0) < 1:
        v.append("planner never scaled the prefill tier into the storm")
    if r["prefill_replicas"]["peak"] <= r["prefill_replicas"]["start"]:
        v.append("prefill tier did not grow under the surge")
    if c["scale_up"] != 0:
        v.append("decode tier scaled — the storm leaked out of the "
                 "prefill tier (decode is pinned by the SLO bounds)")
    if r["slo"]["late_attainment"] < 0.85:
        v.append(f"late-window TTFT attainment "
                 f"{r['slo']['late_attainment']} < 0.85 — scaling the "
                 f"prefill tier did not restore SLO")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} requests")
    return v


def _partition_brownout(seed: int, replicas: int = 12,
                        duration_s: float = 1400.0):
    """Chaos-hardening scenario (ISSUE 13): 3 replicas brown out at
    t=240 — serving 8× slower with FROZEN published stats (the router
    and planner see the stale healthy view) — and recover at t=700.
    The fleet must absorb the brownout without hanging or dropping:
    retries/queueing carry the slow window, the planner may scale into
    the pressure, and late-window SLO must recover once the brownout
    lifts."""
    slo = ServiceLevelObjective(
        ttft_p90_ms=5000.0, itl_p90_ms=600.0, max_queue_depth=3.0,
        min_decode_workers=replicas - 2, max_decode_workers=replicas + 8)
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=2.0, cooldown_s=20.0,
                                  breach_cycles=3, scale_step=2,
                                  drain_timeout_s=120.0, drain_poll_s=0.5,
                                  status_interval_s=10.0),
        stats_interval_s=2.0, scrape_interval_s=1.0,
        provision_delay_s=15.0, drainout_s=600.0)
    wl = generate_workload(duration_s * 0.7, seed, base_rps=2.0,
                           peak_rps=3.5, osl_base=48, osl_spread=96)
    faults = ((240.0, "brownout", _fault_brownout(3, 8.0)),
              (700.0, "brownout_recover", _fault_brownout_recover))
    return cfg, wl, faults, duration_s


def _check_partition_brownout(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if fleet.log.count("brownout") < 3:
        v.append("brownout fault never browned out 3 workers")
    if fleet.log.count("brownout_recover") < 3:
        v.append("browned-out workers never recovered")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} in-flight requests")
    if r["requests"]["completed"] != r["requests"]["arrived"]:
        v.append("not every request completed — something hung")
    # the brownout must actually BITE: TTFT p90 across the brownout
    # window above the pre-brownout window (slow-not-dead, not a no-op)
    from ..llm.slo import percentile
    pre = percentile([f["ttft_ms"] for t, f in
                      fleet.log.of_kind("complete") if t < 240.0], 90)
    mid = percentile([f["ttft_ms"] for t, f in
                      fleet.log.of_kind("complete")
                      if 260.0 <= t < 700.0], 90)
    if pre is not None and mid is not None and mid <= pre:
        v.append("brownout produced no TTFT degradation — "
                 "the fault was a no-op")
    if r["slo"]["late_attainment"] < 0.9:
        v.append(f"late-window TTFT attainment "
                 f"{r['slo']['late_attainment']} < 0.9 — SLO never "
                 f"recovered after the brownout lifted")
    return v


def _disk_pressure(seed: int, replicas: int = 8,
                   duration_s: float = 1200.0):
    """Chaos-hardening scenario (ISSUE 13): fleet-wide ENOSPC mid-spill
    at t=300 (every demote refused until t=700). Write-behind must SHED
    — cache blocks are lost, counted, and serving continues — with zero
    drops and late-window SLO recovered."""
    slo = ServiceLevelObjective(
        ttft_p90_ms=5000.0, itl_p90_ms=600.0, max_queue_depth=3.0,
        min_decode_workers=replicas, max_decode_workers=replicas + 4)
    cfg = FleetConfig(
        # small device tier + agentic reuse → steady demote pressure,
        # so the refused-writes window has real traffic to shed
        replicas=replicas, slots=4, kv_blocks=96, host_blocks=64,
        perf=_perf_small(), slo=slo,
        planner_cfg=PlannerConfig(interval_s=5.0, cooldown_s=30.0,
                                  status_interval_s=20.0),
        stats_interval_s=2.0, scrape_interval_s=1.0, drainout_s=600.0)
    wl = generate_workload(duration_s * 0.7, seed, base_rps=2.5,
                           peak_rps=5.0, tenants=4, agentic_frac=0.6,
                           osl_base=48, osl_spread=96)
    faults = ((300.0, "disk_pressure_on", _fault_disk_pressure(True)),
              (700.0, "disk_pressure_off", _fault_disk_pressure(False)))
    return cfg, wl, faults, duration_s


def _check_disk_pressure(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if fleet.log.count("disk_pressure") < 2:
        v.append("disk pressure fault never toggled on+off")
    if r["requests"]["shed_writes"] < 20:
        v.append(f"only {r['requests']['shed_writes']} writes shed — "
                 f"the pressure window never refused real spill traffic")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} requests — "
                 f"disk pressure must shed cache, not serving")
    if r["requests"]["completed"] != r["requests"]["arrived"]:
        v.append("not every request completed — something hung")
    if r["slo"]["late_attainment"] < 0.9:
        v.append(f"late-window TTFT attainment "
                 f"{r['slo']['late_attainment']} < 0.9")
    return v


NOISY_FLOOD_AT = 300.0
NOISY_FLOOD_LEN = 400.0


def _noisy_neighbor(seed: int, replicas: int = 10,
                    duration_s: float = 1200.0):
    """Multi-tenant fair-share proving ground (ISSUE 14,
    docs/multi_tenant.md): 8 Zipf-weighted tenants; at t=300 tenant t00
    FLOODS ~10× its organic rate for 400s. The fleet is PINNED (no
    scale-out escape hatch) so the only thing standing between the
    flood and everyone else is the tenant machinery: fair-share WDRR
    waiting queues throttle t00 to its weight share of service, and
    per-tenant KV quotas land its eviction storm on its OWN blocks.
    Victims must keep late-window SLO >= 0.9 and a flood-window prefix
    hit rate within 10% of their quiet baseline, with zero drops."""
    slo = ServiceLevelObjective(
        ttft_p90_ms=5000.0, itl_p90_ms=600.0, max_queue_depth=30.0,
        # pinned: fairness carries the storm, not the planner
        min_decode_workers=replicas, max_decode_workers=replicas)
    policies = {f"t{i:02d}": {"weight": 1.0, "kv_quota_blocks": 192}
                for i in range(8)}
    cfg = FleetConfig(
        replicas=replicas, slots=4, kv_blocks=512, host_blocks=256,
        perf=_perf_small(), slo=slo,
        tenant_policies=policies,
        planner_cfg=PlannerConfig(interval_s=5.0, cooldown_s=60.0,
                                  status_interval_s=20.0),
        stats_interval_s=2.0, scrape_interval_s=1.0, drainout_s=600.0)
    # agentic mix builds every tenant's warm prefix state BEFORE the
    # flood, so the quota story (the flood must not crater the victims'
    # hit rate) has a real baseline to protect
    wl = generate_workload(duration_s * 0.85, seed, base_rps=0.8,
                           peak_rps=1.2, tenants=8, zipf_a=0.6,
                           agentic_frac=0.6, long_tail_frac=0.0,
                           osl_base=48, osl_spread=96,
                           flood_tenant="t00", flood_at=NOISY_FLOOD_AT,
                           flood_len_s=NOISY_FLOOD_LEN, flood_factor=10.0)
    return cfg, wl, (), duration_s


def _check_noisy_neighbor(fleet: SimFleet, r: dict) -> List[str]:
    from ..llm.slo import percentile
    v = []
    f0, f1 = NOISY_FLOOD_AT, NOISY_FLOOD_AT + NOISY_FLOOD_LEN
    arrivals = {}
    for _t, f in fleet.log.of_kind("arrive"):
        arrivals[f["tenant"]] = arrivals.get(f["tenant"], 0) + 1
    flood_n = arrivals.get("t00", 0)
    victim_n = sum(n for t, n in arrivals.items() if t != "t00")
    if flood_n < 2 * victim_n:
        v.append(f"flood never formed (t00 sent {flood_n} vs "
                 f"{victim_n} victim arrivals)")
    if r["requests"]["dropped"]:
        v.append(f"dropped {r['requests']['dropped']} requests")
    if r["requests"]["completed"] != r["requests"]["arrived"]:
        v.append("not every request completed — something starved")
    # victims' late-window SLO holds despite the flood
    cut = fleet.clock.now * 0.75
    late_victims = [f["ttft_ms"] for t, f in fleet.log.of_kind("complete")
                    if t >= cut and f["tenant"] != "t00"]
    slo = fleet.cfg.slo
    if late_victims:
        att = (sum(1 for x in late_victims if x <= slo.ttft_p90_ms)
               / len(late_victims))
        if att < 0.9:
            v.append(f"victim late-window TTFT attainment {att:.3f} < 0.9")
    else:
        v.append("no victim traffic in the late window")
    # the throttle: inside the flood window the flooder queues behind
    # its own backlog — its TTFT p90 must sit well above the victims'
    flood_ttft = percentile([f["ttft_ms"] for t, f in
                             fleet.log.of_kind("complete")
                             if f0 + 60 <= t < f1 and f["tenant"] == "t00"],
                            90)
    victim_ttft = percentile([f["ttft_ms"] for t, f in
                              fleet.log.of_kind("complete")
                              if f0 + 60 <= t < f1
                              and f["tenant"] != "t00"], 90)
    if flood_ttft is None or victim_ttft is None:
        v.append("flood window saw no completions on one side")
    elif flood_ttft < 1.5 * victim_ttft:
        v.append(f"flooder was not throttled: its in-flood TTFT p90 "
                 f"{flood_ttft:.0f}ms vs victims' {victim_ttft:.0f}ms")
    # quota isolation: victims' prefix hit rate in the flood window
    # stays within 10% of their pre-flood baseline
    def victim_hit(lo, hi):
        fr = [f["hit"] / max(f["blocks"], 1)
              for t, f in fleet.log.of_kind("route")
              if lo <= t < hi and f.get("tenant") not in (None, "t00")]
        return sum(fr) / len(fr) if fr else None
    pre = victim_hit(f0 - 200, f0)
    mid = victim_hit(f0 + 60, f1)
    if pre is None or mid is None:
        v.append("no victim routing around the flood window")
    else:
        if pre < 0.15:
            v.append(f"victims' prefix reuse never warmed up "
                     f"(pre-flood hit {pre:.2f})")
        if mid < 0.9 * pre:
            v.append(f"flood cratered victims' hit rate: "
                     f"{pre:.3f} → {mid:.3f} (>10% drop)")
    # the quota machinery must actually engage: the flooder's over-
    # quota blocks took preferred evictions
    if r["requests"].get("tenant_evictions", 0) < 10:
        v.append("tenant-quota eviction preference never engaged")
    if victim_n == 0:
        v.append("no victim arrivals at all (workload misconfigured)")
    return v


def _check_disagg_retune(fleet: SimFleet, r: dict) -> List[str]:
    v = []
    if r["requests"]["remote_prefills"] < 10:
        v.append("disagg path barely exercised")
    if r["planner"]["counters"]["retunes"] < 1:
        v.append("planner never retuned the disagg threshold")
    if not fleet.log.count("retune"):
        v.append("retune never reached the DisaggregatedRouter watch key")
    if r["requests"]["dropped"]:
        v.append("retune scenario dropped requests")
    return v


SCENARIOS: Dict[str, Scenario] = {
    "baseline_hour": Scenario(
        "baseline_hour",
        "200 replicas x 1 simulated hour of bursty diurnal mixed traffic "
        "with the real planner/router/retune in the loop",
        _baseline_hour, _check_baseline),
    "scale_storm": Scenario(
        "scale_storm",
        "sudden 6x burst; the planner must scale out and restore SLO",
        _scale_storm, _check_scale_storm),
    "drain_storm": Scenario(
        "drain_storm",
        "load collapse + ops mass-drain; zero dropped in-flight",
        _drain_storm, _check_drain_storm),
    "crash_cascade": Scenario(
        "crash_cascade",
        "staggered replica crashes; retries absorb, planner replaces",
        _crash_cascade, _check_crash_cascade),
    "prefix_flush": Scenario(
        "prefix_flush",
        "fleet-wide prefix-cache flush; hit rate craters then recovers",
        _prefix_flush, _check_prefix_flush),
    "oscillate": Scenario(
        "oscillate",
        "load oscillating across the scale boundary; planner must not flap",
        _oscillate, _check_oscillate),
    "disagg_retune": Scenario(
        "disagg_retune",
        "prefill-queue backlog drives the disagg threshold retune, "
        "floored at the fleet fetch-vs-recompute crossover",
        _disagg_retune, _check_disagg_retune),
    "prefill_storm": Scenario(
        "prefill_storm",
        "prefix-miss surge backs up the prefill queue; the planner "
        "scales the prefill tier out and SLO recovers",
        _prefill_storm, _check_prefill_storm),
    "partition_brownout": Scenario(
        "partition_brownout",
        "slow-not-dead replicas with frozen (partitioned) stats; zero "
        "hangs, zero drops, SLO recovers after the brownout lifts",
        _partition_brownout, _check_partition_brownout),
    "disk_pressure": Scenario(
        "disk_pressure",
        "fleet-wide ENOSPC mid-spill; write-behind sheds (counted), "
        "serving continues, SLO holds",
        _disk_pressure, _check_disk_pressure),
    "noisy_neighbor": Scenario(
        "noisy_neighbor",
        "one tenant floods 10x against a pinned fleet; fair-share WDRR "
        "throttles it to its share and KV quotas keep victims' hit "
        "rate intact (llm/tenancy.py; docs/multi_tenant.md)",
        _noisy_neighbor, _check_noisy_neighbor),
}


def _late_attainment(fleet: SimFleet, slo: ServiceLevelObjective) -> float:
    """TTFT attainment over the last quarter of the run (the post-
    stabilization window storm checks assert on)."""
    cut = fleet.clock.now * 0.75
    late = [f["ttft_ms"] for t, f in fleet.log.of_kind("complete")
            if t >= cut]
    if not late:
        return 1.0
    return sum(1 for x in late if x <= slo.ttft_p90_ms) / len(late)


def run_scenario(name: str, seed: int = 0, **overrides) -> dict:
    """Run one scenario to completion under virtual time; returns the
    report dict (report["violations"] lists failed expectations)."""
    sc = SCENARIOS[name]
    cfg, wl, faults, run_s = sc.build(seed, **overrides)

    async def main():
        fleet = await SimFleet(cfg, seed=seed).start()
        t_wall = REAL_PERF_COUNTER()
        await fleet.run(wl, faults=faults, duration_s=run_s)
        report = fleet.report(wall_s=REAL_PERF_COUNTER() - t_wall)
        report["scenario"] = name
        report["slo"]["late_attainment"] = round(
            _late_attainment(fleet, cfg.slo), 4)
        report["violations"] = sc.check(fleet, report)
        await fleet.stop()
        return report

    return run_simulation(main)


def check_report(report: dict) -> None:
    """Raise AssertionError listing every violated expectation."""
    if report.get("violations"):
        raise AssertionError(
            f"scenario {report.get('scenario')} violated: "
            + "; ".join(report["violations"]))
