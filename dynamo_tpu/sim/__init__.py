"""Fleet-scale co-simulation (docs/fleet_sim.md).

A deterministic discrete-event simulator that runs the REAL control
plane — the SLA planner (components/planner.py), the KV router
(kv_router/{indexer,scheduler,scoring}.py), the disagg-threshold retune,
and the fabric admission gate (llm/kv/fabric.py) — against hundreds of
simulated replicas whose prefill/decode/KV-transfer timing comes from
the device models already in-repo (parallel/ici_model.py, the
sim/models.py step-time line, the fabric PeerLinkTable cost model).

The whole fleet runs on a VIRTUAL clock (sim/clock.py): a simulated hour
of bursty trace-driven traffic over 200+ replicas completes in seconds
of tier-1 CPU time, and a fixed seed reproduces a byte-identical event
log — the determinism gate every scenario test asserts.

Lazy exports (PEP 562): light consumers — the mock worker pulling
:class:`BehaviorProfile`, tooling reading the trace format — must not
drag the full fleet/engine import chain in; only touching the fleet or
scenario surface does.
"""

_LAZY = {
    "VirtualClock": ".clock", "run_simulation": ".clock",
    "FleetConfig": ".fleet", "SimFleet": ".fleet",
    "BehaviorProfile": ".profiles",
    "EventLog": ".report",
    "SCENARIOS": ".scenarios", "run_scenario": ".scenarios",
    "check_report": ".scenarios",
    "Workload": ".workload", "generate_workload": ".workload",
}

__all__ = sorted(_LAZY)


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(mod, __name__), name)
