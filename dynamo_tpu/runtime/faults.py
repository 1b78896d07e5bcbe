"""Deterministic failpoint registry — the chaos-hardening substrate.

Every cross-process boundary the fleet can lose (a daemon link, a disk,
a peer, a dial-back stream) is named as a **failpoint site**: a cheap
``faults.hit("site")`` call at the exact line where the real failure
would surface. Disarmed (the production state) a hit is one dict-truth
check — zero allocation, zero branches beyond ``if not _ARMED``. Armed,
a site deterministically injects the failure class the site declares:

- ``error[:msg]`` — raise (the caller's own failure type via
  ``exc=...`` at the hit, so retry ladders and fallback paths engage
  exactly as they would for the real fault);
- ``delay:ms``    — stall the call (slow-not-dead: the brownout shape);
- ``torn``        — truncate a byte payload mid-write/mid-frame
  (``faults.mangle``);
- ``enospc``      — raise ``OSError(ENOSPC)`` (disk-pressure shape);
- ``1-in-N,<action>`` — fire deterministically on every Nth hit of the
  site (a per-site counter, not a clock or RNG — two identical runs
  inject identically, the property the sim's byte-identical determinism
  gate and recorded replay both lean on).

Arming surfaces (all optional, all composable):

- env: ``DYN_FAULTS="netstore.call=1-in-3,error;wal.append=enospc"``
  parsed at import (subprocess workers inherit it);
- programmatic: :func:`arm` / :func:`disarm` / :func:`reset` (tests);
- fleet-wide: ``llmctl faults {set,clear,status}`` writes
  ``faults/control/{namespace}``; every worker running
  :func:`watch_faults_loop` (launch/run.py) applies the stored table
  live — the chaos-drill lever for a running fleet.

Discipline (docs/chaos.md):

- sites are REGISTERED here, in :data:`SITES` — ``hit()`` on an unknown
  name raises, so a typo'd site can never silently no-op;
- a site is never placed inside ``jax.jit``/``shard_map``/``pallas_call``
  bodies (DL005: traced code must stay pure — inject at the host
  boundary instead);
- async call sites use :func:`hit_async` (delays ride
  ``asyncio.sleep``); sync sites — thread-pool and daemon code — use
  :func:`hit` (the one deliberate ``time.sleep`` below is the injection
  itself);
- every registered site must be exercised by at least one test
  (tests/test_chaos.py coverage gate — an unreferenced site fails the
  suite).
"""

from __future__ import annotations

import asyncio
import dataclasses
import errno
import logging
import os
import re
import time
from typing import Dict, Optional, Type

logger = logging.getLogger("dynamo_tpu.runtime.faults")

__all__ = [
    "SITES",
    "FaultInjected",
    "arm",
    "disarm",
    "reset",
    "armed",
    "fired_count",
    "hit",
    "hit_async",
    "mangle",
    "faults_control_key",
    "watch_faults_loop",
    "arm_from_env",
]

FAULTS_ENV = "DYN_FAULTS"
FAULTS_PREFIX = "faults/"

# The failpoint catalog: every instrumented site, with the module that
# owns it and the failure class it models. hit() on a name not listed
# here raises KeyError — the registry is the single source of truth the
# coverage gate (tests/test_chaos.py) walks.
SITES: Dict[str, str] = {
    "netstore.call":
        "runtime/netstore.py — one daemon RPC attempt (flapping link)",
    "request.egress":
        "runtime/egress.py — request-plane publish toward a worker",
    "request.ingress":
        "runtime/ingress.py — worker-side accept of a decoded request",
    "kvstore.lease.keepalive":
        "runtime/kvstore.py — one lease refresh (liveness blip)",
    "wal.append":
        "runtime/wal.py — durable WAL append (full/failing disk)",
    "diskstore.write":
        "llm/kv/diskstore.py — block payload write (ENOSPC, torn npz)",
    "diskstore.recovery":
        "llm/kv/diskstore.py — manifest/payload read at warm start",
    "diskstore.spill":
        "llm/kv/diskstore.py — write-behind spill pump store",
    "remotestore.put":
        "llm/kv/remotestore.py — object-tier put (promotion pump sink)",
    "fabric.fetch":
        "llm/kv/fabric.py — one peer KV fetch (dead/slow peer)",
    "fabric.dialback":
        "llm/kv/fabric.py — serving peer's dataplane dial-back connect",
    "dataplane.frame":
        "llm/kv/fabric.py — one streamed block frame (torn mid-stream)",
    "prefill.publish":
        "engine/core.py — one prefix-block publish to the object tier",
    "engine.onboard":
        "engine/core.py — off-thread tier-hit onboard prep",
    "engine.harvest":
        "engine/core.py — post-dispatch harvest (loop-fatal boundary)",
    "disagg.layer_stream":
        "llm/kv/stream.py — one per-layer KV frame of a streamed handoff "
        "(torn mid-stream)",
}


class FaultInjected(RuntimeError):
    """Default injected error (sites may request their own class via
    ``exc=`` so production fallback paths engage)."""


_SPEC_RE = re.compile(
    r"^(?:1-in-(?P<n>\d+),)?"
    r"(?P<mode>error|delay|torn|enospc|off)(?::(?P<arg>.*))?$")


@dataclasses.dataclass
class _Armed:
    site: str
    mode: str                 # error | delay | torn | enospc
    every_n: int = 1          # fire on every Nth hit (deterministic)
    arg: str = ""             # error message / delay ms / torn fraction
    hits: int = 0             # total hits while armed
    fired: int = 0            # injections actually performed

    def due(self) -> bool:
        """Advance the per-site hit counter; True when this hit fires.
        Counter-based, so two identical runs inject identically."""
        self.hits += 1
        return self.hits % max(self.every_n, 1) == 0

    def delay_s(self) -> float:
        return float(self.arg or 10.0) / 1e3

    def describe(self) -> str:
        prefix = f"1-in-{self.every_n}," if self.every_n > 1 else ""
        suffix = f":{self.arg}" if self.arg else ""
        return f"{prefix}{self.mode}{suffix}"


def parse_spec(site: str, spec: str) -> Optional[_Armed]:
    """``spec`` grammar: ``[1-in-N,]mode[:arg]``; ``off`` disarms.
    Unknown specs raise ValueError (a typo'd drill must not silently
    run fault-free)."""
    m = _SPEC_RE.match(spec.strip())
    if m is None:
        raise ValueError(f"bad failpoint spec {spec!r} for {site!r} "
                         f"(want [1-in-N,]error|delay:ms|torn|enospc)")
    if m.group("mode") == "off":
        return None
    return _Armed(site=site, mode=m.group("mode"),
                  every_n=int(m.group("n") or 1),
                  arg=m.group("arg") or "")


# site → _Armed. Module-level so the disarmed fast path is one truthy
# check; all mutation goes through arm/disarm/reset.
_ARMED: Dict[str, _Armed] = {}
# fired counts survive disarm (tests assert fired-then-recovered)
_FIRED_TOTAL: Dict[str, int] = {}


def arm(site: str, spec: str) -> None:
    if site not in SITES:
        raise KeyError(f"unknown failpoint site {site!r} "
                       f"(registered: {sorted(SITES)})")
    armed = parse_spec(site, spec)
    if armed is None:
        _ARMED.pop(site, None)
        return
    _ARMED[site] = armed
    logger.info("failpoint armed: %s=%s", site, armed.describe())


def disarm(site: str) -> None:
    _ARMED.pop(site, None)


def disarm_all() -> None:
    """Disarm every site but KEEP the fired counters (the chaos suite's
    per-test isolation)."""
    _ARMED.clear()


def reset() -> None:
    """Disarm everything and zero fired counters (test isolation)."""
    _ARMED.clear()
    _FIRED_TOTAL.clear()


def armed() -> Dict[str, str]:
    return {site: a.describe() for site, a in sorted(_ARMED.items())}


def fired_count(site: Optional[str] = None) -> int:
    if site is not None:
        return _FIRED_TOTAL.get(site, 0)
    return sum(_FIRED_TOTAL.values())


def _check(site: str) -> Optional[_Armed]:
    a = _ARMED.get(site)
    if a is None:
        if site not in SITES:
            raise KeyError(f"unknown failpoint site {site!r}")
        return None
    if not a.due():
        return None
    a.fired += 1
    _FIRED_TOTAL[site] = _FIRED_TOTAL.get(site, 0) + 1
    return a


def _raise_for(a: _Armed, exc: Optional[Type[BaseException]]) -> None:
    if a.mode == "enospc":
        raise OSError(errno.ENOSPC,
                      f"No space left on device [failpoint {a.site}]")
    msg = a.arg or f"injected fault at {a.site}"
    raise (exc or FaultInjected)(f"{msg} [failpoint {a.site}]")


def hit(site: str, exc: Optional[Type[BaseException]] = None) -> None:
    """Sync failpoint (thread-pool / daemon code). Zero-cost disarmed.
    A ``torn`` arming is payload-shaping and fires only at the site's
    :func:`mangle` call — hit() leaves its counter untouched."""
    if not _ARMED:
        return
    pre = _ARMED.get(site)
    if pre is not None and pre.mode == "torn":
        return
    a = _check(site)
    if a is None:
        return
    if a.mode == "delay":
        # the injection IS the deliberate stall (sync sites run
        # off-loop: spill pumps, onboard prep threads, the daemon WAL)
        time.sleep(a.delay_s())  # dynalint: ok DL001 failpoint delay injection is the fault being modeled
        return
    _raise_for(a, exc)


async def hit_async(site: str,
                    exc: Optional[Type[BaseException]] = None) -> None:
    """Async failpoint (event-loop call sites). Delays ride
    ``asyncio.sleep`` so the loop keeps serving everyone else — the
    injected fault is slow-PEER, never a stalled loop."""
    if not _ARMED:
        return
    pre = _ARMED.get(site)
    if pre is not None and pre.mode == "torn":
        return
    a = _check(site)
    if a is None:
        return
    if a.mode == "delay":
        await asyncio.sleep(a.delay_s())
        return
    _raise_for(a, exc)


def mangle(site: str, data: bytes) -> bytes:
    """Payload-shaping failpoint: armed ``torn`` truncates the byte
    payload (default: half; ``torn:frac`` keeps ``frac`` of it) so the
    consumer exercises its corruption path. Other armed modes behave
    like :func:`hit`. Disarmed: identity, zero-cost."""
    if not _ARMED:
        return data
    a = _check(site)
    if a is None:
        return data
    if a.mode == "torn":
        frac = float(a.arg or 0.5)
        return data[:max(int(len(data) * frac), 1)]
    if a.mode == "delay":
        time.sleep(a.delay_s())  # dynalint: ok DL001 failpoint delay injection is the fault being modeled
        return data
    _raise_for(a, None)
    return data  # unreachable


def arm_from_env(env: Optional[str] = None) -> int:
    """Parse ``DYN_FAULTS="site=spec;site=spec"``. Returns the number of
    sites armed; unknown sites/specs raise loudly (a chaos drill with a
    typo must not run fault-free)."""
    raw = env if env is not None else os.environ.get(FAULTS_ENV, "")
    n = 0
    for part in raw.split(";"):
        part = part.strip()
        if not part:
            continue
        site, _, spec = part.partition("=")
        arm(site.strip(), spec.strip() or "error")
        n += 1
    return n


# ---------------------------------------------------------------- fleet ops
def faults_control_key(namespace: str) -> str:
    """``llmctl faults`` target: a JSON ``{site: spec}`` table every
    watching worker applies declaratively (absent site = disarmed)."""
    return f"{FAULTS_PREFIX}control/{namespace}"


def _apply_table(raw: bytes) -> None:
    import json
    try:
        table = json.loads(raw)
    except ValueError:
        logger.warning("ignoring malformed faults control payload")
        return
    if not isinstance(table, dict):
        logger.warning("ignoring non-dict faults control payload")
        return
    # declarative: the stored table IS the armed set (env/programmatic
    # armings made before the first control write survive until then —
    # fleet control is authoritative once used)
    _ARMED.clear()
    for site, spec in table.items():
        try:
            arm(site, str(spec))
        except (KeyError, ValueError):
            logger.warning("faults control: skipping bad entry %r=%r",
                           site, spec)
    logger.info("faults control applied: %s", armed() or "(all clear)")


async def watch_faults_loop(runtime, namespace: str) -> None:
    """Standing task (launch/run.py): apply ``llmctl faults`` live.
    Like the tier-weights watch, the STORED value applies at startup —
    a late-joining worker converges to the namespace's current drill."""
    from .kvstore import WatchEventType
    from .tracing import detach_trace

    detach_trace()
    key = faults_control_key(namespace)
    entry = await runtime.store.kv_get(key)
    if entry is not None:
        _apply_table(entry.value)
    watcher = await runtime.store.watch_prefix(key)
    async for ev in watcher:
        if ev.type == WatchEventType.PUT:
            _apply_table(ev.entry.value)


# env arming at import: subprocess workers (run.py, bench, tests that
# spawn daemons) inherit the drill without any wiring
if os.environ.get(FAULTS_ENV):
    arm_from_env()
