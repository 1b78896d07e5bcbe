"""Engine flight recorder: a bounded ring of per-dispatch records plus an
event-loop lag probe, dumpable on demand.

Motivation (ISSUE 7): when a fleet trace shows a worker spending 80 ms in
"decode" the next question is always *which dispatches* — batch fill,
planned tokens, device time vs host gap, which KV tier fed the admission,
how speculation behaved. That truth only exists inside the engine loop
for an instant; the flight recorder keeps the last N dispatch records in
memory (zero steady-state I/O — strictly cheaper than logging) so a
``/debug`` hit or ``llmctl trace dump`` can reconstruct the recent past
of any worker, the same way an aircraft recorder is read after the fact.

Pieces:

- :class:`FlightRecorder` — the ring. ``record(kind, **fields)`` is
  called synchronously from the engine loop (append-only, no locks
  needed under the GIL); ``dump()`` returns the ring newest-last.
- :class:`PhaseClock` — the engine loop's one clock. The loop is always
  in exactly one phase (``PHASES``); ``enter`` closes the running phase
  and opens the next with ONE timestamp, so the phases tile the cycle
  between two ``record_cycle`` calls. Each boundary is also a
  ``jax.profiler.TraceAnnotation`` named ``loop.<phase>``: under a
  profiler session the phases sit on the profiler's clock beside the
  device ops; with none the annotation costs well under a microsecond.
- Event-loop **lag probe**: a periodic task that measures how late
  asyncio wakes it up — the direct observable for "something is blocking
  the engine loop" (sync file I/O, long host work), feeding the
  ``nv_llm_engine_loop_lag_ms`` gauge.
- A process-global registry (weak, keyed by name) so the HTTP
  ``/debug`` endpoint can enumerate recorders without plumbing.
- :class:`BuildLog` (``BUILD_LOG``, process-global like JAX's own
  listeners) — every XLA program the process builds, from JAX's
  monitoring events: one entry a program with its Python trace, its
  lowering and its compile-or-cache-load times, whether the persistent
  cache had it, and the loop's phase. Each entry is also a ``build``
  record in every live recorder's ring, and every cycle record carries
  the log's running totals, so a step that recompiles while serving is
  seen from inside (docs/observability.md "The build log").
- The ``trace/`` KV-store key layout + worker-side watch loop behind
  ``llmctl trace dump``: the CLI writes the control key, every watching
  worker publishes its ring under its lease, the CLI collects.
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
import logging
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

logger = logging.getLogger("dynamo_tpu.engine.flight")

__all__ = ["FlightRecorder", "PhaseClock", "PHASES", "BuildLog", "BUILD_LOG",
           "register_recorder", "all_recorders", "logged_build",
           "trace_control_key", "trace_dump_key", "watch_trace_dump_loop",
           "TRACE_PREFIX"]

_REGISTRY: "weakref.WeakValueDictionary[str, FlightRecorder]" = \
    weakref.WeakValueDictionary()
_ids = itertools.count()


# What the engine loop can be doing (docs/observability.md has the seams):
#   sweep     cancellation/deadline sweep, idle defrag
#   admit     the admission pass: KV plan, prefill dispatch
#   build     a decode-type dispatch's inputs: slot walk, tables, keys,
#             host→device transfers
#   dispatch  the decode/K-step/ragged/verify jit call until it returns
#   wait      every blocking device→host fetch
#   post      per-slot bookkeeping, block registration and growth, emits,
#             finishes, the flight record itself
#   complete  deferred admissions and tier onboards (minus their wait)
#   yield     what the loop gives the shared event loop (HTTP, detokeniser,
#             SSE): a drain of the loop until it is quiet (EngineCore.
#             _yield_until_quiet counts its iterations in ``yield_iters``),
#             and the idle wait
PHASES = ("sweep", "admit", "build", "dispatch", "wait", "post", "complete",
          "yield")


class PhaseClock:
    """Which phase the engine loop is in, and for how long it has been.

    ``seconds[phase]`` only ever grows; a cycle's split is the difference
    of two readings (``close_cycle``), an admission's fetch stall the
    difference of ``seconds["wait"]`` around it. Single-threaded: only the
    engine loop's thread calls in."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self._names = {p: "loop." + p for p in PHASES}
        self.seconds: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.running = "yield"           # an engine not stepping is idle
        self._since = self._cycle_start = time.monotonic()
        self._at_cycle_start = dict(self.seconds)
        self._annotation = None
        self.admits = 0      # prefill dispatches issued in the open cycle
        self.admit_tokens = 0    # prompt tokens those dispatches prefilled
        self.yield_iters = 0     # event-loop iterations its yield has run

    def enter(self, phase: str) -> float:
        """Close the running phase and open ``phase`` at one timestamp
        (returned). Re-entering the running phase is allowed."""
        now = time.monotonic()
        self.seconds[self.running] += now - self._since
        self.running, self._since = phase, now
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        self._annotation = self._annotate(self._names[phase])
        self._annotation.__enter__()
        return now

    @contextlib.contextmanager
    def phase(self, phase: str):
        """Nested use: suspend the running phase for ``phase`` and resume
        it afterwards, also when the body raises."""
        outer = self.running
        self.enter(phase)
        try:
            yield
        finally:
            self.enter(outer)

    def close_cycle(self) -> Dict[str, float]:
        """End the open cycle now: ``<phase>_ms`` for each phase since the
        last close, ``cycle_ms`` between the two closes by the timestamps
        alone (so a reader can check the tiling), ``admits``,
        ``admit_tokens`` and ``yield_iters``. The running phase carries on
        into the next cycle."""
        now = self.enter(self.running)
        out = {f"{p}_ms": round(
            1e3 * (self.seconds[p] - self._at_cycle_start[p]), 3)
            for p in PHASES}
        out["cycle_ms"] = round(1e3 * (now - self._cycle_start), 3)
        out["admits"] = self.admits
        out["admit_tokens"] = self.admit_tokens
        out["yield_iters"] = self.yield_iters
        self._cycle_start = now
        self._at_cycle_start = dict(self.seconds)
        self.admits = self.admit_tokens = self.yield_iters = 0
        return out


class BuildLog:
    """Every XLA program this process builds, as JAX reports it.

    JAX 0.9 fires three ``jax.monitoring`` duration events a program, each
    with its name: ``jaxpr_trace_duration`` (``fun_name='decode_k'``), then
    ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
    (``fun_name='jit(decode_k)'``), and inside the last the plain events
    ``cache_hits`` / ``cache_misses`` of the persistent cache. A program
    that is built fires none of them again, so the log costs nothing in
    steady state. An entry closes when its ``backend_compile_duration``
    arrives (a compilation, or the cache's retrieval).

    A jit traced inside a jit reports its own trace first and inside the
    outer's duration, and lowering rules trace helpers of their own before
    the lowering's event: an entry's ``trace_ms`` is the LAST trace event
    under the name of the program that lowers, never a sum; a lowering
    with no such trace (JAX kept the jaxpr) reads 0. Builds are matched up
    per thread; the totals only grow."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}

    def __init__(self, capacity: int = 2048):
        self.entries: deque = deque(maxlen=capacity)
        self.built = 0               # programs built
        self.built_trace_ms = 0.0    # of built_ms: Python trace + lowering
        self.built_ms = 0.0          # all three stages
        self.cache_misses = 0
        self._installed = False
        self._lock = threading.Lock()
        self._open = threading.local()   # the build this thread has open
        self._recorders: "weakref.WeakValueDictionary[int, FlightRecorder]" \
            = weakref.WeakValueDictionary()

    def install(self) -> None:
        """Start listening to JAX's build events. Idempotent; called where
        an engine is about to be built, never at import."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)

    def attach(self, recorder: "FlightRecorder") -> None:
        """``recorder`` gets a ``build`` record for every entry closed
        while it lives."""
        self._recorders[id(recorder)] = recorder

    def _mine(self) -> dict:
        """The build the calling thread has open."""
        try:
            return self._open.build
        except AttributeError:
            self._open.build = {"traces": {}, "lowered": ("", 0.0),
                                "cache": "none"}
            return self._open.build

    def on_event(self, event: str, **_kw) -> None:
        cache = self.CACHE.get(event)
        if cache is not None:
            self._mine()["cache"] = cache

    def on_duration(self, event: str, secs: float, fun_name: str = "",
                    **_kw) -> None:
        if event == self.TRACE:
            self._mine()["traces"][fun_name] = secs
        elif event == self.LOWER:
            self._mine()["lowered"] = (fun_name, secs)
        elif event == self.COMPILE:
            mine = self._mine()
            del self._open.build
            program = fun_name[4:-1] if fun_name.startswith("jit(") \
                else fun_name
            lowered, lower_s = mine["lowered"]
            self._close(program, mine["traces"].get(program, 0.0),
                        lower_s if lowered == fun_name else 0.0, secs,
                        mine["cache"])

    def _close(self, program: str, trace_s: float, lower_s: float,
               compile_s: float, cache: str) -> None:
        recorders = list(self._recorders.values())
        entry = {"program": program,
                 "trace_ms": round(1e3 * trace_s, 3),
                 "lower_ms": round(1e3 * lower_s, 3),
                 "compile_ms": round(1e3 * compile_s, 3),
                 "host_ms": round(1e3 * (trace_s + lower_s + compile_s), 3),
                 "cache": cache}
        with self._lock:
            self.built += 1
            self.built_trace_ms += 1e3 * (trace_s + lower_s)
            self.built_ms += 1e3 * (trace_s + lower_s + compile_s)
            if cache == "miss":
                self.cache_misses += 1
            # the newest engine's phase; "init" while none lives
            self.entries.append(dict(
                entry, t=time.time(), phase=recorders[-1].clock.running
                if recorders else "init"))
        for recorder in recorders:
            recorder.record("build", phase=recorder.clock.running, **entry)

    def totals(self) -> dict:
        return {"built": self.built,
                "built_ms": round(self.built_ms, 3),
                "built_trace_ms": round(self.built_trace_ms, 3),
                "cache_misses": self.cache_misses}

    def costliest(self, n: int = 5) -> List[dict]:
        """The ``n`` entries the log still holds that cost the host most."""
        return sorted(self.entries, key=lambda e: -e["host_ms"])[:n]


BUILD_LOG = BuildLog()


class FlightRecorder:
    """Bounded ring of per-dispatch records + loop-lag probe."""

    def __init__(self, capacity: int = 512,
                 lag_probe_interval: float = 0.5):
        self.clock = PhaseClock()
        BUILD_LOG.install()      # an engine built directly is seen from here
        BUILD_LOG.attach(self)
        self._ring: deque = deque(maxlen=capacity)
        self.capacity = capacity
        self.records_total = 0
        self.lag_probe_interval = lag_probe_interval
        self.loop_lag_ms = 0.0       # last probe's scheduling delay
        self.loop_lag_max_ms = 0.0   # high-water mark since start
        self._probe_task: Optional[asyncio.Task] = None

    # --------------------------------------------------------------- records
    def record(self, kind: str, **fields) -> None:
        """Append one dispatch record (engine-loop synchronous; must stay
        allocation-light — scalar fields only, no arrays)."""
        self.records_total += 1
        self._ring.append({"kind": kind, "t": time.time(), **fields})

    def record_cycle(self, kind: str, **fields) -> None:
        """One ``decode`` / ``ragged`` / ``verify`` record, closing the
        clock's cycle: the phase split, ``admits`` / ``admit_tokens`` /
        ``yield_iters``, ``device_ms`` (the cycle's ``wait``: what the loop
        blocked on the device), ``host_gap_ms`` (the rest of the
        harvest-to-harvest cycle) and the build log's running totals
        ``built`` / ``built_ms`` / ``built_trace_ms``, read as a counter is:
        two records' difference is what was built between them."""
        split = self.clock.close_cycle()
        cycle_ms = split.pop("cycle_ms")
        self.record(kind, **fields, device_ms=split["wait_ms"],
                    host_gap_ms=round(cycle_ms - split["wait_ms"], 3),
                    **split, built=BUILD_LOG.built,
                    built_ms=round(BUILD_LOG.built_ms, 3),
                    built_trace_ms=round(BUILD_LOG.built_trace_ms, 3))

    def dump(self, last: Optional[int] = None) -> List[dict]:
        out = list(self._ring)
        return out[-last:] if last else out

    def stats(self) -> dict:
        kinds: Dict[str, int] = {}
        for r in self._ring:
            kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
        return {"records_total": self.records_total,
                "ring": len(self._ring), "capacity": self.capacity,
                "kinds": kinds,
                "loop_lag_ms": round(self.loop_lag_ms, 3),
                "loop_lag_max_ms": round(self.loop_lag_max_ms, 3),
                **BUILD_LOG.totals(),
                "costliest_builds": BUILD_LOG.costliest()}

    def metrics_kw(self) -> dict:
        """The build log's totals as ``ForwardPassMetrics`` fields
        (``nv_llm_engine_programs_built_total`` and
        ``nv_llm_engine_program_build_seconds_total``)."""
        return {"programs_built_total": BUILD_LOG.built,
                "program_build_seconds_total": BUILD_LOG.built_ms / 1e3}

    # ------------------------------------------------------------- lag probe
    def start_lag_probe(self) -> None:
        """Idempotent; requires a running loop."""
        if self._probe_task is not None and not self._probe_task.done():
            return
        self._probe_task = asyncio.get_running_loop().create_task(
            self._probe_loop(), name="engine-lag-probe")

    def stop_lag_probe(self) -> None:
        if self._probe_task is not None:
            self._probe_task.cancel()
            self._probe_task = None

    async def _probe_loop(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            t0 = loop.time()
            await asyncio.sleep(self.lag_probe_interval)
            lag_ms = max(loop.time() - t0 - self.lag_probe_interval,
                         0.0) * 1e3
            self.loop_lag_ms = lag_ms
            if lag_ms > self.loop_lag_max_ms:
                self.loop_lag_max_ms = lag_ms
                if lag_ms > 100.0:
                    logger.warning("event-loop lag %.0fms — something is "
                                   "blocking the engine loop", lag_ms)


def register_recorder(recorder: FlightRecorder,
                      name: Optional[str] = None) -> str:
    """Register for /debug enumeration (weak: a collected engine's
    recorder silently drops out). Returns the registry name."""
    name = name or f"engine-{next(_ids)}"
    _REGISTRY[name] = recorder
    return name


def all_recorders() -> Dict[str, FlightRecorder]:
    return dict(_REGISTRY)


def logged_build(construct, *args, **kwargs):
    """``construct(*args, **kwargs)`` — an ``EngineCore`` — with the build
    log listening from before the constructor runs, so that the random
    weights' init and quantise programs are in it; then one ``engine_build``
    record in the new engine's ring: the constructor's wall time
    (``host_ms``) beside what the log counted over it, i.e. its programs and
    the rest (pool allocation, host work). ``launch/run.py``
    ``build_jax_core`` builds through here. It lives in this file because
    a line added to ``launch/run.py`` above ``run_http``'s ``thread_main``
    moves the compile-cache key of every prefill program (that frame is in
    their kernels' source locations: PERF.md section 6, PR 56)."""
    BUILD_LOG.install()
    before, t0 = BUILD_LOG.totals(), time.monotonic()
    core = construct(*args, **kwargs)
    core.flight.record(
        "engine_build", host_ms=round(1e3 * (time.monotonic() - t0), 3),
        **{k: round(v - before[k], 3) for k, v in BUILD_LOG.totals().items()})
    return core


# ---------------------------------------------------------------------------
# llmctl trace dump plumbing (the kvtier admin pattern, llm/kv/admin.py)
# ---------------------------------------------------------------------------

TRACE_PREFIX = "trace/"


def trace_control_key(namespace: str) -> str:
    """llmctl writes {"dump": <epoch>} here; watching workers answer."""
    return f"{TRACE_PREFIX}control/{namespace}"


def trace_dump_key(namespace: str, worker_id: int) -> str:
    return f"{TRACE_PREFIX}dump/{namespace}/{worker_id:x}"


async def watch_trace_dump_loop(core, runtime, namespace: str,
                                last: int = 128) -> None:
    """Worker side of ``llmctl trace dump``: on every control-key write,
    publish this worker's flight-recorder ring + tracer stats under its
    lease (so a dead worker's stale dump expires with it)."""
    from ..runtime.kvstore import WatchEventType
    from ..runtime.tracing import tracer
    import json

    lease = await runtime.primary_lease()
    watcher = await runtime.store.watch_prefix(trace_control_key(namespace))
    async for ev in watcher:
        if ev.type != WatchEventType.PUT:
            continue
        try:
            n = int(json.loads(ev.entry.value).get("last", last))
        except Exception:  # noqa: BLE001 — admin input
            n = last
        flight = getattr(core, "flight", None)
        payload = {
            "at": time.time(),
            "worker_id": f"{lease.id:x}",
            "tracer": tracer.stats(),
            "flight": flight.stats() if flight is not None else None,
            "records": flight.dump(last=n) if flight is not None else [],
        }
        try:
            await runtime.store.kv_put(
                trace_dump_key(namespace, lease.id),
                json.dumps(payload).encode(), lease_id=lease.id)
        except Exception:  # noqa: BLE001
            logger.exception("trace dump publish failed")
