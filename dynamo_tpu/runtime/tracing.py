"""Fleet-wide distributed tracing: per-request spans with ON-WIRE context
propagation across frontend → router → worker → KV-fabric peers.

Reference: the request plane instruments ingress/egress with request-id
spans (lib/runtime/src/pipeline/network/egress/push.rs:134-151 — a
tracing span wrapping publish + dial-back, carrying the request id). The
TPU runtime goes further than the reference's log-join scheme: a
:class:`TraceContext` ``(trace_id, parent_span, origin_ts)`` rides the
request-plane control message (runtime/codec.py), the disagg prefill
handoff, and kv_fabric peer fetches, so every downstream process opens a
CHILD trace of the originating frontend trace instead of a disjoint one.
A collector (components/trace_collector.py) subscribes the completed
trace dicts workers publish over the event plane and stitches the
per-request fleet tree, exportable as Chrome-trace-event/Perfetto JSON.

Pieces in this module (dependency-free; asyncio only):

- :class:`Trace` — one process's spans for one request, with a stable
  ``span_id`` (its root span identity), an optional ``parent_span``
  linking it into a fleet tree, and wall-clock anchors (``start_epoch``,
  ``origin_ts``) so cross-process offsets are computable.
- :class:`Tracer` — the process-global registry: ring buffer, sampled
  per-trace log line (every Nth + always-on-slow/error — at fleet QPS an
  unconditional INFO per request is log-spam), ``on_finish`` hooks for
  publication, and the ``dropped_log_lines`` counter behind
  ``nv_llm_trace_dropped_log_lines_total``.
- :class:`TracePublisher` — bounded async queue draining finished trace
  dicts into a transport sink (the event plane in production, a list in
  tests) without ever blocking the finishing code path.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import logging
import os
import secrets
import time
from collections import deque
from typing import Callable, Dict, List, Optional

logger = logging.getLogger("dynamo_tpu.trace")

__all__ = ["Span", "Trace", "TraceContext", "Tracer", "TracePublisher",
           "tracer", "current_trace", "current_wire_context", "use_trace",
           "span", "TRACE_EVENTS_SUBJECT"]

# event-plane topic completed trace dicts are published on (same pattern
# as the router's kv_events; components/trace_collector.py subscribes)
TRACE_EVENTS_SUBJECT = "trace_events"


def _new_id(nbytes: int = 8) -> str:
    return secrets.token_hex(nbytes)


@dataclasses.dataclass
class TraceContext:
    """The minimal on-wire propagation record: enough for the receiver to
    open a child trace of the sender's, nothing more. ``origin_ts`` is the
    ORIGINATING frontend's wall clock at root-trace start — every member
    of a fleet tree carries it, so the collector can place all spans on
    one timeline without trusting any single hop's clock twice."""

    trace_id: str
    parent_span: str
    origin_ts: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> Optional["TraceContext"]:
        if not d or not d.get("trace_id"):
            return None
        return cls(trace_id=str(d["trace_id"]),
                   parent_span=str(d.get("parent_span", "")),
                   origin_ts=float(d.get("origin_ts", 0.0) or 0.0))


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def ms(self) -> float:
        return 1e3 * (self.end - self.start)


class Trace:
    """All spans of one request on one process ("role" tags which side).

    Identity: ``trace_id`` names the whole fleet tree (minted at the
    origin, inherited by children), ``span_id`` names THIS trace's root
    span, and ``parent_span`` (when set) is the span_id of the trace one
    hop upstream — the edges the collector stitches on."""

    def __init__(self, request_id: str, role: str = "",
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None,
                 origin_ts: Optional[float] = None,
                 start: Optional[float] = None):
        self.request_id = request_id
        self.role = role
        self.trace_id = trace_id or _new_id()
        self.span_id = _new_id(6)
        self.parent_span = parent_span
        # `start` (monotonic): a moment already past at which the trace
        # begins — the HTTP front end's stamp of the request's first byte.
        # The wall-clock anchors are derived from it, so the wire context
        # every downstream process inherits is anchored there too.
        now = time.monotonic()
        self.start = now if start is None else start
        self.start_epoch = time.time() - (now - self.start)
        # origin_ts: wall clock at the ORIGIN root's start; roots anchor
        # themselves, children inherit the wire value
        self.origin_ts = self.start_epoch if origin_ts is None else origin_ts
        self.finished: Optional[float] = None   # set by Tracer.finish
        self.error: Optional[str] = None
        self.spans: List[Span] = []

    # ------------------------------------------------------------ wire hops
    def wire_context(self) -> dict:
        """The dict to embed in an outgoing control message: the receiver
        opens a child of THIS trace."""
        return TraceContext(trace_id=self.trace_id,
                            parent_span=self.span_id,
                            origin_ts=self.origin_ts).to_dict()

    @classmethod
    def from_wire(cls, ctx, request_id: str, role: str = "") -> "Trace":
        """Open a child trace from a propagated context (dict or
        :class:`TraceContext`). Falls back to a fresh root when the
        context is absent/malformed — propagation is best-effort and must
        never fail a request."""
        if isinstance(ctx, dict):
            ctx = TraceContext.from_dict(ctx)
        if ctx is None:
            return cls(request_id, role=role)
        return cls(request_id, role=role, trace_id=ctx.trace_id,
                   parent_span=ctx.parent_span or None,
                   origin_ts=ctx.origin_ts or None)

    # --------------------------------------------------------------- spans
    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        s = Span(name=name, start=time.monotonic(), attrs=attrs)
        self.spans.append(s)
        try:
            yield s
        finally:
            s.end = time.monotonic()

    def add_span(self, name: str, start: float, end: float, **attrs) -> Span:
        """Record a completed span from explicit monotonic timestamps —
        the non-contextmanager path used by off-thread work (KV onboard
        prep, fabric fetches) that can't hold a contextvar."""
        s = Span(name=name, start=start, end=end, attrs=attrs)
        self.spans.append(s)
        return s

    def event(self, name: str, **attrs) -> None:
        """Zero-duration marker (e.g. first_token)."""
        t = time.monotonic()
        self.spans.append(Span(name=name, start=t, end=t, attrs=attrs))

    def set_error(self, message: str) -> None:
        """Mark the trace errored (tail-based retention keeps these)."""
        self.error = str(message)[:512]

    def to_dict(self) -> dict:
        end = self.finished if self.finished is not None else time.monotonic()
        return {
            "request_id": self.request_id,
            "role": self.role,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span": self.parent_span,
            "origin_ts": self.origin_ts,
            "start_epoch": self.start_epoch,
            # offset of this trace's start on the ORIGIN's timeline (ms)
            "origin_offset_ms": round(
                1e3 * (self.start_epoch - self.origin_ts), 3),
            "total_ms": round(1e3 * (end - self.start), 2),
            **({"error": self.error} if self.error else {}),
            "spans": [{"name": s.name, "ms": round(s.ms, 2),
                       "at_ms": round(1e3 * (s.start - self.start), 2),
                       **({"attrs": s.attrs} if s.attrs else {})}
                      for s in self.spans],
        }


class Tracer:
    """Process-global registry: ring buffer + SAMPLED per-trace log line
    + finish hooks (the publication path).

    Log sampling (fleet-QPS hygiene): ``log_every=N`` logs every Nth
    completed trace; traces slower than ``slow_ms`` or carrying an error
    ALWAYS log. Skipped lines are counted in ``dropped_log_lines``
    (exported as ``nv_llm_trace_dropped_log_lines_total``). Defaults come
    from ``DYN_TRACE_LOG_EVERY`` / ``DYN_TRACE_LOG_SLOW_MS`` (default:
    log everything — the single-process debugging posture)."""

    def __init__(self, keep: int = 256, log_every: Optional[int] = None,
                 slow_ms: Optional[float] = None):
        self._recent: deque = deque(maxlen=keep)
        self.completed = 0
        if log_every is None:
            log_every = int(os.environ.get("DYN_TRACE_LOG_EVERY", "1"))
        if slow_ms is None:
            raw = os.environ.get("DYN_TRACE_LOG_SLOW_MS")
            slow_ms = float(raw) if raw else None
        self.log_every = max(int(log_every), 0)   # 0 = never (still slow/err)
        self.slow_ms = slow_ms
        self.dropped_log_lines = 0
        self._since_logged = 0
        # finish hooks receive the serialized trace dict (publication,
        # embedded collectors); exceptions are swallowed — observability
        # must never fail the serving path
        self.on_finish: List[Callable[[dict], None]] = []

    def configure(self, log_every: Optional[int] = None,
                  slow_ms: Optional[float] = None) -> None:
        if log_every is not None:
            self.log_every = max(int(log_every), 0)
        if slow_ms is not None:
            self.slow_ms = float(slow_ms) if slow_ms > 0 else None

    def _should_log(self, d: dict) -> bool:
        if d.get("error"):
            return True
        if self.slow_ms is not None and d["total_ms"] >= self.slow_ms:
            return True
        if self.log_every <= 0:
            return False
        self._since_logged += 1
        if self._since_logged >= self.log_every:
            self._since_logged = 0
            return True
        return False

    def finish(self, trace: Trace) -> None:
        # store the Trace OBJECT and serialize lazily: code holding a
        # captured reference (e.g. the engine's stream_response) may append
        # events after use_trace exits, and those must still show up in
        # /traces (ADVICE r2). total_ms freezes here, not at read time.
        trace.finished = time.monotonic()
        self._recent.append(trace)
        self.completed += 1
        d = trace.to_dict()
        if self._should_log(d):
            logger.info("trace %s [%s] %.1fms: %s", trace.request_id,
                        trace.role, d["total_ms"],
                        " ".join(f"{s['name']}={s['ms']}ms"
                                 for s in d["spans"]))
        else:
            self.dropped_log_lines += 1
        for cb in list(self.on_finish):
            try:
                cb(d)
            except Exception:  # noqa: BLE001 — hooks must never fail finish
                logger.exception("trace finish hook failed")

    def recent(self, n: int = 32) -> List[dict]:
        return [t.to_dict() for t in list(self._recent)[-n:]]

    def find(self, request_id: str) -> List[dict]:
        return [t.to_dict() for t in self._recent
                if t.request_id == request_id]

    def stats(self) -> dict:
        return {"completed": self.completed,
                "dropped_log_lines": self.dropped_log_lines,
                "log_every": self.log_every,
                "slow_ms": self.slow_ms,
                "ring": len(self._recent)}


class TracePublisher:
    """Drains finished trace dicts into an async ``sink`` (the event
    plane) through a bounded queue — the finishing code path never blocks
    on the network, saturation drops with a counter (the KvEventPublisher
    contract applied to traces)."""

    def __init__(self, sink, max_buffer: int = 2048,
                 tracer_: Optional["Tracer"] = None):
        import asyncio
        self.sink = sink
        self._queue: "asyncio.Queue" = asyncio.Queue(maxsize=max_buffer)
        self._task = None
        self.dropped = 0
        self.published = 0
        self._tracer = tracer_
        if tracer_ is not None:
            tracer_.on_finish.append(self.enqueue)

    def enqueue(self, trace_dict: dict) -> None:
        import asyncio
        try:
            self._queue.put_nowait(trace_dict)
        except asyncio.QueueFull:
            self.dropped += 1
            return
        self._ensure_task()

    def _ensure_task(self) -> None:
        import asyncio
        if self._task is None or self._task.done():
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                return  # no loop (sync context); drains on next enqueue
            self._task = loop.create_task(self._run(), name="trace-pub")

    async def _run(self) -> None:
        while True:
            d = await self._queue.get()
            try:
                await self.sink(d)
                self.published += 1
            except Exception:  # noqa: BLE001 — transport boundary
                logger.exception("trace publish failed (trace dropped)")
            finally:
                self._queue.task_done()

    async def drain(self) -> None:
        self._ensure_task()
        await self._queue.join()

    def close(self) -> None:
        """Detach from the tracer and stop the pump (test hygiene: the
        process tracer is a singleton; a dangling hook would keep
        publishing another test's traces)."""
        if self._tracer is not None:
            try:
                self._tracer.on_finish.remove(self.enqueue)
            except ValueError:
                pass
        if self._task is not None:
            self._task.cancel()


tracer = Tracer()

_current: contextvars.ContextVar[Optional[Trace]] = contextvars.ContextVar(
    "dynamo_tpu_trace", default=None)


def current_trace() -> Optional[Trace]:
    return _current.get()


def current_wire_context() -> Optional[dict]:
    """The ambient trace's propagation dict, or None — what egress embeds
    in the outgoing control message."""
    t = _current.get()
    return t.wire_context() if t is not None else None


def detach_trace() -> None:
    """Clear the ambient trace in THIS context. Long-lived background
    tasks (the engine loop) are created from whatever request context
    first started them and would otherwise inherit that request's trace
    forever — every task they spawn (onboard preps, fabric RPCs) would
    mis-attach to the first request's tree. Such tasks detach at entry;
    per-request identity travels explicitly (EngineRequest.trace,
    trace_ctx parameters)."""
    _current.set(None)


@contextlib.contextmanager
def use_trace(trace: Trace, finish: bool = True):
    """Bind `trace` as the ambient trace for the enclosed async chain."""
    token = _current.set(trace)
    try:
        yield trace
    finally:
        _current.reset(token)
        if finish:
            tracer.finish(trace)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Span on the ambient trace; no-op when none is bound."""
    t = _current.get()
    if t is None:
        yield None
    else:
        with t.span(name, **attrs) as s:
            yield s
