"""One pass over a loop iteration's SSE writes.

A token stream's handler used to end every wake-up in ``await
resp.write(chunk)``: aiohttp's response, its payload writer and the
transport's ``send``, once per stream and token, between the other
handlers' detokenising and JSON work. With 64 live streams that is 64
socket writes spread over one engine cycle's ``yield``. Here a handler hands
its encoded chunk to the service's ``SseWriteCollector`` and goes back to its
stream; the first hand-over of a batch schedules one flush, and the flush
writes every response's pending bytes back to back in the loop's next
iteration. What a batch holds is whatever that iteration's handlers
produced: one live stream is one write a token, 64 are one pass, and several
chunks of one stream leave as one framed write. Nothing here knows of an
engine: it is a property of the event loop the service runs on
(docs/observability.md, "The write collector").

What ``await resp.write`` gave is kept by ``SseOut``: order per stream (one
flush at a time writes a response's chunks in the order handed over);
back-pressure (past ``DRAIN_EVERY`` bytes a handler waits for its own bytes
and its own transport's drain, as aiohttp's writer made it, so a response's
pending bytes are bounded and no other stream waits); a reset transport
(``on_reset`` is called where the write failed, the handler sees
``broken``); and ``flushed()`` is how a handler ends: its last chunk and
``[DONE]`` are on the transport before the response is.
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
from typing import Callable, List, Optional

from aiohttp import web

from ...runtime.tracing import Trace
from .metrics import ServiceMetrics

logger = logging.getLogger("dynamo_tpu.http")

# aiohttp's StreamWriter.write looks at the transport's drain once this many
# bytes have gone out since it last looked; a handler here does the same
DRAIN_EVERY = 0x10000


class SseOut:
    """One streamed response's side of the collector."""

    __slots__ = ("_collector", "_writer", "_on_reset", "_first_write",
                 "_flushed", "chunks", "since_drain", "broken")

    def __init__(self, collector: "SseWriteCollector", request: web.Request,
                 on_reset: Callable[[], None]):
        self._collector = collector
        # the prepared response's payload writer (chunked framing, the
        # buffered headers before the first body bytes)
        self._writer = request.writer
        self._on_reset = on_reset
        self._first_write: Optional[Trace] = None
        self._flushed: Optional[asyncio.Future] = None
        self.chunks: List[bytes] = []   # handed over, not yet on the transport
        self.since_drain = 0
        self.broken = False           # a write failed: the client is gone

    def put(self, data: bytes, first_write: Optional[Trace] = None) -> None:
        """Hand ``data`` over; it is on the transport one loop iteration
        later. ``first_write``: the trace that gets its
        ``stream.first_write`` event when these bytes are."""
        if self.broken:
            return
        if first_write is not None:
            self._first_write = first_write
        self.chunks.append(data)
        self.since_drain += len(data)
        if len(self.chunks) == 1:
            self._collector._schedule(self)

    @property
    def over_mark(self) -> bool:
        return self.since_drain > DRAIN_EVERY

    async def flushed(self) -> None:
        """Everything handed over so far is on the transport (or the
        response is ``broken``)."""
        if self.chunks:
            self._flushed = asyncio.get_running_loop().create_future()
            await self._flushed

    async def drained(self) -> None:
        """The handler's own wait, past the mark: its bytes written, then
        its transport below the high-water mark."""
        self.since_drain = 0
        await self.flushed()
        if not self.broken:
            try:
                await self._writer.drain()
            except ConnectionError:
                self._reset()

    def close(self) -> None:
        """The handler is done with the response: what it never waited for
        is dropped, and a flush that finds it writes nothing."""
        self.chunks = []
        self.broken = True

    def _reset(self) -> None:
        self.close()
        self._on_reset()

    async def _write(self) -> int:
        """The flush's part: this response's pending chunks as one write.
        → chunks written."""
        chunks, self.chunks = self.chunks, []
        try:
            if chunks:
                # drain=False: the flush never waits for a transport
                await self._writer.write(
                    chunks[0] if len(chunks) == 1 else b"".join(chunks),
                    drain=False)
                if self._first_write is not None:
                    self._first_write.event("stream.first_write")
                    self._first_write = None
        except Exception as e:  # noqa: BLE001 — one stream's fault stops no other
            if not isinstance(e, ConnectionError):
                logger.exception("SSE write failed")
            chunks = []
            self._reset()
        waiter, self._flushed = self._flushed, None
        if waiter is not None and not waiter.done():
            waiter.set_result(None)
        return len(chunks)


class SseWriteCollector:
    """The service's pending SSE writes, per response and in order, and the
    one flush that writes them (module docstring)."""

    def __init__(self, metrics: ServiceMetrics):
        self._metrics = metrics
        self._dirty: List[SseOut] = []      # responses with pending chunks
        self._flush_task: Optional[asyncio.Task] = None
        # the flush is no request's: a task copies the context it is made
        # in, else the trace of the handler that happened to hand over first
        self._context = contextvars.Context()

    def open(self, request: web.Request,
             on_reset: Callable[[], None]) -> SseOut:
        """For a response that has been prepared on ``request``."""
        return SseOut(self, request, on_reset)

    def stats(self) -> dict:
        live = [out for out in self._dirty if out.chunks]
        return {"pending_responses": len(live),
                "pending_bytes": sum(len(c) for out in live
                                     for c in out.chunks)}

    def _schedule(self, out: SseOut) -> None:
        self._dirty.append(out)
        if self._flush_task is None:
            # the task's first step is a call_soon: the loop's next iteration
            self._flush_task = self._context.run(
                asyncio.get_running_loop().create_task, self._flush())

    async def _flush(self) -> None:
        try:
            while self._dirty:
                batch, self._dirty = self._dirty, []
                chunks = 0
                for out in batch:
                    chunks += await out._write()
                if chunks:
                    self._metrics.sse_flushes.inc()
                    self._metrics.sse_flushed_chunks.inc(chunks)
        finally:
            self._flush_task = None
