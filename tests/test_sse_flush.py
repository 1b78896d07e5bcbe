"""The front end writes a loop iteration's SSE chunks in one pass
(llm/http/sse_flush.py): every stream gets byte for byte what a write per
chunk gave it, a cycle's chunks are on their transports when the engine's
yield returns, a slow reader holds only its own handler, a reset kills only
its own request, ``stream.first_write`` is stamped at the transport, and the
two counters say how many chunks a pass wrote. Counts and bytes; nothing is
timed but a stall (a bound of seconds)."""

import asyncio
import json
import socket
import struct
import time

import aiohttp
import pytest

from dynamo_tpu.llm.engines.echo import EchoEngineFull
from dynamo_tpu.llm.http import HttpService, ServiceMetrics
from dynamo_tpu.llm.http.sse_flush import DRAIN_EVERY, SseWriteCollector
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.sse import encode_annotated, encode_done
from dynamo_tpu.runtime import ResponseStream
from dynamo_tpu.runtime.tracing import Trace, current_trace, use_trace
from tests.fixtures import wait_until
from tests.test_engine_loop_drain import BOUND, StubStepped, make_core

STREAMS = 64


# ------------------------------------------------------- the collector alone
class FakeWriter:
    """What the collector asks of ``request.writer``."""

    def __init__(self, fail: bool = False):
        self.fail = fail
        self.writes = []            # (monotonic, bytes)

    async def write(self, data, *, drain=True):
        assert drain is False       # the flush never waits for a transport
        if self.fail:
            raise ConnectionResetError("Cannot write to closing transport")
        self.writes.append((time.monotonic(), bytes(data)))
        self.trace_at_write = current_trace()

    async def drain(self):
        pass


class FakeRequest:
    def __init__(self, fail: bool = False):
        self.writer = FakeWriter(fail)


def counters(metrics: ServiceMetrics) -> tuple:
    """(flushes, chunks) so far."""
    text = metrics.render().decode()
    return tuple(
        float(next(line for line in text.splitlines()
                   if line.startswith(f"nv_llm_http_service_{name}_total"))
              .split()[-1])
        for name in ("sse_flushes", "sse_flushed_chunks"))


async def one_iteration():
    """The hand-over's flush runs in the loop's next iteration."""
    await asyncio.sleep(0)


@pytest.mark.asyncio
@pytest.mark.parametrize("streams", [STREAMS, 1])
async def test_a_pass_counts_one_flush_and_a_chunk_a_stream(streams):
    metrics = ServiceMetrics()
    collector = SseWriteCollector(metrics)
    requests = [FakeRequest() for _ in range(streams)]
    outs = [collector.open(r, on_reset=lambda: None) for r in requests]
    for i, out in enumerate(outs):
        out.put(b"data: %d\n\n" % i)
    assert collector.stats() == {"pending_responses": streams,
                                 "pending_bytes": sum(len(o.chunks[0])
                                                      for o in outs)}
    assert not any(r.writer.writes for r in requests)    # handed over only
    await one_iteration()
    assert [[w for _, w in r.writer.writes] for r in requests] == \
        [[b"data: %d\n\n" % i] for i in range(streams)]
    assert counters(metrics) == (1, streams)
    assert collector.stats() == {"pending_responses": 0, "pending_bytes": 0}
    # the next batch is a pass of its own
    outs[0].put(b"data: again\n\n")
    await one_iteration()
    assert counters(metrics) == (2, streams + 1)


@pytest.mark.asyncio
async def test_chunks_of_one_stream_leave_in_order_as_one_write():
    metrics = ServiceMetrics()
    collector = SseWriteCollector(metrics)
    request = FakeRequest()
    out = collector.open(request, on_reset=lambda: None)
    for part in (b"a\n\n", b"b\n\n", b"c\n\n"):
        out.put(part)
    await out.flushed()
    assert [w for _, w in request.writer.writes] == [b"a\n\nb\n\nc\n\n"]
    assert counters(metrics) == (1, 3)
    await out.flushed()             # nothing pending: no wait, no write
    assert len(request.writer.writes) == 1


@pytest.mark.asyncio
async def test_a_failed_write_resets_its_stream_and_no_other():
    metrics = ServiceMetrics()
    collector = SseWriteCollector(metrics)
    requests = [FakeRequest(fail=(i == 5)) for i in range(STREAMS)]
    resets = []
    outs = [collector.open(r, on_reset=lambda i=i: resets.append(i))
            for i, r in enumerate(requests)]
    for out in outs:
        out.put(b"data: x\n\n")
    await outs[5].flushed()         # a waiting handler is woken, not hung
    assert resets == [5] and outs[5].broken
    assert [len(r.writer.writes) for r in requests] == \
        [0 if i == 5 else 1 for i in range(STREAMS)]
    assert counters(metrics) == (1, STREAMS - 1)
    outs[5].put(b"data: late\n\n")  # dropped: the client is gone
    await one_iteration()
    assert collector.stats()["pending_bytes"] == 0 and resets == [5]


@pytest.mark.asyncio
async def test_first_write_is_stamped_at_the_transport_write():
    collector = SseWriteCollector(ServiceMetrics())
    request = FakeRequest()
    out = collector.open(request, on_reset=lambda: None)
    trace = Trace("rid")
    out.put(b"data: role\n\n")                      # no token: no stamp
    handed = time.monotonic()
    out.put(b"data: tok\n\n", first_write=trace)
    time.sleep(0.05)                                # the loop is held
    assert not [s for s in trace.spans if s.name == "stream.first_write"]
    await one_iteration()
    (event,) = [s for s in trace.spans if s.name == "stream.first_write"]
    (written, _), = request.writer.writes
    assert event.start >= written >= handed + 0.05
    out.put(b"data: tok2\n\n")
    await one_iteration()
    assert len([s for s in trace.spans
                if s.name == "stream.first_write"]) == 1


@pytest.mark.asyncio
async def test_the_flush_runs_in_no_requests_context():
    """The first hand-over of a batch makes the flush's task; it must not
    carry that request's trace into every other stream's write."""
    collector = SseWriteCollector(ServiceMetrics())
    request = FakeRequest()
    out = collector.open(request, on_reset=lambda: None)
    with use_trace(Trace("first-to-hand-over")):
        assert current_trace() is not None
        out.put(b"data: x\n\n")
    await one_iteration()
    assert request.writer.writes and request.writer.trace_at_write is None


@pytest.mark.asyncio
async def test_a_closed_stream_writes_nothing_more():
    """The handler ended (cancelled, or its stream failed) with chunks it
    never waited for: the response is finished, nothing may follow it."""
    collector = SseWriteCollector(ServiceMetrics())
    request = FakeRequest()
    out = collector.open(request, on_reset=lambda: None)
    out.put(b"data: x\n\n")
    out.close()
    await one_iteration()
    assert request.writer.writes == []


# --------------------------------------------------- behind a live service
class ScriptEngine:
    """Every request streams the chunks its ``user`` field names, a loop
    iteration apart; a ``None`` in a script is where the stream suspends."""

    def __init__(self, scripts: dict):
        self.scripts = scripts

    async def generate(self, request):
        script = self.scripts[request.data["user"]]

        async def gen():
            for item in script:
                if item is None:
                    await asyncio.sleep(0)
                else:
                    yield item
        return ResponseStream(gen(), request.ctx)


def script(i: int) -> list:
    """Stream ``i``: a role chunk, 3..7 tokens (every third stream gives
    two in one wake-up; some carry piggybacked usage), a comment-only
    event, a finish chunk and a usage-only chunk."""
    def chunk(**kw):
        return {"id": f"cmpl-{i}", "object": "chat.completion.chunk",
                "model": "script", **kw}

    items = [chunk(choices=[{"index": 0, "delta": {"role": "assistant",
                                                   "content": ""}}]), None]
    for k in range(3 + i % 5):
        tok = chunk(choices=[{"index": 0,
                              "delta": {"content": f"<s{i}t{k}> é"}}])
        if k == 1:
            tok["usage"] = {"prompt_tokens": 4, "completion_tokens": k + 1}
        items.append(tok)
        if not (i % 3 == 0 and k == 0):
            items.append(None)
    items += [Annotated(data=None, event="ping", comment=["keep", "alive"]),
              None,
              chunk(choices=[{"index": 0, "delta": {},
                              "finish_reason": "stop"}]),
              chunk(choices=[], usage={"prompt_tokens": 4,
                                       "completion_tokens": 3 + i % 5})]
    return items


def unbatched(items: list, rid: str, include_usage: bool) -> bytes:
    """The body a write per chunk gave: the handler's rules, one chunk at a
    time (nvext.request_id on the first, usage opt-in, [DONE] last)."""
    body, first = b"", True
    for item in items:
        if item is None:
            continue
        ann = item if isinstance(item, Annotated) else Annotated.from_data(item)
        chunk = ann.data
        if first and isinstance(chunk, dict):
            first = False
            chunk = {**chunk, "nvext": {"request_id": rid}}
        if isinstance(chunk, dict) and not include_usage:
            if chunk.get("usage") is not None and not chunk.get("choices"):
                continue
            chunk = {k: v for k, v in chunk.items() if k != "usage"}
        body += encode_annotated(Annotated(
            data=chunk, id=ann.id, event=ann.event,
            comment=ann.comment)).encode()
    return body + encode_done().encode()


def _url(svc, path="/v1/chat/completions"):
    return f"http://127.0.0.1:{svc.port}{path}"


def _body(model: str, user: str, **kw) -> dict:
    return {"model": model, "stream": True, "user": user,
            "messages": [{"role": "user", "content": "x"}], **kw}


async def serve(**engines) -> HttpService:
    svc = HttpService(port=0, host="127.0.0.1")
    for name, engine in engines.items():
        svc.manager.add_chat_model(name, engine)
    await svc.start()
    return svc


@pytest.mark.asyncio
@pytest.mark.parametrize("include_usage", [False, True])
async def test_64_streams_get_byte_for_byte_what_a_write_per_chunk_gave(
        include_usage):
    scripts = {str(i): script(i) for i in range(STREAMS)}
    svc = await serve(script=ScriptEngine(scripts))

    async def one(session, i):
        async with session.post(_url(svc), json=_body(
                "script", str(i),
                stream_options={"include_usage": include_usage})) as r:
            assert r.status == 200
            return r.headers["X-Request-Id"], await r.read()

    try:
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            got = await asyncio.gather(*(one(session, i)
                                         for i in range(STREAMS)))
    finally:
        await svc.stop()
    for i, (rid, body) in enumerate(got):
        assert body == unbatched(scripts[str(i)], rid, include_usage), i
    flushes, chunks = counters(svc.metrics)
    written = sum(body.count(b"\n\n") for _, body in got)
    assert chunks == written        # every chunk left through a pass
    assert flushes < chunks / 8     # ...and the passes held many streams'


async def raw_request(port: int, body: dict, rcvbuf: int = 0):
    """A streamed request over a socket of our own → (reader, writer)."""
    sock = socket.socket()
    if rcvbuf:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.setblocking(False)
    await asyncio.get_running_loop().sock_connect(sock, ("127.0.0.1", port))
    reader, writer = await asyncio.open_connection(sock=sock, limit=2 ** 16)
    payload = json.dumps(body).encode()
    writer.write((f"POST /v1/chat/completions HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Type: application/json\r\n"
                  f"Content-Length: {len(payload)}\r\n\r\n").encode()
                 + payload)
    await writer.drain()
    return reader, writer


async def read_frames(reader) -> list:
    """The response's HTTP chunks (transfer-encoding's), headers skipped."""
    await reader.readuntil(b"\r\n\r\n")
    frames = []
    while True:
        size = int((await reader.readline()).strip(), 16)
        frame = await reader.readexactly(size + 2)
        if size == 0:
            return frames
        frames.append(frame[:-2])


@pytest.mark.asyncio
async def test_one_wake_ups_chunks_leave_as_one_http_frame():
    """The wire's framing: what a stream produced in one wake-up is one
    chunk of the transfer encoding, whole SSE events, in order."""
    items = script(0)               # its first two tokens share a wake-up
    svc = await serve(script=ScriptEngine({"0": items}))
    try:
        reader, writer = await raw_request(
            svc.port, _body("script", "0",
                            stream_options={"include_usage": True}))
        frames = await asyncio.wait_for(read_frames(reader), 30)
        writer.close()
    finally:
        await svc.stop()
    assert all(f.endswith(b"\n\n") for f in frames)
    per_frame = [f.count(b"\n\n") for f in frames]
    #   role | tok0+tok1 | tok2 | ping | finish+usage+[DONE]
    assert per_frame == [1, 2, 1, 1, 3]
    rid = json.loads(frames[0][len(b"data: "):])["nvext"]["request_id"]
    assert b"".join(frames) == unbatched(items, rid, include_usage=True)


class QueueEngine:
    """Streams fed by the test, one queue a request."""

    def __init__(self):
        self.queues = []

    async def generate(self, request):
        q = asyncio.Queue()
        self.queues.append(q)

        async def gen():
            while True:
                item = await q.get()
                if item is None:
                    return
                yield item
        return ResponseStream(gen(), request.ctx)


@pytest.mark.asyncio
async def test_a_cycles_chunks_are_on_their_transports_when_the_yield_returns():
    """A real ``EngineCore`` loop whose step emits a token for each of 64
    streams: when the next step starts, the yield between them has handed
    over AND written all 64, inside the drain's bound."""
    feed = QueueEngine()
    svc = await serve(q=feed)
    engine = StubStepped(make_core())
    steps, found = 8, []

    def emit():
        # the yield that followed the previous step's tokens has returned
        found.append((counters(svc.metrics)[1],
                      svc.sse_writes.stats()["pending_bytes"]))
        for i, q in enumerate(feed.queues):
            q.put_nowait({"choices": [{"index": 0, "delta": {
                "content": f"<s{i}t{engine.steps}>"}}]})

    async def one(session, i):
        async with session.post(_url(svc), json=_body("q", str(i))) as r:
            return await r.read()

    try:
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            clients = [asyncio.create_task(one(session, i))
                       for i in range(STREAMS)]
            await wait_until(lambda: len(feed.queues) == STREAMS,
                             "64 streams at the engine")
            engine.on_step = {k: emit for k in range(1, steps + 2)}
            engine.core.ensure_started()
            await engine.until(steps + 1)
            await engine.core.stop()
            for q in feed.queues:
                q.put_nowait(None)
            bodies = await asyncio.wait_for(asyncio.gather(*clients), 60)
    finally:
        await svc.stop()
    assert found == [(STREAMS * k, 0) for k in range(steps + 1)]
    assert all(body.count(b"data: ") == steps + 2 for body in bodies)
    iters = engine.iters()[1:steps + 1]
    # handlers, then the flush (the clients share this loop: their reads
    # ride the same drain), never the bound
    assert all(2 <= n < BOUND for n in iters), iters


class FirehoseEngine:
    """One stream of 32 KiB chunks, as many as ``cap`` allows."""

    def __init__(self):
        self.yielded = 0
        self.cap = None             # None: without end

    async def generate(self, request):
        text = "x" * 32768

        async def gen():
            while self.cap is None or self.yielded < self.cap:
                self.yielded += 1
                yield {"choices": [{"index": 0, "delta": {"content": text}}]}
                await asyncio.sleep(0)
        return ResponseStream(gen(), request.ctx)


@pytest.mark.asyncio
async def test_a_slow_reader_holds_only_its_own_handler_and_bounded_bytes():
    hose = FirehoseEngine()
    svc = await serve(hose=hose, echo=EchoEngineFull())
    try:
        reader, writer = await raw_request(svc.port, _body("hose", "0"),
                                           rcvbuf=4096)

        async def stalled():
            before = hose.yielded
            await asyncio.sleep(0.3)
            return hose.yielded == before and before > 2

        # the reader reads nothing: the kernel's buffers fill, the
        # transport passes its high-water mark, the handler waits
        for _ in range(100):
            if await stalled():
                break
        else:
            raise AssertionError("the handler never waited for its reader")
        chunk = len(encode_annotated(Annotated.from_data(
            {"choices": [{"index": 0, "delta": {"content": "x" * 32768}}]})))
        assert svc.sse_writes.stats()["pending_bytes"] <= DRAIN_EVERY + chunk
        # ...while another stream is served whole
        async with aiohttp.ClientSession() as s:
            async with s.post(_url(svc), json={
                    "model": "echo", "stream": True,
                    "messages": [{"role": "user", "content": "a b c"}]}) as r:
                text = await asyncio.wait_for(r.read(), 30)
        assert text.endswith(encode_done().encode())
        assert hose.yielded and await stalled()
        # the reader catches up: the stream runs to its end, nothing lost
        hose.cap = hose.yielded + 3
        frames = await asyncio.wait_for(read_frames(reader), 60)
        writer.close()
    finally:
        await svc.stop()
    body = b"".join(frames)
    assert body.count(b'"content"') == hose.yielded == hose.cap
    assert body.endswith(encode_done().encode())
    assert 'status="success"' in svc.metrics.render().decode()


class TickingEngine:
    """Token chunks ten milliseconds apart; remembers who was killed."""

    def __init__(self, ticks: int):
        self.ticks = ticks
        self.killed = set()

    async def generate(self, request):
        user, ctx = request.data["user"], request.ctx

        async def gen():
            for k in range(self.ticks):
                if ctx.is_killed:
                    self.killed.add(user)
                    return
                yield {"choices": [{"index": 0,
                                    "delta": {"content": f"<{user}.{k}>"}}]}
                await asyncio.sleep(0.01)
            if ctx.is_killed:
                self.killed.add(user)
        return ResponseStream(gen(), ctx)


@pytest.mark.asyncio
async def test_a_reset_kills_its_own_request_while_the_other_63_finish():
    ticking = TickingEngine(ticks=60)
    svc = await serve(tick=ticking)

    async def one(session, i):
        async with session.post(_url(svc), json=_body("tick", str(i))) as r:
            return await r.read()

    try:
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=conn) as session:
            rest = [asyncio.create_task(one(session, i))
                    for i in range(1, STREAMS)]
            reader, writer = await raw_request(svc.port, _body("tick", "0"))
            await reader.readuntil(b"<0.1>")
            # a reset, not a close: the server's next write finds it
            writer.get_extra_info("socket").setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
            writer.close()
            bodies = await asyncio.wait_for(asyncio.gather(*rest), 60)
        await wait_until(lambda: 'status="cancelled"'
                         in svc.metrics.render().decode(),
                         "the reset request's guard closed")
    finally:
        await svc.stop()
    assert ticking.killed == {"0"}
    for i, body in enumerate(bodies, start=1):
        assert body.count(b"data: ") == 61 and f"<{i}.59>".encode() in body
        assert body.endswith(encode_done().encode())
    text = svc.metrics.render().decode()
    count = {status: float(line.split()[-1])
             for line in text.splitlines()
             if line.startswith("nv_llm_http_service_requests_total{")
             for status in ("success", "cancelled", "error")
             if f'status="{status}"' in line}
    assert count == {"success": STREAMS - 1, "cancelled": 1}
