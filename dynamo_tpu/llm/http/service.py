"""OpenAI-compatible HTTP frontend.

Reference: the axum service in lib/llm/src/http/service/{service_v2.rs:24-132,
openai.rs:132-528, error.rs} — `/v1/chat/completions`, `/v1/completions`,
`/v1/models`, `/metrics`, `/health`; SSE streaming with a client-disconnect
monitor that calls `ctx.kill()`; a `ModelManager` of named engines that
discovery can add/remove at runtime.

Implementation is aiohttp (asyncio-native streaming + backpressure); engines
are anything implementing `AsyncEngine[openai-request-dict, Annotated[chunk]]`
— an in-process pipeline, a JAX engine, or a remote client over the request
plane, interchangeably.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
import uuid
from typing import Dict, Optional

from aiohttp import web

from ...runtime.engine import AsyncEngine, Context, EngineContext
from ...runtime.tracing import Trace, current_trace, span, use_trace
from ..protocols.annotated import Annotated
from ..protocols.openai import (aggregate_chat_stream,
                                aggregate_completion_stream)
from ..protocols.sse import encode_annotated, encode_done
from .metrics import ServiceMetrics
from .sse_flush import SseWriteCollector

logger = logging.getLogger("dynamo_tpu.http")


class ModelManager:
    """Named engine registry (reference `ModelManager`, service_v2.rs)."""

    def __init__(self) -> None:
        self._chat: Dict[str, AsyncEngine] = {}
        self._completion: Dict[str, AsyncEngine] = {}
        self._cards: Dict[str, dict] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine,
                       card: Optional[dict] = None) -> None:
        self._chat[name] = engine
        self._cards.setdefault(name, card or {})

    def add_completion_model(self, name: str, engine: AsyncEngine,
                             card: Optional[dict] = None) -> None:
        self._completion[name] = engine
        self._cards.setdefault(name, card or {})

    def remove_model(self, name: str,
                     model_type: Optional[str] = None) -> None:
        """Remove one registry's entry ("chat"/"completion") or, with no
        model_type, every trace of the name."""
        if model_type in (None, "chat"):
            self._chat.pop(name, None)
        if model_type in (None, "completion"):
            self._completion.pop(name, None)
        if name not in self._chat and name not in self._completion:
            self._cards.pop(name, None)

    def chat_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._chat.get(name)

    def completion_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._completion.get(name)

    def list_models(self) -> list:
        return sorted(set(self._chat) | set(self._completion))


def _chunk_token_count(chunk) -> int:
    """Text-bearing choices in an OpenAI chunk (for the output-token metric)."""
    if not isinstance(chunk, dict):
        return 0
    n = 0
    for choice in chunk.get("choices") or []:
        delta = choice.get("delta")
        if delta is not None:
            if delta.get("content"):
                n += 1
        elif choice.get("text"):
            n += 1
    return n


def _error_response(status: int, message: str, err_type: str = "invalid_request_error"):
    return web.json_response(
        {"error": {"message": message, "type": err_type, "code": status}},
        status=status)


MAX_N = 16          # parallel-sampling fan-out cap (engine slots are finite)


class _FanoutContext(EngineContext):
    """Parent context of an n>1 request: cancellation fans out to every
    per-choice child generation."""

    __slots__ = ("children",)

    def __init__(self, request_id: Optional[str] = None):
        super().__init__(request_id)
        self.children: list = []

    def stop_generating(self) -> None:
        super().stop_generating()
        for c in self.children:
            c.stop_generating()

    def kill(self) -> None:
        super().kill()
        for c in self.children:
            c.kill()


async def _merge_choice_streams(streams, ectx: "_FanoutContext"):
    """n independent single-choice streams → one multi-choice stream
    (OpenAI `n` semantics): choice indices are rewritten to the sub-stream
    slot, chunk identity (id/created/model) is normalized to one stream's
    (each child pipeline minted its own), and per-stream usage folds into
    ONE trailing usage chunk — prompt counted once, completions summed.
    A child failure kills the sibling generations (their slots must not
    stay held) before the error surfaces."""
    from ..protocols.openai import usage_dict

    q: asyncio.Queue = asyncio.Queue(maxsize=4)   # backpressure: children
    done = object()                               # run at consumer speed

    async def pump(i, s):
        try:
            async for item in s:
                await q.put((i, item, None))
        except Exception as e:  # noqa: BLE001 — surfaced to the consumer
            await q.put((i, None, e))
        finally:
            await q.put((i, done, None))

    tasks = [asyncio.create_task(pump(i, s))
             for i, s in enumerate(streams)]
    usages: Dict[int, dict] = {}
    template: Optional[dict] = None
    pending = len(streams)
    try:
        while pending:
            i, item, err = await q.get()
            if err is not None:
                ectx.kill()               # reap the sibling generations
                raise err
            if item is done:
                pending -= 1
                continue
            ann = (item if isinstance(item, Annotated)
                   else Annotated.from_data(item))
            chunk = ann.data
            if isinstance(chunk, dict):
                if template is None and chunk.get("id"):
                    template = {k: chunk.get(k)
                                for k in ("id", "object", "created",
                                          "model")}
                elif template is not None and chunk.get("id"):
                    # one id per SSE stream (OpenAI contract) — children
                    # minted their own
                    chunk.update(template)
                for c in chunk.get("choices") or []:
                    c["index"] = i
                if chunk.get("usage") is not None:
                    usages[i] = chunk.pop("usage")
                    if not chunk.get("choices"):
                        continue          # combined usage emitted at the end
            yield ann
        if usages:
            vals = list(usages.values())
            combined = usage_dict(
                vals[0].get("prompt_tokens", 0),
                sum(v.get("completion_tokens", 0) for v in vals))
            yield Annotated.from_data({**(template or {}), "choices": [],
                                       "usage": combined})
    finally:
        for t in tasks:
            t.cancel()


async def _start_fanout(engine, body: dict, ectx: "_FanoutContext",
                        n: int):
    """Launch n single-choice generations CONCURRENTLY for one request
    (sequential dispatch would serialize per-child dial-back latency
    against remote engines). Seeded requests get seed+i per choice
    (reproducible but decorrelated); unseeded requests get a fresh random
    base per REQUEST (a constant base would make choices 1..n-1 identical
    across every request).

    This is whole-request fan-out: the prompt prefills n times and holds
    n engine slots. The deeper mechanism — one prefill, n decode streams
    sharing the prompt KV in the engine — would replace this layer's seed
    derivation and stream merging when the engine grows native n; until
    then the prefix cache absorbs the repeat prefills on cache-enabled
    engines."""
    import random

    base = (int(body["seed"]) if body.get("seed") is not None
            else random.getrandbits(31))

    async def one(i: int):
        sub = dict(body)
        sub["n"] = 1
        sub["seed"] = base + i
        sctx = EngineContext(f"{ectx.id}-c{i}")
        sctx.deadline_s = ectx.deadline_s   # children inherit the budget
        sctx.tenant = ectx.tenant           # ...and the tenant identity
        sctx.qos = ectx.qos
        ectx.children.append(sctx)
        return await engine.generate(Context(sub, sctx))

    results = await asyncio.gather(*(one(i) for i in range(n)),
                                   return_exceptions=True)
    errs = [r for r in results if isinstance(r, BaseException)]
    if errs:
        ectx.kill()          # reap the children that did start
        raise errs[0]
    return _merge_choice_streams(list(results), ectx)


class _StampingHandler(web.RequestHandler):
    """aiohttp's per-connection protocol, stamping when a request's first
    bytes reached this process: ``received_at`` (monotonic) is set by the
    first inbound segment while none is pending, read by ``_handle`` as
    the start of the request's trace, and cleared when the request's
    response is prepared (``HttpService._forget_stamp``) — after its whole
    body was read, so the body's later segments stamp nothing and a
    kept-alive connection's next request gets a stamp of its own."""

    __slots__ = ("received_at",)

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.received_at: Optional[float] = None

    def data_received(self, data: bytes) -> None:
        if self.received_at is None:
            self.received_at = time.monotonic()
        super().data_received(data)


class _StampingServer(web.Server):
    """``web.Server`` names its protocol class in ``__call__`` alone."""

    def __call__(self) -> web.RequestHandler:
        return _StampingHandler(self, loop=self._loop, **self._kwargs)


class _StampingRunner(web.AppRunner):
    """The one private seam (aiohttp 3.13; pinned by
    tests/test_ttft_timeline.py): ``_make_server`` is where the runner
    gets its protocol factory, so the ``Server`` the application built is
    rebuilt as one that makes stamping handlers."""

    async def _make_server(self) -> web.Server:
        built = await super()._make_server()
        return _StampingServer(
            built.request_handler, request_factory=built.request_factory,
            handler_cancellation=built.handler_cancellation,
            loop=built._loop, **built._kwargs)


class HttpService:
    """The frontend server (reference `HttpService` service_v2 builder)."""

    def __init__(self, port: int = 8080, host: str = "0.0.0.0",
                 manager: Optional[ModelManager] = None,
                 metrics: Optional[ServiceMetrics] = None):
        self.port = port
        self.host = host
        self.manager = manager or ModelManager()
        self.metrics = metrics or ServiceMetrics()
        # the streams' SSE writes, one pass a loop iteration (sse_flush.py)
        self.sse_writes = SseWriteCollector(self.metrics)
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self._chat)
        self.app.router.add_post("/v1/completions", self._completions)
        self.app.router.add_get("/v1/models", self._models)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_get("/health", self._health)
        self.app.router.add_get("/live", self._health)
        self.app.router.add_get("/traces", self._traces)
        self.app.router.add_get("/debug", self._debug)
        self.app.on_response_prepare.append(self._forget_stamp)
        self._runner: Optional[web.AppRunner] = None
        self._site: Optional[web.TCPSite] = None

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> None:
        if self._runner is not None:
            return  # already serving (run_forever after start is fine)
        self._runner = _StampingRunner(self.app)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, self.host, self.port)
        await self._site.start()
        if self.port == 0:
            # pick up the ephemeral port for tests
            self.port = self._site._server.sockets[0].getsockname()[1]  # type: ignore
        logger.info("HTTP service listening on %s:%s", self.host, self.port)

    async def stop(self) -> None:
        # claim before the await (DL008): concurrent stop()s must not
        # both run cleanup
        runner, self._runner = self._runner, None
        if runner is not None:
            await runner.cleanup()

    async def run_forever(self) -> None:
        await self.start()
        try:
            while True:
                await asyncio.sleep(3600)
        finally:
            await self.stop()

    # ------------------------------------------------------------- handlers
    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy",
                                  "models": self.manager.list_models()})

    async def _traces(self, request: web.Request) -> web.Response:
        """Recent per-request traces (debug): stage latencies keyed by
        request id; ?request_id= filters to one request."""
        from ...runtime.tracing import tracer
        rid = request.query.get("request_id")
        data = tracer.find(rid) if rid else tracer.recent()
        return web.json_response({"traces": data,
                                  "completed": tracer.completed})

    async def _debug(self, request: web.Request) -> web.Response:
        """Operator introspection: tracer sampling state + every
        in-process engine flight recorder's ring (per-dispatch records,
        event-loop lag) — the same payload ``llmctl trace dump``
        collects from remote workers (engine/flight_recorder.py)."""
        from ...engine.flight_recorder import all_recorders
        from ...runtime.tracing import tracer
        try:
            last = int(request.query.get("last", "64"))
        except ValueError:
            last = 64
        return web.json_response({
            "tracer": tracer.stats(),
            "sse_writes": self.sse_writes.stats(),
            "flight_recorders": {
                name: {"stats": fr.stats(), "records": fr.dump(last=last)}
                for name, fr in all_recorders().items()},
        })

    async def _models(self, request: web.Request) -> web.Response:
        now = int(time.time())
        data = []
        for m in self.manager.list_models():
            entry = {"id": m, "object": "model", "created": now,
                     "owned_by": "dynamo-tpu"}
            card = self.manager._cards.get(m)
            if card:
                # registry provenance (llm/registry.py): geometry +
                # program-set key so a client can tell which compiled
                # program family is serving the name
                entry["nvext"] = {k: card[k] for k in
                                  ("program_set", "revision", "endpoint",
                                   "kv_block_size")
                                  if card.get(k) is not None}
            data.append(entry)
        return web.json_response({"object": "list", "data": data})

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(body=self.metrics.render(),
                            content_type="text/plain", charset="utf-8")

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._handle(request, "chat_completions")

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle(request, "completions")

    @staticmethod
    async def _forget_stamp(request: web.Request, response) -> None:
        """on_response_prepare, every route: the request is read, so the
        connection's next inbound segment opens the next request."""
        if isinstance(request.protocol, _StampingHandler):
            request.protocol.received_at = None

    async def _handle(self, request: web.Request,
                      endpoint: str) -> web.StreamResponse:
        # per-request trace (reference egress/push.rs:134-151): stage
        # latencies from the request's first byte through dispatch to the
        # last byte written, keyed by the request id the control plane
        # carries everywhere. It opens FIRST, at the protocol's stamp (a
        # server that is not ours has none: now): http.wire is the parser
        # and the event-loop hops to this task
        ftrace = Trace(uuid.uuid4().hex, role="frontend",
                       start=getattr(request.protocol, "received_at", None))
        ftrace.add_span("http.wire", ftrace.start, time.monotonic())
        with use_trace(ftrace):
            return await self._serve(request, endpoint, ftrace)

    async def _serve(self, request: web.Request, endpoint: str,
                     ftrace: Trace) -> web.StreamResponse:
        def refuse(status: int, message: str,
                   err_type: str = "invalid_request_error"):
            ftrace.set_error(message)
            return _error_response(status, message, err_type)

        with ftrace.span("http.read_body") as read:
            try:
                body = await request.json()
            except json.JSONDecodeError as e:
                return refuse(400, f"invalid JSON body: {e}")
        model = body.get("model")
        if not model:
            return refuse(400, "missing 'model'")
        is_chat = endpoint == "chat_completions"
        engine = (self.manager.chat_engine(model) if is_chat
                  else self.manager.completion_engine(model))
        if engine is None:
            return refuse(404, f"model '{model}' not found",
                          "model_not_found")
        raw_n = body.get("n")
        if raw_n is None:
            n_choices = 1
        elif isinstance(raw_n, int) and not isinstance(raw_n, bool):
            n_choices = raw_n
        else:
            # 2.9 must not silently truncate to 2, nor true to 1
            return refuse(400, "'n' must be an integer")
        if not 1 <= n_choices <= MAX_N:
            return refuse(400, f"'n' must be between 1 and {MAX_N}")
        streaming = bool(body.get("stream", False))
        ectx = (EngineContext(ftrace.request_id) if n_choices == 1
                else _FanoutContext(ftrace.request_id))
        # multi-tenant identity (llm/tenancy.py): tenant + QoS class ride
        # the EngineContext so egress stamps them on the request-plane
        # control message (codec.RequestControlMessage tenant/priority)
        nvext = body.get("nvext") or {}
        if nvext.get("tenant") is not None:
            ectx.tenant = str(nvext["tenant"])
        if nvext.get("priority") is not None:
            ectx.qos = str(nvext["priority"])
        # end-to-end deadline (docs/chaos.md): nvext.deadline_ms or the
        # X-Request-Deadline-Ms header arms a budget that rides the
        # request plane (codec.RequestControlMessage.deadline_ms) all
        # the way into the engine's per-tick cancellation sweep
        deadline_ms = (nvext.get("deadline_ms")
                       or request.headers.get("X-Request-Deadline-Ms"))
        if deadline_ms is not None:
            try:
                ectx.set_deadline_ms(float(deadline_ms))
            except (TypeError, ValueError):
                return refuse(400, f"invalid deadline_ms: {deadline_ms!r}")
        # the operator's histograms count from the first byte too: what
        # the client waited for, not what followed the parse
        guard = self.metrics.inflight_guard(model, endpoint, streaming,
                                            start=ftrace.start)
        ftrace.add_span("http.validate", read.end, time.monotonic())
        with span("dispatch", model=model, endpoint=endpoint):
            try:
                if n_choices == 1:
                    stream = await engine.generate(Context(body, ectx))
                else:
                    stream = await _start_fanout(engine, body, ectx,
                                                 n_choices)
            except ValueError as e:
                guard.close()
                return refuse(400, str(e))
            except Exception as e:  # noqa: BLE001 — engine boundary
                logger.exception("engine error on %s", endpoint)
                guard.close()
                return refuse(500, f"engine error: {e}", "internal_error")

        if streaming:
            include_usage = bool((body.get("stream_options") or {})
                                 .get("include_usage"))
            with span("stream"):
                return await self._stream_sse(request, stream, ectx,
                                              guard, include_usage)
        with span("aggregate"):
            return await self._unary(stream, ectx, guard, is_chat)

    async def _unary(self, stream, ectx: EngineContext, guard,
                     is_chat: bool) -> web.Response:
        try:
            folded = await (aggregate_chat_stream(stream) if is_chat
                            else aggregate_completion_stream(stream))
            guard.mark_ok()
            # surface the request id so a user report joins the
            # collector's trace tree (docs/observability.md)
            return web.json_response(
                folded, headers={"X-Request-Id": ectx.id})
        except RuntimeError as e:
            return _error_response(500, str(e), "internal_error")
        finally:
            guard.close()

    async def _stream_sse(self, request: web.Request, stream,
                          ectx: EngineContext, guard,
                          include_usage: bool) -> web.StreamResponse:
        resp = web.StreamResponse(status=200, headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
            "X-Accel-Buffering": "no",
            # join a user report to the collector's trace tree
            "X-Request-Id": ectx.id,
        })
        try:
            await resp.prepare(request)
        except (ConnectionResetError, asyncio.CancelledError):
            guard.mark_cancelled()
            guard.close()
            ectx.kill()
            raise

        def on_reset():
            guard.mark_cancelled()
            ectx.kill()

        # Disconnect monitor (reference openai.rs:406): if the client goes
        # away mid-stream, kill() the context so the engine frees its slot.
        # aiohttp has no disconnect future, so poll the transport.
        async def monitor():
            while True:
                await asyncio.sleep(0.25)
                tr = request.transport
                if tr is None or tr.is_closing():
                    on_reset()
                    return

        monitor_task = asyncio.create_task(monitor())
        # the chunks go out through the service's write collector: handed
        # over here, written with the other streams' in the loop's next
        # iteration (sse_flush.py)
        out = self.sse_writes.open(request, on_reset)
        first_chunk = True
        # stream.first_write (the request's trace): the first token chunk
        # has reached the transport — with the engine's spans it tiles TTFT
        # from inside the server (docs/observability.md)
        trace = current_trace()
        try:
            async for ann in stream:
                if not isinstance(ann, Annotated):
                    ann = Annotated.from_data(ann)
                chunk = ann.data
                if first_chunk and isinstance(chunk, dict):
                    # nvext.request_id on the first SSE chunk: SSE
                    # consumers that never see response headers (EventSource
                    # wrappers, log captures) can still join user reports
                    # to collector traces
                    first_chunk = False
                    chunk = dict(chunk)
                    chunk["nvext"] = {**(chunk.get("nvext") or {}),
                                      "request_id": ectx.id}
                    ann = Annotated(data=chunk, id=ann.id, event=ann.event,
                                    comment=ann.comment)
                if isinstance(chunk, dict) and not include_usage:
                    # usage chunks / piggybacked usage are opt-in for SSE
                    if chunk.get("usage") is not None and not chunk.get("choices"):
                        continue
                    if "usage" in chunk:
                        chunk = {k: v for k, v in chunk.items() if k != "usage"}
                        ann = Annotated(data=chunk, id=ann.id, event=ann.event,
                                        comment=ann.comment)
                n_tok = _chunk_token_count(chunk)
                if n_tok:
                    guard.note_token(n_tok)
                out.put(encode_annotated(ann).encode(),
                        first_write=trace if n_tok else None)
                if n_tok:
                    trace = None
                if out.over_mark:
                    # back-pressure, this stream's alone: its bytes
                    # written, its transport drained
                    await out.drained()
                if out.broken:
                    return resp
            # the last chunk and [DONE] are on the transport before the
            # response ends
            if not ectx.is_killed:
                out.put(encode_done().encode())
            await out.flushed()
            if not ectx.is_killed and not out.broken:
                guard.mark_ok()
        except asyncio.CancelledError:
            on_reset()
            raise
        except Exception:
            # what the stream gave before it failed still goes out
            await out.flushed()
            raise
        finally:
            out.close()
            monitor_task.cancel()
            guard.close()
        return resp
