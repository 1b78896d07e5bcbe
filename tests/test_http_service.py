"""HTTP frontend tests — analog of lib/llm/tests/http-service.rs:41-300:
stub engines behind a live server, streaming + unary + error matrix +
Prometheus counters."""

import asyncio
import json

import aiohttp
import pytest

from dynamo_tpu.llm.engines.echo import EchoEngineCore, EchoEngineFull
from dynamo_tpu.llm.http import HttpService
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.sse import parse_sse_stream
from dynamo_tpu.runtime import ResponseStream


class AlwaysFailEngine:
    async def generate(self, request):
        raise RuntimeError("engine exploded")


class ErrorStreamEngine:
    async def generate(self, request):
        async def gen():
            yield Annotated.from_error("midstream failure")
        return ResponseStream(gen(), request.ctx)


@pytest.fixture
async def service():
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model("echo", EchoEngineFull())
    svc.manager.add_completion_model("echo", EchoEngineFull())
    svc.manager.add_chat_model("fail", AlwaysFailEngine())
    svc.manager.add_chat_model("errstream", ErrorStreamEngine())
    await svc.start()
    yield svc
    await svc.stop()


def _url(svc, path):
    return f"http://127.0.0.1:{svc.port}{path}"


@pytest.mark.asyncio
async def test_models_list(service):
    async with aiohttp.ClientSession() as s:
        async with s.get(_url(service, "/v1/models")) as r:
            body = await r.json()
    ids = [m["id"] for m in body["data"]]
    assert "echo" in ids and body["object"] == "list"


@pytest.mark.asyncio
async def test_chat_unary(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "echo",
            "messages": [{"role": "user", "content": "hello world"}],
        }) as r:
            assert r.status == 200
            body = await r.json()
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["message"]["content"].strip() == "hello world"
    assert body["choices"][0]["finish_reason"] == "stop"


@pytest.mark.asyncio
async def test_chat_streaming_sse(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "echo", "stream": True,
            "messages": [{"role": "user", "content": "a b c"}],
        }) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            anns = [a async for a in parse_sse_stream(r.content.iter_any())]
    chunks = [a.data for a in anns if a.data]
    text = "".join(c["choices"][0]["delta"].get("content", "")
                   for c in chunks if c.get("choices"))
    assert text.strip() == "a b c"


@pytest.mark.asyncio
async def test_request_id_surfaced_to_clients(service):
    """ISSUE 7 satellite: the request/trace id reaches the CLIENT —
    X-Request-Id on unary and SSE responses, plus an nvext.request_id
    field on the first SSE chunk — so a user report joins the
    collector's trace tree (and the frontend's local /traces ring)."""
    async with aiohttp.ClientSession() as s:
        # unary: header present and joinable against /traces
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "echo",
            "messages": [{"role": "user", "content": "hi"}],
        }) as r:
            assert r.status == 200
            rid = r.headers.get("X-Request-Id")
            assert rid
        async with s.get(_url(service, "/traces"),
                         params={"request_id": rid}) as r:
            traces = (await r.json())["traces"]
        assert traces and traces[-1]["request_id"] == rid

        # SSE: header AND the nvext field on the first chunk
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "echo", "stream": True,
            "messages": [{"role": "user", "content": "a b"}],
        }) as r:
            assert r.status == 200
            sse_rid = r.headers.get("X-Request-Id")
            assert sse_rid and sse_rid != rid
            anns = [a async for a in parse_sse_stream(r.content.iter_any())]
    chunks = [a.data for a in anns if a.data]
    assert chunks[0]["nvext"]["request_id"] == sse_rid
    # only the first chunk carries it (no per-token overhead)
    assert all("nvext" not in c for c in chunks[1:])


@pytest.mark.asyncio
async def test_debug_endpoint_exposes_tracer_and_flight_recorders(service):
    """/debug: tracer sampling stats + every in-process engine flight
    recorder ring (the llmctl trace dump payload, served locally)."""
    from dynamo_tpu.engine.flight_recorder import (FlightRecorder,
                                                   register_recorder)
    fr = FlightRecorder(capacity=4)
    fr.record("decode", K=2, batch_fill=1)
    name = register_recorder(fr, name="http-debug-test")
    async with aiohttp.ClientSession() as s:
        async with s.get(_url(service, "/debug")) as r:
            assert r.status == 200
            body = await r.json()
    assert "completed" in body["tracer"]
    rec = body["flight_recorders"][name]
    assert rec["stats"]["records_total"] == 1
    assert rec["records"][0]["kind"] == "decode"


@pytest.mark.asyncio
async def test_unknown_model_404(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "nope", "messages": [{"role": "user", "content": "x"}],
        }) as r:
            assert r.status == 404
            body = await r.json()
    assert body["error"]["type"] == "model_not_found"


@pytest.mark.asyncio
async def test_invalid_json_400(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"),
                          data=b"{oops") as r:
            assert r.status == 400


@pytest.mark.asyncio
async def test_engine_failure_500(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "fail", "messages": [{"role": "user", "content": "x"}],
        }) as r:
            assert r.status == 500


@pytest.mark.asyncio
async def test_midstream_error_surfaces_unary(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
            "model": "errstream",
            "messages": [{"role": "user", "content": "x"}],
        }) as r:
            assert r.status == 500
            body = await r.json()
    assert "midstream failure" in body["error"]["message"]


@pytest.mark.asyncio
async def test_metrics_counters(service):
    async with aiohttp.ClientSession() as s:
        await s.post(_url(service, "/v1/chat/completions"), json={
            "model": "echo", "messages": [{"role": "user", "content": "x"}]})
        async with s.get(_url(service, "/metrics")) as r:
            text = await r.text()
    assert 'nv_llm_http_service_requests_total' in text
    assert 'model="echo"' in text
    assert 'status="success"' in text


@pytest.mark.asyncio
async def test_health(service):
    async with aiohttp.ClientSession() as s:
        async with s.get(_url(service, "/health")) as r:
            body = await r.json()
    assert body["status"] == "healthy" and "echo" in body["models"]


@pytest.mark.asyncio
async def test_streaming_records_itl_histogram(service):
    """Streaming requests emit inter-token-latency samples alongside TTFT
    (reference exposes TTFT only; ITL is the decode-side SLO metric)."""
    async with aiohttp.ClientSession() as session:
        async with session.post(_url(service, "/v1/chat/completions"), json={
                "model": "echo", "stream": True, "max_tokens": 6,
                "messages": [{"role": "user",
                              "content": "a few words to stream"}]}) as r:
            assert r.status == 200
            async for _ in r.content:
                pass
        async with session.get(_url(service, "/metrics")) as r:
            text = await r.text()
    assert "nv_llm_http_service_inter_token_latency_seconds_count" in text
    count = [l for l in text.splitlines()
             if l.startswith("nv_llm_http_service_inter_token_latency_"
                             "seconds_count")][0]
    assert float(count.split()[-1]) >= 1   # at least one gap observed


# ---------------------------------------------------------------------------
# n>1 parallel sampling (OpenAI `n`) + per-token logprobs over the wire
# (round-2 VERDICT weak-8: these surfaces were untested end to end)
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_n_choices_unary(service):
    body = {"model": "echo", "n": 3,
            "messages": [{"role": "user", "content": "same text"}]}
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"),
                          json=body) as r:
            assert r.status == 200
            out = await r.json()
    assert [c["index"] for c in out["choices"]] == [0, 1, 2]
    texts = {c["message"]["content"].strip() for c in out["choices"]}
    assert texts == {"same text"}          # echo: every choice echoes
    # usage: prompt counted once, completions summed across choices
    one = await _single_usage(service)
    assert out["usage"]["prompt_tokens"] == one["prompt_tokens"]
    assert out["usage"]["completion_tokens"] == \
        3 * one["completion_tokens"]


async def _single_usage(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"),
                          json={"model": "echo", "messages": [
                              {"role": "user", "content": "same text"}]}) as r:
            return (await r.json())["usage"]


@pytest.mark.asyncio
async def test_n_choices_streaming(service):
    body = {"model": "echo", "n": 2, "stream": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": "hi there"}]}
    indices = set()
    usages = []
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"),
                          json=body) as r:
            assert r.status == 200
            async for ann in parse_sse_stream(r.content):
                chunk = ann.data if hasattr(ann, "data") else ann
                if not isinstance(chunk, dict):
                    continue
                for c in chunk.get("choices") or []:
                    indices.add(c["index"])
                if chunk.get("usage"):
                    usages.append(chunk["usage"])
    assert indices == {0, 1}
    assert len(usages) == 1                # ONE combined usage chunk
    assert usages[0]["completion_tokens"] > 0


@pytest.mark.asyncio
async def test_n_out_of_range_rejected(service):
    async with aiohttp.ClientSession() as s:
        for n in (0, 17, "x"):
            async with s.post(_url(service, "/v1/chat/completions"),
                              json={"model": "echo", "n": n,
                                    "messages": []}) as r:
                assert r.status == 400, f"n={n} accepted"


class LogprobStubEngine:
    """Emits BackendOutput with per-token logprobs (the engine layer's
    contract) so the full preproc→wire→aggregate path is under test."""

    async def generate(self, request):
        from dynamo_tpu.llm.protocols.common import (BackendOutput,
                                                     FinishReason)
        from dynamo_tpu.runtime import ResponseStream

        async def gen():
            yield Annotated.from_data(BackendOutput(
                token_ids=[5], tokens=["he"], text="he",
                log_probs=[-0.5],
                top_logprobs=[{5: -0.5, 9: -1.5}]))
            yield Annotated.from_data(BackendOutput(
                token_ids=[6], tokens=["llo"], text="llo",
                log_probs=[-0.25], top_logprobs=[{6: -0.25}],
                finish_reason=FinishReason.EOS))
        return ResponseStream(gen(), request.ctx)


@pytest.fixture
async def logprob_service(tiny_model_dir):
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import link

    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir,
                                              display_name="lp")
    pipe = link(OpenAIPreprocessor(mdc), LogprobStubEngine())
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model("lp", pipe)
    svc.manager.add_completion_model("lp", pipe)
    await svc.start()
    yield svc
    await svc.stop()


@pytest.mark.asyncio
async def test_sse_logprobs_content(logprob_service):
    """Per-token logprob CONTENT rides the SSE deltas when the client asks
    (chat: logprobs bool + top_logprobs count)."""
    body = {"model": "lp", "stream": True, "logprobs": True,
            "top_logprobs": 2,
            "messages": [{"role": "user", "content": "x"}]}
    entries = []
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(logprob_service, "/v1/chat/completions"),
                          json=body) as r:
            assert r.status == 200
            async for ann in parse_sse_stream(r.content):
                chunk = ann.data if hasattr(ann, "data") else ann
                if not isinstance(chunk, dict):
                    continue
                for c in chunk.get("choices") or []:
                    entries.extend((c.get("logprobs") or {})
                                   .get("content") or [])
    assert [e["token"] for e in entries] == ["he", "llo"]
    assert entries[0]["logprob"] == -0.5
    assert {t["token"] for t in entries[0]["top_logprobs"]} == {"5", "9"}


@pytest.mark.asyncio
async def test_unary_logprobs_folded(logprob_service):
    """The unary aggregator folds streamed logprob deltas into the final
    choice (round-2 gap: aggregator dropped logprobs entirely)."""
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(logprob_service, "/v1/chat/completions"),
                          json={"model": "lp", "logprobs": True,
                                "messages": [{"role": "user",
                                              "content": "x"}]}) as r:
            assert r.status == 200
            out = await r.json()
    lp = out["choices"][0]["logprobs"]["content"]
    assert [(e["token"], e["logprob"]) for e in lp] == \
        [("he", -0.5), ("llo", -0.25)]
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(logprob_service, "/v1/completions"),
                          json={"model": "lp", "prompt": "x",
                                "logprobs": 1}) as r:
            assert r.status == 200
            out = await r.json()
    lp = out["choices"][0]["logprobs"]
    assert lp["token_logprobs"] == [-0.5, -0.25]
    assert lp["tokens"] == ["he", "llo"]


@pytest.mark.asyncio
async def test_histograms_count_from_the_first_byte(service):
    """ISSUE 39: a request whose body trickles in is timed from its first
    byte, not from the handler's parse of the whole body: the operator's
    time-to-first-token and duration histograms hold what the client
    waited for."""
    payload = json.dumps({
        "model": "echo", "stream": True,
        "messages": [{"role": "user", "content": "a b c"}]}).encode()
    head = (f"POST /v1/chat/completions HTTP/1.1\r\nHost: x\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n"
            f"Connection: close\r\n\r\n").encode()
    reader, writer = await asyncio.open_connection("127.0.0.1", service.port)
    writer.write(head + payload[:10])
    await writer.drain()
    await asyncio.sleep(0.3)
    writer.write(payload[10:])
    await writer.drain()
    answer = await reader.read()
    writer.close()
    assert answer.startswith(b"HTTP/1.1 200") and b"[DONE]" in answer
    sums = {line.split("{")[0]: float(line.rsplit(" ", 1)[1])
            for line in service.metrics.render().decode().splitlines()
            if line.startswith("nv_llm_http_service_")
            and "_seconds_sum{" in line}
    assert sums["nv_llm_http_service_time_to_first_token_seconds_sum"] >= 0.3
    assert sums["nv_llm_http_service_request_duration_seconds_sum"] >= 0.3
    # the gap between tokens is not moved by it
    assert sums["nv_llm_http_service_inter_token_latency_seconds_sum"] < 0.3


# ---------------------------------------------------------------------------
# ISSUE 44: the SSE write collector (llm/http/sse_flush.py) as the service
# shows it: the two counters beside the other series, /debug, and a stream
# that fails after its first chunks
# ---------------------------------------------------------------------------


def _sse_counters(svc) -> dict:
    return {line.split()[0]: float(line.split()[1])
            for line in svc.metrics.render().decode().splitlines()
            if line.startswith("nv_llm_http_service_sse_")
            and "_total" in line}


@pytest.mark.asyncio
@pytest.mark.parametrize("endpoint,body", [
    ("/v1/chat/completions",
     {"messages": [{"role": "user", "content": "a b c"}]}),
    ("/v1/completions", {"prompt": "a b c"}),
])
async def test_sse_flush_counters_are_exported(service, endpoint, body):
    """One stream alone: a pass a wake-up, a chunk (or the few one wake-up
    gave) a pass; both series sit in /metrics and add up to the events the
    client read."""
    assert _sse_counters(service) == {
        "nv_llm_http_service_sse_flushes_total": 0.0,
        "nv_llm_http_service_sse_flushed_chunks_total": 0.0}
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, endpoint), json={
                "model": "echo", "stream": True, **body}) as r:
            assert r.status == 200
            raw = await r.read()
        async with s.get(_url(service, "/metrics")) as r:
            text = await r.text()
    got = _sse_counters(service)
    assert got["nv_llm_http_service_sse_flushed_chunks_total"] == \
        raw.count(b"\n\n")
    assert 1 <= got["nv_llm_http_service_sse_flushes_total"] <= \
        got["nv_llm_http_service_sse_flushed_chunks_total"]
    assert "nv_llm_http_service_sse_flushes_total" in text
    assert "nv_llm_http_service_sse_flushed_chunks_total" in text


@pytest.mark.asyncio
async def test_debug_shows_the_write_collectors_pending_bytes(service):
    async with aiohttp.ClientSession() as s:
        async with s.post(_url(service, "/v1/chat/completions"), json={
                "model": "echo", "stream": True,
                "messages": [{"role": "user", "content": "a b"}]}) as r:
            await r.read()
        async with s.get(_url(service, "/debug")) as r:
            body = await r.json()
    # nothing is left behind a finished stream
    assert body["sse_writes"] == {"pending_responses": 0, "pending_bytes": 0}


class RaisingStreamEngine:
    """Two chunks, then the stream itself raises (not an error event)."""

    async def generate(self, request):
        async def gen():
            yield {"choices": [{"index": 0, "delta": {"content": "one"}}]}
            yield {"choices": [{"index": 0, "delta": {"content": "two"}}]}
            raise RuntimeError("stream died")
        return ResponseStream(gen(), request.ctx)


@pytest.mark.asyncio
async def test_chunks_before_a_stream_failure_still_reach_the_client(service):
    """What a stream gave before it raised went out when each chunk was
    written at once; handed to the collector, it still does, and no
    [DONE] follows."""
    service.manager.add_chat_model("raising", RaisingStreamEngine())
    got = b""
    async with aiohttp.ClientSession() as s:
        try:
            async with s.post(_url(service, "/v1/chat/completions"), json={
                    "model": "raising", "stream": True,
                    "messages": [{"role": "user", "content": "x"}]}) as r:
                assert r.status == 200
                async for piece in r.content.iter_any():
                    got += piece
        except aiohttp.ClientError:
            pass                    # the connection is dropped mid-body
    assert b'"one"' in got and b'"two"' in got and b"[DONE]" not in got
    assert 'status="error"' in service.metrics.render().decode()
