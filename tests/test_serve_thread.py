"""``launch.run.run_http`` with a local engine serves from a thread and an
event loop of its own: a call that blocks the caller's loop (and releases
the interpreter lock, as a profiler writing its trace does) stops no
request. Where something under the pipeline holds the caller's loop, the
front end serves in place, as before."""

import asyncio
import json
import socket
import threading
import time
import urllib.request

import pytest

from dynamo_tpu.launch import run

pytestmark = pytest.mark.asyncio


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _args(model_dir: str, out: str, port: int, *flags):
    return run.build_parser().parse_args(
        ["in=http", f"out={out}", "--model-path", model_dir,
         "--model-name", "tiny", "--http-host", "127.0.0.1",
         "--http-port", str(port), *flags])


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


def _complete(port: int, max_tokens: int) -> dict:
    body = json.dumps({"model": "tiny", "prompt": "hello world",
                       "max_tokens": max_tokens, "temperature": 0,
                       "nvext": {"ignore_eos": True}}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


async def _serving(args, pipeline, core):
    """The benchmark's and chip_smoke.py's pattern: a task of the caller's
    loop, polled until /health answers."""
    task = asyncio.create_task(run.run_http(args, pipeline, core))
    for _ in range(400):
        if task.done():
            task.result()
        try:
            await asyncio.to_thread(_get, args.http_port, "/health")
            return task
        except OSError:
            await asyncio.sleep(0.025)
    raise AssertionError("HTTP service never answered /health")


async def _build(tiny_model_dir, out="jax", *flags):
    args = _args(tiny_model_dir, out, _free_port(), "--random-weights",
                 "--max-model-len", "128", "--num-kv-blocks", "32", *flags)
    runtime = await run.make_runtime(args)
    engine, mdc, core = await run.build_engine(args, out, runtime)
    return args, runtime, run.link_pipeline(engine, mdc), core


def _serve_threads() -> list:
    return [t for t in threading.enumerate() if t.name == "http-serve"]


async def test_blocked_caller_loop_stops_no_request(tiny_model_dir):
    args, runtime, pipeline, core = await _build(tiny_model_dir)
    task = await _serving(args, pipeline, core)
    try:
        await asyncio.to_thread(_complete, args.http_port, 2)   # compiles
        assert core.running and len(_serve_threads()) == 1
        answered = {}

        def client():
            time.sleep(0.2)                 # well inside the block below
            t0 = time.monotonic()
            answered["body"] = _complete(args.http_port, 8)
            answered["s"] = time.monotonic() - t0
            answered["at"] = time.monotonic()

        t = threading.Thread(target=client)
        t.start()
        time.sleep(3.0)          # the caller's loop is blocked, lock free
        unblocked = time.monotonic()
        await asyncio.to_thread(t.join)
        assert answered["body"]["usage"]["completion_tokens"] == 8
        # served while this loop was blocked, not after it came back
        assert answered["at"] < unblocked, (answered["s"],)
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
    # cancelling the caller's task stopped the engine on its own loop,
    # then the thread; a second stop from this loop is a no-op
    assert task.cancelled()
    assert not core.running and not _serve_threads()
    await core.stop()
    await runtime.shutdown()


async def test_engine_loop_and_handlers_share_the_serving_thread(
        tiny_model_dir):
    args, runtime, pipeline, core = await _build(tiny_model_dir)
    seen = []
    step = core._dispatch_multi

    def spy(*a, **kw):
        seen.append(threading.current_thread().name)
        return step(*a, **kw)
    core._dispatch_multi = spy
    task = await _serving(args, pipeline, core)
    try:
        out = await asyncio.to_thread(_complete, args.http_port, 4)
        assert out["usage"]["completion_tokens"] == 4
        assert seen and set(seen) == {"http-serve"}
        # the build log: the launcher's record of the constructor, and the
        # programs the first request built, by the loop's phase
        records = core.flight.dump()
        (engine_build,) = [r for r in records if r["kind"] == "engine_build"]
        assert engine_build["host_ms"] >= engine_build["built_ms"] >= 0
        built = {r["program"]: r["phase"] for r in records
                 if r["kind"] == "build"}
        assert built["prefill"] == "admit" and built["decode_k"] == "dispatch"
        decode = [r for r in records if r["kind"] == "decode"]
        assert decode[0]["built"] >= engine_build["built"] + 2
        assert core.metrics().programs_built_total == decode[-1]["built"]
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        await core.stop()
        await runtime.shutdown()


async def test_bind_error_reaches_the_caller(tiny_model_dir):
    args, runtime, pipeline, core = await _build(tiny_model_dir)
    with socket.socket() as held:
        held.bind(("127.0.0.1", args.http_port))
        held.listen(1)
        with pytest.raises(OSError):
            await asyncio.wait_for(run.run_http(args, pipeline, core), 30)
    # the thread tells its error before it ends: give it the moment
    for thread in _serve_threads():
        await asyncio.to_thread(thread.join, 10)
    assert not _serve_threads() and not core.running
    await runtime.shutdown()


@pytest.mark.parametrize("case", ["no_core", "engine_already_running",
                                  "remote_prefill", "multi_node"])
async def test_serves_in_place_where_the_callers_loop_is_held(
        tiny_model_dir, case):
    if case == "no_core":
        args = _args(tiny_model_dir, "echo_core", _free_port())
        runtime = await run.make_runtime(args)
        engine, mdc, core = await run.build_engine(args, "echo_core",
                                                   runtime)
        pipeline = run.link_pipeline(engine, mdc)
    else:
        args, runtime, pipeline, core = await _build(tiny_model_dir)
        if case == "engine_already_running":
            core.ensure_started()
        elif case == "remote_prefill":
            args.remote_prefill = True    # the flag alone: nothing dialled
        else:
            args.num_nodes = 2
    task = await _serving(args, pipeline, core)
    try:
        assert not _serve_threads()
        out = await asyncio.to_thread(_complete, args.http_port, 3)
        # the echo engine answers with the prompt's two tokens
        want = 3 if core is not None else 2
        assert out["usage"]["completion_tokens"] == want
    finally:
        task.cancel()
        await asyncio.gather(task, return_exceptions=True)
        if core is not None:
            await core.stop()
        await runtime.shutdown()
