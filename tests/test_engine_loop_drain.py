"""The engine loop's yield drains the shared event loop until it is quiet
(engine/core.py ``_yield_until_quiet``): a chain of hops crosses inside ONE
engine cycle, a spinning neighbour cannot stop dispatches (the bound), a loop
without a readable ready queue gets the single yield, and the count rides the
cycle's flight record. Cycles and iterations are counted; nothing is timed."""

import asyncio
import json
import statistics
import time
import urllib.request

import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import core as core_mod
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import (FINISH_SENTINEL, YIELD_DRAIN_MAX_ITERS,
                                    EngineCore, EngineRequest)
from dynamo_tpu.engine.flight_recorder import PHASES, FlightRecorder
from dynamo_tpu.engine.sampling import SlotSampling
from dynamo_tpu.llm.protocols.common import FinishReason

TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)
BOUND = YIELD_DRAIN_MAX_ITERS


class _Decoding:
    """What the loop asks of a slot before it steps: a live sequence."""
    ready = True
    cancelled = False
    blocks: list = []


class _NoReadyQueue:
    """An event loop that keeps no ``_ready`` (uvloop, a future CPython)."""


def make_core(**kw) -> EngineCore:
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8, num_kv_blocks=64,
                        max_num_seqs=2, prefill_buckets=[32, 64], **kw)
    return EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)


class StubStepped:
    """A real ``EngineCore`` loop over one live slot whose decode step is a
    stub: it counts the cycle, closes it as the real step does, and runs
    what the test hangs on it."""

    def __init__(self, core: EngineCore, block_s: float = 0.0):
        self.core = core
        self.steps = 0
        self.on_step = {}           # step number → callable
        self.reached = {}           # step number → asyncio.Event
        core.slots[0] = _Decoding()
        core._decode_step = self._step
        self._block_s = block_s

    def _step(self):
        if self._block_s:
            time.sleep(self._block_s)   # a device step holds the thread
        self.steps += 1
        self.core.flight.record_cycle("decode", K=1, batch_fill=1)
        if self.steps in self.on_step:
            self.on_step.pop(self.steps)()
        if self.steps in self.reached:
            self.reached[self.steps].set()

    async def until(self, step: int):
        event = self.reached.setdefault(step, asyncio.Event())
        if self.steps < step:
            await asyncio.wait_for(event.wait(), 60)    # a hang, not a pace

    def iters(self) -> list:
        """``yield_iters`` of the cycles closed so far, oldest first."""
        return [r["yield_iters"] for r in self.core.flight.dump()
                if r["kind"] == "decode"]


def single_yield(monkeypatch):
    monkeypatch.setattr(core_mod, "_loop_is_quiet", lambda loop: True)


async def run_chain(engine: StubStepped, hops: int, start: int = 3) -> int:
    """Start a chain of ``hops`` ``call_soon`` callbacks, each scheduling
    the next, from inside step ``start``; → the steps the engine had made
    when the last one ran."""
    loop = asyncio.get_running_loop()
    ended = []

    def hop(left):
        if left > 1:
            loop.call_soon(hop, left - 1)
        else:
            ended.append(engine.steps)

    engine.on_step[start] = lambda: loop.call_soon(hop, hops)
    engine.core.ensure_started()
    try:
        await engine.until(start + hops + 1)    # past either way's end
    finally:
        await engine.core.stop()
    (at,) = ended
    return at


@pytest.mark.asyncio
@pytest.mark.parametrize("hops", [1, 4, 7, BOUND])
async def test_a_chain_of_hops_crosses_inside_one_cycle(hops):
    engine = StubStepped(make_core())
    assert await run_chain(engine, hops, start=3) == 3
    # the yield after step 3 ran one iteration a hop, closed into step 4's
    # record; the quiet cycles before it ran one
    assert engine.iters()[1:4] == [1, 1, hops]


@pytest.mark.asyncio
@pytest.mark.parametrize("hops", [1, 4, 7, BOUND])
async def test_the_same_chain_took_a_cycle_a_hop_on_the_single_yield(
        hops, monkeypatch):
    single_yield(monkeypatch)
    engine = StubStepped(make_core())
    assert await run_chain(engine, hops, start=3) == 3 + hops - 1
    assert set(engine.iters()[1:]) == {1}


@pytest.mark.asyncio
async def test_a_chain_past_the_bound_ends_in_the_next_cycle():
    engine = StubStepped(make_core())
    assert await run_chain(engine, BOUND + 3, start=3) == 4
    assert engine.iters()[3:5] == [BOUND, 3]


@pytest.mark.asyncio
async def test_a_spinning_neighbour_does_not_stop_dispatches():
    """A task that yields with ``await asyncio.sleep(0)`` in a loop (the KV
    tiers' pumps, the indexer's drain) keeps the ready queue non-empty for
    ever: the bound ends the drain."""
    engine = StubStepped(make_core())
    spins = 0

    async def spin():
        nonlocal spins
        while True:
            await asyncio.sleep(0)
            spins += 1

    spinner = asyncio.create_task(spin())
    engine.core.ensure_started()
    try:
        await engine.until(6)
    finally:
        spinner.cancel()
        await engine.core.stop()
    assert engine.steps >= 6
    assert set(engine.iters()[1:6]) == {BOUND}
    assert spins >= 5 * BOUND


@pytest.mark.asyncio
async def test_a_real_engine_steps_beside_a_spinning_offload_pump():
    """The same with real steps and the host tier on, whose write-back pump
    is one of the tree's ``sleep(0)`` loops."""
    core = make_core(host_kv_blocks=16)
    assert core.offload_engine is not None

    async def spin():
        while True:
            await asyncio.sleep(0)

    spinner = asyncio.create_task(spin())
    req = EngineRequest(rid="r0", prompt=list(range(3, 23)),
                        sampling=SlotSampling(temperature=0.0, seed=0),
                        max_new_tokens=8, eos_ids=frozenset())
    tokens = 0
    try:
        await core.submit(req)
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 120)
            if item is FINISH_SENTINEL:
                break
            tokens += 1
    finally:
        spinner.cancel()
        await core.stop()
    assert tokens == 8
    cycles = [r["yield_iters"] for r in core.flight.dump()
              if r["kind"] == "decode"]
    # every loop round beside the spinner ran the bound, and no more
    assert len(cycles) >= 6 and BOUND in cycles
    assert all(c >= BOUND and c % BOUND == 0 for c in cycles)


def test_a_loop_without_a_ready_queue_reads_as_quiet():
    assert core_mod._loop_is_quiet(_NoReadyQueue()) is True
    loop = asyncio.new_event_loop()
    try:
        assert core_mod._loop_is_quiet(loop) is True
        loop.call_soon(lambda: None)
        assert core_mod._loop_is_quiet(loop) is False
    finally:
        loop.close()


@pytest.mark.asyncio
async def test_an_engine_on_such_a_loop_yields_once_a_cycle(monkeypatch):
    helper = core_mod._loop_is_quiet
    monkeypatch.setattr(core_mod, "_loop_is_quiet",
                        lambda loop: helper(_NoReadyQueue()))
    engine = StubStepped(make_core())
    assert await run_chain(engine, 5, start=2) == 2 + 5 - 1
    assert set(engine.iters()[1:]) == {1}


def test_close_cycle_hands_out_the_yield_iterations_and_resets_them():
    fr = FlightRecorder(capacity=4)
    clock = fr.clock
    clock.close_cycle()
    clock.enter("post")
    time.sleep(0.001)
    clock.enter("yield")
    for _ in range(3):
        time.sleep(0.001)
        clock.yield_iters += 1
    clock.enter("sweep")
    split = clock.close_cycle()
    assert split["yield_iters"] == 3 and clock.yield_iters == 0
    # the drain is one phase: the phases still tile the cycle
    assert split["yield_ms"] >= 3.0
    assert abs(sum(split[f"{p}_ms"] for p in PHASES)
               - split["cycle_ms"]) < 0.01
    clock.yield_iters += 2
    for kind in ("decode", "ragged", "verify"):
        fr.record_cycle(kind, K=1, batch_fill=1)
    assert [r["yield_iters"] for r in fr.dump()] == [2, 0, 0]


@pytest.mark.asyncio
async def test_a_new_connection_reaches_submit_in_under_two_steps(
        tiny_model_dir):
    """Over a real socket: accept, transport, reader, first read, handler,
    body and ``submit()`` cross inside the yield whose poll found the
    connection, so a request sent during a step is enqueued when that step's
    yield ends (six steps on the single yield). The median of several, so a
    client thread that a loaded machine held back decides nothing."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.engines.jax_engine import JaxEngine
    from dynamo_tpu.llm.http import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.runtime import link
    mdc = ModelDeploymentCard.from_local_path(tiny_model_dir,
                                              display_name="tiny")
    core = EngineCore(ModelConfig.from_model_dir(tiny_model_dir), EngineConfig(
        max_model_len=256, kv_block_size=8, num_kv_blocks=64,
        max_num_seqs=2, prefill_buckets=[32, 64]),
        attn_impl="xla", param_dtype=jnp.float32)
    engine = StubStepped(core, block_s=0.03)
    submitted = []

    async def submit(req):
        submitted.append(engine.steps)
        req.out_queue.put_nowait((FINISH_SENTINEL, FinishReason.STOP))

    core.submit = submit
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model(
        "tiny", link(OpenAIPreprocessor(mdc), Backend(mdc), JaxEngine(core)))
    await svc.start()
    core.ensure_started()
    body = json.dumps({"model": "tiny", "max_tokens": 4, "messages": [
        {"role": "user", "content": "hello world"}]}).encode()
    sent = []

    def client():
        for i in range(7):
            time.sleep(0.004 * (i + 1))     # anywhere in a step
            sent.append(engine.steps)
            post = urllib.request.Request(      # a new connection each
                f"http://127.0.0.1:{svc.port}/v1/chat/completions",
                data=body, headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(post, timeout=60) as r:
                r.read()

    try:
        await asyncio.to_thread(client)
    finally:
        await svc.stop()
        await core.stop()
    took = [b - a for a, b in zip(sent, submitted)]
    assert len(took) == 7
    assert statistics.median(took) <= 2, took
    # a cycle that met a connection ran the chain's iterations (seven, where
    # the request's bytes were there with it), within the bound
    assert 1 < max(engine.iters()) <= BOUND

