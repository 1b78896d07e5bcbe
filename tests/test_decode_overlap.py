"""One decode step in flight at one step per dispatch (the default path):
step n+1 is launched off step n's on-device tokens before the loop fetches
them. Chaining is per slot, finishes that are known ahead are not chained,
the rest discard one slot-row, and the streams are those of an engine that
harvests every step before it builds the next (which an attached replay
recorder makes it do)."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from dynamo_tpu.engine.sampling import SlotSampling
from dynamo_tpu.llm.protocols.common import FinishReason

pytestmark = pytest.mark.asyncio

TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)


def make_core(drained: bool = False, **over) -> EngineCore:
    # no idle defrag here: a pass that finds a move harvests the step in
    # flight first (tests/test_kv_contig.py), a drain of its own
    ecfg = EngineConfig(**{
        "max_model_len": 256, "kv_block_size": 8, "num_kv_blocks": 64,
        "max_num_seqs": 4, "prefill_buckets": [16, 32, 64],
        "kv_defrag_threshold": 0.0, **over})
    core = EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)
    if drained:
        from dynamo_tpu.engine.replay import Recorder
        core.recorder = Recorder()
    return core


class Stream:
    """One request and what it has emitted so far."""

    def __init__(self, core, prompt, max_new, rid="r", temperature=0.0,
                 seed=0, eos=(), ctx=None):
        self.core = core
        self.req = EngineRequest(
            rid=rid, prompt=list(prompt),
            sampling=SlotSampling(temperature=temperature, seed=seed),
            max_new_tokens=max_new, eos_ids=frozenset(eos), ctx=ctx)
        self.toks = []
        self.reason = None
        self.progress = asyncio.Event()

    async def run(self, after: "Stream" = None, n: int = 0):
        """Submits (once ``after`` has emitted ``n`` tokens) and collects
        to the finish."""
        if after is not None:
            await after.emitted(n)
        await self.core.submit(self.req)
        while True:
            item, payload = await asyncio.wait_for(
                self.req.out_queue.get(), 60)
            if item is FINISH_SENTINEL:
                self.reason = payload
                self.progress.set()
                return self.toks
            self.toks.append(item)
            self.progress.set()

    async def emitted(self, n: int) -> None:
        while len(self.toks) < n and self.reason is None:
            self.progress.clear()
            await asyncio.wait_for(self.progress.wait(), 60)


def decode_records(core) -> list:
    return [r for r in core.flight.dump() if r["kind"] == "decode"]


def prompts(seed: int, *sizes) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, TINY.vocab_size, size=n).tolist() for n in sizes]


async def test_staggered_admission_joins_without_draining_the_pipeline():
    """A request admitted while a step is in flight is fed its host-known
    first token in the next dispatch; the slot already decoding keeps
    chaining from the device. No whole-pipeline drain."""
    p1, p2 = prompts(41, 12, 18)
    ref = make_core(drained=True)
    try:
        r1 = await Stream(ref, p1, 30).run()
        r2 = await Stream(ref, p2, 9).run()
    finally:
        await ref.stop()
    core = make_core()
    try:
        a, b = Stream(core, p1, 30, "a"), Stream(core, p2, 9, "b")
        g1, g2 = await asyncio.gather(a.run(), b.run(after=a, n=4))
        drains_while_serving = dict(core.pipeline_drains)
    finally:
        await core.stop()
    assert g1 == r1 and g2 == r2
    records = decode_records(core)
    joins = [r for r in records
             if r["batch_fill"] == 2 and r["chained"] == 1]
    assert len(joins) == 1, [(r["batch_fill"], r["chained"])
                             for r in records]
    assert all(r["chained"] <= r["batch_fill"] for r in records)
    # b ends by max_tokens, known a step ahead: it sits its last dispatch
    # out while a goes on chaining. The only harvest with nothing queued
    # behind it is the one of a's own last token.
    assert drains_while_serving == {"last_token": 1}
    assert [r.get("drain") for r in records].count(None) == len(records) - 1
    assert records[-1]["drain"] == "last_token"
    after_join = records[records.index(joins[0]) + 1:]
    assert all(r["chained"] == r["batch_fill"] for r in after_join)


async def test_finish_by_max_tokens_is_not_chained():
    """The token that exhausts the budget is known to be the last while
    it is in flight: no step is launched behind it, nothing is wasted."""
    (prompt,) = prompts(5, 9)
    core = make_core()
    try:
        s = Stream(core, prompt, 11)
        toks = await s.run()
    finally:
        await core.stop()
    assert s.reason == FinishReason.LENGTH and len(toks) == 11
    # the first token is the prefill's; ten decode dispatches, not eleven
    assert core._step == 10
    records = decode_records(core)
    assert [r["batch_fill"] for r in records] == [1] * 10
    assert core.pipeline_drains == {"last_token": 1}


async def test_finish_by_eos_discards_exactly_one_overrun_row():
    """EOS cannot be known ahead: the step launched behind it has run for
    the slot, and its row is dropped at its harvest."""
    (prompt,) = prompts(5, 9)
    ref = make_core(drained=True)
    try:
        full = await Stream(ref, prompt, 40).run()
    finally:
        await ref.stop()
    eos_tok = full[2]
    cut = full[:full.index(eos_tok) + 1]
    core = make_core()
    try:
        s = Stream(core, prompt, 40, eos=(eos_tok,))
        toks = await s.run()
        # the loop harvests the orphaned step on its next turn
        for _ in range(200):
            if core._pending is None:
                break
            await asyncio.sleep(0.01)
        drains = dict(core.pipeline_drains)
    finally:
        await core.stop()
    assert s.reason == FinishReason.EOS
    assert toks == cut                      # nothing after EOS leaks out
    # len(cut) - 1 decode steps produced the stream, one more overran
    assert core._step == len(cut)
    records = decode_records(core)
    assert [r["batch_fill"] for r in records] == [1] * (len(cut) - 1) + [0]
    assert records[-1]["drain"] == "idle" and drains == {"idle": 1}
    assert core.kv_manager.pool.used_blocks == 0


async def test_context_capacity_is_not_chained_and_fills_the_context():
    """The step that writes the last position of the context is known to
    be the last: the stream ends with the context full, by LENGTH."""
    (prompt,) = prompts(3, 21)
    core = make_core(max_model_len=64)       # 8 blocks of 8
    try:
        s = Stream(core, prompt, 200)
        toks = await s.run()
    finally:
        await core.stop()
    assert s.reason == FinishReason.LENGTH
    assert len(toks) == 64 - 21 + 1
    assert core._step == 64 - 21
    assert core.pipeline_drains == {"last_token": 1}


class Ctx:
    """A request context that stops when told to."""

    def __init__(self):
        self.is_stopped = False
        self.deadline_exceeded = False


async def test_cancel_mid_flight_vacates_the_slot_and_spares_the_other():
    p1, p2 = prompts(7, 12, 17)
    ref = make_core(drained=True)
    try:
        r2 = await Stream(ref, p2, 24).run()
    finally:
        await ref.stop()
    core = make_core()
    ctx = Ctx()
    try:
        a = Stream(core, p1, 200, "a", ctx=ctx)
        b = Stream(core, p2, 24, "b")

        async def cancel_a():
            await a.emitted(5)
            ctx.is_stopped = True
            return len(a.toks)

        _, g2, seen = await asyncio.gather(a.run(), b.run(), cancel_a())
        assert a.reason == FinishReason.CANCELLED
        # a cancel that arrives with a step in flight is seen at that
        # step's harvest: at most the token in flight and the one chained
        # behind it were still to come when the flag was set
        assert seen <= len(a.toks) <= seen + 2
        assert g2 == r2                          # the survivor is exact
        assert core.requests_cancelled_total == 1
        assert core.kv_manager.pool.used_blocks == 0
        assert all(s is None for s in core.slots)
    finally:
        await core.stop()


async def test_seeded_sampling_is_bit_equal_to_a_drained_run():
    """Keys are a function of (engine seed, slot seed, key_step), and a
    chained slot runs at key_step + 1: three staggered seeded streams
    are the drained engine's, token for token."""
    ps = prompts(31, 12, 18, 9)
    specs = [(17, 0.8, 5), (9, 0.9, 7), (25, 1.1, 11)]

    async def serve(core):
        streams = [Stream(core, p, n, f"r{i}", temperature=t, seed=sd)
                   for i, (p, (n, t, sd)) in enumerate(zip(ps, specs))]
        try:
            return await asyncio.gather(
                streams[0].run(), streams[1].run(after=streams[0], n=3),
                streams[2].run(after=streams[0], n=6))
        finally:
            await core.stop()

    ref = await serve(make_core(drained=True))
    core = make_core()
    got = await serve(core)
    assert got == ref
    assert [len(g) for g in got] == [17, 9, 25]
    assert any(r["chained"] for r in decode_records(core))
    assert "slot_churn" not in core.pipeline_drains
    assert "kv_growth" not in core.pipeline_drains
