"""A resident drafter's two-row step, chained (docs/speculative.md "A
resident drafter"): the program decides acceptance and rewind itself and the
next step is queued behind the one that runs, fed from its on-device carry.

One ``--spec-k 0`` engine and one ``--spec-k 1`` engine of
``tests/test_exaone_moe.py``'s tiny configuration, the window widened to 32
so that the positions a chained step may run ahead cost the ring a block of
its own (R 3 -> 4: at a window of 21 the union fits the three). Drafts are
forced at the program's draft output (``Forced``), so that ``adv = 2`` is
held here: with seeded weights the chip accepts nothing. A file of its own:
a worker of its own under ``--dist loadfile``."""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                    EngineRequest)
from dynamo_tpu.engine.models import llama, mla
from dynamo_tpu.engine.sampling import SlotSampling
from dynamo_tpu.llm.protocols.common import FinishReason

from test_exaone_moe import ACCEPT, CFG, Forced, _prompts, hold_the_window

CFG32 = dataclasses.replace(CFG, swa_window=32)
SAMPLINGS = {"greedy": SlotSampling(temperature=0.0),
             "sampled": SlotSampling(temperature=0.8, top_k=20, seed=3)}
N = 40


class _Ctx:
    is_stopped = False


def _engine(spec_k):
    return EngineCore(CFG32, EngineConfig(
        max_model_len=256, num_kv_blocks=64, max_num_seqs=4,
        kv_block_size=16, seed=5, spec_k=spec_k), param_dtype=jnp.float32)


async def _serve(core, prompts, sampling, n=N, eos=(), cancel_at=None,
                 late=()):
    """→ [(ids, logprobs, finish reason)] a prompt. ``n``: a budget, or one
    a request; ``cancel_at``: {request index: tokens after which its client
    stops}; ``late``: requests submitted once request 0 has streamed eight
    tokens (an admission beside slots that are chained)."""
    budgets = n if isinstance(n, (list, tuple)) else [n] * len(prompts)
    reqs = [EngineRequest(rid=f"r{i}", prompt=list(p), sampling=sampling,
                          max_new_tokens=b, eos_ids=frozenset(eos),
                          ctx=_Ctx())
            for i, (p, b) in enumerate(zip(prompts, budgets))]
    outs = [[[], [], None] for _ in reqs]

    async def read(i):
        req, out = reqs[i], outs[i]
        while True:
            tok, lp = await req.out_queue.get()
            if tok is FINISH_SENTINEL:
                out[2] = lp
                return
            out[0].append(tok)
            out[1].append(lp)
            if cancel_at and len(out[0]) == cancel_at.get(i):
                req.ctx.is_stopped = True
            if i == 0 and len(out[0]) == 8:
                for j in late:
                    await core.submit(reqs[j])

    try:
        for i, r in enumerate(reqs):
            if i not in late:
                await core.submit(r)
        await asyncio.gather(*(read(i) for i in range(len(reqs))))
    finally:
        await core.stop()
    return outs


@pytest.fixture(scope="module")
def engines():
    """(prompts, {sampling: the one-row engine's streams}, the chained
    engine, its ``Forced``): prompts that cross the window's edge at 32, a
    ring wrap at 64 rows and block boundaries while decoding."""
    prompts = _prompts((19, 40, 33))
    base = _engine(0)
    want = {name: asyncio.run(_serve(base, prompts, samp))
            for name, samp in SAMPLINGS.items()}
    with pytest.MonkeyPatch.context() as patch:
        forced = Forced(patch)
        yield prompts, want, _engine(1), forced


def _decodes(core, since=0):
    return [r for r in core.flight.dump() if r["kind"] == "decode"][since:]


def _use(engines, drafts, sampling="greedy"):
    prompts, want, core, forced = engines
    forced.use(prompts, [ids for ids, _, _ in want[sampling]], ACCEPT[drafts])
    return prompts, want[sampling], core, len(_decodes(core))


def test_the_ring_has_room_for_the_rows_ahead(engines):
    """The bound's two homes agree, widen by a block here, and leave every
    layout without a resident drafter as it was."""
    core = engines[2]
    layout = llama.cache_layout(CFG32, 16)
    plain = dataclasses.replace(CFG32, mtp_layers=0)
    assert (layout.rows_ahead, layout.ring_blocks, core.R) == (1, 4, 4)
    assert mla.swa_ring_blocks(CFG32, 16) == 4
    assert mla.swa_ring_blocks(plain, 16) == 3 == llama.cache_layout(
        plain, 16).ring_blocks
    for window, bs in ((128, 16), (21, 16), (21, 4), (512, 16)):
        cfg = dataclasses.replace(plain, swa_window=window)
        assert mla.swa_ring_blocks(cfg, bs) == -(-window // bs) + 1
        cfg = dataclasses.replace(CFG32, swa_window=window)
        assert mla.swa_ring_blocks(cfg, bs) == llama.cache_layout(
            cfg, bs).ring_blocks == -(-(window + 1) // bs) + 1


@pytest.mark.parametrize("sampling", list(SAMPLINGS))
@pytest.mark.parametrize("drafts", list(ACCEPT))
def test_chained_streams_are_the_one_row_streams(engines, drafts, sampling):
    """Token for token and logprob for logprob, across block boundaries, the
    window's edge and a ring wrap, with every block of the union a chained
    step may touch held in a ring that no two of them share an entry of."""
    prompts, want, core, since = _use(engines, drafts, sampling)
    state = hold_the_window(core)
    before = (core.spec_accepted_tokens, core.spec_emitted_tokens)
    got = asyncio.run(_serve(core, prompts, SAMPLINGS[sampling]))
    assert [ids for ids, _, _ in got] == [ids for ids, _, _ in want]
    for (_, a, _), (_, b, _) in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    # the union needs the ring's fourth block somewhere on the way
    assert state["ahead"] > 0.8 * state["held"] and state["most"] == core.R
    accepted = core.spec_accepted_tokens - before[0]
    emitted = core.spec_emitted_tokens - before[1]
    if drafts == "accepted":
        assert accepted >= 0.9 * emitted / 2
    elif drafts == "rejected":
        assert accepted == 0
    else:
        assert 0.25 * emitted < accepted < 0.45 * emitted
    assert core.spec_rewound_rows == (core.spec_drafted_tokens
                                      - core.spec_accepted_tokens)
    rec = _decodes(core, since)
    assert all(r["rows"] == 2 * r["batch_fill"] and r["K"] == 1
               and r["emitted"] == r["batch_fill"] + r["accepted"]
               and r["win_blocks_live"] <= core.R for r in rec)
    # the first step is fed from the host, the rest from the device
    assert rec[0]["chained"] == 0 and "drain" not in rec[0]
    fed = sum(r["chained"] for r in rec) / sum(r["batch_fill"] for r in rec)
    assert fed > 0.85, fed
    # ... until the last slot's last token (a defrag pass harvests first)
    drains = [r["drain"] for r in rec if "drain" in r]
    assert 1 <= len(drains) <= 2 and set(drains) <= {"last_token", "defrag"}
    assert core.preemptions == 0


@pytest.mark.parametrize("budget, row", [(12, "first"), (13, "second")])
def test_a_token_budget_ends_on_either_row_of_a_pair(engines, budget, row):
    """Every draft accepted: after the prefill's token a step emits two, so
    an even budget runs out on a pair's first row (the slot sat the next
    step out: its step in flight was certainly its last) and an odd one on
    its second (it rode, and its rows were dropped)."""
    prompts, want, core, since = _use(engines, "accepted")
    got = asyncio.run(_serve(core, prompts, SAMPLINGS["greedy"],
                             n=[budget, N, N]))
    assert [ids for ids, _, _ in got] == [
        ids[:b] for (ids, _, _), b in zip(want, (budget, N, N))]
    assert got[0][2] == FinishReason.LENGTH
    rec = _decodes(core, since)
    last = [r for r in rec if r["batch_fill"] == 3][-1]
    assert last["emitted"] == (5 if row == "first" else 6)


def test_eos_with_a_step_in_flight_drops_its_rows(engines):
    prompts, want, core, since = _use(engines, "alternating")
    eos = want[1][0][17]
    cut = [ids[:ids.index(eos) + 1] if eos in ids else ids
           for ids, _, _ in want]
    got = asyncio.run(_serve(core, prompts, SAMPLINGS["greedy"], eos=(eos,)))
    assert [ids for ids, _, _ in got] == cut
    assert got[1][2] == FinishReason.EOS and len(cut[1]) <= 18
    # the step queued behind the one that sampled it carried the slot
    rec = _decodes(core, since)
    assert any(a["batch_fill"] > b["batch_fill"] and "drain" not in a
               for a, b in zip(rec, rec[1:]))


def test_a_cancel_with_a_step_in_flight(engines):
    prompts, want, core, _since = _use(engines, "alternating")
    got = asyncio.run(_serve(core, prompts, SAMPLINGS["greedy"],
                             cancel_at={2: 9}))
    assert got[2][2] == FinishReason.CANCELLED
    # what was in flight when the client stopped may still have come
    assert 9 <= len(got[2][0]) <= 13
    for (ids, _, _), (full, _, _) in zip(got, want):
        assert ids == full[:len(ids)]
    assert [len(ids) for ids, _, _ in got[:2]] == [N, N]


def test_an_admission_is_fed_from_the_host_beside_chained_slots(engines):
    prompts, want, core, since = _use(engines, "alternating")
    got = asyncio.run(_serve(core, prompts, SAMPLINGS["greedy"], late=(2,)))
    assert [ids for ids, _, _ in got] == [ids for ids, _, _ in want]
    joined = [r for r in _decodes(core, since)
              if r["batch_fill"] == 3 and r["chained"] == 2]
    assert len(joined) == 1 and "drain" not in joined[0]


def test_a_pool_too_small_drains_then_preempts(engines):
    """All but eleven paged blocks taken away: a growth that fails with a
    step in flight drains (``kv_growth``: no preemption over tokens no one
    has fetched), the fresh step preempts, and the streams stay."""
    prompts, want, core, since = _use(engines, "alternating", "sampled")
    pool = core.kv_manager.pool
    hogged = pool.alloc_uninit(pool.free_blocks - 11)
    drains, preemptions = core.pipeline_drains.get("kv_growth", 0), \
        core.preemptions
    try:
        got = asyncio.run(_serve(core, prompts, SAMPLINGS["sampled"]))
    finally:
        pool.release(hogged)
    assert [ids for ids, _, _ in got] == [ids for ids, _, _ in want]
    assert core.preemptions > preemptions
    assert core.pipeline_drains["kv_growth"] > drains
    assert "kv_growth" in [r.get("drain") for r in _decodes(core, since)]
