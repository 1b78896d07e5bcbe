"""Recompute preemption: KV exhaustion under contention requeues a sequence
(prompt + emitted tokens) instead of truncating it; streams stay exact."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from dynamo_tpu.engine.sampling import SlotSampling

pytestmark = pytest.mark.asyncio

TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)


def make_core(num_kv_blocks: int, k: int = 1,
              pipeline: bool = False) -> EngineCore:
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8,
                        num_kv_blocks=num_kv_blocks, max_num_seqs=2,
                        prefill_buckets=[32, 64, 128],
                        decode_steps_per_dispatch=k,
                        decode_dispatch_pipeline=pipeline)
    return EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)


async def run_req(core, prompt, max_new, rid="r"):
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=max_new, eos_ids=frozenset())
    await core.submit(req)
    toks = []
    while True:
        item, payload = await asyncio.wait_for(req.out_queue.get(), 60)
        if item is FINISH_SENTINEL:
            return toks, payload, req
        toks.append(item)


def assert_exact_to_recompute_boundary(got, ref, req, name):
    """The preemption exactness CONTRACT: a stream matches the uncontended
    reference bit-exactly up to its first recompute boundary. At a
    preemption, the next token is re-derived by the prefill program whose
    f32 numerics differ slightly from the decode program's (different
    matmul shapes), so a greedy argmax at near-tie logits may legitimately
    flip there — root-caused from a recorded schedule via
    tools/race_stress.py + engine/replay.py (divergent seed reproduced
    deterministically; prefill argmax != decode argmax with an 8e-4 logit
    gap). A divergence BEFORE the first boundary would be a real bug."""
    if got == ref:
        return
    boundary = min(req.numeric_boundaries) if req.numeric_boundaries else len(ref)
    first_diff = next(i for i, (a, b) in enumerate(zip(got, ref)) if a != b)
    assert first_diff >= boundary, (
        f"stream {name} diverged at {first_diff}, BEFORE its first "
        f"recompute boundary {boundary} — not explainable by prefill/"
        f"decode numerics; numeric_boundaries={req.numeric_boundaries}")


@pytest.mark.parametrize("k,pipeline,record", [
    (1, False, False), (1, False, True), (4, False, True), (4, True, True),
], ids=["k1-overlapped", "k1-drained-replayed", "k4", "k4-pipelined"])
async def test_preemption_exact_streams_under_contention(k, pipeline,
                                                         record):
    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    max_new = 40

    # uncontended references (big pool)
    big = make_core(num_kv_blocks=64, k=k, pipeline=pipeline)
    try:
        ref1, _, _ = await run_req(big, p1, max_new)
        ref2, _, _ = await run_req(big, p2, max_new)
    finally:
        await big.stop()
    assert len(ref1) == max_new

    # pool big enough for either sequence alone (~9 blocks each + slack)
    # but not both at full length → forced preemption traffic
    small = make_core(num_kv_blocks=16, k=k, pipeline=pipeline)
    if record:
        # record the schedule so post-boundary tokens are verified too
        # (at one step per dispatch an attached recorder makes the loop
        # harvest every step before it builds the next)
        from dynamo_tpu.engine.replay import Recorder
        small.recorder = Recorder()
    try:
        (g1, r1, q1), (g2, r2, q2) = await asyncio.gather(
            run_req(small, p1, max_new, rid="a"),
            run_req(small, p2, max_new, rid="b"))
        from dynamo_tpu.llm.protocols.common import FinishReason
        # structural invariants hold strictly in every mode
        assert r1 == FinishReason.LENGTH and r2 == FinishReason.LENGTH
        assert len(g1) == max_new and len(g2) == max_new
        assert small.preemptions > 0, "contention never triggered preemption"
        assert_exact_to_recompute_boundary(g1, ref1, q1, "a")
        assert_exact_to_recompute_boundary(g2, ref2, q2, "b")
        decodes = [r for r in small.flight.dump() if r["kind"] == "decode"]
        if k == 1 and not record:
            # the overlapped path: steps were chained on the device, and
            # the growth that failed with a token in flight drained the
            # pipeline before anyone was preempted
            assert any(r["chained"] for r in decodes)
            assert small.pipeline_drains.get("kv_growth", 0) > 0
            assert any(r.get("drain") == "kv_growth" for r in decodes)
        elif k == 1:
            assert not small.pipeline_drains
            assert not any(r["chained"] for r in decodes)
        if record:
            # tokens AFTER a recompute boundary aren't waived: a
            # synchronous replay of the recorded schedule (same prefill
            # programs, fresh KV) must reproduce every harvested token —
            # post-preemption corruption would diverge here (advisor
            # round-1 finding: the boundary assert alone left the tail
            # unchecked)
            from dynamo_tpu.engine.replay import compare_replay, replay
            rep = replay(small, small.recorder.events)
            assert compare_replay(small.recorder.events, rep) == []
    finally:
        await small.stop()


async def test_seeded_sampling_reproducible_across_preemption():
    """temperature>0 with a seed: the PRNG step counter survives
    preemption, so a preempted stream matches the uncontended one."""
    rng = np.random.default_rng(31)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    max_new = 40

    async def run_seeded(core, prompt, rid):
        req = EngineRequest(rid=rid, prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.8, seed=77),
                            max_new_tokens=max_new, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
            if item is FINISH_SENTINEL:
                return toks, req
            toks.append(item)

    big = make_core(num_kv_blocks=64)
    try:
        ref, _ = await run_seeded(big, p1, "ref")
    finally:
        await big.stop()

    small = make_core(num_kv_blocks=16)
    try:
        (g1, q1), _g2 = await asyncio.gather(run_seeded(small, p1, "a"),
                                             run_seeded(small, p2, "b"))
        assert small.preemptions > 0
        # PRNG-step continuity is the claim; the recompute-boundary numeric
        # caveat applies here just as in the greedy test
        assert_exact_to_recompute_boundary(g1, ref, q1, "seeded-a")
    finally:
        await small.stop()


async def test_solo_request_on_tiny_pool_finishes_length():
    """With no contention, exhaustion finishes (recompute can't help)."""
    rng = np.random.default_rng(29)
    prompt = rng.integers(1, TINY.vocab_size, size=30).tolist()
    core = make_core(num_kv_blocks=8)     # 7 usable blocks = 56 tokens
    try:
        toks, reason, _req = await run_req(core, prompt, max_new=100)
        from dynamo_tpu.llm.protocols.common import FinishReason
        assert reason == FinishReason.LENGTH
        assert 0 < len(toks) < 100
        assert core.preemptions == 0
    finally:
        await core.stop()
