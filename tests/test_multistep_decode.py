"""Multi-step decode (decode_steps_per_dispatch > 1): K fused steps must
produce exactly the single-step engine's token streams, including EOS and
max_tokens finishes landing mid-dispatch (device overrun discarded). The
single-step engine keeps one step in flight (tests/test_decode_overlap.py);
the reference here is the same engine made to harvest every step before
it builds the next, which an attached replay recorder does."""

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


def make_core(k: int, pipeline: bool = False,
              drained: bool = False) -> EngineCore:
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8, num_kv_blocks=64,
                        max_num_seqs=4, prefill_buckets=[16, 32, 64],
                        decode_steps_per_dispatch=k,
                        decode_dispatch_pipeline=pipeline)
    core = EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)
    if drained:
        from dynamo_tpu.engine.replay import Recorder
        core.recorder = Recorder()
    return core


def decode_records(core) -> list:
    return [r for r in core.flight.dump() if r["kind"] == "decode"]


async def run_req_collect(core, prompt, **kw):
    req = EngineRequest(rid="r", prompt=list(prompt),
                        sampling=SlotSampling(
                            temperature=kw.get("temperature", 0.0),
                            seed=kw.get("seed", 0)),
                        max_new_tokens=kw.get("max_new", 13),
                        eos_ids=frozenset(kw.get("eos", ())))
    await core.submit(req)
    toks = []
    while True:
        item, payload = await asyncio.wait_for(req.out_queue.get(), 30)
        if item is FINISH_SENTINEL:
            return toks, payload
        toks.append(item)


@pytest.mark.parametrize("k,pipeline", [(1, False), (1, True), (4, False),
                                        (5, False), (4, True)])
async def test_multistep_matches_single_step_greedy(k, pipeline):
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, TINY.vocab_size, size=21).tolist()
    core1 = make_core(1, drained=True)
    try:
        ref, reason1 = await run_req_collect(core1, prompt, max_new=13)
    finally:
        await core1.stop()
    # the reference never had a step in flight behind another
    assert not core1.pipeline_drains
    assert not any(r["chained"] for r in decode_records(core1))
    corek = make_core(k, pipeline=pipeline)
    try:
        got, reasonk = await run_req_collect(corek, prompt, max_new=13)
    finally:
        await corek.stop()
    assert got == ref                      # identical greedy stream
    assert reason1 == reasonk
    assert len(got) == 13                  # max_tokens lands mid-dispatch
    if k == 1:
        # and this one did, whatever the flag says: every dispatch but
        # the first fed from the device
        chained = [r["chained"] for r in decode_records(corek)]
        assert chained[0] == 0 and all(chained[1:]), chained


async def test_multistep_eos_mid_dispatch_discards_overrun():
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, TINY.vocab_size, size=9).tolist()
    core1 = make_core(1)
    try:
        ref, _ = await run_req_collect(core1, prompt, max_new=40)
    finally:
        await core1.stop()
    # pick the 3rd generated token as "EOS" so it lands mid-K-dispatch
    eos_tok = ref[2]
    cut = ref[:ref.index(eos_tok) + 1]

    core4 = make_core(4)
    try:
        got, reason = await run_req_collect(core4, prompt, max_new=40,
                                            eos=(eos_tok,))
        from dynamo_tpu.llm.protocols.common import FinishReason
        assert reason == FinishReason.EOS
        assert got == cut                  # nothing after EOS leaks out
    finally:
        await core4.stop()


async def test_multistep_two_concurrent_sequences(anyio_backend):
    """Two slots with different lengths finish independently inside the
    fused dispatches."""
    rng = np.random.default_rng(7)
    p1 = rng.integers(1, TINY.vocab_size, size=12).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=17).tolist()
    core1 = make_core(1)
    try:
        r1 = await run_req_collect(core1, p1, max_new=6)
        r2 = await run_req_collect(core1, p2, max_new=11)
    finally:
        await core1.stop()
    core3 = make_core(3)
    try:
        g1, g2 = await asyncio.gather(
            run_req_collect(core3, p1, max_new=6),
            run_req_collect(core3, p2, max_new=11))
    finally:
        await core3.stop()
    assert g1[0] == r1[0] and g2[0] == r2[0]


async def test_pipelined_two_sequences_and_staggered_admission():
    """Pipelined dispatch with slot churn: a second request admitted while
    a batch is in flight must chain correctly from its prefill token."""
    rng = np.random.default_rng(41)
    p1 = rng.integers(1, TINY.vocab_size, size=12).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=18).tolist()
    ref_core = make_core(1)
    try:
        r1, _ = await run_req_collect(ref_core, p1, max_new=17)
        r2, _ = await run_req_collect(ref_core, p2, max_new=9)
    finally:
        await ref_core.stop()

    core = make_core(4, pipeline=True)
    try:
        async def delayed(prompt, max_new, delay):
            await asyncio.sleep(delay)
            return await run_req_collect(core, prompt, max_new=max_new)

        (g1, _), (g2, _) = await asyncio.gather(
            run_req_collect(core, p1, max_new=17),
            delayed(p2, 9, 0.15))
        assert g1 == r1
        assert g2 == r2
    finally:
        await core.stop()
