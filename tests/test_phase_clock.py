"""The engine loop's phase clock (engine/flight_recorder.py PhaseClock):
the phases tile a cycle, every dispatch path's flight record carries the
split, the request's trace tiles TTFT from inside the server, and each
benchmark reader of these fields returns what a small recorded list says."""

import asyncio
import importlib.util
import os
import time

import aiohttp
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from dynamo_tpu.engine.flight_recorder import (PHASES, FlightRecorder,
                                               PhaseClock)
from dynamo_tpu.engine.sampling import SlotSampling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)


# ------------------------------------------------------------------ the clock

def test_phases_tile_the_cycle():
    clock = PhaseClock()
    clock.close_cycle()
    t0 = clock.enter("sweep")
    before = dict(clock.seconds)
    for phase in ("admit", "build", "dispatch", "wait", "post", "complete",
                  "yield", "build"):
        time.sleep(0.001)
        clock.enter(phase)
    t1 = clock.enter("post")
    spent = sum(clock.seconds[p] - before[p] for p in PHASES)
    assert abs(spent - (t1 - t0)) < 1e-6           # to 1 µs
    split = clock.close_cycle()
    assert set(split) == {f"{p}_ms" for p in PHASES} | {
        "cycle_ms", "admits", "admit_tokens", "yield_iters"}
    # ten values rounded to a microsecond each
    assert abs(sum(split[f"{p}_ms"] for p in PHASES)
               - split["cycle_ms"]) < 0.01
    assert all(split[f"{p}_ms"] >= 1.0 for p in PHASES if p != "post")
    # the running phase carries on into the next cycle, which starts empty
    assert clock.running == "post"
    again = clock.close_cycle()
    assert again["cycle_ms"] < 1.0 and again["wait_ms"] == 0.0


def test_nested_phase_suspends_and_resumes_the_outer():
    clock = PhaseClock()
    opened = clock.enter("admit")
    before = dict(clock.seconds)
    time.sleep(0.002)
    with clock.phase("wait"):
        assert clock.running == "wait"
        time.sleep(0.003)
    assert clock.running == "admit"
    time.sleep(0.002)
    closed = clock.enter("build")
    wait = clock.seconds["wait"] - before["wait"]
    admit = clock.seconds["admit"] - before["admit"]
    assert wait >= 0.003 and admit >= 0.004
    # the wait is not in it: the two tile the span between the timestamps,
    # by however much a loaded machine's sleeps overshoot
    assert admit == pytest.approx(closed - opened - wait, abs=1e-6)


def test_exception_inside_a_phase_still_closes_it():
    clock = PhaseClock()
    clock.enter("complete")
    with pytest.raises(RuntimeError):
        with clock.phase("wait"):
            raise RuntimeError("fetch failed")
    assert clock.running == "complete"
    with pytest.raises(KeyError):
        clock.enter("no-such-phase")


def test_record_cycle_carries_the_split_and_counts_admits():
    fr = FlightRecorder(capacity=4)
    fr.clock.enter("admit")
    fr.clock.admits += 2
    with fr.clock.phase("wait"):
        time.sleep(0.002)
    fr.clock.enter("post")
    fr.record_cycle("decode", K=1, batch_fill=3)
    rec = fr.dump()[-1]
    assert rec["kind"] == "decode" and rec["batch_fill"] == 3
    assert rec["admits"] == 2 and rec["device_ms"] == rec["wait_ms"] >= 2.0
    assert "cycle_ms" not in rec
    # the split, the counts, the two sums and the build log's three totals
    assert set(rec) == {"kind", "t", "K", "batch_fill", "device_ms",
                        "host_gap_ms", "admits", "admit_tokens",
                        "yield_iters", "built", "built_ms",
                        "built_trace_ms"} | {f"{p}_ms" for p in PHASES}
    fr.record_cycle("decode", K=1, batch_fill=3)
    assert fr.dump()[-1]["admits"] == 0
    assert fr.dump()[-1]["built"] >= rec["built"]


def test_close_cycle_returns_and_resets_the_admitted_tokens():
    """``admit_tokens`` beside ``admits``: the prompt tokens the open
    cycle's prefill dispatches took, closed into the cycle's record."""
    fr = FlightRecorder(capacity=4)
    fr.clock.admits += 2
    fr.clock.admit_tokens += 700 + 41
    split = fr.clock.close_cycle()
    assert (split["admits"], split["admit_tokens"]) == (2, 741)
    assert (fr.clock.admits, fr.clock.admit_tokens) == (0, 0)
    fr.clock.admits += 1
    fr.clock.admit_tokens += 5
    fr.record_cycle("ragged", K=1, batch_fill=1)
    fr.record_cycle("verify", K=1, batch_fill=1)
    ragged, verify = fr.dump()[-2:]
    assert (ragged["admits"], ragged["admit_tokens"]) == (1, 5)
    assert (verify["admits"], verify["admit_tokens"]) == (0, 0)


# ------------------------------------------------- every dispatch path's record

def make_core(**kw) -> EngineCore:
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8, num_kv_blocks=64,
                        max_num_seqs=2, prefill_buckets=[32, 64, 128], **kw)
    return EngineCore(TINY, ecfg, attn_impl="xla", param_dtype=jnp.float32)


async def serve(core, prompts, max_new=10):
    async def one(i, prompt):
        req = EngineRequest(rid=f"r{i}", prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.0, seed=i),
                            max_new_tokens=max_new, eos_ids=frozenset())
        await core.submit(req)
        n = 0
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
            if item is FINISH_SENTINEL:
                return n
            n += 1
    return await asyncio.gather(*[one(i, p) for i, p in enumerate(prompts)])


@pytest.mark.asyncio
@pytest.mark.parametrize("kind,kw", [
    ("decode", {}),                  # K=1: deferred _harvest, always
    ("decode", {"decode_steps_per_dispatch": 4}),      # _harvest
    ("decode", {"decode_steps_per_dispatch": 4,
                "decode_dispatch_pipeline": True}),    # deferred _harvest
    ("ragged", {"ragged_dispatch": True}),             # _harvest_ragged
    ("verify", {"spec_k": 3}),                         # _harvest_verify
], ids=["k1", "k4", "k4-pipelined", "ragged", "verify"])
async def test_flight_records_split_the_cycle_on_every_path(kind, kw):
    rng = np.random.default_rng(11)
    # a repeating prompt, so that the n-gram drafter has something to offer
    period = rng.integers(1, TINY.vocab_size, size=6).tolist()
    prompts = [period * 5, rng.integers(1, TINY.vocab_size, size=21).tolist()]
    core = make_core(**kw)
    try:
        counts = await serve(core, prompts, max_new=12)
    finally:
        await core.stop()
    assert counts == [12, 12]
    records = [r for r in core.flight.dump() if r["kind"] == kind]
    assert records, [r["kind"] for r in core.flight.dump()]
    for r in records:
        phases = sum(r[f"{p}_ms"] for p in PHASES)
        # the cycle by its two timestamps against the accumulated phases
        # (ten values, each rounded to a microsecond)
        assert abs(r["device_ms"] + r["host_gap_ms"] - phases) < 0.01, r
        assert r["device_ms"] == r["wait_ms"]
        assert r["admits"] >= 0
    if not kw:
        # one step per dispatch is the overlapped path: steps were fed
        # from the device behind an un-harvested one, and the records
        # above still tile their cycles with device_ms == wait_ms
        assert sum(r["chained"] for r in records) > len(records) // 2
    # the loop really was in these phases, and nothing waits for free
    assert sum(r["wait_ms"] for r in records) > 0
    assert sum(r["dispatch_ms"] for r in records) > 0
    assert sum(r["post_ms"] for r in records) > 0
    assert core.host_stall_s >= 1e-3 * (
        sum(r["wait_ms"] for r in records) - 0.001 * len(records))
    prefills = [r for r in core.flight.dump() if r["kind"] == "prefill"]
    if kind != "ragged":         # ragged admissions ride the batch as lanes
        assert len(prefills) == 2
        assert all(0 < r["dispatch_ms"] <= r["host_ms"]
                   and r["wait_ms"] >= 0 for r in prefills)
        # every prefill record says how many rows ran grouped experts:
        # none on a model without experts
        assert all(r["grouped_rows"] == 0 for r in prefills)
        assert sum(r["admits"] for r in core.flight.dump()
                   if "admits" in r) == 2


# -------------------------------------------------- the request's trace over HTTP

@pytest.mark.asyncio
async def test_http_request_trace_tiles_ttft(tiny_model_dir):
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
        max_num_seqs=4, prefill_buckets=[32, 64, 128, 256]),
        attn_impl="xla", param_dtype=jnp.float32)
    pipe = link(OpenAIPreprocessor(mdc), Backend(mdc), JaxEngine(core))
    svc = HttpService(port=0, host="127.0.0.1")
    svc.manager.add_chat_model("tiny", pipe)
    await svc.start()
    base = f"http://127.0.0.1:{svc.port}"
    body = {"model": "tiny", "stream": True, "max_tokens": 6,
            "temperature": 0.0, "nvext": {"ignore_eos": True},
            "messages": [{"role": "user", "content": "hello world"}]}
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/chat/completions", json=body) as r:
                assert r.status == 200
                rid = r.headers["X-Request-Id"]
                await r.read()
            async with s.get(f"{base}/traces",
                             params={"request_id": rid}) as r:
                traces = (await r.json())["traces"]
    finally:
        await svc.stop()
        await core.stop()
    mine = [t for t in traces if t["request_id"] == rid]
    assert len(mine) == 1
    spans = {s["name"]: s for s in mine[0]["spans"]}
    for name in ("preprocess", "engine.queue_wait", "engine.prefill",
                 "engine.first_token", "stream.first_write"):
        assert name in spans, sorted(spans)
    first = spans["engine.first_token"]
    prefill = spans["engine.prefill"]
    assert first["ms"] > 0
    # it starts inside the admission (when the prefill dispatch returned)
    # and the first chunk is written after the engine emitted the token
    assert (prefill["at_ms"] <= first["at_ms"]
            <= prefill["at_ms"] + prefill["ms"] + 0.02)
    assert spans["stream.first_write"]["at_ms"] >= (first["at_ms"]
                                                    + first["ms"] - 0.02)
    assert [s["name"] for s in mine[0]["spans"]].count(
        "engine.first_token") == 1


# ------------------------------------------------------- the benchmark's readers

def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def decode_record(wait, admits=0, admit=0.0, lost=0.0, **phases):
    split = dict.fromkeys((f"{p}_ms" for p in PHASES), 0.0)
    split.update({"wait_ms": wait, "admit_ms": admit},
                 **{f"{k}_ms": v for k, v in phases.items()})
    cycle = sum(split.values()) + lost
    return {"kind": "decode", "t": 0.0, "K": 1, "batch_fill": 4,
            "device_ms": wait, "host_gap_ms": cycle - wait,
            "admits": admits, **split}


FLIGHT = [
    decode_record(15.0, build=4.0, dispatch=2.0, post=6.0, **{"yield": 9.0}),
    decode_record(40.0, admits=2, admit=30.0, build=5.0, dispatch=3.0,
                  post=8.0, **{"yield": 12.0}),
    decode_record(17.0, build=6.0, dispatch=2.0, post=7.0, lost=2.0,
                  **{"yield": 10.0}),
    {"kind": "prefill", "t": 0.0, "host_ms": 30.0, "queue_wait_ms": 1.0,
     "dispatch_ms": 29.0, "wait_ms": 0.0},
]
# cycles: 36, 98, 44 (2 of it in no phase); phases 36 + 98 + 42
SPANS = [
    {"request_id": "a", "role": "frontend", "start_epoch": 100.0, "spans": [
        {"name": "preprocess", "ms": 1.0, "at_ms": 0.5},
        {"name": "engine.first_token", "ms": 40.0, "at_ms": 30.0},
        {"name": "stream.first_write", "ms": 0.0, "at_ms": 73.0}]},
    # one request whose engine and front end traced in two processes
    {"request_id": "b", "role": "worker", "start_epoch": 200.010, "spans": [
        {"name": "engine.first_token", "ms": 60.0, "at_ms": 20.0}]},
    {"request_id": "b", "role": "frontend", "start_epoch": 200.0, "spans": [
        {"name": "stream.first_write", "ms": 0.0, "at_ms": 95.0}]},
    # an older program's zero-length marker is not this span
    {"request_id": "c", "role": "frontend", "start_epoch": 300.0, "spans": [
        {"name": "engine.first_token", "ms": 0.0, "at_ms": 50.0}]},
]
CTX = {"flight": FLIGHT, "spans": SPANS}
OLD_PROGRAM = {"flight": [{"kind": "decode", "t": 0.0, "K": 1,
                           "batch_fill": 4, "device_ms": 0.0,
                           "host_gap_ms": 40.0}],
               "spans": [SPANS[3]]}


@pytest.mark.parametrize("name,want", [
    ("loop.device_wait_ms", 16.0),              # median of 15, 17
    ("loop.prep_ms", 8.0),                      # median of 6, 8, 8
    ("loop.post_ms", 7.0),
    ("loop.yield_ms", 10.0),
    ("loop.admit_pct", 100.0 * 30.0 / 178.0),
    ("loop.unaccounted_pct", 100.0 * 2.0 / 178.0),
    ("admission.first_token_wait_ms", 50.0),    # median of 40, 60
    ("frontend.emit_to_write_ms", 4.0),         # median of 3, 5
])
def test_benchmark_reader(name, want):
    read = reader(name)
    assert read(CTX) == pytest.approx(want, abs=1e-6)
    assert read({"flight": [], "spans": []}) is None
    # a program from before the clock: nothing to read, and no error
    assert read(OLD_PROGRAM) is None


# ------------------------------------------- the grouped experts' readers (PR 32)

def _prefill(prompt, hit, grouped=None):
    rec = {"kind": "prefill", "prompt": prompt, "hit_device": hit}
    return rec if grouped is None else dict(rec, grouped_rows=grouped)


@pytest.mark.parametrize("flight,want", [
    # every computed row grouped; a prefix hit is not a computed row
    ([_prefill(1100, 0, 1100), _prefill(2000, 512, 1488)], 100.0),
    # a mix of buckets on both sides of the crossover
    ([_prefill(200, 0, 200), _prefill(100, 0, 0), _prefill(100, 0, 0)], 50.0),
    # the dense form everywhere: a number, not nothing
    ([_prefill(64, 0, 0), {"kind": "decode", "batch_fill": 4}], 0.0),
    # a program from before the counter, or no prefill: nothing to read
    ([_prefill(1100, 0)], None),
    ([{"kind": "decode", "batch_fill": 4}], None),
], ids=["all-grouped", "half", "dense-reads-zero", "no-counter",
        "no-prefill"])
def test_grouped_rows_share_is_read_from_the_prefill_records(flight, want):
    got = reader("moe.grouped_rows_pct")({"flight": flight})
    assert got == want and (want is None or isinstance(got, float))


def test_grouped_experts_time_is_read_per_prefill():
    read = reader("kernel.grouped_experts_ms")
    trace = {"ops": [("%grouped_experts.26", 0.2, 96),
                     ("%grouped_experts.27", 0.1, 96),
                     ("%paged_attention.12", 0.4, 100),
                     ("%fusion.9", 1.0, 8)],
             "programs": [("jit_prefill", 2.0, 8), ("jit_decode_k", 1.5, 100)]}
    assert read({"trace": trace}) == pytest.approx(0.3 / 8 * 1e3)
    # the parent's program has no such kernel; a window may hold no prefill
    assert read({"trace": dict(trace, ops=trace["ops"][2:])}) is None
    assert read({"trace": dict(trace, programs=trace["programs"][1:])}) is None
    assert read({"trace": {}}) is None


@pytest.mark.asyncio
async def test_prefill_records_count_the_rows_that_ran_grouped():
    """An expert model served on one device: a 200-token prompt lands in
    the 256-row bucket, whose experts run grouped (the Pallas call, in
    interpret mode here), a 40-token one in the 64-row bucket, dense; the
    records say so with the rows each dispatch computed, and the reader
    turns them into the share."""
    cfg = ModelConfig(
        vocab_size=256, hidden_size=128, intermediate_size=128,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=64,
        max_position_embeddings=512, num_experts=6, num_experts_per_tok=2,
        moe_norm_topk=False, shared_expert_size=128)
    core = EngineCore(cfg, EngineConfig(
        max_model_len=320, kv_block_size=16, num_kv_blocks=48,
        max_num_seqs=2, prefill_buckets=[64, 256]), attn_impl="xla",
        param_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (200, 40)]
    try:
        assert await serve(core, prompts, max_new=3) == [3, 3]
    finally:
        await core.stop()
    prefills = {r["prompt"]: r for r in core.flight.dump()
                if r["kind"] == "prefill"}
    assert prefills[200]["grouped_rows"] == 200
    assert prefills[40]["grouped_rows"] == 0
    assert reader("moe.grouped_rows_pct")(
        {"flight": list(prefills.values())}) == pytest.approx(
            100.0 * 200 / 240)
