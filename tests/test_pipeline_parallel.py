"""Pipeline parallelism (parallel/pipeline_parallel.py): pp-sharded
layer stacks must serve IDENTICALLY to the single-device model —
including the KV the stages write (ramp-tick garbage must land on
dropped slots, never in the pool). The token-interleaved loop is held
to BIT-EQUAL sampled token streams and pool bytes over chained
dispatches, through the full EngineCore serving path, and across a
preemption landing mid-stream (the stage ring's fill/drain ramps
straddle the preempted dispatch).
Reference analog: the vLLM engines' pipeline_parallel_size flag
(subprocess.rs:41); ours is the cross-host THROUGHPUT axis since this
round (module docstring has the DCN arithmetic and the interleave
schedule)."""

import asyncio
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sampling import make_slot_keys, sample_tokens
from dynamo_tpu.parallel.pipeline_parallel import (make_pp_mesh,
                                                   place_pp,
                                                   pp_bubble_fraction,
                                                   pp_decode_k_forward,
                                                   pp_dispatch_ticks,
                                                   pp_dispatch_utilization,
                                                   pp_kv_pspecs,
                                                   pp_param_pspecs,
                                                   pp_prefill_forward,
                                                   pp_split_config)

pytestmark = pytest.mark.pp

TINY = ModelConfig(
    model_type="llama", vocab_size=128, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=16, max_position_embeddings=256, tie_word_embeddings=False)


def _place(params, kv, mesh):
    from jax.sharding import NamedSharding
    specs = pp_param_pspecs(TINY)
    params = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    kvs = pp_kv_pspecs()
    kv = {k: jax.device_put(v, NamedSharding(mesh, kvs[k]))
          for k, v in kv.items()}
    return params, kv


@pytest.mark.parametrize("pp", [2, 4])
def test_pp_decode_matches_single_device(pp):
    """One interleaved dispatch against the plain single-device walk:
    `decode_forward` one step at a time, greedy — not the scan
    `_decode_k_ref` holds it to below. Tokens equal, the chosen tokens'
    logprobs (all the dispatch returns of its logits) and the pool the
    stages wrote allclose."""
    statics = llama.ModelStatics(cfg=TINY, block_size=8, attn_impl="xla")
    params = llama.init_params(TINY, jax.random.PRNGKey(3),
                               dtype=jnp.float32)
    kv0 = llama.init_kv_cache(TINY, 32, 8, dtype=jnp.float32)
    B, M, K = 4, 4, 3
    # disjoint per-slot tables; seq 0 decodes AT the pool's final row
    # (block 31, offset 7 = row NTOK-1): a ramp tick's masked KV scatter
    # must never touch it — a -1 mask would overwrite exactly that row
    # (review catch: advanced-index scatter normalizes -1 BEFORE
    # mode="drop")
    grid = np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    grid[0, M - 1] = 31
    tables = jnp.asarray(grid)
    toks = jnp.asarray([5, 9, 17, 33], jnp.int32)
    pos = jnp.asarray([31, 7, 12, 0], jnp.int32)

    # single-device truth: K chained steps (the pp pool writes must feed
    # later steps exactly)
    want_toks, want_lps = [], []
    kv = jax.tree.map(jnp.copy, kv0)
    t, p = toks, pos
    step = jax.jit(llama.decode_forward, static_argnums=5)
    for _ in range(K):
        lg, kv = step(params, kv, t, p, tables, statics)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        want_toks.append(np.asarray(t))
        want_lps.append(np.asarray(jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1), t[:, None], -1)[:, 0]))
        p = p + 1

    mesh = make_pp_mesh(pp)
    pparams, pkv = _place(params, jax.tree.map(jnp.copy, kv0), mesh)
    zeros = jnp.zeros((B,), jnp.int32)
    tk, lps, pkv = jax.jit(lambda pr, kv_: pp_decode_k_forward(
        pr, kv_, toks, pos, tables, zeros, zeros,
        jnp.zeros((B,), jnp.float32), zeros, jnp.ones((B,), jnp.float32),
        jnp.zeros((K, B), jnp.int32), jnp.zeros((K, B), bool),
        statics, mesh, K, 0))(pparams, pkv)

    np.testing.assert_array_equal(np.asarray(tk), np.stack(want_toks))
    np.testing.assert_allclose(np.asarray(lps), np.stack(want_lps),
                               rtol=1e-5, atol=1e-5)
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pkv[key]),
                                   np.asarray(kv[key]),
                                   rtol=1e-5, atol=1e-5)


def _decode_k_ref(params, kv, tables, statics, seeds, temp, topk, topp,
                  seed, K):
    """The engine's single-device decode_k scan, as a jittable closure —
    the truth the interleaved loop must reproduce BIT-exactly."""
    def fn(kv, tokens, positions, s0, planned, pmask):
        def body(carry, xs):
            kv, tk, p = carry
            keys = make_slot_keys(seed, seeds, s0 + xs["k"])
            tok_in = jnp.where(xs["pm"], xs["pt"], tk)
            logits, kv = llama.decode_forward(params, kv, tok_in, p,
                                              tables, statics)
            t2, lp2 = sample_tokens(logits, keys, temp, topk, topp)
            return (kv, t2, p + 1), (t2, lp2)
        (kv, _, _), (tk, lk) = jax.lax.scan(
            body, (kv, tokens, positions),
            {"k": jnp.arange(K), "pt": planned, "pm": pmask})
        return tk, lk, kv
    return jax.jit(fn)


@pytest.mark.parametrize("pp,tp", [(2, 1), (4, 1), (2, 2)])
def test_pp_interleaved_decode_bit_exact_chained(pp, tp):
    """Token-interleaved K-step decode: sampled token streams (greedy
    AND seeded temperature) are BIT-equal to the single-device scan over
    chained dispatches, and at tp=1 the whole KV pool is byte-identical
    (tp shards compute per-shard K/V projections whose f32 tiling can
    differ at the last bit — tokens still match; the same caveat GSPMD
    tp carries today)."""
    statics = llama.ModelStatics(cfg=TINY, block_size=8, attn_impl="xla")
    params = llama.init_params(TINY, jax.random.PRNGKey(3),
                               dtype=jnp.float32)
    kv0 = llama.init_kv_cache(TINY, 40, 8, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    B, K, SEED = 8, 3, 0
    M = 4
    # disjoint per-slot tables (the engine allocator's guarantee); slot
    # 0 decodes at the pool's FINAL row so a ramp-tick -1-style mask bug
    # would corrupt it (the v1 review catch, re-asserted for the ramp)
    perm = rng.permutation(np.arange(1, 40)).astype(np.int32)[:B * M]
    grid = perm.reshape(B, M)
    swap = np.argwhere(grid == 39)
    if len(swap):
        grid[swap[0][0], swap[0][1]] = grid[0, M - 1]
    grid[0, M - 1] = 39
    tables = jnp.asarray(grid)
    toks = jnp.asarray(rng.integers(1, 128, size=B).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, 8, size=B).astype(np.int32))
    pos = pos.at[0].set(31)
    seeds = jnp.asarray(np.arange(B, dtype=np.int64))
    temp = jnp.asarray(np.where(np.arange(B) % 2, 0.8, 0.0)
                       .astype(np.float32))   # mixed greedy + seeded
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    planned = jnp.zeros((K, B), jnp.int32)
    pmask = jnp.zeros((K, B), bool)

    ref = _decode_k_ref(params, jax.tree.map(jnp.copy, kv0), tables,
                        statics, seeds, temp, topk, topp, SEED, K)
    kv = jax.tree.map(jnp.copy, kv0)
    t, p = toks, pos
    s0 = jnp.asarray(np.zeros(B, np.int64))
    ref_toks = []
    for _ in range(2):                       # chained dispatches
        tk, _lk, kv = ref(kv, t, p, s0, planned, pmask)
        ref_toks.append(np.asarray(tk))
        t, p, s0 = tk[-1], p + K, s0 + K
    ref_kv = kv

    mesh = make_pp_mesh(pp, tp=tp)
    pparams, pkv = place_pp(params, jax.tree.map(jnp.copy, kv0), mesh,
                            TINY)
    fn = jax.jit(lambda pr, kv, t, p, s0: pp_decode_k_forward(
        pr, kv, t, p, tables, seeds, s0, temp, topk, topp,
        planned, pmask, statics, mesh, K, SEED))
    t, p = toks, pos
    s0 = jnp.asarray(np.zeros(B, np.int64))
    for d in range(2):
        tk, _lk, pkv = fn(pparams, pkv, t, p, s0)
        np.testing.assert_array_equal(np.asarray(tk), ref_toks[d])
        t, p, s0 = tk[-1], p + K, s0 + K
    if tp == 1:
        for key in ("k", "v"):
            assert np.array_equal(np.asarray(ref_kv[key]),
                                  np.asarray(pkv[key])), \
                f"pp={pp} kv[{key}] diverged from single-device pool"


def test_pp_interleaved_planned_tokens():
    """Lane-prefill planned inputs thread the interleave exactly like
    the single-device scan (step-0 override at the rank-0 fresh embed,
    later steps at the last stage's next-token selection)."""
    statics = llama.ModelStatics(cfg=TINY, block_size=8, attn_impl="xla")
    params = llama.init_params(TINY, jax.random.PRNGKey(3),
                               dtype=jnp.float32)
    kv0 = llama.init_kv_cache(TINY, 40, 8, dtype=jnp.float32)
    rng = np.random.default_rng(9)
    B, K, SEED = 4, 3, 0
    tables = jnp.asarray(np.arange(1, B * 4 + 1, dtype=np.int32)
                         .reshape(B, 4))
    toks = jnp.asarray(rng.integers(1, 128, size=B).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, 8, size=B).astype(np.int32))
    seeds = jnp.asarray(np.arange(B, dtype=np.int64))
    temp = jnp.zeros((B,), jnp.float32)
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    planned = np.zeros((K, B), np.int32)
    pmask = np.zeros((K, B), bool)
    planned[0, 1], pmask[0, 1] = 42, True    # mid-lane slot
    planned[1, 1], pmask[1, 1] = 17, True
    planned[0, 3], pmask[0, 3] = 9, True     # lane ending at step 1
    planned, pmask = jnp.asarray(planned), jnp.asarray(pmask)

    ref = _decode_k_ref(params, jax.tree.map(jnp.copy, kv0), tables,
                        statics, seeds, temp, topk, topp, SEED, K)
    tk_ref, _, kv_ref = ref(jax.tree.map(jnp.copy, kv0), toks, pos,
                            jnp.asarray(np.zeros(B, np.int64)), planned, pmask)

    mesh = make_pp_mesh(2)
    pparams, pkv = place_pp(params, jax.tree.map(jnp.copy, kv0), mesh,
                            TINY)
    tk, _lk, pkv = jax.jit(lambda pr, kv: pp_decode_k_forward(
        pr, kv, toks, pos, tables, seeds, jnp.asarray(np.zeros(B, np.int64)),
        temp, topk, topp, planned, pmask, statics, mesh, K, SEED))(
            pparams, pkv)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(tk_ref))
    for key in ("k", "v"):
        assert np.array_equal(np.asarray(kv_ref[key]),
                              np.asarray(pkv[key]))


@pytest.mark.parametrize("pp", [2, 4])
def test_pp_prefill_matches_chunk_walk(pp):
    """Microbatched prefill == the engine's sequential chunk walk, bit
    for bit (logits of the true-last token AND every pool byte), with
    true_len landing mid-chunk so pads exercise the trash-slot path."""
    statics = llama.ModelStatics(cfg=TINY, block_size=8, attn_impl="xla")
    params = llama.init_params(TINY, jax.random.PRNGKey(3),
                               dtype=jnp.float32)
    kv0 = llama.init_kv_cache(TINY, 40, 8, dtype=jnp.float32)
    rng = np.random.default_rng(7)
    T, true_len = 32, 27
    tokens = np.zeros((T,), np.int32)
    tokens[:true_len] = rng.integers(1, 128, size=true_len)
    table = np.zeros((8,), np.int32)
    table[:5] = [3, 9, 4, 12, 7]

    pf = jax.jit(llama.prefill_forward, static_argnums=6)
    C = T // pp
    kvw = jax.tree.map(jnp.copy, kv0)
    last_logits = None
    for m in range(pp):
        tl = max(0, min(true_len - m * C, C))
        lg, kvw = pf(params, kvw, jnp.asarray(tokens[m * C:(m + 1) * C]),
                     jnp.asarray(table), jnp.asarray(m * C, jnp.int32),
                     jnp.asarray(tl, jnp.int32), statics)
        if m * C < true_len <= (m + 1) * C:
            last_logits = np.asarray(lg)

    mesh = make_pp_mesh(pp)
    pparams, pkv = place_pp(params, jax.tree.map(jnp.copy, kv0), mesh,
                            TINY)
    got, pkv = jax.jit(lambda pr, kv: pp_prefill_forward(
        pr, kv, jnp.asarray(tokens), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(true_len, jnp.int32),
        statics, mesh))(pparams, pkv)
    np.testing.assert_array_equal(np.asarray(got), last_logits)
    for key in ("k", "v"):
        assert np.array_equal(np.asarray(kvw[key]), np.asarray(pkv[key]))


def test_pp_rejects_bad_factorizations():
    statics = llama.ModelStatics(cfg=TINY, block_size=8, attn_impl="xla")
    with pytest.raises(ValueError, match="divide"):
        pp_split_config(statics, 3)
    sw = dataclasses.replace(TINY, sliding_window=16)
    with pytest.raises(NotImplementedError, match="sliding"):
        pp_split_config(dataclasses.replace(statics, cfg=sw), 2)


def test_pp_schedule_model():
    """The interleave's analytic utilization: pp-1 ramp ticks per
    dispatch, amortized over K·pp live ticks."""
    assert pp_dispatch_ticks(2, 8) == 17
    assert pp_dispatch_utilization(2, 8) == pytest.approx(16 / 17)
    assert pp_bubble_fraction(2, 8) == pytest.approx(1 / 17)
    assert pp_dispatch_utilization(1, 8) == 1.0
    # K → inf drives utilization → 1 (the bubble is per-dispatch, not
    # per-step — the v1 loop's 1/pp floor is gone)
    assert pp_dispatch_utilization(4, 64) > 0.98


def test_pp_engine_config_validation():
    with pytest.raises(ValueError, match="decode_steps_per_dispatch"):
        EngineConfig(pp=2, max_num_seqs=4)
    with pytest.raises(ValueError, match="max_num_seqs"):
        EngineConfig(pp=2, max_num_seqs=3, decode_steps_per_dispatch=4)
    with pytest.raises(NotImplementedError, match="quantization"):
        EngineConfig(pp=2, max_num_seqs=4, decode_steps_per_dispatch=4,
                     quantization="int8")
    with pytest.raises(NotImplementedError, match="speculative"):
        EngineConfig(pp=2, max_num_seqs=4, decode_steps_per_dispatch=4,
                     spec_k=2)
    with pytest.raises(ValueError, match="bucket"):
        EngineConfig(pp=2, max_num_seqs=4, decode_steps_per_dispatch=4,
                     max_model_len=256, prefill_buckets=[31])


def test_auto_kv_block_size():
    """Satellite: the round-5 small-C finding is a bring-up policy now,
    not a bench-only default — kv_block_size=0 resolves at EngineCore
    construction through the ONE shared home."""
    from dynamo_tpu.engine.config import bench_model_config
    small_c = bench_model_config("70b_tp8shard")   # KVH·Dh = 128
    assert EngineConfig.auto_kv_block_size(small_c) == 64
    big_c = bench_model_config("1b")               # KVH·Dh = 512
    assert EngineConfig.auto_kv_block_size(big_c) == 16
    assert EngineConfig.auto_kv_block_size(big_c, "int8") == 32
    # bring-up resolution: an EngineCore built with 0 sees the resolved
    # value everywhere (pool, manager, block tables)
    from dynamo_tpu.engine.core import EngineCore
    core = EngineCore(TINY, EngineConfig(
        kv_block_size=0, max_model_len=128, num_kv_blocks=32,
        max_num_seqs=2, prefill_buckets=[64]),
        attn_impl="xla", param_dtype=jnp.float32)
    assert core.cfg.kv_block_size == 64     # TINY: KVH·Dh = 32 <= 128
    assert core.kv_manager.block_size == 64


# --------------------------------------------------------- engine serving
def _make_engine(pp=1, k=4, pipeline=False, blocks=64, tp=1,
                 model=TINY):
    from dynamo_tpu.engine.core import EngineCore
    mesh = make_pp_mesh(pp, tp=tp) if pp > 1 else None
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8,
                        num_kv_blocks=blocks, max_num_seqs=4,
                        prefill_buckets=[32, 64, 128],
                        decode_steps_per_dispatch=k,
                        decode_dispatch_pipeline=pipeline, pp=pp)
    params = llama.init_params(model, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    return EngineCore(model, ecfg, params=params, attn_impl="xla",
                      param_dtype=jnp.float32, mesh=mesh)


@pytest.mark.asyncio
async def test_pp_engine_serving_bit_exact():
    """Full serving path on a pp=2 mesh — prefill admission (the
    pipelined chunk program), K-step interleaved decode with the
    deferred-harvest dispatch pipeline, greedy AND seeded sampling —
    token streams bit-equal to a single-device engine, and the recorded
    schedule replays bit-exactly (the multihost followers' stage
    dispatches consume the identical event stream)."""
    from tests.test_preemption import run_req
    from dynamo_tpu.engine.replay import (Recorder, compare_replay,
                                          replay)
    rng = np.random.default_rng(11)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=45).tolist()

    ref_core = _make_engine(pp=1)
    try:
        ref1, _, _ = await run_req(ref_core, p1, 16)
        ref2, _, _ = await run_req(ref_core, p2, 16)
    finally:
        await ref_core.stop()

    core = _make_engine(pp=2, pipeline=True)
    core.recorder = Recorder()
    try:
        g1, _, _ = await run_req(core, p1, 16)
        g2, _, _ = await run_req(core, p2, 16)
        assert g1 == ref1 and g2 == ref2
        assert not any(k.startswith("layers.wqkv")
                       or k.startswith("layers.gateup")
                       for k in core.params), \
            "fuse_stacked_matmuls must stay OFF under a pp mesh"
        m = core.metrics()
        assert (m.pp_stages, m.pp_microbatch) == (2, 2)
        assert 0.0 < m.pp_bubble_fraction < 0.2
        rep = replay(core, core.recorder.events)
        assert compare_replay(core.recorder.events, rep) == []
    finally:
        await core.stop()


@pytest.mark.asyncio
async def test_pp_engine_seeded_sampling_bit_exact():
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling

    async def seeded(core, prompt):
        req = EngineRequest(rid="s", prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.9,
                                                  seed=13),
                            max_new_tokens=12, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 120)
            if item is FINISH_SENTINEL:
                return toks
            toks.append(item)

    rng = np.random.default_rng(3)
    prompt = rng.integers(1, TINY.vocab_size, size=20).tolist()
    ref_core = _make_engine(pp=1)
    try:
        ref = await seeded(ref_core, prompt)
    finally:
        await ref_core.stop()
    core = _make_engine(pp=2)
    try:
        got = await seeded(core, prompt)
    finally:
        await core.stop()
    assert got == ref


@pytest.mark.asyncio
async def test_pp_preemption_across_stage_boundary():
    """A preemption landing mid-stream on the pp engine: the small pool
    forces recompute preemption while the stage ring is interleaving —
    the re-admission prefill re-enters through the PIPELINED chunk
    program and the stream stays exact to the recompute boundary, with
    the recorded schedule replaying every harvested token (the
    test_preemption harness, pointed at a pp=2 core)."""
    from tests.test_preemption import (assert_exact_to_recompute_boundary,
                                       run_req)
    from dynamo_tpu.engine.replay import (Recorder, compare_replay,
                                          replay)
    from dynamo_tpu.llm.protocols.common import FinishReason

    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    max_new = 40

    big = _make_engine(pp=2, blocks=64)
    try:
        ref1, _, _ = await run_req(big, p1, max_new)
        ref2, _, _ = await run_req(big, p2, max_new)
    finally:
        await big.stop()
    assert len(ref1) == max_new

    small = _make_engine(pp=2, blocks=16)
    small.recorder = Recorder()
    try:
        (g1, r1, q1), (g2, r2, q2) = await asyncio.gather(
            run_req(small, p1, max_new, rid="a"),
            run_req(small, p2, max_new, rid="b"))
        assert r1 == FinishReason.LENGTH and r2 == FinishReason.LENGTH
        assert len(g1) == max_new and len(g2) == max_new
        assert small.preemptions > 0, "contention never preempted"
        assert_exact_to_recompute_boundary(g1, ref1, q1, "a")
        assert_exact_to_recompute_boundary(g2, ref2, q2, "b")
        rep = replay(small, small.recorder.events)
        assert compare_replay(small.recorder.events, rep) == []
    finally:
        await small.stop()
