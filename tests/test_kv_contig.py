"""Contiguity-aware KV layout (ISSUE 5): the run-tracking block
allocator (llm/kv/pool.py FreeRunIndex), the decode kernel's
run-coalesced DMA path (engine/attention.py wave_contig_table +
wave_dma), the defrag pass (engine/core.py _maybe_defrag), and the
host-side DMA accounting the bench gates on.

The kernel contract under test is BIT-identity: a coalesced wave fetches
the same bytes into the same buffer region as the per-block path, and
masked tail rows contribute exact zeros either way — so
coalesce=True/False must agree to the last bit on every geometry
(contiguous, fragmented, single-block, int8 rows, the MLA MQA mapping).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.attention import (dma_copy_counts,
                                         paged_attention_pallas,
                                         paged_attention_xla,
                                         quantize_kv_rows,
                                         quantize_kv_rows_sections,
                                         wave_contig_table)
from dynamo_tpu.llm.kv.blocks import compute_block_hashes
from dynamo_tpu.llm.kv.native_pool import (NativeKvBlockPool,
                                           load_native_pool_lib)
from dynamo_tpu.llm.kv.pool import FreeRunIndex, KvBlockPool

pytestmark = pytest.mark.kvfrag

_POOL_IMPLS = [KvBlockPool]
if load_native_pool_lib() is not None:
    _POOL_IMPLS.append(NativeKvBlockPool)


@pytest.fixture(params=_POOL_IMPLS, ids=lambda c: c.__name__)
def pool_cls(request):
    return request.param


# ---------------------------------------------------------------------------
# Free-run index + allocator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("counts", [
    [], [1], [2], [1, 1, 1], [2, 2, 1], [1, 2, 1, 1],
    [2] * 300 + [1] * 40,            # a cached document behind own blocks
    [2] * 5 + [1] * 300,             # the own run is longer than a step
    [1] * 128 + [2] + [1] * 128,     # the shared block sits on a step's edge
    [1] * 127 + [2] + [1] * 128, [2] * 256, [1] * 256])
def test_own_suffix_start_is_the_whole_walks_answer(counts):
    """The defrag scan asks the pool from the end, 128 ids at a time (PR 42):
    the same index as the walk over every block's refcount that it
    replaced."""
    from dynamo_tpu.engine.core import _own_suffix_start

    class Pool:
        asked = 0

        def refcounts(self, ids):
            self.asked += len(ids)
            return [counts[i] for i in ids]

    pool, blocks = Pool(), list(range(len(counts)))
    j = len(counts)
    while j > 0 and counts[j - 1] == 1:
        j -= 1
    assert _own_suffix_start(pool, blocks) == j
    assert pool.asked <= len(counts) - j + 128


def test_free_run_index_coalesces():
    idx = FreeRunIndex()
    for b in (3, 5, 4, 9, 1):      # 1, 3-4-5 coalesce; 9 alone
        idx.add(b)
    assert len(idx) == 5
    assert idx.num_runs == 3
    assert idx.largest_run == 3
    # best fit for 2: the [3,5] run (smallest >= 2), carved ascending
    assert idx.take(2) == [3, 4]
    # no run >= 3 left: largest ([5]? no — runs now {1},{5},{9}) → takes
    # largest-length (all 1, smallest start first), repeatedly
    assert idx.take(3) == [1, 5, 9]
    assert len(idx) == 0


def test_alloc_lands_contiguous_runs(pool_cls):
    pool = pool_cls(64)
    a = pool.alloc_uninit(8)
    assert a == list(range(1, 9))          # one maximal run
    b = pool.alloc_uninit(8)
    assert b == list(range(9, 17))
    pool.release(a)                        # hole at [1, 8]
    c = pool.alloc_uninit(4)               # best fit: the 8-hole
    assert c == [1, 2, 3, 4]
    d = pool.alloc_uninit(40)              # too big for the 4-hole tail
    assert d == list(range(17, 57))        # stays one run past b
    assert pool.contiguity_ratio() == 1.0


def test_release_coalesces_free_runs(pool_cls):
    pool = pool_cls(32)
    a = pool.alloc_uninit(30)
    # release interleaved halves: runs re-coalesce as both land
    pool.release(a[::2])
    pool.release(a[1::2])
    assert pool.contig_runs == 1
    assert pool.frag_ratio() == 0.0
    assert pool.alloc_uninit(30) == a


def test_frag_ratio_reflects_shatter(pool_cls):
    pool = pool_cls(33)
    a = pool.alloc_uninit(32)
    pool.release(a[::2])                   # 16 single-block runs
    assert pool.contig_runs == 16
    assert pool.frag_ratio() == 1.0 - 1.0 / 16


def test_eviction_order_preserved_with_heap(pool_cls):
    """The lazy-heap rewrite of _evict_one must keep the exact
    (priority, return_tick) victim order, including after blocks are
    re-matched (stale heap entries) and re-released."""
    removed = []
    pool = pool_cls(6, on_removed=lambda h: removed.append(list(h)))
    b = pool.alloc_uninit(5)
    h = compute_block_hashes(list(range(20)), 4)
    for i, bid in enumerate(b):
        pool.register(bid, h[i], 0, h[i - 1] if i else None)
    pool.release(b)                        # LRU order b0..b4
    # re-match b0's hash: its heap entry goes stale; release re-queues
    # it at the BACK of the LRU
    assert pool.match_prefix([h[0]]) == [b[0]]
    pool.release([b[0]])
    got = pool.alloc_uninit(2)             # evicts b1 then b2, not b0
    # removed events may batch per call (native) or per block (python):
    # compare the flat hash stream, masked to the wire's u64
    flat = [x & 0xFFFFFFFFFFFFFFFF for ev in removed for x in ev]
    assert flat == [h[1] & 0xFFFFFFFFFFFFFFFF,
                    h[2] & 0xFFFFFFFFFFFFFFFF]
    assert sorted(got) == sorted([b[1], b[2]])


def test_evict_one_is_amortized_constant():
    """Regression for the O(n)-min() eviction on a mostly-reusable
    pool: total lazy-heap pops across a full drain stay linear in the
    number of heap entries ever pushed (each stale entry is skipped at
    most once), not quadratic."""
    n = 2048
    pool = KvBlockPool(n + 1)
    blocks = pool.alloc_uninit(n)
    h = compute_block_hashes(list(range(4 * n)), 4)
    for i, bid in enumerate(blocks):
        pool.register(bid, h[i], 0, h[i - 1] if i else None)
    pool.release(blocks)                   # n reusable blocks
    # churn: re-match/release a prefix repeatedly (stale entries pile
    # up), then drain the whole pool through eviction
    for _ in range(4):
        hit = pool.match_prefix(h[:256])
        pool.release(hit)
    for _ in range(n):
        pool.alloc_uninit(1)
    # pushes: n initial + 4*256 re-releases; skips can never exceed the
    # stale surplus, and the drain itself pops exactly one live entry
    # per eviction
    assert pool.evict_heap_skips <= 4 * 256


def test_relocate_hash_registration_follows(pool_cls):
    pool = pool_cls(32)
    a = pool.alloc_uninit(4)
    h = compute_block_hashes(list(range(16)), 4)
    for i, bid in enumerate(a):
        pool.register(bid, h[i], 0, h[i - 1] if i else None)
    tgt = pool.alloc_uninit(4)
    pool.relocate(list(zip(a, tgt)))
    # old ids are free again (coalesced), registrations moved
    assert pool.free_blocks == 31 - 4
    pool.release(tgt)
    assert pool.match_prefix(h[:4]) == tgt
    entries = {e[1] & 0xFFFFFFFFFFFFFFFF: e[0]
               for e in pool.registered_entries()}
    for i, bid in enumerate(tgt):
        assert entries[h[i] & 0xFFFFFFFFFFFFFFFF] == bid
    pool.release(tgt)


def test_relocate_rejects_bad_targets(pool_cls):
    pool = pool_cls(16)
    a = pool.alloc_uninit(2)
    h = compute_block_hashes(list(range(8)), 4)
    pool.register(a[0], h[0], 0, None)
    with pytest.raises(ValueError):
        pool.relocate([(a[1], a[0])])      # target registered
    pool.release(a)
    b = pool.alloc_uninit(1)
    with pytest.raises(ValueError):
        pool.relocate([(5, b[0])])         # source not resident


def test_allocator_churn_contiguity_and_integrity(pool_cls):
    """The acceptance workload: random alloc/release/evict/defrag-style
    relocate cycles. The run allocator must keep the cumulative alloc
    contiguity ratio >= 0.5 under churn, and every hash registration
    must stay consistent (match_prefix returns the block that carries
    the hash) across the whole run."""
    rng = np.random.default_rng(99)
    pool = pool_cls(257)
    hashes = compute_block_hashes(list(range(4 * 1024)), 4)
    held = []        # (blocks, first_hash_index or None)
    next_h = 0
    for step in range(600):
        op = rng.integers(0, 8)
        if op <= 3:                                  # alloc + register
            n = int(rng.integers(2, 9))
            if n > pool.free_blocks:
                continue
            blocks = pool.alloc_uninit(n)
            assert blocks is not None
            if next_h + n <= len(hashes) and rng.integers(0, 2):
                for i, bid in enumerate(blocks):
                    j = next_h + i
                    pool.register(bid, hashes[j], j,
                                  hashes[j - 1] if j else None)
                held.append((blocks, next_h))
                next_h += n
            else:
                held.append((blocks, None))
        elif op <= 5 and held:                       # release a seq
            i = int(rng.integers(0, len(held)))
            blocks, _h0 = held.pop(i)
            pool.release(blocks)
        elif held:                                   # defrag-style move
            i = int(rng.integers(0, len(held)))
            blocks, h0 = held[i]
            if len(blocks) > pool.free_blocks:
                continue
            tgt = pool.alloc_uninit(len(blocks))
            if tgt is None:
                continue
            pool.relocate(list(zip(blocks, tgt)))
            held[i] = (tgt, h0)
    # hash-registration integrity: every live registered sequence still
    # matches at its CURRENT blocks
    for blocks, h0 in held:
        if h0 is None:
            continue
        got = pool.match_prefix(hashes[h0:h0 + len(blocks)])
        assert got == blocks, (h0, blocks, got)
        pool.release(got)
    assert pool.contiguity_ratio() >= 0.5, pool.contiguity_ratio()


# ---------------------------------------------------------------------------
# Kernel: coalesced DMA bit-identity
# ---------------------------------------------------------------------------

B, H, KVH, Dh, BS = 7, 8, 2, 64, 16
C = KVH * Dh
NB = 64
M = 8


def _tables(kind: str, rng, nb=NB, m=M, b=B):
    if kind == "contig":
        t = np.zeros((b, m), np.int32)
        for i in range(b):
            s = 1 + (i * m) % (nb - m)
            t[i] = np.arange(s, s + m)
        return t
    if kind == "fragmented":
        return rng.integers(1, nb, size=(b, m)).astype(np.int32)
    if kind == "mixed":    # contiguous prefix run, scattered tail
        t = _tables("contig", rng, nb, m, b)
        t[:, m // 2:] = rng.integers(1, nb, size=(b, m - m // 2))
        return t
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["contig", "fragmented", "mixed"])
@pytest.mark.parametrize("cb", [2, 4, 8])
def test_coalesced_bit_identical_f32(kind, cb):
    rng = np.random.default_rng(11)
    k = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    tables = jnp.asarray(_tables(kind, rng))
    lens = rng.integers(0, M * BS + 1, size=(B,))
    lens[0], lens[1], lens[2] = 0, 1, M * BS
    seq_lens = jnp.asarray(lens, jnp.int32)
    kw = dict(block_size=BS, scale=Dh ** -0.5, chunk_blocks=cb,
              seqs_per_program=3, interpret=True)
    on = paged_attention_pallas(q, k, v, tables, seq_lens,
                                coalesce=True, **kw)
    off = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 coalesce=False, **kw)
    assert np.array_equal(np.asarray(on), np.asarray(off))
    want = paged_attention_xla(q, k, v, tables, seq_lens,
                               block_size=BS, scale=Dh ** -0.5)
    live = np.asarray(seq_lens) > 0
    np.testing.assert_allclose(np.asarray(on)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)


def test_coalesced_bit_identical_single_block():
    """Single-block sequences: every wave is a partial tail wave — the
    coalesce predicate's bounds check and the per-block clamp must
    still agree bit-for-bit."""
    rng = np.random.default_rng(5)
    nb, m = 16, 1
    k = jnp.asarray(rng.standard_normal((nb * BS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb * BS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((5, H, Dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, size=(5, m)), jnp.int32)
    seq_lens = jnp.asarray([3, 16, 1, 7, 16], jnp.int32)
    kw = dict(block_size=BS, scale=Dh ** -0.5, seqs_per_program=2,
              interpret=True)
    on = paged_attention_pallas(q, k, v, tables, seq_lens,
                                coalesce=True, **kw)
    off = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 coalesce=False, **kw)
    assert np.array_equal(np.asarray(on), np.asarray(off))


def test_coalesced_bit_identical_int8_rows():
    """int8 KV rows (in-row scales): the coalesced copy carries the
    value + scale lanes exactly like the per-block copies."""
    rng = np.random.default_rng(21)
    bs = 32                               # int8 sublane tile
    nb, m, b = 32, 4, 2
    vals = rng.standard_normal((nb * bs, C)).astype(np.float32) * 3.0
    pool = quantize_kv_rows(jnp.asarray(vals))
    q = jnp.asarray(rng.standard_normal((b, H, Dh)), jnp.float32)
    t = np.zeros((b, m), np.int32)
    for i in range(b):                    # contiguous runs
        t[i] = np.arange(1 + i * m, 1 + (i + 1) * m)
    t[-1] = t[-1][::-1]                   # one fragmented row
    tables = jnp.asarray(t)
    seq_lens = jnp.asarray(rng.integers(1, m * bs + 1, size=(b,)),
                           jnp.int32)
    kw = dict(block_size=bs, scale=Dh ** -0.5, chunk_blocks=2,
              interpret=True)
    on = paged_attention_pallas(q, pool, pool, tables, seq_lens,
                                coalesce=True, **kw)
    off = paged_attention_pallas(q, pool, pool, tables, seq_lens,
                                 coalesce=False, **kw)
    assert np.array_equal(np.asarray(on), np.asarray(off))


def test_coalesced_bit_identical_mla_modes():
    """The MLA MQA mapping: v-aliases-k (full precision) and the
    sectioned-int8 latent encoding — the single-stream DMA coalesces
    the same way."""
    rng = np.random.default_rng(31)
    W, bs, m, b, h, vl = 256, 16, 4, 2, 8, 128
    nb = 48
    pool = jnp.asarray(rng.standard_normal((nb * bs, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, W)), jnp.float32)
    t = _tables("mixed", rng, nb, m, b)
    tables = jnp.asarray(t)
    seq_lens = jnp.asarray(rng.integers(1, m * bs + 1, size=(b,)),
                           jnp.int32)
    kw = dict(block_size=bs, scale=0.07, chunk_blocks=4,
              interpret=True, v_lanes=vl)
    on = paged_attention_pallas(q, pool, pool, tables, seq_lens,
                                coalesce=True, **kw)
    off = paged_attention_pallas(q, pool, pool, tables, seq_lens,
                                 coalesce=False, **kw)
    assert np.array_equal(np.asarray(on), np.asarray(off))

    # sectioned int8 latent pool (rank 128 | rope 64)
    rank, dr = 128, 64
    bs2 = 32
    vals = np.concatenate(
        [rng.standard_normal((nb * bs2, rank)).astype(np.float32),
         rng.standard_normal((nb * bs2, dr)).astype(np.float32) * 15.0],
        axis=1)
    enc = np.asarray(quantize_kv_rows_sections(jnp.asarray(vals),
                                               (rank, dr)))
    pool8 = jnp.asarray(np.pad(enc, ((0, 0), (0, 384 - enc.shape[1]))))
    q8 = jnp.asarray(rng.standard_normal((b, h, 256)).astype(np.float32)
                     * 0.3, jnp.bfloat16)
    t8 = _tables("mixed", rng, nb, 4, b)
    lens8 = jnp.asarray(rng.integers(1, 4 * bs2 + 1, size=(b,)),
                        jnp.int32)
    kw8 = dict(block_size=bs2, scale=0.05, chunk_blocks=2,
               interpret=True, v_lanes=rank, quant_sections=(rank, dr))
    on8 = paged_attention_pallas(q8, pool8, pool8, jnp.asarray(t8),
                                 lens8, coalesce=True, **kw8)
    off8 = paged_attention_pallas(q8, pool8, pool8, jnp.asarray(t8),
                                  lens8, coalesce=False, **kw8)
    assert np.array_equal(np.asarray(on8), np.asarray(off8))


def test_coalesced_with_sliding_window():
    """win_lo shifts start_ci: the coalescibility table is indexed by
    absolute wave id, so windowed sequences must stay bit-identical
    too."""
    rng = np.random.default_rng(41)
    b, m = 3, 4
    k = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, H, Dh)), jnp.float32)
    tables = jnp.asarray(_tables("mixed", rng, m=m, b=b))
    lens = rng.integers(1, m * BS + 1, size=(b,))
    seq_lens = jnp.asarray(lens, jnp.int32)
    win_lo = jnp.asarray(rng.integers(-1, 48, size=(b,)), jnp.int32)
    kw = dict(block_size=BS, scale=Dh ** -0.5, chunk_blocks=2,
              win_lo=win_lo, interpret=True)
    on = paged_attention_pallas(q, k, v, tables, seq_lens,
                                coalesce=True, **kw)
    off = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 coalesce=False, **kw)
    # fully-windowed-out rows (win_lo >= seq_len-1) are unspecified on
    # EVERY path (0/0 softmax over an all-masked wave reads whatever is
    # in the buffer) — the identity contract covers live rows
    live = (np.asarray(seq_lens)
            > np.maximum(np.asarray(win_lo) + 1, 0))
    assert live.any()
    assert np.array_equal(np.asarray(on)[live], np.asarray(off)[live])


# ---------------------------------------------------------------------------
# Host-side DMA accounting
# ---------------------------------------------------------------------------


def test_wave_contig_table_np_jnp_agree():
    """ONE predicate, two array namespaces: the in-trace (jnp) table the
    kernel prefetches and the numpy table the host stats use must agree
    on random inputs — drift here would make the bench gate lie about
    what the kernel does."""
    rng = np.random.default_rng(7)
    for _ in range(5):
        bt = rng.integers(0, 60, size=(6, 12)).astype(np.int32)
        sl = rng.integers(0, 12 * 16 + 1, size=(6,)).astype(np.int32)
        kw = dict(block_size=16, chunk=4, pool_blocks=60)
        a = np.asarray(wave_contig_table(jnp.asarray(bt),
                                         jnp.asarray(sl), xp=jnp, **kw))
        b = wave_contig_table(bt, sl, xp=np, **kw)
        assert np.array_equal(a, b)


def test_dma_copy_counts_contig_vs_frag():
    """The acceptance gate's shape: a contiguous layout must cut issued
    copies >= 2x vs the same blocks fragmented."""
    rng = np.random.default_rng(3)
    b, m, bs = 8, 8, 16
    contig = _tables("contig", rng, nb=128, m=m, b=b)
    frag = contig[:, ::-1].copy()          # same blocks, descending
    lens = np.full((b,), m * bs, np.int32)
    kw = dict(block_size=bs, pool_blocks=128, chunk_blocks=4)
    c = dma_copy_counts(contig, lens, **kw)
    f = dma_copy_counts(frag, lens, **kw)
    assert c["waves"] == f["waves"]
    assert c["coalesced_waves"] == c["waves"]
    assert f["coalesced_waves"] == 0
    assert f["copies"] >= 2 * c["copies"]
    # fully coalesced: one copy per stream per wave
    assert c["copies_per_wave"] == 2.0


# ---------------------------------------------------------------------------
# Engine: defrag pass
# ---------------------------------------------------------------------------


@pytest.mark.asyncio
async def test_engine_defrag_restores_contiguity(tiny_model_dir):
    """Fragment a resident sequence's layout on purpose, then let the
    idle defrag pass migrate it: the block table must become one run,
    the output stream must be unaffected (the engine keeps decoding
    through the move), and the pool's registrations must follow."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling

    model_cfg = ModelConfig.from_model_dir(tiny_model_dir)
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8,
                        num_kv_blocks=64, max_num_seqs=2,
                        prefill_buckets=[32],
                        kv_defrag_threshold=0.01)
    core = EngineCore(model_cfg, ecfg, attn_impl="xla",
                      param_dtype=jnp.float32)
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, model_cfg.vocab_size, size=24).tolist()

    async def run(p, n_new):
        req = EngineRequest(rid="r", prompt=list(p),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=n_new, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 30)
            if item is FINISH_SENTINEL:
                return toks, req
            toks.append(item)

    try:
        # baseline stream, no interference
        base_toks, _ = await run(prompt, 24)
        core.kv_manager.pool.reset()

        # shatter the free space: hold the WHOLE pool, release every
        # other block — only single-block free runs remain, so the
        # next admission lands fragmented
        pool = core.kv_manager.pool
        comb = pool.alloc_uninit(63)
        pool.release(comb[::2])

        req = EngineRequest(rid="frag", prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=24, eos_ids=frozenset())
        await core.submit(req)
        # admitted (fragmented) and decoding: a step is in flight from the
        # first decode dispatch on, so the pass below has one to harvest
        # (a pass that finds nothing in flight drains nothing)
        while req.slot < 0 or core._pending is None:
            await asyncio.sleep(0.005)
        assert pool.count_runs(
            core.slots[req.slot].blocks) >= 2
        # release the rest of the comb: contiguous free runs reappear,
        # and the idle defrag pass migrates the resident sequence into
        # one while it keeps decoding
        pool.release(comb[1::2])
        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 30)
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        assert toks == base_toks            # stream unaffected by moves
        assert core.defrag_passes >= 1
        # each pass harvested the decode step in flight before it moved
        # anything (one step per dispatch always has one in flight)
        assert core.pipeline_drains.get("defrag", 0) >= core.defrag_passes
        assert pool.defrag_moves_total >= 2
    finally:
        await core.stop()
