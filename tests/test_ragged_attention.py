"""Unified ragged dispatch (ISSUE 10): one kernel / one scheduler path
for mixed prefill+decode batches.

The exactness ladder, matching the discipline the DMA-coalescing PR
shipped under (tests/test_kv_contig.py):

- the ragged XLA path IS the decode program's attention over
  row-expanded tables — asserted BIT-exact against decode_forward /
  paged_attention_xla on every geometry;
- the ragged Pallas kernel (interpret mode on CPU) matches the XLA
  reference to the established kernel tolerance (2e-5 f32 / looser for
  int8 rows — exactly test_paged_attention_kernel's bar), and its
  coalesced-vs-per-block DMA paths are BIT-identical to each other;
- EngineCore ragged serving is BIT-exact against the lane-prefill
  reference engine (both derive admissions through decode-program
  math) and invariant under packing geometry, greedy AND seeded,
  through preemption (test_preemption's harness) and recorded-schedule
  replay.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.attention import (RAGGED_WIN_SENTINEL,
                                         paged_attention_xla,
                                         quantize_kv_rows,
                                         ragged_paged_attention_pallas,
                                         ragged_supported)
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.ragged import build_ragged_batch
from dynamo_tpu.engine.models import llama

pytestmark = pytest.mark.ragged

BS = 8          # KV block size
NB = 48         # pool blocks


def _pool(rng, C, dtype=np.float32):
    k = rng.normal(size=(NB * BS, C)).astype(dtype)
    v = rng.normal(size=(NB * BS, C)).astype(dtype)
    return jnp.asarray(k), jnp.asarray(v)


def _mix(rng, n_slots, M, *, contig=False):
    """A ragged mix covering the geometry sweep's corner cases: a
    multi-wave prefill chunk, a chunk ending exactly on a block
    boundary, single-token decode rows, and a zero-length slot."""
    if contig:
        # physically consecutive ids per sequence — the coalescible
        # layout the run allocator produces
        tables = np.zeros((n_slots, M), np.int32)
        nxt = 1
        for s in range(n_slots):
            tables[s] = np.arange(nxt, nxt + M)
            nxt += M
    else:
        perm = rng.permutation(np.arange(1, NB))
        tables = perm[:n_slots * M].reshape(n_slots, M).astype(np.int32)
    # (length, ctx): ctx = kv length incl. the span's rows
    seqs = [(9, 21),          # chunk continuing a prefix, crosses waves
            (BS, 2 * BS),     # ends exactly on a block boundary
            (1, 17),          # decode row
            (0, 0),           # inactive slot
            (1, 1)][:n_slots]  # decode row with no history
    starts, counts, ctx = [], [], []
    cursor = 0
    for ln, sl in seqs:
        starts.append(cursor)
        counts.append(ln)
        ctx.append(sl)
        cursor += ln
    return (tables, np.asarray(starts, np.int32),
            np.asarray(counts, np.int32), np.asarray(ctx, np.int32),
            cursor)


def _row_expand(tables, starts, counts, ctx):
    """Per-row (table, seq_len) expansion — the XLA reference's input."""
    rt, rl, rows = [], [], []
    for s in range(len(counts)):
        for r in range(int(counts[s])):
            rows.append(int(starts[s]) + r)
            rt.append(tables[s])
            rl.append(int(ctx[s]) - int(counts[s]) + r + 1)
    return (np.asarray(rows), np.stack(rt),
            np.asarray(rl, np.int32))


@pytest.mark.parametrize("H,KVH,Dh", [(8, 2, 64), (4, 1, 128)])
def test_ragged_kernel_vs_xla_geometry_sweep(H, KVH, Dh):
    """Ragged kernel (interpret) vs the XLA reference over the corner
    mix — GQA slotting and MQA — at the established kernel tolerance,
    plus coalesced-vs-per-block AND prefetch-on-vs-off BIT-identity
    (the cross-sequence wave-prefetch chain must never change a bit —
    the mix includes a zero-length span, which breaks the chain)."""
    rng = np.random.default_rng(0)
    C = KVH * Dh
    k, v = _pool(rng, C)
    for contig in (False, True):
        tables, starts, counts, ctx, total = _mix(rng, 5, 5,
                                                  contig=contig)
        q = jnp.asarray(rng.normal(size=(total + 3, H, Dh))
                        .astype(np.float32))
        got = ragged_paged_attention_pallas(
            q, k, v, jnp.asarray(tables), starts, counts, ctx,
            block_size=BS, scale=0.11, max_rows=16, chunk_blocks=2,
            interpret=True)
        rows, rt, rl = _row_expand(tables, starts, counts, ctx)
        want = paged_attention_xla(q[rows], k, v, jnp.asarray(rt),
                                   jnp.asarray(rl), block_size=BS,
                                   scale=0.11)
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want), rtol=2e-5,
                                   atol=2e-5)
        nopf = ragged_paged_attention_pallas(
            q, k, v, jnp.asarray(tables), starts, counts, ctx,
            block_size=BS, scale=0.11, max_rows=16, chunk_blocks=2,
            prefetch=False, interpret=True)
        assert np.array_equal(np.asarray(got)[rows],
                              np.asarray(nopf)[rows]), (
            "cross-sequence prefetch changed the output")
        if contig:
            off = ragged_paged_attention_pallas(
                q, k, v, jnp.asarray(tables), starts, counts, ctx,
                block_size=BS, scale=0.11, max_rows=16, chunk_blocks=2,
                coalesce=False, interpret=True)
            assert np.array_equal(np.asarray(got)[rows],
                                  np.asarray(off)[rows]), (
                "coalesced and per-block ragged DMA paths diverged")


def test_ragged_kernel_int8_rows():
    """int8 pools with in-row (e, m) scales: the ragged kernel's
    in-VMEM dequant (shared with the decode kernel) vs the XLA
    reference's row dequant. int8 pools need 32-token blocks (the int8
    sublane tile — pallas_supported), so this mix uses its own
    geometry."""
    rng = np.random.default_rng(1)
    H, KVH, Dh = 4, 1, 128
    bs32 = 32
    C = KVH * Dh
    kf = rng.normal(size=(16 * bs32, C)).astype(np.float32)
    vf = rng.normal(size=(16 * bs32, C)).astype(np.float32)
    k8 = quantize_kv_rows(jnp.asarray(kf))
    v8 = quantize_kv_rows(jnp.asarray(vf))
    M = 3
    tables = rng.permutation(np.arange(1, 16))[:5 * M].reshape(
        5, M).astype(np.int32)
    starts = np.asarray([0, 9, 9 + bs32, 9 + bs32 + 1, 9 + bs32 + 1],
                        np.int32)
    counts = np.asarray([9, bs32, 1, 0, 1], np.int32)
    ctx = np.asarray([21, 2 * bs32, 17, 0, 1], np.int32)
    total = int(counts.sum())
    q = jnp.asarray(rng.normal(size=(total + 2, H, Dh))
                    .astype(np.float32))
    got = ragged_paged_attention_pallas(
        q, k8, v8, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(counts), jnp.asarray(ctx), block_size=bs32,
        scale=0.09, max_rows=max(bs32, 16), chunk_blocks=2,
        interpret=True)
    rows, rt, rl = _row_expand(tables, starts, counts, ctx)
    want = paged_attention_xla(q[rows], k8, v8, jnp.asarray(rt),
                               jnp.asarray(rl), block_size=bs32,
                               scale=0.09)
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    nopf = ragged_paged_attention_pallas(
        q, k8, v8, jnp.asarray(tables), jnp.asarray(starts),
        jnp.asarray(counts), jnp.asarray(ctx), block_size=bs32,
        scale=0.09, max_rows=max(bs32, 16), chunk_blocks=2,
        prefetch=False, interpret=True)
    assert np.array_equal(np.asarray(got)[rows], np.asarray(nopf)[rows]), \
        "cross-sequence prefetch changed int8 output"


def test_ragged_kernel_v_aliases_k():
    """MLA latent mode: v IS the first v_lanes lanes of each k row —
    the v-side DMA is skipped and the output narrows."""
    rng = np.random.default_rng(2)
    W, vl = 256, 128
    k, _ = _pool(rng, W)
    tables, starts, counts, ctx, total = _mix(rng, 5, 5)
    q = jnp.asarray(rng.normal(size=(total + 2, 4, W))
                    .astype(np.float32))
    got = ragged_paged_attention_pallas(
        q, k, k, jnp.asarray(tables), starts, counts, ctx,
        block_size=BS, scale=0.07, max_rows=16, chunk_blocks=2,
        v_lanes=vl, interpret=True)
    rows, rt, rl = _row_expand(tables, starts, counts, ctx)
    want = paged_attention_xla(q[rows], k, k, jnp.asarray(rt),
                               jnp.asarray(rl), block_size=BS,
                               scale=0.07)[..., :vl]
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    nopf = ragged_paged_attention_pallas(
        q, k, k, jnp.asarray(tables), starts, counts, ctx,
        block_size=BS, scale=0.07, max_rows=16, chunk_blocks=2,
        v_lanes=vl, prefetch=False, interpret=True)
    assert np.array_equal(np.asarray(got)[rows], np.asarray(nopf)[rows]), \
        "cross-sequence prefetch changed v-aliases-k output"


def test_ragged_kernel_sliding_window():
    """Per-row sliding-window floors: win_base[s] + r must mask exactly
    what per-row win_lo masks in the reference (and the global-layer
    sentinel must mask nothing)."""
    rng = np.random.default_rng(3)
    H, KVH, Dh = 8, 2, 64
    window = 10
    k, v = _pool(rng, KVH * Dh)
    tables, starts, counts, ctx, total = _mix(rng, 5, 5)
    pos0 = ctx - counts
    win_base = np.where(counts > 0, pos0 - window,
                        RAGGED_WIN_SENTINEL).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(total + 2, H, Dh))
                    .astype(np.float32))
    got = ragged_paged_attention_pallas(
        q, k, v, jnp.asarray(tables), starts, counts, ctx,
        block_size=BS, scale=0.1, max_rows=16, chunk_blocks=2,
        win_base=jnp.asarray(win_base), interpret=True)
    rows, rt, rl = _row_expand(tables, starts, counts, ctx)
    win_lo = (np.asarray(rl) - 1 - window).astype(np.int32)
    want = paged_attention_xla(q[rows], k, v, jnp.asarray(rt),
                               jnp.asarray(rl), block_size=BS,
                               scale=0.1, win_lo=jnp.asarray(win_lo))
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    nopf = ragged_paged_attention_pallas(
        q, k, v, jnp.asarray(tables), starts, counts, ctx,
        block_size=BS, scale=0.1, max_rows=16, chunk_blocks=2,
        win_base=jnp.asarray(win_base), prefetch=False, interpret=True)
    assert np.array_equal(np.asarray(got)[rows], np.asarray(nopf)[rows]), \
        "cross-sequence prefetch changed sliding-window output"


def test_ragged_prefetch_counts_mirror():
    """The host-side mirror of the kernel's prefetch chain: a sequence
    has a first wave iff it owns rows; zero-row sequences break the
    chain (their successor starts its own first wave); sliding-window
    floors can kill every wave of a sequence."""
    from dynamo_tpu.engine.attention import ragged_prefetch_counts

    counts = np.asarray([9, 8, 1, 0, 1], np.int32)
    ctx = np.asarray([21, 16, 17, 0, 1], np.int32)
    pf = ragged_prefetch_counts(counts, ctx, block_size=BS,
                                chunk_blocks=2, blocks_per_table=5)
    # slots 0..2 chain (2 hits); slot 3 is empty, so slot 4 is exposed
    assert pf == {"first_waves": 4, "prefetched": 2, "exposed": 2,
                  "hit_ratio": 0.5}
    # no sequences → no waves, ratio well-defined at 0
    pf0 = ragged_prefetch_counts(np.zeros(3, np.int32),
                                 np.zeros(3, np.int32), block_size=BS)
    assert pf0["first_waves"] == 0 and pf0["hit_ratio"] == 0.0
    # a window floor past the last wave kills the middle sequence's
    # waves entirely — both its own first wave and the chain through it
    win = np.asarray([-(1 << 30), 10_000, -(1 << 30)], np.int32)
    pfw = ragged_prefetch_counts(
        np.asarray([1, 1, 1], np.int32),
        np.asarray([40, 40, 40], np.int32), win_base=win,
        block_size=BS, chunk_blocks=2)
    assert pfw["first_waves"] == 2 and pfw["prefetched"] == 0


def test_ragged_supported_bounds():
    assert ragged_supported(8, 2, 64, 16, max_rows=32)
    assert not ragged_supported(8, 2, 64, 12, max_rows=32)   # sublane
    assert not ragged_supported(4, 2, 16, 16, max_rows=32)   # lanes
    # VMEM window: a huge GQA geometry at a deep row budget must refuse
    assert not ragged_supported(64, 8, 128, 16, max_rows=256)


# --------------------------------------------------------------------------
# ragged_forward: BIT-exactness against the split programs (XLA, CPU)
# --------------------------------------------------------------------------

TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                   max_position_embeddings=512)
TINY_SLIDE = ModelConfig(vocab_size=256, hidden_size=64,
                         intermediate_size=128, num_layers=2,
                         num_heads=4, num_kv_heads=2, head_dim=16,
                         max_position_embeddings=512, sliding_window=12)


def _ragged_args(n_slots, TT, chunks):
    """chunks: {slot: (tokens, pos0)} → device args for ragged_forward;
    rows packed in slot order."""
    tokens = np.zeros((TT,), np.int32)
    positions = np.zeros((TT,), np.int32)
    row_slot = np.full((TT,), n_slots, np.int32)
    starts = np.zeros((n_slots + 1,), np.int32)
    counts = np.zeros((n_slots + 1,), np.int32)
    sample_rows = np.zeros((n_slots + 1,), np.int32)
    cursor = 0
    for slot in sorted(chunks):
        toks, pos0 = chunks[slot]
        L = len(toks)
        tokens[cursor:cursor + L] = toks
        positions[cursor:cursor + L] = pos0 + np.arange(L)
        row_slot[cursor:cursor + L] = slot
        starts[slot] = cursor
        counts[slot] = L
        sample_rows[slot] = cursor + L - 1
        cursor += L
    starts[n_slots] = cursor
    return tuple(jnp.asarray(a) for a in
                 (tokens, positions, row_slot, starts, counts,
                  sample_rows))


@pytest.mark.parametrize("cfg", [TINY, TINY_SLIDE],
                         ids=["global", "sliding"])
def test_ragged_forward_bit_exact_vs_split_programs(cfg):
    """The serving-level exactness anchor: ONE ragged dispatch carrying
    two full prompts produces (a) final-row logits BIT-identical to an
    incremental decode_forward walk over the same prompts (the lane
    program's math), (b) KV pool bytes BIT-identical where written,
    and (c) decode rows BIT-identical to decode_forward."""
    statics = llama.ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")
    params = llama.init_params(cfg, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    rng = np.random.default_rng(4)
    M = 6
    tblA = np.arange(1, 1 + M).astype(np.int32)
    tblB = np.array([9, 8, 12, 11, 14, 13], np.int32)
    pA = rng.integers(1, cfg.vocab_size, size=19).tolist()
    pB = rng.integers(1, cfg.vocab_size, size=5).tolist()

    kv_ref = llama.init_kv_cache(cfg, 32, BS, dtype=jnp.float32)
    tables2 = jnp.asarray(np.stack([tblA, tblB]))
    logits_at = {}
    for t in range(len(pA)):
        toks = jnp.asarray(np.array(
            [pA[t], pB[min(t, len(pB) - 1)]], np.int32))
        pos = jnp.asarray(np.array([t, min(t, len(pB) - 1)], np.int32))
        lg, kv_ref = llama.decode_forward(params, kv_ref, toks, pos,
                                          tables2, statics)
        logits_at[t] = np.asarray(lg)

    kv_rag = llama.init_kv_cache(cfg, 32, BS, dtype=jnp.float32)
    tables = jnp.asarray(np.stack([tblA, tblB,
                                   np.zeros((M,), np.int32)]))
    args = _ragged_args(2, 32, {0: (pA, 0), 1: (pB, 0)})
    lg, kv_rag = llama.ragged_forward(params, kv_rag, *args[:2], tables,
                                      *args[2:], statics)
    lg = np.asarray(lg)
    assert (lg[0] == logits_at[len(pA) - 1][0]).all()
    assert (lg[1] == logits_at[len(pB) - 1][1]).all()
    # pool bytes where A's prompt wrote
    idx = (tblA[:, None] * BS + np.arange(BS)[None, :]).reshape(-1)
    idx = idx[:len(pA)]
    assert (np.asarray(kv_ref["k"])[:, idx]
            == np.asarray(kv_rag["k"])[:, idx]).all()
    # a follow-up decode row through ragged == decode_forward, bit-for-bit
    nxtA = int(np.argmax(lg[0]))
    kv_d = jax.tree_util.tree_map(lambda x: x.copy(), kv_rag)
    lgd, _ = llama.decode_forward(
        params, kv_d, jnp.asarray([nxtA, 0]),
        jnp.asarray([len(pA), 0]),
        jnp.asarray(np.stack([tblA, np.zeros((M,), np.int32)])),
        statics)
    args2 = _ragged_args(2, 32, {0: ([nxtA], len(pA))})
    lgr, _ = llama.ragged_forward(params, kv_rag, *args2[:2], tables,
                                  *args2[2:], statics)
    assert (np.asarray(lgr)[0] == np.asarray(lgd)[0]).all()


def test_ragged_forward_mla_parity():
    """MLA: the ragged dispatch vs an incremental mla.decode_forward
    walk — full-precision AND the sectioned-int8 latent pool. Unlike
    the llama family (bit-exact above), the absorbed-attention einsums
    ("bhd,hrd->bhr" and friends) lower batch-size-DEPENDENTLY on CPU
    XLA (dot_general batching picks different accumulation shapes for
    1 vs TT rows), so MLA parity is tight-allclose at f32
    accumulation-order level rather than bit-equal — measured ~1e-6
    relative on this geometry, asserted at 1e-4."""
    from dynamo_tpu.engine.models import mla

    cfg = ModelConfig(model_type="deepseek_v2", vocab_size=256,
                      hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=4,
                      head_dim=48, max_position_embeddings=512,
                      q_lora_rank=0, kv_lora_rank=64,
                      qk_nope_head_dim=32, qk_rope_head_dim=16,
                      v_head_dim=32)
    for quant in ("none", "int8"):
        statics = llama.ModelStatics(cfg=cfg, block_size=BS,
                                     attn_impl="xla")
        params = mla.init_params(cfg, jax.random.PRNGKey(1),
                                 dtype=jnp.float32)
        kv_ref = mla.init_kv_cache(cfg, 32, BS, dtype=jnp.float32,
                                   quantization=quant)
        rng = np.random.default_rng(5)
        M = 4
        tbl = np.arange(1, 1 + M).astype(np.int32)
        p = rng.integers(1, cfg.vocab_size, size=9).tolist()
        lg_ref = None
        for t, tok in enumerate(p):
            lg_ref, kv_ref = mla.decode_forward(
                params, kv_ref, jnp.asarray([tok]), jnp.asarray([t]),
                jnp.asarray(tbl[None, :]), statics)
        kv_rag = mla.init_kv_cache(cfg, 32, BS, dtype=jnp.float32,
                                   quantization=quant)
        tables = jnp.asarray(np.stack([tbl, np.zeros((M,), np.int32)]))
        args = _ragged_args(1, 16, {0: (p, 0)})
        lg, kv_rag = mla.ragged_forward(params, kv_rag, *args[:2],
                                        tables, *args[2:], statics)
        np.testing.assert_allclose(np.asarray(lg)[0],
                                   np.asarray(lg_ref)[0],
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=quant)
        pool_ref = np.asarray(kv_ref["kv"])
        pool_rag = np.asarray(kv_rag["kv"])
        idx = (tbl[:, None] * BS + np.arange(BS)[None, :]).reshape(-1)
        idx = idx[:len(p)]
        np.testing.assert_allclose(
            pool_ref[:, idx].astype(np.float32),
            pool_rag[:, idx].astype(np.float32),
            rtol=1e-4, atol=2e-2 if quant == "int8" else 1e-4,
            err_msg=quant)


# --------------------------------------------------------------------------
# Batch builder: packing policy + metadata contract
# --------------------------------------------------------------------------


def test_builder_packing_policy():
    """Decode rows always land; every prefill lane gets a minimum row;
    the surplus round-robins fairly; starts ascend in slot order; the
    metadata contract carries (start, len, mode)."""
    b = build_ragged_batch(
        16, 4,
        decode_rows=[(0, 7, 30), (3, 9, 12)],
        prefill_lanes=[(1, list(range(100, 140)), 0),
                       (2, list(range(200, 203)), 5)],
        max_seq_rows=32)
    assert b.rows_used == 16 and b.fill_ratio == 1.0
    assert b.mixed and b.n_prefill == 2 and b.n_decode == 2
    meta = {slot: (start, ln, mode)
            for slot, start, ln, mode in b.seqs_meta()}
    assert meta[0][1] == 1 and meta[0][2] == "decode"
    assert meta[3][1] == 1 and meta[3][2] == "decode"
    # 14 surplus rows split fairly: the short lane is capped at its 3
    # tokens, the long lane takes the rest
    assert meta[2][1] == 3
    assert meta[1][1] == 11
    starts = [s.start for s in b.seqs]
    assert starts == sorted(starts)
    ends = [s.start + s.length for s in b.seqs]
    assert all(starts[i + 1] == ends[i] for i in range(len(ends) - 1))
    # dead rows aim at the trash sequence
    assert (b.row_slot[b.rows_used:] == 4).all()
    assert b.seq_starts[4] == b.rows_used
    # replaced = 2 prefill dispatches + 1 decode dispatch
    assert b.dispatches_replaced == 3
    # positions are consecutive per span
    for s in b.seqs:
        assert (b.positions[s.start:s.start + s.length]
                == s.pos0 + np.arange(s.length)).all()


def test_builder_respects_max_seq_rows_and_capacity():
    b = build_ragged_batch(
        8, 2, decode_rows=[],
        prefill_lanes=[(0, list(range(100)), 0),
                       (1, list(range(100)), 0)],
        max_seq_rows=3)
    assert [s.length for s in b.seqs] == [3, 3]
    assert b.rows_used == 6          # row budget binds before capacity
    with pytest.raises(ValueError):
        build_ragged_batch(2, 4,
                           decode_rows=[(0, 1, 1), (1, 1, 1), (2, 1, 1)],
                           prefill_lanes=[], max_seq_rows=4)
    assert build_ragged_batch(8, 2, [], [], 4) is None


def test_builder_spec_spans():
    """Spec spans (ragged × speculative decoding): row 0 is the
    mandatory decode row, draft rows ride as surplus AFTER prefill
    minimums, truncate deterministically under pressure (never split),
    and a span truncated to one row degrades to a plain decode row."""
    b = build_ragged_batch(
        16, 4, decode_rows=[(0, 7, 30)],
        prefill_lanes=[(1, list(range(100, 140)), 0)],
        max_seq_rows=32,
        spec_lanes=[(2, [9, 10, 11, 12], 12)])
    meta = {slot: (start, ln, mode)
            for slot, start, ln, mode in b.seqs_meta()}
    assert meta[2][2] == "spec" and meta[2][1] == 4
    assert b.n_spec == 1 and b.spec_rows == 3
    assert b.mixed and b.dispatches_replaced == 2
    # the spec span's rows carry the chained token + drafts at
    # consecutive positions
    s2 = next(s for s in b.seqs if s.slot == 2)
    assert list(b.tokens[s2.start:s2.start + 4]) == [9, 10, 11, 12]
    assert list(b.positions[s2.start:s2.start + 4]) == [12, 13, 14, 15]
    # capacity pressure: drafts truncate (atomic — the span still
    # appears whole in THIS dispatch, surplus drafts are dropped)
    tight = build_ragged_batch(
        4, 4, decode_rows=[(0, 7, 30), (1, 8, 5)],
        prefill_lanes=[],
        max_seq_rows=32,
        spec_lanes=[(2, [9, 10, 11, 12], 12), (3, [5, 6], 2)])
    meta = {slot: (start, ln, mode)
            for slot, start, ln, mode in tight.seqs_meta()}
    assert tight.rows_used == 4
    # slot order: slot 2 takes the single surplus row... capacity 4 =
    # 2 decode + 2 spec row-0; zero surplus → both degrade to decode
    assert meta[2][2] == "decode" and meta[2][1] == 1
    assert meta[3][2] == "decode" and meta[3][1] == 1
    # one more row of capacity goes to the FIRST spec lane in slot order
    tight5 = build_ragged_batch(
        5, 4, decode_rows=[(0, 7, 30), (1, 8, 5)],
        prefill_lanes=[], max_seq_rows=32,
        spec_lanes=[(2, [9, 10, 11, 12], 12), (3, [5, 6], 2)])
    meta = {slot: (start, ln, mode)
            for slot, start, ln, mode in tight5.seqs_meta()}
    assert meta[2][2] == "spec" and meta[2][1] == 2
    assert meta[3][2] == "decode" and meta[3][1] == 1


def test_builder_fuzz_invariants():
    """Property/fuzz sweep over random pending sets: every packing must
    satisfy the metadata contract — ascending contiguous starts, token
    capacity respected, every decode/spec slot present (decode rows
    first: emission never starves), min-progress per prefill lane, spec
    spans atomic (whole in one dispatch, row 0 = the chained token,
    consecutive positions), trash sequence pinned past the live rows."""
    rng = np.random.default_rng(1234)
    for trial in range(200):
        n_slots = int(rng.integers(1, 9))
        max_rows = int(rng.integers(1, 9))
        roles = rng.integers(0, 4, size=n_slots)   # 0 free, 1 decode,
        decode_rows, prefill_lanes, spec_lanes = [], [], []
        for slot in range(n_slots):
            pos = int(rng.integers(0, 50))
            if roles[slot] == 1:
                decode_rows.append((slot, int(rng.integers(1, 99)), pos))
            elif roles[slot] == 2:                 # 2 prefill
                toks = rng.integers(1, 99,
                                    size=int(rng.integers(1, 30))).tolist()
                prefill_lanes.append((slot, toks, pos))
            elif roles[slot] == 3:                 # 3 spec
                toks = rng.integers(1, 99,
                                    size=int(rng.integers(1, 6))).tolist()
                spec_lanes.append((slot, toks, pos))
        n_mand = len(decode_rows) + len(spec_lanes) + len(prefill_lanes)
        capacity = int(rng.integers(max(n_mand, 1), n_mand + 24))
        b = build_ragged_batch(capacity, n_slots, decode_rows,
                               prefill_lanes, max_rows,
                               spec_lanes=spec_lanes)
        if n_mand == 0:
            assert b is None
            continue
        assert b.rows_used <= capacity, "token capacity violated"
        # ascending contiguous starts in slot order; trash start after
        starts = [s.start for s in b.seqs]
        ends = [s.start + s.length for s in b.seqs]
        assert starts == sorted(starts)
        assert all(starts[i + 1] == ends[i]
                   for i in range(len(ends) - 1))
        assert b.seq_starts[n_slots] == b.rows_used
        assert (b.row_slot[b.rows_used:] == n_slots).all()
        by_slot = {s.slot: s for s in b.seqs}
        for slot, tok, pos in decode_rows:        # decode rows first
            assert by_slot[slot].length == 1
            assert b.tokens[by_slot[slot].start] == tok
        for slot, toks, pos in prefill_lanes:     # min-progress
            sp = by_slot[slot]
            assert 1 <= sp.length <= min(len(toks), max_rows)
            assert list(b.tokens[sp.start:sp.start + sp.length]) \
                == [int(t) for t in toks[:sp.length]]
        for slot, toks, pos in spec_lanes:        # spec spans atomic
            sp = by_slot[slot]
            assert 1 <= sp.length <= min(len(toks), max_rows)
            assert sp.mode == ("spec" if sp.length > 1 else "decode")
            assert list(b.tokens[sp.start:sp.start + sp.length]) \
                == [int(t) for t in toks[:sp.length]]
            assert list(b.positions[sp.start:sp.start + sp.length]) \
                == list(range(pos, pos + sp.length))
        # every span's positions are consecutive from its pos0
        for sp in b.seqs:
            assert (b.positions[sp.start:sp.start + sp.length]
                    == sp.pos0 + np.arange(sp.length)).all()


def test_engine_config_ragged_validation():
    base = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=32,
                max_num_seqs=4, ragged_dispatch=True)
    cfg = EngineConfig(**base)
    assert cfg.ragged_max_tokens == 4 + 2 * 64     # auto resolution
    with pytest.raises(ValueError):
        EngineConfig(**base, ragged_max_tokens=3)
    # round 11 retired the spec and pipelined-dispatch refusals: both
    # compose with ragged now (spec spans + the chained-sample merge) —
    # including pipelining WITHOUT a K-step scan (ragged dispatches are
    # single-step)
    EngineConfig(**base, spec_k=2)
    EngineConfig(**base, decode_dispatch_pipeline=True)
    EngineConfig(**base, spec_k=2, decode_dispatch_pipeline=True)
    # on a NON-ragged engine at one step per dispatch the flag is
    # accepted and redundant: that path always keeps a step in flight
    # (tests/test_multistep_decode.py holds it to the same streams)
    assert EngineConfig(max_model_len=128, kv_block_size=8,
                        num_kv_blocks=32, max_num_seqs=4,
                        decode_dispatch_pipeline=True
                        ).decode_steps_per_dispatch == 1
    # the two SURVIVING refusals (docs/ragged_attention.md
    # §composition) must stay loud and must say what composes
    for kw in ({"sp": 2},
               {"pp": 2, "decode_steps_per_dispatch": 4}):
        with pytest.raises(NotImplementedError) as ei:
            EngineConfig(**{**base, **kw})
        msg = str(ei.value)
        assert "ragged_attention.md" in msg and "composes" in msg, (
            f"refusal for {kw} must point at the composition matrix: "
            f"{msg}")


# --------------------------------------------------------------------------
# EngineCore: mixed-batch serving, preemption, replay
# --------------------------------------------------------------------------

def _harness():
    """The test_preemption harness (the test_lane_prefill /
    test_spec_decode import precedent)."""
    from tests.test_preemption import (
        assert_exact_to_recompute_boundary, run_req)
    return assert_exact_to_recompute_boundary, run_req


def _make_core(ragged: bool, num_kv_blocks: int = 64, **kw) -> "object":
    from dynamo_tpu.engine.core import EngineCore
    ecfg = EngineConfig(max_model_len=256, kv_block_size=8,
                        num_kv_blocks=num_kv_blocks, max_num_seqs=2,
                        prefill_buckets=[32, 64, 128],
                        ragged_dispatch=ragged, **kw)
    return EngineCore(TINY, ecfg, attn_impl="xla",
                      param_dtype=jnp.float32)


@pytest.mark.asyncio
async def test_engine_ragged_mixed_serving_bit_exact():
    """Greedy mixed-batch serving: ragged streams must be BIT-exact
    against the split-path reference engine (the test_lane_prefill
    equality precedent — this tiny f32 geometry has no near-tie
    argmaxes, so even the admission boundary token matches) and
    invariant under packing geometry; genuinely mixed dispatches must
    occur."""
    _, run_req = _harness()
    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=17).tolist()

    ref = _make_core(False, decode_steps_per_dispatch=4,
                     lane_prefill_max_tokens=64)
    try:
        r1, _, _ = await run_req(ref, p1, 24, rid="a")
        r2, _, _ = await run_req(ref, p2, 24, rid="b")
    finally:
        await ref.stop()

    rag = _make_core(True, ragged_max_seq_rows=6)
    try:
        (g1, _, rq1), (g2, _, rq2) = await asyncio.gather(
            run_req(rag, p1, 24, rid="a"), run_req(rag, p2, 24, rid="b"))
    finally:
        await rag.stop()
    assert len(g1) == 24 and len(g2) == 24
    assert rag.ragged_dispatches > 0
    assert rag.ragged_mixed_dispatches > 0, (
        "overlapping admissions never produced a mixed "
        "prefill+decode dispatch")
    assert rag.ragged_dispatches_saved > 0
    assert rq1.numeric_boundaries and rq2.numeric_boundaries, (
        "ragged admissions must record their numeric boundary")
    assert g1 == r1, "ragged stream a diverged from the split path"
    assert g2 == r2, "ragged stream b diverged from the split path"

    # packing invariance: a different capacity/row budget must not
    # change a single token (per-row math is packing-independent)
    rag2 = _make_core(True, ragged_max_seq_rows=64)
    try:
        (h1, _, _), (h2, _, _) = await asyncio.gather(
            run_req(rag2, p1, 24, rid="a"),
            run_req(rag2, p2, 24, rid="b"))
    finally:
        await rag2.stop()
    assert h1 == g1 and h2 == g2


@pytest.mark.asyncio
async def test_engine_ragged_seeded_bit_exact():
    """Seeded sampling: the per-(seed, key_step) key discipline holds
    through ragged serving — streams are packing-invariant and match
    the lane-mode engine bit-for-bit (admissions in both derive the
    first token through decode-program math under the same keys)."""
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling

    rng = np.random.default_rng(31)
    p1 = rng.integers(1, TINY.vocab_size, size=21).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=9).tolist()

    async def run_seeded(core, prompt, rid):
        req = EngineRequest(rid=rid, prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.8,
                                                  seed=77),
                            max_new_tokens=16, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await asyncio.wait_for(req.out_queue.get(), 60)
            if item is FINISH_SENTINEL:
                return toks
            toks.append(item)

    streams = []
    for rows in (5, 64):
        core = _make_core(True, ragged_max_seq_rows=rows)
        try:
            s1, s2 = await asyncio.gather(run_seeded(core, p1, "a"),
                                          run_seeded(core, p2, "b"))
        finally:
            await core.stop()
        streams.append((s1, s2))
    assert streams[0] == streams[1]
    # lane-mode reference under the same seeds: the BUSY-admitted
    # request (b, admitted while a decodes) is fully lane-derived in
    # both engines → bit-exact
    ref = _make_core(False, decode_steps_per_dispatch=4,
                     lane_prefill_max_tokens=64)
    try:
        r1, r2 = await asyncio.gather(run_seeded(ref, p1, "a"),
                                      run_seeded(ref, p2, "b"))
    finally:
        await ref.stop()
    assert streams[0][1] == r2


@pytest.mark.asyncio
async def test_engine_ragged_preemption_exact_and_replayable():
    """The test_preemption harness on the ragged path: contention
    forces recompute preemptions; streams stay exact to their recompute
    boundaries, and a synchronous replay of the recorded ragged
    schedule reproduces every harvested token (post-boundary tails are
    NOT waived — the replay covers them)."""
    from dynamo_tpu.engine.replay import (Recorder, check_inputs,
                                          check_log, compare_replay,
                                          replay)
    from dynamo_tpu.llm.protocols.common import FinishReason

    assert_exact_to_recompute_boundary, run_req = _harness()
    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    max_new = 40

    big = _make_core(True, num_kv_blocks=64)
    try:
        ref1, _, _ = await run_req(big, p1, max_new)
        ref2, _, _ = await run_req(big, p2, max_new)
    finally:
        await big.stop()
    assert len(ref1) == max_new

    small = _make_core(True, num_kv_blocks=16)
    small.recorder = Recorder()
    try:
        (g1, r1, q1), (g2, r2, q2) = await asyncio.gather(
            run_req(small, p1, max_new, rid="a"),
            run_req(small, p2, max_new, rid="b"))
        assert r1 == FinishReason.LENGTH and r2 == FinishReason.LENGTH
        assert len(g1) == max_new and len(g2) == max_new
        assert small.preemptions > 0, \
            "contention never triggered preemption"
        assert_exact_to_recompute_boundary(g1, ref1, q1, "a")
        assert_exact_to_recompute_boundary(g2, ref2, q2, "b")
        events = small.recorder.events
        rep = replay(small, events)
        assert compare_replay(events, rep) == []
        assert check_log(events, 8) == []
        assert check_inputs(events) == []
    finally:
        await small.stop()


# --------------------------------------------------------------------------
# EngineCore: ragged × speculative decoding (round 11)
# --------------------------------------------------------------------------


def _repetitive(rng, period=6, reps=5):
    return rng.integers(1, TINY.vocab_size, size=period).tolist() * reps


async def _run_seeded(core, prompt, rid, max_new=16, temperature=0.8,
                      seed=77):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling

    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=temperature,
                                              seed=seed),
                        max_new_tokens=max_new, eos_ids=frozenset())
    await core.submit(req)
    toks = []
    while True:
        item, _ = await asyncio.wait_for(req.out_queue.get(), 120)
        if item is FINISH_SENTINEL:
            return toks
        toks.append(item)


@pytest.mark.asyncio
async def test_engine_ragged_spec_bit_exact_greedy_and_seeded():
    """The acceptance anchor: the ragged×spec stream must be BIT-exact
    vs the NON-ragged spec engine — greedy and seeded — because both
    sample every stream index under the same per-(seed, key_step) keys
    (lockstep PRNG riding the ragged batch). Speculation must actually
    engage (drafts accepted) and draft rows must ride ragged spans."""
    _, run_req = _harness()
    rng = np.random.default_rng(101)
    prompt = _repetitive(rng)

    base = _make_core(False, spec_k=3)
    try:
        ref, _, _ = await run_req(base, prompt, 32, rid="a")
    finally:
        await base.stop()
    rag = _make_core(True, spec_k=3)
    try:
        got, _, _ = await run_req(rag, prompt, 32, rid="a")
        assert rag.spec_dispatches > 0, "speculation never engaged"
        assert rag.spec_accepted_tokens > 0, \
            "repetitive prompt produced zero accepted drafts"
        assert rag.ragged_spec_rows > 0, \
            "no draft rows rode ragged spans"
        assert got == ref, \
            "greedy ragged×spec diverged from the split spec engine"
    finally:
        await rag.stop()

    base = _make_core(False, spec_k=3)
    try:
        ref_s = await _run_seeded(base, prompt, "a")
    finally:
        await base.stop()
    rag = _make_core(True, spec_k=3)
    try:
        got_s = await _run_seeded(rag, prompt, "a")
        assert rag.spec_dispatches > 0
        assert got_s == ref_s, \
            "seeded ragged×spec diverged from the split spec engine"
    finally:
        await rag.stop()


@pytest.mark.asyncio
async def test_engine_ragged_spec_mixed_traffic_and_metrics():
    """Spec spans and prefill lanes in the SAME engine run (the refusal
    this round retired: draft rows and prompt rows sharing ragged
    capacity): streams match the non-ragged spec engine, and the new
    observability fields are live — ragged_spec_rows_total,
    ragged_prefetch_hit_ratio (two concurrent spans chain waves), and
    the flight recorder's per-dispatch spec/prefetch columns."""
    _, run_req = _harness()
    rng = np.random.default_rng(61)
    p1 = _repetitive(rng)
    p2 = _repetitive(rng)

    ref_core = _make_core(False, spec_k=3)
    try:
        r1, _, _ = await run_req(ref_core, p1, 20, rid="a")
        r2, _, _ = await run_req(ref_core, p2, 20, rid="b")
    finally:
        await ref_core.stop()

    rag = _make_core(True, spec_k=3, ragged_max_seq_rows=6)
    try:
        (g1, _, _), (g2, _, _) = await asyncio.gather(
            run_req(rag, p1, 20, rid="a"), run_req(rag, p2, 20, rid="b"))
        assert rag.spec_dispatches > 0 and rag.ragged_spec_rows > 0
        assert g1 == r1, "ragged×spec stream a diverged"
        assert g2 == r2, "ragged×spec stream b diverged"
        m = rag.metrics().to_dict()
        assert m["ragged_spec_rows_total"] == rag.ragged_spec_rows > 0
        assert 0.0 < m["ragged_prefetch_hit_ratio"] <= 1.0, (
            "two concurrent spans never chained a wave prefetch")
        recs = [r for r in rag.flight.dump() if r["kind"] == "ragged"]
        assert recs
        for r in recs:
            assert {"n_spec", "spec_rows", "prefetch_first_waves",
                    "prefetch_hits", "chained"} <= set(r)
        assert any(r["spec_rows"] > 0 for r in recs)
        assert any(r["prefetch_hits"] > 0 for r in recs)
        # wire round trip: the appended fields survive from_dict and
        # old payloads (without them) still decode to zeros
        from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
        assert ForwardPassMetrics.from_dict(m).ragged_spec_rows_total \
            == m["ragged_spec_rows_total"]
        legacy = {k: v for k, v in m.items()
                  if not k.startswith("ragged_prefetch")
                  and not k.startswith("ragged_spec")}
        assert ForwardPassMetrics.from_dict(
            legacy).ragged_prefetch_hit_ratio == 0.0
    finally:
        await rag.stop()


@pytest.mark.asyncio
async def test_engine_ragged_spec_preemption_exact_and_replayable():
    """The acceptance criterion's hard case: ragged×spec under KV
    contention — recompute preemptions fire, streams stay exact to
    their recompute boundaries vs the NON-ragged spec engine, and the
    recorded ragged schedule (row-sampled variant, spec spans and all)
    replays bit-exactly and passes both static checkers."""
    from dynamo_tpu.engine.replay import (Recorder, check_inputs,
                                          check_log, compare_replay,
                                          replay)
    from dynamo_tpu.llm.protocols.common import FinishReason

    assert_exact_to_recompute_boundary, run_req = _harness()
    rng = np.random.default_rng(61)
    p1 = _repetitive(rng)
    p2 = _repetitive(rng)
    max_new = 40

    big = _make_core(False, spec_k=3, num_kv_blocks=64)
    try:
        ref1, _, _ = await run_req(big, p1, max_new)
        ref2, _, _ = await run_req(big, p2, max_new)
    finally:
        await big.stop()
    assert len(ref1) == max_new

    small = _make_core(True, spec_k=3, num_kv_blocks=16)
    small.recorder = Recorder()
    try:
        (g1, r1, q1), (g2, r2, q2) = await asyncio.gather(
            run_req(small, p1, max_new, rid="a"),
            run_req(small, p2, max_new, rid="b"))
        assert r1 == FinishReason.LENGTH and r2 == FinishReason.LENGTH
        assert len(g1) == max_new and len(g2) == max_new
        assert small.preemptions > 0, \
            "contention never triggered preemption"
        assert small.spec_dispatches > 0, "speculation never engaged"
        assert_exact_to_recompute_boundary(g1, ref1, q1, "rspec-a")
        assert_exact_to_recompute_boundary(g2, ref2, q2, "rspec-b")
        events = small.recorder.events
        assert any(e["ev"] == "ragged"
                   and any(m == "spec" for *_x, m in e["seqs"])
                   for e in events), "no spec span was ever recorded"
        rep = replay(small, events)
        assert compare_replay(events, rep) == []
        assert check_log(events, 8) == []
        assert check_inputs(events) == []
    finally:
        await small.stop()


@pytest.mark.asyncio
async def test_engine_ragged_pipelined_dispatch():
    """Ragged × decode_dispatch_pipeline (the other retired refusal):
    steady pure-decode phases chain dispatch N+1 off dispatch N's
    device tokens (the chained-sample merge), streams stay BIT-exact
    vs the unpipelined ragged engine, chained events replay bit-exactly
    through the recorded schedule, and both static checkers pass."""
    from dynamo_tpu.engine.replay import (Recorder, check_inputs,
                                          check_log, compare_replay,
                                          replay)

    _, run_req = _harness()
    rng = np.random.default_rng(23)
    p1 = rng.integers(1, TINY.vocab_size, size=30).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=17).tolist()

    plain = _make_core(True)
    try:
        (a1, _, _), (a2, _, _) = await asyncio.gather(
            run_req(plain, p1, 24, rid="a"),
            run_req(plain, p2, 24, rid="b"))
    finally:
        await plain.stop()

    piped = _make_core(True, decode_dispatch_pipeline=True)
    piped.recorder = Recorder()
    try:
        (b1, _, _), (b2, _, _) = await asyncio.gather(
            run_req(piped, p1, 24, rid="a"),
            run_req(piped, p2, 24, rid="b"))
        assert b1 == a1 and b2 == a2, \
            "pipelined ragged streams diverged from synchronous ragged"
        events = piped.recorder.events
        chained = [e for e in events if e["ev"] == "ragged"
                   and e.get("chained_from") is not None]
        assert chained, "the pipeline never chained a ragged dispatch"
        rep = replay(piped, events)
        assert compare_replay(events, rep) == []
        assert check_log(events, 8) == []
        assert check_inputs(events) == []
    finally:
        await piped.stop()


@pytest.mark.asyncio
async def test_engine_ragged_metrics_and_flight_records():
    """Observability satellite: ForwardPassMetrics carries the ragged
    gauges and the flight recorder logs per-dispatch mode mix."""
    _, run_req = _harness()
    rng = np.random.default_rng(9)
    p1 = rng.integers(1, TINY.vocab_size, size=25).tolist()
    p2 = rng.integers(1, TINY.vocab_size, size=13).tolist()
    core = _make_core(True, ragged_max_seq_rows=6)
    try:
        await asyncio.gather(run_req(core, p1, 10, rid="a"),
                             run_req(core, p2, 10, rid="b"))
        m = core.metrics().to_dict()
        assert 0.0 < m["ragged_fill_ratio"] <= 1.0
        assert 0.0 <= m["ragged_mixed_ratio"] <= 1.0
        assert m["ragged_dispatches_saved_total"] >= 1
        recs = [r for r in core.flight.dump() if r["kind"] == "ragged"]
        assert recs, "no ragged flight records"
        for r in recs:
            assert {"rows", "fill", "prefill_rows", "decode_rows",
                    "mixed"} <= set(r)
        assert any(r["mixed"] for r in recs) == \
            (core.ragged_mixed_dispatches > 0)
    finally:
        await core.stop()
