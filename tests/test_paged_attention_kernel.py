"""Pallas paged-attention decode kernel vs the XLA reference, exercising
the grid structure the engine tests never reach: multiple grid programs
(B > seqs_per_program), the cross-program wave-parity handoff, group-tail
padding (B not divisible by G), ragged/zero/windowed sequence lengths.

Reference spec being matched: vLLM-style paged attention over block
tables (the reference's lib/llm vendored engines); our block-major layout
is engine/attention.py's own design.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.attention import (paged_attention_pallas,
                                         paged_attention_xla)

B, H, KVH, Dh, BS = 11, 8, 2, 64, 16   # C = 128: pallas-eligible
C = KVH * Dh
NB = 64
M = 8                                  # up to 128 tokens per sequence


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(42)
    k = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB * BS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, NB, size=(B, M)), jnp.int32)
    # ragged: zero-length, one-token, full, and odd lengths mid-batch
    lens = rng.integers(0, M * BS + 1, size=(B,))
    lens[0], lens[1], lens[2] = 0, 1, M * BS
    lens[5] = 0                        # empty sequence between live ones
    seq_lens = jnp.asarray(lens, jnp.int32)
    return q, k, v, tables, seq_lens


@pytest.mark.parametrize("g", [1, 2, 4, 8])
def test_grouped_grid_matches_xla(inputs, g):
    """G=1 is one sequence per program (pure cross-program handoff);
    G=2/4 leave B=11 non-divisible (pad sequences inside the grid);
    G=8 puts the handoff mid-program. All must agree with the XLA path."""
    q, k, v, tables, seq_lens = inputs
    got = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 block_size=BS, scale=Dh ** -0.5,
                                 seqs_per_program=g, interpret=True)
    want = paged_attention_xla(q, k, v, tables, seq_lens,
                               block_size=BS, scale=Dh ** -0.5)
    live = np.asarray(seq_lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("g", [2, 8])
def test_grouped_grid_with_sliding_window(inputs, g):
    """win_lo shifts each sequence's first live chunk (start_ci > 0), so
    the parity handoff must stay consistent for windowed layers too."""
    q, k, v, tables, seq_lens = inputs
    rng = np.random.default_rng(7)
    win_lo = jnp.asarray(rng.integers(-1, 64, size=(B,)), jnp.int32)
    got = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 block_size=BS, scale=Dh ** -0.5,
                                 win_lo=win_lo, seqs_per_program=g,
                                 interpret=True)
    want = paged_attention_xla(q, k, v, tables, seq_lens,
                               block_size=BS, scale=Dh ** -0.5,
                               win_lo=win_lo)
    live = (np.asarray(seq_lens)
            > np.maximum(np.asarray(win_lo) + 1, 0))
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cb", [1, 2, 4])
def test_multi_wave_online_softmax(inputs, cb):
    """Small chunk_blocks force the MULTI-wave branch (online-softmax
    carry, alpha rescale, epilogue divide) that default chunking never
    reaches with M=8 tables. Compared under matmul precision 'highest':
    the default TPU-style bf16 multiply passes wiggle the two impls'
    dots by ~2e-3, which would mask real carry bugs at this tolerance
    (verified f32-highest vs f64: 3e-7)."""
    q, k, v, tables, seq_lens = inputs
    with jax.default_matmul_precision("highest"):
        got = paged_attention_pallas(q, k, v, tables, seq_lens,
                                     block_size=BS, scale=Dh ** -0.5,
                                     chunk_blocks=cb, seqs_per_program=4,
                                     interpret=True)
        want = paged_attention_xla(q, k, v, tables, seq_lens,
                                   block_size=BS, scale=Dh ** -0.5)
    live = np.asarray(seq_lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)


def test_single_wave_chain():
    """Consecutive single-wave sequences: every wave is both a first and
    a last wave, the hardest case for the parity handoff."""
    rng = np.random.default_rng(3)
    nb, m = 16, 1                      # one block per sequence
    k = jnp.asarray(rng.standard_normal((nb * BS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb * BS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((5, H, Dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, size=(5, m)), jnp.int32)
    seq_lens = jnp.asarray([3, 16, 1, 7, 16], jnp.int32)
    got = paged_attention_pallas(q, k, v, tables, seq_lens,
                                 block_size=BS, scale=Dh ** -0.5,
                                 seqs_per_program=2, interpret=True)
    want = paged_attention_xla(q, k, v, tables, seq_lens,
                               block_size=BS, scale=Dh ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_v_aliases_k_mode_matches_double_dma():
    """MQA v-aliases-k mode (MLA latent pools, models/mla.py decode):
    v_lanes skips the v-side DMA and reads v as the first v_lanes lanes
    of each k tile — output must equal the double-DMA kernel mode
    sliced, AND the XLA reference, including ragged/zero lengths."""
    rng = np.random.default_rng(77)
    W, bs, m, b, h, vl = 256, 16, 8, 9, 8, 128
    nb = 48
    pool = jnp.asarray(rng.standard_normal((nb * bs, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, W)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, nb, size=(b, m)), jnp.int32)
    lens = rng.integers(0, m * bs + 1, size=(b,))
    lens[0], lens[1] = 0, m * bs
    seq_lens = jnp.asarray(lens, jnp.int32)
    # 4 sequences a program and waves of 4 blocks (two a full table): a
    # third of the default tiling's unrolled copies to interpret, and the
    # aliased read crosses a wave and a padded tail group besides
    kw = dict(block_tables=tables, seq_lens=seq_lens, block_size=bs,
              scale=0.07, interpret=True, seqs_per_program=4, chunk_blocks=4)
    a = paged_attention_pallas(q, pool, pool, v_lanes=vl, **kw)
    assert a.shape == (b, h, vl)
    ref = paged_attention_pallas(q, pool, pool, **kw)[..., :vl]
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    xla = paged_attention_xla(q, pool, pool,
                              block_tables=tables, seq_lens=seq_lens,
                              block_size=bs, scale=0.07)[..., :vl]
    live = np.asarray(seq_lens) > 0     # zero-length rows: unspecified
    np.testing.assert_allclose(np.asarray(a)[live],
                               np.asarray(xla)[live],
                               rtol=2e-4, atol=2e-4)


def test_v_aliases_k_rejects_bad_geometry():
    pool = jnp.zeros((64 * 16, 256), jnp.float32)
    q = jnp.zeros((2, 8, 128), jnp.float32)           # KVH = 2
    tables = jnp.zeros((2, 4), jnp.int32)
    lens = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="MQA"):
        paged_attention_pallas(q, pool, pool, v_lanes=128,
                               block_tables=tables, seq_lens=lens,
                               block_size=16, scale=1.0, interpret=True)
    q1 = jnp.zeros((2, 8, 256), jnp.float32)          # KVH = 1
    with pytest.raises(ValueError, match="128-aligned"):
        paged_attention_pallas(q1, pool, pool, v_lanes=100,
                               block_tables=tables, seq_lens=lens,
                               block_size=16, scale=1.0, interpret=True)


def _sectioned_reference(q, pool, tables, seq_lens, bs, scale, sections):
    """Gather + host-side sectioned dequant + masked softmax, in numpy:
    what the kernel's quant_sections form must give."""
    from dynamo_tpu.engine.attention import dequant_kv_rows_sections
    rank, dr = sections
    b, m = tables.shape
    Wq = q.shape[-1]
    deq = np.asarray(dequant_kv_rows_sections(
        pool[:, :rank + dr + 128], sections, jnp.float32))
    qf = np.asarray(q, np.float32)
    idx = np.asarray(tables)[:, :, None] * bs + np.arange(bs)[None, None]
    idx = idx.reshape(b, -1)
    k = deq[idx]                                       # [b, T, rank + dr]
    kq = np.pad(k, ((0, 0), (0, 0), (0, Wq - rank - dr)))
    scores = np.einsum("bhw,btw->bht", qf, kq) * scale
    mask = np.arange(m * bs)[None, :] < np.asarray(seq_lens)[:, None]
    scores = np.where(mask[:, None, :], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bht,btr->bhr", p, k[..., :rank])


def test_sectioned_int8_kernel_mode_matches_reference():
    """quant_sections (int8 MLA pools): in-kernel per-section dequant +
    v-aliases-k must equal the host-side sectioned dequant reference —
    the path models/mla.py decode takes on TPU for int8 latent pools."""
    from dynamo_tpu.engine.attention import quantize_kv_rows_sections
    rng = np.random.default_rng(88)
    rank, dr = 128, 64                  # sum 192 -> q width 256, row 384
    Wq, bs, m, b, h = 256, 32, 4, 6, 8
    nb = 32
    vals = np.concatenate(
        [rng.standard_normal((nb * bs, rank)).astype(np.float32),
         rng.standard_normal((nb * bs, dr)).astype(np.float32) * 15.0],
        axis=1)                          # skewed k_pe, the MLA reality
    enc = np.asarray(quantize_kv_rows_sections(jnp.asarray(vals),
                                               (rank, dr)))
    pool = jnp.asarray(np.pad(enc, ((0, 0), (0, 384 - enc.shape[1]))))
    assert pool.shape[1] == 384 and pool.dtype == jnp.int8
    q = jnp.asarray(rng.standard_normal((b, h, Wq)).astype(np.float32)
                    * 0.3, jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, nb, size=(b, m)), jnp.int32)
    lens = rng.integers(1, m * bs + 1, size=(b,))
    seq_lens = jnp.asarray(lens, jnp.int32)
    got = paged_attention_pallas(
        q, pool, pool, tables, seq_lens, block_size=bs, scale=0.05,
        v_lanes=rank, quant_sections=(rank, dr), interpret=True)
    assert got.shape == (b, h, rank)

    want = _sectioned_reference(q, pool, tables, seq_lens, bs, 0.05,
                                (rank, dr))
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)  # bf16 q rounding


# ---------------------------------------------------------------------------
# rows > 1: the rows a sequence scores in one step share one pass
# ---------------------------------------------------------------------------

RBS, RM, RCB = 8, 12, 2            # block 8, <= 96 keys, waves of 16 keys
# what each sequence of the one call exercises (its length as the LAST row
# sees it; the window variant reads the last 20 positions of every row, from
# the sequence's start where it has no more: a bound that does not slide)
ROW_SEQS = {
    "boundary_inside_a_block": 13,        # rows at 11, 12 (| 10, 11, 12)
    "boundary_across_blocks": 17,         # the last row opens a block, a wave
    "one_wave": 16,
    "many_waves": 96,                     # six waves, the table's end
    "shorter_than_rows": 1,               # an idle slot's trash rows
}                                         # 5 sequences, 2 a program: a
                                          # padded tail group


@functools.cache
def _rows_case(rows: int, window: bool):
    """→ (got [B, R, H, Dv], want, live [B, R]): ONE call of the kernel with
    ``rows`` queries a sequence, and the parent's call with every row a
    sequence of its own (in XLA, as tier 1 holds the kernel everywhere: the
    two kernels' outputs were bit-equal when this was written, at four times
    the seconds). ``window``: a lower bound a row (its last 20 positions)
    and a sink. Per-block copies: the wave walk is not what ``rows``
    touches, and interpreting both of its branches doubles the seconds."""
    rng = np.random.default_rng(100 + rows)
    nb, b = 40, len(ROW_SEQS)
    k = jnp.asarray(rng.standard_normal((nb * RBS, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb * RBS, C)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b * rows, H, Dh)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, size=(b, RM)), jnp.int32)
    lens = jnp.asarray(list(ROW_SEQS.values()), jnp.int32)
    back = jnp.tile(jnp.arange(rows - 1, -1, -1, dtype=jnp.int32), b)
    kw = dict(block_size=RBS, scale=Dh ** -0.5)
    row_lens = jnp.repeat(lens, rows) - back
    if window:
        kw["sink"] = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
        kw["win_lo"] = jnp.maximum(row_lens - 1 - 20, -1)
    got = paged_attention_pallas(
        q, k, v, tables, lens, rows=rows, chunk_blocks=RCB,
        seqs_per_program=2, coalesce=False, interpret=True, **kw)
    want = paged_attention_xla(q, k, v, jnp.repeat(tables, rows, axis=0),
                               row_lens, **kw)
    live = np.asarray(row_lens).reshape(b, rows) > 0
    shape = (b, rows, H, Dh)
    return (np.asarray(got).reshape(shape), np.asarray(want).reshape(shape),
            live)


@pytest.mark.parametrize("window", [False, True],
                         ids=["full", "window_and_sink"])
@pytest.mark.parametrize("seq", list(ROW_SEQS))
@pytest.mark.parametrize("rows", [2, 3])
def test_rows_of_a_sequence_share_one_pass(rows, seq, window):
    """``rows`` = R queries a sequence (q [B·R, H, Dh], the tables and
    lengths a SEQUENCE, the last row's; the lower bounds a row) give what
    the parent's call gives with every row a sequence of its own (tables
    repeated, each row's own length and lower bound): each wave fetched
    once, the mask a row's own. A row that sees no key (a length under R: an
    idle slot) is finite."""
    got, want, live = _rows_case(rows, window)
    i = list(ROW_SEQS).index(seq)
    assert live[i].sum() == min(rows, ROW_SEQS[seq])
    np.testing.assert_allclose(got[i][live[i]], want[i][live[i]],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(got[i]).all()


def test_rows_are_refused_where_no_read_has_them():
    """v-aliases-k and int8 pools have no step of several rows a sequence,
    the XLA gather nothing to share: each says so."""
    from dynamo_tpu.engine.attention import paged_attention
    pool = jnp.zeros((64 * 16, 256), jnp.float32)
    q = jnp.zeros((4, 8, 256), jnp.float32)
    tables, lens = jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)
    kw = dict(block_tables=tables, seq_lens=lens, block_size=16, scale=1.0)
    with pytest.raises(ValueError, match="rows=2"):
        paged_attention_pallas(q, pool, pool, v_lanes=128, rows=2,
                               interpret=True, **kw)
    with pytest.raises(ValueError, match="whole number"):
        paged_attention_pallas(q[:3], pool, pool, rows=2, interpret=True,
                               **kw)
    with pytest.raises(ValueError, match="a sequence a row"):
        paged_attention(q, pool, pool, tables, lens, block_size=16,
                        scale=1.0, impl="xla", rows=2)


# ---------------------------------------------------------------------------
# The looped body: one traced sequence and one traced block copy serve every
# form the cells read through
# ---------------------------------------------------------------------------

FB, FM, FCB = 11, 8, 2      # 11 sequences at the default 8 a program: the
                            # last group pads with 5 of no length; waves of
                            # 2 blocks, up to 4 a sequence
FORMS = ["bf16", "int8", "v_lanes", "quant_sections", "v_dim", "sink",
         "win_lo", "rows2"]


def _form_tables(kind: str, rng, nb: int, b: int = FB):
    if kind == "contiguous":
        return np.stack([1 + (i * FM) % (nb - FM - 1) + np.arange(FM)
                         for i in range(b)]).astype(np.int32)
    return rng.integers(1, nb, size=(b, FM)).astype(np.int32)


@functools.cache
def _form_case(form: str, tables_kind: str):
    """→ (call(coalesce) → got, want, live, tolerance): the kernel's
    arguments in one of the forms a cell serves, and the gather's answer to
    the same call (once for both settings of ``coalesce``)."""
    from dynamo_tpu.engine.attention import (quantize_kv_rows,
                                             quantize_kv_rows_sections)
    rng = np.random.default_rng(570 + FORMS.index(form))
    bs = 32 if form in ("int8", "quant_sections") else BS
    nb = 48
    tables = jnp.asarray(_form_tables(tables_kind, rng, nb))
    lens = rng.integers(1, FM * bs + 1, size=(FB,))
    lens[0], lens[1], lens[2], lens[5] = 0, 1, FM * bs, 0
    seq_lens = jnp.asarray(lens, jnp.int32)
    live = lens > 0
    kw = dict(block_size=bs, scale=Dh ** -0.5)
    pkw, tol = {}, 2e-5
    q = jnp.asarray(rng.standard_normal((FB, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((nb * bs, C)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb * bs, C)), jnp.float32)
    ref = None
    if form == "bf16":      # the served dtypes; the gather on their values
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = paged_attention_xla(*(x.astype(jnp.float32) for x in (q, k, v)),
                                  tables, seq_lens, **kw)
        tol = 1e-2          # the kernel's bfloat16 output
    elif form == "int8":
        k = v = quantize_kv_rows(k * 3.0)
        ref = paged_attention_xla(q, k, v, tables, seq_lens, kv_heads=KVH,
                                  **kw)
    elif form == "v_lanes":             # a 256-lane one-head row, v its
        k = v = jnp.asarray(            # first 128 lanes
            rng.standard_normal((nb * bs, 256)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((FB, H, 256)), jnp.float32)
        kw["scale"], pkw["v_lanes"], tol = 0.07, 128, 2e-4
        ref = paged_attention_xla(q, k, v, tables, seq_lens, **kw)[..., :128]
    elif form == "quant_sections":      # rank 128 | rope 64, int8 rows
        sections = (128, 64)
        vals = np.concatenate(
            [rng.standard_normal((nb * bs, 128)).astype(np.float32),
             rng.standard_normal((nb * bs, 64)).astype(np.float32) * 15.0],
            axis=1)
        enc = np.asarray(quantize_kv_rows_sections(jnp.asarray(vals),
                                                   sections))
        k = v = jnp.asarray(np.pad(enc, ((0, 0), (0, 384 - enc.shape[1]))))
        q = jnp.asarray(rng.standard_normal((FB, H, 256)).astype(np.float32)
                        * 0.3, jnp.bfloat16)
        kw["scale"], tol = 0.05, 2e-2   # bf16 q rounding
        pkw.update(v_lanes=128, quant_sections=sections)
        ref = _sectioned_reference(q, k, tables, seq_lens, bs, 0.05, sections)
    elif form == "v_dim":               # value heads twice the keys' size
        v = jnp.asarray(rng.standard_normal((nb * bs, KVH * 128)),
                        jnp.float32)
        kw["v_dim"] = 128
    elif form == "sink":
        kw["sink"] = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    elif form == "win_lo":
        # lower bounds up to 100: past wave 0 (32 keys) and wave 2 for some
        win_lo = rng.integers(-1, 100, size=(FB,))
        win_lo[2], win_lo[3] = 70, -1   # the full table read from wave 2 on
        kw["win_lo"] = jnp.asarray(win_lo, jnp.int32)
        live = lens > np.maximum(win_lo + 1, 0)
        assert (win_lo[live] >= FCB * bs).any()
    elif form == "rows2":               # two rows a sequence, one pass
        q = jnp.asarray(rng.standard_normal((FB * 2, H, Dh)), jnp.float32)
        row_lens = jnp.repeat(seq_lens, 2) - jnp.tile(
            jnp.asarray([1, 0], jnp.int32), FB)
        ref = paged_attention_xla(q, k, v, jnp.repeat(tables, 2, axis=0),
                                  row_lens, **kw)
        pkw["rows"] = 2
        live = np.asarray(row_lens) > 0
    if ref is None:
        ref = paged_attention_xla(q, k, v, tables, seq_lens, **kw)

    def call(coalesce: bool):
        return paged_attention_pallas(
            q, k, v, tables, seq_lens, chunk_blocks=FCB, coalesce=coalesce,
            interpret=True, **kw, **pkw)
    return call, np.asarray(ref, np.float32), live, tol


@pytest.mark.parametrize("coalesce", [True, False],
                         ids=["coalesced", "per_block"])
@pytest.mark.parametrize("tables_kind", ["contiguous", "fragmented"])
@pytest.mark.parametrize("form", FORMS)
def test_looped_body_equals_the_gather(form, tables_kind, coalesce):
    """The kernel's body is traced once a program and its block copies
    once a wave (two loops on the device): every form a cell reads
    through — pool dtype, aliased or narrower or wider values, a sink, a
    window that starts past wave 0, two rows a sequence — over tables that
    take the one-copy and the per-block path, in a batch whose last group
    pads with sequences of no length, equals the XLA gather."""
    call, want, live, tol = _form_case(form, tables_kind)
    got = np.asarray(call(coalesce), np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr its equations hold."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


@pytest.mark.parametrize("shape", ["chat-open", "latent-chunk-64"])
def test_the_body_is_traced_once_whatever_the_group_and_the_wave(shape):
    """What a served process traces at every start: the kernel at 8
    sequences a program and 16 (64) blocks a wave holds no more than 1.5
    times the equations it holds at one sequence and one block (7.2 times
    while the group and a wave's copies unrolled in Python)."""
    if shape == "chat-open":            # mistral-7b: B 64, H 32, KVH 8
        b, h, dh, lanes, m, deep, kw = 64, 32, 128, 1024, 256, 16, {}
    else:                               # kimi-k2's latent row, v its 512
        b, h, dh, lanes, m, deep, kw = 16, 64, 640, 640, 1600, 64, {
            "v_lanes": 512}

    def equations(group: int, chunk: int) -> int:
        s = jax.ShapeDtypeStruct
        pool = s((2048 * 16, lanes), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(functools.partial(
            paged_attention_pallas, block_size=16, scale=0.1,
            seqs_per_program=group, chunk_blocks=chunk, **kw))(
                s((b, h, dh), jnp.bfloat16), pool, pool,
                s((b, m), jnp.int32), s((b,), jnp.int32))
        return _equations(jaxpr.jaxpr)

    assert equations(8, deep) <= 1.5 * equations(1, 1)
