"""Pallas kernel for one key block of a dense latent-attention prefill chunk:
expand, attend, merge, with nothing but the running state going back to HBM.

Why (PERF.md section 5, PR 37): ``models/mla.py`` ``_dense_chunk`` walks a
prefill chunk's table by key blocks of latent rows. Done as three steps —
an XLA expansion of the block's keys and values ``[KB, H, 192]``, the shared
flash kernel over them (values padded to the keys' width, because it has one
width for both), an XLA merge of the partial result — a 1,024-token chunk
at 16,384 live rows took 13.2 ms a layer on a v5e against 4.8 ms of
operations at the MXU's peak; this kernel took 8.4 (PR 37) and takes 6.7.
A head's queries stay in VMEM while the block's rows stream past them
once: each tile of rows is expanded through the head's slices of ``wkv_b``
on the spot (the expanded keys and values never exist in HBM), scored
against ``q_nope`` and ``q_pe`` separately (128 and 64 lanes: no padding of
the values, no concatenation of the keys), and folded into the running max,
sum and accumulator, which come in from the previous key block and go out
to the next.

Per grid step (head h, query tile i, row tile j):

    c, k_pe = rows[j][:, :rank], rows[j][:, rank:rank+dr]
    k_nope  = c · W_k[h]            # [tk, dn], float32 accumulation
    v       = c · W_v[h]            # [tk, dv]
    for each sub-range a of the tile's queries (Q_SPLIT = 4 of 256 rows):
        s = (q_nope[h, i, a] · k_nopeᵀ + q_pe[h, i, a] · k_peᵀ) · scale
        s = where(key <= query and key < live, s, -inf)   # masked tiles only
        m, l, acc[a] ← online softmax

The grid's last axis runs over the row tiles ("arbitrary": the state is a
scratch carried across it). **A tile has one of three classes, read from
the scalars the kernel holds** (``q_lo``, ``live``, the grid indices, the
tile sizes; no option chooses):

- *interior*: every row live and at or below the tile's FIRST query
  (``(j+1)·tk <= live`` and ``(j+1)·tk - 1 <= q_first``), so every query
  reads every row: scores → max → exp → sum → cast, no mask built or
  applied. Eight of a 1,024-token chunk's nine live tiles at the coding
  cell's mean length (8,636 keys a query), fifteen of sixteen at 16,384.
- *masked*: some row that some query may read, and not interior — the
  tile on the causal diagonal, the tile that holds the live length's edge,
  a tile whose queries sit at negative positions in this key block's frame.
  The same arithmetic with the mask as ONE compare a score (column against
  ``min(row + q_first - k_first, live - k_first - 1)``, a per-row bound)
  and one ``where``; a row that reads nothing yet keeps its state.
- *skipped*: wholly above the diagonal or past the live length: nothing.

Either body is one straight line over the query tile's sub-ranges with
sub-range a+1's score matmuls issued BEFORE sub-range a's softmax: the
bundle scheduler then lays the softmax's vector work under the MXU's (a
sub-range's rows depend on no other's, so the state is updated exactly as
often as in one piece, which is what a split over the KEYS cannot offer).
The results are bit-equal to the one-piece masked body's.

A query tile is as long as the chunk up to ``Q_TILE`` rows, so a chunk of
1,024 expands every row once a head. Row tiles of 1,024 measured 8.4 ms
where 512 took 11.3 and 256 took 18.9 (PR 37, the same chunk; fewer grid
steps and state updates a row); 2,048 and 4,096 read the same as 1,024.

Measured alone on a v5e (PR 60; published widths, bf16, ms a layer for a
1,024-token chunk at 16,384 live rows | the first chunk of a prompt | a
4,096-token bucket from position 0; in brackets the body's bundles from
the compiler's dump, interior / masked; the MXU is busy 7,050-7,280):
PR 37's body 8.32 | 0.88 | 7.97 (9,862); the tile classes 7.34 | 0.88 |
7.57 (8,268 / 9,860); the mask as one compare too 7.28 | 0.82 | 7.32;
**the sub-ranges, this file, 6.69 | 0.78 | 6.97 (7,770 / 7,864)**.
Sub-ranges without the early issue 7.58 (8,502), two / eight of them
issued early 6.78 / 6.72.
Tried and left out: the scale multiplied into the keys (7.22 in one piece,
nothing under the sub-ranges, 7,720 bundles, and the results are no longer
the parent's bit for bit); the diagonal tile by sub-tiles of 512 or 256
(7.28 / 7.56: a skipped sub-tile saves less than the second state update
costs); key sub-tiles inside an interior grid step (7.99 at 512, 14.77 at
256); the running sum kept by lane (7.26); one matmul over
``[k_nope | k_pe]`` (6.54 with the sub-ranges: 2% for a concatenation a
sub-range); with no softmax at all the step reads 6.46.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_prefill_block", "mla_prefill_supported", "Q_TILE", "K_TILE"]

NEG_INF = -1e30
Q_TILE = 1024          # query rows a grid step holds (the whole chunk up to it)
K_TILE = 1024          # latent rows a grid step expands and scores
Q_SPLIT = 4            # query sub-ranges a grid step's body is laid out in
_VMEM_LIMIT = 64 * 1024 * 1024


def mla_prefill_supported(rank: int, dn: int, dr: int, dv: int) -> bool:
    """Widths Mosaic tiles without relayout: the latent and the two head
    widths on whole 128-lane groups, the rope part on sublane groups."""
    return (rank % 128 == 0 and dn % 128 == 0 and dv % 128 == 0
            and dr % 8 == 0)


def _kernel(meta_ref, qn_ref, qp_ref, rows_ref, wk_ref, wv_ref, acc_in,
            ml_in, acc_out, ml_out, m_ref, l_ref, acc_ref, *, tq: int,
            tk: int, rank: int, dr: int, scale: float):
    """meta_ref (SMEM): [q_lo, live] in the key block's frame (row s of the
    block is position s; query t sits at q_lo + t, possibly negative).
    qn_ref [1, tq, dn], qp_ref [1, tq, dr], rows_ref [tk, W], wk_ref
    [1, rank, dn], wv_ref [1, rank, dv]; acc_in/out [1, tq, dv] f32,
    ml_in/out [1, tq, 2] f32 (max, sum)."""
    i, j = pl.program_id(1), pl.program_id(2)
    q_lo, live = meta_ref[0], meta_ref[1]
    q_first, k_first = q_lo + i * tq, j * tk
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        acc_ref[:] = acc_in[0]
        m_ref[:] = ml_in[0, :, 0:1]
        l_ref[:] = ml_in[0, :, 1:2]

    # the query tile in sub-ranges, where they fall on whole sublane groups
    # of the queries' packed rows
    n = Q_SPLIT if tq % (16 * Q_SPLIT) == 0 else 1
    qr = tq // n
    sub_ranges = [slice(a * qr, (a + 1) * qr) for a in range(n)]

    def fold(masked: bool):
        rows = rows_ref[:]
        c, k_pe = rows[:, :rank], rows[:, rank:rank + dr]
        k_nope = jnp.dot(c, wk_ref[0],
                         preferred_element_type=f32).astype(rows.dtype)
        v = jnp.dot(c, wv_ref[0],
                    preferred_element_type=f32).astype(rows.dtype)
        contract_last = (((1,), (1,)), ((), ()))

        def scores(rq: slice):
            return (jax.lax.dot_general(qn_ref[0, rq, :], k_nope,
                                        contract_last,
                                        preferred_element_type=f32)
                    + jax.lax.dot_general(qp_ref[0, rq, :], k_pe,
                                          contract_last,
                                          preferred_element_type=f32)
                    ) * scale

        if masked:
            # key <= query and key < live, as ONE compare: row r of a
            # sub-range reads the tile's columns up to min(r + its first
            # query - k_first, live - k_first - 1)
            col = jax.lax.broadcasted_iota(jnp.int32, (qr, tk), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (qr, 1), 0)
        # one straight line: sub-range a+1's scores are issued before
        # sub-range a's softmax, which then runs under the MXU's work
        s_next = scores(sub_ranges[0])
        for a, rq in enumerate(sub_ranges):
            s = s_next
            if a + 1 < n:
                s_next = scores(sub_ranges[a + 1])
            m_prev = m_ref[rq, :]
            if masked:
                last = jnp.minimum(row + (q_first + a * qr - k_first),
                                   live - k_first - 1)
                s = jnp.where(col <= last, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            if masked:
                # a row with nothing to read yet keeps max NEG_INF, and
                # exp(NEG_INF - NEG_INF) is not 0: subtract 0 there
                p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
            else:
                p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rq, :] = l_ref[rq, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[rq, :] = acc_ref[rq, :] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=f32)
            m_ref[rq, :] = m_new

    # a tile's class, from scalars: wholly live and at or below every query
    # of the tile (no mask) / some key that some query may read (the mask) /
    # neither (nothing)
    interior = (k_first + tk <= live) & (k_first + tk - 1 <= q_first)
    reads = (k_first < live) & (k_first <= q_first + tq - 1)
    pl.when(interior)(functools.partial(fold, False))
    pl.when(reads & jnp.logical_not(interior))(functools.partial(fold, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        acc_out[0] = acc_ref[:]
        ml_out[0, :, 0:1] = m_ref[:]
        ml_out[0, :, 1:2] = l_ref[:]


def mla_prefill_block(q_nope, q_pe, rows, w_k, w_v, acc, ml, *, q_lo, live,
                      scale: float, rank: int, dr: int,
                      interpret: bool = False) -> tuple:
    """One key block folded into a chunk's running attention state.

    q_nope [H, T, dn], q_pe [H, T, dr]: the chunk's queries, head-major
    (T a multiple of 8); rows [KB, W]: the block's latent rows
    ``[c (rank) | k_pe (dr) | pad]`` in the queries' dtype; w_k [H, rank, dn],
    w_v [H, rank, dv]: ``wkv_b`` by head; acc [H, T, dv] float32 and ml
    [H, T, 2] float32 (running max, running sum): the state so far
    (zeros, (NEG_INF, 0) before the first block). q_lo, live: the first
    query's position and the live length, in the block's frame.
    → (acc, ml) with the block's keys folded in; normalise by the sum after
    the last block."""
    H, T, dn = q_nope.shape
    KB, W = rows.shape
    dv = w_v.shape[-1]
    tq = min(Q_TILE, T)
    tk = min(K_TILE, KB)
    assert T % tq == 0 and KB % tk == 0, (T, tq, KB, tk)
    meta = jnp.stack([jnp.asarray(q_lo, jnp.int32),
                      jnp.asarray(live, jnp.int32)])
    head_q = lambda h, i, j, *_: (h, i, 0)         # noqa: E731
    head = lambda h, i, j, *_: (h, 0, 0)           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, T // tq, KB // tk),
        in_specs=[
            pl.BlockSpec((1, tq, dn), head_q),
            pl.BlockSpec((1, tq, dr), head_q),
            pl.BlockSpec((tk, W), lambda h, i, j, *_: (j, 0)),
            pl.BlockSpec((1, rank, dn), head),
            pl.BlockSpec((1, rank, dv), head),
            pl.BlockSpec((1, tq, dv), head_q),
            pl.BlockSpec((1, tq, 2), head_q),
        ],
        out_specs=[pl.BlockSpec((1, tq, dv), head_q),
                   pl.BlockSpec((1, tq, 2), head_q)],
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),      # m
                        pltpu.VMEM((tq, 1), jnp.float32),      # l
                        pltpu.VMEM((tq, dv), jnp.float32)],    # acc
    )
    return tuple(pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, rank=rank, dr=dr,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, T, 2), jnp.float32)],
        # the state is updated in place (operand 0 is the scalar prefetch)
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mla_prefill",
    )(meta, q_nope, q_pe, rows, w_k, w_v, acc, ml))
