"""Pallas kernel for one key block of a dense latent-attention prefill chunk:
expand, attend, merge, with nothing but the running state going back to HBM.

Why (PERF.md section 5, PR 37): ``models/mla.py`` ``_dense_chunk`` walks a
prefill chunk's table by key blocks of latent rows. Done as three steps —
an XLA expansion of the block's keys and values ``[KB, H, 192]``, the shared
flash kernel over them (values padded to the keys' width, because it has one
width for both), an XLA merge of the partial result — a 1,024-token chunk
at 16,384 live rows took 13.2 ms a layer on a v5e against 4.8 ms of
operations at the MXU's peak; this kernel takes 8.4. A head's queries stay
in VMEM while the block's rows stream past them once: each tile of rows is
expanded through the head's slices of ``wkv_b`` on the spot (the expanded
keys and values never exist in HBM), scored against ``q_nope`` and ``q_pe``
separately (128 and 64 lanes: no padding of the values, no concatenation
of the keys), and folded into the running max, sum and accumulator, which
come in from the previous key block and go out to the next.

Per grid step (head h, query tile i, row tile j):

    c, k_pe = rows[j][:, :rank], rows[j][:, rank:rank+dr]
    k_nope  = c · W_k[h]            # [tk, dn], float32 accumulation
    v       = c · W_v[h]            # [tk, dv]
    s       = (q_nope[h, i] · k_nopeᵀ + q_pe[h, i] · k_peᵀ) · scale
    s       = where(key <= query and key < live, s, -inf)
    m, l, acc ← online softmax

The grid's last axis runs over the row tiles ("arbitrary": the state is a
scratch carried across it); a tile wholly above the causal diagonal or past
the live length computes nothing. A query tile is as long as the chunk up to
``Q_TILE`` rows, so a chunk of 1,024 expands every row once a head. Row
tiles of 1,024 measured 8.4 ms where 512 took 11.3 and 256 took 18.9 (the
same chunk; fewer grid steps and state updates a row); 2,048 and 4,096 read
the same as 1,024.
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
    q_first = q_lo + i * tq

    @pl.when(j == 0)
    def _():
        acc_ref[:] = acc_in[0]
        m_ref[:] = ml_in[0, :, 0:1]
        l_ref[:] = ml_in[0, :, 1:2]

    # a tile with a key some query of the tile may read
    @pl.when((j * tk < live) & (j * tk <= q_first + tq - 1))
    def _():
        rows = rows_ref[:]
        c, k_pe = rows[:, :rank], rows[:, rank:rank + dr]
        f32 = jnp.float32
        k_nope = jnp.dot(c, wk_ref[0],
                         preferred_element_type=f32).astype(rows.dtype)
        v = jnp.dot(c, wv_ref[0],
                    preferred_element_type=f32).astype(rows.dtype)
        contract_last = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], k_nope, contract_last,
                                 preferred_element_type=f32)
             + jax.lax.dot_general(qp_ref[0], k_pe, contract_last,
                                   preferred_element_type=f32)) * scale
        kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = (kpos <= qpos) & (kpos < live)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with nothing to read yet: exp(NEG_INF - NEG_INF) is not 0
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=f32)
        m_ref[:] = m_new

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
