"""Pallas index scores of a decode step, read straight from the pool.

DeepSeek Sparse Attention scores every live position of a sequence with
the lightning indexer, ``I[n, s] = Σ_j w[n, j] · relu(qI[n, j] · k[s])``,
before it keeps the ``index_topk`` best (``models/mla.py`` ``_select``).
The XLA form gathers the index keys by block into a ``[B, S, dI]`` copy
(``mla._keys_by_block``: one 4 KB copy per block, 69,632 a layer at 64
slots of 17,408 positions, bound by the copies issued and not by their
bytes) and reads the copy back for the dots. Here the keys stream from
the pool as it lies into VMEM by waves, and only the ``[B, S]`` float32
scores are written (PERF.md section 6, PR 36).

The read is the wave walk of ``attention._paged_attn_kernel`` in its
one-stream form: per sequence, waves ``[0, ceil(blocks / chunk))``
double-buffered through ``attention._make_wave_dma``; a wave whose blocks
``attention.wave_contig_table`` finds physically consecutive (the
run-tracking allocator lands a shared document as one run) is ONE copy,
any other wave ``chunk`` per-block copies, with the same bytes in the same
buffer either way. The last wave of a sequence starts the first wave of
the next, across programs too. No read is shared between sequences that
hold the same document.

Per wave: ``[J, dI] @ [dI, chunk·bsz]`` on the MXU in the pool's dtype
with float32 accumulation, relu, times ``w[n, :, None]``, summed over J:
the arithmetic of ``mla._index_scores``; only the order of the float32 sum
over J may differ. A position the sequence does not own scores 0 (it is
not read where its whole wave lies past the sequence's end), so the
result is the same whatever the pool holds there; ``_select``'s ``live``
mask excludes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (ATTN_SEQS_PER_PROGRAM, _make_wave_dma,
                        wave_contig_table)

__all__ = ["index_scores_pallas", "index_scores_supported",
           "key_wave_blocks"]

# a wave of index keys in VMEM: deep enough that a contiguous wave is one
# long copy (a 4 KB block a copy is bound by the copies issued), small
# enough that two of them and the wave's [J, chunk·bsz] float32 dots sit
# well inside the scoped VMEM of every generation
KEY_WAVE_BYTES = 256 * 1024


def key_wave_blocks(table_blocks: int, block_size: int, lanes: int,
                    itemsize: int = 2) -> int:
    """Blocks per DMA wave of the index-key read: the largest divisor of
    the table's length whose wave stays within ``KEY_WAVE_BYTES`` (a
    divisor, so the waves tile the table and the result needs no pad).
    From the row's width and the table alone: 64 blocks of 16 rows of 128
    bf16 lanes at 1,088 blocks (17 waves a sequence)."""
    most = max(1, KEY_WAVE_BYTES // (block_size * lanes * itemsize))
    return max(d for d in range(1, min(most, table_blocks) + 1)
               if table_blocks % d == 0)


def index_scores_supported(heads: int, lanes: int, block_size: int) -> bool:
    """Whether the kernel builds for the chip at this geometry: key rows
    on the 128-lane tiling, the index heads on the 8-sublane one, blocks
    on bf16's 16-row tile. Tiny test geometries take the XLA form."""
    return lanes % 128 == 0 and heads % 8 == 0 and block_size % 16 == 0


def _index_scores_kernel(tables_ref, seq_lens_ref, runs_ref,
                         q_ref, w_ref, k_hbm, o_ref,
                         k_bufs, sems, wave_ref,
                         *, block_size: int, chunk: int, num_seqs: int,
                         seqs_per_program: int):
    """q_ref [G, J, dI]; w_ref [G, J, 1] float32; k_hbm [rows, dI] (HBM);
    o_ref [G, n_waves, chunk·block_size] float32; k_bufs
    [2, chunk·block_size, dI]; wave_ref [1] SMEM: the parity of the next
    wave's buffer, carried across sequences and programs."""
    pb = pl.program_id(0)
    cbs = chunk * block_size
    wave_dma = _make_wave_dma(
        tables_ref, runs_ref, k_hbm, None, k_bufs, None, sems,
        block_size=block_size, chunk=chunk, v_lanes=0, coalesce=True)

    def seq_shape(bi):
        nb = (seq_lens_ref[bi] + block_size - 1) // block_size
        return nb, (nb + chunk - 1) // chunk

    @pl.when(pb == 0)
    def _():
        wave_ref[0] = 0

    def sequence(s, _):
        """One sequence of the program's group. A loop, not an unrolled
        walk: every wave_dma site below is `chunk` + 1 copy descriptors
        to trace, and a served process traces them at every start."""
        sq = pb * seqs_per_program + s
        num_blocks, num_chunks = seq_shape(sq)
        seq_len = seq_lens_ref[sq]
        p0 = wave_ref[0]
        # the first wave was started by the predecessor's last one, unless
        # there is none or it had no wave
        _, prev_nc = seq_shape(jnp.maximum(sq - 1, 0))
        nsq = jnp.minimum(sq + 1, num_seqs - 1)
        next_nb, next_nc = seq_shape(nsq)
        has_next = (sq + 1 < num_seqs) & (next_nc > 0)

        @pl.when((num_chunks > 0) & ~((sq > 0) & (prev_nc > 0)))
        def _():
            wave_dma("start", sq, 0, jax.lax.rem(p0, 2), num_blocks)

        # waves past the sequence's end are not read: they score 0
        o_ref[s] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        q = q_ref[s]                                       # [J, dI]
        w = w_ref[s]                                       # [J, 1]

        def wave(ci, _):
            slot = jax.lax.rem(p0 + ci, 2)
            last = ci + 1 >= num_chunks

            @pl.when(~last | has_next)
            def _():   # the next wave, or the successor's first
                wave_dma("start", jnp.where(last, nsq, sq),
                         jnp.where(last, 0, ci + 1), 1 - slot,
                         jnp.where(last, next_nb, num_blocks))

            wave_dma("wait", sq, ci, slot, num_blocks)
            dots = jax.lax.dot_general(
                q, k_bufs[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [J, cbs]
            row = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0,
                          keepdims=True)                   # [1, cbs]
            pos = ci * cbs + jax.lax.broadcasted_iota(
                jnp.int32, row.shape, dimension=1)
            o_ref[s, pl.ds(ci, 1), :] = jnp.where(pos < seq_len, row, 0.0)
            return 0

        jax.lax.fori_loop(0, num_chunks, wave, 0)
        wave_ref[0] = jax.lax.rem(p0 + num_chunks, 2)
        return 0

    jax.lax.fori_loop(0, seqs_per_program, sequence, 0)


def index_scores_pallas(qI: jax.Array, w: jax.Array, idx_flat: jax.Array,
                        tables_l: jax.Array, seq_lens: jax.Array, *,
                        block_size: int, chunk_blocks: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """qI [B, J, dI] index queries, w [B, J] float32 head weights,
    idx_flat [rows, dI] the index-key pool of every layer as it lies,
    tables_l [B, M] block ids into it (layer offset applied), seq_lens [B]
    live positions. → float32 [B, M·block_size]: the index score of every
    position a sequence owns, 0 elsewhere. ``chunk_blocks`` (a divisor of
    M) is for measurements; the depth is ``key_wave_blocks``'s."""
    B, J, dI = qI.shape
    M = tables_l.shape[1]
    chunk = (key_wave_blocks(M, block_size, dI, idx_flat.dtype.itemsize)
             if chunk_blocks is None else chunk_blocks)
    assert M % chunk == 0, (M, chunk)
    n_waves, cbs = M // chunk, chunk * block_size
    G = min(ATTN_SEQS_PER_PROGRAM, B)
    pad = -B % G
    Bp = B + pad
    if pad:            # sequences of no length: no wave, a row of zeros
        qI = jnp.pad(qI, ((0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        tables_l = jnp.pad(tables_l, ((0, pad), (0, 0)))
        seq_lens = jnp.pad(seq_lens, (0, pad))
    # from the SAME tables the kernel reads, inside the jitted step
    runs = wave_contig_table(tables_l, seq_lens, block_size=block_size,
                             chunk=chunk,
                             pool_blocks=idx_flat.shape[0] // block_size)

    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, block_size=block_size,
                          chunk=chunk, num_seqs=Bp, seqs_per_program=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp // G,),
            in_specs=[
                pl.BlockSpec((G, J, dI), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((G, J, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.ANY),  # keys stay in HBM
            ],
            out_specs=pl.BlockSpec((G, n_waves, cbs),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, cbs, dI), idx_flat.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # cross-program wave parity
            ]),
        out_shape=jax.ShapeDtypeStruct((Bp, n_waves, cbs), jnp.float32),
        interpret=interpret,
        name="index_scores",
    )(tables_l.astype(jnp.int32), seq_lens.astype(jnp.int32), runs,
      qI.astype(idx_flat.dtype), w.astype(jnp.float32)[..., None], idx_flat)
    return out[:B].reshape(B, M * block_size)
