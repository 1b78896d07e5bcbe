"""Attention for the TPU engine: prefill (dense causal) + paged decode.

TPU-native replacement for the engine-side attention the reference delegates
to vLLM/TRT-LLM (paged attention over KV block tables; the reference's KV
block layout is kv/layer.rs `[kv, blocks, block_size, heads, head_size]`).

Our canonical KV-cache layout is BLOCK-MAJOR: `[NTOK, KVH*Dh]` per layer
where `NTOK = num_blocks * block_size` is a flat paged token pool and every
kv head's vector sits side by side in lanes (see the decode section header
for the full rationale).

Two decode implementations with identical semantics:
- `paged_attention_xla`: gather + masked softmax, runs everywhere (CPU tests).
- `paged_attention_pallas`: flash-style streaming kernel over the block table
  with scalar-prefetched indices (TPU; `interpret=True` for CPU testing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# int8 KV pools carry per-token scales IN-ROW as two extra int8 lanes
# (exponent at lane C, mantissa at C+1; scale = 2^e·(1+m/256)), padded to
# one 128-lane group so rows stay lane-aligned. Rationale: TPU DMA slices
# must be tile-aligned — int8 memrefs tile at (32, 128), f32 at (8, 128)
# — so a separate per-token scale array cannot be block-DMA'd (Mosaic
# rejects sub-tile slices; measured on v5e). quantize_kv_rows /
# dequant_kv_rows below are the encoding's single home.
KV_SCALE_LANES = 128

# The paged kernels' tiling, shared by the decode and ragged kernels, the
# host-side DMA counters that mirror their wave walk, and ragged_supported's
# VMEM budget. Callers that sweep pass chunk_blocks= / seqs_per_program=,
# which key the compile cache.
# DMA wave depth in blocks: 16 = 256 tokens a wave at block size 16. Deeper
# waves amortize the per-wave DMA issue cost at long sequences (16 beat 8
# by 1-2 ms a step at seq 512-1024, llama-1B shapes, on the setup before
# PR 25; not re-measured on this chip).
ATTN_CHUNK_BLOCKS = 16
# sequences per grid program of the decode kernel: amortizes the
# per-program fixed costs (_paged_attn_kernel's docstring)
ATTN_SEQS_PER_PROGRAM = 8


def kv_value_lanes(k_cache: jax.Array) -> int:
    """C (= KVH·Dh value lanes) of a pool row, minus the in-row scale
    group when the pool is int8-quantized."""
    lanes = k_cache.shape[-1]
    return lanes - KV_SCALE_LANES if k_cache.dtype == jnp.int8 else lanes


def _encode_scale(absmax: jax.Array):
    """absmax -> (e int8-ready, m 0..255, scale f32): scale =
    2^e·(1+m/256) ≈ absmax/127 (within 2^-9 relative). THE one home of
    the (e, m) encode — both row writers call it."""
    target = jnp.maximum(absmax, 1e-30) / 127.0
    e = jnp.floor(jnp.log2(target))
    m = jnp.clip(jnp.round((target / jnp.exp2(e) - 1.0) * 256.0), 0, 255)
    return e, m, jnp.exp2(e) * (1.0 + m / 256.0)


def _decode_scale(e_lane: jax.Array, m_lane: jax.Array) -> jax.Array:
    """Inverse of _encode_scale from the stored int8 lanes (m is stored
    uint8-wrapped; mask with & 0xFF). THE one home of the decode."""
    e = e_lane.astype(jnp.float32)
    m = (m_lane.astype(jnp.int32) & 0xFF).astype(jnp.float32)
    return jnp.exp2(e) * (1.0 + m / 256.0)


def quantize_kv_rows(x: jax.Array, groups: int = 1) -> jax.Array:
    """Per-row int8 with in-row (e, m) scale lanes: x [N, C] ->
    int8 [N, C + KV_SCALE_LANES]. scale = 2^e·(1+m/256) ≈ absmax/127
    (within 2^-9 relative). One home for the encoding; the kernel's
    dequant_tile and dequant_kv_rows below are its readers.

    ``groups=g`` (tp-sharded pools, parallel/sharding.kv_pspecs): the row
    is g independent (values, scales) sections — [N, g*(C/g +
    KV_SCALE_LANES)] — so sharding the lane axis into g equal chunks
    gives every tp shard whole sections; each shard's local view is
    exactly the groups=1 encoding over its own KV heads. Under pjit the
    per-group absmax needs no cross-shard collective. groups=1 is
    bit-identical to the ungrouped encoding."""
    N, C = x.shape
    xf = x.astype(jnp.float32).reshape(N, groups, C // groups)
    e, m, scale = _encode_scale(jnp.max(jnp.abs(xf), axis=2))
    q = jnp.clip(jnp.round(xf / scale[:, :, None]),
                 -127, 127).astype(jnp.int8)
    pad = jnp.zeros((N, groups, KV_SCALE_LANES), jnp.int8)
    pad = pad.at[:, :, 0].set(jnp.clip(e, -127, 127).astype(jnp.int8))
    # m 0..255 stored as wrapped int8; readers mask with & 0xFF
    pad = pad.at[:, :, 1].set(m.astype(jnp.uint8).astype(jnp.int8))
    rows = jnp.concatenate([q, pad], axis=2)
    return rows.reshape(N, groups * (C // groups + KV_SCALE_LANES))


def quantize_kv_rows_sections(x: jax.Array,
                              sections: tuple) -> jax.Array:
    """Per-row int8 with one independent (e, m) scale pair per UNEQUAL
    section, all sharing the single KV_SCALE_LANES pad: x [N, C] ->
    int8 [N, C + KV_SCALE_LANES], section i's scale at pad lanes
    (2i, 2i+1). Built for MLA latent rows, where the RMSNorm-bounded
    c_kv (rank lanes) and the UNNORMALIZED post-rope k_pe (rope lanes)
    can differ in magnitude by 10-50x on real checkpoints — a shared
    absmax would leave the smaller section a handful of int8 levels.
    sections=(C,) is bit-identical to quantize_kv_rows(x). The MLA pool
    never lane-shards (it replicates under tp), so no per-shard section
    alignment applies."""
    N, C = x.shape
    assert sum(sections) == C and 2 * len(sections) <= KV_SCALE_LANES
    xf = x.astype(jnp.float32)
    pad = jnp.zeros((N, KV_SCALE_LANES), jnp.int8)
    qs = []
    off = 0
    for i, w in enumerate(sections):
        seg = xf[:, off:off + w]
        off += w
        e, m, scale = _encode_scale(jnp.max(jnp.abs(seg), axis=1))
        qs.append(jnp.clip(jnp.round(seg / scale[:, None]),
                           -127, 127).astype(jnp.int8))
        pad = pad.at[:, 2 * i].set(
            jnp.clip(e, -127, 127).astype(jnp.int8))
        pad = pad.at[:, 2 * i + 1].set(m.astype(jnp.uint8).astype(jnp.int8))
    return jnp.concatenate(qs + [pad], axis=1)


def dequant_kv_rows_sections(rows: jax.Array, sections: tuple,
                             out_dtype) -> jax.Array:
    """Inverse of quantize_kv_rows_sections for gathered rows
    [..., sum(sections) + KV_SCALE_LANES]."""
    C = sum(sections)
    pad = rows[..., C:]
    outs = []
    off = 0
    for i, w in enumerate(sections):
        scale = _decode_scale(pad[..., 2 * i], pad[..., 2 * i + 1])
        outs.append(rows[..., off:off + w].astype(jnp.float32)
                    * scale[..., None])
        off += w
    return jnp.concatenate(outs, axis=-1).astype(out_dtype)


def kv_row_groups(lanes: int, C: int) -> int:
    """Scale-group count of an int8 pool row: lanes = C + g·SCALE_LANES
    (g = the tp shard count the pool was built for; llama.init_kv_cache
    kv_shards)."""
    g = (lanes - C) // KV_SCALE_LANES
    if g < 1 or C + g * KV_SCALE_LANES != lanes or (g > 1 and C % g != 0):
        raise ValueError(
            f"int8 pool row width {lanes} does not decompose as value "
            f"lanes C={C} plus whole {KV_SCALE_LANES}-lane scale groups")
    return g


def dequant_kv_rows(rows: jax.Array, C: int, out_dtype) -> jax.Array:
    """Inverse of quantize_kv_rows for gathered rows
    [..., C + g·SCALE_LANES]; the group count is inferred from the row
    width (kv_row_groups)."""
    g = kv_row_groups(rows.shape[-1], C)
    lead = rows.shape[:-1]
    r = rows.reshape(lead + (g, rows.shape[-1] // g))
    cg = C // g
    scale = _decode_scale(r[..., cg], r[..., cg + 1])
    vals = r[..., :cg].astype(jnp.float32) * scale[..., None]
    return vals.reshape(lead + (C,)).astype(out_dtype)


def softcap_scores(scores: jax.Array, cap) -> jax.Array:
    """Gemma2 logit soft-capping: cap·tanh(x/cap) — the single home of the
    formula, shared by prefill, both decode impls, and the lm head."""
    return cap * jnp.tanh(scores / cap)


# ---------------------------------------------------------------------------
# Prefill: dense causal attention (optionally against a KV prefix from cache)
# ---------------------------------------------------------------------------


def sink_softmax(scores: jax.Array, sink: jax.Array | None) -> jax.Array:
    """softmax over the last axis; with ``sink`` (broadcastable to
    scores[..., 0]) the denominator also holds exp(sink): a learned scalar
    a query head that takes mass and adds no value (mimo_v2's window
    layers). It is no extra key: the result keeps scores' shape and its
    rows sum to less than 1."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    sink = sink.astype(scores.dtype)[..., None]
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    p = jnp.exp(scores - m)
    return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     *, scale: float, kv_offset: int = 0,
                     length: jax.Array | None = None,
                     window: int | None = None,
                     sink: jax.Array | None = None) -> jax.Array:
    """q: [T, H, Dh], k: [S, KVH, Dh], v: [S, KVH, Dv] (Dv may differ from
    Dh). Causal with query i attending to kv j where j <= i + kv_offset.
    `length` masks padded kv positions; ``window`` keeps the last `window`
    of them, the query's own included; ``sink`` [H]: see sink_softmax.
    Returns [T, H, Dv]."""
    T, H, Dh = q.shape
    S, KVH, _ = k.shape
    g = H // KVH
    qg = q.reshape(T, KVH, g, Dh)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) * scale
    qpos = jnp.arange(T)[:, None] + kv_offset
    kpos = jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if length is not None:
        mask = mask & (kpos < length)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    else:
        probs = sink_softmax(
            scores, sink.reshape(KVH, g, 1)).astype(v.dtype)
    out = jnp.einsum("kgts,skd->tkgd", probs, v)
    return out.reshape(T, H, v.shape[-1])


# ---------------------------------------------------------------------------
# Prefill: Pallas flash kernel (chunked online softmax, no [.., T, S] scores)
# ---------------------------------------------------------------------------
#
# The XLA prefill path above materializes [KVH, g, T, S] float32 scores —
# at T=S=2048 with 32 heads that is 512MB and the reason long-ISL prefill
# was memory-bound (VERDICT round 1, "What's weak" 4). This kernel streams
# KV in chunks with the same online-softmax recurrence as the decode kernel,
# so live memory is O(TQ·SC) per grid step and the score matmuls hit the MXU
# at [TQ*g, Dh] x [Dh, SC].
#
# Layout: queries are rearranged to [KVH, T*g, Dh] (all g query heads of one
# kv head contiguous in sublanes), k/v dense-gathered from the block-major
# pool to [KVH, S, Dh]. Grid (KVH, nTq, nSc) with the kv-chunk axis
# innermost; scratch m/l/acc carry the softmax state across kv chunks.
# Causality prunes the grid: chunk sc runs only for first(tq) <= sc <=
# last(tq), where `last` follows the diagonal and `first` skips chunks
# entirely below a sliding window (gemma2 local layers).


def _flash_prefill_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref,
                          *, q_chunk: int, kv_chunk: int, g: int,
                          scale: float, window: int | None,
                          softcap: float | None,
                          ml_ref=None, sink_ref=None):
    """meta_ref (SMEM): [start_pos, seq_len, sliding]; q_ref: [1, TQ*g, Dh];
    k_ref: [1, SC, Dh]; v_ref: [1, SC, Dv]; o_ref: [1, TQ*g, Dv]; m/l:
    [TQ*g, 1] f32; acc: [TQ*g, Dv] f32.

    ``sink_ref`` [1, TQ*g, 1] f32 set → every row's running state starts
    at (m, l) = (sink, 1) = exp(sink - m): the sink is in the softmax's
    denominator from the first chunk on, and adds no value.

    ``ml_ref`` set → PARTIAL mode (ring attention, attention.py
    flash_prefill_partial): o gets the UNNORMALIZED f32 accumulator and
    ml_ref [1, TQ*g, 2] gets (m, l), so ring steps combine across devices
    with the online-softmax recurrence. Partial mode also tolerates a
    fully-masked q chunk (negative start_pos / zero seq_len — a ring hop
    whose KV lies entirely after the queries): it contributes exact zeros.
    """
    tq, sc = pl.program_id(1), pl.program_id(2)
    n_sc = pl.num_programs(2)
    start_pos = meta_ref[0]
    seq_len = meta_ref[1]
    sliding = meta_ref[2]
    partial = ml_ref is not None

    qpos_lo = start_pos + tq * q_chunk
    qpos_hi = qpos_lo + q_chunk - 1
    # causal upper bound: kv chunks past the diagonal never contribute
    last = jnp.minimum(qpos_hi // kv_chunk, n_sc - 1)
    # sliding-window lower bound: chunks entirely below every query's
    # window are dead (global layers, or no window configured: first = 0)
    if window is None:
        first = 0
    else:
        first = jnp.where(
            sliding > 0,
            jnp.maximum(qpos_lo - window + 1, 0) // kv_chunk,
            0)
    if partial:
        # empty causal range: still run chunk 0 (fully masked → zeros) so
        # the outputs are always written
        empty = last < first
        first = jnp.where(empty, 0, first)
        last = jnp.where(empty, 0, last)

    @pl.when((sc >= first) & (sc <= last))
    def _():
        @pl.when(sc == first)
        def _():
            if sink_ref is None:
                m_ref[:] = jnp.full_like(m_ref, NEG_INF)
                l_ref[:] = jnp.zeros_like(l_ref)
            else:
                m_ref[:] = sink_ref[0]
                l_ref[:] = jnp.ones_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        q = q_ref[0]                               # [TQ*g, Dh]
        k = k_ref[0]                               # [SC, Dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap_scores(s, softcap)
        kv_pos = sc * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1)
        qpos = qpos_lo + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=0) // g
        mask = (kv_pos <= qpos) & (kv_pos < seq_len)
        if window is not None:
            mask = mask & ((sliding == 0) | (kv_pos > qpos - window))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if partial:
            # fully-masked rows: m_new == NEG_INF makes exp(s-m) == 1 —
            # zero them so dead ring hops contribute nothing
            p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

        @pl.when(sc == last)
        def _():
            if partial:
                o_ref[0] = acc_ref[:].astype(o_ref.dtype)
                ml_ref[0, :, 0:1] = m_ref[:]
                ml_ref[0, :, 1:2] = l_ref[:]
            else:
                o_ref[0] = (acc_ref[:] /
                            jnp.maximum(l_ref[:], 1e-20)).astype(o_ref.dtype)


def _flash_layout(q, k, v, q_chunk: int, kv_chunk: int):
    """Shared wrapper plumbing for both flash variants: ceil-pad T/S,
    rearrange q to [KVH, Tp*g, Dh] (g query heads of one kv head
    contiguous in sublanes) and k/v to [KVH, Sp, Dh]. ONE home — a tiling
    or layout change here serves flash_prefill AND flash_prefill_partial."""
    T, H, Dh = q.shape
    S, KVH, _ = k.shape
    g = H // KVH
    Tp = -(-T // q_chunk) * q_chunk
    Sp = -(-S // kv_chunk) * kv_chunk
    if Tp != T:   # pad queries; pad rows attend real kv, output sliced off
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    if Sp != S:   # pad kv; dead rows are masked by kv_pos < seq_len
        k = jnp.pad(k, ((0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, Sp - S), (0, 0), (0, 0)))
    qr = q.reshape(Tp, KVH, g, Dh).transpose(1, 0, 2, 3).reshape(
        KVH, Tp * g, Dh)
    kr = k.transpose(1, 0, 2)
    vr = v.transpose(1, 0, 2)
    return qr, kr, vr, Tp, Sp, g


def _flash_grid_spec(KVH: int, n_tq: int, n_sc: int, tqg: int, Dh: int,
                     kv_chunk: int, out_specs, Dv: int | None = None,
                     sink: bool = False):
    Dv = Dh if Dv is None else Dv
    in_specs = [
        pl.BlockSpec((1, tqg, Dh), lambda kh, tq, sc, *_: (kh, tq, 0)),
        pl.BlockSpec((1, kv_chunk, Dh),
                     lambda kh, tq, sc, *_: (kh, sc, 0)),
        pl.BlockSpec((1, kv_chunk, Dv),
                     lambda kh, tq, sc, *_: (kh, sc, 0)),
    ]
    if sink:
        # one q chunk's rows of the kv head's sinks: the same for every tq
        in_specs.append(pl.BlockSpec((1, tqg, 1),
                                     lambda kh, tq, sc, *_: (kh, 0, 0)))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(KVH, n_tq, n_sc),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((tqg, 1), jnp.float32),     # m
            pltpu.VMEM((tqg, 1), jnp.float32),     # l
            pltpu.VMEM((tqg, Dv), jnp.float32),    # acc
        ],
    )


def _flash_unpack(x, KVH: int, Tp: int, g: int, last: int, T: int):
    x = x.reshape(KVH, Tp, g, last).transpose(1, 0, 2, 3)
    return x.reshape(Tp, KVH * g, last)[:T]


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  scale: float, start_pos: jax.Array, seq_len: jax.Array,
                  sliding: jax.Array | bool = False,
                  window: int | None = None,
                  softcap: float | None = None,
                  q_chunk: int = 128, kv_chunk: int = 256,
                  interpret: bool = False,
                  sink: jax.Array | None = None,
                  name: str = "flash_prefill") -> jax.Array:
    """Flash causal attention for prefill. q: [T, H, Dh] (query t sits at
    absolute position start_pos + t); k: [S, KVH, Dh], v: [S, KVH, Dv]
    dense, positions 0..S (prefix + chunk, as gathered from the paged
    pool; Dv may differ from Dh); seq_len masks kv padding; `sliding`
    (traced bool) applies the static `window` to this layer (gemma2
    interleaving); ``sink`` [H] float32: one scalar a query head in the
    softmax's denominator (the kernel's docstring); ``name``: the Pallas
    call's, for a trace to tell one read from another. Returns
    [T, H, Dv]."""
    T, H, Dh = q.shape
    KVH, Dv = k.shape[1], v.shape[2]
    qr, kr, vr, Tp, Sp, g = _flash_layout(q, k, v, q_chunk, kv_chunk)
    meta = jnp.stack([jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(seq_len, jnp.int32),
                      jnp.asarray(sliding, jnp.int32)])

    n_tq, n_sc = Tp // q_chunk, Sp // kv_chunk
    tqg = q_chunk * g
    grid_spec = _flash_grid_spec(
        KVH, n_tq, n_sc, tqg, Dh, kv_chunk,
        out_specs=pl.BlockSpec((1, tqg, Dv),
                               lambda kh, tq, sc, *_: (kh, tq, 0)),
        Dv=Dv, sink=sink is not None)
    kernel = functools.partial(
        _flash_prefill_kernel, q_chunk=q_chunk, kv_chunk=kv_chunk, g=g,
        scale=scale, window=window, softcap=softcap)
    operands = (meta, qr, kr, vr)
    if sink is not None:
        plain = kernel

        def kernel(meta_ref, q_ref, k_ref, v_ref, sink_ref, o_ref, m_ref,
                   l_ref, acc_ref):
            plain(meta_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, sink_ref=sink_ref)

        # row (t, j) of kv head kh's q chunk is query head kh * g + j
        operands += (jnp.tile(
            sink.astype(jnp.float32).reshape(KVH, 1, g),
            (1, q_chunk, 1)).reshape(KVH, tqg, 1),)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KVH, Tp * g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*operands)
    return _flash_unpack(out, KVH, Tp, g, Dv, T)


def flash_prefill_partial(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          scale: float, start_pos: jax.Array,
                          seq_len: jax.Array,
                          q_chunk: int = 128, kv_chunk: int = 256,
                          interpret: bool = False,
                          name: str = "flash_prefill_partial") -> tuple:
    """Flash attention returning UNNORMALIZED partial state for cross-chunk
    combination (ring attention: each hop computes a partial against one
    KV chunk; hops merge with the online-softmax recurrence).

    q: [T, H, Dh] at absolute positions start_pos + t (start_pos may be
    NEGATIVE — queries before this KV chunk are fully masked and
    contribute zeros); k: [S, KVH, Dh], v: [S, KVH, Dv] at positions
    0..seq_len. Returns (acc [T, H, Dv] f32, m [T, H] f32, l [T, H] f32).
    """
    T, H, Dh = q.shape
    KVH, Dv = k.shape[1], v.shape[2]
    qr, kr, vr, Tp, Sp, g = _flash_layout(q, k, v, q_chunk, kv_chunk)
    meta = jnp.stack([jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(seq_len, jnp.int32),
                      jnp.asarray(0, jnp.int32)])

    n_tq, n_sc = Tp // q_chunk, Sp // kv_chunk
    tqg = q_chunk * g
    grid_spec = _flash_grid_spec(
        KVH, n_tq, n_sc, tqg, Dh, kv_chunk,
        out_specs=[
            pl.BlockSpec((1, tqg, Dv), lambda kh, tq, sc, *_: (kh, tq, 0)),
            pl.BlockSpec((1, tqg, 2), lambda kh, tq, sc, *_: (kh, tq, 0)),
        ], Dv=Dv)

    def kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, ml_ref,
               m_ref, l_ref, acc_ref):
        _flash_prefill_kernel(
            meta_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            q_chunk=q_chunk, kv_chunk=kv_chunk, g=g, scale=scale,
            window=None, softcap=None, ml_ref=ml_ref)

    acc, ml = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((KVH, Tp * g, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((KVH, Tp * g, 2), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(meta, qr, kr, vr)

    acc = _flash_unpack(acc, KVH, Tp, g, Dv, T)
    ml = _flash_unpack(ml, KVH, Tp, g, 2, T)
    return acc, ml[:, :, 0], ml[:, :, 1]


def flash_prefill_supported(num_heads: int, num_kv_heads: int,
                            head_dim: int, v_dim: int | None = None) -> bool:
    """The flash prefill kernel handles any GQA geometry with 8-aligned
    head dims (lanes are padded to 128 by Mosaic; sub-8 dims aren't worth
    tiling); ``v_dim``: the value heads', where they differ."""
    return (num_heads % num_kv_heads == 0 and head_dim % 8 == 0
            and head_dim >= 8
            and (v_dim is None or (v_dim % 8 == 0 and v_dim >= 8)))


# ---------------------------------------------------------------------------
# Decode: paged attention (XLA reference implementation)
# ---------------------------------------------------------------------------
#
# The canonical KV-cache layout is BLOCK-MAJOR: per layer `[NTOK, C]` where
# `NTOK = num_blocks * block_size` is the flat paged token pool and
# `C = KVH * Dh` packs every kv head's vector side by side in lanes. Chosen
# so that (a) one contiguous DMA per KV block fetches ALL heads (the
# head-major layout needed KVH separate sub-slices per block), (b) decode
# attention for every query head is ONE MXU dot against packed rows (see the
# Pallas kernel), and (c) tensor-parallel sharding over kv heads is a plain
# last-axis PartitionSpec (head vectors are contiguous lane groups).


def flat_token_indices(block_tables: jax.Array, block_size: int) -> jax.Array:
    """[B, M] block ids → [B, M*BS] flat token-pool indices."""
    B, M = block_tables.shape
    offs = jnp.arange(block_size)[None, None, :]
    return (block_tables[:, :, None] * block_size + offs).reshape(B, -1)


def paged_attention_xla(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        block_tables: jax.Array, seq_lens: jax.Array,
                        *, block_size: int, scale: float,
                        softcap: float | None = None,
                        win_lo: jax.Array | None = None,
                        kv_heads: int | None = None,
                        v_dim: int | None = None,
                        sink: jax.Array | None = None) -> jax.Array:
    """q: [B, H, Dh]; k_cache/v_cache: [NTOK, KVH*Dh] (block-major pool;
    int8 pools carry KV_SCALE_LANES extra in-row scale lanes — one group,
    or ``kv_heads`` sizes the value lanes of a tp-grouped row — and
    dequantize after the gather); block_tables: [B, M] int32; seq_lens:
    [B] (kv length incl. current token). ``v_dim``: the value heads' size
    where it is not Dh (v_cache [NTOK, KVH*v_dim]; full-precision pools);
    ``sink`` [H]: see sink_softmax. Returns [B, H, v_dim or Dh]."""
    B, H, Dh = q.shape
    C = kv_heads * Dh if kv_heads is not None else kv_value_lanes(k_cache)
    KVH = C // Dh
    g = H // KVH
    Dv = Dh if v_dim is None else v_dim
    idx = flat_token_indices(block_tables, block_size)        # [B, T]
    T = idx.shape[1]
    k = jnp.take(k_cache, idx, axis=0)
    v = jnp.take(v_cache, idx, axis=0)
    if k_cache.dtype == jnp.int8:
        if Dv != Dh:
            raise ValueError("int8 pools have no encoding for value heads "
                             "of another size than the keys'")
        k = dequant_kv_rows(k, C, q.dtype)
        v = dequant_kv_rows(v, C, q.dtype)
    k = k.reshape(B, T, KVH, Dh)
    v = v.reshape(B, T, KVH, Dv)
    qg = q.reshape(B, KVH, g, Dh)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32) * scale
    if softcap:
        scores = softcap_scores(scores, softcap)              # gemma2
    mask = jnp.arange(T)[None, :] < seq_lens[:, None]         # [B, T]
    if win_lo is not None:   # sliding-window layers: trailing window only
        mask = mask & (jnp.arange(T)[None, :] > win_lo[:, None])
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    else:
        probs = sink_softmax(scores, sink.reshape(1, KVH, g)).astype(v.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v)
    return out.reshape(B, H, Dv)


# ---------------------------------------------------------------------------
# Decode: run-coalesced DMA support (contiguity-aware KV layout)
# ---------------------------------------------------------------------------
#
# The run-tracking allocator (llm/kv/pool.py FreeRunIndex) lands a
# sequence's blocks as few maximal runs of physically-adjacent ids. The
# decode kernel exploits that: when one DMA wave's blocks are consecutive
# in the pool, the whole wave is ONE contiguous [chunk*block_size, Cx]
# copy instead of `chunk` per-block copies — the "multi-block-per-DMA
# layout" PERF.md round-5 names as the next lever for small-C geometries
# where a 16-token block row is a latency-bound 4 KB payload.
#
# The coalescibility table is derived from (block_tables, seq_lens) at
# trace time — INSIDE the jitted step, so it is always consistent with
# the tables the kernel reads (a host-precomputed table would go stale
# mid-K-scan as sequences cross block boundaries). wave_contig_table is
# the ONE home of the predicate; the numpy call path serves host-side
# stats (EngineCore metrics, bench --kv-frag, tools/decode_profile.py).


def wave_contig_table(block_tables, seq_lens, *, block_size: int,
                      chunk: int, pool_blocks: int, xp=jnp):
    """[B, n_waves] int32: 1 where DMA wave w of sequence b may be
    fetched as ONE contiguous copy of `chunk` blocks.

    A wave is coalescible iff (a) every VALID table entry in it (indices
    < ceil(seq_len/block_size)) is physically consecutive from the
    wave's first entry, and (b) the full chunk-block span stays inside
    the pool (`pool_blocks`). Tail rows past the valid blocks are then
    fetched from adjacent pool rows instead of the per-block path's
    trash-block clamp — BOTH are masked by the seq_len bound before the
    softmax, so the two paths are bit-identical (every pool row is
    finite by construction: zeros at init, real KV or quantizer output
    after). ``xp`` picks the array namespace: jnp inside the jitted
    wrapper, np for host-side DMA accounting."""
    B, M = block_tables.shape
    n_waves = -(-M // chunk)
    pad = n_waves * chunk - M
    bt = xp.pad(xp.asarray(block_tables), ((0, 0), (0, pad)))
    bt = bt.reshape(B, n_waves, chunk)
    nb = (xp.asarray(seq_lens) + block_size - 1) // block_size       # [B]
    idx = xp.arange(n_waves * chunk).reshape(n_waves, chunk)
    valid = idx[None] < nb[:, None, None]            # [B, n_waves, chunk]
    expect = bt[:, :, :1] + xp.arange(chunk)[None, None, :]
    consec = xp.all((bt == expect) | ~valid, axis=2)
    in_bounds = bt[:, :, 0] + chunk <= pool_blocks
    return (consec & in_bounds).astype(xp.int32)


def dma_copy_counts(block_tables, seq_lens, *, block_size: int,
                    pool_blocks: int, chunk_blocks: int | None = None,
                    dual_stream: bool = True, win_lo=None,
                    coalesce: bool = True) -> dict:
    """Host-side count of the DMA copies one Pallas decode call issues
    over these tables — the CPU-side truth the --kv-frag bench and the
    coalescing tests gate on (and the attn_dma_copies_per_wave metrics
    feed). Mirrors the kernel's wave walk exactly: per sequence, waves
    [start_ci, num_chunks); a coalescible wave is 1 copy per KV stream,
    a fragmented one is `chunk` per stream. ``dual_stream`` False for
    v-aliases-k pools (MLA latents: k only)."""
    bt = np.asarray(block_tables)
    sl = np.asarray(seq_lens)
    B, M = bt.shape
    if chunk_blocks is None:
        chunk_blocks = ATTN_CHUNK_BLOCKS
    chunk = max(1, min(chunk_blocks, M))
    contig = (wave_contig_table(bt, sl, block_size=block_size,
                                chunk=chunk, pool_blocks=pool_blocks,
                                xp=np)
              if coalesce else np.zeros((B, -(-M // chunk)), np.int32))
    nb = -(-sl // block_size)
    nc = -(-nb // chunk)
    start = (np.zeros((B,), np.int64) if win_lo is None
             else np.maximum(np.asarray(win_lo) + 1, 0)
             // (chunk * block_size))
    streams = 2 if dual_stream else 1
    copies = waves = coalesced = 0
    for b in range(B):
        for ci in range(int(start[b]), int(nc[b])):
            waves += 1
            if contig[b, ci]:
                coalesced += 1
                copies += streams
            else:
                copies += streams * chunk
    return {"waves": waves, "copies": copies,
            "coalesced_waves": coalesced,
            "copies_per_wave": copies / max(waves, 1)}


# ---------------------------------------------------------------------------
# Shared wave-DMA machinery (decode kernel + ragged kernel)
# ---------------------------------------------------------------------------
#
# The round-7 run-coalesced DMA walk is the ONE home of the KV wave
# fetch: a wave of `chunk` blocks streams either as one contiguous
# [chunk*block_size, Cx] copy per KV stream (runs_ref said the blocks
# are physically consecutive — wave_contig_table above) or as `chunk`
# per-block copies. The ragged kernel below reuses it unchanged —
# ragged waves are just variable-length contiguous runs, exactly the
# shape the coalescing machinery was built for.


def _make_wave_dma(block_tables_ref, runs_ref, k_hbm, v_hbm,
                   k_bufs, v_bufs, sems, *, block_size: int, chunk: int,
                   v_lanes: int | None, coalesce: bool):
    """Build the `wave_dma(op, sq, ci, slot, nb)` closure both Pallas
    kernels share. ``op`` is "start" or "wait"; ``sq`` the sequence row
    in block_tables_ref; ``ci`` the wave (chunk) index; ``slot`` the
    double-buffer slot; ``nb`` the sequence's valid block count (tail
    clamp for the per-block path)."""

    def block_copies(op, sq, ci, slot, nb):
        """Start or wait the per-block copies of sequence `sq`'s chunk
        `ci` into buffer `slot` — 2*chunk (k and v), or chunk in
        v-aliases-k mode; the wait rebuilds the descriptor the start
        issued, all on one semaphore. One traced body, not a Python
        unroll (every wave_dma site is traced at every start of a served
        process, and a wave is 16-64 blocks); the lowering unrolls it,
        because the scalar core issues descriptors from a rolled loop
        7-50% slower a fragmented wave (2x in index_scores; PERF.md §6,
        PR 57)."""
        def one_block(j, carry):
            bi = ci * chunk + j
            bi = jax.lax.select(bi < nb, bi, 0)  # clamp tail
            src = pl.ds(block_tables_ref[sq, bi] * block_size, block_size)
            dst = pl.ds(pl.multiple_of(j * block_size, block_size),
                        block_size)
            getattr(pltpu.make_async_copy(
                k_hbm.at[src, :], k_bufs.at[slot, dst, :],
                sems.at[slot]), op)()
            if v_lanes is None:                # v aliases k otherwise
                getattr(pltpu.make_async_copy(
                    v_hbm.at[src, :], v_bufs.at[slot, dst, :],
                    sems.at[slot]), op)()
            return carry

        jax.lax.fori_loop(0, chunk, one_block, 0, unroll=True)

    def run_copies(sq, ci, slot):
        """The coalesced form of one wave: the chunk blocks are
        physically consecutive (runs_ref said so), so the WHOLE wave is
        one [chunk*block_size, Cx] copy per KV stream — same bytes into
        the same buffer region, chunk× fewer DMA issues."""
        blk0 = block_tables_ref[sq, ci * chunk]
        copies = [pltpu.make_async_copy(
            k_hbm.at[pl.ds(blk0 * block_size, chunk * block_size), :],
            k_bufs.at[slot], sems.at[slot])]
        if v_lanes is None:
            copies.append(pltpu.make_async_copy(
                v_hbm.at[pl.ds(blk0 * block_size, chunk * block_size), :],
                v_bufs.at[slot], sems.at[slot]))
        return copies

    def wave_dma(op, sq, ci, slot, nb):
        """Start or wait one wave's DMAs, branching on the wave's
        coalescibility. The runs table is immutable across the call, so
        the wait reconstructs the exact copy set the start issued (and
        either way the semaphore balances: one coalesced copy carries
        the same byte count as the chunk per-block copies)."""
        if not coalesce:
            block_copies(op, sq, ci, slot, nb)
            return
        contig = runs_ref[sq, ci] > 0

        @pl.when(contig)
        def _():
            for c in run_copies(sq, ci, slot):
                getattr(c, op)()

        @pl.when(~contig)
        def _():
            block_copies(op, sq, ci, slot, nb)

    return wave_dma


def _make_dequant_tile(quant_lanes: int | None, quant_sections,
                       q_width: int):
    """The kernels' in-VMEM int8 row dequant, shared by the decode and
    ragged kernels. Returns (dequant_tile, dequant_tile_sections) — the
    single- and sectioned-scale readers of the in-row (e, m) encoding
    (quantize_kv_rows / quantize_kv_rows_sections)."""
    C = quant_lanes if quant_lanes is not None else q_width

    def dequant_tile(tile):
        """[cbs, Cx] int8 tile → [cbs, C] f32 values, rescaled from the
        in-row (e, m) lanes. Keepdim lane slices ([cbs, 1]) broadcast
        along lanes with no sublane↔lane movement — the score-space
        variant (scale as a [cbs] LANE vector) costs a transpose per
        wave and measured slower than the DMA saving on v5e."""
        scale = _decode_scale(tile[:, C:C + 1], tile[:, C + 1:C + 2])
        return tile[:, :C].astype(jnp.float32) * scale

    def dequant_tile_sections(tile):
        """[cbs, Cx] sectioned-int8 tile → [cbs, q_width] f32: each
        section rescaled by ITS (e, m) pair (pad lanes 2i, 2i+1 after
        the values), zero lanes up to the query width — same keepdim
        lane-broadcast shape as dequant_tile."""
        Cs = sum(quant_sections)
        parts = []
        off = 0
        for i, w in enumerate(quant_sections):
            scale = _decode_scale(tile[:, Cs + 2 * i:Cs + 2 * i + 1],
                                  tile[:, Cs + 2 * i + 1:Cs + 2 * i + 2])
            parts.append(tile[:, off:off + w].astype(jnp.float32) * scale)
            off += w
        if q_width > Cs:
            parts.append(jnp.zeros((tile.shape[0], q_width - Cs),
                                   jnp.float32))
        return jnp.concatenate(parts, axis=1)

    return dequant_tile, dequant_tile_sections


# ---------------------------------------------------------------------------
# Decode: Pallas flash kernel streaming block-major KV from HBM
# ---------------------------------------------------------------------------
#
# Grid (B,): one sequence per step, ALL heads at once. The sparse-slotted
# query matrix `qm[h, kh(h)*Dh:(kh(h)+1)*Dh] = q[h]` (zeros elsewhere) makes
# `qm @ k_row` select exactly head h's kv slot, so scores for every query
# head are one [H, C] x [C, chunk*bs] MXU dot per KV chunk; the accumulator
# keeps all C lanes and the host-side wrapper extracts each head's slot.
# KV blocks stream `chunk_blocks` per DMA wave into double-buffered VMEM
# (next wave in flight during compute); each block is ONE contiguous
# [block_size, C] copy — the payoff of the block-major layout.


def _paged_attn_kernel(block_tables_ref, seq_lens_ref, win_lo_ref,
                       runs_ref,
                       q_ref, k_hbm, v_hbm, o_ref,
                       m_ref, l_ref, acc_ref, k_bufs, v_bufs, sems,
                       wave_ref,
                       *, block_size: int, chunk: int, scale: float,
                       num_seqs: int, seqs_per_program: int,
                       softcap: float | None = None,
                       quant_lanes: int | None = None,
                       v_lanes: int | None = None,
                       quant_sections: tuple | None = None,
                       coalesce: bool = True, sink_ref=None, rows: int = 1):
    """q_ref: [G, Hp, C] sparse-slotted (VMEM); k_hbm/v_hbm: [NTOK, Cx]
    (HBM; value heads of another size than the keys': v_hbm, v_bufs, acc
    and o_ref are KVH*Dv wide, nothing else differs); o_ref: [G, Hp, C];
    k_bufs/v_bufs: [2, chunk*block_size, Cx] double buffers; sems: DMA
    semaphore pair; m/l: [Hp, 1]; acc: [Hp, C] f32; sink_ref: [Hp, 1] f32
    or None: one scalar a query head that starts the running state at
    (m, l) = (sink, 1), so it is in the softmax's denominator and adds no
    value; wave_ref: [1] SMEM global wave-parity carried ACROSS programs;
    runs_ref: [B, n_waves] SMEM per-wave coalescibility
    (wave_contig_table) — with ``coalesce`` a flagged wave streams as
    ONE contiguous chunk-block copy per KV stream instead of `chunk`
    per-block copies (wave_dma below; bit-identical output, the
    fragmented fallback is the per-block path).

    int8 KV pools carry their per-token scales IN-ROW (KV_SCALE_LANES;
    Cx = C + 128, `quant_lanes`=C — the int8 flag AND payload width,
    distinct from `v_lanes` below): the block DMA is unchanged — ONE
    contiguous copy fetches values + scales — and dequant_tile rescales
    each wave's [cbs, C] tile in ROW space before the dots (keepdim lane
    slices broadcast along lanes with no sublane↔lane movement; the
    score-space variant needed a transpose per wave and measured slower
    on v5e).

    ``v_lanes`` (MLA latent pools, models/mla.py decode): v IS the
    first v_lanes lanes of each k row (probs·c in the absorbed form),
    so the v-side DMA is skipped entirely — HALVING the KV stream —
    and the accumulator/output narrow to v_lanes. v_hbm/v_bufs are
    untouched in this mode (the wrapper passes dummies).

    ``quant_sections`` (int8 MLA pools; implies v_lanes): rows carry
    the SECTIONED in-row encoding (quantize_kv_rows_sections — one
    (e, m) pair per section at pad lanes (2i, 2i+1), then tail zeros
    to the 128-lane row alignment). dequant produces a q-width tile:
    dequantized sections followed by zero lanes, so the score dot
    against the zero-padded query is identical to the full-precision
    layout.

    Each grid program handles G = seqs_per_program sequences (a loop on
    the device over one traced body): per-program fixed costs (q/o block
    pipelining, grid step dispatch) measured ~150 us per kernel call at
    B=128 on v5e — ~2.4 ms/step over 16 layers — and amortize G-fold.

    The DMA pipeline crosses sequence AND program boundaries: scratch
    persists over the grid, so each sequence's LAST wave prefetches the
    NEXT sequence's first wave. Without this every sequence exposes its
    first wave's DMA latency — at seq 512 / chunk 16 that is 1 exposed
    wave in 2, which measured as ~44% of HBM peak. Buffer slots follow a
    GLOBAL wave counter (wave_ref) rather than the per-sequence chunk
    index so producer and consumer agree on parity across boundaries.

    ``rows`` = R > 1: a sequence brings R queries, at its last R positions
    (the rows a slot scores in one step), stacked in the sublanes: q_ref,
    o_ref [G, R·Hp, C], m / l / acc / sink_ref R·Hp rows. seq_lens_ref is
    the LAST row's and row r sees R - 1 - r keys fewer; win_lo_ref [B·R]
    holds a lower bound a ROW (a window pinned at the sequence's start does
    not slide with the row), row 0's the lowest. The mask is a row's own;
    every wave is fetched once and scored by all R rows. At R == 1 nothing
    here differs from the kernel without it."""
    pb = pl.program_id(0)
    G = seqs_per_program

    def seq_shape(bi):
        """(num_blocks, num_chunks, start_ci) for sequence bi
        (scalar-prefetch math)."""
        nb = (seq_lens_ref[bi] + block_size - 1) // block_size
        nc = (nb + chunk - 1) // chunk
        # sliding-window layers: chunks entirely below the window would
        # be DMA'd and masked to nothing — start at the first in-window
        # chunk (of the sequence's first row: the lowest bound of its rows)
        sc = jnp.maximum(win_lo_ref[bi * rows if rows > 1 else bi] + 1,
                         0) // (chunk * block_size)
        return nb, nc, sc

    quantized = quant_lanes is not None
    C = quant_lanes if quantized else q_ref.shape[-1]

    # shared wave-DMA walk + int8 tile dequant (ONE home with the
    # ragged kernel — _make_wave_dma / _make_dequant_tile above)
    dequant_tile, dequant_tile_sections = _make_dequant_tile(
        quant_lanes, quant_sections, C)
    wave_dma = _make_wave_dma(
        block_tables_ref, runs_ref, k_hbm, v_hbm, k_bufs, v_bufs, sems,
        block_size=block_size, chunk=chunk, v_lanes=v_lanes,
        coalesce=coalesce)

    @pl.when(pb == 0)
    def _():
        wave_ref[0] = 0

    def sequence(s, carry):
        """One sequence of the program's group: a loop on the device, so
        the body (its one-wave path, its wave loop and seven wave_dma
        sites) is traced once a program and not once a sequence."""
        sq = pb * G + s
        num_blocks, num_chunks, start_ci = seq_shape(sq)
        seq_len = seq_lens_ref[sq]
        if rows == 1:
            win_lo = win_lo_ref[sq]
        else:
            # a column a query sublane (row r = sublane // Hp): what the
            # row's upper bound lies below the last row's, its lower bound
            row = jax.lax.broadcasted_iota(
                jnp.int32, (q_ref.shape[1], 1), 0) // (q_ref.shape[1] // rows)
            seq_len = seq_len - (rows - 1 - row)
            win_lo = win_lo_ref[sq * rows]
            for r in range(1, rows):
                win_lo = jnp.where(row == r, win_lo_ref[sq * rows + r],
                                   win_lo)

        one_wave = (num_chunks - start_ci) == 1

        qm = q_ref[s].astype(jnp.float32) * scale   # [Hp, C]

        p0 = wave_ref[0]      # global parity of this sequence's first wave

        # this sequence's first wave was already started by the previous
        # sequence's last loop iteration — unless there is no predecessor
        # or the predecessor had no waves (its loop never ran)
        if num_seqs > 1:
            _, prev_nc, prev_sc = seq_shape(jnp.maximum(sq - 1, 0))
            pred_started = (sq > 0) & (prev_sc < prev_nc)
            nsq = jnp.minimum(sq + 1, num_seqs - 1)
            next_nb, next_nc, next_sc = seq_shape(nsq)
        else:
            pred_started = jnp.bool_(False)

        @pl.when((start_ci < num_chunks) & ~pred_started)
        def _():
            # empty range: an unwaited start would leak semaphore signal
            # into the next sequence's waves
            wave_dma("start", sq, start_ci, jax.lax.rem(p0, 2),
                     num_blocks)

        def wave_scores(ci, slot):
            """DMA bookkeeping + masked scores for wave `ci`: start the
            next wave (or the successor sequence's first), wait this
            one, return (p-ready scores, v)."""
            @pl.when(ci + 1 < num_chunks)
            def _():
                wave_dma("start", sq, ci + 1, 1 - slot, num_blocks)

            if num_seqs > 1:
                @pl.when((ci + 1 >= num_chunks) & (sq + 1 < num_seqs)
                         & (next_sc < next_nc))
                def _():      # last wave: prefetch the successor's first
                    wave_dma("start", nsq, next_sc, 1 - slot, next_nb)

            wave_dma("wait", sq, ci, slot, num_blocks)
            if quant_sections is not None:
                k = dequant_tile_sections(k_bufs[slot])   # [cbs, C] f32
                v = k[:, :v_lanes]        # sections mode implies alias
            elif quantized:
                k = dequant_tile(k_bufs[slot])        # [cbs, C] f32
                v = dequant_tile(v_bufs[slot])
            else:
                k = k_bufs[slot].astype(jnp.float32)  # [chunk*bs, C]
                v = (k[:, :v_lanes] if v_lanes is not None
                     else v_bufs[slot].astype(jnp.float32))
            sm = jax.lax.dot_general(qm, k, (((1,), (1,)), ((), ())))
            if softcap:
                sm = softcap_scores(sm, softcap)    # [Hp, cbs]
            kv_pos = ci * chunk * block_size + jax.lax.broadcasted_iota(
                jnp.int32, sm.shape, dimension=1)
            sm = jnp.where((kv_pos < seq_len) & (kv_pos > win_lo),
                           sm, NEG_INF)
            return sm, v

        def body(ci, _):
            slot = jax.lax.rem(p0 + (ci - start_ci), 2)
            sm, v = wave_scores(ci, slot)
            m_prev = m_ref[:]                       # [Hp, 1]
            m_new = jnp.maximum(m_prev, jnp.max(sm, axis=1, keepdims=True))
            p = jnp.exp(sm - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))     # [Hp, C]
            m_ref[:] = m_new
            return 0

        @pl.when(one_wave)
        def _():
            # fast path for sequences whose live KV fits one wave (every
            # sequence at seq <= chunk*block_size, the common serving
            # case): plain softmax straight to the output block — no
            # scratch init, no carry reads, no epilogue divide pass
            sm, v = wave_scores(start_ci, jax.lax.rem(p0, 2))
            m = jnp.max(sm, axis=1, keepdims=True)
            if sink_ref is not None:
                m = jnp.maximum(m, sink_ref[:])
            p = jnp.exp(sm - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            if sink_ref is not None:
                l = l + jnp.exp(sink_ref[:] - m)
            o_ref[s] = (jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
                / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)

        @pl.when(~one_wave)
        def _():
            if sink_ref is None:
                m_ref[:] = jnp.full_like(m_ref, NEG_INF)  # online-softmax
                l_ref[:] = jnp.zeros_like(l_ref)          # carry state
            else:
                m_ref[:] = sink_ref[:]
                l_ref[:] = jnp.ones_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)
            jax.lax.fori_loop(start_ci, num_chunks, body, 0)
            o_ref[s] = (acc_ref[:] /
                        jnp.maximum(l_ref[:], 1e-20)).astype(o_ref.dtype)

        # hand the successor its first-wave parity: the prefetch above
        # placed it at 1 - rem(p0 + num_waves - 1, 2) == rem(p0+waves, 2)
        wave_ref[0] = jax.lax.rem(
            p0 + jnp.maximum(num_chunks - start_ci, 0), 2)
        return carry

    jax.lax.fori_loop(0, G, sequence, 0)


def paged_attention_pallas(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                           block_tables: jax.Array, seq_lens: jax.Array,
                           *, block_size: int, scale: float,
                           softcap: float | None = None,
                           win_lo: jax.Array | None = None,
                           chunk_blocks: int | None = None,
                           seqs_per_program: int | None = None,
                           v_lanes: int | None = None,
                           quant_sections: tuple | None = None,
                           coalesce: bool = True,
                           interpret: bool = False,
                           v_dim: int | None = None,
                           sink: jax.Array | None = None,
                           name: str = "paged_attention",
                           rows: int = 1) -> jax.Array:
    """Same contract as `paged_attention_xla`; KV stays in HBM and streams
    chunk-by-chunk with double buffering (no [B, M*BS] gather). Sliding
    windows are in-kernel (win_lo: [B], -1 for global layers). int8 pools
    (in-row scales, KV_SCALE_LANES) cut the DMA bytes 1.6× with the same
    one-copy-per-block structure.

    ``coalesce`` (default on): waves whose blocks are physically
    consecutive in the pool — the run-tracking allocator's layout —
    stream as ONE DMA per KV stream instead of one per block
    (wave_contig_table above; bit-identical output either way, asserted
    in tests/test_kv_contig.py). False forces the per-block path (the
    --kv-frag A/B baseline and the EngineConfig.kv_contig_alloc=off
    escape hatch).

    ``v_lanes`` (MQA/MLA only, KVH == 1): v is the first v_lanes lanes
    of each k row — the v-side DMA is skipped (HALVING the stream) and
    the output narrows to [B, H, v_lanes]; v_cache is ignored.

    ``quant_sections`` (int8 MLA pools; requires v_lanes): rows carry
    the sectioned in-row encoding and dequant to the query's width
    in-kernel (kernel docstring). The row width is
    pad128(sum + KV_SCALE_LANES); q width must be pad128(sum).

    ``rows`` = R > 1: the R rows a sequence scores in one step share ONE
    pass over its cache. q [B·R, H, Dh], sequence-major (sequence b's rows
    at its last R positions, oldest first, are q[b·R : (b+1)·R]);
    block_tables [B, M]; seq_lens [B] as the LAST row sees them (row r sees
    R - 1 - r keys fewer); win_lo [B·R], a ROW's own lower bound in the
    sequence's table (a window that has not left the sequence's start does
    not slide with the row), row 0's the lowest. Returns [B·R, H, v_dim or
    Dh], equal to the call with every row a sequence of its own.
    Full-precision pools with a v stream of their own (the reads that have
    such a step)."""
    N, H, Dh = q.shape
    B = N // rows
    if rows > 1 and (N % rows or v_lanes is not None
                     or k_cache.dtype == jnp.int8
                     or (win_lo is not None and win_lo.shape != (N,))):
        raise ValueError(
            f"rows={rows} needs a whole number of sequences (q has {N} "
            f"rows), a lower bound a ROW where there is one, and a "
            f"full-precision pool read without v_lanes: no other read has "
            f"a step of several rows a sequence")
    NTOK, Cx = k_cache.shape
    quantized = k_cache.dtype == jnp.int8
    if quant_sections is not None:
        if not quantized or v_lanes is None:
            raise ValueError("quant_sections needs an int8 pool and "
                             "v_lanes (the MLA sectioned layout)")
        C = Dh          # dequant produces query-width tiles (KVH == 1)
    else:
        C = kv_value_lanes(k_cache)
    KVH = C // Dh
    if not pallas_supported(H, KVH, Dh, block_size,
                            kv_dtype=k_cache.dtype, v_dim=v_dim):
        raise ValueError(
            f"unsupported pallas geometry (H={H}, KVH={KVH}, Dh={Dh}, "
            f"v_dim={v_dim}, block_size={block_size}, "
            f"kv={k_cache.dtype}): needs KVH*Dh % 128 == 0 (and "
            f"KVH*v_dim, on a full-precision pool) and block_size % 8 == 0 "
            f"(int8 pools: % 32, the int8 sublane tile) — see "
            f"pallas_supported")
    if v_dim is not None and v_lanes is not None:
        raise ValueError("v_dim (a v pool of its own width) and v_lanes "
                         "(v aliases k) exclude each other")
    if v_lanes is not None and (KVH != 1 or v_lanes % 128 != 0
                                or v_lanes > C):
        raise ValueError(
            f"v_lanes={v_lanes} needs an MQA-shaped pool (KVH == 1, got "
            f"{KVH}) and a 128-aligned width <= {C}")
    if quant_sections is not None:
        Cs = sum(quant_sections)
        if (-(-(Cs + KV_SCALE_LANES) // 128) * 128 != Cx
                or -(-Cs // 128) * 128 != Dh):
            raise ValueError(
                f"quant_sections {quant_sections} (sum {Cs}) does not "
                f"match row width {Cx} = pad128(sum + "
                f"{KV_SCALE_LANES}) / query width {Dh} = pad128(sum)")
    if v_lanes is not None and quantized and quant_sections is None:
        # single-scale int8 rows (the llama encoding) have no
        # v-aliasing user or test — refuse rather than ship a dead,
        # unexercised compile path; sectioned MLA pools pass
        # quant_sections and ARE the supported int8 alias mode
        raise ValueError(
            "v_lanes on a single-scale int8 pool is not supported "
            "(sectioned MLA pools pass quant_sections)")
    Dv = Dh if v_dim is None else v_dim
    Cv = KVH * Dv if v_lanes is None else v_lanes
    Cvx = Cx if v_dim is None else v_cache.shape[1]
    g = H // KVH
    M = block_tables.shape[1]
    if chunk_blocks is None:
        chunk_blocks = ATTN_CHUNK_BLOCKS
    chunk = max(1, min(chunk_blocks, M))
    Hp = max(8, H)   # sublane-pad the head rows for tiny models
    Hq = rows * Hp   # a sequence's query sublanes: its rows, stacked
    if seqs_per_program is None:
        seqs_per_program = ATTN_SEQS_PER_PROGRAM
    G = max(1, min(seqs_per_program, B))
    Bp = ((B + G - 1) // G) * G
    # sparse slot placement: row h carries q[h] at its kv head's lane group
    qm = jnp.zeros((Bp * rows, Hp, KVH, Dh), q.dtype)
    qm = qm.at[:N, jnp.arange(H), jnp.arange(H) // g, :].set(q)
    qm = qm.reshape(Bp, Hq, C)
    if win_lo is None:
        win_lo = jnp.full((B * rows,), -1, jnp.int32)
    if Bp > B:       # pad group tail with zero-length sequences (no waves)
        block_tables = jnp.concatenate(
            [block_tables, jnp.zeros((Bp - B, M), block_tables.dtype)])
        seq_lens = jnp.concatenate(
            [seq_lens, jnp.zeros((Bp - B,), seq_lens.dtype)])
        win_lo = jnp.concatenate(
            [win_lo, jnp.full(((Bp - B) * rows,), -1, jnp.int32)])
    # per-wave coalescibility, derived from the SAME tables the kernel
    # reads (trace-time: stays correct as seq_lens advance inside a
    # K-step scan); zeros = per-block path everywhere
    runs = (wave_contig_table(block_tables, seq_lens,
                              block_size=block_size, chunk=chunk,
                              pool_blocks=NTOK // block_size)
            if coalesce else
            jnp.zeros((Bp, -(-M // chunk)), jnp.int32))

    operands = (qm, k_cache, v_cache)
    in_specs = [
        pl.BlockSpec((G, Hq, C), lambda b, *_: (b, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.ANY),   # k_cache stays in HBM
        pl.BlockSpec(memory_space=pltpu.ANY),   # v_cache stays in HBM
    ]
    if sink is not None:
        in_specs.append(pl.BlockSpec((Hq, 1), lambda b, *_: (0, 0)))
        sink_col = jnp.zeros((Hp, 1), jnp.float32).at[:H, 0].set(
            sink.astype(jnp.float32))
        operands += (jnp.tile(sink_col, (rows, 1)) if rows > 1
                     else sink_col,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(Bp // G,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((G, Hq, Cv), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),                 # m
            pltpu.VMEM((Hq, 1), jnp.float32),                 # l
            pltpu.VMEM((Hq, Cv), jnp.float32),                # acc
            pltpu.VMEM((2, chunk * block_size, Cx), k_cache.dtype),
            # v buffers shrink to a dummy tile when v aliases k
            # (32 sublanes: the int8 tile, legal for every dtype)
            pltpu.VMEM((2, chunk * block_size, Cvx)
                       if v_lanes is None else (1, 32, 128),
                       v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),   # cross-program wave parity
        ],
    )

    def kernel(block_tables_ref, seq_lens_ref, win_lo_ref, runs_ref,
               q_ref, k_hbm, v_hbm, *rest):
        # rest: [sink_ref,] o_ref, m_ref, l_ref, acc_ref, k_bufs, v_bufs,
        # sems, wave_ref
        sink_ref = rest[0] if sink is not None else None
        _paged_attn_kernel(
            block_tables_ref, seq_lens_ref, win_lo_ref, runs_ref,
            q_ref, k_hbm, v_hbm, *rest[sink is not None:],
            block_size=block_size, chunk=chunk, scale=scale,
            num_seqs=Bp, seqs_per_program=G, softcap=softcap,
            quant_lanes=(C if quantized and quant_sections is None
                         else None),
            v_lanes=v_lanes, quant_sections=quant_sections,
            coalesce=coalesce, sink_ref=sink_ref, rows=rows)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, Hq, Cv), q.dtype),
        interpret=interpret,
        name=name,
    )(block_tables, seq_lens, jnp.asarray(win_lo, jnp.int32), runs,
      *operands)
    if v_lanes is not None:
        # MQA: every head's slot is the whole row — no extraction
        return out[:B, :H]
    # row h's useful lanes are its kv head's slot; the rest is cross-slot
    # garbage by construction
    out = out.reshape(Bp * rows, Hp, KVH, Dv)[:N, :H]
    kh = (jnp.arange(H) // g)[None, :, None, None]
    return jnp.take_along_axis(out, kh, axis=2)[:, :, 0].reshape(N, H, Dv)


def pallas_supported(num_heads: int, num_kv_heads: int, head_dim: int,
                     block_size: int, kv_dtype=None,
                     v_dim: int | None = None) -> bool:
    """True if the Pallas decode kernel handles this geometry: the packed
    lane width KVH*Dh must be lane-aligned (128) and KV blocks must be
    8-sublane aligned — 32 for int8 pools (the int8 sublane tile; DMA
    slices must be tile-aligned). Tiny test models (KVH*Dh < 128) fall
    back to XLA. ``v_dim`` (value heads of another size than the keys'):
    the v row KVH*v_dim must be lane-aligned too, on a full-precision
    pool (int8 rows have one encoding, of one width)."""
    sublane = 32 if kv_dtype == jnp.int8 else 8
    return ((num_kv_heads * head_dim) % 128 == 0
            and block_size % sublane == 0
            and num_heads % num_kv_heads == 0
            and (v_dim is None or v_dim == head_dim
                 or ((num_kv_heads * v_dim) % 128 == 0
                     and kv_dtype != jnp.int8)))


def paged_attention(q, k_cache, v_cache, block_tables, seq_lens, *,
                    block_size: int, scale: float,
                    impl: str = "auto",
                    softcap: float | None = None,
                    win_lo: jax.Array | None = None,
                    kv_heads: int | None = None,
                    v_lanes: int | None = None,
                    coalesce: bool = True,
                    chunk_blocks: int | None = None,
                    v_dim: int | None = None,
                    sink: jax.Array | None = None,
                    name: str = "paged_attention",
                    rows: int = 1) -> jax.Array:
    """Dispatch: pallas on TPU (block-major streaming kernel, incl. sliding
    windows, soft-capping, and int8 pools w/ in-row per-token scales), XLA
    gather fallback elsewhere and for geometries the kernel can't tile
    (lane width KVH*Dh < 128; int8 pools with block_size % 32 != 0).
    ``coalesce`` gates the kernel's run-coalesced DMA path (ignored by
    the XLA gather, which has no per-block copy structure);
    ``chunk_blocks`` is the kernel's wave depth (None: ATTN_CHUNK_BLOCKS).

    ``kv_heads``: the true KV head count — required to size the value
    lanes of a tp-GROUPED int8 pool (g scale groups per row; without it
    the row width is assumed to carry exactly one group). Grouped pools
    take the XLA path: the kernel's in-score dequant reads a single
    tail scale group.

    ``v_dim`` / ``sink`` / ``name``: value heads of another size than the
    keys', a scalar a query head in the softmax's denominator, the Pallas
    call's name (paged_attention_pallas; the XLA form takes the first
    two). ``rows`` > 1: several rows a sequence in one pass over its cache,
    the kernel's alone (paged_attention_pallas): the XLA gather has nothing
    to share, and its callers hand it a sequence a row."""
    B, H, Dh = q.shape
    # what only a caller with the new geometry passes: every other call
    # reaches the two forms with the arguments it always had
    extra = {k: v for k, v in (("v_dim", v_dim), ("sink", sink))
             if v is not None}
    if rows > 1:
        extra["rows"] = rows
    groups = 1
    if k_cache.dtype == jnp.int8:
        if kv_heads is None:
            # refuse to infer: a grouped row of width C + g·SCALE_LANES
            # also validates as a single-group row with inflated C, so
            # silent inference could misread scale lanes as values
            raise ValueError(
                "int8 KV pools require kv_heads= (the row width alone "
                "cannot distinguish a tp-grouped pool from a wider "
                "single-group one)")
        C = kv_heads * Dh
        groups = kv_row_groups(k_cache.shape[-1], C)
    if impl == "auto":
        KVH = (kv_heads if kv_heads is not None
               else kv_value_lanes(k_cache) // Dh)
        impl = ("pallas" if kernel_wanted(impl) and groups == 1
                and pallas_supported(H, KVH, Dh, block_size,
                                     kv_dtype=k_cache.dtype, v_dim=v_dim)
                else "xla")
    if rows > 1 and impl not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"rows={rows} is the kernel's form and this call takes the XLA "
            f"gather: hand it a sequence a row")
    if groups > 1 and impl in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"pallas decode kernel cannot read a tp-grouped int8 pool "
            f"({groups} scale groups per row); use the XLA path")
    if impl == "pallas":
        return paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                      seq_lens, block_size=block_size,
                                      scale=scale, softcap=softcap,
                                      win_lo=win_lo, v_lanes=v_lanes,
                                      coalesce=coalesce,
                                      chunk_blocks=chunk_blocks,
                                      name=name, **extra)
    if impl == "pallas_interpret":
        return paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                      seq_lens, block_size=block_size,
                                      scale=scale, softcap=softcap,
                                      win_lo=win_lo, v_lanes=v_lanes,
                                      coalesce=coalesce,
                                      chunk_blocks=chunk_blocks,
                                      interpret=True, name=name, **extra)
    if v_lanes is not None:
        # the v-aliases-k CONTRACT holds on every impl: v IS k's first
        # v_lanes lanes and v_cache is ignored — same validation as the
        # kernel (minus its lane-alignment DMA constraint), so a call
        # cannot silently mean different things on different backends
        C_ = kv_value_lanes(k_cache)
        if C_ // q.shape[-1] != 1 or v_lanes > C_:
            raise ValueError(
                f"v_lanes={v_lanes} needs an MQA-shaped pool "
                f"(KVH == 1) and width <= {C_}")
        out = paged_attention_xla(q, k_cache, k_cache, block_tables,
                                  seq_lens, block_size=block_size,
                                  scale=scale, softcap=softcap,
                                  win_lo=win_lo, kv_heads=kv_heads)
        return out[..., :v_lanes]
    return paged_attention_xla(q, k_cache, v_cache, block_tables, seq_lens,
                               block_size=block_size, scale=scale,
                               softcap=softcap, win_lo=win_lo,
                               kv_heads=kv_heads, **extra)


# ---------------------------------------------------------------------------
# Ragged dispatch: ONE kernel walks a [sum(T_i)] mixed prefill+decode batch
# ---------------------------------------------------------------------------
#
# The unified ragged kernel (PAPERS.md "Ragged Paged Attention"): a flat
# [TT, H, Dh] query batch where sequence s owns the CONSECUTIVE rows
# [starts[s], starts[s]+counts[s]) at consecutive absolute positions
# ending at seq_lens[s]-1. A decode step is counts[s] == 1; a prefill
# chunk is counts[s] == T_chunk — the same kernel serves both in one
# dispatch, so the scheduler can fill every dispatch to token capacity
# with whatever mix of prefill chunks and decode rows is pending
# (engine/ragged.py owns the packing policy and metadata contract).
#
# KV streaming reuses the round-7 run-coalesced wave machinery verbatim
# (_make_wave_dma / wave_contig_table): per sequence, KV streams in
# double-buffered waves exactly as in the decode kernel — but ONE wave
# fetch now feeds ALL of the sequence's query rows (the ragged win: a
# T-row prefill chunk reads each KV byte once instead of T times), and
# a coalescible wave is still one contiguous copy per KV stream.
#
# Query layout is the decode kernel's sparse-slot trick per row
# (qm[r, h, kh(h)*Dh:(kh(h)+1)*Dh] = q[r, h]), so scores for every
# (row, head) are one [Lmax*Hp, C] x [C, cbs] MXU dot per wave and the
# int8 in-row dequant / MLA v-aliases-k / sectioned-int8 modes compose
# unchanged. Per-row causality is pure mask arithmetic: row r of
# sequence s sits at position seq_lens[s] - counts[s] + r and attends
# kv_pos <= that (plus the sliding-window floor win_base[s] + r).
#
# Grid is (S,) sequential; each sequence DMAs its q rows in (dynamic
# start — the batch stays ragged in HBM, no [S, Lmax] dense padding)
# and writes its output rows back the same way. The write covers the
# full static Lmax window; the overhang past counts[s] lands in the
# NEXT sequence's region and is rewritten by it (the grid is
# sequential), so the builder must hand the kernel ASCENDING starts.
#
# Cross-sequence wave prefetch (round 11): the decode kernel's
# wave-parity trick, ported. Scratch persists over the sequential grid,
# so each sequence's LAST KV wave starts the SUCCESSOR's first wave —
# without it every sequence exposes one first-wave DMA latency (at
# short ragged spans that is 1 exposed wave in 2, the same economics
# the decode kernel measured at ~44% of HBM peak). Buffer slots follow
# a GLOBAL wave parity carried in SMEM (wave_ref) rather than the
# per-sequence chunk index, so producer and consumer agree on the
# double-buffer slot across sequence boundaries. `seq_shape` is the
# ONE home of a sequence's wave geometry — the prefetching predecessor
# and the consuming sequence both derive (nb, nc, start_ci) from it,
# so a prefetch is issued iff the consumer will wait for it. A
# zero-row or zero-wave sequence breaks the chain (its successor
# starts its own first wave), exactly like the decode kernel's
# empty-predecessor case. ``prefetch=False`` keeps the round-10 walk
# (the A/B baseline; BIT-identical output either way).

# per-sequence sliding-window base for GLOBAL layers: hugely negative so
# win_base + row never masks anything (a real floor is pos0 - window,
# bounded below by -window)
RAGGED_WIN_SENTINEL = -(1 << 30)


def _ragged_attn_kernel(block_tables_ref, starts_ref, counts_ref,
                        seq_lens_ref, win_base_ref, runs_ref,
                        q_hbm, k_hbm, v_hbm, o_hbm,
                        q_buf, o_buf, m_ref, l_ref, acc_ref,
                        k_bufs, v_bufs, sems, qo_sem, wave_ref,
                        *, block_size: int, chunk: int, scale: float,
                        Lmax: int, Hp: int,
                        softcap: float | None = None,
                        quant_lanes: int | None = None,
                        v_lanes: int | None = None,
                        quant_sections: tuple | None = None,
                        coalesce: bool = True,
                        prefetch: bool = True):
    """One grid program = one sequence: DMA its q rows, stream its KV
    waves (shared machinery), online-softmax all rows at once, DMA the
    output rows back. q_hbm/o_hbm: [TT + Lmax, Hp, C/Cv] (ANY memory,
    Lmax overhang rows so the static-window copies stay in bounds);
    scalar-prefetched metadata as in the module comment above;
    wave_ref: [1] SMEM global wave parity carried ACROSS programs (the
    cross-sequence prefetch chain — module comment)."""
    s = pl.program_id(0)
    S = pl.num_programs(0)
    quantized = quant_lanes is not None
    C = quant_lanes if quantized else q_buf.shape[-1]
    dequant_tile, dequant_tile_sections = _make_dequant_tile(
        quant_lanes, quant_sections, C)
    wave_dma = _make_wave_dma(
        block_tables_ref, runs_ref, k_hbm, v_hbm, k_bufs, v_bufs, sems,
        block_size=block_size, chunk=chunk, v_lanes=v_lanes,
        coalesce=coalesce)

    def seq_shape(si):
        """(num_blocks, num_chunks, start_ci) for sequence si — the ONE
        home of the wave geometry the prefetch chain's producer and
        consumer must agree on. Zero rows → zero waves; start_ci is
        clamped to nc so `nc - start_ci` IS the wave count."""
        nb = (seq_lens_ref[si] + block_size - 1) // block_size
        nc = (nb + chunk - 1) // chunk
        nc = jnp.where(counts_ref[si] > 0, nc, 0)
        # sliding windows: waves entirely below every row's window are
        # dead — the FIRST row's floor is the loosest bound
        sc = jnp.minimum(
            jnp.maximum(win_base_ref[si] + 1, 0) // (chunk * block_size),
            nc)
        return nb, nc, sc

    L = counts_ref[s]

    if prefetch:
        @pl.when(s == 0)
        def _():
            wave_ref[0] = 0

    @pl.when(L > 0)
    def _():
        start = starts_ref[s]
        seq_len = seq_lens_ref[s]
        win_base = win_base_ref[s]
        pos0 = seq_len - L           # row r sits at position pos0 + r
        nb, nc, start_ci = seq_shape(s)

        if prefetch:
            p0 = wave_ref[0]  # global parity of this seq's first wave
            # this sequence's first wave was already started by the
            # previous sequence's last loop iteration — unless there is
            # no predecessor or the predecessor had no waves
            if S > 1:
                _, prev_nc, prev_sc = seq_shape(jnp.maximum(s - 1, 0))
                pred_started = (s > 0) & (prev_sc < prev_nc)
                nsq = jnp.minimum(s + 1, S - 1)
                next_nb, next_nc, next_sc = seq_shape(nsq)
            else:
                pred_started = jnp.bool_(False)
        else:
            p0 = jnp.int32(0)
            pred_started = jnp.bool_(False)

        qc = pltpu.make_async_copy(
            q_hbm.at[pl.ds(start, Lmax)], q_buf, qo_sem)
        qc.start()

        @pl.when((start_ci < nc) & ~pred_started)
        def _():
            # empty wave range: an unwaited start would leak semaphore
            # signal into the next sequence's waves
            wave_dma("start", s, start_ci, jax.lax.rem(p0, 2), nb)
        qc.wait()
        qm = q_buf[...].reshape(Lmax * Hp, C).astype(jnp.float32) * scale

        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

        cbs = chunk * block_size
        row = jax.lax.broadcasted_iota(
            jnp.int32, (Lmax * Hp, cbs), 0) // Hp
        rpos = pos0 + row                       # absolute row positions
        live = row < L                          # overhang rows are dead
        win_lo_r = win_base + row               # sentinel stays huge-neg

        def body(ci, _):
            slot = jax.lax.rem(p0 + ci - start_ci, 2)

            @pl.when(ci + 1 < nc)
            def _():
                wave_dma("start", s, ci + 1, 1 - slot, nb)

            if prefetch and S > 1:
                @pl.when((ci + 1 >= nc) & (s + 1 < S)
                         & (next_sc < next_nc))
                def _():   # last wave: prefetch the successor's first
                    wave_dma("start", nsq, next_sc, 1 - slot, next_nb)

            wave_dma("wait", s, ci, slot, nb)
            if quant_sections is not None:
                k = dequant_tile_sections(k_bufs[slot])   # [cbs, C] f32
                v = k[:, :v_lanes]        # sections mode implies alias
            elif quantized:
                k = dequant_tile(k_bufs[slot])
                v = dequant_tile(v_bufs[slot])
            else:
                k = k_bufs[slot].astype(jnp.float32)
                v = (k[:, :v_lanes] if v_lanes is not None
                     else v_bufs[slot].astype(jnp.float32))
            sm = jax.lax.dot_general(qm, k, (((1,), (1,)), ((), ())))
            if softcap:
                sm = softcap_scores(sm, softcap)
            kv_pos = ci * cbs + jax.lax.broadcasted_iota(
                jnp.int32, sm.shape, dimension=1)
            mask = ((kv_pos <= rpos) & (kv_pos < seq_len) & live
                    & (kv_pos > win_lo_r))
            sm = jnp.where(mask, sm, NEG_INF)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev,
                                jnp.max(sm, axis=1, keepdims=True))
            p = jnp.exp(sm - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1,
                                                  keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[:] = m_new
            return 0

        jax.lax.fori_loop(start_ci, nc, body, 0)
        o_buf[...] = (acc_ref[:] /
                      jnp.maximum(l_ref[:], 1e-20)).reshape(
            Lmax, Hp, acc_ref.shape[-1]).astype(o_buf.dtype)
        oc = pltpu.make_async_copy(
            o_buf, o_hbm.at[pl.ds(start, Lmax)], qo_sem)
        oc.start()
        oc.wait()

        if prefetch:
            # hand the successor its first-wave parity: the last-wave
            # prefetch above placed it at rem(p0 + waves, 2)
            wave_ref[0] = jax.lax.rem(
                p0 + jnp.maximum(nc - start_ci, 0), 2)


def ragged_paged_attention_pallas(q: jax.Array, k_cache: jax.Array,
                                  v_cache: jax.Array,
                                  block_tables: jax.Array,
                                  seq_starts: jax.Array,
                                  seq_counts: jax.Array,
                                  seq_lens: jax.Array, *,
                                  block_size: int, scale: float,
                                  max_rows: int,
                                  softcap: float | None = None,
                                  win_base: jax.Array | None = None,
                                  chunk_blocks: int | None = None,
                                  v_lanes: int | None = None,
                                  quant_sections: tuple | None = None,
                                  coalesce: bool = True,
                                  prefetch: bool = True,
                                  interpret: bool = False) -> jax.Array:
    """Ragged mixed prefill+decode attention in ONE dispatch.

    q: [TT, H, Dh] flat token rows; block_tables: [S, M]; sequence s
    owns rows [seq_starts[s], seq_starts[s]+seq_counts[s]) (starts must
    ascend in s; counts[s] == 0 skips the sequence) at consecutive
    positions ending at seq_lens[s]-1. ``max_rows`` (static) bounds any
    sequence's row count per dispatch and sizes the kernel's q/acc VMEM
    window — the builder splits longer chunks across dispatches.
    ``win_base``: [S] first-row sliding floor (pos0 - window), or
    RAGGED_WIN_SENTINEL for global layers / None.

    int8 pools (in-row scales), MLA v-aliases-k (``v_lanes``) and
    sectioned-int8 MLA rows (``quant_sections``) follow the decode
    kernel's contracts exactly. Returns [TT, H, Dh-or-v_lanes]; rows not
    owned by any sequence return garbage (the engine reads only sample
    rows and the tests compare only owned rows).

    ``prefetch`` (default on): carry the wave parity across the
    sequential grid so each sequence's last KV wave starts the
    successor's first — the cross-sequence prefetch chain (module
    comment; BIT-identical output, asserted across the geometry sweep).
    False keeps the round-10 walk with one exposed first-wave latency
    per sequence (the A/B baseline and escape hatch)."""
    TT, H, Dh = q.shape
    NTOK, Cx = k_cache.shape
    S, M = block_tables.shape
    quantized = k_cache.dtype == jnp.int8
    if quant_sections is not None:
        if not quantized or v_lanes is None:
            raise ValueError("quant_sections needs an int8 pool and "
                             "v_lanes (the MLA sectioned layout)")
        C = Dh          # dequant produces query-width tiles (KVH == 1)
    else:
        C = kv_value_lanes(k_cache)
    KVH = C // Dh
    if not pallas_supported(H, KVH, Dh, block_size,
                            kv_dtype=k_cache.dtype):
        raise ValueError(
            f"unsupported ragged pallas geometry (H={H}, KVH={KVH}, "
            f"Dh={Dh}, block_size={block_size}, kv={k_cache.dtype}) — "
            f"see pallas_supported")
    if v_lanes is not None and (KVH != 1 or v_lanes % 128 != 0
                                or v_lanes > C):
        raise ValueError(
            f"v_lanes={v_lanes} needs an MQA-shaped pool (KVH == 1, got "
            f"{KVH}) and a 128-aligned width <= {C}")
    if v_lanes is not None and quantized and quant_sections is None:
        raise ValueError(
            "v_lanes on a single-scale int8 pool is not supported "
            "(sectioned MLA pools pass quant_sections)")
    Cv = C if v_lanes is None else v_lanes
    g = H // KVH
    if chunk_blocks is None:
        chunk_blocks = ATTN_CHUNK_BLOCKS
    chunk = max(1, min(chunk_blocks, M))
    Hp = max(8, H)
    Lmax = max(8, int(max_rows))     # 8-sublane floor for the q window
    # sparse slot placement per ROW (the decode kernel's trick), with
    # Lmax overhang rows so the per-sequence static-window DMAs stay in
    # bounds
    qm = jnp.zeros((TT + Lmax, Hp, KVH, Dh), q.dtype)
    qm = qm.at[:TT, jnp.arange(H), jnp.arange(H) // g, :].set(q)
    qm = qm.reshape(TT + Lmax, Hp, C)
    if win_base is None:
        win_base = jnp.full((S,), RAGGED_WIN_SENTINEL, jnp.int32)
    runs = (wave_contig_table(block_tables, seq_lens,
                              block_size=block_size, chunk=chunk,
                              pool_blocks=NTOK // block_size)
            if coalesce else
            jnp.zeros((S, -(-M // chunk)), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(S,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.ANY),   # q stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),   # k_cache
            pl.BlockSpec(memory_space=pltpu.ANY),   # v_cache
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[
            pltpu.VMEM((Lmax, Hp, C), q.dtype),               # q window
            pltpu.VMEM((Lmax, Hp, Cv), q.dtype),              # o window
            pltpu.VMEM((Lmax * Hp, 1), jnp.float32),          # m
            pltpu.VMEM((Lmax * Hp, 1), jnp.float32),          # l
            pltpu.VMEM((Lmax * Hp, Cv), jnp.float32),         # acc
            pltpu.VMEM((2, chunk * block_size, Cx), k_cache.dtype),
            pltpu.VMEM((2, chunk * block_size, Cx)
                       if v_lanes is None else (1, 32, 128),
                       v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,          # q/o window copies
            pltpu.SMEM((1,), jnp.int32),   # cross-sequence wave parity
        ],
    )

    def kernel(block_tables_ref, starts_ref, counts_ref, seq_lens_ref,
               win_base_ref, runs_ref, q_hbm, k_hbm, v_hbm, o_hbm,
               q_buf, o_buf, m_ref, l_ref, acc_ref, k_bufs, v_bufs,
               sems, qo_sem, wave_ref):
        _ragged_attn_kernel(
            block_tables_ref, starts_ref, counts_ref, seq_lens_ref,
            win_base_ref, runs_ref, q_hbm, k_hbm, v_hbm, o_hbm,
            q_buf, o_buf, m_ref, l_ref, acc_ref, k_bufs, v_bufs,
            sems, qo_sem, wave_ref,
            block_size=block_size, chunk=chunk, scale=scale,
            Lmax=Lmax, Hp=Hp, softcap=softcap,
            quant_lanes=(C if quantized and quant_sections is None
                         else None),
            v_lanes=v_lanes, quant_sections=quant_sections,
            coalesce=coalesce, prefetch=prefetch)

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((TT + Lmax, Hp, Cv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_tables, jnp.asarray(seq_starts, jnp.int32),
      jnp.asarray(seq_counts, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(win_base, jnp.int32), runs, qm, k_cache, v_cache)
    out = out[:TT]
    if v_lanes is not None:
        # MQA: every head's slot is the whole row — no extraction
        return out[:, :H]
    out = out.reshape(TT, Hp, KVH, Dh)[:, :H]
    kh = (jnp.arange(H) // g)[None, :, None, None]
    return jnp.take_along_axis(out, kh, axis=2)[:, :, 0].reshape(
        TT, H, Dh)


def ragged_prefetch_counts(seq_counts, seq_lens, win_base=None, *,
                           block_size: int,
                           blocks_per_table: int | None = None,
                           chunk_blocks: int | None = None) -> dict:
    """Host-side count of the ragged kernel's cross-sequence prefetch
    chain over one dispatch — the CPU-side truth the ragged prefetch
    gauges and bench ride (the dma_copy_counts precedent: the metric is
    the kernel's wave walk mirrored exactly, so it is honest on CPU
    where the XLA fallback runs no kernel at all).

    Per sequence (in grid order): it has a first wave iff it owns rows
    and at least one KV wave survives its window floor (the kernel's
    `seq_shape`); that first wave is PREFETCHED iff the immediately
    preceding sequence also had >= 1 wave (its last wave started ours —
    the parity chain). ``win_base`` None = global layers (floor 0).
    Returns {first_waves, prefetched, exposed, hit_ratio}."""
    counts = np.asarray(seq_counts)
    sl = np.asarray(seq_lens)
    if chunk_blocks is None:
        chunk_blocks = ATTN_CHUNK_BLOCKS
    chunk = max(1, (min(chunk_blocks, blocks_per_table)
                    if blocks_per_table else chunk_blocks))
    nb = -(-sl // block_size)
    nc = np.where(counts > 0, -(-nb // chunk), 0)
    if win_base is None:
        sc = np.zeros_like(nc)
    else:
        sc = np.minimum(np.maximum(np.asarray(win_base) + 1, 0)
                        // (chunk * block_size), nc)
    has = (nc - sc) > 0
    first_waves = int(has.sum())
    prefetched = int((has[1:] & has[:-1]).sum())
    return {"first_waves": first_waves, "prefetched": prefetched,
            "exposed": first_waves - prefetched,
            "hit_ratio": prefetched / max(first_waves, 1)}


# What the ragged kernel asks of Mosaic's scoped VMEM, 16 MiB on v5e, in
# bytes of the buffers it really holds (ragged_supported adds them up):
#
#   window   [Lmax*Hp, C]     15 B/element: the q and o windows in bf16
#                             (2 + 2), the f32 accumulator (4) and the f32
#                             scaled copy of q the wave loop keeps live (4),
#                             plus 3 B of compiler staging
#   rows     [Lmax*Hp, 1]     2048 B/row: m and l are f32 columns, padded to
#                             a 128-lane tile (512 B each), and the loop
#                             holds a second copy of each (m_new, alpha)
#   scores   [Lmax*Hp, wave]  6 B/element: the f32 score/probability tile
#                             and its mask
#   KV waves [2, wave, lanes] the K and the V tile, double-buffered, at the
#                             pool's own lane width and itemsize — exact
#
# The explicit scratch is exact; the 3 B of staging and the 6 B per score
# element are what the compiler's own temporaries came to, calibrated on
# deviceless v5e compiles of 17 geometries (C 128..1024, Hp 8..64, waves
# of 256..2048 tokens, bf16 and int8 pools, MLA's v-aliases-k form) and
# then held against three they had not seen (gemma-2b, gemma2-9b, MLA at
# 128 heads): at all 20 the sum stops at 0.73..0.98 of the row budget
# where the compiler stops and never beyond it; the low end is where V
# aliases K and its wave tile is charged although the kernel has none
# (tests/test_tpu_compile.py pins both sides at 1B, 8B and MQA widths).
_RAGGED_VMEM_LIMIT = 16 << 20
_RAGGED_WINDOW_BYTES = 2 + 2 + 4 + 4 + 3
_RAGGED_ROW_BYTES = 4 * 512
_RAGGED_SCORE_BYTES = 6


def ragged_supported(num_heads: int, num_kv_heads: int, head_dim: int,
                     block_size: int, max_rows: int,
                     kv_dtype=None) -> bool:
    """True if the ragged Pallas kernel handles this geometry at this
    per-sequence row budget: the decode kernel's lane/sublane
    constraints (pallas_supported) plus its VMEM working set fitting the
    scoped limit — [Lmax*Hp, C] f32 scores duplicate query rows across
    sublanes, so large GQA geometries bound Lmax (MQA/MLA pools,
    KVH == 1, carry no duplication and take the deepest windows), and a
    larger KV block widens every wave, which buys a smaller Lmax. The
    q window is DMA-sliced per sequence with the head axis second-minor,
    so the head count must sit on the 8-sublane tiling: Mosaic refuses
    the slice at 12, 14 or 28 heads (the decode and flash kernels take
    them)."""
    if not pallas_supported(num_heads, num_kv_heads, head_dim,
                            block_size, kv_dtype=kv_dtype):
        return False
    Hp = max(8, num_heads)
    if Hp % 8 != 0:
        return False
    rows = max(8, max_rows) * Hp                          # Lmax * Hp
    C = num_kv_heads * head_dim
    wave = ATTN_CHUNK_BLOCKS * block_size
    lanes = C + KV_SCALE_LANES if kv_dtype == jnp.int8 else C
    itemsize = jnp.dtype(kv_dtype or jnp.bfloat16).itemsize
    kv_waves = 2 * 2 * wave * lanes * itemsize
    windows = rows * (_RAGGED_WINDOW_BYTES * C + _RAGGED_ROW_BYTES
                      + _RAGGED_SCORE_BYTES * wave)
    return windows + kv_waves <= _RAGGED_VMEM_LIMIT


@functools.cache
def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: it must never read
    # as "not a TPU" and turn every attn_impl="auto" site into XLA
    return jax.devices()[0].platform == "tpu"


def kernel_wanted(impl: str) -> bool:
    """Whether ``impl`` asks for a Pallas kernel where the geometry has
    one: forced by name, or "auto" on a TPU. The one statement of what
    "auto" means for the paged kernels; the model code asks it too (to
    know whether a kernel may sit inside what it hands to shard_map)."""
    return impl in ("pallas", "pallas_interpret") or (
        impl == "auto" and _on_tpu())
