"""Pallas grouped matmul for the routed experts: rows sorted by expert,
each contiguous group of rows times its own expert's weight.

Why (PERF.md section 6, PR 32): the dense-over-experts einsum
(models/llama.py run_experts_dense) runs every expert for every row and
multiplies E - top_k of the E results by zero. Above ~120 rows on a v5e
that form is compute-bound, so a 2,048-row prefill of a 60-expert top-4
model spent 15x the routed FLOPs. Here only the (row, expert) pairs the
router picked are computed: the caller sorts the pairs by expert and
gathers their rows once into ``x [M, K]``; ``group_sizes [E]`` says how
many consecutive rows belong to each expert.

The design is the one of JAX's megablox ``gmm``: no padded copy per
expert. The row axis is cut into tiles of ``tm`` rows wherever the
groups fall, and the grid walks *visits*: (group, row tile) pairs, one
per tile a group touches, in row order. A tile that straddles two groups
is visited once by each, and each visit stores only its own group's rows
(a masked select over the output block, which stays in VMEM between two
consecutive visits of one tile). A group touches at most
``ceil(size / tm) + 1`` tiles, so ``M / tm + E - 1`` visits always
suffice; that is the static grid, and the visits past the real count
compute nothing and move no block. Rows past ``sum(group_sizes)`` belong
to no group: they are never computed and their output rows are never
written (the caller must not read them).

int8 weights stay int8 in HBM (engine/quant.py QuantizedArray, one scale
per (expert, out-channel)): the ``[K, tn]`` weight tile of the visit's
expert is converted in VMEM and the scale multiplies the float32
accumulator after the contraction: qeinsum's rule. The converted tile is
kept in a VMEM scratch while consecutive visits stay with one expert
(the grid runs out-channel tiles outermost, visits innermost, so every
(expert, out-channel tile) block is fetched from HBM exactly once).

The weights may be the whole stack of a scanned model, ``[L, E, K, N]``
with the layer as a traced scalar: the layer is then one more block
index. A custom call wants its operands whole, so handing it the scan's
per-layer slice makes XLA copy ``[E, K, N]`` out of the stack first
(measured, PR 32: 0.73 s of a 2.36 s prefill program; an XLA fusion
reads the slice in place, a kernel cannot).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import QuantizedArray

__all__ = ["grouped_matmul", "grouped_matmul_eligible", "ROW_TILE"]

ROW_TILE = 128            # rows per visit: the MXU's height on a v5e
_LANE = 128
# elements of one [K, tn] weight tile: at int8 the double-buffered tile
# plus its converted bf16 copy is 4 bytes an element, 12 MB at this cap
_MAX_TILE_ELEMS = 3 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def _out_tile(k: int, n: int) -> int:
    """Out-channel tile: the widest lane-aligned divisor of ``n`` whose
    [k, tn] weight tile stays under the VMEM cap; an ``n`` off the lane
    grid is taken whole (a block equal to the array's extent is always a
    legal block)."""
    if n % _LANE:
        return n
    best = _LANE
    for tn in range(_LANE, n + 1, _LANE):
        if n % tn == 0 and k * tn <= _MAX_TILE_ELEMS:
            best = tn
    return best


def grouped_matmul_eligible(k: int, n: int) -> bool:
    """Shapes the kernel builds for the chip: a lane-aligned contraction
    and out width, and a [k, 128] weight tile that fits."""
    return (k % _LANE == 0 and n % _LANE == 0
            and k * _LANE <= _MAX_TILE_ELEMS)


def _visits(group_sizes: jax.Array, m: int, tm: int):
    """Per grid step: the group it works for and the row tile it works
    on, from ``group_sizes`` alone. Steps past the last real visit repeat
    it (no block index changes, so nothing is fetched or written)."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    count = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first, 0)
    visit_ends = jnp.cumsum(count)
    total = visit_ends[-1]
    steps = jnp.arange(m // tm + E - 1, dtype=jnp.int32)
    step = jnp.clip(steps, 0, jnp.maximum(total - 1, 0))
    gid = jnp.minimum(
        jnp.searchsorted(visit_ends, step, side="right"), E - 1
    ).astype(jnp.int32)
    tile = first[gid] + (step - (visit_ends[gid] - count[gid]))
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    return gid, tile, offsets, total.astype(jnp.int32).reshape(1)


def _kernel(gid_ref, tile_ref, off_ref, total_ref, layer_ref, x_ref, w_ref,
            *rest, tm: int, quantized: bool):
    if quantized:
        s_ref, o_ref, wb_ref = rest
    else:
        (o_ref,) = rest
    i = pl.program_id(1)

    @pl.when(i < total_ref[0])
    def _visit():
        g = gid_ref[i]
        if quantized:
            # the converted tile outlives the visit: convert only when
            # the expert (or, at i == 0, the out-channel tile) changes
            @pl.when((i == 0) | (g != gid_ref[jnp.maximum(i - 1, 0)]))
            def _convert():
                wb_ref[...] = w_ref[...].astype(wb_ref.dtype)
            acc = jnp.dot(x_ref[...], wb_ref[...],
                          preferred_element_type=jnp.float32)
            acc = acc * s_ref[...]
        else:
            acc = jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
        rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(x: jax.Array, w, group_sizes: jax.Array,
                   layer: jax.Array | None = None, *,
                   tm: int = ROW_TILE, interpret: bool = False) -> jax.Array:
    """``out[r] = x[r] @ w[g]`` for every row r of group g, the groups
    being consecutive runs of ``group_sizes[g]`` rows of ``x [M, K]``.

    ``w``: ``[E, K, N]`` array, or a per-channel int8 QuantizedArray
    (``q [E, K, N]``, ``scale [E, 1, N]``); with ``layer`` (a traced
    scalar) the stack of every layer, ``[L, E, K, N]`` (scale
    ``[L, E, 1, N]``), of which layer ``layer`` is read in place. Returns
    ``[M, N]`` in ``x.dtype``; rows past ``sum(group_sizes)`` are left
    unwritten. ``M`` must be a multiple of ``tm``."""
    quantized = isinstance(w, QuantizedArray)
    if quantized and (w.group or w.packed4):
        raise NotImplementedError(
            "grouped_matmul takes per-channel int8 expert weights only")
    if layer is None:             # one layer's stacks: a stack of one
        w = jax.tree.map(lambda a: a[None], w)
        layer = jnp.zeros((), jnp.int32)
    wq = w.q if quantized else w
    M, K = x.shape
    _L, E, Kw, N = wq.shape
    assert K == Kw and M % tm == 0, (x.shape, wq.shape, tm)
    tn = _out_tile(K, N)
    gid, tile, offsets, total = _visits(group_sizes.astype(jnp.int32), M, tm)

    in_specs = [
        pl.BlockSpec((tm, K), lambda n, i, gid, tile, off, tot, layer:
                     (tile[i], 0)),
        pl.BlockSpec((None, None, K, tn),
                     lambda n, i, gid, tile, off, tot, layer:
                     (layer[0], gid[i], 0, n)),
    ]
    operands = [x, wq]
    scratch = []
    if quantized:
        in_specs.append(
            pl.BlockSpec((None, None, 1, tn),
                         lambda n, i, gid, tile, off, tot, layer:
                         (layer[0], gid[i], 0, n)))
        operands.append(w.scale.astype(jnp.float32))
        scratch.append(pltpu.VMEM((K, tn), x.dtype))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, gid.shape[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, gid, tile, off, tot, layer:
                (tile[i], n)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_experts",
    )(gid, tile, offsets, total, layer.astype(jnp.int32).reshape(1),
      *operands)
