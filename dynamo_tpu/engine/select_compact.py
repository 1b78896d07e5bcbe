"""Pallas exact top-k of every row as a threshold search and a compaction:
nothing is sorted, and no score is moved.

Why (PERF.md section 6, PR 34): DeepSeek Sparse Attention keeps, per query,
the ``index_topk`` best of up to 17,408 index scores, and nobody reads the
order of the kept. A stable sort by score that carried position and pool
row was 1.25 ms a layer on a v5e ([64, 17408], three operands); a bisection
for the k-th score followed by an XLA sort of one packed operand was 0.76
(0.45 the sort, 0.29 the bisection's 47 row counts, each a pass over HBM).
Here eight rows at a time stay in VMEM from the first compare to the last
move: 0.16 ms a layer.

Per row, on ``order`` (uint32 that order as the scores do, 0 = not live:
``models/mla.py`` ``_order_bits``):

1. **the k-th largest by bisection on the bits** — 32 steps, each one
   compare and one row count; no data-dependent control flow;
2. **the ties** — taken are the entries above the threshold and the first
   ``k − count(above)`` equal to it in position order: the same bisection
   over ``S − position`` of the ties finds the last one that fits;
3. **rank** — a running count of the taken (log-step scan along the lanes);
4. **compaction** — a taken entry at position p with rank r moves
   ``p − r`` lanes towards lane 0, one power of two per stage, lowest bit
   first. The distances never decrease along the row, so no two entries
   ever meet (the network of Hacker's Delight's ``compress``); the values
   that ride along (a packed key, or a position and a pool row) move with
   them. The first k lanes are the result, in position order.

Work is O(S log S) lane operations on data that never leaves VMEM; a sort
network is O(S log² S) and XLA's streams every stage's operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["compact_top_k", "NOT_TAKEN"]

NOT_TAKEN = np.uint32(0xFFFFFFFF)   # the first value of a slot nothing fills
_ROWS = 8                           # rows per program: one sublane tile
_LANE = 128
_I32 = jnp.int32
_SIGN = np.int32(-2 ** 31)
_VMEM_LIMIT = 64 * 1024 * 1024


def _count(mask) -> jax.Array:
    return jnp.sum(mask.astype(_I32), axis=1, keepdims=True)


def _kth_largest(x, k, bits: int) -> jax.Array:
    """The k-th largest of every row of x as UNSIGNED bits below 2**bits
    (int32 [R, S]; k an int or [R, 1], at most S), built from the top bit
    down: a bit stays if at least k entries reach the candidate. → [R, 1].
    Signed compares order unsigned bits once the sign bit is flipped."""
    flipped = x ^ _SIGN
    t = jnp.zeros((x.shape[0], 1), _I32)
    for b in reversed(range(bits)):
        cand = t | (_SIGN if b == 31 else np.int32(1 << b))
        t = jnp.where(_count(flipped >= (cand ^ _SIGN)) >= k, cand, t)
    return t


def _kernel(order_ref, *refs, k: int):
    n = len(refs) // 2
    value_refs, out_refs = refs[:n], refs[n:]
    order = order_ref[...]
    R, S = order.shape
    lane = jax.lax.broadcasted_iota(_I32, (R, S), 1)

    t = _kth_largest(order, k, 32)
    above = (order ^ _SIGN) > (t ^ _SIGN)
    tie = (order == t) & (order != 0)
    back = jnp.where(tie, S - lane, 0)               # unique among the ties
    t2 = _kth_largest(back, k - _count(above), S.bit_length())
    take = above | (tie & (back >= t2))

    rank = take.astype(_I32)                         # inclusive running count
    step = 1
    while step < S:
        rank = rank + jnp.where(lane >= step, pltpu.roll(rank, step, 1), 0)
        step *= 2
    # lanes still to go towards lane 0, with "holds a taken entry" in bit 0
    todo = jnp.where(take, ((lane - (rank - 1)) << 1) | 1, 0)
    values = [ref[...] for ref in value_refs]
    step, bit = 1, 1
    while step < S:
        moving = ((todo >> bit) & 1) == 1
        # an entry never has further to go than its lane: what the rotation
        # wraps around the row's end is always empty
        arriving = pltpu.roll(jnp.where(moving, todo, 0), S - step, 1)
        lands = (arriving & 1) == 1
        todo = jnp.where(lands, arriving, jnp.where(moving, 0, todo))
        values = [jnp.where(lands, pltpu.roll(v, S - step, 1), v)
                  for v in values]
        step, bit = step * 2, bit + 1
    width = out_refs[0].shape[1]
    filled = (todo[:, :width] & 1) == 1
    for i, (ref, v) in enumerate(zip(out_refs, values)):
        ref[...] = jnp.where(filled, v[:, :width], -1 if i == 0 else 0)


def _as_i32(x, shape) -> jax.Array:
    x = jnp.broadcast_to(x, shape)
    return x if x.dtype == _I32 else jax.lax.bitcast_convert_type(x, _I32)


def compact_top_k(order, values, k: int, *, interpret: bool = False):
    """order [N, S] uint32 (0 = not live, larger = better); values: arrays
    [N, S] or [S], int32 or uint32, that describe each position. → the
    values of the k best positions of every row (ties to the lower
    position), [N, k] each, in position order; where fewer than k positions
    are live the tail of the first array is ``NOT_TAKEN`` (all ones: no
    live position may carry that value) and of the others 0."""
    N, S = order.shape
    assert 0 < k <= S, (k, S)
    n_pad, s_pad = -N % _ROWS, -S % _LANE
    width = k + -k % _LANE
    pad = lambda x: jnp.pad(x, ((0, n_pad), (0, s_pad)))  # noqa: E731
    operands = [pad(_as_i32(x, (N, S))) for x in (order, *values)]
    rows = pl.BlockSpec((_ROWS, S + s_pad), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=((N + n_pad) // _ROWS,),
        in_specs=[rows] * len(operands),
        out_specs=[pl.BlockSpec((_ROWS, width), lambda i: (i, 0))
                   ] * len(values),
        out_shape=[jax.ShapeDtypeStruct((N + n_pad, width), _I32)
                   ] * len(values),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_select_compact",
    )(*operands)
    return tuple(
        jax.lax.bitcast_convert_type(out[:N, :k], jnp.asarray(v).dtype)
        for out, v in zip(outs, values))
