"""Pallas grouped-dequant matmul: x @ W for packed-int4 weights.

The XLA lowering of the grouped-int4 contraction is a batched dot whose
per-group partial [N, D/128, F] MATERIALIZES in HBM — measured ~17 GB of
activation traffic per 70B-shard decode step (21.4 ms, slower than
int8). This kernel is the reason int4 wins: it streams the PACKED
weights (two signed nibbles per int8 byte, quant.pack_int4_rows) from
HBM at 0.5 B/elem, splits nibbles on the VPU in VMEM, runs two MXU dots
per 128-row group (even/odd contraction rows — no interleave needed),
and folds the per-(group, out-channel) scale into the f32 accumulator.
Nothing but x and y ever touches HBM at full width.

Reference analog: the CUDA ecosystem's weight-only-quant GEMMs (AWQ /
Marlin kernels) that the reference reaches through its engines; here it
is a first-class Pallas kernel, the same way attention.py owns paged
attention.

Grid: (n_tiles, f_tiles, d_steps), d innermost/sequential — each d step
covers GD groups (so every block meets Mosaic's >=8x128 tiling; GD is
the largest of 8/4/2 dividing the group count), the f32 accumulator
lives in VMEM scratch across the d sweep, and the output writes once
per (n, f) tile. Scales ride as one full-row [nd, TF] block per f tile
(tiny) with a dynamic sublane load per group. Pallas double-buffers the
HBM block fetches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 128          # contraction rows per scale group (quant.GROUP_SIZE)
_HG = GROUP // 2     # packed bytes (and even/odd x columns) per group

__all__ = ["grouped_int4_matmul", "grouped_kernel_eligible"]


def _kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref,
            *, nd_steps: int, gd: int):
    """One (n, f, d) grid step: for each of the gd groups in this step,
    acc += (xe_g @ lo_g + xo_g @ hi_g) * s_row_g.

    xe/xo: [TN, gd*_HG] this step's even/odd contraction rows of x;
    w: [gd*_HG, TF] packed bytes; s: [nd, TF] ALL group scales for this
    f tile; o: [TN, TF]; acc scratch [TN, TF] f32.
    """
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xe = xe_ref[...]
    xo = xo_ref[...]
    # nibble split in-register: Mosaic has no int8 shifts (arith.shli on
    # i8 fails to legalize) — widen the tile to i32 for the shifts and
    # narrow straight into the dot dtype
    w = w_ref[...].astype(jnp.int32)
    lo = (jnp.left_shift(w, 28) >> 28).astype(xe.dtype)
    hi = (w >> 4).astype(xo.dtype)
    acc = acc_ref[...]
    for g in range(gd):
        sl = slice(g * _HG, (g + 1) * _HG)
        part = (jax.lax.dot(xe[:, sl], lo[sl],
                            preferred_element_type=jnp.float32)
                + jax.lax.dot(xo[:, sl], hi[sl],
                              preferred_element_type=jnp.float32))
        srow = s_ref[pl.ds(d * gd + g, 1), :]          # [1, TF] dynamic
        acc = acc + part * srow
    acc_ref[...] = acc

    @pl.when(d == nd_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gd_for(nd: int) -> int:
    for gd in (8, 4, 2):
        if nd % gd == 0:
            return gd
    return 0


def grouped_kernel_eligible(n: int, d: int, f: int, group: int) -> bool:
    """Shapes the kernel tiles: the group-128 encoding, an even group
    count (so x/w blocks reach 128 lanes), and a lane-aligned output
    width. Everything else takes the XLA path."""
    return (group == GROUP and d % GROUP == 0 and f % 128 == 0
            and _gd_for(d // GROUP) > 0)


def grouped_int4_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                        *, interpret: bool = False) -> jax.Array:
    """x [N, D] @ packed-int4 W: packed [D/2, F] int8 (pack_int4_rows
    layout: byte d holds rows 2d/2d+1), scale [D/GROUP, F] f32.
    Returns [N, F] in x.dtype."""
    N, D = x.shape
    _half, F = packed.shape
    nd = D // GROUP
    gd = _gd_for(nd)

    # even/odd contraction rows, laid out group-major so each grid step
    # reads one contiguous [gd*_HG] span: [N, nd*_HG]
    xs = x.reshape(N, nd, _HG, 2)
    xe = xs[..., 0].reshape(N, D // 2)
    xo = xs[..., 1].reshape(N, D // 2)

    TN = min(256, max(8, ((N + 7) // 8) * 8))
    Np = ((N + TN - 1) // TN) * TN
    if Np > N:
        pad = Np - N
        xe = jnp.concatenate([xe, jnp.zeros((pad, D // 2), xe.dtype)])
        xo = jnp.concatenate([xo, jnp.zeros((pad, D // 2), xo.dtype)])
    # widest lane tile that divides F (measured on v5e at the 70B shard
    # gate/up shape: TF=1024 0.154 ms/layer-matmul vs 512's 0.171)
    TF = next(t for t in (1024, 512, 256, 128) if F % t == 0)

    grid = (Np // TN, F // TF, nd // gd)
    out = pl.pallas_call(
        functools.partial(_kernel, nd_steps=nd // gd, gd=gd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TN, gd * _HG), lambda n, f, d: (n, d)),
            pl.BlockSpec((TN, gd * _HG), lambda n, f, d: (n, d)),
            pl.BlockSpec((gd * _HG, TF), lambda n, f, d: (d, f)),
            pl.BlockSpec((nd, TF), lambda n, f, d: (0, f)),
        ],
        out_specs=pl.BlockSpec((TN, TF), lambda n, f, d: (n, f)),
        out_shape=jax.ShapeDtypeStruct((Np, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((TN, TF), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="quant_matmul_int4",
    )(xe, xo, packed, scale.astype(jnp.float32))
    return out[:N]
