"""Pallas kernels of the Mamba-1 selective state-space recurrence.

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) (x) B_t        [N, Di]
    y_t = sum_n s_t[n] * C_t[n]                                  [Di]

``A`` is per (state, channel) and ``dt`` per (token, channel), so the decay
``exp(dt_t * A)`` differs in every one of the N * Di cells: there is no
matmul form (that is Mamba-2's), and an associative scan over a prompt
streams ``[T, N, Di]`` float32 operands through HBM a dozen times. Here the
state stays in VMEM and time runs sequentially over it, which is what the
recurrence is:

* ``ssm_scan`` (prefill): one sequence, T tokens. Grid (channel tiles,
  time blocks), time innermost; the state ``[N, tile]`` lives in a VMEM
  scratch across the time blocks of a channel tile, eight tokens are
  unrolled per loop step. A token with ``dt = 0`` leaves the state as it
  is (decay 1, input 0): the caller zeroes ``dt`` past ``true_len``, so
  the state that comes out is the one at ``true_len``, not at the padded
  bucket's end.
* ``ssm_step`` (decode): one token for each of B slots, eight slots a
  program, the state array ``[layers * B, N, Di]`` updated in place
  (aliased) at a layer offset that arrives as a prefetched scalar, so the
  caller never slices or copies the cache. ``keep`` 0 starts a slot from
  the zero state (its first token); ``dt = 0`` leaves a slot untouched.

Layout: channels on lanes, the N states on sublanes (N = 16: two float32
tiles), so every operation is on ``[N, 128]`` groups; ``B_t`` / ``C_t``
arrive replicated over 128 lanes (``[T, N, 128]``: 8 KB a token, made by
the caller) so that no transpose or lane broadcast happens in the kernel.
Off the TPU both run interpreted (``interpret=True``), like
``grouped_matmul`` and ``select_compact``: the CPU tests run these bodies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssm_scan", "ssm_step"]

LANE = 128
_SUB = 8                 # tokens (scan) or slots (step) per unrolled group
_F32 = jnp.float32


def lane_replicated(m: jax.Array) -> jax.Array:
    """[..., N] -> [..., N, 128] float32, each value over a lane row."""
    return jnp.broadcast_to(m.astype(_F32)[..., None], m.shape + (LANE,))


def _channel_tile(di: int) -> int:
    for t in (512, 256, 128):
        if di % t == 0:
            return t
    raise ValueError(f"state-space width {di} is not a multiple of 128")


def _advance(h, dt_row, x_row, a, b_t, c_t):
    """One token of one 128-channel group. h, a, b_t, c_t: [N, 128];
    dt_row, x_row: [1, 128]. -> (new h, y [1, 128])."""
    h = jnp.exp(dt_row * a) * h + (dt_row * x_row) * b_t
    return h, jnp.sum(h * c_t, axis=0, keepdims=True)


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref,
                 *, tb: int, groups: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = [a_ref[:, g * LANE:(g + 1) * LANE] for g in range(groups)]
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANE), 0)

    def eight(i, hs):
        r0 = pl.multiple_of(i * _SUB, _SUB)
        dt8 = dt_ref[pl.ds(r0, _SUB), :]
        x8 = x_ref[pl.ds(r0, _SUB), :]
        hs = list(hs)
        ys = [jnp.zeros((_SUB, LANE), _F32) for _ in range(groups)]
        for j in range(_SUB):
            b_t, c_t = b_ref[r0 + j], c_ref[r0 + j]
            for g in range(groups):
                sl = slice(g * LANE, (g + 1) * LANE)
                hs[g], y = _advance(hs[g], dt8[j:j + 1, sl],
                                    x8[j:j + 1, sl], a[g], b_t, c_t)
                ys[g] = jnp.where(row == j, y, ys[g])
        for g in range(groups):
            y_ref[pl.ds(r0, _SUB), g * LANE:(g + 1) * LANE] = ys[g]
        return tuple(hs)

    hs = jax.lax.fori_loop(
        0, tb // _SUB, eight,
        tuple(h_ref[:, g * LANE:(g + 1) * LANE] for g in range(groups)))
    for g in range(groups):
        h_ref[:, g * LANE:(g + 1) * LANE] = hs[g]


def ssm_scan(dt, x, b, c, a, h0, *, interpret: bool = False):
    """dt, x: [T, Di] float32 (dt after softplus, 0 where the token is
    padding); b, c: [T, N]; a: [N, Di] (= -exp(A_log), transposed); h0:
    [N, Di]. -> (y [T, Di] float32, the state after the last token with
    dt > 0, [N, Di])."""
    T, di = dt.shape
    N = a.shape[0]
    pad = -T % _SUB
    if pad:
        dt, x = (jnp.pad(v, ((0, pad), (0, 0))) for v in (dt, x))
        b, c = (jnp.pad(v, ((0, pad), (0, 0))) for v in (b, c))
    Tp = T + pad
    tb = next(t for t in (128, 64, 32, 16, 8) if Tp % t == 0)
    dtile = _channel_tile(di)
    tok = pl.BlockSpec((tb, dtile), lambda d, t: (t, d))
    rep = pl.BlockSpec((tb, N, LANE), lambda d, t: (t, 0, 0))
    chan = pl.BlockSpec((N, dtile), lambda d, t: (0, d))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tb=tb, groups=dtile // LANE),
        grid=(di // dtile, Tp // tb),
        in_specs=[tok, tok, rep, rep, chan, chan],
        out_specs=[tok, chan],
        out_shape=[jax.ShapeDtypeStruct((Tp, di), _F32),
                   jax.ShapeDtypeStruct((N, di), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(dt.astype(_F32), x.astype(_F32), lane_replicated(b),
      lane_replicated(c), a.astype(_F32), h0.astype(_F32))
    return y[:T], h


def _step_kernel(off_ref, dt_ref, x_ref, keep_ref, b_ref, c_ref, a_ref,
                 h_ref, y_ref, h_out_ref, *, groups: int):
    del off_ref                      # read by the index maps
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, LANE), 0)
    dt8, x8, keep8 = dt_ref[...], x_ref[...], keep_ref[...]
    for g in range(groups):
        sl = slice(g * LANE, (g + 1) * LANE)
        a = a_ref[:, sl]
        ys = jnp.zeros((_SUB, LANE), _F32)
        for j in range(_SUB):
            h, y = _advance(h_ref[j, :, sl] * keep8[j:j + 1, :],
                            dt8[j:j + 1, sl], x8[j:j + 1, sl], a,
                            b_ref[j], c_ref[j])
            h_out_ref[j, :, sl] = h
            ys = jnp.where(row == j, y, ys)
        y_ref[:, sl] = ys


def ssm_step(dt, x, keep, b, c, a, state, layer, *,
             interpret: bool = False):
    """One token for each of B slots. dt, x: [B, Di] float32 (dt 0: the
    slot's state stays); keep: [B] (0: the slot starts from the zero
    state); b, c: [B, N]; a: [N, Di]; state: [layers * B, N, Di] float32,
    of which rows [layer * B, (layer + 1) * B) are read and rewritten in
    place; layer: int32 scalar. -> (y [B, Di], state). B is a multiple
    of 8."""
    B, di = dt.shape
    N = a.shape[0]
    if B % _SUB:
        raise ValueError(f"ssm_step takes slots in eights, got {B}")
    dtile = _channel_tile(di)
    slot = pl.BlockSpec((_SUB, dtile), lambda s, d, off: (s, d))
    lanes = pl.BlockSpec((_SUB, LANE), lambda s, d, off: (s, 0))
    rep = pl.BlockSpec((_SUB, N, LANE), lambda s, d, off: (s, 0, 0))
    chan = pl.BlockSpec((N, dtile), lambda s, d, off: (0, d))
    cells = pl.BlockSpec((_SUB, N, dtile),
                         lambda s, d, off: (off[0] + s, 0, d))
    off = (jnp.asarray(layer, jnp.int32) * (B // _SUB)).reshape(1)
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, groups=dtile // LANE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // _SUB, di // dtile),
            in_specs=[slot, slot, lanes, rep, rep, chan, cells],
            out_specs=[slot, cells]),
        out_shape=[jax.ShapeDtypeStruct((B, di), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operands count the prefetched scalar: 7 is ``state``
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_step",
    )(off, dt.astype(_F32), x.astype(_F32),
      jnp.broadcast_to(keep.astype(_F32)[:, None], (B, LANE)),
      lane_replicated(b), lane_replicated(c), a.astype(_F32), state)
    return y, state
