"""Pallas kernels of the Mamba-2 recurrence (state-space duality, SSD;
Dao & Gu, arXiv:2405.21060): ONE decay a head and token.

Per head h (P lanes), with x_t [P], B_t and C_t [N] shared by every head (one
group), dt_t > 0 and a_t = dt_t * A_h <= 0, the state S [P, N] float32 runs

    S_t = exp(a_t) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t                                   (the caller adds D x_t)

The decay is a scalar a head and token: neither Mamba-1's (``ssm.py``: one a
state AND channel, no matmul form) nor the delta rule's (``kda.py``: one a
key channel, and a ``k k^T`` term). So inside a chunk of ``CHUNK`` tokens the
recurrence IS matmuls. With cum the running sum of a inside the chunk:

    Y   = ((C B^T) * L) (dt * X) + exp(cum) * (C S_0^T)
    S_C = exp(cum_C) S_0 + (dt * exp(cum_C - cum) * X)^T B
    L[i, j] = exp(cum_i - cum_j)  for j <= i, else 0

Every exponent taken is <= 0 (cum falls): ``exp(cum_i) * exp(-cum_j)`` would
overflow float32 once a chunk's decay is strong.

**Layout.** The state is held TRANSPOSED and with the heads' lanes side by
side: ``[H * P / W, N, W]``, W = 128 lanes = ``W // P`` heads of P lanes (two
at the published 64), the N states on sublanes. A head's decay and a token's
``dt x`` are then ROW vectors (a sublane broadcast, cheap) and only B_t / C_t,
which every head shares, stand as columns: the step's per-head work has no
lane broadcast and no transpose, which is what ``kda_step``'s time is made
of. ``state_to_hpn`` / ``state_from_hpn`` turn it into the ``[H, P, N]`` of
the equations for a test or a check.

* ``ssd_chunk`` (prefill): one sequence. Grid (lane-group blocks, chunks),
  chunks innermost; a block's states ``[GB, N, W]`` float32 stay in a VMEM
  scratch across the chunks of a dispatch, start from ``s0`` (the caller
  passes zeros at position 0, the slot's state on a later dispatch of the
  prompt) and are written once, after the last chunk. ``C B^T`` and the two
  running sums (a matmul with a triangle: exact in float32) are taken once a
  program, the ``[C, C]`` decay matrix once a head. A row with ``dt = 0``
  changes nothing (decay 1, input 0): the caller zeroes dt past ``true_len``,
  so the state written is the one after ``true_len`` rows; a chunk that lies
  wholly past ``true_len`` (a scalar the kernel is handed) is not computed.
  VMEM at the published widths (CHUNK 128, GB 4 groups = 8 heads): x and y
  tiles 128 x 512 (bf16 in, float32 out), B / C / B^T 64 KB each, the
  scratch 256 KB, the per-head temporaries three [128, 128] float32: under
  2 MB with double buffering.
* ``ssd_step`` (decode): one token for each of B slots; grid (slots, blocks
  of ``STEP_GROUPS`` lane groups); the state array ``[layers * B, H * P / W,
  N, W]`` is updated in place (aliased) at a layer offset that arrives as a
  prefetched scalar, 1 MB tiles. ``decay = 0`` starts a slot from the zero
  state (its first token); ``decay = 1`` with ``dt x = 0`` leaves a slot as
  it is. B_t / C_t arrive replicated over the lanes (``[B, N, W]``: 64 KB a
  slot against its 4 MB of state) so that the kernel broadcasts nothing.

Off the TPU both run interpreted (``interpret=True``), like ``ssm.py``'s and
``kda.py``'s: the CPU tests run these bodies. ``ssd_recurrence`` is the
token-by-token form, for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_chunk", "ssd_step", "ssd_recurrence", "state_to_hpn",
           "state_from_hpn", "state_shape", "CHUNK"]

CHUNK = 128           # tokens a chunk: the [C, C] decay matrix of a head
LANES = 128           # W: the lanes of a state tile (W // P heads)
CHUNK_GROUPS = 4      # lane groups a program of ssd_chunk holds
STEP_GROUPS = 16      # lane groups a program of ssd_step holds (1 MB)
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _lane_width(heads: int, lanes: int) -> int:
    """W: 128 lanes where whole heads of ``lanes`` fill them, a head's own
    lanes where those are a multiple of 128, else all there are (tiny
    widths, interpreted)."""
    total = heads * lanes
    if LANES % lanes == 0 and total % LANES == 0:
        return LANES
    return lanes if lanes % LANES == 0 else total


def state_shape(heads: int, lanes: int, n_state: int) -> tuple:
    """The shape one slot's state of one layer is held in."""
    W = _lane_width(heads, lanes)
    return (heads * lanes // W, n_state, W)


def state_from_hpn(s):
    """[..., H, P, N] -> the held layout [..., H * P / W, N, W]."""
    *lead, H, P, N = s.shape
    G, _, W = state_shape(H, P, N)
    s = jnp.swapaxes(s.reshape(*lead, H * P, N), -1, -2)      # [.., N, H P]
    return jnp.swapaxes(s.reshape(*lead, N, G, W), -2, -3)


def state_to_hpn(s, heads: int):
    """The held layout [..., G, N, W] -> [..., H, P, N]."""
    *lead, G, N, W = s.shape
    s = jnp.swapaxes(s, -2, -3).reshape(*lead, N, G * W)
    return jnp.swapaxes(s, -1, -2).reshape(*lead, heads, G * W // heads, N)


def ssd_recurrence(x, dt, a, b, c, s0):
    """The recurrence as written, a token at a time. x: [T, H, P]; dt, a:
    [T, H] (a = dt * A); b, c: [T, N]; s0: [H, P, N]. -> (y [T, H, P], S)."""
    def step(S, t):
        xt, dtt, at, bt, ct = t
        S = (jnp.exp(at)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, ct, precision=_HI)
    S, y = jax.lax.scan(step, s0.astype(_F32), tuple(
        v.astype(_F32) for v in (x, dt, a, b, c)))
    return y, S


# ---------------------------------------------------------------------------
# The chunked form
# ---------------------------------------------------------------------------


def _chunk_kernel(len_ref, x_ref, dt_ref, a_ref, at_ref, b_ref, c_ref,
                  bt_ref, s0_ref, y_ref, s_ref, acc_ref, *, lanes: int):
    """One chunk of one block of lane groups (module docstring). x [C, GB W];
    dt, a [1, C, HB] (a head a column) and a^T [1, HB, C] (a head a row); B,
    C [C, N]; B^T [N, C]; the states [GB, N, W]."""
    C = x_ref.shape[0]
    GB, _, W = acc_ref.shape
    per = W // lanes                                  # heads a lane group
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        acc_ref[...] = s0_ref[...]

    @pl.when(chunk * C >= len_ref[0])
    def _():                          # wholly padding: nothing to compute
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(chunk * C < len_ref[0])
    def _():
        dot = functools.partial(jax.lax.dot_general, precision=_HI,
                                preferred_element_type=_F32)
        nn = lambda p, q: dot(p, q, (((1,), (0,)), ((), ())))   # noqa: E731
        nt = lambda p, q: dot(p, q, (((1,), (1,)), ((), ())))   # noqa: E731
        ri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        tri = ci <= ri
        cum_col = nn(tri.astype(_F32), a_ref[0])                # [C, HB]
        cum_row = nn(at_ref[0], (ri <= ci).astype(_F32))        # [HB, C]
        dt = dt_ref[0]                                          # [C, HB]
        b, c = b_ref[...], c_ref[...]
        cb = nt(c, b)                                           # [C, C]
        lane = jax.lax.broadcasted_iota(jnp.int32, (C, W), 1) // lanes
        for g in range(GB):
            x = x_ref[:, g * W:(g + 1) * W].astype(_F32)        # [C, W]
            S = acc_ref[g]                                      # [N, W]
            y = jnp.zeros((C, W), _F32)
            grow = jnp.zeros((C, W), _F32)      # exp(cum), a head's lanes
            w = jnp.zeros((C, W), _F32)         # dt exp(cum_C - cum)
            keep = jnp.zeros((1, W), _F32)      # exp(cum_C)
            for j in range(per):
                k = g * per + j
                cc, cr = cum_col[:, k:k + 1], cum_row[k:k + 1, :]
                decay = jnp.where(tri, jnp.exp(jnp.minimum(cc - cr, 0.0)),
                                  0.0)
                mine = lane == j
                y = y + nn(cb * decay,
                           jnp.where(mine, x * dt[:, k:k + 1], 0.0))
                last = cc[C - 1:C]
                grow = jnp.where(mine, jnp.exp(cc), grow)
                w = jnp.where(mine, dt[:, k:k + 1] * jnp.exp(last - cc), w)
                keep = jnp.where(lane[:1] == j, jnp.exp(last), keep)
            y_ref[:, g * W:(g + 1) * W] = y + grow * nn(c, S)
            acc_ref[g] = keep * S + nn(bt_ref[...], x * w)

    @pl.when(chunk == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = acc_ref[...]


def ssd_chunk(x, dt, a, b, c, s0, true_len=None, *, interpret: bool = False):
    """x: [T, H, P] (any float dtype); dt, a: [T, H] float32 (a = dt * A, both
    0 where the row is padding); b, c: [T, N]; s0: the held layout
    ``state_shape(H, P, N)`` float32; true_len: int32 scalar, the rows that
    are not padding (None: all). -> (y [T, H, P] float32, the state after the
    last row with dt > 0, in the held layout)."""
    T, H, P = x.shape
    N = b.shape[-1]
    G, _, W = state_shape(H, P, N)
    per = W // P
    C = CHUNK
    pad = -T % C
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        dt, a, b, c = (jnp.pad(v, ((0, pad), (0, 0))) for v in (dt, a, b, c))
    Tp = T + pad
    GB = next(g for g in (CHUNK_GROUPS, 2, 1) if G % g == 0)
    HB = GB * per                                  # heads a program
    n_len = jnp.asarray(Tp if true_len is None else true_len,
                        jnp.int32).reshape(1)
    # a head's dt and a as a column of its block, and a as a row
    cols = lambda v: jnp.swapaxes(                               # noqa: E731
        v.astype(_F32).reshape(Tp, H // HB, HB), 0, 1)
    a_cols = cols(a)
    b, c = b.astype(_F32), c.astype(_F32)
    col = pl.BlockSpec((1, C, HB), lambda g, n, ln: (g, n, 0))
    tok = pl.BlockSpec((C, N), lambda g, n, ln: (n, 0))
    wide = pl.BlockSpec((C, GB * W), lambda g, n, ln: (n, g))
    cells = pl.BlockSpec((GB, N, W), lambda g, n, ln: (g, 0, 0))
    y, S = pl.pallas_call(
        functools.partial(_chunk_kernel, lanes=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(G // GB, Tp // C),
            in_specs=[wide, col, col,
                      pl.BlockSpec((1, HB, C), lambda g, n, ln: (g, 0, n)),
                      tok, tok,
                      pl.BlockSpec((N, C), lambda g, n, ln: (0, n)), cells],
            out_specs=[wide, cells],
            scratch_shapes=[pltpu.VMEM((GB, N, W), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((Tp, H * P), _F32),
                   jax.ShapeDtypeStruct((G, N, W), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(n_len, x.reshape(Tp, H * P), cols(dt), a_cols,
      jnp.swapaxes(a_cols, 1, 2), b, c, b.T, s0.astype(_F32))
    return y[:T].reshape(T, H, P), S


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def _step_kernel(off_ref, xdt_ref, decay_ref, b_ref, c_ref, s_ref,
                 y_ref, s_out_ref):
    del off_ref                      # read by the index maps
    b, c = b_ref[0], c_ref[0]                                   # [N, W]
    for g in range(s_ref.shape[1]):
        S = decay_ref[0, g:g + 1, :] * s_ref[0, g] + b * xdt_ref[0, g:g + 1, :]
        s_out_ref[0, g] = S
        y_ref[0, g:g + 1, :] = jnp.sum(S * c, axis=0, keepdims=True)


def ssd_step(x, dt, decay, b, c, state, layer, *, interpret: bool = False):
    """One token for each of B slots. x: [B, H, P]; dt, decay: [B, H] float32
    (decay = exp(dt * A); 0: the slot starts from the zero state; 1 with
    dt 0: the slot's state stays); b, c: [B, N]; state: [layers * B, G, N, W]
    float32 (the held layout a slot and layer), of which rows [layer * B,
    (layer + 1) * B) are read and rewritten in place; layer: int32 scalar.
    -> (y [B, H, P] float32, state)."""
    B, H, P = x.shape
    N = b.shape[-1]
    G, _, W = state.shape[1:]
    GB = next(g for g in (STEP_GROUPS, 8, 4, 2, 1) if G % g == 0)
    rows = lambda v: v.astype(_F32).reshape(B, G, W)             # noqa: E731
    rep = lambda v: jnp.broadcast_to(                            # noqa: E731
        v.astype(_F32)[..., None], (B, N, W))
    row = pl.BlockSpec((1, GB, W), lambda s, g, off: (s, g, 0))
    shared = pl.BlockSpec((1, N, W), lambda s, g, off: (s, 0, 0))
    cells = pl.BlockSpec((1, GB, N, W),
                         lambda s, g, off: (off[0] + s, g, 0, 0))
    off = (jnp.asarray(layer, jnp.int32) * B).reshape(1)
    y, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G // GB),
            in_specs=[row, row, shared, shared, cells],
            out_specs=[row, cells]),
        out_shape=[jax.ShapeDtypeStruct((B, G, W), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operands count the prefetched scalar: 5 is ``state``
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_step",
    )(off, rows(x.astype(_F32) * dt.astype(_F32)[..., None]),
      rows(jnp.broadcast_to(decay.astype(_F32)[..., None], (B, H, P))),
      rep(b), rep(c), state)
    return y.reshape(B, H, P), state
