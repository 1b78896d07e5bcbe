"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): the gated delta
rule over a matrix state, with a decay per head AND per key channel.

Per head, with q_t, k_t [dk] (L2-normalised by the caller, q scaled by
dk^-1/2), v_t [dv], g_t [dk] <= 0 (the log of the decay alpha_t = exp(g_t))
and beta_t in (0, 1), the state S [dk, dv] float32 advances as

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

``alpha`` differs in every key channel, which is what makes this neither
Mamba's recurrence (no ``k k^T`` term) nor plain DeltaNet's (one decay a
head): across a chunk the decay between tokens j < i is
``exp(gamma_i - gamma_j)`` per channel (gamma the running sum of g), and
that does NOT factor into ``exp(gamma_i) * exp(-gamma_j)`` once the decay is
strong: at g = -20 a token ``exp(-gamma_j)`` overflows float32 after five
tokens. So every exponent taken here is <= 0.

* ``kda_chunk`` (prefill): the recurrence over chunks of ``CHUNK`` tokens as
  matmuls, the WY / UT form. With gamma the running sum of g inside the
  chunk, ``A[i,j] = beta_i sum_c k_i k_j exp(gamma_i - gamma_j)`` (j < i)
  and ``B[i,j] = sum_c q_i k_j exp(gamma_i - gamma_j)`` (j <= i):

      U   = (I + A)^-1 diag(beta) (V - (K * exp(gamma)) S_0)
      O   = (Q * exp(gamma)) S_0 + B U
      S_C = Diag(exp(gamma_C)) S_0 + (K * exp(gamma_C - gamma))^T U

  A and B are built in sub-chunks of ``SUB`` tokens: between sub-chunks the
  decay goes through the later sub-chunk's first boundary (both factors
  <= 1), and inside one it is taken pairwise, one exponent
  ``min(gamma_i - gamma_j, 0)`` for A and B. ``(I + A)^-1`` is forward
  substitution: row by row inside the ``SUB`` x ``SUB`` diagonal blocks,
  block row by block row across them; never a Neumann series (its terms
  grow combinatorially when keys are alike). Two Pallas kernels, and no
  XLA form of any of it:

  - ``kda_prepare`` builds everything that does not depend on S_0, a
    (chunk, head) tile a program, all programs independent. It holds the
    tile's q, k, v, g ``[C, d]`` in VMEM (read as column blocks of the
    rows the caller holds, ``[T, H * d]``: no head-major copy), and in
    VMEM gamma, A, B, the blocks' inverses and the solve; it writes what
    the walk takes: ``W = (I + A)^-1 diag(beta) K exp(gamma)``,
    ``Uv = (I + A)^-1 diag(beta) V``, ``Q exp(gamma)``, B, and
    ``K^T exp(gamma_C - gamma)`` with the chunk's decay ``exp(gamma_C)``
    beside it.
  - ``kda_chunk`` walks a head's chunks in order and holds S ``[dk, dv]``
    float32 in VMEM from chunk to chunk: ``U = Uv - W S``,
    ``O = Q~ S + B U``, ``S = exp(gamma_C) S + K^^T U``.

  A row with ``g = 0`` and ``beta = 0`` leaves the state as it is: the
  caller zeroes both past ``true_len``, so the state that comes out is the
  one at ``true_len``.
* ``kda_step`` (decode): one token for each of B slots, every head of a
  slot a program, the state array ``[layers * B, H, dk, dv]`` updated in
  place (aliased) at a layer offset that arrives as a prefetched scalar.
  ``alpha = 0`` starts a slot from the zero state (its first token);
  ``alpha = 1, beta = 0`` leaves a slot untouched. The per-channel vectors
  arrive with the channels on sublanes (``[B, dk, H]``), so that scaling
  the rows of S needs no transpose in the kernel.

Off the TPU the kernels run interpreted (``interpret=True``), like
``ssm.py``'s: the CPU tests run these bodies. ``kda_recurrence`` is the
token-by-token form, for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_chunk", "kda_step", "kda_recurrence", "CHUNK", "SUB"]

CHUNK = 64            # tokens a chunk: the [C, C] systems and one state walk
SUB = 16              # tokens a sub-chunk: pairwise decays inside, factored
                      # through a boundary between
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta, s0):
    """The recurrence as written, a token at a time. q, k, g: [T, H, dk];
    v: [T, H, dv]; beta: [T, H]; s0: [H, dk, dv]. -> (o [T, H, dv], S)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        r = jnp.einsum("hkv,hk->hv", S, kt, precision=_HI)
        S = S + bt[:, None, None] * kt[..., None] * (vt - r)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)
    S, o = jax.lax.scan(step, s0.astype(_F32), tuple(
        a.astype(_F32) for a in (q, k, v, g, beta)))
    return o, S


# ---------------------------------------------------------------------------
# The chunked form
# ---------------------------------------------------------------------------


def _prepare_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                    w_ref, uv_ref, qd_ref, b_ref, kx_ref):
    """One chunk of one head, everything of it that does not depend on the
    carried state (module docstring): q, k, v, g tiles [C, d], the chunk's
    beta for every head [C, H] -> W, Uv, Q~ [C, d], B [C, C] and, in one
    [d, 2 C] tile, K^^T beside the chunk's decay exp(gamma_C), a column
    repeated over C lanes: a [d, C] float32 tile takes 128 lanes in HBM and
    in VMEM whatever C, so the column rides in lanes that are there."""
    C, n = CHUNK, CHUNK // SUB
    f = functools.partial(jax.lax.dot_general, preferred_element_type=_F32,
                          precision=_HI)
    mm = lambda a, b: f(a, b, (((1,), (0,)), ((), ())))         # noqa: E731
    mm_t = lambda a, b: f(a, b, (((1,), (1,)), ((), ())))       # noqa: E731
    q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
    d = k.shape[-1]
    heads = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape[1:], 1)
    beta = jnp.sum(jnp.where(heads == pl.program_id(1), beta_ref[0], 0.0),
                   axis=1, keepdims=True)                           # [C, 1]
    ri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    gam = mm((ci <= ri).astype(_F32), g)       # running sum of g, exact
    last = gam[C - 1:C]                                             # gamma_C

    def row_of(a, j):
        """Row j of every sub-chunk of a [C, w], over its sub-chunk."""
        a = a.reshape(n, SUB, a.shape[-1])
        return jnp.broadcast_to(a[:, j:j + 1], a.shape).reshape(C, -1)

    # inside a sub-chunk, pairwise, a column (the partner j) at a time: one
    # exponent for A and B; above the diagonal it is clamped and masked
    local = ci - ri // SUB * SUB          # column inside the row's sub-chunk
    below = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) % SUB
    kb = beta * k
    b_in = jnp.zeros((C, C), _F32)
    a_cols = []
    for j in range(SUB):
        e = row_of(k, j) * jnp.exp(jnp.minimum(gam - row_of(gam, j), 0.0))
        a_cols.append(jnp.where(
            below > j, jnp.sum(kb * e, axis=1, keepdims=True), 0.0))  # [C, 1]
        b_in = jnp.where(local == j, jnp.sum(q * e, axis=1, keepdims=True),
                         b_in)
    # between sub-chunks, through the later one's first boundary: every
    # earlier row decayed up to it, the later rows from it, both <= 1
    a_off, b_off = [jnp.zeros((SUB, C), _F32)], [jnp.zeros((SUB, C), _F32)]
    for s in range(1, n):
        lo = s * SUB
        edge = gam[lo - 1:lo]
        into = jnp.exp(gam[lo:lo + SUB] - edge)
        rows = jnp.concatenate([k[lo:lo + SUB] * into,
                                q[lo:lo + SUB] * into], axis=0)
        upto = k[:lo] * jnp.exp(jnp.minimum(edge - gam[:lo], 0.0))
        off = mm_t(rows, jnp.concatenate(
            [upto, jnp.zeros((C - lo, d), _F32)], axis=0))      # [2 SUB, C]
        a_off.append(off[:SUB])
        b_off.append(off[SUB:])
    a_off = beta * jnp.concatenate(a_off, axis=0)
    b_ref[0, 0] = (jnp.concatenate(b_off, axis=0)
                   + jnp.where(ci <= ri, b_in, 0.0))
    # (I + A)^-1 of the diagonal blocks, all n side by side, by forward
    # substitution: once row j of a block's inverse is final, every later
    # row i takes its -A[i, j] X[j] (the column sweep of the row recurrence
    # X[i] = e_i - sum_{j<i} A[i, j] X[j])
    X = (ci == ri).astype(_F32)
    for j in range(SUB - 1):
        X = X - a_cols[j] * row_of(X, j)
    # and across them a block row at a time: row block s of (I + A)^-1 is
    # X_s (E_s - A[s, :s] T[:s]), its two products taken apart so that only
    # the second waits for the rows above
    M = mm(X, a_off)
    T = X[:SUB]
    for lo in range(SUB, C, SUB):
        above = jnp.concatenate([T, jnp.zeros((C - lo, C), _F32)], axis=0)
        T = jnp.concatenate(
            [T, X[lo:lo + SUB] - mm(M[lo:lo + SUB], above)], axis=0)
    grow = jnp.exp(gam)
    Y = mm(T, jnp.concatenate([beta * k * grow, beta * v], axis=1))
    w_ref[0, 0] = Y[:, :d]
    uv_ref[0, 0] = Y[:, d:]
    qd_ref[0, 0] = q * grow
    kx_ref[0, 0] = jnp.concatenate(
        [k * jnp.exp(last - gam), jnp.broadcast_to(jnp.exp(last), (C, d))],
        axis=0).T


def _prepare(q, k, v, g, beta, *, interpret: bool):
    """q, k, g: [N, C, H * dk]; v: [N, C, H * dv]; beta: [N, C, H], float32,
    as the caller holds them (a head's tile is a column block) ->
    chunk-major (W, Uv, Q~, B, [K^^T | dC]): ``_prepare_kernel``."""
    N, C, H = beta.shape
    dk, dv = k.shape[-1] // H, v.shape[-1] // H
    tile = lambda d: pl.BlockSpec((1, C, d), lambda n, h: (n, 0, h))  # noqa: E731
    out = lambda *shape: pl.BlockSpec(                          # noqa: E731
        (1, 1) + shape, lambda n, h: (n, h, 0, 0))
    shapes = [(C, dk), (C, dv), (C, dk), (C, C), (dk, 2 * C)]
    return pl.pallas_call(
        _prepare_kernel,
        grid=(N, H),
        in_specs=[tile(dk), tile(dk), tile(dv), tile(dk),
                  pl.BlockSpec((1, C, H), lambda n, h: (n, 0, 0))],
        out_specs=[out(*s) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct((N, H) + s, _F32) for s in shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_prepare",
    )(q, k, v, g, beta)


def _walk_kernel(w_ref, uv_ref, qd_ref, b_ref, kx_ref, s0_ref,
                 o_ref, s_ref, acc_ref):
    """One chunk of one head: U = Uv - W S; O = Qd S + B U;
    S = dC * S + Kt U, S float32 in VMEM across the chunks of a head;
    [Kt | dC] as ``_prepare_kernel`` writes them."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[...] = s0_ref[0]

    dot = functools.partial(jnp.dot, preferred_element_type=_F32,
                            precision=_HI)
    S = acc_ref[...]
    U = uv_ref[0, 0] - dot(w_ref[0, 0], S)
    o_ref[0, 0] = dot(qd_ref[0, 0], S) + dot(b_ref[0, 0], U)
    S = (kx_ref[0, 0, :, CHUNK:CHUNK + 1] * S
         + dot(kx_ref[0, 0, :, :CHUNK], U))
    acc_ref[...] = S

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        s_ref[0] = S


def kda_chunk(q, k, v, g, beta, s0, *, interpret: bool = False):
    """q, k, g: [T, H, dk] float32 (q, k normalised; g <= 0, and 0 where the
    row is padding); v: [T, H, dv]; beta: [T, H] (0 where the row is
    padding); s0: [H, dk, dv] float32. -> (o [T, H, dv] float32, the state
    after the last row with beta > 0 or g < 0, [H, dk, dv])."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    C = CHUNK
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    N = (T + pad) // C
    # chunked as the caller holds the rows: [N, C, H * d], no copy
    rows = lambda a: a.astype(_F32).reshape(N, C, -1)           # noqa: E731
    W, Uv, Qd, B, Kx = _prepare(rows(q), rows(k), rows(v), rows(g),
                                rows(beta), interpret=interpret)
    blk = lambda *shape: pl.BlockSpec(                          # noqa: E731
        (1, 1) + shape, lambda h, n: (n, h, 0, 0))
    head = pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0))
    o, S = pl.pallas_call(
        _walk_kernel,
        grid=(H, N),
        in_specs=[blk(C, dk), blk(C, dv), blk(C, dk), blk(C, C),
                  blk(dk, 2 * C), head],
        out_specs=[pl.BlockSpec((1, 1, C, dv), lambda h, n: (h, n, 0, 0)),
                   head],
        out_shape=[jax.ShapeDtypeStruct((H, N, C, dv), _F32),
                   jax.ShapeDtypeStruct((H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(W, Uv, Qd, B, Kx, s0.astype(_F32))
    return jnp.moveaxis(o, 0, 2).reshape(N * C, H, dv)[:T], S


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


def _step_kernel(off_ref, at_ref, kt_ref, qt_ref, v_ref, beta_ref, s_ref,
                 o_ref, s_out_ref, *, heads: int):
    del off_ref                      # read by the index maps
    at, kt, qt = at_ref[0], kt_ref[0], qt_ref[0]         # [dk, H]
    shape = s_ref.shape[2:]
    for h in range(heads):
        col = slice(h, h + 1)
        # a channel's value over the lanes, once a vector and head: the
        # lane broadcasts are what the step's time is made of
        k_rep = jnp.broadcast_to(kt[:, col], shape)
        S = jnp.broadcast_to(at[:, col], shape) * s_ref[0, h]   # Diag(a) S
        r = jnp.sum(S * k_rep, axis=0, keepdims=True)           # [1, dv]
        S = S + k_rep * (beta_ref[0, h:h + 1, :]
                         * (v_ref[0, h:h + 1, :] - r))
        s_out_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(
            S * jnp.broadcast_to(qt[:, col], shape), axis=0, keepdims=True)


def kda_step(q, k, v, alpha, beta, state, layer, *, interpret: bool = False):
    """One token for each of B slots. q, k, alpha: [B, H, dk] float32
    (alpha = exp(g); 0: the slot starts from the zero state; 1 with
    beta 0: the slot's state stays); v: [B, H, dv]; beta: [B, H]; state:
    [layers * B, H, dk, dv] float32, of which rows [layer * B,
    (layer + 1) * B) are read and rewritten in place; layer: int32 scalar.
    -> (o [B, H, dv] float32, state)."""
    B, H, dk = k.shape
    dv = v.shape[-1]
    t = lambda a: jnp.swapaxes(a.astype(_F32), 1, 2)     # noqa: E731
    cols = pl.BlockSpec((1, dk, H), lambda b, off: (b, 0, 0))
    rows = pl.BlockSpec((1, H, dv), lambda b, off: (b, 0, 0))
    cells = pl.BlockSpec((1, H, dk, dv),
                         lambda b, off: (off[0] + b, 0, 0, 0))
    off = (jnp.asarray(layer, jnp.int32) * B).reshape(1)
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[cols, cols, cols, rows, rows, cells],
            out_specs=[rows, cells]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operands count the prefetched scalar: 6 is ``state``
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_step",
    )(off, t(alpha), t(k), t(q), v.astype(_F32),
      jnp.broadcast_to(beta.astype(_F32)[..., None], (B, H, dv)), state)
    return o, state
