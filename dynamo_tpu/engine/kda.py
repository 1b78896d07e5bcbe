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
  decay goes through the sub-chunk's first boundary (both factors <= 1), and
  inside one it is taken pairwise, one diagonal at a time. ``(I + A)^-1`` is
  forward substitution: row by row inside the ``SUB`` x ``SUB`` diagonal
  blocks, block by block across them; never a Neumann series (its terms
  grow combinatorially when keys are alike). Everything that does not
  depend on S_0 is computed for all chunks at once in XLA; the walk over
  the chunks, which carries S in float32 from chunk to chunk in VMEM, is
  the Pallas kernel named ``kda_chunk``. A row with ``g = 0`` and
  ``beta = 0`` leaves the state as it is: the caller zeroes both past
  ``true_len``, so the state that comes out is the one at ``true_len``.
* ``kda_step`` (decode): one token for each of B slots, every head of a
  slot a program, the state array ``[layers * B, H, dk, dv]`` updated in
  place (aliased) at a layer offset that arrives as a prefetched scalar.
  ``alpha = 0`` starts a slot from the zero state (its first token);
  ``alpha = 1, beta = 0`` leaves a slot untouched. The per-channel vectors
  arrive with the channels on sublanes (``[B, dk, H]``), so that scaling
  the rows of S needs no transpose in the kernel.

Off the TPU both kernels run interpreted (``interpret=True``), like
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


def _shift(x, d: int):
    """x [..., SUB, dk] moved d rows down its sub-chunk (row i holds what
    row i - d held; the first d rows hold zeros)."""
    if d == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (d, 0)
    return jnp.pad(x, pad)[..., :x.shape[-2], :]


def _inside(x, k, gam):
    """Pairwise-decayed products inside every sub-chunk: x, k, gam
    [..., n, SUB, dk] -> [..., n, SUB, SUB] with entry (i, j) =
    sum_c x_i k_j exp(gam_i - gam_j) for j <= i, 0 above the diagonal. One
    diagonal (distance d = i - j) at a time: every exponent is <= 0."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    out = jnp.zeros(x.shape[:-1] + (SUB,), _F32)
    for d in range(SUB):
        kd, gd = _shift(k, d), _shift(gam, d)
        # rows i < d have no partner: their shifted k is zero
        diag = jnp.sum(x * kd * jnp.exp(jnp.minimum(gam - gd, 0.0)), -1)
        out = out + jnp.where(rows - cols == d, diag[..., None], 0.0)
    return out


def _decayed_products(q, k, g, beta):
    """q, k, g: [H, N, C, dk]; beta: [H, N, C] -> (A strictly lower,
    B lower with its diagonal, both [H, N, C, C]: module docstring; gamma,
    the running sum of g inside each chunk)."""
    H, N, C, dk = k.shape
    n = C // SUB
    gam = jnp.cumsum(g, axis=2)
    sub = lambda a: a.reshape(H, N, n, SUB, a.shape[-1])      # noqa: E731
    gs, ks, qs = sub(gam), sub(k), sub(q)
    # the boundary a sub-chunk's rows decay from: gamma before its first row
    edge = jnp.concatenate([jnp.zeros_like(gs[:, :, :1, -1]),
                            gs[:, :, :-1, -1]], axis=2)        # [H, N, n, dk]
    into = jnp.exp(gs - edge[..., None, :])                    # <= 1
    rows = jnp.concatenate([ks * into, qs * into], axis=3)     # [.., 2SUB, dk]
    # every earlier row decayed up to that boundary; rows at or past it are
    # masked below, their exponent clamped so that nothing overflows
    upto = jnp.exp(jnp.minimum(edge[:, :, :, None, :]
                               - gam[:, :, None, :, :], 0.0))  # [H,N,n,C,dk]
    cols = k[:, :, None] * upto
    off = jnp.einsum("hnsid,hnsjd->hnsij", rows, cols, precision=_HI)
    before = (jnp.arange(C)[None, None, :]
              < (jnp.arange(n) * SUB)[:, None, None])          # [n, 1, C]
    off = jnp.where(before, off, 0.0)
    a_off = off[:, :, :, :SUB].reshape(H, N, C, C)
    b_off = off[:, :, :, SUB:].reshape(H, N, C, C)
    # the diagonal blocks, pairwise
    blk = jnp.eye(n, dtype=_F32)[:, None, :, None]             # [n,1,n,1]

    def spread(d):       # [H, N, n, SUB, SUB] -> block diagonal [H, N, C, C]
        return (d[:, :, :, :, None, :] * blk).reshape(H, N, C, C)

    a_in = _inside(ks, ks, gs)
    strict = jnp.tril(jnp.ones((SUB, SUB), _F32), -1)
    A = (a_off + spread(a_in * strict)) * beta[..., None]
    B = b_off + spread(_inside(qs, ks, gs))
    return A, B, gam


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower A [..., C, C] by forward substitution:
    inside each SUB x SUB diagonal block a row at a time, across the blocks
    a block row at a time."""
    C = A.shape[-1]
    n = C // SUB
    lead = A.shape[:-2]
    blocks = A.reshape(lead + (n, SUB, n, SUB))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], -3)
    eye = jnp.broadcast_to(jnp.eye(SUB, dtype=_F32), diag.shape)

    def row(i, X):
        # row i of the inverse: e_i - A[i, :i] X[:i]; rows >= i of A[i] are 0
        a = jax.lax.dynamic_slice_in_dim(diag, i, 1, axis=-2)
        new = (jax.lax.dynamic_slice_in_dim(eye, i, 1, axis=-2)
               - jnp.matmul(a, X, precision=_HI))
        return jax.lax.dynamic_update_slice_in_dim(X, new, i, axis=-2)

    dinv = jax.lax.fori_loop(1, SUB, row, eye)     # [..., n, SUB, SUB]
    eye_c = jnp.eye(C, dtype=_F32)
    rows = []
    for i in range(n):
        lo = i * SUB
        rhs = jnp.broadcast_to(eye_c[lo:lo + SUB], lead + (SUB, C))
        if i:
            done = jnp.concatenate(rows, axis=-2)              # [.., lo, C]
            rhs = rhs - jnp.matmul(A[..., lo:lo + SUB, :lo], done,
                                   precision=_HI)
        rows.append(jnp.matmul(dinv[..., i, :, :], rhs, precision=_HI))
    return jnp.concatenate(rows, axis=-2)


def _walk_kernel(w_ref, uv_ref, qd_ref, b_ref, kt_ref, dc_ref, s0_ref,
                 o_ref, s_ref, acc_ref):
    """One chunk of one head: U = Uv - W S; O = Qd S + B U;
    S = dC * S + Kt U, S float32 in VMEM across the chunks of a head."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[...] = s0_ref[0]

    dot = functools.partial(jnp.dot, preferred_element_type=_F32,
                            precision=_HI)
    S = acc_ref[...]
    U = uv_ref[0, 0] - dot(w_ref[0, 0], S)
    o_ref[0, 0] = dot(qd_ref[0, 0], S) + dot(b_ref[0, 0], U)
    S = dc_ref[0, 0] * S + dot(kt_ref[0, 0], U)
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
    # head-major, chunked: [H, N, C, d]
    hm = lambda a: jnp.moveaxis(                                # noqa: E731
        a.astype(_F32).reshape(N, C, H, -1), 2, 0)
    q, k, v, g = hm(q), hm(k), hm(v), hm(g)
    beta = jnp.moveaxis(beta.astype(_F32).reshape(N, C, H), 2, 0)
    A, B, gam = _decayed_products(q, k, g, beta)
    Tm = _unit_lower_inverse(A)
    last = gam[:, :, -1:, :]                                    # gamma_C
    grow = jnp.exp(gam)
    W = jnp.matmul(Tm, beta[..., None] * k * grow, precision=_HI)
    Uv = jnp.matmul(Tm, beta[..., None] * v, precision=_HI)
    Kt = jnp.swapaxes(k * jnp.exp(last - gam), -1, -2)          # [H,N,dk,C]
    dC = jnp.broadcast_to(jnp.exp(jnp.swapaxes(last, -1, -2)),
                          (H, N, dk, dv))
    blk = lambda *shape: pl.BlockSpec(                          # noqa: E731
        (1, 1) + shape, lambda h, n: (h, n, 0, 0))
    head = pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0))
    o, S = pl.pallas_call(
        _walk_kernel,
        grid=(H, N),
        in_specs=[blk(C, dk), blk(C, dv), blk(C, dk), blk(C, C), blk(dk, C),
                  blk(dk, dv), head],
        out_specs=[blk(C, dv), head],
        out_shape=[jax.ShapeDtypeStruct((H, N, C, dv), _F32),
                   jax.ShapeDtypeStruct((H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(W, Uv, q * grow, B, Kt, dC, s0.astype(_F32))
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
