"""Fused int8 dequant-matmul for the LM head (Pallas).

Why (PERF.md "Decode step budget" + "next wins" 2): the head is the
single largest matmul of a decode step — [B, D] @ [D, V≈128k] — and with
int8 weights its floor is a pure weights-read: ~0.33 GB → ~0.4 ms on
v5e. The XLA paths measured 0.5–1.4 ms and, worse, XLA's int8 matmul
heuristics are batch-dependent (llama.py:_logits: the pre-transposed
int8 head collapses from 4.5 ms to 82 ms between B=16 and B=64). This
kernel pins the schedule instead of relying on heuristics:

- grid over vocab tiles; each step DMAs one [D, TV] int8 weight tile
  (Pallas double-buffers the HBM→VMEM stream automatically),
- converts int8→bf16 in-register, one MXU dot per tile with f32
  accumulation, scales by the per-column quant scale on the way out.

HBM traffic = the int8 weights once + the f32 logits once — the floor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lm_head_int8", "TILE_V"]

TILE_V = 256    # vocab tile; the gate in models/llama.py checks V % TILE_V


def _kernel(x_ref, wq_ref, scale_ref, out_ref):
    w = wq_ref[...].astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] = acc * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("tile_v", "interpret"))
def lm_head_int8(x: jax.Array, q: jax.Array, scale: jax.Array,
                 *, tile_v: int = TILE_V,
                 interpret: bool = False) -> jax.Array:
    """``x[B, D] @ q[D, V](int8) * scale[V] → f32 logits [B, V]``.

    ``scale`` may be [V], [1, V] or [V, 1] (per-output-channel). V must
    divide by ``tile_v`` (the llama vocab 128256 = 501·256); B and D are
    padded to hardware tiles internally.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    B, D = x.shape
    Dw, V = q.shape
    assert D == Dw, (x.shape, q.shape)
    if V % tile_v != 0:
        raise ValueError(f"vocab {V} not divisible by tile_v={tile_v}")
    scale2d = scale.reshape(1, -1).astype(jnp.float32)
    assert scale2d.shape[1] == V, (scale.shape, V)
    # bf16 sublane tile is 16: pad the batch so the MXU rows are aligned
    Bp = max(16, ((B + 15) // 16) * 16)
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
    grid = (V // tile_v,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Bp, D), lambda i: (0, 0)),       # activations
            pl.BlockSpec((D, tile_v), lambda i: (0, i)),   # int8 weights
            pl.BlockSpec((1, tile_v), lambda i: (0, i)),   # quant scales
        ],
        out_specs=pl.BlockSpec((Bp, tile_v), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((Bp, V), jnp.float32),
        interpret=interpret,
        name="lm_head_int8",
    )(x, q, scale2d)
    out = out[:B]
    return out[0] if squeeze else out
