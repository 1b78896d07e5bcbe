"""Weight-only int8/int4 quantization for the serving engine.

The reference's headline configs serve quantized models through its
external engines (BASELINE: R1-Distill-Llama-70B FP8 on vLLM/TRT-LLM;
docs/architecture.md benchmarks; AWQ/int4 checkpoints via vLLM). Our
engine owns the model, so the analog is native: weights are stored
int8/int4 and dequantized inside the matmul — XLA reads the narrow dtype
from HBM and fuses the convert+scale into the MXU op, cutting the
per-decode-step weights-read floor (the dominant cost at small batch)
2×/4× vs bf16. int4 HBM streaming measured real on v5e: ~0.5 B/elem
effective, 1.9× the int8 read rate (PERF.md int4 probe).

int8 scheme: symmetric absmax per output channel (the last axis of a
stacked [L, D, F] weight; per row for the [V, D] embedding so the token
gather dequantizes cheaply and a tied lm head reuses the same scales per
column; per (layer, expert, out-channel) for the stacked MoE expert
tensors — for mixtral-class models the experts are the bulk of the
weights). Norms, biases, and the MoE router stay in the load dtype.

int4 scheme (AWQ-style group quantization, minus the activation-aware
calibration which needs calibration data): one scale per
(stack axes, contraction GROUP of 128, out-channel) — per-channel-only
int4 is too coarse for real checkpoints' outlier channels. The grouped
matmul contracts per group and applies scales between the two einsums
(:func:`mm`). Applied to the dense layer matmuls + lm_head; the
embedding stays int8 (its per-row gather scheme is already cheap) and
MoE experts stay int8 (the grouped expert-einsum generalization isn't
worth its complexity until a MoE config is weights-read-bound at int8).

int4 STORAGE is packed — two signed nibbles per int8 byte, adjacent
contraction rows paired — so every stored leaf is a plain int8 array
(whether S4 jax.Arrays could cross the jit boundary on the local chip
has not been tried). Each jitted program calls :func:`unpack_params` ONCE at its
top: bitcast int8→int4 ([.., D/2, F] → [.., D/2, F, 2]), un-interleave,
and an optimization_barrier pins the unpacked S4 buffer, so a K-step
decode dispatch pays one ~weights-pass unpack and then K steps read S4
at packed (0.5 B/elem) bandwidth. Measured on v5e (8192×14336, B=32,
K=32): 0.040 ms/step incl. amortized unpack vs int8's 0.093 — the win
scales with decode_steps_per_dispatch.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

__all__ = ["QuantizedArray", "quantize_array", "quantize_array_grouped",
           "quantize_params", "mm", "qeinsum", "GROUP_SIZE",
           "unpack_params", "pack_int4_rows", "unpack_int4_rows"]

# int4 contraction-group width (AWQ convention; divides every serving
# model's hidden/intermediate dims — falls back to one whole-axis group
# for tiny test geometries)
GROUP_SIZE = 128


@jax.tree_util.register_pytree_node_class
class QuantizedArray:
    """int8/int4 tensor + f32 scale; dequantizes as q * scale.

    ``group`` == 0: scale is broadcast-shaped against q (per-channel
    int8). ``group`` > 0: logical q is [..., D, F] with one scale per
    (contraction group, out-channel) — scale [..., D/group, F] — the
    grouped-int4 encoding (module docstring). ``packed4``: q holds two
    signed nibbles per byte, [..., D/2, F] int8 — unpack with
    :func:`unpack_int4_rows` (or the tree-level :func:`unpack_params`)
    before computing. ``no_kernel``: the Pallas grouped matmul
    (quant_matmul.py) must not serve this leaf — set by shard_params
    under any multi-device mesh, where pallas_call has no GSPMD
    partitioning rule."""

    def __init__(self, q: jax.Array, scale: jax.Array, group: int = 0,
                 packed4: bool = False, no_kernel: bool = False):
        self.q = q
        self.scale = scale
        self.group = group
        self.packed4 = packed4
        self.no_kernel = no_kernel

    @property
    def shape(self):           # the LOGICAL (unpacked) shape
        if self.packed4:
            s = self.q.shape
            return s[:-2] + (s[-2] * 2, s[-1])
        return self.q.shape

    @property
    def dtype(self):           # the *logical* dtype callers compute in
        return self.scale.dtype

    def __getitem__(self, idx) -> "QuantizedArray":
        """LEADING-axis (layer) indexing only: q and every scale layout
        share their leading dims (per-channel [L, 1, F], grouped
        [L, D/g, F], expert [L, E, 1, F]), so the same index applies to
        both. Used by the deepseek hybrid scans, which split stacked
        weights into a dense prefix and a MoE suffix."""
        return QuantizedArray(self.q[idx], self.scale[idx],
                              group=self.group, packed4=self.packed4,
                              no_kernel=self.no_kernel)

    def unpacked(self) -> "QuantizedArray":
        if not self.packed4:
            return self
        return QuantizedArray(unpack_int4_rows(self.q), self.scale,
                              group=self.group)

    def dequantize(self, dtype=None) -> jax.Array:
        w = self.unpacked()
        if w.group:
            s = jnp.repeat(w.scale, w.group, axis=-2)
            out = w.q.astype(w.scale.dtype) * s
        else:
            out = w.q.astype(w.scale.dtype) * w.scale
        return out.astype(dtype) if dtype is not None else out

    def tree_flatten(self):
        return (self.q, self.scale), (self.group, self.packed4,
                                      self.no_kernel)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, group=aux[0], packed4=aux[1],
                   no_kernel=aux[2])

    def __repr__(self):
        return (f"QuantizedArray(q={self.q.shape}, "
                f"scale={self.scale.shape}, group={self.group}, "
                f"packed4={self.packed4})")


def quantize_array(w: jax.Array, *,
                   keep_axes: tuple = (-1,)) -> QuantizedArray:
    """Symmetric absmax int8, one scale per coordinate of ``keep_axes``
    (reduced over every other axis; scale stays broadcast-shaped). Stacked
    per-layer weights pass keep_axes=(0, -1) so each (layer, out-channel)
    pair gets its own scale."""
    w32 = w.astype(jnp.float32)
    keep = {a % w.ndim for a in keep_axes}
    reduce_axes = tuple(a for a in range(w.ndim) if a not in keep)
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantizedArray(q, scale.astype(jnp.float32))


def pack_int4_rows(q: jax.Array) -> jax.Array:
    """int4-valued int8 [..., D, F] (D even) -> packed int8 [..., D/2, F]:
    adjacent contraction rows 2d/2d+1 become the low/high nibble of one
    byte — the layout jax.lax.bitcast_convert_type(int8 -> int4)
    reverses (low nibble first; verified identical on CPU and TPU)."""
    # all-int8 arithmetic: wider intermediates would materialize int32
    # copies of the whole weight tensor during streaming init (an OOM at
    # 70B scale); int8 shifts wrap to exactly the bit patterns we want
    lo = q[..., 0::2, :] & jnp.int8(0xF)
    hi = jnp.left_shift(q[..., 1::2, :], 4)
    return lo | hi


def unpack_int4_rows(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4_rows`: packed int8 [..., D/2, F] ->
    int4 [..., D, F]. A bitcast (free view of the packed bytes) plus one
    un-interleave — call OUTSIDE per-step loops so a K-step dispatch
    pays it once (module docstring)."""
    # arithmetic nibble split instead of bitcast_convert_type(int8→int4):
    # the bitcast lowering is broken on jax 0.4.x CPU (rank verifier
    # rejects it); int8 shifts sign-extend, so lo/hi land already signed
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)      # low nibble
    hi = jnp.right_shift(packed, 4)                         # high nibble
    un = jnp.stack([lo, hi], axis=-2)                       # [.., D/2, 2, F]
    s = packed.shape
    return un.reshape(s[:-2] + (s[-2] * 2, s[-1])).astype(jnp.int4)


def _kernel_serves(w: "QuantizedArray") -> bool:
    """True when the Pallas grouped matmul (quant_matmul.py) will
    consume this packed leaf directly — the ONE gate shared by
    unpack_params (which then leaves it packed) and mm (which then calls
    the kernel), so the two can't disagree.

    Default ON (DYN_INT4_KERNEL=0 falls back to the XLA grouped path):
    the XLA path materializes a [T, D/128, F] partial that grows with
    prefill length — measured 14 GB at a 7.7K-token 8B prefill, an OOM
    on the exact capacity/long-context configs int4 exists for — while
    the kernel streams with no partial. The kernel is ~15-20% slower at
    decode than the XLA grouped form (PERF.md int4 sections), a fair
    price for actually fitting."""
    import os
    if os.environ.get("DYN_INT4_KERNEL", "1") == "0":
        return False
    from .attention import _on_tpu
    from .quant_matmul import grouped_kernel_eligible
    if not (w.packed4 and not w.no_kernel and _on_tpu()):
        return False
    *_lead, d, f = w.shape
    return grouped_kernel_eligible(0, d, f, w.group)


def unpack_params(params: Dict[str, object]) -> Dict[str, object]:
    """Unpack packed-int4 leaves of a params tree into their S4 form,
    behind an optimization_barrier so XLA materializes the unpacked
    buffer once per program instead of re-deriving it per use. Call at
    the TOP of each jitted model program (engine/core.py does); outside
    jit the packed tree is the one that crosses boundaries (S4 arrays
    cannot — module docstring). Leaves the grouped Pallas kernel will
    serve stay PACKED — the kernel streams the packed bytes itself, so
    no unpack pass (or S4 copy) exists at all on that path."""
    out: Dict[str, object] = {}
    for k, v in params.items():
        if isinstance(v, QuantizedArray) and v.packed4 \
                and not _kernel_serves(v):
            u = v.unpacked()
            out[k] = QuantizedArray(jax.lax.optimization_barrier(u.q),
                                    u.scale, group=u.group)
        else:
            out[k] = v
    return out


def quantize_array_grouped(w: jax.Array, group: int = GROUP_SIZE,
                           bits: int = 4) -> QuantizedArray:
    """Symmetric absmax with one scale per (leading stack axes,
    contraction group, out-channel): w [..., D, F] -> logical q
    [..., D, F] int4/int8, scale [..., D/group, F] f32. When ``group``
    does not divide D the whole axis becomes one group (tiny test
    geometries). bits=4 with even D returns PACKED storage
    (pack_int4_rows); odd-D tiny geometries stay unpacked int8-held."""
    *_lead, D, F = w.shape
    if D % group != 0:
        group = D
    gn = D // group
    qmax = 2 ** (bits - 1) - 1
    w32 = w.astype(jnp.float32).reshape(w.shape[:-2] + (gn, group, F))
    absmax = jnp.max(jnp.abs(w32), axis=-2)            # [..., gn, F]
    scale = jnp.maximum(absmax, 1e-12) / qmax
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]), -qmax, qmax)
    q = q.reshape(w.shape).astype(jnp.int8)
    scale = scale.astype(jnp.float32)
    if bits == 4 and D % 2 == 0:
        return QuantizedArray(pack_int4_rows(q), scale, group=group,
                              packed4=True)
    return QuantizedArray(q, scale, group=group)


def _mm_grouped(x: jax.Array, w: QuantizedArray) -> jax.Array:
    """x [..., D] @ grouped-quantized w [D, F]: contract per group, then
    fold the [gn, F] scales in a second (tiny) contraction. XLA reads the
    int4/int8 payload from HBM and converts in-register; under a tp mesh
    both contractions partition cleanly (q and scale shard together on
    either axis). Packed weights unpack here for direct callers —
    per-step loops should pre-unpack the whole tree (unpack_params)."""
    if w.packed4 and _kernel_serves(w):
        from .quant_matmul import grouped_int4_matmul
        x2 = x[None, :] if x.ndim == 1 else x
        y = grouped_int4_matmul(x2, w.q, w.scale)
        return y[0] if x.ndim == 1 else y
    if w.packed4:
        w = w.unpacked()
    D = x.shape[-1]
    gn = D // w.group
    xg = x.reshape(x.shape[:-1] + (gn, w.group))
    qg = w.q.astype(x.dtype).reshape(gn, w.group, w.q.shape[-1])
    part = jnp.einsum("...gd,gdf->...gf", xg, qg)
    return jnp.einsum("...gf,gf->...f", part, w.scale.astype(x.dtype))


def mm(x: jax.Array, w, out_dtype=None) -> jax.Array:
    """x @ w for a plain array or a QuantizedArray (dequant fused into the
    matmul: XLA reads int8/int4 and converts in-register). ``out_dtype``
    (models/sambay.py asks for float32): the accumulator's precision is
    kept in the result and the scale is applied in it, where the default
    rounds both to x's dtype."""
    quantized = isinstance(w, QuantizedArray)
    if quantized and w.group:
        y = _mm_grouped(x, w)
        return y if out_dtype is None else y.astype(out_dtype)
    if out_dtype is not None:
        y = jnp.dot(x, (w.q if quantized else w).astype(x.dtype),
                    preferred_element_type=out_dtype)
        if quantized:
            y = y * w.scale.astype(out_dtype).reshape(w.scale.shape[-1])
        return y
    if quantized:
        y = x @ w.q.astype(x.dtype)
        return y * w.scale.astype(x.dtype).reshape(w.scale.shape[-1])
    return x @ w


def qeinsum(spec: str, a: jax.Array, w) -> jax.Array:
    """einsum with the same dequant-fuse rule as :func:`mm` for batched
    weights (MoE experts): contract on int8 converted in-register, apply
    the broadcast-shaped scale after the contraction. One owner for the
    dequant semantics — keep in sync with mm by calling, not copying."""
    if isinstance(w, QuantizedArray):
        if w.group:
            raise NotImplementedError(
                "grouped-quantized weights are not supported in qeinsum "
                "(MoE experts stay int8 under --quantization int4; see "
                "module docstring)")
        return jnp.einsum(spec, a, w.q.astype(a.dtype)) \
            * w.scale.astype(a.dtype)
    return jnp.einsum(spec, a, w)


# Weight names quantized (stacked per-layer [L, D, F] → per (L, F) scales).
_LAYER_MATMULS = ("wq", "wk", "wv", "wo", "gate", "up", "down",
                  # qwen2_moe shared expert (dense swiglu; the sigmoid
                  # sh_router stays full precision like the MoE router)
                  "sh_gate", "sh_up", "sh_down",
                  # MLA (models/mla.py): the q-LoRA pair, the latent
                  # down-projection, and the deepseek hybrid dense
                  # prefix — all consumed through mm(). wkv_b stays
                  # full precision DELIBERATELY: the absorbed decode
                  # contracts it raw in einsums (_split_wkv_b), and its
                  # [rank, H*(dn+dv)] bytes are small
                  "wq_a", "wq_b", "wkv_a",
                  "dense_gate", "dense_up", "dense_down",
                  # deepseek_v32 lightning indexer: query up-projection,
                  # key projection and head-weight projection, all
                  # through mm() (its LayerNorm stays full precision)
                  "idx_wq_b", "idx_wk", "idx_w",
                  # dots3_note's window layers (models/mla.py): the same
                  # leaves at the second geometry; swa_wkv_b stays full
                  # precision like wkv_b, and the headwise gates (wg,
                  # swa_wg: H out-channels behind a sigmoid) like a router
                  "swa_wq_a", "swa_wq_b", "swa_wkv_a", "swa_wo",
                  # mimo_v2's window layers (models/mimo.py): plain
                  # grouped-query projections at the second geometry
                  # (swa_sink, one float32 scalar a head, stays as it is)
                  "swa_wq", "swa_wk", "swa_wv",
                  # exaone_moe's resident multi-token-prediction module
                  # (mtp.<leaf>, a stack of one layer): eh_proj int8 like
                  # the block's own projections; its norms stay as they are
                  "eh_proj",
                  # phi4flash (models/sambay.py; names are
                  # layers.<kind>.<leaf>): the MLP, the state-space
                  # layer's four projections, the attention layers' qkv /
                  # q / output projections and the gated memory unit's
                  # two. A_log, D, dt_b, the conv taps, the lambda vectors,
                  # norms and biases stay as they are
                  "mlp_gateup", "mlp_down", "ssm_in", "ssm_x", "ssm_dt",
                  "ssm_out", "attn_qkv", "attn_out", "cross_q", "gmu_in",
                  "gmu_out",
                  # kimi_linear's delta-attention layers (models/
                  # kimi_linear.py): the fused q|k|v projection and the
                  # output projection, 37.7 M of a layer's 39.5 M. The
                  # low-rank pairs (kda_low, kda_fb, kda_gb: the decay and
                  # the gates stand behind them) stay in the load dtype,
                  # A_log, dt_bias and the convolution taps float32
                  "kda_in", "kda_wo",
                  # granitemoehybrid's Mamba-2 layers (models/
                  # granite_hybrid.py): the z | xBC | dt projection and the
                  # output projection, 102.2 M of a layer's 102.3 M. A_log,
                  # dt_bias, D and the convolution's taps stay float32, its
                  # bias and the gated norm in the load dtype
                  "ssd_in", "ssd_out")
# MoE expert tensors [L, E, D, F] → per (L, E, out-channel) scales. For
# mixtral-class models the experts ARE the weights, so leaving them bf16
# would forfeit the whole int8 HBM-read win; the router stays full
# precision (tiny, and routing is precision-sensitive).
_MOE_MATMULS = ("moe_gate", "moe_up", "moe_down")


def quantize_params(params: Dict[str, jax.Array],
                    include_embed: bool = True,
                    bits: int = 8) -> Dict[str, object]:
    """Return a params tree with matmul weights quantized.

    bits=8:
    - ``layers.{wq,wk,wv,wo,gate,up,down}``: per-(layer, out-channel).
    bits=4: the same layer matmuls, int4 with per-(group-of-128,
    out-channel) scales (module docstring).
    Either way:
    - ``lm_head`` ([D, V]): int8 per out-channel (vocab widths don't
      lane-align for the int4 kernel; the int8 head keeps its fused
      Pallas kernel).
    - ``embed`` ([V, D], optional): int8 per ROW (= per token vector), so
      the embedding gather dequantizes with one scale per token and a
      TIED lm head (x @ embed.T) gets per-column scales from the same
      tensor.
    - ``layers.{moe_gate,moe_up,moe_down}`` ([L, E, D, F]): int8 per
      (layer, expert, out-channel) — for MoE models the experts are the
      bulk of the weights (models/llama.py moe_mlp dequant-fuses them).
    - norms / biases / MoE router untouched.
    """
    tied = "lm_head" not in params
    out: Dict[str, object] = {}
    for name, w in params.items():
        out.update(_quantize_named(name, w, include_embed, tied, bits))
    return out


def _quantize_named(name: str, w: jax.Array, include_embed: bool,
                    tied: bool, bits: int = 8) -> Dict[str, object]:
    """The per-tensor dispatch shared by quantize_params (whole-tree,
    eager) and init_params_quantized (streaming, one jit per tensor)."""
    # the leaf: "wq" of layers.wq, "ssm_in" of layers.mamba.ssm_in; the
    # multi-token-prediction block's leaves are a stack of one layer
    stacked = name.startswith(("layers.", "mtp."))
    suffix = name.rsplit(".", 1)[1] if stacked else name
    if stacked and suffix in _LAYER_MATMULS:
        if bits == 4:
            # stacked [L, D, F]: int4, scale [L, D/128, F]
            return {name: quantize_array_grouped(w, bits=4)}
        # stacked [L, D, F]: per (layer, out-channel) → scale [L, 1, F]
        return {name: quantize_array(w, keep_axes=(0, -1))}
    if stacked and suffix in _MOE_MATMULS:
        # stacked [L, E, D, F]: per (layer, expert, out-channel)
        # → scale [L, E, 1, F], which broadcasts over the expert
        # einsums' batched-N axis after the per-layer slice.
        # (int8 even under bits=4 — module docstring)
        return {name: quantize_array(w, keep_axes=(0, 1, -1))}
    if name == "lm_head":
        # int8 even under bits=4: vocab widths (e.g. 128256/8) don't
        # lane-align for the grouped kernel, the XLA grouped fallback
        # materializes a [N, D/128, V] partial bigger than the int8 read
        # it saves, and int8 keeps the fused Pallas head kernel
        return {name: quantize_array(w, keep_axes=(-1,))}
    if name == "embed" and include_embed:
        # int8 per-row: scale shape [V, 1] (bits=4 keeps the embed int8 —
        # the gather reads one row per token, not the whole tensor)
        out = {name: quantize_array(w, keep_axes=(0,))}
        if tied:
            # tied head: materialize a PRE-TRANSPOSED int8 head —
            # `x @ q.T` of an int8 matrix defeats XLA's transpose
            # fusion and measured 2x slower than the bf16 tied path
            # at small batch; the [D, V] copy reads int8 in natural
            # orientation instead (263MB vs 525MB bf16 per step for
            # llama-1B)
            out["lm_head"] = quantize_array(w.T, keep_axes=(-1,))
        return out
    return {name: w}


def init_params_quantized(cfg, key: jax.Array, dtype=jnp.bfloat16,
                          include_embed: bool = True,
                          bits: int = 8) -> Dict[str, object]:
    """Random-init + quantize one stacked tensor at a time, entirely
    inside a jit, so the full bf16 tree is never materialized.

    init_params followed by quantize_params peaks at the whole bf16 tree
    (16 GB for Llama-3-8B geometry — an OOM on one 16 GB v5e chip before
    quantization even starts). Here each tensor's init→absmax→round
    pipeline is one jitted program whose only output is the int8 payload
    + f32 scales, so XLA frees the bf16/f32 intermediates inside the
    program; peak HBM ≈ quantized-so-far + one tensor's working set.

    Key-splitting order matches init_params exactly, so the quantized
    values equal quantize_params(init_params(...)) for the same seed, up
    to one-step int8 rounding ties (jit fusion may contract the
    round(w/scale) arithmetic differently than the eager two-pass)."""
    from .models import module_for
    family = module_for(cfg)
    shapes = family.param_shapes(cfg)
    tied = "lm_head" not in shapes
    out: Dict[str, object] = {}
    for name, shape in shapes.items():
        key, sub = jax.random.split(key)

        def build(sub, name=name, shape=shape):
            w = family.init_one_param(cfg, name, shape, sub, dtype)
            return _quantize_named(name, w, include_embed, tied, bits)

        out.update(jax.jit(build)(sub))
    return out
