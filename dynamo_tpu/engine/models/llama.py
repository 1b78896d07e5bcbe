"""Llama-family transformer in pure JAX with a paged KV cache.

This is the engine-side model the reference outsources to vLLM/SGLang/TRT-LLM
(SURVEY.md §2.2 engines). Design is TPU-first:

- stacked-layer parameters + `lax.scan` over layers → one compiled layer body
  (fast compile, good for pjit partitioning);
- KV cache per layer is a flat paged token pool `[NTOK, KVH*Dh]`
  (block-major; see attention.py for why), updated in place via donated
  buffers;
- prefill is "batched multi-token decode": chunk KV is scattered into the
  paged pool first, then queries attend over the block table — which makes
  chunked prefill and prefix-cache reuse the same code path;
- no data-dependent Python control flow: everything under jit uses static
  shapes (bucketed T) and `lax` primitives.

Weight layout matches HF llama checkpoints after transpose (see weights.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..attention import causal_attention  # noqa: F401  (used by sp path)
from ..attention import (KV_SCALE_LANES, RAGGED_WIN_SENTINEL, _on_tpu,
                         dequant_kv_rows, flash_prefill,
                         flash_prefill_supported, flat_token_indices,
                         kernel_wanted, kv_row_groups, paged_attention,
                         quantize_kv_rows, ragged_paged_attention_pallas,
                         ragged_supported,
                         softcap_scores as _softcap)
from ..config import ModelConfig
from ..grouped_matmul import (ROW_TILE, grouped_matmul,
                              grouped_matmul_eligible)
from ..quant import QuantizedArray, mm, qeinsum

Params = Dict[str, jax.Array]
KVCache = Dict[str, jax.Array]  # {"k": [L, NTOK, KVH*Dh], "v": ...}


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    if plus_one:   # gemma convention: weights are zero-centered
        return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return normed.astype(x.dtype) * w




def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Rotary inverse frequencies incl. llama-3 rope scaling."""
    dim = cfg.head_dim
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    rs = cfg.rope_scaling
    if rs is not None and rs.rope_type in ("llama3",):
        low_wl = rs.original_max_position_embeddings / rs.low_freq_factor
        high_wl = rs.original_max_position_embeddings / rs.high_freq_factor
        wl = 2 * np.pi / inv
        smooth = (rs.original_max_position_embeddings / wl - rs.low_freq_factor) / (
            rs.high_freq_factor - rs.low_freq_factor)
        scaled = np.where(
            wl > low_wl, inv / rs.factor,
            np.where(wl < high_wl, inv,
                     (1 - smooth) * inv / rs.factor + smooth * inv))
        inv = scaled
    elif rs is not None and rs.rope_type == "linear":
        inv = inv / rs.factor
    elif rs is not None and rs.rope_type == "longrope":
        # phi3 128k: per-dim frequency divisors (HF
        # _compute_longrope_parameters). Selection is STATIC (see
        # config.RopeScaling): long iff the deployment can exceed the
        # pretrained window, short when EngineCore proved it can't.
        use_long = (rs.longrope_active == "long"
                    or (rs.longrope_active == "auto"
                        and cfg.max_position_embeddings
                        > rs.original_max_position_embeddings))
        ext = np.asarray(rs.long_factor if use_long else rs.short_factor,
                         np.float64)
        inv = inv / ext
    return inv.astype(np.float32)


def rope_attention_scaling(cfg: ModelConfig) -> float:
    """cos/sin multiplier — longrope's sqrt(1 + ln(M/O)/ln(O)) (HF
    attention_scaling, fixed at init from the CONFIG ratio and applied
    in both short and long modes); 1.0 for every other rope type."""
    import math
    rs = cfg.rope_scaling
    if rs is None or rs.rope_type != "longrope":
        return 1.0
    if rs.attention_factor:
        return rs.attention_factor
    factor = (cfg.max_position_embeddings
              / rs.original_max_position_embeddings)
    if factor <= 1.0:
        return 1.0
    return math.sqrt(1 + math.log(factor)
                     / math.log(rs.original_max_position_embeddings))


def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array, scaling: float = 1.0) -> jax.Array:
    """x: [T, H, Dh]; positions: [T]. HF half-split rotate convention.
    ``scaling`` multiplies cos/sin (longrope attention factor)."""
    angles = positions[:, None].astype(jnp.float32) * inv_freq[None, :]  # [T, Dh/2]
    cos = jnp.cos(angles)[:, None, :] * scaling
    sin = jnp.sin(angles)[:, None, :] * scaling
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin,
                           x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("swiglu")
def swiglu(x: jax.Array, gate_w: jax.Array, up_w: jax.Array,
           down_w: jax.Array, act: str = "silu",
           gateup_w=None) -> jax.Array:
    if gateup_w is not None:      # fused gate|up (fuse_stacked_matmuls)
        gu = mm(x, gateup_w)
        F = gu.shape[-1] // 2
        g, u = gu[..., :F], gu[..., F:]
    else:
        g, u = mm(x, gate_w), mm(x, up_w)
    if act in ("gelu_pytorch_tanh", "gelu"):   # gemma families
        gated = jax.nn.gelu(g, approximate=True)
    elif act == "silu":
        gated = jax.nn.silu(g)
    else:
        raise ValueError(f"unsupported hidden_act {act!r}")
    return mm(gated * u, down_w)


def fuse_stacked_matmuls(params: dict, cfg: ModelConfig) -> dict:
    """Concatenate wq|wk|wv → wqkv and gate|up → gateup along the out
    axis (round-5 decode perf: one wide matmul streams the same weight
    bytes with fewer fusion boundaries — measured ~16 µs/layer at the
    70B-shard geometry, PERF.md "Where the next wins are").

    SINGLE-DEVICE layouts only (EngineCore applies it when no mesh is
    given): under tp, the fused out axis would need a per-shard column
    permutation that NamedSharding cannot express — each rank of a
    future shard_map decode path could fuse its LOCAL weights with this
    same transform. Biases (bq/bk/bv) stay separate: they add after the
    split, bit-identically. Grouped (int4) weights are left unfused —
    the Pallas grouped kernel serves them per-tensor."""
    def cat(keys, new, stack="layers."):
        ws = [params.get(stack + k) for k in keys]
        if any(w is None for w in ws):
            return
        if all(isinstance(w, QuantizedArray) for w in ws):
            if any(w.group or w.packed4 for w in ws):
                return
            params[stack + new] = QuantizedArray(
                jnp.concatenate([w.q for w in ws], axis=-1),
                jnp.concatenate([w.scale for w in ws], axis=-1))
        elif not any(isinstance(w, QuantizedArray) for w in ws):
            params[stack + new] = jnp.concatenate(ws, axis=-1)
        else:
            return
        for k in keys:
            del params[stack + k]

    cat(("wq", "wk", "wv"), "wqkv")
    # mimo_v2's window layers: the same three at their own geometry
    cat(("swa_wq", "swa_wk", "swa_wv"), "swa_wqkv")
    cat(("gate", "up"), "gateup")
    # MoE families: expert grids, shared experts, and the deepseek
    # hybrid's dense-prefix stacks fuse the same way (cat skips any
    # pair the family doesn't have)
    cat(("moe_gate", "moe_up"), "moe_gateup")
    cat(("sh_gate", "sh_up"), "sh_gateup")
    cat(("dense_gate", "dense_up"), "dense_gateup")
    # exaone_moe's resident multi-token-prediction block (models/mimo.py
    # mtp_shapes): a stack of one layer, fused the same way
    cat(("wq", "wk", "wv"), "wqkv", "mtp.")
    cat(("moe_gate", "moe_up"), "moe_gateup", "mtp.")
    cat(("sh_gate", "sh_up"), "sh_gateup", "mtp.")
    return params


# Where the dense-over-experts form stops being the cheap one. It reads
# every expert's weights once and runs all E experts for all N rows, so
# it costs max(weight bytes / HBM bandwidth, 2*N*E*D*F / MXU peak): the
# two meet at N = peak / (2 * bandwidth) rows per int8 weight byte. On a
# TPU v5e (197e12 bf16 FLOP/s, 819e9 B/s: Google Cloud's "TPU v5e" page)
# that ridge is ~120 rows. Below it the dense form costs the weight
# stream, which the grouped form reads too, and nothing can beat it;
# above it the dense form costs FLOPs that grow with E / top_k. The
# grouped form adds a sort, a row gather and an un-permute, and tiles of
# ROW_TILE rows that straddle the groups; timed on the chip against the
# dense form at the benchmark's Qwen1.5-MoE widths, buckets 128-2048
# (PERF.md section 5, PR 32), it wins by more than noise from here up.
GROUPED_MIN_ROWS = 256


def experts_run_grouped(n_rows: int, num_experts: int, top_k: int,
                        d_model: int, d_ff: int, sharded: bool) -> bool:
    """Which form ``run_experts`` takes, from what the program can see:
    its static row count, the expert stacks' shapes and their layout.
    The one chooser: the model code and the engine's ``grouped_rows``
    counter both ask it.

    Dense wherever the stacks are sharded over a mesh (the "ep" layout
    relies on E staying a contracted axis so that XLA turns the combine
    into a psum, and a Pallas call has no partitioning rule), wherever
    the row count leaves the dense form bandwidth-bound
    (GROUPED_MIN_ROWS), where every expert is picked anyway, and at
    widths the kernel does not tile."""
    return (not sharded and n_rows >= GROUPED_MIN_ROWS
            and top_k < num_experts
            and grouped_matmul_eligible(d_model, d_ff)
            and grouped_matmul_eligible(d_ff, d_model))


def grouped_prefill_rows(statics: "ModelStatics", bucket: int,
                         true_len: int) -> int:
    """The rows of one prefill dispatch (``true_len`` valid rows in a
    ``bucket``-row program) whose expert layers run grouped: all of them
    or none. For the prefill flight record's ``grouped_rows``."""
    cfg = statics.cfg
    if cfg.num_experts <= 0:
        return 0
    return true_len if experts_run_grouped(
        bucket, cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size,
        cfg.intermediate_size, statics.sharded) else 0


@jax.named_scope("run_experts_dense")
def run_experts_dense(x: jax.Array, gate_w: jax.Array, up_w: jax.Array,
                      down_w: jax.Array, top_idx: jax.Array,
                      top_w: jax.Array, gateup_w=None) -> jax.Array:
    """Dense-over-E expert execution + one-hot combine: every expert for
    every row, the unpicked ones multiplied by zero. E stays a
    batched/contracted axis, so the mesh "ep" sharding turns the combine
    into an XLA psum, and a ``top_idx`` outside [0, E) adds nothing. The
    form for few rows and for every mesh (``experts_run_grouped``)."""
    E = down_w.shape[0]
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
        * top_w[..., None], axis=1)                              # [N, E]
    if gateup_w is not None:      # fused gate|up (fuse_stacked_matmuls)
        gu = qeinsum("nd,edf->enf", x, gateup_w)
        F = gu.shape[-1] // 2
        g, u = gu[..., :F], gu[..., F:]
    else:
        g = qeinsum("nd,edf->enf", x, gate_w)
        u = qeinsum("nd,edf->enf", x, up_w)
    y = qeinsum("enf,efd->end", jax.nn.silu(g) * u, down_w)      # [E, N, D]
    return jnp.einsum("ne,end->nd", combine.astype(y.dtype), y)


@jax.named_scope("run_experts_grouped")
def run_experts_grouped(x: jax.Array, gate_w: jax.Array, up_w: jax.Array,
                        down_w: jax.Array, top_idx: jax.Array,
                        top_w: jax.Array, gateup_w=None,
                        valid_rows: Optional[jax.Array] = None,
                        layer: Optional[jax.Array] = None,
                        interpret: bool = False) -> jax.Array:
    """The routed experts only: same arguments and result as
    ``run_experts_dense``, computed over the N*k (row, expert) pairs
    sorted by expert (grouped_matmul.py). With ``layer`` (a traced
    scalar) the stacks are every layer's, ``[L, E, ...]``, and the
    kernel reads that layer's part in place.

    A pair that must compute nothing sorts behind the last group and
    belongs to none: a ``top_idx`` outside [0, E) (an expert another
    chip holds) and, with ``valid_rows`` (a traced scalar), every pair
    of a row at or past it (a prefill bucket's padding). Their rows of
    the grouped matmuls are never computed, and never read back: the
    combine selects zero for them."""
    N, D = x.shape
    k = top_idx.shape[1]
    E = down_w.shape[-3]
    P = N * k
    pair_expert = top_idx.reshape(P).astype(jnp.int32)
    live = (pair_expert >= 0) & (pair_expert < E)
    if valid_rows is not None:
        live &= jnp.arange(P, dtype=jnp.int32) // k < valid_rows
    key = jnp.where(live, pair_expert, E)
    pad = -P % ROW_TILE                       # whole row tiles
    key_p = jnp.concatenate([key, jnp.full((pad,), E, jnp.int32)])
    # sorted position -> pair (stable: a group keeps its rows in order)
    order = jnp.argsort(key_p, stable=True).astype(jnp.int32)
    group_sizes = jnp.bincount(key_p, length=E + 1)[:E].astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0, mode="clip")         # [P+pad, D]

    gmm = functools.partial(grouped_matmul, group_sizes=group_sizes,
                            layer=layer, interpret=interpret)
    if gateup_w is not None:      # fused gate|up (fuse_stacked_matmuls)
        gu = gmm(xs, gateup_w)
        F = gu.shape[-1] // 2
        g, u = gu[..., :F], gu[..., F:]
    else:
        g, u = gmm(xs, gate_w), gmm(xs, up_w)
    y = gmm(jax.nn.silu(g) * u, down_w)                       # [P+pad, D]

    # pair -> sorted position, then the weighted sum over a row's k pairs
    where = jnp.zeros((P + pad,), jnp.int32).at[order].set(
        jnp.arange(P + pad, dtype=jnp.int32), unique_indices=True)[:P]
    picked = jnp.where(live[:, None], jnp.take(y, where, axis=0), 0)
    out = jnp.sum(picked.reshape(N, k, D).astype(jnp.float32)
                  * top_w[..., None].astype(jnp.float32), axis=1)
    return out.astype(x.dtype)


def run_experts(x: jax.Array, gate_w: jax.Array, up_w: jax.Array,
                down_w: jax.Array, top_idx: jax.Array, top_w: jax.Array,
                gateup_w=None, *, sharded: bool = True,
                valid_rows: Optional[jax.Array] = None,
                layer: Optional[jax.Array] = None) -> jax.Array:
    """The expert MLPs of one MoE layer: ``top_idx`` / ``top_w`` [N, k]
    are each row's experts and mixing weights, whatever the family's
    router made them. ONE entry point (moe_mlp and mla._moe_mlp both
    call it, so their layouts cannot diverge) over two forms of the same
    function, picked by ``experts_run_grouped`` from the static row
    count, the stacks' shapes and ``sharded`` (True, the default for a
    caller that does not know, keeps the dense form). With ``layer`` the
    stacks are every layer's: only where ``split_expert_stacks``, which
    asks the same chooser, kept them whole for the grouped form."""
    E, F, D = down_w.shape[-3:]
    if experts_run_grouped(x.shape[0], E, top_idx.shape[1], D, F, sharded):
        return run_experts_grouped(x, gate_w, up_w, down_w, top_idx, top_w,
                                   gateup_w=gateup_w, valid_rows=valid_rows,
                                   layer=layer, interpret=not _on_tpu())
    return run_experts_dense(x, gate_w, up_w, down_w, top_idx, top_w,
                             gateup_w=gateup_w)


EXPERT_STACKS = ("moe_gate", "moe_up", "moe_down", "moe_gateup")


def split_expert_stacks(stack: dict, n_rows: int, top_k: int,
                        sharded: bool) -> Tuple[dict, dict]:
    """→ (what the layer scan slices, what it must not): where the
    experts of an ``n_rows`` program run grouped, their stacks stay whole
    beside the scan and the layer body hands them on with the layer's
    index (``run_experts(..., layer=)``). A Pallas call is a custom call
    and wants its operands whole: given the scan's per-layer slice, XLA
    first copies ``[E, D, 2F]`` and ``[E, F, D]`` out of the stacks,
    every layer (measured, PR 32: 0.73 s of a 2.36 s prefill program at
    the Qwen1.5-MoE widths; an XLA fusion reads the slice in place)."""
    down = stack.get("moe_down")
    if down is None:
        return stack, {}
    E, F, D = down.shape[-3:]
    if not experts_run_grouped(n_rows, E, top_k, D, F, sharded):
        return stack, {}
    return ({k: v for k, v in stack.items() if k not in EXPERT_STACKS},
            {k: v for k, v in stack.items() if k in EXPERT_STACKS})


@jax.named_scope("moe_mlp")
def moe_mlp(x: jax.Array, router_w: jax.Array, gate_w: jax.Array,
            up_w: jax.Array, down_w: jax.Array, top_k: int,
            norm_topk: bool = True,
            shared: Optional[tuple] = None,
            gateup_w=None, shared_gateup=None, *,
            sharded: bool = True,
            valid_rows: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None) -> jax.Array:
    """Sparse MoE MLP: route, run the experts, add the shared expert.

    x: [N, D]; router_w: [D, E]; gate/up: [E, D, F]; down: [E, F, D].
    ``norm_topk``: True = softmax renormalized over the top-k logits
    (HF Mixtral convention, ≡ softmax-then-topk-then-renorm); False =
    qwen2_moe's norm_topk_prob=false — softmax over ALL experts, the
    top-k weights used WITHOUT renormalization (a different function:
    weights no longer sum to 1). ``shared``: qwen2_moe shared expert
    (sh_gate [D,Fs], sh_up, sh_down [Fs,D], sh_router [D,1]) — a dense
    swiglu added to every token, scaled by a learned sigmoid gate.

    The experts run in one of two forms (``run_experts``). Dense over
    the expert axis where the row count leaves it bandwidth-bound
    (decode) and under every mesh: E stays a contracted/batched axis, so
    sharding E over the mesh "ep" axis makes XLA compute E/ep experts
    per device and psum the combine, expert parallelism as a compiler
    layout with no explicit dispatch, at E/top_k times the routed FLOPs.
    Grouped, the routed pairs only, where that factor is what the
    device's time goes to: a single-device prefill (``sharded`` False)
    of GROUPED_MIN_ROWS rows or more, of which only ``valid_rows`` are
    computed. ``layer``: gate/up/down are every layer's stacks,
    ``[L, E, ...]``, and this is the layer to read (run_experts).
    """
    N, E = x.shape[0], router_w.shape[-1]
    logits = (x @ router_w).astype(jnp.float32)                  # [N, E]
    if norm_topk:
        top_logits, top_idx = jax.lax.top_k(logits, top_k)       # [N, k]
        top_w = jax.nn.softmax(top_logits, axis=-1)              # [N, k]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, top_k)
    out = run_experts(x, gate_w, up_w, down_w, top_idx, top_w,
                      gateup_w=gateup_w, sharded=sharded,
                      valid_rows=valid_rows, layer=layer)
    if shared is not None:
        sh_gate, sh_up, sh_down, sh_router = shared
        with jax.named_scope("shared_expert"):
            s = swiglu(x, sh_gate, sh_up, sh_down, "silu",
                       gateup_w=shared_gateup)
            sg = jax.nn.sigmoid(
                (x @ sh_router).astype(jnp.float32))             # [N, 1]
            out = out + sg.astype(out.dtype) * s
    return out


# ---------------------------------------------------------------------------
# Parameter init / shapes
# ---------------------------------------------------------------------------


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    if cfg.has_swa_gqa:
        # grouped-query layers of two geometries (mimo_v2): a sibling
        # module, entered through this family's four entry points so that
        # every caller that knows the grouped-query family by this module
        # (the engine, quant.init_params_quantized, engine/replay.py,
        # benchmark/compile_check.py) gets the model it was given
        from . import mimo
        return mimo.param_shapes(cfg)
    L, D = cfg.num_layers, cfg.hidden_size
    H, KVH, Dh, F = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        "layers.wq": (L, D, H * Dh),
        "layers.wk": (L, D, KVH * Dh),
        "layers.wv": (L, D, KVH * Dh),
        "layers.wo": (L, H * Dh, D),
    }
    if cfg.num_experts > 0:
        # mixtral-style sparse MoE MLP (experts stacked on axis 1, sharded
        # over the mesh "ep" axis — parallel/sharding.py param_pspecs)
        E = cfg.num_experts
        shapes.update({
            "layers.router": (L, D, E),
            "layers.moe_gate": (L, E, D, F),
            "layers.moe_up": (L, E, D, F),
            "layers.moe_down": (L, E, F, D),
        })
        if cfg.shared_expert_size > 0:
            # qwen2_moe shared expert: dense swiglu + sigmoid gate
            Fs = cfg.shared_expert_size
            shapes.update({
                "layers.sh_gate": (L, D, Fs),
                "layers.sh_up": (L, D, Fs),
                "layers.sh_down": (L, Fs, D),
                "layers.sh_router": (L, D, 1),
            })
    else:
        shapes.update({
            "layers.gate": (L, D, F),
            "layers.up": (L, D, F),
            "layers.down": (L, F, D),
        })
    if cfg.attention_bias:  # qwen2-style qkv biases
        shapes["layers.bq"] = (L, H * Dh)
        shapes["layers.bk"] = (L, KVH * Dh)
        shapes["layers.bv"] = (L, KVH * Dh)
    if cfg.qk_norm:  # qwen3-style per-head q/k rms norm
        shapes["layers.q_norm"] = (L, Dh)
        shapes["layers.k_norm"] = (L, Dh)
    if cfg.post_norms:  # gemma2 post-attn / pre+post-ffw norms
        shapes["layers.ln1_post"] = (L, D)
        shapes["layers.ln2_post"] = (L, D)
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def init_one_param(cfg: ModelConfig, name: str, shape: tuple,
                   sub: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Initialize a single (stacked) parameter tensor; factored out of
    init_params so quant.init_params_quantized can build+quantize one
    tensor at a time without materializing the full bf16 tree."""
    if cfg.norm_on_output and name.endswith("q_norm"):
        # exaone_moe norms every query and key head, so wq and wk set no
        # scale: a trained q_norm does (QK_NORM_SEEDED)
        return jnp.full(shape, QK_NORM_SEEDED, dtype=dtype)
    if cfg.norm_on_output and name.endswith(("ln1", "ln2")):
        # the weight a normed sub-layer output joins the stream at
        return jnp.full(shape, OUTPUT_NORM_SEEDED, dtype=dtype)
    if name.endswith("hnorm"):
        # the module's norm on the main model's (already normed) hidden
        # state: at 1 it would change nothing, and say nothing of whether
        # the module reads it
        return jnp.full(shape, MTP_HNORM_SEEDED, dtype=dtype)
    if name.endswith(("ln1", "ln2", "ln1_post", "ln2_post",
                      "q_norm", "k_norm",
                      "kv_norm", "q_a_norm",
                      "idx_k_norm_w", "final_norm", "enorm", "hnorm")):
        if cfg.mla_lora_rescale and name.endswith("q_a_norm"):
            # dots3_note multiplies its normed LoRA latents by
            # sqrt(hidden / rank). The q latent's norm carries the inverse
            # (a trained weight would); the kv latent's stays 1, so keys and
            # values stand sqrt(hidden / rank) over a normalised input: see
            # MIXED_SEEDED for what the two were measured against
            return jnp.full(shape, (shape[-1] / cfg.hidden_size) ** 0.5,
                            dtype=dtype)
        return (jnp.zeros(shape, dtype=dtype)
                if cfg.norm_plus_one
                else jnp.ones(shape, dtype=dtype))
    if cfg.has_swa_gqa and name.endswith("router_bias"):
        # mimo_v2: a trained e_score_correction_bias is not 0, and one that
        # is says nothing of whether the choice reads it (it biases the
        # choice alone, never the weights)
        return (ROUTER_BIAS_SEEDED * jax.random.normal(
            sub, shape, dtype=jnp.float32)).astype(dtype)
    if name.endswith(("bq", "bk", "bv", "router_bias", "idx_k_norm_b")):
        return jnp.zeros(shape, dtype=dtype)
    if name.endswith("swa_sink"):
        # mimo_v2's learned sinks, float32 whatever the load dtype: seeded
        # around the log of what a window's keys sum to under the seeded
        # scores (GQA_MIXED_SEEDED), so that a sink takes a share of a
        # window row's mass that a comparison can see, wide enough that
        # the heads differ
        mean, std = SINK_SEEDED
        return mean + std * jax.random.normal(sub, shape, dtype=jnp.float32)
    fan_in = shape[-2] if len(shape) > 1 else shape[-1]
    return (jax.random.normal(sub, shape, dtype=jnp.float32)
            * seeded_std(cfg, name, fan_in)).astype(dtype)


# Seeded weights of a sparse-attention model (ModelConfig.index_topk > 0).
# Its top-k is a step: a bf16 program and a float32 reference disagree on
# the members nearest the threshold, and at fan_in^-0.5 throughout (an
# embedding row of norm 0.7 under branches of norm 85) the stream is made
# of branch outputs alone, so that disagreement is passed on whole, layer
# after layer (docs/dsa.md "Random weights"). So the stream carries the
# embedding at the scale of a normalised branch input and every branch is
# a perturbation of it, as in a trained model: "embed" is a standard
# deviation, the others factors on fan_in^-0.5 of the projections that
# write into the stream (attention, dense and shared MLPs, routed experts);
# what they were measured against: PERF.md section 6, PR 31.
SPARSE_SEEDED = {"embed": 1.0, "wo": 0.5, "down": 0.25, "moe_down": 0.1}

# Seeded weights of a model that holds one chip's share of its experts
# (ModelConfig.num_experts_total > 0) and has no indexer. The router's
# top-k is a step too, and with a share held a flipped choice is not one
# expert for another but a held expert's output there or not: at
# fan_in^-0.5 one flip moves a logit by 0.1-0.4 of the logits' standard
# deviation (docs/mla_dense.md "Random weights"). Only what a flip moves
# is damped: the routed experts' down-projection; the embedding stands at
# the scale of a normalised branch input, so that it is a part of the
# stream (a quarter of its variance after eight layers) and not nothing.
# Attention, the dense and the shared MLPs keep fan_in^-0.5: a layer left
# out, a lower precision and the router cut to the share all stay visible
# (what the factors were measured against: PERF.md section 6, PR 37).
SHARE_SEEDED = {"embed": 1.0, "moe_down": 0.5}


# Seeded weights of a model whose full layers (indexer) stand beside window
# layers of a geometry of their own and whose LoRA latents are rescaled
# (dots3_note; ModelConfig.has_swa_latent). SPARSE_SEEDED as it stands
# averages 2,048 or 513 random values under a softmax of unit scores: every
# attention branch is a few percent of the stream at 33k tokens, and a gate,
# a window, a rope base or a selection left out reads 0.03-0.10 of the
# logits' standard deviation, inside the tolerance. With both LoRA norms at 1
# (scores 7 times a unit model's, a softmax near one-hot) the bf16 program
# stands 1.3 off the float32 reference. Between the two: q_a_norm at the
# inverse of its rescale and kv_norm at 1 (init_one_param: scores 2-2.7
# times a unit model's, values 2.2-3.2), the window layers' wo as
# SPARSE_SEEDED has it, and the full layers' wo at half of that, because
# what their top-k's flipped members move is passed on through it. Measured
# on the chip at 32,832 tokens (PERF.md section 6, PR 42, second session):
# the program 0.07 off the reference and the reference in bf16 0.08 off the
# program; the gate left out 1.13, the selection 0.45, the kv rescale 0.62.
MIXED_SEEDED = dict(SPARSE_SEEDED, wo=0.25, swa_wo=0.5)


# Seeded weights of a model of two grouped-query geometries with a sink in
# its window layers' softmax (mimo_v2; ModelConfig.has_swa_gqa). At
# fan_in^-0.5 throughout the scores are of unit variance: a softmax over
# 33k (or 128) such keys is nearly flat, attention averages that many random
# values, and a window, a rope base, a sink or a value scale left out moves
# the logits by less than the tolerance. So wq and wk stand at 1.6 times
# fan_in^-0.5 (scores of standard deviation ~2.6: a query's mass lies on a
# few keys, as in a trained model, and short of the near-one-hot softmax at
# which a bf16 program and a float32 reference part), the stream carries the
# embedding at the scale of a normalised branch input, and the branches that
# write into it are perturbations of it (SPARSE_SEEDED's reasoning). Larger
# output projections do not make the comparison sharper: with wo and swa_wo
# at 1.0 the served program's own bf16 error at 32,832 tokens rose from 0.03
# to 0.22 of the logits' standard deviation beside the breakages, at 1.5 it
# left the tolerance (my chip runs, PR 46: PERF.md section 6).
GQA_MIXED_SEEDED = {"embed": 1.0, "wq": 1.6, "wk": 1.6, "wo": 0.5,
                    "swa_wo": 0.5, "down": 0.25, "moe_down": 0.5}
# mean and standard deviation of mimo_v2's seeded sinks (init_one_param):
# 128 window keys under scores of standard deviation s sum to about
# 128 · exp(s^2 / 2); at s ~ 2.6 its log is ~8, and a sink a little under it
# takes a fifth to a half of a row's mass
SINK_SEEDED = (7.0, 1.0)
# standard deviation of mimo_v2's seeded router bias (init_one_param), on
# sigmoid scores in (0, 1): enough to change which experts a token's top-8
# holds, not so much that the bias alone chooses
ROUTER_BIAS_SEEDED = 0.1


# Seeded weights of a model that norms each sub-layer's OUTPUT and every
# query and key head (exaone_moe; ModelConfig.norm_on_output). No projection
# sets a scale: every branch joins the stream at its output norm's weight,
# and a score is the dot product of two normed heads over sqrt(head_dim).
# * q_norm at QK_NORM_SEEDED: at 1 the scores are of unit variance, a softmax
#   over 128 (or 7k) such keys is nearly flat, and a window, a rope or a norm
#   left out moves the logits by less than the tolerance (GQA_MIXED_SEEDED's
#   reasoning); at 2.6 a query's mass lies on a few keys, as in a trained
#   model.
# * the output norms (ln1 / ln2) at OUTPUT_NORM_SEEDED: at 1 every one of the
#   2L branches is a voice as loud as the embedding, and what a sharp softmax
#   or a flipped expert choice does to one branch's rounding is passed on
#   whole, layer after layer: the bf16 program stood 0.18 off the float32
#   reference on the chip's first probe and 0.2-0.5 on the CPU at a quarter
#   of the widths, seed by seed. At 0.2 the stream carries the embedding and
#   the branches are perturbations of it (SPARSE_SEEDED's reasoning): 0.02-0.08
#   over nine seeds there, with the breakages that are not small by nature at
#   0.34-1.5 (what it was measured against: PERF.md section 6, PR 50).
# * the routed experts' down-projection at a quarter of fan_in^-0.5
#   (SHARE_SEEDED's reasoning: only what a flipped choice moves is damped).
#   The router's top-8 of 128 is a step, a bf16 stream flips the members
#   nearest the threshold, and with a share held a flip is a held expert's
#   output there or not; under an output norm the routed part is not a small
#   addend but a part of a branch's DIRECTION. At fan_in^-0.5 the program read
#   0.15 off the reference on one of five chip probes (0.02-0.05 on the
#   others) and up to 0.18 on the CPU at a quarter of the widths (0.09-0.49
#   with every expert held: the flips are the mechanism); at a quarter 0.03-
#   0.06 over twelve seeds there. The shared expert, attention and the dense
#   MLP keep fan_in^-0.5.
# * the module's hnorm at MTP_HNORM_SEEDED: it norms a state that the model's
#   final norm has normed already, so at 1 it changes nothing and says
#   nothing of whether the module reads it.
OUTPUT_NORMED_SEEDED = {"embed": 1.0, "moe_down": 0.25}
QK_NORM_SEEDED = 2.6
OUTPUT_NORM_SEEDED = 0.2
MTP_HNORM_SEEDED = 0.5


# Seeded weights of kimi_linear (ModelConfig.has_kda): SHARE_SEEDED's
# embedding, and the routed experts' down-projection at a quarter of
# fan_in^-0.5 (OUTPUT_NORMED_SEEDED's reasoning: only what a flipped choice
# moves is damped). All 27 layers are held, 26 of them route, and a flip is
# a held expert's output there or not: at SHARE_SEEDED's half the served
# program stood 0.15 of the logits' standard deviation off the float32
# reference on the chip's first probe (PERF.md section 6, PR 54). Attention,
# the delta-attention block, the dense and the shared MLPs keep fan_in^-0.5.
KDA_SEEDED = {"embed": 1.0, "moe_down": 0.25}


def seeded_std(cfg: ModelConfig, name: str, fan_in: int) -> float:
    """Standard deviation of a --random-weights matrix: fan_in^-0.5, but
    see SPARSE_SEEDED, MIXED_SEEDED, GQA_MIXED_SEEDED, SHARE_SEEDED,
    KDA_SEEDED and OUTPUT_NORMED_SEEDED."""
    std = fan_in ** -0.5
    rule = (MIXED_SEEDED if cfg.has_swa_latent
            else OUTPUT_NORMED_SEEDED if cfg.norm_on_output
            else GQA_MIXED_SEEDED if cfg.has_swa_gqa
            else SPARSE_SEEDED if cfg.index_topk > 0
            else KDA_SEEDED if cfg.has_kda
            else SHARE_SEEDED if cfg.num_experts_total > 0 else {})
    if name == "embed":
        return rule.get("embed", std)
    if cfg.has_swa_gqa and name.endswith(("wq", "wk")):
        return std * rule.get(name[-2:], 1.0)
    for suffix in ("moe_down", "down", "swa_wo", "wo"):
        if name.endswith(suffix):
            return std * rule.get(suffix, 1.0)
    return std


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    params: Params = {}
    for name, shape in param_shapes(cfg).items():
        key, sub = jax.random.split(key)
        params[name] = init_one_param(cfg, name, shape, sub, dtype)
    return params


# int8 KV rows carry their per-token scale IN-ROW as two extra int8 lanes
# (lane C = exponent e, lane C+1 = mantissa m, scale = 2^e · (1+m/256)),
# padded to one 128-lane group — KV_SCALE_LANES, imported from
# attention.py (the kernel side owns the constant; full rationale there).
# The pool stays the same {"k","v"} pytree. Cost: 128 extra lanes per
# row → 2048/1280 = 1.6× compression instead of 2× (the scale-bearing
# lane group is mostly pad).


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, quantization: str = "none",
                  kv_shards: int = 1, win_blocks: int = 0) -> KVCache:
    """quantization="int8": per-token int8 KV with in-row scales (see
    KV_SCALE_LANES). At seq >= ~1k the KV read stream rivals the weights
    stream during decode (VERDICT r3 next #6); int8 KV cuts that term
    1.6×. The reference's analog is FP8 KV in its quantized serving
    configs (R1-Distill FP8, docs/architecture.md:57).

    ``kv_shards`` (int8 + tensor parallelism): rows carry one
    (values, scales) section per tp shard — g·(C/g + KV_SCALE_LANES)
    lanes — so the lane-axis tp sharding (parallel/sharding.kv_pspecs)
    gives each shard whole sections; see attention.quantize_kv_rows.

    ``win_blocks``: mimo_v2 alone (models/mimo.py: the window pool's)."""
    if cfg.has_swa_gqa:
        from . import mimo
        return mimo.init_kv_cache(cfg, num_blocks, block_size, dtype=dtype,
                                  quantization=quantization,
                                  win_blocks=win_blocks, kv_shards=kv_shards)
    C = cfg.num_kv_heads * cfg.head_dim
    if quantization == "int8":
        if C % kv_shards != 0:
            raise ValueError(
                f"int8 KV pool: value lanes C={C} do not divide into "
                f"kv_shards={kv_shards} scale groups")
        shape = (cfg.num_layers, num_blocks * block_size,
                 C + kv_shards * KV_SCALE_LANES)
        return {"k": jnp.zeros(shape, dtype=jnp.int8),
                "v": jnp.zeros(shape, dtype=jnp.int8)}
    if quantization != "none":
        raise ValueError(f"unknown kv quantization {quantization!r} "
                         f"(none|int8)")
    shape = (cfg.num_layers, num_blocks * block_size, C)
    return {"k": jnp.zeros(shape, dtype=dtype),
            "v": jnp.zeros(shape, dtype=dtype)}




def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know of a model whose layers keep
    rows of more than one kind: mimo_v2's two groups of pool blocks
    (models/mimo.py); None for every other model of this family (one
    uniform paged pool)."""
    if not cfg.has_swa_gqa:
        return None
    from . import mimo
    return mimo.cache_layout(cfg, block_size, dtype_bytes)


def _layer_stack(params: Params):
    return {k.split(".", 1)[1]: v for k, v in params.items()
            if k.startswith("layers.")}


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelStatics:
    """Static (hashable) arguments threaded into the jitted functions."""

    cfg: ModelConfig
    block_size: int
    attn_impl: str = "auto"
    # run-coalesced decode DMA (attention.py wave_contig_table):
    # EngineConfig.kv_contig_alloc=False forces the per-block path
    kv_coalesce: bool = True
    # the engine's mesh when it shards heads over "tp" (else None): the
    # compiler refuses to partition a Pallas kernel ("Mosaic kernels
    # cannot be automatically partitioned"), so the attention kernels
    # run per tp shard under shard_map (_per_tp_shard)
    mesh: Optional[Any] = None
    # the parameters are placed over a mesh (any mesh: tp, ep, sp, pp),
    # so the expert stacks may be sharded: what experts_run_grouped asks
    sharded: bool = False
    # entries of a sequence's block table (the engine's max_blocks_per_seq):
    # a model whose window layers keep their rows under ids of their own
    # (models/mla.py dots3_note) finds their table behind these entries; 0
    # = every table is a plain one
    table_blocks: int = 0

    def __hash__(self):
        return hash((id(self.cfg), self.block_size, self.attn_impl,
                     self.kv_coalesce, id(self.mesh), self.sharded,
                     self.table_blocks))

    @property
    def tp(self) -> int:
        return self.mesh.shape["tp"] if self.mesh is not None else 1


def _run_layers(params: Params, kv: KVCache, x: jax.Array,
                positions: jax.Array, slots: jax.Array, cfg: ModelConfig,
                attn_fn, final_norm: bool = True,
                reduce_axis: Optional[str] = None,
                experts_sharded: bool = True,
                valid_rows: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, KVCache]:
    """Shared transformer stack: per layer — qkv projection, rope, KV
    scatter into the paged pool, ``attn_fn`` (the only thing the three
    forward paths differ in), wo residual, swiglu MLP; scanned over the
    stacked layer params.

    ``experts_sharded`` / ``valid_rows``: what ``moe_mlp`` needs to pick
    the experts' form (``ModelStatics.sharded``; a prefill's
    ``true_len``). The defaults, for a caller that knows neither (the pp
    stage ring), keep the dense form over every row.

    attn_fn(q, k_chunk, v_chunk, k_flat, v_flat, li, sliding) -> [N, H, Dh]
    where N is the leading axis of x (tokens for prefill, batch for
    decode), k_flat/v_flat are the FULL pool flattened to [L*NTOK, Cx]
    (already containing this step's scattered KV; int8 pools' Cx carries
    the in-row scale lanes and readers dequantize via dequant_kv_rows /
    the kernel's in-score path), ``li`` is the traced layer index (reads
    address rows li*NTOK + slot — callers offset their block tables /
    gather indices by li), and ``sliding`` is this layer's
    local-attention flag (bool scalar, traced through the scan — gemma2
    interleaved window layers).

    ``reduce_axis``: mesh axis name to psum the row-parallel matmul
    outputs (wo, MLP down) over — the manual-collective hook the pp×tp
    stage loop uses under shard_map, where GSPMD cannot insert the
    Megatron reductions for it (parallel/pipeline_parallel.py). The
    psum lands BEFORE any post-norm/residual so the un-reduced partial
    sums never leak into the stream. None (every jit/GSPMD caller)
    changes nothing.

    The KV pool rides the scan as a CARRY with in-place [li, slots]
    scatters — NOT as per-layer xs/ys slices. The ys form forced XLA to
    materialize every layer's whole [NTOK, C] slice into the stacked
    output each step (~pool-sized read+write per step), which made decode
    scale with pool size instead of batch (measured: B=64 step 15.9ms →
    the stack alone was 14.4ms; see tools/decode_profile.py).
    """
    N = x.shape[0]
    L = cfg.num_layers
    inv_freq = jnp.asarray(rope_inv_freq(cfg))
    rope_att = rope_attention_scaling(cfg)
    # experts that run grouped read their stacks whole, by layer index
    layer_params, whole = split_expert_stacks(
        _layer_stack(params), N, cfg.num_experts_per_tok, experts_sharded)
    sliding_flags = jnp.asarray(sliding_layer_mask(cfg))
    NTOK = kv["k"].shape[1]

    p1 = cfg.norm_plus_one

    quantized = kv["k"].dtype == jnp.int8
    kv_groups = (kv_row_groups(kv["k"].shape[2],
                               cfg.num_kv_heads * cfg.head_dim)
                 if quantized else 1)

    def layer(carry, xs):
        h, kp, vp = carry
        lp, sliding, li = xs["lp"], xs["sliding"], xs["i"]
        hn = rms_norm(h, lp["ln1"], cfg.rms_norm_eps, p1)
        if "wqkv" in lp:          # fused qkv (fuse_stacked_matmuls)
            qd = cfg.num_heads * cfg.head_dim
            kvd = cfg.num_kv_heads * cfg.head_dim
            qkv = mm(hn, lp["wqkv"])
            q, k, v = (qkv[:, :qd], qkv[:, qd:qd + kvd],
                       qkv[:, qd + kvd:])
        else:
            q, k, v = mm(hn, lp["wq"]), mm(hn, lp["wk"]), mm(hn, lp["wv"])
        if cfg.attention_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(N, cfg.num_heads, cfg.head_dim)
        k = k.reshape(N, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(N, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, p1)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, p1)
        q = apply_rope(q, positions, inv_freq, rope_att)
        k = apply_rope(k, positions, inv_freq, rope_att)
        if quantized:
            # per-token int8 write with in-row (e, m) scale lanes;
            # attention reads (incl. this step's own tokens) dequantize
            # from the same rows, so the current token sees the same
            # quantized values later steps do. The group count comes from
            # the pool's row width (one section per tp shard) — under
            # pjit each shard quantizes its own KV heads locally.
            kp = kp.at[li, slots, :].set(
                quantize_kv_rows(k.reshape(N, -1), kv_groups), mode="drop")
            vp = vp.at[li, slots, :].set(
                quantize_kv_rows(v.reshape(N, -1), kv_groups), mode="drop")
        else:
            kp = kp.at[li, slots, :].set(k.reshape(N, -1).astype(kp.dtype),
                                         mode="drop")
            vp = vp.at[li, slots, :].set(v.reshape(N, -1).astype(vp.dtype),
                                         mode="drop")
        # flat [L*NTOK, Cx] views (metadata-only reshape of the carry
        # buffers); readers address layer li at row offset li*NTOK
        with jax.named_scope("attention"):
            attn = attn_fn(q, k, v, kp.reshape(L * NTOK, kp.shape[2]),
                           vp.reshape(L * NTOK, vp.shape[2]), li, sliding)
        attn_out = mm(attn.reshape(N, -1), lp["wo"])
        if reduce_axis is not None:   # row-parallel wo under shard_map tp
            attn_out = jax.lax.psum(attn_out, reduce_axis)
        if cfg.post_norms:   # gemma2: norm the block output, then residual
            attn_out = rms_norm(attn_out, lp["ln1_post"],
                                cfg.rms_norm_eps, p1)
        h = h + attn_out
        hn2 = rms_norm(h, lp["ln2"], cfg.rms_norm_eps, p1)
        if cfg.num_experts > 0:
            shared = (tuple(lp.get(k) for k in ("sh_gate", "sh_up",
                                                "sh_down", "sh_router"))
                      if cfg.shared_expert_size > 0 else None)
            ex = whole or lp
            mlp_out = moe_mlp(hn2, lp["router"], ex.get("moe_gate"),
                              ex.get("moe_up"), ex["moe_down"],
                              cfg.num_experts_per_tok,
                              norm_topk=cfg.moe_norm_topk,
                              shared=shared,
                              gateup_w=ex.get("moe_gateup"),
                              shared_gateup=lp.get("sh_gateup"),
                              sharded=experts_sharded,
                              valid_rows=valid_rows,
                              layer=li if whole else None)
        else:
            mlp_out = swiglu(hn2, lp.get("gate"), lp.get("up"),
                             lp["down"], cfg.hidden_act,
                             gateup_w=lp.get("gateup"))
        if reduce_axis is not None:   # row-parallel down under shard_map tp
            mlp_out = jax.lax.psum(mlp_out, reduce_axis)
        if cfg.post_norms:
            mlp_out = rms_norm(mlp_out, lp["ln2_post"], cfg.rms_norm_eps, p1)
        h = h + mlp_out
        return (h, kp, vp), None

    (x, k_new, v_new), _ = jax.lax.scan(
        layer, (x, kv["k"], kv["v"]),
        {"lp": layer_params, "sliding": sliding_flags,
         "i": jnp.arange(L, dtype=jnp.int32)})
    if final_norm:   # pp stages norm ONCE after the last stage, not per slice
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, p1)
    return x, {"k": k_new, "v": v_new}


def _lm_head_kernel_ok(head: QuantizedArray,
                       cfg: ModelConfig = None) -> bool:
    """Use the fused Pallas head on real TPUs when the vocab tiles evenly
    AND the head is unsharded — under tensor parallelism the vocab axis is
    mesh-sharded and pallas_call has no GSPMD partitioning rule (the
    engine clears cfg.lm_head_pallas when it shards params over tp>1)."""
    if cfg is not None and not cfg.lm_head_pallas:
        return False
    if head.group or head.q.dtype != jnp.int8:
        # the fused kernel's dequant is per-column int8; grouped-int4
        # heads take the XLA paths (mm handles the grouped contraction)
        return False
    from ..lm_head import TILE_V
    if head.q.shape[1] % TILE_V != 0:
        return False
    return _on_tpu()


@jax.named_scope("lm_head")
def _logits(params: Params, x: jax.Array,
            cfg: ModelConfig = None) -> jax.Array:
    head = params.get("lm_head")
    emb = params["embed"]
    # "tied" must come from the config, not from both leaves being
    # quantized — an untied quantized model has a real lm_head AND a
    # quantized embed, and projecting through the embedding would be
    # garbage
    tied_q = (cfg is not None and cfg.tie_word_embeddings
              and isinstance(head, QuantizedArray)
              and isinstance(emb, QuantizedArray))
    # Fused Pallas dequant-matmul (engine/lm_head.py): pins the int8 head
    # at its weights-read floor regardless of batch — XLA's int8 matmul
    # heuristics are batch-dependent (the pre-transposed head collapses
    # 4.5ms → 82ms between B=16 and B=64 on v5e). Where the kernel does
    # not apply (_lm_head_kernel_ok) the XLA paths below serve.
    if (isinstance(head, QuantizedArray) and head.q.ndim == 2
            and _lm_head_kernel_ok(head, cfg)):
        from ..lm_head import lm_head_int8
        out = lm_head_int8(x, head.q, head.scale)
    else:
        # XLA's int8 matmul heuristics flip with batch size (measured on
        # v5e, llama-1B head [2048, 128256]): the pre-transposed int8 head
        # wins below ~32 rows (4.5ms vs 12.3ms step at B=16) but collapses
        # at B=64 (82ms), where computing against the transposed int8
        # embedding is fine (9.7ms) — pick per traced batch size, it's
        # static under jit
        big_batch = x.ndim > 1 and x.shape[0] >= 32
        if head is not None and not (tied_q and big_batch):
            out = mm(x, head)
        elif isinstance(emb, QuantizedArray):
            # tied head: per-row embed scales become per-column here
            out = (x @ emb.q.T.astype(x.dtype)) * emb.scale.astype(
                x.dtype).reshape(-1)
        else:
            out = x @ emb.T.astype(x.dtype)
    out = out.astype(jnp.float32)
    if cfg is not None and cfg.final_logit_softcap:
        out = _softcap(out, cfg.final_logit_softcap)
    return out


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    emb = params["embed"]
    if isinstance(emb, QuantizedArray):
        dt = params["final_norm"].dtype
        x = emb.q[tokens].astype(dt) * emb.scale[tokens].astype(dt)
    else:
        x = emb[tokens]
    if cfg.embed_scale:   # gemma normalizer, applied in the embed dtype
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype=x.dtype)
    return x


def _attn_scale(cfg: ModelConfig) -> float:
    return (cfg.query_pre_attn_scalar or cfg.head_dim) ** -0.5


def _per_tp_shard(statics: ModelStatics, fn, in_specs, out_specs):
    """fn as the engine must call it when a Pallas kernel may be inside:
    unchanged on one device; under a tp mesh, per shard via shard_map —
    attention heads are independent, q/out shard on the head axis and
    the KV pool on its lane axis (whole KV heads per shard,
    parallel/sharding.kv_pspecs), so no collective is needed."""
    if statics.tp == 1:
        return fn
    return jax.shard_map(fn, mesh=statics.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _paged_attention(statics: ModelStatics, q, k_flat, v_flat, tables,
                     seq_lens, win_lo, scale: float):
    """paged_attention at the model's geometry (decode rows and the
    ragged row path share it). Under tp each shard resolves its own
    impl from its LOCAL geometry — a shard of an int8 pool holds exactly
    one (values, scales) section, so it reads as a single-group pool."""
    cfg = statics.cfg
    impl = statics.attn_impl
    tp = statics.tp if kernel_wanted(impl) else 1
    if cfg.num_kv_heads % tp != 0:
        # a KV head straddles two shards: no per-shard kernel exists
        if impl != "auto":
            raise ValueError(
                f"attn_impl {impl!r} forced but tp={tp} does not divide "
                f"the {cfg.num_kv_heads} KV heads")
        impl, tp = "xla", 1

    def attend(q, k_flat, v_flat, tables, seq_lens, win_lo):
        return paged_attention(q, k_flat, v_flat, tables, seq_lens,
                               block_size=statics.block_size, scale=scale,
                               impl=impl,
                               softcap=cfg.attn_logit_softcap,
                               win_lo=win_lo,
                               kv_heads=cfg.num_kv_heads // tp,
                               coalesce=statics.kv_coalesce)

    if tp > 1:
        heads, lanes, rep = P(None, "tp", None), P(None, "tp"), P()
        attend = _per_tp_shard(
            statics, attend,
            (heads, lanes, lanes, rep, rep, None if win_lo is None else rep),
            heads)
    return attend(q, k_flat, v_flat, tables, seq_lens, win_lo)


def _prefill_flash_impl(statics: ModelStatics):
    """Prefill attention dispatch: the Pallas flash kernel on TPU (or
    interpret mode when forced), the dense-score einsum elsewhere. Mirrors
    paged_attention's impl resolution for decode — including raising on a
    forced impl the geometry can't run, so a parity test can never silently
    compare the einsum path against itself."""
    cfg = statics.cfg
    supported = (flash_prefill_supported(cfg.num_heads, cfg.num_kv_heads,
                                         cfg.head_dim)
                 and cfg.num_kv_heads % statics.tp == 0)
    impl = statics.attn_impl
    if impl == "auto":
        return _on_tpu() and supported
    if impl in ("pallas", "pallas_interpret"):
        if not supported:
            raise ValueError(
                f"prefill impl {impl!r} forced but unsupported geometry "
                f"(H={cfg.num_heads}, KVH={cfg.num_kv_heads}, "
                f"Dh={cfg.head_dim}) — see flash_prefill_supported")
        return "interpret" if impl == "pallas_interpret" else True
    return False


def sliding_layer_mask(cfg: ModelConfig) -> np.ndarray:
    """Per-layer local-attention flags. gemma2 interleaves sliding and
    global layers: HF ``layer_types`` when present, else the
    even-layers-local default (HF Gemma2Config)."""
    if cfg.sliding_window is None:
        return np.zeros((cfg.num_layers,), dtype=bool)
    if cfg.layer_types:
        return np.array([t == "sliding_attention" for t in cfg.layer_types],
                        dtype=bool)
    return np.array([l % 2 == 0 for l in range(cfg.num_layers)], dtype=bool)


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics
                    ) -> Tuple[jax.Array, KVCache]:
    """Single-sequence (chunk) prefill.

    tokens: [T] padded to a bucket; block_table: [M] this sequence's blocks;
    start_pos: scalar — tokens[0]'s absolute position (>0 for chunked prefill
    or prefix-cache hits, in which case blocks [0, start_pos) must already
    hold the prefix KV); true_len: scalar — valid tokens in this chunk.

    Returns (logits_last [V], updated kv). Pad positions scatter into the
    reserved trash block 0 (allocators never hand out block 0) and are masked
    out of attention reads.
    """
    cfg = statics.cfg
    if cfg.has_swa_gqa:
        from . import mimo
        return mimo.prefill_forward(params, kv, tokens, block_table,
                                    start_pos, true_len, statics)
    T = tokens.shape[0]
    bsz = statics.block_size
    scale = _attn_scale(cfg)

    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T, dtype=jnp.int32) < true_len
    # flat pool slot for each chunk token; pads → slot 0 (trash block)
    slots = jnp.where(
        valid,
        block_table[positions // bsz] * bsz + positions % bsz,
        0)
    seq_len = start_pos + true_len

    use_flash = _prefill_flash_impl(statics)

    def attn(q, _k, _v, k_flat, v_flat, li, sliding):
        # attend over the whole block table (prefix KV + this chunk);
        # layer li's rows sit at offset li*NTOK in the flat pool
        NTOK = k_flat.shape[0] // cfg.num_layers
        idx = (flat_token_indices(block_table[None, :], bsz)[0]      # [S]
               + li * NTOK)
        S = idx.shape[0]
        ks = jnp.take(k_flat, idx, axis=0)                           # [S, Cx]
        vs = jnp.take(v_flat, idx, axis=0)
        if k_flat.dtype == jnp.int8:
            # int8 pool: dequantize the gathered rows (in-row scales);
            # the flash kernel and the einsum fallback then run unchanged
            C = cfg.num_kv_heads * cfg.head_dim
            ks = dequant_kv_rows(ks, C, q.dtype)
            vs = dequant_kv_rows(vs, C, q.dtype)
        ks = ks.reshape(S, cfg.num_kv_heads, cfg.head_dim)
        vs = vs.reshape(S, cfg.num_kv_heads, cfg.head_dim)
        if use_flash:
            # Pallas online-softmax kernel: O(TQ·SC) live memory instead
            # of a [KVH, g, T, S] score materialization
            def flash(q, ks, vs, start_pos, seq_len, sliding):
                return flash_prefill(
                    q, ks, vs, scale=scale, start_pos=start_pos,
                    seq_len=seq_len, sliding=sliding,
                    window=cfg.sliding_window,
                    softcap=cfg.attn_logit_softcap or None,
                    interpret=(use_flash == "interpret"))
            heads = P(None, "tp", None)
            return _per_tp_shard(
                statics, flash, (heads, heads, heads, P(), P(), P()),
                heads)(q, ks, vs, start_pos, seq_len,
                       jnp.asarray(sliding))
        g = cfg.num_heads // cfg.num_kv_heads
        qg = q.reshape(T, cfg.num_kv_heads, g, cfg.head_dim)
        scores = jnp.einsum("tkgd,skd->kgts", qg, ks).astype(jnp.float32) * scale
        if cfg.attn_logit_softcap:
            scores = _softcap(scores, cfg.attn_logit_softcap)
        kv_pos = jnp.arange(idx.shape[0], dtype=jnp.int32)
        mask = (kv_pos[None, :] <= positions[:, None]) & (
            kv_pos[None, :] < seq_len)
        if cfg.sliding_window is not None:
            # local layers attend only the trailing window
            win_lo = jnp.where(sliding,
                               positions - cfg.sliding_window, -1)
            mask = mask & (kv_pos[None, :] > win_lo[:, None])
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vs.dtype)
        return jnp.einsum("kgts,skd->tkgd", probs, vs).reshape(
            T, cfg.num_heads, cfg.head_dim)

    x = _embed(params, tokens, cfg)  # activation dtype follows param dtype
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn,
                            experts_sharded=statics.sharded,
                            valid_rows=true_len)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, cfg), kv_new


def prefill_forward_sp(params: Params, kv: KVCache, tokens: jax.Array,
                       block_table: jax.Array, true_len: jax.Array,
                       statics: ModelStatics, mesh) -> Tuple[jax.Array, KVCache]:
    """Sequence-parallel whole-prompt prefill: the token axis is sharded
    over the mesh's "sp" axis and attention runs as a ring over ICI
    (parallel/ring_attention.py) — per-device activation/KV memory is
    O(T / sp), enabling prompts that don't fit one chip's HBM.

    Same contract as `prefill_forward` with start_pos fixed at 0 (the
    engine uses this path for long prompts with no prefix-cache hit; hits
    fall back to the chunked path). T must divide by the sp axis size.
    """
    from ...parallel.ring_attention import ring_attention

    cfg = statics.cfg
    T = tokens.shape[0]
    bsz = statics.block_size
    scale = _attn_scale(cfg)

    positions = jnp.arange(T, dtype=jnp.int32)
    valid = positions < true_len
    slots = jnp.where(valid, block_table[positions // bsz] * bsz +
                      positions % bsz, 0)

    def attn(q, k, v, _k_flat, _v_flat, _li, sliding):
        del sliding   # sp path serves global-attention models only
        return ring_attention(q, k, v, mesh, scale=scale, kv_len=true_len)

    x = _embed(params, tokens, cfg)
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, cfg), kv_new


def ragged_attn_impl(statics: ModelStatics, max_rows: int, kv_dtype,
                     kv_groups: int = 1):
    """Ragged attention dispatch: the sequence-grouped Pallas kernel on
    TPU when the geometry tiles (attention.ragged_supported), the
    per-row paged path elsewhere. Mirrors _prefill_flash_impl's impl
    resolution — including raising on a forced impl the geometry can't
    run, so a parity test can never silently compare the row path
    against itself. Grouped int8 pools (one scale section per tp shard)
    always take the row path, exactly as paged_attention refuses them
    for the decode kernel."""
    cfg = statics.cfg
    # tp meshes take the row path: the sequence-grouped kernel has no
    # per-shard form yet (its q window spans all of a sequence's heads)
    ok = (kv_groups == 1 and statics.tp == 1
          and ragged_supported(cfg.num_heads, cfg.num_kv_heads,
                               cfg.head_dim, statics.block_size,
                               max_rows, kv_dtype=kv_dtype))
    impl = statics.attn_impl
    if impl == "auto":
        return _on_tpu() and ok
    if impl in ("pallas", "pallas_interpret"):
        if not ok:
            raise ValueError(
                f"ragged attention impl {impl!r} forced but unsupported "
                f"geometry (H={cfg.num_heads}, KVH={cfg.num_kv_heads}, "
                f"Dh={cfg.head_dim}, block={statics.block_size}, "
                f"max_rows={max_rows}, groups={kv_groups}) — see "
                f"ragged_supported")
        return "interpret" if impl == "pallas_interpret" else True
    return False


def ragged_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   row_slot: jax.Array, seq_starts: jax.Array,
                   seq_counts: jax.Array, sample_rows: jax.Array,
                   statics: ModelStatics, max_rows: int = 8,
                   sample_all_rows: bool = False
                   ) -> Tuple[jax.Array, KVCache]:
    """Unified ragged mixed prefill+decode step (one dispatch serves
    prefill chunks AND decode rows; docs/ragged_attention.md).

    tokens/positions: [TT] flat token rows; block_tables: [S, M] where
    the LAST row is all-zeros (the trash sequence dead rows aim at);
    row_slot: [TT] row → sequence; seq_starts/seq_counts: [S] each
    sequence's contiguous row span, ascending starts (the (start, len)
    half of the engine/ragged.py metadata contract — `mode` is packing
    metadata; the math is identical for both modes, a decode step is
    simply len == 1); sample_rows: [S] the row whose hidden state each
    sequence's logits come from (its LAST row; inactive sequences point
    at row 0 and their sample is discarded). Returns
    (logits [S, V], new kv).

    Per ROW this is exactly decode_forward's math: the same rope/
    scatter at (table, position), the same paged attention masked at the
    row's own position — so a ragged dispatch is bit-exact per row with
    the decode/lane programs (row-count independence of every per-row
    op; the spec-verify program's flattening precedent). On TPU the
    sequence-grouped ragged kernel instead streams each sequence's KV
    waves ONCE for all its rows (attention.ragged_paged_attention_
    pallas) — same contract, kernel-grade DMA economics.

    ``sample_all_rows`` (static; the ragged×spec variant): return
    logits for EVERY token row ([TT, V]) instead of gathering
    sample_rows — speculative spans need a sample at each draft row
    for lockstep acceptance (the verify program's per-row sampling,
    now riding the ragged batch). sample_rows is ignored in this
    mode."""
    cfg = statics.cfg
    TT = tokens.shape[0]
    bsz = statics.block_size
    scale = _attn_scale(cfg)
    quantized = kv["k"].dtype == jnp.int8
    kv_groups = (kv_row_groups(kv["k"].shape[2],
                               cfg.num_kv_heads * cfg.head_dim)
                 if quantized else 1)
    use_kernel = ragged_attn_impl(statics, max_rows, kv["k"].dtype,
                                  kv_groups)

    row_tables = jnp.take(block_tables, row_slot, axis=0)      # [TT, M]
    slots = (row_tables[jnp.arange(TT), positions // bsz] * bsz
             + positions % bsz)
    seq_lens = positions + 1
    if use_kernel:
        last_rows = seq_starts + jnp.maximum(seq_counts - 1, 0)
        seq_ctx = jnp.where(seq_counts > 0,
                            jnp.take(positions, last_rows) + 1, 0)
        pos0 = seq_ctx - seq_counts

    def attn(q, _k, _v, k_flat, v_flat, li, sliding):
        num_blocks = k_flat.shape[0] // (cfg.num_layers * bsz)
        if use_kernel:
            win_base = None
            if cfg.sliding_window is not None:
                win_base = jnp.where(
                    sliding & (seq_counts > 0),
                    pos0 - cfg.sliding_window,
                    jnp.full_like(pos0, RAGGED_WIN_SENTINEL))
            return ragged_paged_attention_pallas(
                q, k_flat, v_flat, block_tables + li * num_blocks,
                seq_starts, seq_counts, seq_ctx, block_size=bsz,
                scale=scale, max_rows=max_rows,
                softcap=cfg.attn_logit_softcap or None,
                win_base=win_base, coalesce=statics.kv_coalesce,
                interpret=(use_kernel == "interpret"))
        win_lo = None
        if cfg.sliding_window is not None:
            win_lo = jnp.where(sliding, positions - cfg.sliding_window,
                               jnp.full_like(positions, -1))
        # the decode program's attention verbatim, over row-expanded
        # tables — the bit-exactness anchor of the ragged contract
        return _paged_attention(statics, q, k_flat, v_flat,
                                row_tables + li * num_blocks, seq_lens,
                                win_lo, scale)

    x = _embed(params, tokens, cfg)  # [TT, D]
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn,
                            experts_sharded=statics.sharded)
    if sample_all_rows:
        return _logits(params, x, cfg), kv_new             # [TT, V]
    sel = jnp.take(x, sample_rows, axis=0)                     # [S, D]
    return _logits(params, sel, cfg), kv_new


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics) -> Tuple[jax.Array, KVCache]:
    """Batched single-token decode step.

    tokens: [B] current input token per slot; positions: [B] their absolute
    positions (inactive slots: position 0 w/ trash block table);
    block_tables: [B, M]. Returns (logits [B, V], updated kv).
    """
    cfg = statics.cfg
    if cfg.has_swa_gqa:
        from . import mimo
        return mimo.decode_forward(params, kv, tokens, positions,
                                   block_tables, statics)
    B = tokens.shape[0]
    bsz = statics.block_size
    scale = _attn_scale(cfg)
    slots = block_tables[jnp.arange(B), positions // bsz] * bsz + positions % bsz
    seq_lens = positions + 1

    def attn(q, _k, _v, k_flat, v_flat, li, sliding):
        win_lo = None
        if cfg.sliding_window is not None:
            win_lo = jnp.where(sliding,
                               positions - cfg.sliding_window,
                               jnp.full_like(positions, -1))
        # layer li's blocks sit at block offset li*num_blocks in the flat
        # pool — the whole paged-attention path (incl. the Pallas kernel's
        # DMA addressing, and int8 pools via in-row scales) works
        # unchanged on offset tables
        num_blocks = k_flat.shape[0] // (cfg.num_layers * bsz)
        return _paged_attention(statics, q, k_flat, v_flat,
                                block_tables + li * num_blocks, seq_lens,
                                win_lo, scale)

    x = _embed(params, tokens, cfg)  # [B, D]
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn,
                            experts_sharded=statics.sharded)
    return _logits(params, x, cfg), kv_new


# The door (models.module_for; docs/architecture.md "What a model family
# brings"). New functions go HERE, at the end: a Pallas kernel's serialized
# body names the frames it was traced under, file and line, so a line added
# above a call site on that stack (here; in core.py above its jits and their
# call sites) re-keys every cached executable that holds the kernel.

def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """What this engine asks for that the model does not run under yet, by
    name; empty = go. Only mimo_v2's window group refuses."""
    if cfg.has_swa_gqa:
        from . import mimo
        return mimo.refusals(cfg, engine_cfg, mesh)
    return []


def prefill_forward_mtp(*args, **kw):
    """``prefill_forward`` with the resident multi-token-prediction
    module's tail (models/mimo.py; a model with ``mtp_layers`` only)."""
    from . import mimo
    return mimo.prefill_forward_mtp(*args, **kw)


def decode_forward_mtp(*args, **kw):
    """``decode_forward`` with the module's tail over the same rows."""
    from . import mimo
    return mimo.decode_forward_mtp(*args, **kw)


def engine_cache(cfg: ModelConfig, engine_cfg, dtype, kv_shards: int = 1):
    """-> (kv, layout, win_blocks): the arrays an engine of ``engine_cfg``
    holds, the layout its block manager pages them by (None: one uniform
    paged pool) and the blocks of the window layers' pool, sized from the
    layout and the paged pool (no flag; docs/hybrid_cache.md). One body for
    every family whose cache is pools of blocks: ``mla`` imports it."""
    from . import module_for
    family, e = module_for(cfg), engine_cfg
    layout = family.cache_layout(cfg, e.kv_block_size,
                                 jnp.dtype(dtype).itemsize)
    win_blocks = 0 if layout is None else layout.window_pool_blocks(
        e.num_kv_blocks, e.max_num_seqs,
        e.prefill_chunk or max(e.prefill_buckets))
    kv = family.init_kv_cache(
        cfg, e.num_kv_blocks, e.kv_block_size, dtype=dtype,
        quantization=e.kv_quantization, win_blocks=win_blocks,
        kv_shards=kv_shards)
    return kv, layout, win_blocks


def prefill_counters(cfg: ModelConfig, bucket: int, rows: int,
                     prompt_len: int) -> dict:
    return {}   # no key of a ``prefill`` flight record is this family's own


def decode_cache_passes(statics: ModelStatics, rows: int) -> int:
    """The passes over a slot's cache a read of a step with ``rows`` rows a
    slot makes (mimo.decode_cache_passes: the only family with such a
    step)."""
    from . import mimo
    return mimo.decode_cache_passes(statics, rows)
