"""Granite 4.0-H (``model_type: granitemoehybrid``, granite-4.0-h-small; IBM,
2025-10) in pure JAX: Mamba-2 layers that keep a float32 state a slot, beside
grouped-query attention layers with NO positional term over ``llama.py``'s
paged K/V pool, every layer followed by softmax-over-the-picked-logits
experts with an ungated shared expert (docs/hybrid_cache.md part six).

    h0 = embedding_multiplier * embed[token]
    per layer:  h += residual_multiplier * Mix(RMSNorm(h; ln1))
                h += residual_multiplier * (Experts(u) + Shared(u)),
                                            u = RMSNorm(h; ln2)
    logits = RMSNorm(h; final_norm) embed^T / logits_scaling

**Kind M** (``layer_types`` "mamba"; ``engine/ssd.py``), H heads of P lanes,
N states, one B / C group, d_inner = H P, x the normed input:

    [z | xBC | dt] = x W_in                 (``ssd_in`` [D, 2 d_inner + 2N + H])
    xBC = SiLU(conv_causal_depthwise(xBC) + b)      -> x' [H, P] | B [N] | C [N]
    dt = softplus(dt + dt_bias) [H] ;  A = -exp(A_log) [H]
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x'_t[h] (x) B_t
    y_t[h] = S_t[h] C_t + D[h] x'_t[h]
    out = RMSNorm_{d_inner}(y * SiLU(z); ssd_norm) W_out      (gate, THEN norm)

``S`` is float32 a slot and layer (``kv["ssd"]``, in ``ssd.state_shape``'s
held layout), the last ``taps - 1`` inputs of the convolution beside it
(``kv["conv"]``, the activation dtype). A step at position 0 starts from
zero, in prefill and in decode; a row whose block table is the trash row is
not live and leaves both as they are; the state a prefill dispatch writes is
the one after ``true_len`` rows, and the prompt's next dispatch continues
from it (``ssd_chunk``, ``ssd_step``).

**Kind A** ("attention"): q, k, v = x W_q, x W_k, x W_v with no bias and no
rotation, causal softmax at the scale ``attention_multiplier`` (held as
``query_pre_attn_scalar``: 1/128 at the published sizes, NOT 128^-1/2) over
the paged rows ``kv["k"]`` / ``kv["v"]`` (the A layers only), read by the
flash prefill kernel and ``attention.paged_attention`` as ``llama.py`` reads
its own.

**Experts**: ``llama.moe_mlp`` with ``norm_topk`` (softmax over the picked
logits) and no shared expert of its own; the shared expert is a plain SwiGLU
added as it is (qwen2_moe's carries a sigmoid gate, this one none).

The layers are walked by ``layer_plan``: the smallest period of
``layer_types`` is the body of ONE scan over the whole periods, and inside it
every run of one kind is a scan of its own, so that the program holds two
Mamba-2 layers and one attention layer whatever the depth (the benchmark's
cut depth is one period, M M M M M A M M M M, which ``mla.layer_plan``'s rule
reads as five periods of one M and five layers unrolled; and
``mla.walk_layer_kinds`` is bound to DeepSeek's router and to a residual
without a multiplier).

``prefill_forward`` takes the slot as an argument; the engine's prefill
program reads it from behind the block table's M entries, as for
``models/sambay.py`` and ``models/kimi_linear.py``.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..attention import _on_tpu, flash_prefill, flat_token_indices
from ..config import ModelConfig
from ..quant import mm
from ..ssd import CHUNK, ssd_chunk, ssd_step, state_shape
from . import llama
from .llama import (KVCache, ModelStatics, Params, _layer_stack, moe_mlp,
                    rms_norm, split_expert_stacks, swiglu)
from .mla import stack_at
from .sambay import state_refusals   # the stateful families' one table

_F32 = jnp.float32
# seeded A_log / dt_bias / D (init_one_param): the published initialisation's
# own ranges (mamba_ssm: A in [1, 16], dt in [1e-3, 1e-1]), the heads' decay
# rates a geometric ladder over A_RANGE, their step sizes log-uniform over
# DT_RANGE, so that half-lives ln 2 / (A dt) run from under a token to ~700;
# D uniform over D_RANGE (at its published 1 every head's skip is alike)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
D_RANGE = (0.5, 1.5)
# seeded matrices: factors on fan_in^-0.5. The multipliers change every
# branch's loudness: the embedding is drawn at 1 / embedding_multiplier (the
# stream starts at the scale of a normalised branch input, and the tied head's
# logits stand at hidden^0.5 / (embedding_multiplier * logits_scaling)), the
# four projections that write into the stream at ``factor /
# residual_multiplier`` (a branch joins at ``factor`` of a unit branch:
# llama.SPARSE_SEEDED's reasoning, with the routed experts damped most
# because a flipped choice is passed on whole), and wq / wk at the factor that
# makes the scores' standard deviation SCORE_SEEDED: the published scale
# 1/128 under unit queries and keys gives 0.09 (a flat softmax, which neither
# the scale nor a rotation moves); at 2.6 (wq, wk at 5.4 times fan_in^-0.5 at
# the published sizes) a query's mass lies on a few keys
# (llama.GQA_MIXED_SEEDED's reasoning)
BRANCH_SEEDED = {"wo": 2.0, "ssd_out": 1.0, "sh_down": 0.5, "moe_down": 1.0}
SCORE_SEEDED = 2.6
CONV_BIAS_SEEDED = 0.5


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """"M" (Mamba-2) or "A" (attention) for every layer."""
    return tuple("M" if t == "mamba" else "A" for t in cfg.layer_types)


def _n_kind(kinds, kind: str) -> int:
    return sum(1 for k in kinds if k == kind)


def _runs(kinds) -> list:
    """[(kind, length)] of the consecutive runs of one kind."""
    out: list = []
    for k in kinds:
        if out and out[-1][0] == k:
            out[-1] = (k, out[-1][1] + 1)
        else:
            out.append((k, 1))
    return out


def layer_plan(cfg: ModelConfig):
    """-> (the period of the kinds, whole periods, the kinds left over): the
    smallest p such that the list repeats with period p to its end (a cut
    depth may end inside a period: those layers are left over). The published
    40 layers: (M M M M M A M M M M, 4, ()); the first ten: the same period
    once."""
    kinds = layer_kinds(cfg)
    n = len(kinds)
    p = next(q for q in range(1, n + 1)
             if all(kinds[i] == kinds[i % q] for i in range(n)))
    return kinds[:p], n // p, kinds[n // p * p:]


def ssd_sizes(cfg: ModelConfig) -> Tuple[int, int, int, int, int]:
    """-> (heads, lanes a head, states, d_inner, the convolution's lanes)."""
    H, P, N = cfg.ssd_num_heads, cfg.ssd_head_dim, cfg.ssd_d_state
    return H, P, N, H * P, H * P + 2 * N


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """ln1 / ln2 and the expert stacks hold every layer, the attention
    stacks the A layers and ``ssd_*`` the M layers, each at the layer's index
    among its kind. The head is the embedding's (tied) unless the config says
    otherwise."""
    kinds = layer_kinds(cfg)
    L, D = cfg.num_layers, cfg.hidden_size
    n_m, n_a = _n_kind(kinds, "M"), _n_kind(kinds, "A")
    Hq, KVH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    H, P, N, di, cd = ssd_sizes(cfg)
    E, F, Fs = cfg.num_experts, cfg.intermediate_size, cfg.shared_expert_size
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        "layers.wq": (n_a, D, Hq * Dh),
        "layers.wk": (n_a, D, KVH * Dh),
        "layers.wv": (n_a, D, KVH * Dh),
        "layers.wo": (n_a, Hq * Dh, D),
        "layers.ssd_in": (n_m, D, 2 * di + 2 * N + H),
        "layers.ssd_conv": (n_m, cfg.ssd_conv_kernel, cd),
        "layers.ssd_conv_b": (n_m, cd),
        "layers.ssd_dt_bias": (n_m, H),
        "layers.ssd_A_log": (n_m, H),
        "layers.ssd_D": (n_m, H),
        "layers.ssd_norm": (n_m, di),
        "layers.ssd_out": (n_m, di, D),
        "layers.router": (L, D, E),
        "layers.moe_gate": (L, E, D, F),
        "layers.moe_up": (L, E, D, F),
        "layers.moe_down": (L, E, F, D),
        "layers.sh_gate": (L, D, Fs),
        "layers.sh_up": (L, D, Fs),
        "layers.sh_down": (L, Fs, D),
    }
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def init_one_param(cfg: ModelConfig, name: str, shape: tuple,
                   sub: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """The engine's seeded rule (``llama.init_one_param``: normal at
    fan_in^-0.5, norms 1) with this family's own scales (BRANCH_SEEDED,
    SCORE_SEEDED above) and, where a normal draw would make every head alike,
    ladders: ``ssd_A_log`` the log of A_RANGE's geometric ladder over the
    heads, ``ssd_dt_bias`` the inverse softplus of a log-uniform draw over
    DT_RANGE, ``ssd_D`` uniform over D_RANGE (all float32); the convolution's
    taps normal at taps^-1/2 (float32) and its bias normal at
    CONV_BIAS_SEEDED."""
    leaf = name.rsplit(".", 1)[-1]
    normal = lambda std: (jax.random.normal(sub, shape, _F32)    # noqa: E731
                          * std).astype(dtype)
    if leaf == "ssd_A_log":
        H = shape[-1]
        lo, hi = (math.log(a) for a in A_RANGE)
        ladder = lo + (hi - lo) * jnp.arange(H, dtype=_F32) / max(H - 1, 1)
        return jnp.broadcast_to(ladder, shape)
    if leaf == "ssd_dt_bias":
        lo, hi = (math.log(a) for a in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(sub, shape, _F32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "ssd_D":
        lo, hi = D_RANGE
        return jax.random.uniform(sub, shape, _F32) * (hi - lo) + lo
    if leaf == "ssd_conv":
        return jax.random.normal(sub, shape, _F32) * shape[-2] ** -0.5
    if leaf == "ssd_conv_b":
        return normal(CONV_BIAS_SEEDED)
    if leaf == "ssd_norm":
        return jnp.ones(shape, dtype)
    if leaf == "embed":
        return normal(1.0 / (cfg.embedding_multiplier or 1.0))
    if leaf in BRANCH_SEEDED:
        return normal(shape[-2] ** -0.5 * BRANCH_SEEDED[leaf]
                      / cfg.residual_multiplier)
    if leaf in ("wq", "wk"):
        # unit q and k lanes give scores of scale * head_dim^0.5
        return normal(shape[-2] ** -0.5 * (
            SCORE_SEEDED / (llama._attn_scale(cfg) * cfg.head_dim ** 0.5))
            ** 0.5)
    return llama.init_one_param(cfg, name, shape, sub, dtype)


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    params: Params = {}
    for name, shape in param_shapes(cfg).items():
        key, sub = jax.random.split(key)
        params[name] = init_one_param(cfg, name, shape, sub, dtype)
    return params


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  max_num_seqs: int, dtype=jnp.bfloat16) -> KVCache:
    """``k`` / ``v``: the A layers' grouped-query rows, paged (``llama``'s
    pool at the A layers' count; the first keys: pool-agnostic code reads the
    first array as THE paged pool); ``ssd`` [M layers, slots] + the held
    layout of one state (``ssd.state_shape``: float32) and ``conv`` [M
    layers, slots, taps - 1, d_inner + 2N]: one recurrent state a slot (rank
    5 and 4: how ``block_copy`` knows that they hold no blocks)."""
    kinds = layer_kinds(cfg)
    n_m = _n_kind(kinds, "M")
    H, P, N, _, cd = ssd_sizes(cfg)
    rows = (_n_kind(kinds, "A"), num_blocks * block_size,
            cfg.num_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype),
            "ssd": jnp.zeros((n_m, max_num_seqs) + state_shape(H, P, N),
                             _F32),
            "conv": jnp.zeros((n_m, max_num_seqs, cfg.ssd_conv_kernel - 1,
                               cd), dtype)}


def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know: a paged group of plain
    grouped-query rows and a state group, no window (llm/kv/hybrid.py, the
    fifth layout)."""
    from ...llm.kv.hybrid import HybridCacheLayout
    kinds = layer_kinds(cfg)
    H, P, N, _, cd = ssd_sizes(cfg)
    n_a = _n_kind(kinds, "A")
    return HybridCacheLayout(
        block_size=block_size,
        row_bytes=2 * cfg.num_kv_heads * cfg.head_dim * dtype_bytes,
        paged_layers=n_a, readers_of_paged=n_a,
        window_layers=0, window=0,
        state_layers=_n_kind(kinds, "M"),
        state_bytes=(4 * H * P * N
                     + dtype_bytes * (cfg.ssd_conv_kernel - 1) * cd))


def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """What this engine asks for that cannot carry a slot's state: the
    stateful families' one table (``sambay.state_refusals``) and int4, which
    no projection of this family is validated under."""
    bad = state_refusals(engine_cfg, mesh)
    if engine_cfg.quantization.startswith("int4"):
        bad.append("--quantization int4 (the grouped-int4 kernels are "
                   "unvalidated for these projections)")
    return bad


# ---------------------------------------------------------------------------
# The blocks
# ---------------------------------------------------------------------------


def _scaled(delta, cfg: ModelConfig, like):
    """residual_multiplier * a branch's output, in the stream's dtype."""
    return (delta.astype(_F32) * cfg.residual_multiplier).astype(like.dtype)


def _ssd_inputs(lp, proj, conv_out, cfg: ModelConfig, act):
    """(the layer's leaves, ``x W_in`` [n, 2 d_inner + 2N + H], the
    convolution's output after SiLU [n, d_inner + 2N] float32) -> z [n,
    d_inner], x' [n, H, P] (the activation dtype), B, C [n, N] float32, dt
    [n, H] float32 (after softplus), A [H] float32."""
    H, P, N, di, _ = ssd_sizes(cfg)
    n = proj.shape[0]
    x = conv_out[:, :di].astype(act).reshape(n, H, P)
    dt = jax.nn.softplus(proj[:, 2 * di + 2 * N:].astype(_F32)
                         + lp["ssd_dt_bias"].astype(_F32))
    return (proj[:, :di], x, conv_out[:, di:di + N], conv_out[:, di + N:],
            dt, -jnp.exp(lp["ssd_A_log"].astype(_F32)))


def _ssd_out(lp, y, x, z, cfg: ModelConfig):
    """y [n, H, P] float32 (the state's read), x' [n, H, P], the gate z [n,
    d_inner] -> the block's output [n, D]: the skip, the gate, THEN one norm
    over all d_inner lanes."""
    n = y.shape[0]
    y = y + lp["ssd_D"].astype(_F32)[None, :, None] * x.astype(_F32)
    y = y.reshape(n, -1) * jax.nn.silu(z.astype(_F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y * lp["ssd_norm"].astype(_F32)
    return mm(y.astype(z.dtype), lp["ssd_out"])


def _conv_taps(lp, taps):
    """taps: the inputs of every output row, oldest first, as a list of
    [..., d_inner + 2N]. -> silu(conv + bias), float32."""
    w = lp["ssd_conv"].astype(_F32)
    acc = lp["ssd_conv_b"].astype(_F32) + w[0] * taps[0].astype(_F32)
    for j in range(1, len(taps)):
        acc = acc + w[j] * taps[j].astype(_F32)
    return jax.nn.silu(acc)


def _qkv(lp, hn, cfg: ModelConfig):
    """-> q [n, H, Dh], k, v [n, KVH, Dh]: no bias, no rotation."""
    n = hn.shape[0]
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    if "wqkv" in lp:              # fused (llama.fuse_stacked_matmuls)
        qkv = mm(hn, lp["wqkv"])
        q, k, v = qkv[:, :qd], qkv[:, qd:qd + kvd], qkv[:, qd + kvd:]
    else:
        q, k, v = mm(hn, lp["wq"]), mm(hn, lp["wk"]), mm(hn, lp["wv"])
    return (q.reshape(n, cfg.num_heads, cfg.head_dim),
            k.reshape(n, cfg.num_kv_heads, cfg.head_dim),
            v.reshape(n, cfg.num_kv_heads, cfg.head_dim))


_MOE_STACKS = ("router", "moe_gate", "moe_up", "moe_down", "moe_gateup",
               "sh_gate", "sh_up", "sh_down", "sh_gateup")
_ATTN_STACKS = ("wq", "wk", "wv", "wqkv", "wo")


def _walk(params, kv, x, slots, cfg: ModelConfig, read_rows, ssd_mix,
          experts_sharded: bool = True, valid_rows=None):
    """The layers in the published order by ``layer_plan`` (module
    docstring). Every stack stays whole beside the scans and is read at its
    layer in place: ln1 / ln2 and the experts at the layer's index, the
    attention and ``ssd_*`` stacks at its index among its kind.

    read_rows(q, k_flat, v_flat, ai) -> [n, H, Dh]: the attention read of
    the paged rows (this dispatch's own already written);
    ssd_mix(lp, hn, pools, ai) -> (the block's output [n, D], pools)."""
    stack = _layer_stack(params)
    moe_lp, whole = split_expert_stacks(
        {n: stack[n] for n in _MOE_STACKS if n in stack}, x.shape[0],
        cfg.num_experts_per_tok, experts_sharded)
    kind_lp = {"A": {n: stack[n] for n in _ATTN_STACKS if n in stack},
               "M": {n: w for n, w in stack.items()
                     if n.startswith("ssd_")}}
    n_a, NTOK = kv["k"].shape[:2]

    def attend(lp, hn, pools, ai):
        q, k, v = _qkv(lp, hn, cfg)
        n = hn.shape[0]
        kp = pools["k"].at[ai, slots, :].set(
            k.reshape(n, -1).astype(pools["k"].dtype), mode="drop")
        vp = pools["v"].at[ai, slots, :].set(
            v.reshape(n, -1).astype(pools["v"].dtype), mode="drop")
        with jax.named_scope("attention"):
            out = read_rows(q, kp.reshape(n_a * NTOK, -1),
                            vp.reshape(n_a * NTOK, -1), ai)
        return mm(out.reshape(n, -1), lp["wo"]), dict(pools, k=kp, v=vp)

    def experts(u, li):
        lp = {**stack_at(moe_lp, li), **whole}
        out = moe_mlp(u, lp["router"], lp.get("moe_gate"), lp.get("moe_up"),
                      lp["moe_down"], cfg.num_experts_per_tok,
                      norm_topk=True, gateup_w=lp.get("moe_gateup"),
                      sharded=experts_sharded, valid_rows=valid_rows,
                      layer=li if whole else None)
        with jax.named_scope("shared_expert"):        # no gate on this path
            return out + swiglu(u, lp.get("sh_gate"), lp.get("sh_up"),
                                lp["sh_down"], "silu",
                                gateup_w=lp.get("sh_gateup"))

    def layer(h, pools, li, ai, kind):
        ln = stack_at({"ln1": stack["ln1"], "ln2": stack["ln2"]}, li)
        hn = rms_norm(h, ln["ln1"], cfg.rms_norm_eps)
        lp = stack_at(kind_lp[kind], ai)
        if kind == "M":
            with jax.named_scope("ssd"):
                delta, pools = ssd_mix(lp, hn, pools, ai)
        else:
            delta, pools = attend(lp, hn, pools, ai)
        h = h + _scaled(delta, cfg, h)
        u = rms_norm(h, ln["ln2"], cfg.rms_norm_eps)
        return h + _scaled(experts(u, li), cfg, h), pools

    def run(carry, li0, ai0, kinds):
        """``kinds`` from layer li0 on; ai0: the layers of each kind before
        it. A run of one kind is one scan, which carries that kind's arrays
        alone (a loop that carries the K/V pool past the Mamba-2 layers
        makes XLA keep a second copy of it)."""
        li, seen = li0, dict(ai0)
        for kind, n in _runs(kinds):
            if n == 1:
                carry = layer(*carry, li, seen[kind], kind)
            else:
                h, pools = carry
                own = ("ssd", "conv") if kind == "M" else ("k", "v")
                rest = {k: v for k, v in pools.items() if k not in own}

                def body(c, j, li=li, a0=seen[kind], kind=kind, rest=rest,
                         own=own):
                    h, out = layer(c[0], {**rest, **c[1]}, li + j, a0 + j,
                                   kind)
                    return (h, {k: out[k] for k in own}), None
                (h, mine), _ = jax.lax.scan(
                    body, (h, {k: pools[k] for k in own}),
                    jnp.arange(n, dtype=jnp.int32))
                carry = (h, {**rest, **mine})
            li, seen[kind] = li + n, seen[kind] + n
        return carry

    period, n_periods, tail = layer_plan(cfg)
    per = {kd: _n_kind(period, kd) for kd in ("M", "A")}
    carry = (x, dict(kv))
    if n_periods == 1:
        carry = run(carry, 0, {"M": 0, "A": 0}, period)
    else:
        def body(c, pi):
            return run(c, pi * len(period),
                       {kd: pi * per[kd] for kd in per}, period), None
        carry, _ = jax.lax.scan(body, carry,
                                jnp.arange(n_periods, dtype=jnp.int32))
    if tail:
        carry = run(carry, n_periods * len(period),
                    {kd: n_periods * per[kd] for kd in per}, tail)
    x, pools = carry
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps), pools


def _embed(params: Params, tokens, cfg: ModelConfig):
    x = llama._embed(params, tokens, cfg)
    return x * jnp.asarray(cfg.embedding_multiplier or 1.0, x.dtype)


def _logits(params: Params, x, cfg: ModelConfig):
    return llama._logits(params, x, cfg) / cfg.logits_scaling


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics) -> Tuple[jax.Array, KVCache]:
    """Batched single-token decode step (llama.decode_forward's contract).
    Row b is slot b. A row aimed at the trash block is not live."""
    cfg = statics.cfg
    B = tokens.shape[0]
    bsz = statics.block_size
    H, P, N, di, cd = ssd_sizes(cfg)
    live = block_tables[:, 0] > 0
    first = live & (positions == 0)
    keep = jnp.where(first, 0.0, 1.0)
    interpret = not _on_tpu()
    slots = (block_tables[jnp.arange(B), positions // bsz] * bsz
             + positions % bsz)
    scale = llama._attn_scale(cfg)

    def read_rows(q, k_flat, v_flat, ai):
        # layer ai's blocks sit at block offset ai * num_blocks of the flat
        # pool (llama.decode_forward's addressing)
        num_blocks = kv["k"].shape[1] // bsz
        return llama._paged_attention(
            statics, q, k_flat, v_flat, block_tables + ai * num_blocks,
            positions + 1, None, scale)

    def ssd_mix(lp, hn, pools, ai):
        state, conv = pools["ssd"], pools["conv"]
        proj = mm(hn, lp["ssd_in"])
        with jax.named_scope("causal_conv"):
            prev = conv[ai]                                  # [B, taps-1, cd]
            taps = jnp.concatenate(
                [prev * keep[:, None, None].astype(prev.dtype),
                 proj[:, None, di:di + cd].astype(prev.dtype)], axis=1)
            out = _conv_taps(lp, [taps[:, j] for j in range(taps.shape[1])])
            conv = conv.at[ai].set(jnp.where(live[:, None, None],
                                             taps[:, 1:], prev))
        z, x, b, c, dt, A = _ssd_inputs(lp, proj, out, cfg, hn.dtype)
        # a row that is not live leaves its state (dt 0: decay 1, input 0);
        # position 0 starts from zero (decay 0)
        dt = jnp.where(live[:, None], dt, 0.0)
        decay = jnp.where(first[:, None], 0.0, jnp.exp(dt * A))
        with jax.named_scope("ssd_step"):
            y, flat = ssd_step(x, dt, decay, b, c,
                               state.reshape((-1,) + state.shape[2:]), ai,
                               interpret=interpret)
        pools = dict(pools, ssd=flat.reshape(state.shape), conv=conv)
        return _ssd_out(lp, y, x, z, cfg), pools

    x, kv_new = _walk(params, kv, _embed(params, tokens, cfg), slots, cfg,
                      read_rows, ssd_mix, experts_sharded=statics.sharded)
    return _logits(params, x, cfg), kv_new


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics,
                    slot=0) -> Tuple[jax.Array, KVCache]:
    """Single-sequence (chunk) prefill, llama.prefill_forward's contract,
    plus ``slot``: whose state and conv inputs these are. ``start_pos`` 0
    starts from the zero state; a later dispatch continues from what the
    slot holds. The state written is the one after ``true_len`` tokens,
    whatever the bucket's padding."""
    cfg = statics.cfg
    T = tokens.shape[0]
    bsz = statics.block_size
    H, P, N, di, cd = ssd_sizes(cfg)
    slot = jnp.asarray(slot, jnp.int32)
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T, dtype=jnp.int32) < true_len
    rows = jnp.where(valid,
                     block_table[positions // bsz] * bsz + positions % bsz, 0)
    seq_len = start_pos + true_len
    fresh = start_pos == 0
    interpret = not _on_tpu()
    K1 = cfg.ssd_conv_kernel - 1
    scale = llama._attn_scale(cfg)
    use_flash = llama._prefill_flash_impl(statics)

    def read_rows(q, k_flat, v_flat, ai):
        # attend over the whole block table (the rows before this dispatch
        # and its own), as llama.prefill_forward does
        NTOK = kv["k"].shape[1]
        idx = flat_token_indices(block_table[None, :], bsz)[0] + ai * NTOK
        S = idx.shape[0]
        ks = jnp.take(k_flat, idx, axis=0).reshape(
            S, cfg.num_kv_heads, cfg.head_dim)
        vs = jnp.take(v_flat, idx, axis=0).reshape(
            S, cfg.num_kv_heads, cfg.head_dim)
        if use_flash:
            return flash_prefill(q, ks, vs, scale=scale, start_pos=start_pos,
                                 seq_len=seq_len,
                                 interpret=(use_flash == "interpret"))
        g = cfg.num_heads // cfg.num_kv_heads
        qg = q.reshape(T, cfg.num_kv_heads, g, cfg.head_dim)
        scores = jnp.einsum("tkgd,skd->kgts", qg, ks).astype(_F32) * scale
        kv_pos = jnp.arange(S, dtype=jnp.int32)
        mask = (kv_pos[None, :] <= positions[:, None]) & (
            kv_pos[None, :] < seq_len)
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vs.dtype)
        return jnp.einsum("kgts,skd->tkgd", probs, vs).reshape(
            T, cfg.num_heads, cfg.head_dim)

    def ssd_mix(lp, hn, pools, ai):
        state, conv = pools["ssd"], pools["conv"]
        proj = mm(hn, lp["ssd_in"])
        with jax.named_scope("causal_conv"):
            prev = jnp.where(fresh, 0, conv[ai, slot])
            xx = jnp.concatenate([prev, proj[:, di:di + cd].astype(prev.dtype)])
            out = _conv_taps(lp, [xx[j:j + T] for j in range(K1 + 1)])
            conv = conv.at[ai, slot].set(
                jax.lax.dynamic_slice_in_dim(xx, true_len, K1))
        z, x, b, c, dt, A = _ssd_inputs(lp, proj, out, cfg, hn.dtype)
        dt = jnp.where(valid[:, None], dt, 0.0)
        s0 = jnp.where(fresh, 0.0, state[ai, slot])
        with jax.named_scope("ssd_chunk"):
            y, s = ssd_chunk(x, dt, dt * A, b, c, s0, true_len,
                             interpret=interpret)
        pools = dict(pools, ssd=state.at[ai, slot].set(s), conv=conv)
        return _ssd_out(lp, y, x, z, cfg), pools

    x, kv_new = _walk(params, kv, _embed(params, tokens, cfg), rows, cfg,
                      read_rows, ssd_mix, experts_sharded=statics.sharded,
                      valid_rows=true_len)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, cfg), kv_new


# The door (models.module_for), with ``refusals`` above

def engine_cache(cfg: ModelConfig, engine_cfg, dtype, kv_shards: int = 1):
    """-> (kv, layout, win_blocks) as ``llama.engine_cache``:
    ``--num-kv-blocks`` sizes the A layers' pool, ``--max-num-seqs`` the
    states; no window blocks and, every mesh refused, one shard."""
    e = engine_cfg
    kv = init_kv_cache(cfg, e.num_kv_blocks, e.kv_block_size,
                       e.max_num_seqs, dtype=dtype)
    return kv, cache_layout(cfg, e.kv_block_size,
                            jnp.dtype(dtype).itemsize), 0


def prefill_counters(cfg: ModelConfig, bucket: int, rows: int,
                     prompt_len: int) -> dict:
    """Of a prefill of ``rows`` prompt rows in ``bucket``-row dispatches:
    the rows the Mamba-2 layers ran over (``scan_tokens``), the chunks of
    ``ssd.CHUNK`` rows a layer's state walked (``ssd_chunks``: a dispatch
    walks the chunks that hold its rows, not its bucket's padding) and the
    keys the attention layers' rows attended (``key_tokens``: row i of the
    prompt reads i + 1 keys)."""
    first = prompt_len - rows
    full, rest = divmod(rows, bucket)
    return {"scan_tokens": rows,
            "ssd_chunks": full * -(-bucket // CHUNK) + -(-rest // CHUNK),
            "key_tokens": rows * first + rows * (rows + 1) // 2}
