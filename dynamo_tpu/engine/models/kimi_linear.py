"""Kimi Linear (``model_type: kimi_linear``, Kimi-Linear-48B-A3B; Kimi Team,
arXiv:2510.26692) in pure JAX: Kimi-Delta-Attention layers that keep a
matrix state a slot, beside latent-attention layers over the paged latent
pool (docs/hybrid_cache.md part five).

Pre-norm blocks, ``h += Mix(RMSNorm(h)); h += MLP(RMSNorm(h))``, the layer
kinds by ``linear_attn_config``'s two lists (``mla.layer_kinds``: "K" and
"F"), the MLPs DeepSeek-V3's (one dense layer, then sigmoid-scored
``noaux_tc`` experts in one group with a shared expert: ``mla._moe_mlp``).

**Kind F** is ``models/mla.py``'s latent block with a plain ``wq`` (no
q-LoRA) and NO rotation of the pe lanes (``cfg.mla_nope``): the 64 lanes
behind the 128 are plain key lanes shared by all heads. Its rows live in the
latent pool under the block table; a prefill chunk reads them by key blocks
(``mla._dense_chunk`` / the ``mla_prefill`` kernel), a decode step in the
absorbed form through the paged kernel: both are ``mla.prefill_forward`` /
``mla.decode_forward``'s own reads, handed to this module's layer walk.

**Kind K** (``_kda_mix``), H heads of d lanes, P = H d, x the normed input:

    [q~ | k~ | v~] = x W_in                      (``kda_in`` [D, 3P])
    each through its depthwise causal convolution over time (``kda_conv``
    [taps, 3P], no bias), then SiLU
    q = q / |q| d^-1/2,  k = k / |k|             (per head, eps L2_EPS)
    [fa | ga | b] = x W_low                      (``kda_low`` [D, 2d + H])
    g = -exp(A_log[h]) softplus(fa W_fb + dt_bias)   <= 0, per head and lane
    beta = sigmoid(b)
    S' = Diag(exp g) S ; S = S' + beta k (v - S'^T k)^T ; o = S^T q
    y = RMSNorm_d(o; kda_onorm) * sigmoid(ga W_gb + gb_bias) ; out = y W_o

``S`` is float32 ``[H, d, d]`` a slot and layer (``kv["kda"]``), the last
``taps - 1`` inputs of the convolutions beside it (``kv["conv"]``). A step
at position 0 starts from zero, in prefill and in decode; a row whose block
table is the trash row is not live and leaves both as they are; the state a
prefill chunk writes is the one after ``true_len`` rows, and the next chunk
of the prompt continues from it (``engine/kda.py``: ``kda_chunk``,
``kda_step``).

``prefill_forward`` takes the slot as an argument; the engine's prefill
program reads it from behind the block table's M entries, as for
``models/sambay.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..attention import _on_tpu
from ..config import ModelConfig
from ..kda import CHUNK, kda_chunk, kda_step
from ..quant import mm
from . import llama, mla
from .llama import KVCache, ModelStatics, Params, _layer_stack
from .sambay import state_refusals   # the stateful families' one table

_F32 = jnp.float32
L2_EPS = 1e-6
# seeded A_log / dt_bias (init_one_param): heads' decay rates spaced
# geometrically over A_RANGE, the channels' step sizes log-uniform over
# DT_RANGE, so that half-lives ln 2 / (A dt) run from ~10 to ~2,800 tokens
A_RANGE = (0.25, 2.0)
DT_RANGE = (1e-3, 3e-2)


def kda_shapes(cfg: ModelConfig, n: int) -> Dict[str, Tuple[int, ...]]:
    """The leaves of ``n`` delta-attention layers (module docstring)."""
    D, H, d = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    P = H * d
    return {f"layers.kda_{k}": (n,) + v for k, v in {
        "in": (D, 3 * P), "conv": (cfg.kda_conv_kernel, 3 * P),
        "low": (D, 2 * d + H), "fb": (d, P), "A_log": (H,),
        "dt_bias": (P,), "gb": (d, P), "gb_bias": (P,), "onorm": (d,),
        "wo": (P, D)}.items()}


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """``mla.param_shapes`` (the F layers' attention stacks hold the F
    layers only; ln1 / ln2 and the MLP stacks every layer) and the K
    layers' stack, before the head so that the head stays last."""
    shapes = mla.param_shapes(cfg)
    head = {k: shapes.pop(k) for k in ("lm_head",) if k in shapes}
    shapes.update(kda_shapes(cfg, mla._n_kind(mla.layer_kinds(cfg), "K")))
    shapes.update(head)
    return shapes


def init_one_param(cfg: ModelConfig, name: str, shape: tuple,
                   sub: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """The engine's seeded rule (``llama.init_one_param``), with the decay's
    two leaves where a normal draw would make every half-life alike:
    ``A_log`` per head the log of A_RANGE's geometric ladder, ``dt_bias``
    per channel the inverse softplus of a log-uniform draw over DT_RANGE
    (both float32); the convolution taps normal at taps^-1/2 (float32); the
    output norm 1 and the gate's bias 0."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "kda_A_log":
        H = shape[-1]
        lo, hi = (math.log(a) for a in A_RANGE)
        ladder = lo + (hi - lo) * jnp.arange(H, dtype=_F32) / max(H - 1, 1)
        return jnp.broadcast_to(ladder, shape)
    if leaf == "kda_dt_bias":
        lo, hi = (math.log(a) for a in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(sub, shape, _F32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "kda_conv":
        return jax.random.normal(sub, shape, _F32) * shape[-2] ** -0.5
    if leaf == "kda_onorm":
        return jnp.ones(shape, dtype)
    if leaf == "kda_gb_bias":
        return jnp.zeros(shape, dtype)
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
    """``kv``: the F layers' latent rows, paged (``mla.init_kv_cache``; the
    first key: pool-agnostic code reads the first array as THE paged pool);
    ``kda`` [K layers, slots, H, d, d] float32 and ``conv`` [K layers,
    slots, taps - 1, 3P]: one recurrent state a slot (rank 5 and 4: how
    ``block_copy`` knows that they hold no blocks)."""
    n_k = mla._n_kind(mla.layer_kinds(cfg), "K")
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    kv = mla.init_kv_cache(cfg, num_blocks, block_size, dtype=dtype)
    kv["kda"] = jnp.zeros((n_k, max_num_seqs, H, d, d), _F32)
    kv["conv"] = jnp.zeros(
        (n_k, max_num_seqs, cfg.kda_conv_kernel - 1, 3 * H * d), dtype)
    return kv


def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know: a paged latent group and a
    state group, no window (llm/kv/hybrid.py, the fourth layout)."""
    from ...llm.kv.hybrid import HybridCacheLayout
    kinds = mla.layer_kinds(cfg)
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    n_f = mla._n_kind(kinds, "F")
    return HybridCacheLayout(
        block_size=block_size,
        row_bytes=mla.latent_row_lanes(cfg) * dtype_bytes,
        paged_layers=n_f, readers_of_paged=n_f,
        window_layers=0, window=0,
        state_layers=mla._n_kind(kinds, "K"),
        state_bytes=(4 * H * d * d
                     + dtype_bytes * (cfg.kda_conv_kernel - 1) * 3 * H * d))


def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """What this engine asks for that cannot carry a slot's matrix state:
    the stateful families' one table (``sambay.state_refusals``) and what
    the latent block refuses of itself (``mla.refusals``: int4, an expert
    share under a mesh), each option named once."""
    bad = state_refusals(engine_cfg, mesh)
    named = {b.split(" ", 1)[0] for b in bad}
    return bad + [b for b in mla.refusals(cfg, engine_cfg, mesh)
                  if b.split(" ", 1)[0] not in named]


# ---------------------------------------------------------------------------
# The delta-attention block
# ---------------------------------------------------------------------------


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _kda_inputs(lp, hn, conv_out, cfg: ModelConfig):
    """(the layer's leaves, its normed input [N, D], the convolutions'
    output after SiLU [N, 3P] float32) -> q, k, v [N, H, d], g [N, H, d],
    beta [N, H], the output gate's logits [N, P]; all float32."""
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    N = hn.shape[0]
    q, k, v = (a.reshape(N, H, d) for a in jnp.split(conv_out, 3, axis=-1))
    low = mm(hn, lp["kda_low"], out_dtype=_F32)
    fa, ga, b = low[:, :d], low[:, d:2 * d], low[:, 2 * d:]
    act = hn.dtype
    a = mm(fa.astype(act), lp["kda_fb"], out_dtype=_F32)
    g = -jnp.exp(lp["kda_A_log"].astype(_F32))[None, :, None] * (
        jax.nn.softplus(a + lp["kda_dt_bias"].astype(_F32)).reshape(N, H, d))
    z = (mm(ga.astype(act), lp["kda_gb"], out_dtype=_F32)
         + lp["kda_gb_bias"].astype(_F32))
    return (_l2(q) * d ** -0.5, _l2(k), v, g, jax.nn.sigmoid(b), z)


def _kda_out(lp, o, z, cfg: ModelConfig, act):
    """o [N, H, d] float32, the gate's logits z [N, P] -> the block's
    output [N, D]."""
    N = o.shape[0]
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = y * lp["kda_onorm"].astype(_F32)
    y = y.reshape(N, -1) * jax.nn.sigmoid(z)
    return mm(y.astype(act), lp["kda_wo"])


def _conv_taps(lp, taps):
    """taps: the inputs of every output row, oldest first, as a list of
    [..., 3P]. -> silu(conv), float32."""
    w = lp["kda_conv"].astype(_F32)
    acc = w[0] * taps[0].astype(_F32)
    for j in range(1, len(taps)):
        acc = acc + w[j] * taps[j].astype(_F32)
    return jax.nn.silu(acc)


def _walk(params, kv, x, positions, slots, cfg, attn_fn, kda_mix,
          experts_sharded=True, valid_rows=None):
    """``mla._run_layers`` for this family: ``mla.walk_layer_kinds`` (the
    dense prefix, ONE scan over the periods K K F K, the two layers the
    published list leaves over) with the latent block for "F" — attn_fn is
    ``mla.prefill_forward``'s or ``mla.decode_forward``'s read of the
    latent pool — and ``kda_mix(lp, hn, pools, ai) -> (delta, pools)`` for
    "K". Every stack stays whole and is read at the layer's index among
    its kind."""
    stack = _layer_stack(params)
    NTOK = kv["kv"].shape[1]
    n_f = kv["kv"].shape[0]
    f_names = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
    k_names = [n for n in stack if n.startswith("kda_")]

    def attend(kind, hn, pools, ai):
        if kind == "K":
            with jax.named_scope("kda"):
                return kda_mix(mla.stack_at({n: stack[n] for n in k_names},
                                            ai), hn, pools, ai)
        lp = mla.stack_at({n: stack[n] for n in f_names}, ai)
        # no rotation: q_pe and the rows' pe lanes are plain (cfg.mla_nope)
        q_nope, q_pe, _ = mla._q_proj(lp, hn, cfg)
        rows = mla._latent_rows(lp, hn, positions, cfg)
        pool = pools["kv"]
        enc = jnp.pad(rows.astype(pool.dtype),
                      ((0, 0), (0, pool.shape[2] - rows.shape[1])))
        pool = pool.at[ai, slots, :].set(enc, mode="drop")
        attn = attn_fn(q_nope, q_pe, rows,
                       pool.reshape(n_f * NTOK, pool.shape[2]), lp, ai)
        return mm(attn, lp["wo"]), dict(pools, kv=pool)

    return mla.walk_layer_kinds(
        params, kv, x, cfg, attend,
        experts_sharded=experts_sharded, valid_rows=valid_rows)


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics) -> Tuple[jax.Array, KVCache]:
    """Batched single-token decode step (llama.decode_forward's contract).
    Row b is slot b. A row aimed at the trash block is not live."""
    cfg = statics.cfg
    B = tokens.shape[0]
    live = block_tables[:, 0] > 0
    first = live & (positions == 0)
    interpret = not _on_tpu()
    keep = jnp.where(first, 0.0, 1.0)

    def kda_mix(lp, hn, pools, ai):
        state, conv = pools["kda"], pools["conv"]
        qkv = mm(hn, lp["kda_in"])
        with jax.named_scope("causal_conv"):
            prev = conv[ai]                                  # [B, taps-1, 3P]
            taps = jnp.concatenate(
                [prev * keep[:, None, None].astype(prev.dtype),
                 qkv[:, None, :].astype(prev.dtype)], axis=1)
            out = _conv_taps(lp, [taps[:, j] for j in range(taps.shape[1])])
            conv = conv.at[ai].set(jnp.where(live[:, None, None],
                                             taps[:, 1:], prev))
        q, k, v, g, beta, z = _kda_inputs(lp, hn, out, cfg)
        # position 0 starts from zero (alpha 0); a row that is not live
        # leaves its state (alpha 1, beta 0)
        alpha = jnp.where(first[:, None, None], 0.0, jnp.exp(g))
        alpha = jnp.where(live[:, None, None], alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        with jax.named_scope("kda_step"):
            o, flat = kda_step(
                q, k, v, alpha, beta,
                state.reshape((-1,) + state.shape[2:]), ai,
                interpret=interpret)
        pools = dict(pools, kda=flat.reshape(state.shape), conv=conv)
        return _kda_out(lp, o, z, cfg, hn.dtype), pools

    return mla.decode_forward(
        params, kv, tokens, positions, block_tables, statics,
        layers=functools.partial(_walk, kda_mix=kda_mix))


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics,
                    slot=0) -> Tuple[jax.Array, KVCache]:
    """Single-sequence (chunk) prefill, llama.prefill_forward's contract,
    plus ``slot``: whose state and conv inputs these are. ``start_pos`` 0
    starts from the zero state; a later chunk continues from what the slot
    holds. The state written is the one after ``true_len`` tokens, whatever
    the bucket's padding."""
    cfg = statics.cfg
    T = tokens.shape[0]
    slot = jnp.asarray(slot, jnp.int32)
    valid = jnp.arange(T, dtype=jnp.int32) < true_len
    fresh = start_pos == 0
    interpret = not _on_tpu()
    K1 = cfg.kda_conv_kernel - 1

    def kda_mix(lp, hn, pools, ai):
        state, conv = pools["kda"], pools["conv"]
        qkv = mm(hn, lp["kda_in"])
        with jax.named_scope("causal_conv"):
            prev = jnp.where(fresh, 0, conv[ai, slot])
            xx = jnp.concatenate([prev, qkv.astype(prev.dtype)])
            out = _conv_taps(lp, [xx[j:j + T] for j in range(K1 + 1)])
            conv = conv.at[ai, slot].set(
                jax.lax.dynamic_slice_in_dim(xx, true_len, K1))
        q, k, v, g, beta, z = _kda_inputs(lp, hn, out, cfg)
        g = jnp.where(valid[:, None, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
        s0 = jnp.where(fresh, 0.0, state[ai, slot])
        with jax.named_scope("kda_chunk"):
            o, s = kda_chunk(q, k, v, g, beta, s0, interpret=interpret)
        pools = dict(pools, kda=state.at[ai, slot].set(s), conv=conv)
        return _kda_out(lp, o, z, cfg, hn.dtype), pools

    return mla.prefill_forward(
        params, kv, tokens, block_table, start_pos, true_len, statics,
        layers=functools.partial(_walk, kda_mix=kda_mix))


# The door (models.module_for), with ``refusals`` above

def engine_cache(cfg: ModelConfig, engine_cfg, dtype, kv_shards: int = 1):
    """-> (kv, layout, win_blocks) as ``llama.engine_cache``:
    ``--num-kv-blocks`` sizes the latent pool, ``--max-num-seqs`` the
    states; no window blocks and, every mesh refused, one shard."""
    e = engine_cfg
    kv = init_kv_cache(cfg, e.num_kv_blocks, e.kv_block_size,
                       e.max_num_seqs, dtype=dtype)
    return kv, cache_layout(cfg, e.kv_block_size,
                            jnp.dtype(dtype).itemsize), 0


def prefill_counters(cfg: ModelConfig, bucket: int, rows: int,
                     prompt_len: int) -> dict:
    """Of a prefill of ``rows`` prompt rows in ``bucket``-row dispatches:
    the rows the delta-attention layers ran over (``scan_tokens``), the
    chunks of ``kda.CHUNK`` rows a layer's state walked (``kda_chunks``:
    every dispatch walks its whole bucket) and the keys the latent layers'
    rows attended (``key_tokens``, as ``mla.prefill_counters``)."""
    return {"scan_tokens": rows,
            "kda_chunks": -(-rows // bucket) * -(-bucket // CHUNK),
            **mla.prefill_counters(cfg, bucket, rows, prompt_len)}
