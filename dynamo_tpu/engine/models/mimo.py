"""mimo_v2: grouped-query attention of two geometries in one model.

MiMo-V2's layers are plain grouped-query attention (no latent), of two
kinds by ``hybrid_layer_pattern``: full layers (``layer_types`` entry
"full_attention": H query / KVH key-value heads, rope base ``rope_theta``)
attend over the whole context, window layers ("sliding_attention":
``swa_num_heads`` / ``swa_num_kv_heads``, base ``swa_rope_theta``) over the
last ``swa_window`` positions, the query's own included, with one learned
scalar a query head in their softmax's denominator (``swa_sink``). Both
kinds: keys of ``head_dim`` lanes and values of ``v_head_dim`` lanes
(192 / 128 published), rope in HF's half-split pairing on the first
``rotary_dim`` lanes of every query and key head, values times
``value_scale``. Layer 0 is dense (SwiGLU at ``dense_intermediate_size``),
the others route ``mla._moe_mlp``'s sigmoid_noaux branch over one group,
of which this chip may hold a share. docs/hybrid_cache.md part three.

The block of a layer of kind k (H, KVH, theta of that kind; dk, dv, r =
head_dim, v_head_dim, rotary_dim):

    q = n(h)·Wq -> [H, dk]     k = n(h)·Wk -> [KVH, dk]
    v = value_scale · n(h)·Wv -> [KVH, dv]
    rope(theta) on lanes [0, r) of q and k; lanes [r, dk) pass
    s_tj = q_t·k_j / sqrt(dk), head h reads kv head h // (H / KVH)
    F: p = softmax_j(s) over j <= t
    S: p_tj = exp(s_tj) / (exp(b_h) + sum_j' exp(s_tj')), t-window < j <= t
    h' = h + Wo·(sum_j p_tj v_j)       h'' = h' + M(n(h'))

**One walker.** The layers run through ``mla.walk_layer_kinds`` (dense
prefix unrolled, ONE lax.scan over the periods of the layer kinds, a tail),
which this model shares with dots3_note; only the attention block of each
kind is this module's. Parameter leaves: ``layers.<leaf>`` [n_F, ...] for
the full layers, ``layers.swa_<leaf>`` [n_S, ...] for the window layers
(``swa_sink`` [n_S, Hs] float32, never quantised), ``dense_*``, the expert
stacks as deepseek_v3's.

**Two pools.** ``kv["k"]`` / ``kv["v"]`` [n_F, NTOK, KVH·dk | KVH·dv] hold
the full layers' rows under the paged pool's block ids; ``kv["win_k"]`` /
``kv["win_v"]`` [n_S, WTOK, KVHs·dk | KVHs·dv] the window layers' under the
window pool's (llm/kv/hybrid.py ``window_pool``; engine/core.py keeps the
second table). A row is the heads side by side, unpadded: head kh's key
starts at lane dk·kh. With dk = 192 that is no lane-tile boundary, and it
need not be: the decode kernel dots a sparse-slotted query against whole
rows, so it is the ROW that must lie on 128-lane tiles (4·192 = 768,
8·192 = 1,536), and the prefill kernels take dense [S, KVH, dk] operands.
A head padded to 256 lanes would cost 25% more bytes a row for nothing.

**Reads.** Decode: ``attention.paged_attention`` with ``v_dim`` over the
whole table (full, Pallas name ``gqa_full_read``) and over a ring view of
at most ``ring_blocks`` window-pool blocks with ``win_lo`` and the sink
(window, ``gqa_window_read``); where several of a step's rows are one slot's
(a resident drafter's step) the kernel takes them as ONE sequence with
``rows=`` and fetches the slot's cache once (``_decode_plan``). Prefill: a
full layer walks its table by key
blocks of GQA_KEY_BLOCK rows up to the live length and folds each block's
partial softmax state (``flash_prefill_partial``, ``gqa_full_prefill``); a
window layer gathers the chunk's own rows and the window - 1 before them
from the window pool by their blocks and runs ``flash_prefill`` with the
window and the sink (``gqa_window_prefill``). Off the TPU and at widths the
kernels do not tile, the same reads in XLA.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..attention import (ATTN_CHUNK_BLOCKS, NEG_INF, _on_tpu,
                         causal_attention, flash_prefill,
                         flash_prefill_partial, flash_prefill_supported,
                         kernel_wanted, paged_attention, pallas_supported)
from ..config import ModelConfig
from ..quant import mm
from .llama import (KVCache, ModelStatics, Params, _embed, _layer_stack,
                    _logits, apply_rope, rms_norm)
from .mla import (_n_kind, _swa_ring_view, _swa_tables, layer_kinds,
                  stack_at, swa_ring_blocks, walk_layer_kinds)

logger = logging.getLogger("dynamo_tpu.engine")

# rows of the table a full layer's prefill chunk reads and attends at a
# time (a whole number of the pool's blocks): bounds what one call of the
# kernel is handed; the walk ends at the live length, not at the table's
GQA_KEY_BLOCK = 2048
# rows of a DMA wave of the full layers' decode read (a 768-lane key row
# and a 512-lane value row serve all 64 heads of a sequence)
GQA_WAVE_ROWS = 512


def _attn_shapes(cfg: ModelConfig, n: int, prefix: str = "") -> Dict:
    """The attention leaves of ``n`` layers of one geometry."""
    D, H, KVH = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads
    dk, dv = cfg.head_dim, cfg.v_head_dim
    shapes = {f"layers.{prefix}wq": (n, D, H * dk),
              f"layers.{prefix}wk": (n, D, KVH * dk),
              f"layers.{prefix}wv": (n, D, KVH * dv),
              f"layers.{prefix}wo": (n, H * dv, D)}
    if cfg.qk_norm:
        # exaone_moe: RMSNorm over a head's lanes on every query and key
        # head, before the rope
        shapes[f"layers.{prefix}q_norm"] = (n, dk)
        shapes[f"layers.{prefix}k_norm"] = (n, dk)
    return shapes


def _expert_shapes(cfg: ModelConfig, n: int) -> Dict:
    """The leaves of ``n`` expert layers: the router at its published
    width, the held experts, the shared expert where the model has one."""
    D, E, F, R = (cfg.hidden_size, cfg.num_experts, cfg.intermediate_size,
                  cfg.router_width)
    shapes = {"layers.router": (n, D, R),
              "layers.moe_gate": (n, E, D, F),
              "layers.moe_up": (n, E, D, F),
              "layers.moe_down": (n, E, F, D),
              "layers.router_bias": (n, R)}
    if cfg.shared_expert_size:
        Fs = cfg.shared_expert_size
        shapes.update({"layers.sh_gate": (n, D, Fs),
                       "layers.sh_up": (n, D, Fs),
                       "layers.sh_down": (n, Fs, D)})
    return shapes


def mtp_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """The resident multi-token-prediction module's leaves (``mtp.<leaf>``,
    every one with a leading axis of 1: quant.py and the walker read them as
    a stack of one layer): the two input norms, ``eh_proj`` [2D, D], one
    decoder block of kind "F" with an expert MLP, its final norm. Embedding
    and head are the model's."""
    D = cfg.hidden_size
    block = {**_attn_shapes(cfg, 1), **_expert_shapes(cfg, 1),
             "layers.ln1": (1, D), "layers.ln2": (1, D)}
    return {"mtp.enorm": (1, D), "mtp.hnorm": (1, D),
            "mtp.eh_proj": (1, 2 * D, D),
            **{"mtp." + k.split(".", 1)[1]: v for k, v in block.items()},
            "mtp.final_norm": (1, D)}


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    """The order is the order the seeded weights' keys are split in."""
    L, D = cfg.num_layers, cfg.hidden_size
    kinds = layer_kinds(cfg)
    k, Lm = cfg.first_k_dense, L - cfg.first_k_dense
    Fd = cfg.dense_intermediate_size
    cfg_s = cfg.swa_gqa_geometry()
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        **_attn_shapes(cfg, _n_kind(kinds, "F")),
        "layers.dense_gate": (k, D, Fd),
        "layers.dense_up": (k, D, Fd),
        "layers.dense_down": (k, Fd, D),
        **_expert_shapes(cfg, Lm),
        **_attn_shapes(cfg_s, _n_kind(kinds, "S"), "swa_"),
    }
    if cfg.swa_sink:
        shapes["layers.swa_sink"] = (_n_kind(kinds, "S"), cfg_s.num_heads)
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    if cfg.mtp_layers:
        # LAST: the seeded weights' keys are split in this order, so a
        # model served without its module draws the same main weights
        shapes.update(mtp_shapes(cfg))
    return shapes


def row_lanes(cfg: ModelConfig) -> Tuple[int, int]:
    """(key row, value row) lanes of one layer of ``cfg``'s geometry: the
    heads side by side, unpadded."""
    return cfg.num_kv_heads * cfg.head_dim, cfg.num_kv_heads * cfg.v_head_dim


def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know: two groups of pool blocks,
    paged rows of the full layers and window rows (the wider ones) of the
    window layers."""
    from ...llm.kv.hybrid import HybridCacheLayout
    kinds = layer_kinds(cfg)
    n_f, n_s = _n_kind(kinds, "F"), _n_kind(kinds, "S")
    # a resident multi-token-prediction block keeps rows of its own in the
    # paged group, under the same block ids: one more full layer's a token
    n_f += cfg.mtp_layers
    return HybridCacheLayout(
        block_size=block_size, row_bytes=sum(row_lanes(cfg)) * dtype_bytes,
        paged_layers=n_f, readers_of_paged=n_f,
        window_layers=n_s, window=cfg.swa_window,
        state_layers=0, state_bytes=0, window_pool=True,
        window_row_bytes=sum(row_lanes(cfg.swa_gqa_geometry())) * dtype_bytes,
        rows_read_next_token=cfg.mtp_layers > 0,
        rows_ahead=min(cfg.mtp_layers, 1))


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, quantization: str = "none",
                  win_blocks: int = 0, kv_shards: int = 1) -> KVCache:
    """``win_blocks``: what HybridCacheLayout.window_pool_blocks derives
    (0 = as many as the paged pool, for a caller that drives one table for
    both groups)."""
    if quantization != "none" or kv_shards != 1:
        raise NotImplementedError(
            "kv_quantization / a sharded pool with mimo_v2's two row widths "
            "is not implemented (int8 rows have one encoding, of one width)")
    kinds = layer_kinds(cfg)
    n_f, n_s = _n_kind(kinds, "F"), _n_kind(kinds, "S")
    ck, cv = row_lanes(cfg)
    sk, sv = row_lanes(cfg.swa_gqa_geometry())
    if _on_tpu() and not decode_kernels_tile(cfg, block_size):
        # never silently: attn_impl "auto" would take the XLA gather for a
        # row width the kernel does not tile
        logger.warning(
            "mimo_v2: key/value rows of %s / %s lanes at --kv-block-size %d "
            "are off the Pallas decode kernel's tiles: both decode reads "
            "take the XLA gather", (ck, cv), (sk, sv), block_size)
    ntok, wtok = num_blocks * block_size, (win_blocks
                                           or num_blocks) * block_size
    n_f += cfg.mtp_layers            # the module's rows: pool index n_F
    return {"k": jnp.zeros((n_f, ntok, ck), dtype),
            "v": jnp.zeros((n_f, ntok, cv), dtype),
            "win_k": jnp.zeros((n_s, wtok, sk), dtype),
            "win_v": jnp.zeros((n_s, wtok, sv), dtype)}


def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """What this engine asks for that a window group of pool blocks does
    not run under yet, by name; empty = go (read once, at engine build)."""
    e = engine_cfg
    checks = {
        "--ragged (ragged_forward has no window layers)": e.ragged_dispatch,
        "--spec-k with the n-gram drafter (its verify program takes no "
        "window blocks; a model with a resident multi-token-prediction "
        "module drafts for itself)": e.spec_k > 0 and not cfg.mtp_layers,
        "--spec-k > 1 (one multi-token-prediction module gives one draft a "
        "step; it is not run recurrently for a second)": e.spec_k > 1,
        "--lane-prefill-max-tokens (a lane's rows take no window blocks)":
            e.lane_prefill_max_tokens > 0,
        "--decode-steps-per-dispatch > 1 (window blocks are taken and "
        "released a step at a time)": e.decode_steps_per_dispatch > 1,
        "--kv-quantization (rows of two widths have no int8 encoding)":
            e.kv_quantization != "none",
        "--quantization int4 (the grouped-int4 paths are not validated "
        "for this family)": e.quantization.startswith("int4"),
        "--host-kv-blocks / --kv-disk-* / --kv-remote-* (the tiers ship "
        "the paged pool's rows only)": bool(
            e.host_kv_blocks or e.kv_disk_blocks or e.kv_remote_dir),
        "tp/sp/pp/ep/dp meshes (the window pool has no sharding rule; an "
        "expert share IS this chip's part of an expert-parallel layer)":
            mesh is not None or max(e.tp, e.sp, e.pp, e.ep, e.dp) > 1,
    }
    return [name for name, on in checks.items() if on]


# ---------------------------------------------------------------------------
# The attention block
# ---------------------------------------------------------------------------


def _inv_freq(cfg: ModelConfig) -> np.ndarray:
    r = cfg.rotary_dim or cfg.head_dim
    return 1.0 / (cfg.rope_theta ** (np.arange(0, r, 2, dtype=np.float64)
                                     / r)).astype(np.float32)


def _rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rope on the first rotary_dim lanes of every head of x [N, heads,
    dk] (HF's half-split pairs within them); the other lanes pass."""
    r = cfg.rotary_dim or cfg.head_dim
    rot = apply_rope(x[..., :r], positions, jnp.asarray(_inv_freq(cfg)))
    return rot if r == x.shape[-1] else jnp.concatenate(
        [rot, x[..., r:]], axis=-1)


def _qkv(lp, hn: jax.Array, positions: jax.Array, cfg: ModelConfig,
         rope: bool = True):
    """→ (q [N, H, dk], k [N, KVH, dk], v [N, KVH, dv]) of one layer of
    ``cfg``'s geometry: projected (one fused matmul where
    llama.fuse_stacked_matmuls made one), every head normed where the model
    norms them, roped (``rope``: exaone_moe's full layers carry no
    position), the values scaled."""
    N = hn.shape[0]
    H, KVH, dk, dv = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.v_head_dim)
    if "wqkv" in lp:
        qkv = mm(hn, lp["wqkv"])
        q, k, v = (qkv[:, :H * dk], qkv[:, H * dk:(H + KVH) * dk],
                   qkv[:, (H + KVH) * dk:])
    else:
        q, k, v = mm(hn, lp["wq"]), mm(hn, lp["wk"]), mm(hn, lp["wv"])
    def heads(x, n, norm):
        x = x.reshape(N, n, dk)
        if cfg.qk_norm:
            x = rms_norm(x, lp[norm], cfg.rms_norm_eps)
        return _rope(x, positions, cfg) if rope else x

    q, k = heads(q, H, "q_norm"), heads(k, KVH, "k_norm")
    v = v.reshape(N, KVH, dv)
    if cfg.value_scale != 1.0:
        v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
    return q, k, v


def _kernel_form(statics: ModelStatics, supported: bool, what: str):
    """How a read runs under ``statics.attn_impl``: True (the Pallas
    kernel), "interpret", or False (XLA). A forced kernel on a geometry it
    does not tile raises: nothing falls to XLA silently."""
    impl = statics.attn_impl
    if not kernel_wanted(impl) or (impl == "auto" and not supported):
        return False
    if not supported:
        raise ValueError(f"attn_impl {impl!r} forced but the {what} "
                         f"kernel does not tile this geometry")
    return "interpret" if impl == "pallas_interpret" else True


def _take_blocks(pool: jax.Array, ai, ids: jax.Array, bsz: int,
                 heads: int) -> jax.Array:
    """The blocks ``ids`` of layer ``ai`` of a pool [n, NTOK, C] as rows
    [len(ids)·bsz, heads, C/heads]: gathered from the pool as ONE array of
    blocks under layer-global ids (a view, as the decode reads take it), so
    that no layer's slice of the pool is copied to be read from."""
    n, ntok, C = pool.shape
    blocks = pool.reshape(n * (ntok // bsz), bsz, C)
    ids = jnp.clip(ids, 0, ntok // bsz - 1) + ai * (ntok // bsz)
    return jnp.take(blocks, ids, axis=0).reshape(-1, heads, C // heads)


def _full_chunk(q, k_pool, v_pool, ai, table, start_pos, seq_len,
                cfg: ModelConfig, bsz: int, kernel,
                name: str = "gqa_full_prefill") -> jax.Array:
    """Causal attention of the T queries of one prefill chunk (query t at
    position start_pos + t) over the live rows of its table (positions <
    seq_len), by key blocks of GQA_KEY_BLOCK rows with a running max and
    sum: each block is read from the pool by its blocks and attended (the
    Pallas flash kernel in its partial form, or the same in XLA), its state
    folded in. Nothing of size chunk × table exists and the walk ends at
    the live length. → [T, H, dv] float32."""
    T, H, dk = q.shape
    KVH, dv, g = cfg.num_kv_heads, cfg.v_head_dim, H // cfg.num_kv_heads
    scale = dk ** -0.5
    M = table.shape[0]
    nb = max(1, min(GQA_KEY_BLOCK // bsz, M))
    KB = nb * bsz
    table = jnp.pad(table, (0, -M % nb))           # the trash block: masked
    f32 = jnp.float32

    def block(j, state):
        acc, m, l = state
        ids = jax.lax.dynamic_slice(table, (j * nb,), (nb,))
        ks = _take_blocks(k_pool, ai, ids, bsz, KVH)
        vs = _take_blocks(v_pool, ai, ids, bsz, KVH)
        # the block's frame: its first row is position 0
        q_lo, live = start_pos - j * KB, jnp.clip(seq_len - j * KB, 0, KB)
        if kernel:
            acc_j, m_j, l_j = flash_prefill_partial(
                q, ks, vs, scale=scale, start_pos=q_lo, seq_len=live,
                interpret=(kernel == "interpret"), name=name)
        else:
            s = jnp.einsum("tkgd,skd->tkgs", q.reshape(T, KVH, g, dk), ks,
                           preferred_element_type=f32) * scale
            kpos = jnp.arange(KB)[None, :]
            mask = ((kpos <= q_lo + jnp.arange(T)[:, None])
                    & (kpos < live))[:, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_j = jnp.max(s, axis=-1)
            # a row with nothing to read: exp(NEG_INF - NEG_INF) is not 0
            p = jnp.where(mask, jnp.exp(s - m_j[..., None]), 0.0)
            l_j = jnp.sum(p, axis=-1).reshape(T, H)
            acc_j = jnp.einsum("tkgs,skd->tkgd", p.astype(vs.dtype), vs,
                               preferred_element_type=f32).reshape(T, H, dv)
            m_j = m_j.reshape(T, H)
        m_new = jnp.maximum(m, m_j)
        a, b = jnp.exp(m - m_new), jnp.exp(m_j - m_new)
        return (acc * a[..., None] + acc_j * b[..., None], m_new,
                l * a + l_j * b)

    acc, _m, l = jax.lax.fori_loop(
        0, (seq_len + KB - 1) // KB, block,
        (jnp.zeros((T, H, dv), f32), jnp.full((T, H), NEG_INF, f32),
         jnp.zeros((T, H), f32)))
    return acc / jnp.maximum(l, 1e-20)[..., None]


def _window_chunk(q, k_pool, v_pool, ai, table, start_pos, seq_len,
                  cfg: ModelConfig, window: int, bsz: int, sink,
                  kernel) -> jax.Array:
    """Window attention of the T queries of one prefill chunk (query t at
    position start_pos + t reads the keys s with t - window < s <= t): the
    rows [start_pos - window + 1, start_pos + T) are read from the window
    pool by their blocks (table: the block of every logical block) and
    attended in the frame of the first block read. cfg: the window layers'
    geometry. → [T, H, dv]."""
    T, KVH = q.shape[0], cfg.num_kv_heads
    scale = q.shape[2] ** -0.5
    back = window - 1
    nb = (T + back) // bsz + 2           # the chunk, the window, misaligned
    b0 = jnp.maximum(start_pos - back, 0) // bsz
    ids = jax.lax.dynamic_slice(jnp.pad(table, (0, nb)), (b0,), (nb,))
    ks = _take_blocks(k_pool, ai, ids, bsz, KVH)
    vs = _take_blocks(v_pool, ai, ids, bsz, KVH)
    q0, live = start_pos - b0 * bsz, seq_len - b0 * bsz
    if kernel:
        return flash_prefill(
            q, ks, vs, scale=scale, start_pos=q0, seq_len=live,
            sliding=True, window=window, sink=sink,
            interpret=(kernel == "interpret"), name="gqa_window_prefill")
    return causal_attention(q, ks, vs, scale=scale, kv_offset=q0,
                            length=live, window=window, sink=sink)


def _attend_fn(params: Params, cfg: ModelConfig, positions, slots, slots_s,
               read_full, read_window, paged_at: int = 0):
    """``walk_layer_kinds``' attention block for this model: project, write
    the layer's rows into its pool, read (``read_full(q, pools, ai)`` /
    ``read_window(q, pools, ai, sink)`` -> [N, H·dv]) and project out.
    ``paged_at``: where the full layers of ``params`` start in the paged
    pool (the multi-token-prediction block's rows lie behind the model's)."""
    cfg_s = cfg.swa_gqa_geometry()
    stack = _layer_stack(params)
    names = {"F": [n for n in ("wq", "wk", "wv", "wqkv", "wo", "q_norm",
                               "k_norm") if n in stack],
             "S": [n for n in stack if n.startswith("swa_")]}

    def attend(kind, hn, pools, ai):
        lp = stack_at({n: stack[n] for n in names[kind]}, ai)
        N = hn.shape[0]
        if kind == "F":
            q, k, v = _qkv(lp, hn, positions, cfg, rope=not cfg.nope_full)
            if paged_at:
                ai = ai + paged_at
            pools = dict(
                pools,
                k=pools["k"].at[ai, slots, :].set(
                    k.reshape(N, -1).astype(pools["k"].dtype), mode="drop"),
                v=pools["v"].at[ai, slots, :].set(
                    v.reshape(N, -1).astype(pools["v"].dtype), mode="drop"))
            attn = read_full(q, pools, ai)
        else:
            lp = {n[len("swa_"):]: w for n, w in lp.items()}
            q, k, v = _qkv(lp, hn, positions, cfg_s)
            pools = dict(
                pools,
                win_k=pools["win_k"].at[ai, slots_s, :].set(
                    k.reshape(N, -1).astype(pools["win_k"].dtype),
                    mode="drop"),
                win_v=pools["win_v"].at[ai, slots_s, :].set(
                    v.reshape(N, -1).astype(pools["win_v"].dtype),
                    mode="drop"))
            attn = read_window(q, pools, ai, lp.get("sink"))
        return mm(attn.reshape(N, -1).astype(hn.dtype), lp["wo"]), pools

    return attend


# ---------------------------------------------------------------------------
# Forward passes (llama.prefill_forward / decode_forward's contracts)
# ---------------------------------------------------------------------------


def _prefill_plan(tokens, block_table, start_pos, true_len,
                  statics: ModelStatics):
    """What a prefill chunk's attention blocks share → (positions, slots,
    slots_s, read_full(name), read_window): where the chunk's rows go in the
    two pools, and the two reads (``read_full(name)`` gives the full
    layers' read under a Pallas ``name=`` of the caller's)."""
    cfg, bsz = statics.cfg, statics.block_size
    cfg_s = cfg.swa_gqa_geometry()
    T = tokens.shape[0]
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < true_len
    block_table, table_s = _swa_tables(block_table, statics.table_blocks, 0,
                                       doubled=True)
    table_s = block_table if table_s is None else table_s
    slots = jnp.where(
        valid, block_table[positions // bsz] * bsz + positions % bsz, 0)
    slots_s = jnp.where(
        valid, table_s[positions // bsz] * bsz + positions % bsz, 0)
    seq_len = start_pos + true_len
    kernel = _kernel_form(
        statics,
        flash_prefill_supported(cfg.num_heads, cfg.num_kv_heads,
                                cfg.head_dim, cfg.v_head_dim)
        and flash_prefill_supported(cfg_s.num_heads, cfg_s.num_kv_heads,
                                    cfg_s.head_dim, cfg_s.v_head_dim),
        "flash prefill")

    def full_reader(name: str):
        def read_full(q, pools, ai):
            with jax.named_scope(name + "_attention"):
                return _full_chunk(q, pools["k"], pools["v"], ai,
                                   block_table, start_pos, seq_len, cfg, bsz,
                                   kernel, name=name)
        return read_full

    def read_window(q, pools, ai, sink):
        with jax.named_scope("gqa_window_prefill_attention"):
            return _window_chunk(q, pools["win_k"], pools["win_v"], ai,
                                 table_s, start_pos, seq_len, cfg_s,
                                 cfg.swa_window, bsz, sink, kernel)

    return positions, slots, slots_s, full_reader, read_window


def _prefill_hidden(params: Params, kv: KVCache, tokens, block_table,
                    start_pos, true_len, statics: ModelStatics):
    """→ (the chunk's final hidden states [T, D] as the head reads them, new
    kv, the plan): the chunk's rows are scattered first; the full layers
    read the live rows of the table back by key blocks, the window layers
    the chunk's and the window - 1 before them."""
    cfg = statics.cfg
    plan = _prefill_plan(tokens, block_table, start_pos, true_len, statics)
    positions, slots, slots_s, full_reader, read_window = plan
    x = _embed(params, tokens, cfg)
    x, kv_new = walk_layer_kinds(
        params, kv, x, cfg,
        _attend_fn(params, cfg, positions, slots, slots_s,
                   full_reader("gqa_full_prefill"), read_window),
        experts_sharded=statics.sharded, valid_rows=true_len)
    return x, kv_new, plan


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics
                    ) -> Tuple[jax.Array, KVCache]:
    """tokens [T] (padded), block_table [M] or the engine's [2M] (the window
    pool's block of every logical block behind the paged pool's:
    mla._swa_tables) → (last-token logits [V], new kv)."""
    x, kv_new, _plan = _prefill_hidden(params, kv, tokens, block_table,
                                       start_pos, true_len, statics)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, statics.cfg), kv_new


def _decode_plan(kv: KVCache, positions, block_tables,
                 statics: ModelStatics, rows: int = 1):
    """What a decode step's attention blocks share → (slots, slots_s,
    read_full(name), read_window). Both reads are
    ``attention.paged_attention``: the full layers' over the whole table
    (under a Pallas ``name=`` of the caller's), the window layers' over a
    ring view of R window-pool blocks with the window's lower bound and the
    sink. ``rows``: how many of the step's rows are one slot's (adjacent
    positions under one table, slot-major: the shape of the step). Under
    the kernel they share ONE pass over the slot's cache
    (``decode_cache_passes``); every row is written into both pools before
    any read either way."""
    cfg, bsz = statics.cfg, statics.block_size
    cfg_s = cfg.swa_gqa_geometry()
    B = positions.shape[0]
    R = swa_ring_blocks(cfg, bsz)
    block_tables, ring = _swa_tables(block_tables, statics.table_blocks, R,
                                     doubled=False)
    slots = (block_tables[jnp.arange(B), positions // bsz] * bsz
             + positions % bsz)
    seq_lens = positions + 1
    view, view_len, view_lo = _swa_ring_view(
        cfg.swa_window, bsz, positions, block_tables, ring, R)
    # the newest row's block is the view's last entry
    slots_s = view[:, R - 1] * bsz + positions % bsz
    num_blocks = kv["k"].shape[1] // bsz
    win_blocks = kv["win_k"].shape[1] // bsz
    share = {}
    if decode_cache_passes(statics, rows) < rows:
        # the call takes a SLOT's table, length and ring view: its last
        # row's (that row's ring holds the earlier rows' windows too,
        # swa_ring_blocks), and every row's own lower bound, moved into that
        # view's frame: a row's own view starts so many blocks earlier
        last = slice(rows - 1, None, rows)
        blk = positions // bsz
        view_lo = view_lo - (jnp.repeat(blk[last], rows) - blk) * bsz
        block_tables, seq_lens = block_tables[last], seq_lens[last]
        view, view_len = view[last], view_len[last]
        share = {"rows": rows}

    def reader(c: ModelConfig, name: str, chunk_blocks: int):
        # under jit so that the kernel is traced and lowered once for all
        # the layers of a kind in a period, not once a layer
        return jax.jit(functools.partial(
            paged_attention, block_size=bsz, scale=c.head_dim ** -0.5,
            impl=statics.attn_impl, kv_heads=c.num_kv_heads,
            v_dim=c.v_head_dim, coalesce=statics.kv_coalesce,
            chunk_blocks=chunk_blocks, name=name, **share))

    def full_reader(name: str):
        full = reader(cfg, name, max(ATTN_CHUNK_BLOCKS, GQA_WAVE_ROWS // bsz))

        def read_full(q, pools, ai):
            k, v = pools["k"], pools["v"]
            with jax.named_scope("gqa_full_decode_attention"):
                return full(q, k.reshape(-1, k.shape[2]),
                            v.reshape(-1, v.shape[2]),
                            block_tables + ai * num_blocks, seq_lens)
        return read_full

    window = reader(cfg_s, "gqa_window_read", max(ATTN_CHUNK_BLOCKS, R))

    def read_window(q, pools, ai, sink):
        k, v = pools["win_k"], pools["win_v"]
        with jax.named_scope("gqa_window_decode_attention"):
            return window(q, k.reshape(-1, k.shape[2]),
                          v.reshape(-1, v.shape[2]),
                          view + ai * win_blocks, view_len, win_lo=view_lo,
                          sink=sink)

    return slots, slots_s, full_reader, read_window


def _decode_hidden(params: Params, kv: KVCache, tokens, positions,
                   block_tables, statics: ModelStatics, rows: int = 1):
    """→ (the rows' final hidden states [B, D], new kv, the plan)."""
    cfg = statics.cfg
    plan = _decode_plan(kv, positions, block_tables, statics, rows)
    slots, slots_s, full_reader, read_window = plan
    x = _embed(params, tokens, cfg)
    x, kv_new = walk_layer_kinds(
        params, kv, x, cfg,
        _attend_fn(params, cfg, positions, slots, slots_s,
                   full_reader("gqa_full_read"), read_window),
        experts_sharded=statics.sharded)
    return x, kv_new, plan


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics) -> Tuple[jax.Array, KVCache]:
    """tokens [B], positions [B], block_tables [B, M] or the engine's
    [B, M + R] (logical window block b at entry M + b % R) → (logits
    [B, V], new kv)."""
    x, kv_new, _plan = _decode_hidden(params, kv, tokens, positions,
                                      block_tables, statics)
    return _logits(params, x, statics.cfg), kv_new


# ---------------------------------------------------------------------------
# The multi-token-prediction module, resident (exaone_moe; docs/
# speculative.md "A resident drafter")
# ---------------------------------------------------------------------------


def _mtp_block(params: Params, kv: KVCache, h, next_tokens, positions, slots,
               read_full, statics: ModelStatics, valid_rows=None):
    """The module over N rows: row i, at position p = positions[i], takes
    the main model's last hidden state of that position as its head reads
    it (h [N, D]) and the token at p + 1 (next_tokens [N]):

        u  = W_eh · [ N_e(Emb(x_{p+1})) ; N_h(h_p) ]
        u' = Block_F(u)    over the module's OWN rows 0..p (pool index n_F)
        → N_mtp(u') [N, D]: what the model's head turns into the logits of
          the token at p + 2

    The block is one layer of the model's kind "F" with an expert MLP, read
    from the ``mtp.<leaf>`` stacks through the model's own walker and
    attention block (fields of the configuration, not a copy)."""
    cfg = statics.cfg
    mtp = {n[len("mtp."):]: w for n, w in params.items()
           if n.startswith("mtp.")}
    own = ("enorm", "hnorm", "eh_proj", "final_norm")
    head = stack_at({n: mtp[n] for n in own}, 0)
    sub = {"final_norm": head["final_norm"],
           **{"layers." + n: w for n, w in mtp.items() if n not in own}}
    cfg_m = dataclasses.replace(cfg, num_layers=1, first_k_dense=0,
                                layer_types=["full_attention"], mtp_layers=0)
    eps = cfg.rms_norm_eps
    u = mm(jnp.concatenate(
        [rms_norm(_embed(params, next_tokens, cfg), head["enorm"], eps),
         rms_norm(h, head["hnorm"], eps)], axis=-1), head["eh_proj"])
    n_f = _n_kind(layer_kinds(cfg), "F")
    return walk_layer_kinds(
        sub, kv, u, cfg_m,
        _attend_fn(sub, cfg_m, positions, slots, None, read_full, None,
                   paged_at=n_f),
        experts_sharded=statics.sharded, valid_rows=valid_rows)


def _draft_logits(params: Params, u: jax.Array, cfg: ModelConfig):
    """The module's logits, through the model's head. A function of its own
    so that a check can carry them out of the compiled programs
    (benchmark/references/exaone_moe_check.py taps it): the served path
    keeps their argmax only, and lockstep acceptance hides a wrong drafter
    completely (it only slows)."""
    return _logits(params, u, cfg)


def prefill_forward_mtp(params: Params, kv: KVCache, tokens: jax.Array,
                        block_table: jax.Array, start_pos: jax.Array,
                        true_len: jax.Array, next_token: jax.Array,
                        statics: ModelStatics, sample):
    """``prefill_forward`` with the module's tail → (token, logprob, draft
    logits [V], new kv). ``sample``: last-token logits [V] → (token,
    logprob), the engine's. The tail runs the module over the chunk's rows
    shifted one token: row p takes h_p and x_{p+1}, which for the chunk's
    last row is ``next_token`` (the prompt's next one; < 0 after the last
    chunk: the token just sampled). The draft logits are the last row's:
    the module's guess at the token after the sampled one."""
    cfg = statics.cfg
    x, kv, plan = _prefill_hidden(params, kv, tokens, block_table, start_pos,
                                  true_len, statics)
    positions, slots, _slots_s, full_reader, _read_window = plan
    last = jnp.maximum(true_len - 1, 0)
    tok, logprob = sample(_logits(params, x[last], cfg))
    nxt = jnp.roll(tokens, -1).at[last].set(
        jnp.where(next_token >= 0, next_token, tok).astype(tokens.dtype))
    u, kv = _mtp_block(params, kv, x, nxt, positions, slots,
                       full_reader("mtp_full_prefill"), statics,
                       valid_rows=true_len)
    return tok, logprob, _draft_logits(params, u[last], cfg), kv


def decode_forward_mtp(params: Params, kv: KVCache, tokens: jax.Array,
                       positions: jax.Array, block_tables: jax.Array,
                       statics: ModelStatics, sample, rows: int = 1):
    """``decode_forward`` with the module's tail over the same rows →
    (tokens [N], logprobs [N], draft logits [N, V], new kv). ``sample``:
    logits [N, V] → (tokens, logprobs), the engine's. Row i scores the
    input token at positions[i]; the module then takes that row's hidden
    state and the token sampled from it, and its logits are the guess at
    the token AFTER the sampled one. The rows of one slot of a two-row step
    are adjacent positions under the same table: row 1 reads row 0's fresh
    rows in both pools (written before any read), and the module's too.
    ``rows`` says how many a slot has (slot-major; ``_decode_plan``): the
    three reads then fetch a slot's cache once for all of them."""
    cfg = statics.cfg
    x, kv, plan = _decode_hidden(params, kv, tokens, positions, block_tables,
                                 statics, rows)
    slots, _slots_s, full_reader, _read_window = plan
    toks, logprobs = sample(_logits(params, x, cfg))
    u, kv = _mtp_block(params, kv, x, toks, positions, slots,
                       full_reader("mtp_full_read"), statics)
    return toks, logprobs, _draft_logits(params, u, cfg), kv


def decode_kernels_tile(cfg: ModelConfig, block_size: int) -> bool:
    """Whether both decode reads run as the Pallas kernel on a TPU (a
    geometry it refuses takes the XLA gather: paged_attention's "auto")."""
    return all(pallas_supported(c.num_heads, c.num_kv_heads, c.head_dim,
                                block_size, v_dim=c.v_head_dim)
               for c in (cfg, cfg.swa_gqa_geometry()))


def decode_cache_passes(statics: ModelStatics, rows: int) -> int:
    """The passes over a slot's cache that a read of a decode step makes
    when ``rows`` of the step's rows are that slot's: 1 where both reads run
    as the Pallas kernel (it takes ``rows`` queries a sequence and fetches
    each wave once for all of them), ``rows`` on the XLA gather, where every
    row stays a sequence of its own (nothing to share there)."""
    tiled = (kernel_wanted(statics.attn_impl)
             and decode_kernels_tile(statics.cfg, statics.block_size))
    return 1 if tiled or rows < 2 else rows
