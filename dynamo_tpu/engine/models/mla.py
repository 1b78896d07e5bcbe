"""Multi-head Latent Attention (MLA, deepseek_v2) in pure JAX with the
paged LATENT-KV cache.

The reference serves DeepSeek models through its external engines; here
MLA is an engine-native model definition like models/llama.py. The
design is what makes MLA attractive for serving: the per-token cache is
the COMPRESSED latent row — ``[c_kv (kv_lora_rank) | k_pe
(qk_rope_head_dim)]``, e.g. 512+64 lanes instead of H·(192+128) — and
decode runs the ABSORBED form, contracting queries into latent space so
attention reads only those rows (an MQA-shaped read despite H heads).
The row format drops straight into the block-major paged pool
``[L, NTOK, rank+rope]`` the whole KV subsystem (reuse, offload,
handoff) already speaks.

Conventions pinned against HF ``DeepseekV2Attention`` (transformers
4.57, modeling_deepseek_v2.py:288-400, verified by the parity tests):

- rope is INTERLEAVED complex rotation (pairs (2i, 2i+1), angle
  pos·inv_freq[i]) — NOT llama's half-split convention;
- softmax scale is (qk_nope + qk_rope)^-0.5;
- the cached latent is the POST-RMSNorm compressed kv (k/v expand from
  it with the pure matmul ``kv_b``), and k_pe is cached post-rope;
- q path: plain ``q_proj`` when q_lora_rank == 0 (the -Lite layout),
  else ``q_a → rmsnorm → q_b``.

Scope: dense MLP layers AND the deepseek MoE block (additive shared
experts, first_k_dense hybrid sparsity via split scans, greedy +
group-limited-greedy routing with routed_scaling — all HF-parity
tested); deepseek_v3's sigmoid-scored noaux_tc routing (bias-corrected
top-2-sum group selection, renormalized top-k, and the yarn mscale²
score scale HF applies in DeepseekV3Attention); default AND yarn rope
(incl. the inferred mscale attention factor); EngineCore serves MLA
end-to-end through the model dispatch (core.is_mla), including dp/tp/ep
meshes (parallel/sharding.py: head-sharded projections, replicated
latent pool, expert-parallel MoE stacks), int8 latent-KV pools
(init_kv_cache quantization="int8": in-row scales, one pair per
c_kv/k_pe section), int8 weights (quant._LAYER_MATMULS; wkv_b stays
full precision for the absorbed einsums), the host KV tier (latent
rows ship whole as one opaque wire head — llm/kv/offload.py), both
disagg planes, and sequence-parallel ring prefill (prefill_forward_sp:
the ring moves compressed latent rows and accumulates in rank-space).
Still refusing loudly: int4 weights.

deepseek_v32 (``index_topk > 0``; docs/dsa.md) adds DeepSeek Sparse
Attention on top of the v3 block:

- the **lightning indexer**: per layer ``qI = qr·WqI_b → [T, J, dI]`` from
  the q-LoRA latent, one index key ``kI = LayerNorm(a·WkI)`` per token,
  rope on the FIRST ``qk_rope_head_dim`` lanes of both in the HALF-SPLIT
  convention (not the main path's interleaved one), head weights
  ``w = a·Ww · J^-0.5 · dI^-0.5``, and the score
  ``I[t, s] = Σ_j w[t, j] · relu(qI[t, j]·kI[s])``;
- a **second per-token cache** for the index keys, ``kv["idx"]``
  ``[L, NTOK, dI]``, beside the latent pool and under the same block ids
  (not wider latent rows: the indexer reads dI lanes per token, not
  rank+rope+dI). ``_run_layers`` writes both at ``slots``; the block
  copies of engine/block_copy.py move every array of the pool dict, so
  prefix reuse, defrag and preemption carry both;
- **select, then attend**: an exact top-k of the masked scores,
  ``k = min(index_topk, table capacity)``, ties to the lower position,
  that never orders the table by score (``_select`` →
  ``engine/select_compact.py``, a Pallas kernel): the k-th largest score
  by a bisection on the scores' bits (a compare and a row count a step),
  the ties at it by position, and then a compaction that moves ONE
  uint32 per taken position to the front (position above, layer-offset
  block id below), from which the pool rows come back by arithmetic.
  Where position and block id need more than 32 bits (``TableSlots``:
  the table's length and the pool's block count, static shapes) the
  position and the row move as two values. Then the absorbed attention
  over the gathered rows only. ``ctx <= index_topk`` selects every valid
  row and equals the dense path. Prefill blocks its queries
  (``DSA_QUERY_BLOCK``) so that nothing of size heads × chunk × table
  exists, and walks only the blocks that hold a live row of the chunk;
- **one chip's share of the experts** (``num_experts_total > 0``): the
  router, its bias and the groups keep the published width; the expert
  stacks hold ``num_experts`` of them, and ``_moe_mlp`` adds what the
  held experts give for the tokens routed to them and drops the rest.

Without an indexer (deepseek_v2, deepseek_v3, kimi_k2 — the v3 block at
its own sizes, one routing group; docs/mla_dense.md) every query reads
every cached row: a prefill chunk in the EXPANDED form, by key blocks of
the live table with a running max and sum (``_dense_chunk``; on the TPU
one Pallas call a key block, engine/mla_prefill.py), a decode step in the
ABSORBED form over the paged pool (``decode_forward``).

dots3_note (``cfg.has_swa_latent``; docs/hybrid_cache.md) has TWO latent
geometries in one model. Its "full_attention" layers are the v3.2 block
above at this model's sizes; its "sliding_attention" layers are the same
MLA form at sizes of their own (``ModelConfig.swa_geometry``: other head
count, q-LoRA and latent ranks, head dims and rope theta, no indexer) and
attend over the last ``swa_window`` positions, the query's own included.
Both kinds multiply each head's attention output by a sigmoid gate of the
layer's normed input (``wg`` / ``swa_wg``: the headwise **gate**) before
``wo``, and rescale the normed q and kv latents by sqrt(hidden / rank)
(``mla_lora_rescale``; the cache holds the scaled latent, the indexer
reads the scaled q latent). The parameters are two attention stacks of
different shapes (``layers.<leaf>`` [full layers, ...], ``layers.swa_<leaf>``
[window layers, ...]) beside the MLP stacks; ``_run_layers_mixed`` runs
them in the published order by a scan over the period of ``layer_types``
and reads every stack whole and in place. The window layers' rows live in
``kv["win"]`` [window layers, NTOK_S, rank_s + rope_s padded], a pool of its
own block ids: a decode step reads them through a table of ``ring_blocks``
entries a sequence (``_swa_ring_view``), a prefill chunk expands the keys
and values of ``chunk + window`` rows once and attends by blocks of queries
(``_swa_chunk``).

What carries ``idx`` and what refuses is decided once, at engine build
(``refusals``): ragged dispatch, speculative verify, sequence-parallel
prefill, every mesh (tp/sp/pp/ep/dp), int8 KV pools, the host/disk/remote
tiers, the KV fabric and both disagg planes refuse while ``index_topk > 0``.
The multi-token-prediction layer is not served (weights.py skips it).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..attention import (ATTN_CHUNK_BLOCKS, dequant_kv_rows_sections,
                         quantize_kv_rows_sections,
                         ragged_paged_attention_pallas)
from ..config import ModelConfig
from ..index_scores import index_scores_pallas, index_scores_supported
from ..mla_prefill import (K_TILE, Q_TILE, mla_prefill_block,
                           mla_prefill_supported)
from ..quant import mm
from ..select_compact import NOT_TAKEN, compact_top_k
from .llama import (ModelStatics, _embed, _layer_stack, _logits, engine_cache,
                    flat_token_indices, init_one_param, rms_norm, run_experts,
                    split_expert_stacks, swiglu)

Params = Dict[str, jax.Array]
KVCache = Dict[str, jax.Array]   # {"kv": [L, NTOK, rank + rope]}

NEG_INF = -1e30


def get_mscale(scale: float, m: float = 1.0) -> float:
    """HF yarn_get_mscale — the ONE home for the yarn mscale formula
    (rope_params' cos/sin attention factor AND softmax_scale's v3 score
    correction derive from it)."""
    import math
    if scale <= 1:
        return 1.0
    return 0.1 * m * math.log(scale) + 1.0


# ---------------------------------------------------------------------------
# Rope (interleaved complex convention — HF apply_rotary_emb)
# ---------------------------------------------------------------------------


def rope_params(cfg: ModelConfig):
    """(inv_freq [d/2], attention_scaling) — default rope, or yarn
    (deepseek checkpoints): mirrors HF _compute_yarn_parameters
    (modeling_rope_utils.py:246-365) — NTK interpolation/extrapolation
    blend over a linear ramp between the beta_fast/beta_slow correction
    dims, and the inferred attention factor that multiplies cos/sin
    (mscale; = 1.0 when mscale == mscale_all_dim, the released-V2
    setting)."""
    import math
    d = cfg.qk_rope_head_dim
    base = cfg.rope_theta
    pos_freqs = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv = 1.0 / pos_freqs
    rs = cfg.rope_scaling
    if rs is None:
        return inv.astype(np.float32), 1.0
    if rs.rope_type != "yarn":
        # loud-rejection convention (config.py phi3 longrope): serving a
        # linear/llama3/longrope deepseek checkpoint with unscaled
        # positions would decode garbage past the original context
        raise ValueError(
            f"MLA rope_scaling type {rs.rope_type!r} is not implemented "
            f"(yarn is; remove rope_scaling for base-context models)")
    factor = rs.factor
    if rs.attention_factor:
        # HF priority: an explicit attention_factor overrides inference
        att = rs.attention_factor
    elif rs.mscale and rs.mscale_all_dim:
        att = get_mscale(factor, rs.mscale) / get_mscale(
            factor, rs.mscale_all_dim)
    else:
        att = get_mscale(factor)
    interp = 1.0 / (factor * pos_freqs)

    def corr_dim(num_rot):
        return (d * math.log(rs.original_max_position_embeddings
                             / (num_rot * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs.beta_fast)), 0)
    high = min(math.ceil(corr_dim(rs.beta_slow)), d - 1)
    if low == high:
        high += 0.001                    # HF's singularity guard
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrap = 1.0 - ramp
    inv_freq = interp * (1 - extrap) + inv * extrap
    return inv_freq.astype(np.float32), float(att)


def softmax_scale(cfg: ModelConfig) -> float:
    """Attention score scale. Base = qk_head_dim^-0.5 for both
    generations; deepseek_v3 under yarn additionally multiplies by
    mscale(factor, mscale_all_dim)² (HF DeepseekV3Attention.__init__ —
    v2 applies its attention factor through cos/sin instead, so the two
    corrections never double-apply)."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if cfg.is_deepseek_v3 and rs is not None and rs.mscale_all_dim:
        m = get_mscale(rs.factor, rs.mscale_all_dim)
        s *= m * m
    return s


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           inv_freq: jax.Array,
                           scaling: float = 1.0) -> jax.Array:
    """x [..., T, d] with the pair (2i, 2i+1) rotated by pos·inv_freq[i]
    (torch.view_as_complex pairing). positions: [T]. ``scaling``
    multiplies cos/sin (yarn attention factor — HF scales freqs_cis)."""
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = jnp.cos(ang) * scaling                        # [T, d/2]
    sin = jnp.sin(ang) * scaling
    shape = x.shape
    xp = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    # broadcast the [T, d/2] angles over any middle axes (q_pe carries a
    # head axis, k_pe does not)
    for _ in range(xp.ndim - 3):
        cos = cos[:, None]
        sin = sin[:, None]
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters / cache
# ---------------------------------------------------------------------------


def _attn_shapes(cfg: ModelConfig, n: int, prefix: str = "") -> tuple:
    """The attention leaves of ``n`` layers of one latent geometry, in the
    two runs ``param_shapes`` lists them in (its order is the order the
    seeded weights' keys are split in): the latent side, then the query
    side with the indexer where cfg has one and the headwise gate where it
    has that."""
    D, H = cfg.hidden_size, cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kv_side = {
        "wkv_a": (n, D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": (n, cfg.kv_lora_rank),
        "wkv_b": (n, cfg.kv_lora_rank,
                  H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (n, H * cfg.v_head_dim, D),
    }
    q_side = {}
    if cfg.q_lora_rank > 0:
        q_side.update({
            "wq_a": (n, D, cfg.q_lora_rank),
            "q_a_norm": (n, cfg.q_lora_rank),
            "wq_b": (n, cfg.q_lora_rank, H * qk),
        })
    else:
        q_side["wq"] = (n, D, H * qk)
    if cfg.index_topk > 0:
        # deepseek_v32 lightning indexer (int8 under --quantization int8:
        # idx_wq_b, idx_wk and idx_w go through mm(); the key LayerNorm's
        # weight and bias stay in the load dtype)
        J, dI = cfg.index_n_heads, cfg.index_head_dim
        q_side.update({
            "idx_wq_b": (n, cfg.q_lora_rank, J * dI),
            "idx_wk": (n, D, dI),
            "idx_k_norm_w": (n, dI),
            "idx_k_norm_b": (n, dI),
            "idx_w": (n, D, J),
        })
    if cfg.attention_gate:
        # the headwise gate: one sigmoid scalar a head (bf16 under
        # --quantization int8: H out-channels, and a sigmoid behind them)
        q_side["wg"] = (n, D, H)
    return tuple({f"layers.{prefix}{k}": v for k, v in part.items()}
                 for part in (kv_side, q_side))


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """"F" (attends over the whole context), "S" (over the window, with
    the second geometry) or "K" (keeps a recurrent state: kimi_linear's
    delta-attention layers) for every layer; all "F" without either."""
    if not (cfg.has_swa_latent or cfg.has_swa_gqa or cfg.has_kda):
        return ("F",) * cfg.num_layers
    return tuple({"sliding_attention": "S", "linear_attention": "K"}.get(
        t, "F") for t in cfg.layer_types)


def _n_kind(kinds, kind: str) -> int:
    """How many of ``kinds`` are ``kind``."""
    return sum(1 for k in kinds if k == kind)


def layer_plan(cfg: ModelConfig):
    """→ (dense prefix k, the period of the kinds after it, whole periods,
    the kinds left over): how ``walk_layer_kinds`` walks the layers, e.g.
    F | F S S S | F S S S -> (1, ("F","S","S","S"), 2, ()). ONE rule: the
    period is the one whose repetition from the first layer after the prefix
    runs furthest beyond one period of itself (the smallest such); the scan
    takes its whole periods and what is left is unrolled. A list that repeats
    to its end, whole or cut (S S F S | S S F), keeps its period; one that
    ends SHORT of it (kimi_linear's K | K K F K x 6 | K F: the last layer is
    F where the period says K) keeps it too, with two layers left over, and
    not the "period" of 23 that alone reaches the end."""
    kinds = layer_kinds(cfg)
    k = cfg.first_k_dense if cfg.num_experts > 0 else 0
    rest = kinds[k:]

    def run(p):         # the length of the longest p-periodic prefix
        return next((i for i in range(len(rest)) if rest[i] != rest[i % p]),
                    len(rest))

    p = max(range(1, len(rest) + 1), key=lambda q: (run(q) - q, -q),
            default=0)
    n = run(p) // p if p else 0
    return k, rest[:p], n, rest[n * p:]


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    L, D = cfg.num_layers, cfg.hidden_size
    kinds = layer_kinds(cfg)
    kv_side, q_side = _attn_shapes(cfg, _n_kind(kinds, "F"))
    shapes: Dict[str, Tuple[int, ...]] = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        **kv_side,
    }
    if cfg.num_experts > 0:
        # deepseek hybrid: the first k layers are DENSE (their own
        # intermediate size), the rest are MoE with additive shared
        # experts — two parameter stacks, two scans (_run_layers)
        k = cfg.first_k_dense
        Lm = L - k
        # E: the experts HELD here; the router scores all the published
        # ones (cfg.router_width — the same number unless this chip holds
        # a share)
        E, F = cfg.num_experts, cfg.intermediate_size
        R = cfg.router_width
        if k > 0:
            Fd = cfg.dense_intermediate_size or F
            shapes.update({
                "layers.dense_gate": (k, D, Fd),
                "layers.dense_up": (k, D, Fd),
                "layers.dense_down": (k, Fd, D),
            })
        shapes.update({
            "layers.router": (Lm, D, R),
            "layers.moe_gate": (Lm, E, D, F),
            "layers.moe_up": (Lm, E, D, F),
            "layers.moe_down": (Lm, E, F, D),
        })
        if cfg.moe_routing == "sigmoid_noaux":
            # deepseek_v3: the router's e_score_correction_bias buffer —
            # it biases expert CHOICE only, never the mixing weights
            shapes["layers.router_bias"] = (Lm, R)
        if cfg.shared_expert_size > 0:
            Fs = cfg.shared_expert_size
            shapes.update({
                "layers.sh_gate": (Lm, D, Fs),
                "layers.sh_up": (Lm, D, Fs),
                "layers.sh_down": (Lm, Fs, D),
            })
    else:
        shapes.update({
            "layers.gate": (L, D, cfg.intermediate_size),
            "layers.up": (L, D, cfg.intermediate_size),
            "layers.down": (L, cfg.intermediate_size, D),
        })
    shapes.update(q_side)
    if cfg.has_swa_latent:
        # the window layers' stack, at their own sizes
        for part in _attn_shapes(cfg.swa_geometry(), _n_kind(kinds, "S"),
                                 "swa_"):
            shapes.update(part)
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    params: Params = {}
    for name, shape in param_shapes(cfg).items():
        key, sub = jax.random.split(key)
        params[name] = init_one_param(cfg, name, shape, sub, dtype)
    return params


def latent_row_lanes(cfg: ModelConfig, quantization: str = "none") -> int:
    """Pool row width, PADDED to a 128-lane multiple either way: the
    lane alignment is what makes the latent pool a legal block-DMA
    source for the Pallas paged-attention kernel (decode maps onto it
    as MQA — see decode_forward). Full precision: rank+rope up (e.g.
    512+64 -> 640). int8: the sectioned encode's rank+rope +
    KV_SCALE_LANES, padded (e.g. 576+128 -> 768). Readers slice the
    exact value/scale ranges, so pad lanes are write-only zeros."""
    from ..attention import KV_SCALE_LANES
    C = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    if quantization == "int8":
        C = C + KV_SCALE_LANES
    return -(-C // 128) * 128


def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know of a model with window layers
    of their own geometry (dots3_note): two groups, both made of pool
    blocks. None for every other MLA model (one uniform paged pool)."""
    if not cfg.has_swa_latent:
        return None
    from ...llm.kv.hybrid import HybridCacheLayout
    kinds = layer_kinds(cfg)
    lanes = latent_row_lanes(cfg) + (cfg.index_head_dim
                                     if cfg.index_topk > 0 else 0)
    n_f, n_s = _n_kind(kinds, "F"), _n_kind(kinds, "S")
    return HybridCacheLayout(
        block_size=block_size, row_bytes=lanes * dtype_bytes,
        paged_layers=n_f, readers_of_paged=n_f,
        window_layers=n_s, window=cfg.swa_window,
        state_layers=0, state_bytes=0, window_pool=True,
        window_row_bytes=latent_row_lanes(cfg.swa_geometry()) * dtype_bytes)


def init_kv_cache(cfg: ModelConfig, num_blocks: int,
                  block_size: int, dtype=jnp.bfloat16,
                  quantization: str = "none",
                  win_blocks: int = 0, kv_shards: int = 1) -> KVCache:
    """quantization="int8": the latent row quantizes with one in-row
    (e, m) scale pair PER c_kv/k_pe section
    (attention.quantize_kv_rows_sections — both pairs share one
    128-lane pad, and the row then PADS to a 128-lane multiple like
    the full-precision layout: e.g. 576+128 -> 768, wider than the
    unpadded llama encoding). Unlike llama pools there is never a
    per-tp-shard section (``kv_shards`` is not read): the latent pool
    replicates under tp (parallel/sharding.shard_kv), so every rank reads
    whole rows. Row widths: latent_row_lanes."""
    if quantization not in ("none", "int8"):
        raise ValueError(f"unknown kv quantization {quantization!r} "
                         f"(none|int8)")
    W = latent_row_lanes(cfg, quantization)
    # the layers whose rows this pool holds: all of them, or the
    # full-attention ones of a model with window layers (below)
    n_pool = _n_kind(layer_kinds(cfg), "F")
    kv = {"kv": jnp.zeros(
        (n_pool, num_blocks * block_size, W),
        dtype=jnp.int8 if quantization == "int8" else dtype)}
    if cfg.index_topk > 0:
        if quantization != "none":
            raise NotImplementedError(
                "kv_quantization with the deepseek_v32 index-key cache is "
                "not implemented (the index keys have no int8 encoding)")
        # the indexer's second per-token cache, under the same block ids
        # ("kv" stays the first key: pool-agnostic code reads it)
        kv["idx"] = jnp.zeros(
            (n_pool, num_blocks * block_size, cfg.index_head_dim),
            dtype=dtype)
    if cfg.has_swa_latent:
        if quantization != "none":
            raise NotImplementedError(
                "kv_quantization with window-layer latent rows is not "
                "implemented (they have no int8 encoding)")
        # the window layers' rows, at their own width and under the block
        # ids of a pool of their own (win_blocks of them: what
        # HybridCacheLayout.window_pool_blocks derives; 0 = as many as the
        # paged pool, for a caller that drives one table for both)
        kv["win"] = jnp.zeros(
            (_n_kind(layer_kinds(cfg), "S"),
             (win_blocks or num_blocks) * block_size,
             latent_row_lanes(cfg.swa_geometry())), dtype=dtype)
    return kv


# ---------------------------------------------------------------------------
# Shared layer body
# ---------------------------------------------------------------------------


def _q_proj(lp, hn, cfg: ModelConfig):
    """[N, D] -> (q_nope [N, H, dn], q_pe [N, H, dr], qr): qr is the
    normalised q-LoRA latent [N, q_lora_rank] (the deepseek_v32 indexer
    projects its queries from it), None with a plain q_proj."""
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    qa = None
    if cfg.q_lora_rank > 0:
        qa = rms_norm(mm(hn, lp["wq_a"]), lp["q_a_norm"], cfg.rms_norm_eps)
        if cfg.mla_lora_rescale:
            qa = qa * (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
        q = mm(qa, lp["wq_b"])
    else:
        q = mm(hn, lp["wq"])
    q = q.reshape(hn.shape[0], H, dn + dr)
    return q[..., :dn], q[..., dn:], qa


# ---------------------------------------------------------------------------
# deepseek_v32: the lightning indexer, the selection, the sparse attend
# ---------------------------------------------------------------------------

# the indexer's key LayerNorm (the model repository's LayerNorm default)
INDEX_NORM_EPS = 1e-6
# queries a prefill selects and attends for at a time: bounds the index
# scores [J, block, table] and the gathered rows [block, topk, W]
DSA_QUERY_BLOCK = 32


def apply_rope_half_split(x: jax.Array, positions: jax.Array,
                          inv_freq: jax.Array,
                          scaling: float = 1.0) -> jax.Array:
    """x [T, ..., d]: lane i pairs with lane i + d/2 (rotate_half, the
    NON-interleaved convention the indexer uses), angle pos·inv_freq[i]."""
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * scaling, jnp.sin(ang) * scaling
    for _ in range(x.ndim - 2):
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def _indexer_proj(lp, hn, qr, positions, cfg: ModelConfig):
    """→ (qI [N, J, dI], kI [N, dI], w [N, J] float32): the indexer's
    queries from the q-LoRA latent, this token's index key (LayerNorm with
    weight and bias, one head) and the per-head weights, already scaled by
    J^-0.5 · dI^-0.5. Rope turns the first qk_rope_head_dim lanes of qI
    and kI, half-split, with the main path's frequencies."""
    J, dI, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    inv_np, att = rope_params(cfg)
    inv = jnp.asarray(inv_np)
    qI = mm(qr, lp["idx_wq_b"]).reshape(hn.shape[0], J, dI)
    qI = jnp.concatenate(
        [apply_rope_half_split(qI[..., :dr], positions, inv, att),
         qI[..., dr:]], axis=-1)
    k = mm(hn, lp["idx_wk"]).astype(jnp.float32)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
         * lp["idx_k_norm_w"].astype(jnp.float32)
         + lp["idx_k_norm_b"].astype(jnp.float32))
    kI = jnp.concatenate(
        [apply_rope_half_split(k[..., :dr], positions, inv, att),
         k[..., dr:]], axis=-1).astype(hn.dtype)
    w = mm(hn, lp["idx_w"]).astype(jnp.float32) * (J ** -0.5 * dI ** -0.5)
    return qI, kI, w


def _index_scores(qI, w, keys) -> jax.Array:
    """I[n, s] = Σ_j w[n, j] · relu(qI[n, j]·keys[(n,) s]) in float32.
    keys: [S, dI] shared by every query, or [N, S, dI] one table each."""
    eq = "njd,sd->njs" if keys.ndim == 2 else "njd,nsd->njs"
    dots = jnp.einsum(eq, qI.astype(keys.dtype), keys,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("nj,njs->ns", w, jax.nn.relu(dots))


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["blocks"],
                   meta_fields=["bsz", "pool_blocks"])
@dataclasses.dataclass(frozen=True)
class TableSlots:
    """The pool rows of a block table's positions, kept as the arithmetic
    that gives them: position p lives in pool row
    ``blocks[..., p // bsz] * bsz + p % bsz``. ``pool_blocks`` bounds the
    ids (a static shape): with the table's length it decides whether a
    position and its block id share one 32-bit value in ``_select``."""
    blocks: jax.Array     # [..., M] layer-offset block ids
    bsz: int
    pool_blocks: int

    def ids(self) -> jax.Array:
        """[..., M * bsz]: the block id of every position (a broadcast)."""
        return jnp.repeat(self.blocks, self.bsz, axis=-1)

    def rows(self) -> jax.Array:
        """[..., M * bsz]: the pool row of every position (no gather)."""
        rows = (self.blocks[..., None] * self.bsz
                + jnp.arange(self.bsz, dtype=self.blocks.dtype))
        return rows.reshape(self.blocks.shape[:-1] + (-1,))


_U32 = jnp.uint32


def _order_bits(scores, live) -> jax.Array:
    """float32 scores → uint32 that order the same way (−0.0 equals +0.0,
    as a sort's comparator has them), 0 for a position that is not live;
    every live one is above 0. A live −inf or NaN counts as not live: the
    stable sort this replaced gave it the dead positions' key."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), _U32)
    up = jnp.where(bits >> 31 == 1, ~bits, bits | _U32(1 << 31))
    return jnp.where(live & (scores > -jnp.inf), up, _U32(0))


def _select(scores, live, topk: int, slots):
    """Exact top-k of the live positions, ties to the lower position (as a
    stable sort by score has them, and lax.top_k), without ordering the
    table by score: ``select_compact.compact_top_k`` finds the k-th
    largest score by a bisection on the scores' bits, takes the positions
    above it and the first ties in position order, and moves what
    describes the taken positions to the front (a Pallas kernel; it runs
    interpreted off the TPU). What moves holds no score: ONE uint32 per
    position, the position above and its block id below (``TableSlots``;
    the pool row comes back by arithmetic), where the two fit 32 bits;
    else, or where ``slots`` is a plain array of pool rows ([N, S] or
    [S]), the position and the row, two values. scores, live [N, S]. →
    (positions [N, k], valid [N, k], pool rows [N, k]) in position order,
    k = min(index_topk, table capacity); where fewer than k positions are
    live the tail is invalid (position 0, row 0)."""
    from ..attention import _on_tpu
    N, S = scores.shape
    k = min(topk, S)
    take = functools.partial(compact_top_k, _order_bits(scores, live), k=k,
                             interpret=not _on_tpu())
    pos = jnp.arange(S, dtype=_U32)
    packed = isinstance(slots, TableSlots)
    id_bits = slots.pool_blocks.bit_length() if packed else 32
    if (S - 1).bit_length() + id_bits <= 32:
        # ids stay under 2**id_bits − 1: no taken key is NOT_TAKEN
        key, = take((pos << id_bits | slots.ids().astype(_U32),))
        valid = key != NOT_TAKEN
        key = jnp.where(valid, key, _U32(0))
        at = (key >> id_bits).astype(jnp.int32)
        ids = (key & _U32((1 << id_bits) - 1)).astype(jnp.int32)
        return at, valid, ids * slots.bsz + at % slots.bsz
    at, rows = take((pos, slots.rows() if packed else slots))
    valid = at != NOT_TAKEN
    return jnp.where(valid, at, _U32(0)).astype(jnp.int32), valid, rows


def _attend_selected(q_lat, q_pe, kv_flat, slot_ids, valid, scale: float,
                     rank: int, dr: int) -> jax.Array:
    """Absorbed attention over the gathered latent rows only.
    q_lat [N, H, rank], q_pe [N, H, dr] float32; slot_ids [N, k] rows of
    kv_flat; valid [N, k]. → probs·c [N, H, rank] float32."""
    # every id is a live slot of the table: no out-of-range fill to build
    rows = jnp.take(kv_flat, slot_ids, axis=0, mode="clip")  # [N, k, W]
    c, k_pe = rows[..., :rank], rows[..., rank:rank + dr]
    f32 = jnp.float32
    scores = (jnp.einsum("nhr,nkr->nhk", q_lat.astype(rows.dtype), c,
                         preferred_element_type=f32)
              + jnp.einsum("nhd,nkd->nhk", q_pe.astype(rows.dtype), k_pe,
                           preferred_element_type=f32)) * scale
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("nhk,nkr->nhr", probs.astype(rows.dtype), c,
                      preferred_element_type=f32)


def _keys_by_block(idx_flat, tables_l, bsz: int) -> jax.Array:
    """The index keys of whole blocks: idx_flat [L*NTOK, dI] read as
    [L*NB, bsz, dI] (splitting the row axis costs no relayout; one
    bsz*dI-lane row per block would), tables_l [...] block ids →
    [..., bsz, dI]. A block is 4 KB at the published sizes: the read is
    by block, not by 256-byte row."""
    dI = idx_flat.shape[-1]
    return jnp.take(idx_flat.reshape(-1, bsz, dI), tables_l, axis=0,
                    mode="clip")


def _sparse_rows(q_lat, q_pe, index, kv_flat, tables_l, seq_lens,
                 cfg: ModelConfig, bsz: int, scale: float) -> jax.Array:
    """Select, then attend, for N query rows that each have a block table
    of their own (decode; tables_l [N, M] holds layer-offset block ids,
    seq_lens [N] the live positions). → probs·c [N, H, rank].

    On the TPU, at a geometry the kernel builds for, the index scores come
    from ONE Pallas call that streams each row's keys from the pool as it
    lies (engine/index_scores.py: a run of adjacent blocks is one copy);
    elsewhere (the CPU, tiny widths) the keys are gathered by block and
    scored by XLA, as a prefill chunk's one shared table always is."""
    from ..attention import _on_tpu
    qI, w, idx_flat = index
    N, M = tables_l.shape
    S, dI = M * bsz, idx_flat.shape[-1]
    with jax.named_scope("dsa_select"):
        if _on_tpu() and index_scores_supported(qI.shape[1], dI, bsz):
            scores = index_scores_pallas(qI, w, idx_flat, tables_l,
                                         seq_lens, block_size=bsz)
        else:
            keys = _keys_by_block(idx_flat, tables_l, bsz).reshape(N, S, dI)
            scores = _index_scores(qI, w, keys)
        live = jnp.arange(S)[None, :] < seq_lens[:, None]
        _, valid, slot_ids = _select(scores, live, cfg.index_topk,
                                     TableSlots(tables_l, bsz,
                                                kv_flat.shape[0] // bsz))
    with jax.named_scope("sparse_attention"):
        return _attend_selected(q_lat, q_pe, kv_flat, slot_ids, valid,
                                scale, cfg.kv_lora_rank,
                                cfg.qk_rope_head_dim)


def sparse_query_blocks(T: int, true_len):
    """→ (blocks, blocks_run): the DSA_QUERY_BLOCK-row query blocks of a
    T-row prefill chunk, and those that hold one of its ``true_len`` live
    rows: what ``_sparse_chunk`` runs. ``true_len`` is the host's int (the
    prefill flight record's ``dsa_blocks`` / ``dsa_blocks_run``) or the
    traced length inside the program: one arithmetic for both."""
    TQ = math.gcd(T, DSA_QUERY_BLOCK)
    blocks = T // TQ
    run = (true_len + TQ - 1) // TQ
    if isinstance(true_len, jax.Array):
        return blocks, jnp.clip(run, 0, blocks)
    return blocks, min(max(int(run), 0), blocks)


def _sparse_chunk(q_nope, q_pe, w_k, index, kv_flat, table_l, positions,
                  seq_len, cfg: ModelConfig, bsz: int,
                  scale: float) -> jax.Array:
    """Select, then attend, for the queries of one prefill chunk, which
    share one block table (table_l [M], layer-offset block ids), a block
    of DSA_QUERY_BLOCK queries at a time: the index scores of a block are
    [J, block, table] and the rows it gathers [block, topk, W]; nothing
    of size chunk × table × heads exists. Only the blocks that hold a live
    query (a position below seq_len) run: a chunk's padded tail selects
    and reads nothing, and its rows of the result are zero (the last live
    block's own padded rows are computed with it and discarded by the
    caller). → probs·c [T, H, rank]."""
    qI, w, idx_flat = index
    T, H = q_nope.shape[0], q_nope.shape[1]
    S, dI = table_l.shape[0] * bsz, idx_flat.shape[-1]
    keys = _keys_by_block(idx_flat, table_l, bsz).reshape(S, dI)
    kpos = jnp.arange(S)[None, :]
    slots = TableSlots(table_l, bsz, kv_flat.shape[0] // bsz)
    blocks, n_live = sparse_query_blocks(T, seq_len - positions[0])
    TQ = T // blocks

    def block(qn, qp, qi, wj, pos):
        with jax.named_scope("dsa_select"):
            live = (kpos <= pos[:, None]) & (kpos < seq_len)
            _, valid, slot_ids = _select(_index_scores(qi, wj, keys), live,
                                         cfg.index_topk, slots)
        with jax.named_scope("sparse_attention"):
            q_lat = jnp.einsum("thd,hrd->thr", qn.astype(jnp.float32),
                               w_k.astype(jnp.float32))
            return _attend_selected(q_lat, qp.astype(jnp.float32), kv_flat,
                                    slot_ids, valid, scale,
                                    cfg.kv_lora_rank, cfg.qk_rope_head_dim)

    xs = tuple(a.reshape((blocks, TQ) + a.shape[1:])
               for a in (q_nope, q_pe, qI, w, positions))

    def step(i, ctx):
        # the trip count is the traced n_live: a while loop on the device
        out = block(*(a[i] for a in xs))
        return jax.lax.dynamic_update_index_in_dim(ctx, out, i, 0)

    ctx = jax.lax.fori_loop(
        0, n_live, step,
        jnp.zeros((blocks, TQ, H, cfg.kv_lora_rank), jnp.float32))
    return ctx.reshape(T, H, cfg.kv_lora_rank)


def _latent_rows(lp, hn, positions, cfg: ModelConfig):
    """[N, D] -> latent cache rows [N, rank+rope]: post-norm c_kv with
    post-rope k_pe — the format every reader expands from."""
    ckv = mm(hn, lp["wkv_a"])
    c, k_pe = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c = rms_norm(c, lp["kv_norm"], cfg.rms_norm_eps)
    if cfg.mla_lora_rescale:
        c = c * (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
    if not cfg.mla_nope:     # kimi_linear: the pe lanes are plain key lanes
        inv, att = rope_params(cfg)
        k_pe = apply_rope_interleaved(k_pe, positions, jnp.asarray(inv), att)
    return jnp.concatenate([c, k_pe], axis=-1)


def _moe_mlp(hn, lp, cfg: ModelConfig, sharded: bool = True,
             valid_rows: Optional[jax.Array] = None,
             layer: Optional[jax.Array] = None) -> jax.Array:
    """deepseek routing, both generations (verified by the parity
    tests). v2 (HF DeepseekV2MoEGate): f32 softmax over ALL experts,
    greedy (or group-limited greedy) top-k of the SCORES without
    renormalization, scaled by routed_scaling. v3 (HF
    DeepseekV3TopkRouter, moe_routing == "sigmoid_noaux"): f32 sigmoid
    scores; expert CHOICE uses scores + e_score_correction_bias with
    groups selected by the sum of each group's top-2 corrected scores
    (masked groups ZEROED, matching masked_fill(0.0)); the mixing
    weights are the UNBIASED sigmoid scores of the chosen experts,
    renormalized over the top-k (+1e-20) when norm_topk_prob, then
    scaled. Shared experts are a plain additive swiglu either way.
    The experts run through llama.run_experts: dense over E or, on one
    device from llama.GROUPED_MIN_ROWS rows up, the routed pairs only
    (``sharded`` / ``valid_rows``: what its chooser needs; ``layer``:
    the expert stacks in ``lp`` are every layer's, read at this one)."""
    # E: the router's width — every published expert, of which this chip
    # may hold a share (cfg.num_experts_total; below)
    N, E = hn.shape[0], cfg.router_width
    logits = (hn.astype(jnp.float32)
              @ lp["router"].astype(jnp.float32))          # [N, E]
    if cfg.moe_routing == "sigmoid_noaux":
        scores = jax.nn.sigmoid(logits)
        choice = scores + lp["router_bias"][None, :].astype(jnp.float32)
        if cfg.n_group > 1:
            g = cfg.n_group
            top2, _i = jax.lax.top_k(choice.reshape(N, g, E // g), 2)
            gscore = top2.sum(axis=-1)                     # [N, g]
            _w, gidx = jax.lax.top_k(gscore, cfg.topk_group)
            gmask = jnp.sum(jax.nn.one_hot(gidx, g, dtype=choice.dtype),
                            axis=1)                        # [N, g]
            choice = (choice.reshape(N, g, E // g)
                      * gmask[..., None]).reshape(N, E)
        _cw, top_idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
        top_w = jnp.take_along_axis(scores, top_idx, axis=1)
        if cfg.moe_norm_topk:
            top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + 1e-20)
        top_w = top_w * cfg.routed_scaling
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        if cfg.n_group > 1:
            # group-limited greedy (DeepSeek-V2/-Chat): keep only the
            # topk_group groups with the best per-group max score
            g = cfg.n_group
            gmax = scores.reshape(N, g, E // g).max(axis=-1)  # [N, g]
            _w, gidx = jax.lax.top_k(gmax, cfg.topk_group)
            gmask = jnp.sum(jax.nn.one_hot(gidx, g, dtype=scores.dtype),
                            axis=1)                           # [N, g]
            scores = (scores.reshape(N, g, E // g)
                      * gmask[..., None]).reshape(N, E)
        top_w, top_idx = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        # NO renormalization: the HF-native reference never applies
        # norm_topk_prob (from_hf_config rejects true for deepseek_v2)
        top_w = top_w * cfg.routed_scaling
    if cfg.num_experts_total:
        # one chip's share: the choice and the weights above are over all
        # the published experts; the stacks hold num_experts of them.
        # in both forms of run_experts an index outside
        # [0, num_experts) computes nothing (the dense one-hot is zero
        # there, the grouped pair joins no group), so a chosen expert
        # that lives elsewhere adds nothing here — and nothing stands in
        # for it
        top_idx = top_idx - cfg.expert_share_index * cfg.num_experts
    out = run_experts(hn, lp.get("moe_gate"), lp.get("moe_up"),
                      lp["moe_down"], top_idx, top_w,
                      gateup_w=lp.get("moe_gateup"), sharded=sharded,
                      valid_rows=valid_rows, layer=layer)
    if cfg.shared_expert_size > 0:
        out = out + swiglu(hn, lp.get("sh_gate"), lp.get("sh_up"),
                           lp["sh_down"], cfg.hidden_act,
                           gateup_w=lp.get("sh_gateup"))
    return out


def _run_layers(params: Params, kv: KVCache, x: jax.Array,
                positions: jax.Array, slots: jax.Array, cfg: ModelConfig,
                attn_fn, experts_sharded: bool = True,
                valid_rows: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, KVCache]:
    """attn_fn(q_nope, q_pe, rows_new, kv_flat, lp, li) -> [N, H*v]; with
    an indexer (cfg.index_topk > 0) it is also given
    ``index=(qI, w, idx_flat)``: this layer's index queries and head
    weights, and the index-key cache flattened like kv_flat, with this
    chunk's keys already written.

    deepseek hybrid sparsity (first_k_dense): the layers run as a dense
    prefix and a MoE suffix, each its own lax.scan with the SAME attention
    body — the pools carry across both, with li addressing rows globally.
    Each scan slices only the stacks of its own layer kind (one stack per
    kind: dense_*, router / moe_* / sh_*); the attention stacks hold every
    layer, stay whole beside both scans, and the body reads layer li from
    them in place — the read lax.scan lowers its own operands to. Handing
    the scans ``stack[n][:k]`` / ``stack[n][k:]`` made XLA copy every
    attention weight of the model in every dispatch (measured, PR 36: 4.1
    of a 39.2 ms decode step at the DeepSeek-V3.2 widths).

    ``experts_sharded`` / ``valid_rows`` go to ``_moe_mlp`` (as in
    llama._run_layers: ModelStatics.sharded, a prefill's true_len)."""
    L = cfg.num_layers
    stack = _layer_stack(params)
    NTOK = kv["kv"].shape[1]
    inv_np, att = rope_params(cfg)
    inv = jnp.asarray(inv_np)
    dsa = cfg.index_topk > 0

    _ATTN = ("ln1", "ln2", "wq", "wq_a", "q_a_norm", "wq_b", "wkv_a",
             "kv_norm", "wkv_b", "wo", "idx_wq_b", "idx_wk",
             "idx_k_norm_w", "idx_k_norm_b", "idx_w")

    quantized = kv["kv"].dtype == jnp.int8
    k = cfg.first_k_dense if cfg.num_experts > 0 else 0
    attn_stacks = {n: stack[n] for n in _ATTN if n in stack}
    # two scans over one [L, ...] stack: it stays whole beside both and the
    # body reads it at li; a single scan slices it as its own operand
    whole_attn = attn_stacks if k > 0 else {}

    def make_layer(mlp_fn):
        def layer(carry, xs):
            h, pools = carry
            pool = pools["kv"]
            li = xs["i"]
            lp = {**xs["lp"], **jax.tree.map(
                lambda w: jax.lax.dynamic_index_in_dim(w, li, keepdims=False),
                whole_attn)}
            hn = rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
            q_nope, q_pe, qr = _q_proj(lp, hn, cfg)
            q_pe = apply_rope_interleaved(q_pe, positions, inv, att)
            rows = _latent_rows(lp, hn, positions, cfg)
            if quantized:
                # in-row (e, m) scales, one pair PER SECTION — the
                # RMSNormed c_kv and the unnormalized post-rope k_pe
                # must not share an absmax (10-50x magnitude skew on
                # real checkpoints would crush the latent's
                # resolution). Every reader dequantizes the same
                # encoding — the pool-reading attn paths gather these
                # rows back, and the sp ring round-trips its fresh rows
                # through the same encode/decode — so the current token
                # sees the same quantized latent later steps do
                enc = quantize_kv_rows_sections(
                    rows, (cfg.kv_lora_rank, cfg.qk_rope_head_dim))
            else:
                enc = rows.astype(pool.dtype)
            pad = pool.shape[2] - enc.shape[1]
            if pad:
                # 128-lane row alignment (latent_row_lanes); attn_fn
                # below must keep seeing the UNPADDED rows
                enc = jnp.pad(enc, ((0, 0), (0, pad)))
            pool = pool.at[li, slots, :].set(enc.astype(pool.dtype),
                                             mode="drop")
            pools = dict(pools, kv=pool)
            extra = {}
            if dsa:
                # the second row of the token: its index key, at the same
                # slot of the same block
                with jax.named_scope("indexer"):
                    qI, kI, w = _indexer_proj(lp, hn, qr, positions, cfg)
                idx = pools["idx"].at[li, slots, :].set(
                    kI.astype(pools["idx"].dtype), mode="drop")
                pools["idx"] = idx
                extra["index"] = (qI, w,
                                  idx.reshape(L * NTOK, idx.shape[2]))
            attn = attn_fn(q_nope, q_pe, rows,
                           pool.reshape(L * NTOK, pool.shape[2]), lp, li,
                           **extra)
            h = h + mm(attn, lp["wo"])
            hn2 = rms_norm(h, lp["ln2"], cfg.rms_norm_eps)
            h = h + mlp_fn(hn2, lp, li)
            return (h, pools), None
        return layer

    pools = dict(kv)
    if cfg.num_experts > 0:
        if k > 0:
            dense_lp = {"down": stack["dense_down"]}
            if "dense_gateup" in stack:   # fused (fuse_stacked_matmuls)
                dense_lp["gateup"] = stack["dense_gateup"]
            else:
                dense_lp.update({"gate": stack["dense_gate"],
                                 "up": stack["dense_up"]})
            (x, pools), _ = jax.lax.scan(
                make_layer(lambda hn, lp, _li: swiglu(
                    hn, lp.get("gate"), lp.get("up"), lp["down"],
                    cfg.hidden_act, gateup_w=lp.get("gateup"))),
                (x, pools),
                {"lp": dense_lp, "i": jnp.arange(k, dtype=jnp.int32)})
        moe_lp = {} if whole_attn else dict(attn_stacks)
        for n in ("router", "router_bias", "moe_gate", "moe_up",
                  "moe_down", "moe_gateup", "sh_gate", "sh_up",
                  "sh_down", "sh_gateup"):
            if n in stack:
                moe_lp[n] = stack[n]
        # experts that run grouped read their stacks whole, at the
        # layer's index among the expert layers (llama.split_expert_stacks)
        moe_lp, whole = split_expert_stacks(
            moe_lp, x.shape[0], cfg.num_experts_per_tok, experts_sharded)
        (x, pools), _ = jax.lax.scan(
            make_layer(lambda hn, lp, li: _moe_mlp(
                hn, {**lp, **whole}, cfg, sharded=experts_sharded,
                valid_rows=valid_rows,
                layer=li - k if whole else None)),
            (x, pools),
            {"lp": moe_lp, "i": jnp.arange(k, L, dtype=jnp.int32)})
    else:
        (x, pools), _ = jax.lax.scan(
            make_layer(lambda hn, lp, _li: swiglu(
                hn, lp.get("gate"), lp.get("up"), lp["down"],
                cfg.hidden_act, gateup_w=lp.get("gateup"))),
            (x, pools),
            {"lp": {**attn_stacks,
                    **{n: stack[n] for n in ("gate", "up", "down", "gateup")
                       if n in stack}},
             "i": jnp.arange(L, dtype=jnp.int32)})
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, pools


# ---------------------------------------------------------------------------
# dots3_note: window layers of a latent geometry of their own
# ---------------------------------------------------------------------------

# queries a prefill chunk's window layers attend at a time, against the
# block + window keys they can reach
SWA_QUERY_BLOCK = 256


def swa_ring_blocks(cfg: ModelConfig, bsz: int) -> int:
    """Window blocks a decoding sequence holds a layer: the window's, one
    more, ``rows_ahead`` of a drafter (HybridCacheLayout.ring_blocks)."""
    return -(-(cfg.swa_window + min(cfg.mtp_layers, 1)) // bsz) + 1


def _swa_tables(block_tables, M: int, R: int, doubled: bool):
    """The tables a dispatch carries → (the full-attention layers' [.., M],
    the window layers' part). The engine's prefill table is [2M] (the
    window pool's block of every logical block behind the paged pool's, 0
    where it was released), its decode tables [B, M + R] (logical block b
    at entry b % R). A plain [M] / [B, M] table names blocks of both pools
    by one id: what benchmark/selftest.py's ``greedy`` hands the engine's
    own programs for every family (an accepted benchmark file), so the
    format is told by the width, the one thing such a caller states."""
    width = block_tables.shape[-1]
    if M and width == (2 * M if doubled else M + R):
        return block_tables[..., :M], block_tables[..., M:]
    return block_tables, None


def _swa_ring_view(window: int, bsz: int, positions, tables_f, ring, R: int):
    """A decode step's window rows as ``paged_attention`` reads them (as
    sambay._ring_view): per row a table of R window-pool blocks, oldest
    first, so that the live window is one interval of it. The newest row
    sits at index n = (R - 1) * bsz + p % bsz; live: the last
    min(window, p + 1) positions. → (tables [B, R], seq_lens, win_lo)."""
    blk = positions // bsz
    j = jnp.arange(R, dtype=jnp.int32)
    if ring is not None:
        view = jnp.take_along_axis(ring, (blk[:, None] + 1 + j) % R, axis=1)
    else:
        at = blk[:, None] - (R - 1) + j
        view = jnp.where(at >= 0, jnp.take_along_axis(
            tables_f, jnp.clip(at, 0, tables_f.shape[1] - 1), axis=1), 0)
    newest = (R - 1) * bsz + positions % bsz
    return (view, newest + 1,
            newest - jnp.minimum(window, positions + 1))


def _swa_chunk(q_nope, q_pe, lp, win_flat, table_l, start_pos, seq_len,
               cfg: ModelConfig, window: int, bsz: int,
               scale: float) -> jax.Array:
    """Window attention of the T queries of one prefill chunk (query t at
    position start_pos + t reads the keys s with t - window < s <= t), in
    the expanded form: the rows [start_pos - window + 1, start_pos + T) are
    read from the window pool by their blocks (table_l: the layer-offset
    block of every logical block) and expanded through wkv_b ONCE, then a
    block of SWA_QUERY_BLOCK queries at a time attends the block + window
    keys it can reach: no [T, S] tensor exists. cfg: the window layers'
    geometry. → [T, H, dv] float32."""
    import math
    T, H = q_nope.shape[0], q_nope.shape[1]
    rank, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    cd, f32 = q_nope.dtype, jnp.float32
    W = win_flat.shape[-1]
    QB = math.gcd(T, SWA_QUERY_BLOCK)
    back = window - 1
    nb = (T + back) // bsz + 2           # covers the chunk, the window
    KB = QB + back + bsz                 # ... and a block's keys, misaligned
    b0 = jnp.maximum(start_pos - back, 0) // bsz
    ids = jax.lax.dynamic_slice(jnp.pad(table_l, (0, nb)), (b0,), (nb,))
    rows = jnp.take(win_flat.reshape(-1, bsz, W), ids, axis=0,
                    mode="clip").reshape(nb * bsz, W).astype(cd)
    w_k, w_v = (w.astype(cd) for w in _split_wkv_b(lp, cfg))
    c, k_pe = rows[:, :rank], rows[:, rank:rank + dr]
    k_nope = jnp.einsum("sr,hrd->hsd", c, w_k,
                        preferred_element_type=f32).astype(cd)
    v = jnp.einsum("sr,hrd->hsd", c, w_v,
                   preferred_element_type=f32).astype(cd)
    qn = jnp.moveaxis(q_nope, 1, 0).reshape(H, T // QB, QB, -1)
    qp = jnp.moveaxis(q_pe.astype(cd), 1, 0).reshape(H, T // QB, QB, -1)

    def block(i):
        q_lo = start_pos + i * QB                    # first query's position
        lo = jnp.clip(q_lo - back - b0 * bsz, 0, nb * bsz - KB)
        kn = jax.lax.dynamic_slice_in_dim(k_nope, lo, KB, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(k_pe, lo, KB, axis=0)
        vv = jax.lax.dynamic_slice_in_dim(v, lo, KB, axis=1)
        s_ = (jnp.einsum("htd,hsd->hts", qn[:, i], kn,
                         preferred_element_type=f32)
              + jnp.einsum("htd,sd->hts", qp[:, i], kp,
                           preferred_element_type=f32)) * scale
        kpos = (b0 * bsz + lo + jnp.arange(KB))[None, :]
        qpos = (q_lo + jnp.arange(QB))[:, None]
        mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos < seq_len)
        s_ = jnp.where(mask[None], s_, NEG_INF)
        # a padded query row reads nothing: its softmax is over NEG_INF
        # alone and its output is dropped with the row
        probs = jax.nn.softmax(s_, axis=-1)
        return jnp.einsum("hts,hsd->htd", probs.astype(cd), vv,
                          preferred_element_type=f32)

    out = jax.lax.map(block, jnp.arange(T // QB))    # [T/QB, H, QB, dv]
    return jnp.moveaxis(out, 1, 2).reshape(T, H, -1)


def walk_layer_kinds(params: Params, kv: KVCache, x: jax.Array,
                     cfg: ModelConfig, attend, experts_sharded: bool = True,
                     valid_rows: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, KVCache]:
    """The layers of a model of two attention geometries (dots3_note's two
    latent ones; models/mimo.py's two grouped-query ones), in the
    published order: the dense prefix unrolled, then ONE lax.scan over the
    periods of ``layer_types`` whose body holds a period's layers, then
    what a cut depth leaves of a last period. The program's size is that
    of one period, whatever the depth.

    Every stack stays whole beside the scan and is read at its layer in
    place (the rule of _run_layers): ln1 / ln2 at the layer's index, the
    expert stacks among the expert layers, and the attention stacks by
    ``attend``, which is the model's:

    attend(kind, hn, pools, ai) -> (what the attention block adds to the
    stream [N, D], pools): kind "F", "S" or "K", hn the layer's normed input,
    pools the cache arrays as the layers before left them, ai the layer's
    index among the layers of its kind (its row of that kind's stacks and
    of that kind's pool)."""
    stack = _layer_stack(params)
    k, period, n_periods, tail = layer_plan(cfg)
    kinds = layer_kinds(cfg)
    moe_all = {n: stack[n] for n in (
        "router", "router_bias", "moe_gate", "moe_up", "moe_down",
        "moe_gateup", "sh_gate", "sh_up", "sh_down", "sh_gateup")
        if n in stack}
    moe_lp, whole = split_expert_stacks(
        moe_all, x.shape[0], cfg.num_experts_per_tok, experts_sharded)
    dense_lp = {n[len("dense_"):]: stack[n] for n in stack
                if n.startswith("dense_")}

    def layer(h, pools, li, ai, kind, mlp):
        """li: the layer; ai: its index among the layers of its kind; mlp:
        what the layer's second sub-layer computes of its input."""
        ln = stack_at({"ln1": stack["ln1"], "ln2": stack["ln2"]}, li)
        if cfg.norm_on_output:
            # exaone_moe: each sub-layer reads the stream as it is, and its
            # OUTPUT is normed (ln1 / ln2) before it joins the stream
            delta, pools = attend(kind, h, pools, ai)
            h = h + rms_norm(delta, ln["ln1"], cfg.rms_norm_eps)
            return h + rms_norm(mlp(h), ln["ln2"], cfg.rms_norm_eps), pools
        hn = rms_norm(h, ln["ln1"], cfg.rms_norm_eps)
        delta, pools = attend(kind, hn, pools, ai)
        h = h + delta
        hn2 = rms_norm(h, ln["ln2"], cfg.rms_norm_eps)
        return h + mlp(hn2), pools

    def dense_mlp(hn2, li):
        lp = stack_at(dense_lp, li)
        return swiglu(hn2, lp.get("gate"), lp.get("up"), lp["down"],
                      cfg.hidden_act, gateup_w=lp.get("gateup"))

    def expert_mlp(hn2, mi):
        return _moe_mlp(hn2, {**stack_at(moe_lp, mi), **whole}, cfg,
                        sharded=experts_sharded, valid_rows=valid_rows,
                        layer=mi if whole else None)

    pools = dict(kv)
    for li in range(k):                  # the dense prefix, of either kind
        x, pools = layer(x, pools, li, _n_kind(kinds[:li], kinds[li]),
                         kinds[li], lambda hn2, li=li: dense_mlp(hn2, li))
    # a layer's index among its kind: those of its kind before the scan,
    # a period's worth for every period gone by, and its rank in the period
    before = {kd: _n_kind(kinds[:k], kd) for kd in ("F", "S", "K")}
    per = {kd: _n_kind(period, kd) for kd in before}

    def run(carry, li0, ai0, some_kinds):
        h, pools = carry
        seen = dict.fromkeys(before, 0)
        for j, kind in enumerate(some_kinds):
            h, pools = layer(h, pools, li0 + j, ai0[kind] + seen[kind], kind,
                             lambda hn2, j=j: expert_mlp(hn2, li0 + j - k))
            seen[kind] += 1
        return h, pools

    if n_periods:
        def body(carry, pi):
            return run(carry, k + pi * len(period),
                       {kd: before[kd] + pi * per[kd] for kd in per},
                       period), None
        (x, pools), _ = jax.lax.scan(
            body, (x, pools), jnp.arange(n_periods, dtype=jnp.int32))
    if tail:
        x, pools = run((x, pools), k + n_periods * len(period),
                       {kd: before[kd] + n_periods * per[kd] for kd in per},
                       tail)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, pools


def stack_at(tree, i):
    """Layer ``i`` of every stack in ``tree``, read in place."""
    return jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False), tree)


def _run_layers_mixed(params: Params, kv: KVCache, x: jax.Array,
                      positions: jax.Array, slots: jax.Array,
                      slots_s: jax.Array, cfg: ModelConfig, attn_fn,
                      attn_s_fn, experts_sharded: bool = True,
                      valid_rows: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, KVCache]:
    """``_run_layers`` for a model of two latent geometries (dots3_note):
    ``walk_layer_kinds`` with the latent attention block of each kind. The
    full-attention stack (``layers.<leaf>``) is read at the layer's index
    among the full layers, the window stack (``layers.swa_<leaf>``) among
    the window layers.

    attn_fn: as _run_layers gives it, with li the layer's index in the
    paged pool. attn_s_fn(q_nope, q_pe, win_flat, lp, si) -> [N, Hs*dv]:
    the window layers' read of kv["win"], whose rows for this dispatch go
    to ``slots_s``."""
    cfg_s = cfg.swa_geometry()
    stack = _layer_stack(params)
    NTOK = kv["kv"].shape[1]
    n_f = kv["kv"].shape[0]
    f_names = [n for n in stack if n in (
        "wq", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
        "wg", "idx_wq_b", "idx_wk", "idx_k_norm_w", "idx_k_norm_b", "idx_w")]
    s_names = [n for n in stack if n.startswith("swa_")]

    def gated(attn, hn, lp, heads):
        # the headwise gate: g_h = sigmoid(n(x)·Wg), o_h <- g_h · o_h
        g = jax.nn.sigmoid(mm(hn, lp["wg"]).astype(jnp.float32))
        N = attn.shape[0]
        return (attn.reshape(N, heads, -1).astype(jnp.float32)
                * g[..., None]).reshape(N, -1).astype(attn.dtype)

    def attend(kind, hn, pools, ai):
        if kind == "F":
            lp = stack_at({n: stack[n] for n in f_names}, ai)
            inv_np, att = rope_params(cfg)
            q_nope, q_pe, qr = _q_proj(lp, hn, cfg)
            q_pe = apply_rope_interleaved(q_pe, positions,
                                          jnp.asarray(inv_np), att)
            rows = _latent_rows(lp, hn, positions, cfg)
            pool = pools["kv"]
            enc = jnp.pad(rows.astype(pool.dtype),
                          ((0, 0), (0, pool.shape[2] - rows.shape[1])))
            pool = pool.at[ai, slots, :].set(enc, mode="drop")
            pools = dict(pools, kv=pool)
            extra = {}
            if cfg.index_topk > 0:
                with jax.named_scope("indexer"):
                    qI, kI, w = _indexer_proj(lp, hn, qr, positions, cfg)
                idx = pools["idx"].at[ai, slots, :].set(
                    kI.astype(pools["idx"].dtype), mode="drop")
                pools["idx"] = idx
                extra["index"] = (qI, w,
                                  idx.reshape(n_f * NTOK, idx.shape[2]))
            attn = attn_fn(q_nope, q_pe, rows,
                           pool.reshape(n_f * NTOK, pool.shape[2]), lp, ai,
                           **extra)
            heads = cfg.num_heads
        else:
            lp = {n[len("swa_"):]: w for n, w in
                  stack_at({n: stack[n] for n in s_names}, ai).items()}
            inv_np, att = rope_params(cfg_s)
            q_nope, q_pe, _qr = _q_proj(lp, hn, cfg_s)
            q_pe = apply_rope_interleaved(q_pe, positions,
                                          jnp.asarray(inv_np), att)
            rows = _latent_rows(lp, hn, positions, cfg_s)
            win = pools["win"]
            enc = jnp.pad(rows.astype(win.dtype),
                          ((0, 0), (0, win.shape[2] - rows.shape[1])))
            win = win.at[ai, slots_s, :].set(enc, mode="drop")
            pools = dict(pools, win=win)
            attn = attn_s_fn(q_nope, q_pe,
                             win.reshape(-1, win.shape[2]), lp, ai)
            heads = cfg_s.num_heads
        if cfg.attention_gate:
            attn = gated(attn, hn, lp, heads)
        return mm(attn, lp["wo"]), pools

    return walk_layer_kinds(params, kv, x, cfg, attend,
                            experts_sharded=experts_sharded,
                            valid_rows=valid_rows)



def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """What this engine asks for that the latent pool, the index-key cache,
    the expert share or the window layers' pool cannot carry yet: the
    refusal matrix of docs/dsa.md, read once at engine build. → the
    offending options, by name; empty = go."""
    e = engine_cfg
    bad = []
    if e.quantization.startswith("int4"):
        # int8 works (quant.py _LAYER_MATMULS carries the MLA names; wkv_b
        # deliberately stays full precision for the absorbed einsums)
        bad.append("--quantization int4 (int8 is integrated; the "
                   "grouped-int4 kernel's lane alignment and the hybrid "
                   "scans' slicing of packed rows are unvalidated for this "
                   "family)")
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        bad.append("a pp mesh (the latent pool has no per-stage form yet)")
    if cfg.index_topk > 0:
        checks = {
            "--ragged (ragged_forward has no selection step)":
                e.ragged_dispatch,
            "--spec-k (the verify program is not tested with the "
            "selection)": e.spec_k > 0,
            "--kv-quantization (index keys have no int8 encoding)":
                e.kv_quantization != "none",
            "--host-kv-blocks / --kv-disk-* / --kv-remote-* (the tiers "
            "ship latent rows only)": bool(
                e.host_kv_blocks or e.kv_disk_blocks or e.kv_remote_dir),
            "tp/sp/pp/ep/dp meshes (the index-key cache has no sharding "
            "rule)": mesh is not None or max(
                e.tp, e.sp, e.pp, e.ep, e.dp) > 1,
        }
        bad += [name for name, on in checks.items() if on]
    if cfg.num_experts_total and (mesh is not None or engine_cfg.ep > 1):
        bad.append("a mesh with an expert share (the share IS this chip's "
                   "part of an expert-parallel layer)")
    if cfg.has_swa_latent:
        # dots3_note: what cannot carry the window layers' second pool and
        # table (one list; an indexer's refusals above are a part of it)
        checks = {
            "--ragged (ragged_forward has no window layers)":
                e.ragged_dispatch,
            "--spec-k (the verify program has no window layers)":
                e.spec_k > 0,
            "--lane-prefill-max-tokens (a lane's rows take no window "
            "blocks)": e.lane_prefill_max_tokens > 0,
            "--decode-steps-per-dispatch > 1 (window blocks are taken and "
            "released a step at a time)": e.decode_steps_per_dispatch > 1,
            "--kv-quantization (window rows have no int8 encoding)":
                e.kv_quantization != "none",
            "--host-kv-blocks / --kv-disk-* / --kv-remote-* (the tiers "
            "ship the paged pool's rows only)": bool(
                e.host_kv_blocks or e.kv_disk_blocks or e.kv_remote_dir),
            "tp/sp/pp/ep/dp meshes (the window pool has no sharding "
            "rule)": mesh is not None or max(
                e.tp, e.sp, e.pp, e.ep, e.dp) > 1,
        }
        # an option the indexer's list names already is not named twice
        named = {b.split(" ", 1)[0] for b in bad}
        bad += [name for name, on in checks.items()
                if on and name.split(" ", 1)[0] not in named]
    return bad


def _split_wkv_b(lp, cfg: ModelConfig):
    """wkv_b [rank, H*(dn+v)] -> (w_k [H, rank, dn], w_v [H, rank, v])."""
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, H, dn + dv)
    return (jnp.moveaxis(w[..., :dn], 1, 0),
            jnp.moveaxis(w[..., dn:], 1, 0))


# ---------------------------------------------------------------------------
# Prefill: expand k/v from latent rows by key blocks, causal attention
# ---------------------------------------------------------------------------

# rows of the table a dense prefill chunk reads, expands and attends at a
# time (a whole number of the pool's blocks is taken): bounds what one call
# of the kernel streams and, off the kernel, the expanded keys and values
# [H, block, dn | dv] and the scores [H, chunk, block]
MLA_KEY_BLOCK = 2048


def _dense_chunk(q_nope, q_pe, lp, kv_flat, table_l, start_pos, seq_len,
                 cfg: ModelConfig, bsz: int, scale: float,
                 impl: str) -> jax.Array:
    """Causal attention of the T queries of one prefill chunk (query t at
    position start_pos + t) over the live rows of its table (table_l [M],
    layer-offset block ids; positions < seq_len), in the expanded form and
    by key blocks with a running max and sum (docs/mla_dense.md): each
    block of MLA_KEY_BLOCK rows is read from the pool by its blocks,
    expanded through wkv_b once a head (in the activations' dtype, float32
    accumulation), attended, and folded into the running state — on the
    TPU in ONE Pallas call a key block (engine/mla_prefill.py), elsewhere
    (the CPU, a mesh, widths off the lane tiling) the same three steps in
    XLA. Nothing of size heads × chunk × table or heads × table × head_dim
    exists, and the walk ends at the live length, not at the table's
    capacity. → [T, H, dv] float32."""
    from ..attention import _on_tpu
    T, H = q_nope.shape[0], q_nope.shape[1]
    rank, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    cd, f32 = q_nope.dtype, jnp.float32
    M, W = table_l.shape[0], kv_flat.shape[-1]
    nb = max(1, min(MLA_KEY_BLOCK // bsz, M))      # pool blocks a key block
    kernel = ("interpret" if impl == "pallas_interpret" else
              impl in ("auto", "pallas") and _on_tpu()
              and mla_prefill_supported(rank, dn, dr, dv))
    if kernel and nb * bsz > K_TILE:
        # whole row tiles of the kernel, where blocks divide a tile
        if K_TILE % bsz:
            kernel = False
        else:
            nb -= nb % (K_TILE // bsz)
    KB = nb * bsz
    table_l = jnp.pad(table_l, (0, -M % nb))       # the trash block: masked
    # head-major, the query rows on whole tiles of the kernel
    Tp = -(-T // Q_TILE) * Q_TILE if T > Q_TILE else -(-T // 8) * 8
    qn = jnp.pad(jnp.moveaxis(q_nope, 1, 0), ((0, 0), (0, Tp - T), (0, 0)))
    qp = jnp.pad(jnp.moveaxis(q_pe.astype(cd), 1, 0),
                 ((0, 0), (0, Tp - T), (0, 0)))
    w_k, w_v = (w.astype(cd) for w in _split_wkv_b(lp, cfg))
    pool = kv_flat.reshape(-1, bsz, W)

    def block(j, state):
        acc, ml = state
        ids = jax.lax.dynamic_slice(table_l, (j * nb,), (nb,))
        rows = jnp.take(pool, ids, axis=0, mode="clip").reshape(KB, W)
        if rows.dtype == jnp.int8:
            rows = dequant_kv_rows_sections(rows, (rank, dr), f32)
        rows = rows.astype(cd)
        # the block's frame: its first row is position 0
        q_lo, live = start_pos - j * KB, jnp.clip(seq_len - j * KB, 0, KB)
        if kernel:
            return mla_prefill_block(
                qn, qp, rows, w_k, w_v, acc, ml, q_lo=q_lo, live=live,
                scale=scale, rank=rank, dr=dr,
                interpret=(kernel == "interpret"))
        c, k_pe = rows[:, :rank], rows[:, rank:rank + dr]
        k_nope = jnp.einsum("sr,hrd->hsd", c, w_k,
                            preferred_element_type=f32).astype(cd)
        v = jnp.einsum("sr,hrd->hsd", c, w_v,
                       preferred_element_type=f32).astype(cd)
        s = (jnp.einsum("htd,hsd->hts", qn, k_nope,
                        preferred_element_type=f32)
             + jnp.einsum("htd,sd->hts", qp, k_pe,
                          preferred_element_type=f32)) * scale
        kpos = jnp.arange(KB)[None, :]
        mask = (kpos <= q_lo + jnp.arange(Tp)[:, None]) & (kpos < live)
        s = jnp.where(mask[None], s, NEG_INF)
        m, l = ml[..., 0], ml[..., 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a row with nothing to read yet: exp(NEG_INF - NEG_INF) is not 0
        p = jnp.where(mask[None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,hsd->htd", p.astype(cd), v, preferred_element_type=f32)
        return acc, jnp.stack([m_new, l * alpha + jnp.sum(p, axis=-1)], -1)

    acc, ml = jax.lax.fori_loop(
        0, (seq_len + KB - 1) // KB, block,
        (jnp.zeros((H, Tp, dv), f32),
         jnp.stack([jnp.full((H, Tp), NEG_INF, f32),
                    jnp.zeros((H, Tp), f32)], -1)))
    out = acc / jnp.maximum(ml[..., 1:], 1e-20)
    return jnp.moveaxis(out[:, :T], 0, 1)


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics,
                    layers=None) -> Tuple[jax.Array, KVCache]:
    """Same contract as llama.prefill_forward: tokens [T] (padded),
    block_table [M], returns (last-token logits [V], new kv). Supports a
    cached prefix (start_pos > 0 — chunked prefill / prefix reuse): the
    chunk's rows are scattered first and attention reads the live rows of
    the table back from the latent pool, a key block at a time
    (``_dense_chunk``; with an indexer, ``_sparse_chunk``)."""
    cfg, bsz = statics.cfg, statics.block_size
    T = tokens.shape[0]
    H = cfg.num_heads
    scale = softmax_scale(cfg)
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T) < true_len
    table_s = None
    if cfg.has_swa_latent:
        block_table, table_s = _swa_tables(
            block_table, statics.table_blocks, 0, doubled=True)
    slots = jnp.where(
        valid, block_table[positions // bsz] * bsz + positions % bsz, 0)
    seq_len = start_pos + true_len

    def attn(q_nope, q_pe, _rows, kv_flat, lp, li, index=None):
        # li: the layer's index in the pool (among the full-attention
        # layers, where the model has window layers too)
        NTOK = kv_flat.shape[0] // kv["kv"].shape[0]
        if index is not None:
            # deepseek_v32: select, then attend over the selected rows
            # only, in the absorbed form, a block of queries at a time
            w_k, w_v = _split_wkv_b(lp, cfg)
            ctx = _sparse_chunk(q_nope, q_pe, w_k, index, kv_flat,
                                block_table + li * (NTOK // bsz),
                                positions, seq_len, cfg, bsz, scale)
            out = jnp.einsum("thr,hrd->thd", ctx, w_v.astype(jnp.float32))
            return out.reshape(T, H * cfg.v_head_dim).astype(q_nope.dtype)
        with jax.named_scope("mla_prefill_attention"):
            # a Pallas call has no partitioning rule: over a mesh the key
            # blocks are attended by XLA
            out = _dense_chunk(q_nope, q_pe, lp, kv_flat,
                               block_table + li * (NTOK // bsz), start_pos,
                               seq_len, cfg, bsz, scale,
                               "xla" if statics.sharded
                               else statics.attn_impl)
        return out.reshape(T, H * cfg.v_head_dim).astype(q_nope.dtype)

    x = _embed(params, tokens, cfg)
    if cfg.has_swa_latent:
        cfg_s = cfg.swa_geometry()
        table_s = block_table if table_s is None else table_s
        slots_s = jnp.where(
            valid, table_s[positions // bsz] * bsz + positions % bsz, 0)
        win_blocks = kv["win"].shape[1] // bsz

        def attn_s(q_nope, q_pe, win_flat, lp, si):
            with jax.named_scope("swa_prefill_attention"):
                out = _swa_chunk(q_nope, q_pe, lp, win_flat,
                                 table_s + si * win_blocks, start_pos,
                                 seq_len, cfg_s, cfg.swa_window, bsz,
                                 softmax_scale(cfg_s))
            return out.reshape(T, -1).astype(q_nope.dtype)

        x, kv_new = _run_layers_mixed(
            params, kv, x, positions, slots, slots_s, cfg, attn, attn_s,
            experts_sharded=statics.sharded, valid_rows=true_len)
    else:
        # layers: a family that walks kinds of its own (kimi_linear) runs
        # them with this function's latent read
        x, kv_new = (layers or _run_layers)(
            params, kv, x, positions, slots, cfg, attn,
            experts_sharded=statics.sharded, valid_rows=true_len)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, cfg), kv_new


def prefill_forward_sp(params: Params, kv: KVCache, tokens: jax.Array,
                       block_table: jax.Array, true_len: jax.Array,
                       statics: ModelStatics, mesh
                       ) -> Tuple[jax.Array, KVCache]:
    """Sequence-parallel whole-prompt prefill: same contract as
    llama.prefill_forward_sp (start_pos fixed at 0; T divides the sp
    axis). The ring (parallel/ring_attention.ring_attention_mla) is the
    ABSORBED form lifted to prefill: queries drop into latent space
    once, the ICI hops move only the compressed [S/sp, rank+rope] row
    chunks (vs llama's per-head 2·KVH·Dh payload), the softmax
    accumulates in rank-space with the hop streamed in bounded
    sub-chunks (ring_attention.RING_SUB_CHUNK), and w_v applies once
    after the ring. Per-device state is the absorbed form's inherent
    O(T·H·rank / sp) for q_lat/acc; ring traffic is
    O(T·(rank+rope) / sp)."""
    from ...parallel.ring_attention import ring_attention_mla

    cfg, bsz = statics.cfg, statics.block_size
    if cfg.index_topk > 0:
        raise NotImplementedError(
            "sequence-parallel prefill has no selection step "
            "(deepseek_v32; docs/dsa.md)")
    T = tokens.shape[0]
    H = cfg.num_heads
    rank = cfg.kv_lora_rank
    dr = cfg.qk_rope_head_dim
    scale = softmax_scale(cfg)
    quantized = kv["kv"].dtype == jnp.int8
    positions = jnp.arange(T, dtype=jnp.int32)
    valid = positions < true_len
    slots = jnp.where(
        valid, block_table[positions // bsz] * bsz + positions % bsz, 0)

    def attn(q_nope, q_pe, rows, _kv_flat, lp, _li):
        if quantized:
            # int8-KV invariant (same as the pool-reading paths): this
            # chunk's attention must see exactly the rows decode will
            # read later — round-trip through the sectioned encoding
            rows = dequant_kv_rows_sections(
                quantize_kv_rows_sections(rows, (rank, dr)),
                (rank, dr), jnp.float32)
        w_k, w_v = _split_wkv_b(lp, cfg)
        q_lat = jnp.einsum("thd,hrd->thr", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        ctx = ring_attention_mla(
            q_lat, q_pe.astype(jnp.float32), rows.astype(jnp.float32),
            mesh, scale=scale, rank=rank, kv_len=true_len)
        out = jnp.einsum("thr,hrd->thd", ctx.astype(jnp.float32),
                         w_v.astype(jnp.float32))
        return out.reshape(T, H * cfg.v_head_dim).astype(q_nope.dtype)

    x = _embed(params, tokens, cfg)
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn)
    last = x[jnp.maximum(true_len - 1, 0)]
    return _logits(params, last, cfg), kv_new


# ---------------------------------------------------------------------------
# Decode: the ABSORBED form — attention reads only the latent rows
# ---------------------------------------------------------------------------


def ragged_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   row_slot: jax.Array, seq_starts: jax.Array,
                   seq_counts: jax.Array, sample_rows: jax.Array,
                   statics: ModelStatics, max_rows: int = 8,
                   sample_all_rows: bool = False
                   ) -> Tuple[jax.Array, KVCache]:
    """MLA form of llama.ragged_forward (same metadata contract): one
    ragged [TT] token batch serves prefill chunks and decode steps in
    one absorbed-attention dispatch. Per row this is decode_forward's
    math over row-expanded tables (bit-exact per row with MLA decode);
    on TPU the full-precision latent pool takes the sequence-grouped
    ragged kernel as MQA with v-aliases-k (one latent-row stream per
    sequence for ALL its rows). int8 latent pools keep the explicit
    gather + sectioned dequant of the decode fallback — the sectioned
    ragged-kernel mode exists (attention.ragged_paged_attention_pallas
    quant_sections) but is unwired here until it has device truth."""
    from ..attention import _on_tpu, ragged_supported

    cfg, bsz = statics.cfg, statics.block_size
    if cfg.index_topk > 0:
        raise NotImplementedError(
            "ragged dispatch has no selection step (deepseek_v32; "
            "docs/dsa.md)")
    TT = tokens.shape[0]
    H = cfg.num_heads
    rank, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    scale = softmax_scale(cfg)
    row_tables = jnp.take(block_tables, row_slot, axis=0)      # [TT, M]
    slots = (row_tables[jnp.arange(TT), positions // bsz] * bsz
             + positions % bsz)
    seq_lens = positions + 1
    quantized = kv["kv"].dtype == jnp.int8
    # the latent pool is MQA-shaped for the kernel: one "kv head" of
    # the full row width (decode_forward's MQA framing); unsupported
    # geometries / int8 rows fall back to the per-row paths, so a
    # forced impl never hard-fails here (decode_forward's leniency)
    W = kv["kv"].shape[2]
    ok = (not quantized and rank % 128 == 0
          and ragged_supported(H, 1, W, bsz, max_rows,
                               kv_dtype=kv["kv"].dtype))
    impl = statics.attn_impl
    use_kernel = False
    if ok:
        if impl == "auto":
            use_kernel = _on_tpu()
        elif impl == "pallas_interpret":
            use_kernel = "interpret"
        elif impl == "pallas":
            use_kernel = True
    if use_kernel:
        last_rows = seq_starts + jnp.maximum(seq_counts - 1, 0)
        seq_ctx = jnp.where(seq_counts > 0,
                            jnp.take(positions, last_rows) + 1, 0)

    def attn(q_nope, q_pe, _rows, kv_flat, lp, li):
        NTOK = kv_flat.shape[0] // cfg.num_layers
        num_blocks = NTOK // bsz
        tables_l = row_tables + li * num_blocks
        w_k, w_v = _split_wkv_b(lp, cfg)
        q_lat = jnp.einsum("bhd,hrd->bhr", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        if not quantized:
            vl = rank if rank % 128 == 0 else None
            qc = jnp.concatenate(
                [q_lat, q_pe.astype(jnp.float32),
                 jnp.zeros((TT, H, W - rank - dr), jnp.float32)],
                axis=-1).astype(kv_flat.dtype)
            if use_kernel:
                ctx = ragged_paged_attention_pallas(
                    qc, kv_flat, kv_flat,
                    block_tables + li * num_blocks, seq_starts,
                    seq_counts, seq_ctx, block_size=bsz, scale=scale,
                    max_rows=max_rows, v_lanes=vl,
                    coalesce=statics.kv_coalesce,
                    interpret=(use_kernel == "interpret"))
            else:
                from ..attention import paged_attention
                ctx = paged_attention(
                    qc, kv_flat, kv_flat, tables_l, seq_lens,
                    block_size=bsz, scale=scale,
                    impl=statics.attn_impl, kv_heads=1, v_lanes=vl,
                    coalesce=statics.kv_coalesce)
            ctx = ctx[..., :rank].astype(jnp.float32)
        else:
            idx = flat_token_indices(tables_l, bsz)
            T = idx.shape[1]
            rows = jnp.take(kv_flat, idx, axis=0)    # [TT, T, W]
            rows = dequant_kv_rows_sections(rows, (rank, dr),
                                            jnp.float32)
            c = rows[..., :rank]
            k_pe = rows[..., rank:rank + dr]
            scores = (jnp.einsum("bhr,btr->bht", q_lat, c)
                      + jnp.einsum("bhd,btd->bht",
                                   q_pe.astype(jnp.float32),
                                   k_pe)) * scale
            mask = jnp.arange(T)[None, :] < seq_lens[:, None]
            scores = jnp.where(mask[:, None, :], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bht,btr->bhr", probs, c)
        out = jnp.einsum("bhr,hrd->bhd", ctx,
                         w_v.astype(jnp.float32))
        return out.reshape(TT, H * cfg.v_head_dim).astype(q_nope.dtype)

    x = _embed(params, tokens, cfg)
    x, kv_new = _run_layers(params, kv, x, positions, slots, cfg, attn,
                            experts_sharded=statics.sharded)
    if sample_all_rows:
        # ragged×spec variant (llama.ragged_forward): per-row logits
        # for lockstep acceptance over speculative spans
        return _logits(params, x, cfg), kv_new             # [TT, V]
    sel = jnp.take(x, sample_rows, axis=0)                     # [S, D]
    return _logits(params, sel, cfg), kv_new


# rows of a DMA wave of the latent decode read: one 640-lane row serves
# every head, so a wave of the llama-path depth (16 blocks: 256 rows of
# block size 16) is consumed faster than the next is issued. 1,024 rows
# (1.3 MB a buffer) read 73% of the HBM peak at 8k-25k contexts where 256
# read 50% and 512 read 68% (PERF.md section 5, PR 37)
LATENT_WAVE_ROWS = 1024


def latent_wave_blocks(bsz: int) -> int:
    """The wave depth, in pool blocks, of the latent one-head form of the
    paged-attention kernel: never shallower than the kernel's own."""
    return max(ATTN_CHUNK_BLOCKS, LATENT_WAVE_ROWS // bsz)


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics,
                   layers=None) -> Tuple[jax.Array, KVCache]:
    """Same contract as llama.decode_forward: tokens [B], positions [B],
    block_tables [B, M] -> (logits [B, V], new kv).

    Absorption: scores_h = (q_nope_h W_k_h)·c + q_pe_h·k_pe and
    out_h = (probs·c) W_v_h — queries drop into latent space once per
    step, so the per-token HBM read is ONE (rank+rope)-lane row shared
    by all H heads (the serving win MLA exists for).

    Full-precision pools route through the SHARED paged-attention stack
    (attention.paged_attention) as MQA: the 128-aligned latent row
    (latent_row_lanes) is the single "kv head", the combined query
    [q_lat | q_pe | 0-pad] dots against whole rows (pad lanes are
    zeros on both sides), the pool serves as k AND v, and the output's
    first `rank` lanes ARE probs·c. On TPU that is the block-DMA
    Pallas kernel — the XLA row-gather measured ~27x the pure-bandwidth
    cost of the latent read at seq ≈1K (PERF.md). int8 pools take the
    kernel too on TPU (quant_sections: in-kernel per-section dequant +
    v-aliases-k, the rows stream ONCE at int8 width); the explicit
    gather + sectioned dequant remains the fallback (CPU, non-aligned
    ranks, attn_impl=xla)."""
    cfg, bsz = statics.cfg, statics.block_size
    B = tokens.shape[0]
    H = cfg.num_heads
    rank, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    scale = softmax_scale(cfg)
    ring = None
    if cfg.has_swa_latent:
        R = swa_ring_blocks(cfg, bsz)
        block_tables, ring = _swa_tables(
            block_tables, statics.table_blocks, R, doubled=False)
    slots = (block_tables[jnp.arange(B), positions // bsz] * bsz
             + positions % bsz)
    seq_lens = positions + 1

    def attn(q_nope, q_pe, _rows, kv_flat, lp, li, index=None):
        NTOK = kv_flat.shape[0] // kv["kv"].shape[0]
        num_blocks = NTOK // bsz
        tables_l = block_tables + li * num_blocks
        w_k, w_v = _split_wkv_b(lp, cfg)
        # absorb the k expansion into the query: [B, H, rank]
        q_lat = jnp.einsum("bhd,hrd->bhr", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        if index is not None:
            # deepseek_v32: the indexer picks the rows this step reads
            ctx = _sparse_rows(q_lat, q_pe.astype(jnp.float32), index,
                               kv_flat, tables_l, seq_lens, cfg, bsz,
                               scale)
        elif kv_flat.dtype != jnp.int8:
            from ..attention import paged_attention
            W = kv_flat.shape[-1]
            # Deliberate: the kernel dots q against pool rows in the
            # pool dtype, so the f32 query rounds to bf16 here (the XLA
            # fallback keeps f32 queries — scores differ in the last
            # bits). A mixed-precision kernel dot costs a second VREG
            # stream for no measured accuracy gain.
            qc = jnp.concatenate(
                [q_lat, q_pe.astype(jnp.float32),
                 jnp.zeros((B, H, W - rank - dr), jnp.float32)],
                axis=-1).astype(kv_flat.dtype)
            # v_lanes=rank: v IS the c section of each row — the kernel
            # skips the v-side DMA entirely (halving the latent stream)
            # and returns probs·c directly. Ranks that don't lane-align
            # (tiny test geometries) slice after instead
            vl = rank if rank % 128 == 0 else None
            with jax.named_scope("mla_decode_attention"):
                ctx = paged_attention(
                    qc, kv_flat, kv_flat, tables_l, seq_lens,
                    block_size=bsz, scale=scale, impl=statics.attn_impl,
                    kv_heads=1, v_lanes=vl,
                    coalesce=statics.kv_coalesce,
                    chunk_blocks=latent_wave_blocks(bsz))[..., :rank].astype(
                        jnp.float32)
        else:
            from ..attention import (_on_tpu, paged_attention_pallas,
                                     pallas_supported)
            Wq = -(-(rank + dr) // 128) * 128
            if (statics.attn_impl in ("auto", "pallas") and _on_tpu()
                    and rank % 128 == 0
                    and pallas_supported(H, 1, Wq, bsz,
                                         kv_dtype=jnp.int8)):
                # sectioned-int8 kernel mode: in-kernel per-section
                # dequant + v-aliases-k — the int8 row streams ONCE
                qc = jnp.concatenate(
                    [q_lat, q_pe.astype(jnp.float32),
                     jnp.zeros((B, H, Wq - rank - dr), jnp.float32)],
                    axis=-1).astype(jnp.bfloat16)
                ctx = paged_attention_pallas(
                    qc, kv_flat, kv_flat, tables_l, seq_lens,
                    block_size=bsz, scale=scale, v_lanes=rank,
                    quant_sections=(rank, dr),
                    coalesce=statics.kv_coalesce).astype(jnp.float32)
            else:
                idx = flat_token_indices(tables_l, bsz)
                T = idx.shape[1]
                rows = jnp.take(kv_flat, idx, axis=0)    # [B, T, W]
                rows = dequant_kv_rows_sections(rows, (rank, dr),
                                                jnp.float32)
                c = rows[..., :rank]
                k_pe = rows[..., rank:rank + dr]
                scores = (jnp.einsum("bhr,btr->bht", q_lat, c)
                          + jnp.einsum("bhd,btd->bht",
                                       q_pe.astype(jnp.float32),
                                       k_pe)) * scale
                mask = jnp.arange(T)[None, :] < seq_lens[:, None]
                scores = jnp.where(mask[:, None, :], scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1)
                ctx = jnp.einsum("bht,btr->bhr", probs, c)  # [B,H,rank]
        out = jnp.einsum("bhr,hrd->bhd", ctx,
                         w_v.astype(jnp.float32))        # [B, H, dv]
        return out.reshape(B, H * cfg.v_head_dim).astype(q_nope.dtype)

    x = _embed(params, tokens, cfg)
    if cfg.has_swa_latent:
        from ..attention import paged_attention
        cfg_s = cfg.swa_geometry()
        rank_s, dr_s = cfg_s.kv_lora_rank, cfg_s.qk_rope_head_dim
        view, view_len, view_lo = _swa_ring_view(
            cfg.swa_window, bsz, positions, block_tables, ring, R)
        # the newest row's block is the view's last entry
        slots_s = view[:, R - 1] * bsz + positions % bsz
        win_blocks = kv["win"].shape[1] // bsz
        vl = rank_s if rank_s % 128 == 0 else None
        # under jit so that the kernel is traced and lowered once for all
        # the window layers of a period, not once a layer: its waves unroll
        # in Python, 5 s of the host a trace at 64 slots x 34 blocks
        read_window = jax.jit(functools.partial(
            paged_attention, block_size=bsz, scale=softmax_scale(cfg_s),
            impl=statics.attn_impl, kv_heads=1, v_lanes=vl,
            coalesce=statics.kv_coalesce,
            chunk_blocks=max(ATTN_CHUNK_BLOCKS, R)))

        def attn_s(q_nope, q_pe, win_flat, lp, si):
            # the absorbed form over the window's rows: at most R blocks a
            # sequence, one 1,152-lane row for all heads (as attn above)
            w_k, w_v = _split_wkv_b(lp, cfg_s)
            q_lat = jnp.einsum("bhd,hrd->bhr", q_nope.astype(jnp.float32),
                               w_k.astype(jnp.float32))
            Ws = win_flat.shape[-1]
            qc = jnp.concatenate(
                [q_lat, q_pe.astype(jnp.float32),
                 jnp.zeros((B, cfg_s.num_heads, Ws - rank_s - dr_s),
                           jnp.float32)], axis=-1).astype(win_flat.dtype)
            with jax.named_scope("swa_decode_attention"):
                ctx = read_window(
                    qc, win_flat, win_flat, view + si * win_blocks,
                    view_len, win_lo=view_lo
                )[..., :rank_s].astype(jnp.float32)
            out = jnp.einsum("bhr,hrd->bhd", ctx, w_v.astype(jnp.float32))
            return out.reshape(B, -1).astype(q_nope.dtype)

        x, kv_new = _run_layers_mixed(
            params, kv, x, positions, slots, slots_s, cfg, attn, attn_s,
            experts_sharded=statics.sharded)
    else:
        x, kv_new = (layers or _run_layers)(
            params, kv, x, positions, slots, cfg, attn,
            experts_sharded=statics.sharded)
    return _logits(params, x, cfg), kv_new


# The door (models.module_for), with ``refusals`` above and llama's
# ``engine_cache`` and ``init_one_param`` imported: new functions go HERE,
# at the end (llama.py says why)

def prefill_counters(cfg: ModelConfig, bucket: int, rows: int,
                     prompt_len: int) -> dict:
    """Of a prefill of ``rows`` prompt rows, the last of ``prompt_len``, in
    ``bucket``-row dispatches. Without an indexer ``key_tokens``: Σ over
    the rows of the keys each attended (a dense prefill reads every earlier
    row). With one, the query blocks of the sparse attention's walk in one
    layer over the dispatches (``dsa_blocks``) and those that held a live
    row and ran (``dsa_blocks_run``): ``sparse_query_blocks``, what the
    program computes from ``true_len``."""
    if not cfg.index_topk:
        return {"key_tokens": (rows * (prompt_len - rows)
                               + rows * (rows + 1) // 2)}
    counts = [sparse_query_blocks(bucket, min(bucket, rows - lo))
              for lo in range(0, rows, bucket)]
    return {"dsa_blocks": sum(b for b, _ in counts),
            "dsa_blocks_run": sum(r for _, r in counts)}
