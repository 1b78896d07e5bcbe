"""SambaY decoder-hybrid-decoder (``model_type: phi4flash``,
Phi-4-mini-flash-reasoning; Ren et al., arXiv:2507.06607) in pure JAX,
with three kinds of per-sequence memory (docs/hybrid_cache.md).

Every layer: ``h += Mixer(LN(h)); h += MLP(LN'(h))`` with LayerNorm (weight
and bias), ``MLP(x) = (up * silu(gate)) @ W2``, ``[gate | up] = x @ W1``,
no positional encoding anywhere, logits ``LN_f(h) @ E^T`` (tied). The mixer
by layer index (``layer_kinds``; L layers, h = L/2 rounded down to even):

========  ==============================================================
l < h     even: ``mamba`` (Mamba-1); odd: ``window`` (differential
          attention over the last ``sliding_window`` keys)
l = h     ``export``: Mamba-1 that also hands its scan output BEFORE the
          ``silu(z)`` gate, ``m``, to the layers below
l = h+1   ``full``: differential attention over the whole context; its
          K and V are the model's only full-length cache
l > h+1   even: ``gmu`` (gated memory unit, ``(m * silu(a W_g)) W_o``);
          odd: ``cross`` (differential attention with its own queries
          over the ``full`` layer's K and V)
========  ==============================================================

**Differential attention as plain grouped-query attention.** Query heads
(2j, 2j+1) = (q1, q2), key heads (2i, 2i+1) = (k1, k2), value
``[v_2i | v_2i+1]`` (2*dh wide), pair j reads pair j // 2, and
``o_j = softmax(q1 k1^T / sqrt(dh)) v - lambda * softmax(q2 k2^T / ..) v``.
A cache row ``[KVH * dh]`` read as KVH/2 heads of 2*dh IS ``[k1 | k2]`` and
``[v_2i | v_2i+1]``; a query ``[q1 | 0]`` scores against k1 alone and
``[0 | q2]`` against k2 alone. So both maps are ordinary GQA with H query
heads over KVH/2 key/value heads of 2*dh = 128 lanes (query head 2j+s reads
head (2j+s) // 4 = j // 2) and the kernels the other families run
(``paged_attention``, ``flash_prefill``) serve it unchanged; the
subtraction, the per-pair RMSNorm and ``(1 - lambda_init)`` follow
(``_diff_combine``). The zeros double the score FLOPs of prefill and cost
decode nothing (it is bound by the cache read).

**Three kinds of memory** (``init_kv_cache``; keys of the cache dict):

* ``k`` / ``v`` ``[1, blocks * bs, KVH * dh]``: the ``full`` layer's rows,
  paged under the block table, read by that layer and by every ``cross``
  layer;
* ``win_k`` / ``win_v`` ``[window layers, slots, R, KVH * dh]``: a ring of
  ``R = (ceil(window / bs) + 1) * bs`` rows per slot and window layer;
  position p lives in ring row ``p % R``. Attention has no positional
  term, so a ring is read in any order: decode hands ``paged_attention``
  the slot's ring as a block table rotated to start at the oldest block,
  which makes the live window one interval (``_ring_view``); stale rows
  of a slot's predecessor fall outside it by position, so nothing is
  zeroed;
* ``ssm`` ``[state-space layers, slots8, N, Di]`` float32 and ``conv``
  ``[.., slots8, d_conv - 1, Di]``: one recurrent state per slot (slots
  rounded up to eights for ``ssm_step``; N on sublanes, channels on lanes:
  ``[Di, N]`` would pad 16 lanes to 128 in HBM). A step at position 0
  starts from zero, in prefill and in decode alike: that is the reset at
  admission. A decode row whose block table is the trash row (block 0:
  an empty slot, a slot sitting a dispatch out) is not live: its state,
  conv inputs and ring rows stay as they are.

``prefill_forward`` takes the slot as an argument; the engine's prefill
program keeps the other families' signature and reads it from behind the
block table's M entries (``[M + 1]``; a table of ``[M]`` is slot 0, which
is how hand-driven callers such as ``benchmark/selftest.py`` call it).

**Linear prefill** (the paper's): ``gmu`` and ``cross`` layers keep no
per-token state, so only the prompt's last position runs them; positions
before it stop after the ``full`` layer, whose rows they leave in the pool.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..attention import (NEG_INF, _on_tpu, flash_prefill,
                         flat_token_indices, paged_attention)
from ..config import ModelConfig
from ..quant import mm
from ..ssm import ssm_scan, ssm_step
from ..quant import QuantizedArray
from .llama import KVCache, ModelStatics, Params, _logits, seeded_std

KINDS = ("mamba", "window", "export", "full", "gmu", "cross")
_F32 = jnp.float32


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """The mixer of every layer, by index (module docstring). The published
    rule splits at L/2, which is even for the published depth; for a depth
    whose half is odd the split moves down to the even number below, so
    that a tiny model of 6 layers still has one layer of every kind."""
    half = (cfg.num_layers // 2) & ~1
    kinds = []
    for l in range(cfg.num_layers):
        if l < half:
            kinds.append("mamba" if l % 2 == 0 else "window")
        elif l <= half + 1:
            kinds.append("export" if l == half else "full")
        else:
            kinds.append("gmu" if l % 2 == 0 else "cross")
    return tuple(kinds)


def layers_of(cfg: ModelConfig, kind: str) -> Tuple[int, ...]:
    return tuple(l for l, k in enumerate(layer_kinds(cfg)) if k == kind)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ---------------------------------------------------------------------------
# Parameters: one stack per layer kind, ``layers.<kind>.<leaf>``, so that a
# scan over the pairs of a half takes whole arrays and nothing is sliced
# out of a stack inside a program
# ---------------------------------------------------------------------------

_ONES = ("ln1_w", "ln2_w", "subnorm")
_ZEROS = ("ln1_b", "ln2_b", "conv_b", "attn_qkv_b", "attn_out_b",
          "cross_q_b")


def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    D, F, Di = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_d_inner
    N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    dh = cfg.head_dim
    Hq, Hkv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    ssm = {"ssm_in": (D, 2 * Di), "conv_w": (K, Di), "conv_b": (Di,),
           "ssm_x": (Di, R + 2 * N), "ssm_dt": (R, Di), "dt_b": (Di,),
           "A_log": (N, Di), "D": (Di,), "ssm_out": (Di, D)}
    diff = {"attn_out": (Hq, D), "attn_out_b": (D,), "lam": (4, dh),
            "subnorm": (2 * dh,)}
    mixers = {
        "mamba": ssm, "export": ssm,
        "window": {"attn_qkv": (D, Hq + 2 * Hkv),
                   "attn_qkv_b": (Hq + 2 * Hkv,), **diff},
        "gmu": {"gmu_in": (D, Di), "gmu_out": (Di, D)},
        "cross": {"cross_q": (D, Hq), "cross_q_b": (Hq,), **diff},
    }
    mixers["full"] = mixers["window"]
    block = {"ln1_w": (D,), "ln1_b": (D,), "ln2_w": (D,), "ln2_b": (D,),
             "mlp_gateup": (D, 2 * F), "mlp_down": (F, D)}
    shapes: Dict[str, Tuple[int, ...]] = {
        "embed": (cfg.vocab_size, D), "final_norm": (D,),
        "final_norm_b": (D,)}
    for kind in KINDS:
        n = len(layers_of(cfg, kind))
        if n:
            for leaf, shape in {**block, **mixers[kind]}.items():
                shapes[f"layers.{kind}.{leaf}"] = (n,) + shape
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def init_one_param(cfg: ModelConfig, name: str, shape: tuple,
                   sub: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Seeded weights: the engine's rule (normal, fan_in^-0.5; norms 1,
    biases 0), with the state-space layer's published initialisation where
    a normal draw would make it degenerate: ``A_log = log(1..N)`` per
    channel, ``D = 1``, ``dt_b`` the inverse softplus of a log-uniform draw
    in [1e-3, 1e-1]; the lambda vectors normal at 0.1."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf in _ONES or name == "final_norm":
        return jnp.ones(shape, dtype)
    if leaf in _ZEROS or name == "final_norm_b":
        return jnp.zeros(shape, dtype)
    if leaf == "A_log":
        n = jnp.arange(1, shape[-2] + 1, dtype=_F32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    if leaf == "D":
        return jnp.ones(shape, _F32)
    if leaf == "dt_b":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(sub, shape, _F32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "lam":
        return 0.1 * jax.random.normal(sub, shape, _F32)
    fan_in = shape[-2] if len(shape) > 1 else shape[-1]
    return (jax.random.normal(sub, shape, _F32)
            * seeded_std(cfg, name, fan_in)).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    params: Params = {}
    for name, shape in param_shapes(cfg).items():
        key, sub = jax.random.split(key)
        params[name] = init_one_param(cfg, name, shape, sub, dtype)
    return params


def _stacks(params: Params) -> Dict[str, dict]:
    out: Dict[str, dict] = {k: {} for k in KINDS}
    for name, w in params.items():
        if name.startswith("layers."):
            _, kind, leaf = name.split(".")
            out[kind][leaf] = w
    return out


def _one(stack: dict) -> dict:
    """The single layer of a stack of one (``export``, ``full``)."""
    return jax.tree.map(lambda a: a[0], stack)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------


def ring_blocks(cfg: ModelConfig, block_size: int) -> int:
    """Blocks of a window layer's ring: the window and one more, so that
    the block being written never holds a row the window still needs."""
    return -(-cfg.sliding_window // block_size) + 1


def state_slots(max_num_seqs: int) -> int:
    return -(-max_num_seqs // 8) * 8


def init_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                  max_num_seqs: int, dtype=jnp.bfloat16) -> KVCache:
    """The three kinds (module docstring). ``k`` stays the first key:
    pool-agnostic code reads the first array as THE paged pool; the
    per-slot arrays have rank 4, which is how ``block_copy`` knows that
    they hold no blocks."""
    C = cfg.num_kv_heads * cfg.head_dim
    n_win = len(layers_of(cfg, "window"))
    n_ssm = len(layers_of(cfg, "mamba")) + 1
    R = ring_blocks(cfg, block_size) * block_size
    S8 = state_slots(max_num_seqs)
    Di = cfg.mamba_d_inner
    return {
        "k": jnp.zeros((1, num_blocks * block_size, C), dtype),
        "v": jnp.zeros((1, num_blocks * block_size, C), dtype),
        "win_k": jnp.zeros((n_win, max_num_seqs, R, C), dtype),
        "win_v": jnp.zeros((n_win, max_num_seqs, R, C), dtype),
        "ssm": jnp.zeros((n_ssm, S8, cfg.mamba_d_state, Di), _F32),
        "conv": jnp.zeros((n_ssm, S8, cfg.mamba_d_conv - 1, Di), dtype),
    }


def cache_layout(cfg: ModelConfig, block_size: int, dtype_bytes: int = 2):
    """What the block manager needs to know of the three kinds."""
    from ...llm.kv.hybrid import HybridCacheLayout
    Di = cfg.mamba_d_inner
    return HybridCacheLayout(
        block_size=block_size,
        row_bytes=2 * cfg.num_kv_heads * cfg.head_dim * dtype_bytes,
        paged_layers=1, readers_of_paged=1 + len(layers_of(cfg, "cross")),
        window_layers=len(layers_of(cfg, "window")),
        window=cfg.sliding_window,
        state_layers=len(layers_of(cfg, "mamba")) + 1,
        state_bytes=(4 * cfg.mamba_d_state * Di
                     + dtype_bytes * (cfg.mamba_d_conv - 1) * Di))


def state_refusals(engine_cfg, mesh) -> list:
    """What an engine asks for that cannot carry a slot's recurrent state:
    the ONE table of the stateful families (this one's Mamba state and
    window rings; ``models/kimi_linear.py``'s matrix state), the refusal
    matrix of docs/hybrid_cache.md. -> the offending options, by name."""
    e = engine_cfg
    checks = {
        "--ragged (ragged_forward has no state update)": e.ragged_dispatch,
        "--spec-k (a rejected draft would have advanced the state)":
            e.spec_k > 0,
        "--kv-quantization (per-slot rows and state have no int8 encoding)":
            e.kv_quantization != "none",
        "--host-kv-blocks / --kv-disk-* / --kv-remote-* (the tiers ship "
        "paged rows only; a block without the state at its boundary "
        "cannot be resumed)": bool(
            e.host_kv_blocks or e.kv_disk_blocks or e.kv_remote_dir),
        "tp/sp/pp/ep/dp meshes (the per-slot arrays have no sharding "
        "rule)": mesh is not None or max(e.tp, e.sp, e.pp, e.ep, e.dp) > 1,
    }
    return [name for name, on in checks.items() if on]


def refusals(cfg: ModelConfig, engine_cfg, mesh) -> list:
    """``state_refusals`` and what is this family's own, read once at
    engine build. -> the offending options, by name."""
    bad = state_refusals(engine_cfg, mesh)
    if engine_cfg.quantization.startswith("int4"):
        bad.append("--quantization int4 (the grouped-int4 kernels are "
                   "unvalidated for these projections)")
    return bad


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def layer_norm(x, w, b, eps: float):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y * w.astype(_F32) + b.astype(_F32)


# Precision (PERF.md section 6, PR 35). Between matmuls everything is
# float32: the residual stream, the norms' outputs, the state-space layer's
# inner path, every branch's output (the MXU's accumulator, kept:
# ``mm(..., out_dtype=float32)``). INTO a matmul a float32 activation goes
# as TWO bf16 rows, its leading bits and what they leave (``mm2``), so the
# MXU runs bf16 x int8-as-bf16 as for every other family, reads each weight
# once, and sees its input to about 16 bits. Cache rows, the queries and
# the attention kernels' outputs are bf16. Why: a perturbation that enters
# the first layers of this block reaches the logits amplified three- to
# tenfold (the first branch's output IS the stream of the next layers),
# and with bf16 inputs and a bf16 stream the served logits sat 0.12-0.25
# standard deviations from the float32 reference at 32 layers (0.20 on the
# chip), at the tolerance, where the llama block sits at 0.02-0.06.
def _act(lp):
    """The dtype activations enter a matmul in: the parameters' own."""
    return lp["ln1_w"].dtype


def mm2(x, w, act):
    """x [.., D] float32 @ w -> float32 (see "Precision" above). With
    float32 parameters (the CPU tests) it is the plain matmul."""
    if act == _F32:
        return mm(x, w, out_dtype=_F32)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    hi = x2.astype(act)
    lo = (x2 - hi.astype(_F32)).astype(act)
    y = mm(jnp.concatenate([hi, lo]), w, out_dtype=_F32)
    n = x2.shape[0]
    return (y[:n] + y[n:]).reshape(lead + (y.shape[-1],))


@jax.named_scope("swiglu")
def _swiglu(a, lp, hidden_act: str):
    if hidden_act != "silu":
        raise ValueError(f"unsupported hidden_act {hidden_act!r}")
    gu = mm2(a, lp["mlp_gateup"], _act(lp))
    F = gu.shape[-1] // 2
    return mm2(jax.nn.silu(gu[..., :F]) * gu[..., F:], lp["mlp_down"],
               _act(lp))


def _mlp(lp, x, cfg: ModelConfig):
    a = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.rms_norm_eps)
    return x + _swiglu(a, lp, cfg.hidden_act)


def _norm1(lp, x, cfg: ModelConfig):
    return layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.rms_norm_eps)


def _pad_queries(q, cfg: ModelConfig):
    """[..., H * dh] -> [..., H, 2 * dh]: head 2j as [q | 0], head 2j+1 as
    [0 | q] (module docstring)."""
    dh = cfg.head_dim
    q = q.reshape(q.shape[:-1] + (cfg.num_heads // 2, 2, 1, dh))
    eye = jnp.eye(2, dtype=q.dtype)[:, :, None]            # [s, half, 1]
    return (q * eye).reshape(q.shape[:-4] + (cfg.num_heads, 2 * dh))


def _lambda(lp) -> jax.Array:
    lam = lp["lam"].astype(_F32)
    return (jnp.exp(jnp.sum(lam[0] * lam[1]))
            - jnp.exp(jnp.sum(lam[2] * lam[3])))


def _diff_combine(o, lp, lam_init, cfg: ModelConfig):
    """[..., H, 2dh] attention outputs of the padded heads -> the mixer's
    output [..., D]: o1 - lambda o2 per pair, RMSNorm over the pair's 2dh,
    (1 - lambda_init), the output projection."""
    lam = _lambda(lp) + lam_init
    o = o.astype(_F32).reshape(o.shape[:-2] + (cfg.num_heads // 2, 2, -1))
    d = o[..., 0, :] - lam * o[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    d = d * lp["subnorm"].astype(_F32) * (1.0 - lam_init)
    return (mm2(d.reshape(d.shape[:-2] + (-1,)), lp["attn_out"], _act(lp))
            + lp["attn_out_b"].astype(_F32))


def _qkv(lp, a, cfg: ModelConfig):
    Hq = cfg.num_heads * cfg.head_dim
    Hkv = cfg.num_kv_heads * cfg.head_dim
    qkv = (mm2(a, lp["attn_qkv"], _act(lp))
           + lp["attn_qkv_b"].astype(_F32)).astype(_act(lp))
    return (_pad_queries(qkv[..., :Hq], cfg), qkv[..., Hq:Hq + Hkv],
            qkv[..., Hq + Hkv:])


def _paged(statics: ModelStatics, q, k_flat, v_flat, tables, seq_lens,
           win_lo=None):
    """``paged_attention`` at this family's geometry: KVH/2 heads of 2dh
    lanes, scores scaled by dh^-0.5."""
    return paged_attention(
        q, k_flat, v_flat, tables, seq_lens,
        block_size=statics.block_size, scale=statics.cfg.head_dim ** -0.5,
        impl=statics.attn_impl, win_lo=win_lo,
        coalesce=statics.kv_coalesce)


def _mamba_mix(lp, xc, z, y):
    """(scan output y, conv output xc, gate z; all float32) -> (the memory
    m = y + D xc, the mixer's output), float32."""
    m = y + lp["D"].astype(_F32) * xc
    return m, mm2(m * jax.nn.silu(z), lp["ssm_out"], _act(lp))


def _dt_b_c(lp, xc, cfg: ModelConfig):
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = mm2(xc, lp["ssm_x"], _act(lp))
    dt = jax.nn.softplus(mm2(dbc[..., :R], lp["ssm_dt"], _act(lp))
                         + lp["dt_b"].astype(_F32))
    return dt, dbc[..., R:R + N], dbc[..., R + N:]


def _conv(lp, taps):
    """taps: the d_conv inputs of every output row, oldest first, as a
    list of [..., Di]. -> silu(conv), float32."""
    w = lp["conv_w"].astype(_F32)
    acc = lp["conv_b"].astype(_F32)
    for k, t in enumerate(taps):
        acc = acc + w[k] * t.astype(_F32)
    return jax.nn.silu(acc)


@jax.named_scope("gmu")
def _gmu(lp, a, m):
    gate = jax.nn.silu(mm2(a, lp["gmu_in"], _act(lp)))
    return mm2(m * gate, lp["gmu_out"], _act(lp))


def _cross_rows(statics, lp, lam_init, a, k_flat, v_flat, tables,
                seq_lens):
    """A ``cross`` layer's mixer for rows [B, D] over the paged pool."""
    cfg = statics.cfg
    with jax.named_scope("cross_attention"):
        q = _pad_queries((mm2(a, lp["cross_q"], _act(lp))
                          + lp["cross_q_b"].astype(_F32)).astype(_act(lp)),
                         cfg)
        o = _paged(statics, q, k_flat, v_flat, tables, seq_lens)
        return _diff_combine(o, lp, lam_init, cfg)


def _cross_decoder(statics, st, x, m, k_flat, v_flat, tables, seq_lens):
    """The layers below the ``full`` one, for rows [B, D]: pairs of
    (gmu, cross), scanned."""
    cfg = statics.cfg
    if not st["gmu"]:
        return x
    lam_inits = jnp.asarray([lambda_init(l) for l in layers_of(cfg, "cross")],
                            _F32)

    def pair(x, xs):
        gp, cp, li = xs
        x = _mlp(gp, x + _gmu(gp, _norm1(gp, x, cfg), m), cfg)
        x = _mlp(cp, x + _cross_rows(statics, cp, li, _norm1(cp, x, cfg),
                                     k_flat, v_flat, tables, seq_lens), cfg)
        return x, None

    x, _ = jax.lax.scan(pair, x, (st["gmu"], st["cross"], lam_inits))
    return x


def _embed(params, tokens):
    """The embedding rows in float32 (int8: q * scale per row): what enters
    the first layer is amplified about tenfold on its way to the logits
    (the first branch's output, computed from it alone, IS the stream of
    the next layers), so it is not rounded to bf16 first."""
    emb = params["embed"]
    if isinstance(emb, QuantizedArray):
        return emb.q[tokens].astype(_F32) * emb.scale[tokens].astype(_F32)
    return emb[tokens].astype(_F32)


def _final(params, x, cfg: ModelConfig):
    x = layer_norm(x, params["final_norm"], params["final_norm_b"],
                   cfg.rms_norm_eps)
    return _logits(params, x.astype(params["final_norm"].dtype), cfg)


def _flat_caches(kv: KVCache):
    """The per-slot arrays as the layer scans carry them: the rings of all
    window layers as ONE flat pool ``[layers * slots * R, C]`` (layer l's
    rows at offset l * slots * R, as llama's pool is laid), the states as
    ``[layers * slots8, N, Di]``; the conv inputs as they are."""
    C = kv["win_k"].shape[-1]
    return (kv["win_k"].reshape(-1, C), kv["win_v"].reshape(-1, C),
            kv["ssm"].reshape((-1,) + kv["ssm"].shape[2:]), kv["conv"])


def _cache_like(kv: KVCache, k_flat, v_flat, wk, wv, ssm, conv) -> KVCache:
    return {"k": k_flat[None], "v": v_flat[None],
            "win_k": wk.reshape(kv["win_k"].shape),
            "win_v": wv.reshape(kv["win_v"].shape),
            "ssm": ssm.reshape(kv["ssm"].shape), "conv": conv}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _ring_view(cfg: ModelConfig, bsz: int, positions, n_slots: int):
    """The slots' rings as ``paged_attention`` reads them: per slot a block
    table (relative to the layer's first ring block) rotated to start at
    the ring's oldest block, and the one interval of it that is live.
    Position p sits in ring row p % R; with c the block that holds the
    newest position, the table runs c+1, c+2, ..., c (mod NB), so the
    newest row is at index n = (NB - 1) * bs + p % bs and position p - d at
    n - d. Live: the last min(window, p + 1) positions."""
    NB = ring_blocks(cfg, bsz)
    R = NB * bsz
    cur = (positions % R) // bsz
    rot = (cur[:, None] + 1 + jnp.arange(NB, dtype=jnp.int32)) % NB
    tables = jnp.arange(n_slots, dtype=jnp.int32)[:, None] * NB + rot
    newest = (NB - 1) * bsz + positions % bsz
    win_lo = newest - jnp.minimum(cfg.sliding_window, positions + 1)
    return tables, newest + 1, win_lo


def decode_forward(params: Params, kv: KVCache, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   statics: ModelStatics) -> Tuple[jax.Array, KVCache]:
    """Batched single-token decode step (llama.decode_forward's contract).
    Row b is slot b. A row aimed at the trash block is not live."""
    cfg, bsz = statics.cfg, statics.block_size
    B = tokens.shape[0]
    st = _stacks(params)
    live = block_tables[:, 0] > 0
    first = live & (positions == 0)
    interpret = not _on_tpu()
    n_win, _, R, _ = kv["win_k"].shape
    n_ssm, S8 = kv["ssm"].shape[:2]
    NB = R // bsz
    ring_tab, ring_len, ring_lo = _ring_view(cfg, bsz, positions, B)
    ring_row = jnp.where(live, jnp.arange(B) * R + positions % R,
                         n_win * B * R)            # not live: dropped
    pool_row = (block_tables[jnp.arange(B), positions // bsz] * bsz
                + positions % bsz)
    pad8 = lambda v: jnp.pad(v, ((0, S8 - B),) + ((0, 0),) * (v.ndim - 1))
    keep = pad8(jnp.where(first, 0.0, 1.0))
    live8 = pad8(live)

    def mamba(lp, x, ssm, conv, li):
        """One state-space layer for all slots; li: its row of the state
        arrays."""
        with jax.named_scope("mamba"):
            a = _norm1(lp, x, cfg)
            xi, z = jnp.split(mm2(a, lp["ssm_in"], _act(lp)), 2, axis=-1)
            with jax.named_scope("causal_conv"):
                prev = conv[li]                              # [S8, K-1, Di]
                taps = jnp.concatenate(
                    [prev.astype(_F32) * keep[:, None, None],
                     pad8(xi)[:, None, :]], axis=1)
                xc = _conv(lp, [taps[:B, k] for k in range(taps.shape[1])])
                conv = conv.at[li].set(jnp.where(
                    live8[:, None, None], taps[:, 1:].astype(prev.dtype),
                    prev))
            dt, Bm, Cm = _dt_b_c(lp, xc, cfg)
            dt = jnp.where(live[:, None], dt, 0.0)
            with jax.named_scope("ssm_step"):
                y, ssm = ssm_step(
                    pad8(dt), pad8(xc), keep, pad8(Bm), pad8(Cm),
                    -jnp.exp(lp["A_log"].astype(_F32)), ssm, li,
                    interpret=interpret)
            m, out = _mamba_mix(lp, xc, z, y[:B])
        return _mlp(lp, x + out, cfg), m, ssm, conv

    def self_pair(carry, xs):
        x, wk, wv, ssm, conv = carry
        mp, wp, li, lam_init = xs
        x, _, ssm, conv = mamba(mp, x, ssm, conv, li)
        with jax.named_scope("window_attention"):
            q, k, v = _qkv(wp, _norm1(wp, x, cfg), cfg)
            rows = jnp.where(live, ring_row + li * B * R, ring_row)
            wk = wk.at[rows].set(k, mode="drop")
            wv = wv.at[rows].set(v, mode="drop")
            o = _paged(statics, q, wk, wv, ring_tab + li * B * NB,
                       ring_len, ring_lo)
            x = x + _diff_combine(o, wp, lam_init, cfg)
        return (_mlp(wp, x, cfg), wk, wv, ssm, conv), None

    x = _embed(params, tokens)
    wk, wv, ssm, conv = _flat_caches(kv)
    if n_win:
        lam_w = jnp.asarray([lambda_init(l)
                             for l in layers_of(cfg, "window")], _F32)
        (x, wk, wv, ssm, conv), _ = jax.lax.scan(
            self_pair, (x, wk, wv, ssm, conv),
            (st["mamba"], st["window"], jnp.arange(n_win, dtype=jnp.int32),
             lam_w))
    x, m, ssm, conv = mamba(_one(st["export"]), x, ssm, conv,
                            jnp.int32(n_ssm - 1))
    fp = _one(st["full"])
    k_flat, v_flat = kv["k"][0], kv["v"][0]
    with jax.named_scope("full_attention"):
        q, k, v = _qkv(fp, _norm1(fp, x, cfg), cfg)
        # a row that is not live writes the trash block's first row
        k_flat = k_flat.at[pool_row].set(k)
        v_flat = v_flat.at[pool_row].set(v)
        o = _paged(statics, q, k_flat, v_flat, block_tables, positions + 1)
        x = x + _diff_combine(o, fp, lambda_init(layers_of(cfg, "full")[0]),
                              cfg)
    x = _mlp(fp, x, cfg)
    x = _cross_decoder(statics, st, x, m, k_flat, v_flat, block_tables,
                       positions + 1)
    kv_new = _cache_like(kv, k_flat, v_flat, wk, wv, ssm, conv)
    return _final(params, x, cfg), kv_new


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def _query_block(T: int) -> int:
    return next((t for t in (256, 128, 64, 32, 16, 8) if T % t == 0), T)


def _window_prefill(q, k_all, v_all, key_pos, positions, cfg: ModelConfig):
    """Banded attention of a chunk's queries [T, H, 2dh] over keys
    ``k_all`` [S, KVH*dh] (the ring's R rows before the chunk, then the
    chunk's T), whose positions are ``key_pos`` [S] (negative: no such
    row). Blocks of queries against the slice of keys their windows can
    reach: scores are [H, block, block + window], never [T, S]."""
    T, H, Dh = q.shape
    W = cfg.sliding_window
    KVH = k_all.shape[-1] // Dh
    g = H // KVH
    R = k_all.shape[0] - T
    tq = _query_block(T)
    span = tq + W
    lead = max(0, W - R)           # the first block may reach before row 0
    if lead:
        k_all, v_all = (jnp.pad(a, ((lead, 0), (0, 0)))
                        for a in (k_all, v_all))
        key_pos = jnp.pad(key_pos, (lead, 0), constant_values=-1)
    scale = cfg.head_dim ** -0.5

    def block(i):
        t0 = i * tq
        s0 = t0 + R + lead - W
        qb = jax.lax.dynamic_slice_in_dim(q, t0, tq).reshape(tq, KVH, g, Dh)
        kb = jax.lax.dynamic_slice_in_dim(k_all, s0, span).reshape(
            span, KVH, Dh)
        vb = jax.lax.dynamic_slice_in_dim(v_all, s0, span).reshape(
            span, KVH, Dh)
        kp = jax.lax.dynamic_slice_in_dim(key_pos, s0, span)
        qp = jax.lax.dynamic_slice_in_dim(positions, t0, tq)
        s = jnp.einsum("tkgd,skd->kgts", qb, kb).astype(_F32) * scale
        ok = ((kp[None, :] <= qp[:, None]) & (kp[None, :] > qp[:, None] - W)
              & (kp[None, :] >= 0))
        s = jnp.where(ok[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        return jnp.einsum("kgts,skd->tkgd", p, vb).reshape(tq, H, Dh)

    out = jax.lax.map(block, jnp.arange(T // tq))
    return out.reshape(T, H, Dh)


def _full_prefill(statics, q, ks, vs, positions, start_pos, seq_len):
    """The ``full`` layer's attention of a chunk over its whole table:
    the flash kernel on a TPU, dense scores elsewhere (tiny sizes)."""
    cfg = statics.cfg
    T, H, Dh = q.shape
    S = ks.shape[0]
    KVH = ks.shape[-1] // Dh
    scale = cfg.head_dim ** -0.5
    ks, vs = ks.reshape(S, KVH, Dh), vs.reshape(S, KVH, Dh)
    impl = statics.attn_impl
    if impl in ("pallas", "pallas_interpret") or (impl == "auto"
                                                  and _on_tpu()):
        return flash_prefill(q, ks, vs, scale=scale, start_pos=start_pos,
                             seq_len=seq_len,
                             interpret=impl == "pallas_interpret")
    g = H // KVH
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, KVH, g, Dh),
                   ks).astype(_F32) * scale
    kp = jnp.arange(S, dtype=jnp.int32)
    ok = (kp[None, :] <= positions[:, None]) & (kp[None, :] < seq_len)
    s = jnp.where(ok[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vs.dtype)
    return jnp.einsum("kgts,skd->tkgd", p, vs).reshape(T, H, Dh)


def prefill_forward(params: Params, kv: KVCache, tokens: jax.Array,
                    block_table: jax.Array, start_pos: jax.Array,
                    true_len: jax.Array, statics: ModelStatics,
                    slot=0) -> Tuple[jax.Array, KVCache]:
    """Single-sequence (chunk) prefill, llama.prefill_forward's contract,
    plus ``slot``: whose state, conv inputs and ring rows these are (the
    engine's prefill program takes it from behind the block table's M
    entries). ``start_pos`` 0 starts from the zero state; a later chunk
    continues from what the slot holds. The state written is the one after
    ``true_len`` tokens, whatever the bucket's padding."""
    cfg, bsz = statics.cfg, statics.block_size
    T = tokens.shape[0]
    st = _stacks(params)
    n_win, B, R, _ = kv["win_k"].shape
    n_ssm, S8 = kv["ssm"].shape[:2]
    slot = jnp.asarray(slot, jnp.int32)
    interpret = not _on_tpu()
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.arange(T, dtype=jnp.int32) < true_len
    seq_len = start_pos + true_len
    fresh = start_pos == 0
    pool_row = jnp.where(
        valid, block_table[positions // bsz] * bsz + positions % bsz, 0)
    # the ring keeps the chunk's last R valid positions (each row once)
    ring_row = jnp.where(valid & (positions >= seq_len - R),
                         slot * R + positions % R, n_win * B * R)
    prev_pos = start_pos - R + jnp.arange(R, dtype=jnp.int32)
    prev_row = slot * R + prev_pos % R
    key_pos = jnp.concatenate(
        [prev_pos, jnp.where(valid, positions, -1)])
    K1 = cfg.mamba_d_conv - 1

    def mamba(lp, x, ssm, conv, li):
        with jax.named_scope("mamba"):
            a = _norm1(lp, x, cfg)
            xi, z = jnp.split(mm2(a, lp["ssm_in"], _act(lp)), 2, axis=-1)
            with jax.named_scope("causal_conv"):
                prev = jnp.where(fresh, 0, conv[li, slot]).astype(_F32)
                xx = jnp.concatenate([prev, xi])             # [K-1+T, Di]
                xc = _conv(lp, [xx[k:k + T] for k in range(K1 + 1)])
                conv = conv.at[li, slot].set(
                    jax.lax.dynamic_slice_in_dim(xx, true_len, K1).astype(
                        conv.dtype))
            dt, Bm, Cm = _dt_b_c(lp, xc, cfg)
            dt = jnp.where(valid[:, None], dt, 0.0)
            row = li * S8 + slot
            h0 = jnp.where(fresh, 0.0, ssm[row])
            with jax.named_scope("ssm_scan"):
                y, h = ssm_scan(dt, xc, Bm, Cm,
                                -jnp.exp(lp["A_log"].astype(_F32)), h0,
                                interpret=interpret)
            ssm = ssm.at[row].set(h)
            m, out = _mamba_mix(lp, xc, z, y)
        return _mlp(lp, x + out, cfg), m, ssm, conv

    def self_pair(carry, xs):
        x, wk, wv, ssm, conv = carry
        mp, wp, li, lam_init = xs
        x, _, ssm, conv = mamba(mp, x, ssm, conv, li)
        with jax.named_scope("window_attention"):
            q, k, v = _qkv(wp, _norm1(wp, x, cfg), cfg)
            base = li * B * R
            k_all = jnp.concatenate([wk[prev_row + base], k])
            v_all = jnp.concatenate([wv[prev_row + base], v])
            o = _window_prefill(q, k_all, v_all, key_pos, positions, cfg)
            rows = jnp.where(ring_row < n_win * B * R, ring_row + base,
                             ring_row)
            wk = wk.at[rows].set(k, mode="drop")
            wv = wv.at[rows].set(v, mode="drop")
            x = x + _diff_combine(o, wp, lam_init, cfg)
        return (_mlp(wp, x, cfg), wk, wv, ssm, conv), None

    x = _embed(params, tokens)
    wk, wv, ssm, conv = _flat_caches(kv)
    if n_win:
        lam_w = jnp.asarray([lambda_init(l)
                             for l in layers_of(cfg, "window")], _F32)
        (x, wk, wv, ssm, conv), _ = jax.lax.scan(
            self_pair, (x, wk, wv, ssm, conv),
            (st["mamba"], st["window"], jnp.arange(n_win, dtype=jnp.int32),
             lam_w))
    x, m, ssm, conv = mamba(_one(st["export"]), x, ssm, conv,
                            jnp.int32(n_ssm - 1))
    fp = _one(st["full"])
    k_flat, v_flat = kv["k"][0], kv["v"][0]
    with jax.named_scope("full_attention"):
        q, k, v = _qkv(fp, _norm1(fp, x, cfg), cfg)
        k_flat = k_flat.at[pool_row].set(k)
        v_flat = v_flat.at[pool_row].set(v)
        idx = flat_token_indices(block_table[None, :], bsz)[0]
        o = _full_prefill(statics, q, jnp.take(k_flat, idx, axis=0),
                          jnp.take(v_flat, idx, axis=0), positions,
                          start_pos, seq_len)
        x = x + _diff_combine(o, fp, lambda_init(layers_of(cfg, "full")[0]),
                              cfg)
    x = _mlp(fp, x, cfg)
    # linear prefill: only the last position runs the layers below
    last = jnp.maximum(true_len - 1, 0)
    x = _cross_decoder(statics, st, x[last][None], m[last][None], k_flat,
                       v_flat, block_table[None, :], seq_len[None])
    kv_new = _cache_like(kv, k_flat, v_flat, wk, wv, ssm, conv)
    return _final(params, x[0], cfg), kv_new


# The door (models.module_for), with ``refusals`` above: new functions go
# HERE, at the end (llama.py says why)

def engine_cache(cfg: ModelConfig, engine_cfg, dtype, kv_shards: int = 1):
    """-> (kv, layout, win_blocks) as ``llama.engine_cache``:
    ``--num-kv-blocks`` sizes the paged pool, ``--max-num-seqs`` the rings
    and the recurrent state; no window-pool blocks (the window rows are
    per-slot rings) and, every mesh refused, one shard."""
    e = engine_cfg
    kv = init_kv_cache(cfg, e.num_kv_blocks, e.kv_block_size,
                       e.max_num_seqs, dtype=dtype)
    return kv, cache_layout(cfg, e.kv_block_size,
                            jnp.dtype(dtype).itemsize), 0


def prefill_counters(cfg: ModelConfig, bucket: int, rows: int,
                     prompt_len: int) -> dict:
    """The prompt tokens a state-space scan ran over."""
    return {"scan_tokens": rows}
