"""The plain reference of the DeepSeek-V3.2 block (MLA with the q-LoRA pair,
the lightning indexer and its top-k selection, the v3 ``noaux_tc`` MoE): the
forward pass only. The comparison and its tolerance are
``reference.compare`` / ``reference.TOL_STD``, the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no
absorbed form, no scan over layers, no fused layout. It follows the model
repository's own ``inference/model.py`` (``MLA``, ``Indexer``, ``Gate``,
``MoE``) as the writer knows it — there is no network here, so every line is
stated, for a reader who has the file to check. With H heads,
dn = ``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``,
rank = ``kv_lora_rank``, J = ``index_n_heads``, dI = ``index_head_dim``,
K = ``index_topk``:

    h = embed[tokens]
    per layer:
      a      = RMSNorm(h)
      qr     = RMSNorm(a·Wq_a)                     (the q-LoRA latent)
      q      = qr·Wq_b → [T, H, dn+dr] = q_nope | q_pe
      kv     = a·Wkv_a → [T, rank+dr]
      c      = RMSNorm(kv[:rank]; kv_norm)         (the latent)
      k_pe   = rope(kv[rank:])                     (one head, shared by all)
      c·Wkv_b → [T, H, dn+dv] = k_nope | v
      q_pe   = rope(q_pe)
      rope:  pairs (2i, 2i+1) rotated by pos·inv_freq[i] (INTERLEAVED); yarn
             frequencies as in references/deepseek_v2.py; cos and sin times
             mscale(factor, mscale) / mscale(factor, mscale_all_dim) (= 1 at
             the published 1 / 1)
      indexer:
        qI   = qr·WqI_b → [T, J, dI]
        kI   = LayerNorm(a·WkI; weight, bias, eps 1e-6) → [T, dI], one head
        rope on the FIRST dr lanes of qI and kI, HALF-SPLIT (lane i with
             lane i + dr/2), the same frequencies
        w    = a·Ww · J^-0.5 · dI^-0.5 → [T, J]
        I[t, s] = Σ_j w[t, j] · relu(qI[t, j]·kI[s])        for s ≤ t
        S_t  = the indices of the min(K, t+1) largest I[t, s], exact
      s      = (q_nope·k_nope + q_pe·k_pe) · (dn+dr)^-0.5 · mscale(factor,
               mscale_all_dim)², softmax over s ∈ S_t only
      h     += (softmax(s)·v) · Wo
      m      = RMSNorm(h)
      layer < first_k_dense_replace:
              h += (silu(m·Wg) * (m·Wu)) · Wd      at intermediate_size
      else:   p = sigmoid(m·Wr) over ALL published experts (float32)
              choice = p + e_score_correction_bias; per group the sum of its
              two best; the topk_group best groups keep their choice, the
              rest are set to 0; the num_experts_per_tok best of that
              weights = p of the chosen (not choice), divided by their sum
              (norm_topk_prob), times routed_scaling_factor
              h += Σ_{chosen e held here} w_e · expert_e(m) + shared(m)
    logits = RMSNorm(h) · W_head

**The expert share.** ``n_routed_experts`` counts the experts held here,
``n_routed_experts_published`` the router's width, ``expert_share_index``
which share this is: the experts [index·held, (index+1)·held). The router, its bias, the groups, the top-k and
the renormalisation are over all the published experts; what a chosen expert
that lives elsewhere would add is left out, as in the program, and that
partial result goes on to the next layer. The shared expert is whole.

It reads the engine's own parameter tree (``mla.param_shapes`` names; int8 as
q·scale; gate|up split where ``fuse_stacked_matmuls`` joined them). It is
blocked so that a 16k-token prompt fits beside the engine: queries in blocks
(index scores, top-k and the attention's scores never span more than a block
of queries), heads in groups, one expert and one slice of the dense MLP at a
time, one layer at a time. The main attention computes every causal score of
a block of queries and softmaxes over the selected ones: the same numbers as
gathering the selected keys, with nothing of size T × K × H kept.

It refuses what it does not compute: a ``model_type`` other than
``deepseek_v32``, no ``q_lora_rank``, ``scoring_func`` other than ``sigmoid``,
``topk_method`` other than ``noaux_tc``, ``attention_bias``, rope scaling
other than yarn.

**Forcing the selection** (``forward(..., forced=...)``,
``references/deepseek_v32_check.py``). The top-k is a discontinuous step: two
correct computations in different precisions disagree on the members nearest
its threshold, and with random weights the attention's output then differs by
the share of members that changed, whatever else agrees. Given the sets S_t
another implementation chose (per layer, [T, k] key indices, -1 = none), the
attention reads those, and the layer also counts how many of them this
module's own selection (and each ``variants`` selection) picked too: the
selection and everything after it are then held apart.

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
lower stated precision). ``fp8_cache`` rounds what the program caches in bf16
(the latent c, the roped k_pe and the index key kI) to float8_e4m3fn, the
precision the published deployment caches in. ``logits_for(...,
precision="default")`` runs every matmul in the device's default precision
(bf16 passes on a TPU) instead of float32.

Departures from the published code, each shared with the program:
- index keys and queries are compared in the precision stated here
  (float32); the published code rounds both to FP8 with per-row scales;
- the Hadamard rotation of qI and kI is left out: it is orthogonal, so
  qI·kI is unchanged in exact arithmetic, and exists for the FP8 rounding;
- the multi-token-prediction module (layer 61) is not run, as in the
  published inference code;
- weights are the int8-rounded ones the engine holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_shared_expert", "unit_routing_weights",
             "no_kv_norm", "half_split_rope", "k_pe_unrotated",
             "no_selection", "recent_window", "index_no_relu",
             "index_unweighted", "index_rope_interleaved",
             "v2_softmax_router")

CONTROLS = ("fp8_cache",)

INDEX_NORM_EPS = 1e-6
# queries whose index scores [J, block, T] and attention scores
# [heads of a group, block, T] exist at once, heads a group holds, and the
# width of a dense MLP's slice
QUERY_BLOCK = 64
HEAD_GROUP = 16
MLP_SLICE = 2048


def breakages_for(hf: dict) -> tuple:
    """All of them: every v3.2 configuration has experts and an indexer."""
    family(hf)
    return BREAKAGES


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "deepseek_v32":
        raise ValueError(f"the deepseek_v32 reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "q_lora_rank (none)": not hf.get("q_lora_rank"),
        "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
        "n_routed_experts (none)": not hf.get("n_routed_experts"),
    }
    if any(refused.values()):
        raise ValueError("the deepseek_v32 reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    held = int(hf["n_routed_experts"])
    return {
        "layers": int(hf["num_hidden_layers"]),
        "heads": int(hf["num_attention_heads"]),
        "rank": int(hf["kv_lora_rank"]),
        "dn": int(hf["qk_nope_head_dim"]), "dr": int(hf["qk_rope_head_dim"]),
        "dv": int(hf["v_head_dim"]),
        "eps": float(hf["rms_norm_eps"]),
        "J": int(hf["index_n_heads"]), "dI": int(hf["index_head_dim"]),
        "topk": int(hf["index_topk"]),
        "held": held,
        "experts": int(hf.get("n_routed_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_tok"]),
        "groups": int(hf.get("n_group") or 1),
        "top_groups": int(hf.get("topk_group") or 1),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "first_dense": int(hf.get("first_k_dense_replace") or 0),
        "shared": (int(hf.get("n_shared_experts") or 0)
                   * int(hf["moe_intermediate_size"])),
        "routed_scaling": float(hf.get("routed_scaling_factor") or 1.0),
    }


def _fp8(x):
    """x rounded to float8_e4m3fn and back (the ``fp8_cache`` control)."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mscale(scale: float, m: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_frequencies(hf: dict) -> tuple:
    """→ (inv_freq [dr/2] float32, the factor on cos and sin, the factor
    on the softmax scale: mscale(factor, mscale_all_dim)²)."""
    d, base = int(hf["qk_rope_head_dim"]), float(hf["rope_theta"])
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling")
    if not rs:
        return (1.0 / pos).astype(np.float32), 1.0, 1.0
    kind = rs.get("rope_type", rs.get("type"))
    if kind != "yarn":
        raise ValueError(f"the deepseek_v32 reference has no {kind!r} rope "
                         "scaling")
    factor = float(rs["factor"])
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        att = (_mscale(factor, float(rs["mscale"]))
               / _mscale(factor, float(rs["mscale_all_dim"])))
    else:
        att = _mscale(factor)
    score = (_mscale(factor, float(rs["mscale_all_dim"])) ** 2
             if rs.get("mscale_all_dim") else 1.0)
    original = int(rs.get("original_max_position_embeddings")
                   or hf["max_position_embeddings"])

    def correction_dim(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs.get("beta_fast") or 32)), 0)
    high = min(math.ceil(correction_dim(rs.get("beta_slow") or 1)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1 - ramp)
    return inv.astype(np.float32), att, score


def _rope(x, inv_freq, att, half_split: bool):
    """x: [T, heads, dr], positions 0..T-1. Interleaved: pairs (2i, 2i+1);
    half-split: lane i with lane i + dr/2."""
    T, _, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * att, jnp.sin(ang)[:, None, :] * att
    if half_split:
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _blocked(fn, rows: tuple, block: int):
    """fn over blocks of ``block`` leading rows of each array of ``rows``
    (padded with zero rows, whose results are dropped), one block at a
    time."""
    T = rows[0].shape[0]
    if T <= block:
        return fn(rows)
    n = -(-T // block)
    pad = n * block - T
    split = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, block) + a.shape[1:]) for a in rows)
    out = jax.lax.map(fn, split)
    return out.reshape((n * block,) + out.shape[2:])[:T]


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names, still as stored
    (int8 and scale apart until the jitted layer dequantises them)."""
    def get(name, i):
        w = params.get(f"layers.{name}")
        if w is None:
            return None
        return (w.q[i], w.scale[i]) if hasattr(w, "q") else w[i]
    out = {n: get(n, li) for n in (
        "ln1", "ln2", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm",
        "wkv_b", "wo", "idx_wq_b", "idx_wk", "idx_k_norm_w", "idx_k_norm_b",
        "idx_w")}
    if li >= fam["first_dense"]:
        names = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
                 "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    else:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def selection(fam: dict, hf: dict, broken=None):
    """→ f(a [T, D], qr [T, q_rank], layer weights) → allowed [T, T] bool:
    allowed[t, s] says that query t may read key s (s ∈ S_t)."""
    J, dI, dr, K = fam["J"], fam["dI"], fam["dr"], fam["topk"]
    inv_np, att, _ = rope_frequencies(hf)
    half_split = broken != "index_rope_interleaved"

    def select(a, qr, lw):
        T = a.shape[0]
        causal = jnp.tril(jnp.ones((T, T), bool))
        if broken == "no_selection":
            return causal
        if broken == "recent_window":
            recent = (jnp.arange(T)[None, :] > jnp.arange(T)[:, None] - K)
            return causal & recent
        inv = jnp.asarray(inv_np)
        qI = (qr @ _w(lw["idx_wq_b"])).reshape(T, J, dI)
        qI = jnp.concatenate(
            [_rope(qI[..., :dr], inv, att, half_split), qI[..., dr:]], -1)
        k = a @ _w(lw["idx_wk"])
        mu = jnp.mean(k, -1, keepdims=True)
        var = jnp.mean((k - mu) ** 2, -1, keepdims=True)
        k = ((k - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
             * _w(lw["idx_k_norm_w"]) + _w(lw["idx_k_norm_b"]))
        kI = jnp.concatenate(
            [_rope(k[:, None, :dr], inv, att, half_split)[:, 0], k[:, dr:]],
            -1)                                                   # [T, dI]
        if broken == "fp8_cache":
            kI = _fp8(kI)
        w = (a @ _w(lw["idx_w"])) * (J ** -0.5 * dI ** -0.5)     # [T, J]
        if broken == "index_unweighted":
            w = jnp.ones_like(w)
        k_sel = min(K, T)

        def block(rows):
            q_b, w_b, t_b = rows                  # [b, J, dI], [b, J], [b]
            dots = jnp.einsum("tjd,sd->tjs", q_b, kI)
            if broken != "index_no_relu":
                dots = jax.nn.relu(dots)
            score = jnp.einsum("tj,tjs->ts", w_b, dots)           # [b, T]
            seen = jnp.arange(T)[None, :] <= t_b[:, None]
            score = jnp.where(seen, score, -jnp.inf)
            top, idx = jax.lax.top_k(score, k_sel)
            picked = jnp.zeros(score.shape, bool).at[
                jnp.arange(score.shape[0])[:, None], idx].set(top > -jnp.inf)
            return picked & seen

        return _blocked(block, (qI, w, jnp.arange(T)), QUERY_BLOCK)

    return select


def moe_block(fam: dict, broken=None):
    """→ f(m [T, D] f32, an expert layer's weights) → the layer's MLP output
    [T, D]: what the experts held here add for the tokens routed to them,
    plus the shared expert."""
    def routing(m, lw):
        """→ weight [T, experts]: each token's mixing weight for every
        published expert, 0 where it is not chosen."""
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        logits = m @ _w(lw["router"])
        if broken == "v2_softmax_router":
            p = jax.nn.softmax(logits, -1)
            top_p, top_i = jax.lax.top_k(p, K)
        else:
            p = jax.nn.sigmoid(logits)
            choice = p + _w(lw["router_bias"])[None, :]
            g = fam["groups"]
            if g > 1:
                per = choice.reshape(T, g, E // g)
                best2, _ = jax.lax.top_k(per, 2)
                _, keep = jax.lax.top_k(best2.sum(-1), fam["top_groups"])
                kept = jnp.zeros((T, g), bool).at[
                    jnp.arange(T)[:, None], keep].set(True)
                choice = jnp.where(kept[..., None], per, 0.0).reshape(T, E)
            _, top_i = jax.lax.top_k(choice, K)
            top_p = jnp.take_along_axis(p, top_i, axis=1)
            if fam["norm_topk"]:
                top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
        top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        return jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)

    def moe_mlp(m, lw):
        weight = routing(m, lw)
        # this chip's experts: the columns of the experts it holds
        weight = weight[:, fam["first_held"]:fam["first_held"] + fam["held"]]
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])

        def expert(acc, x):
            g, u = (_split(None, x["gu"]) if fused
                    else _split(x["gu"], None))
            return acc + x["w"][:, None] * _swiglu(m, g, u, _w(x["down"])), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                              {"gu": gu, "down": lw["moe_down"],
                               "w": weight.T})
        if fam["shared"] and broken != "no_shared_expert":
            g, u = _split((lw.get("sh_gate"), lw.get("sh_up")),
                          lw.get("sh_gateup"))
            out = out + _swiglu(m, g, u, _w(lw["sh_down"]))
        return out
    return moe_mlp


def mask_of(sets, T: int):
    """sets [T, k] key indices (-1 = none) → allowed [T, T] bool."""
    return jnp.zeros((T, T), bool).at[
        jnp.arange(T)[:, None], jnp.maximum(sets, 0)].max(sets >= 0)


def make_layer(fam: dict, hf: dict, moe: bool, broken=None, variants=()):
    """→ jitted f(h [T, D] f32, layer weights, sets=None) → (h, allowed
    [T, T]), for a dense layer or for an expert layer. With ``sets``
    [T, k] (the selection forced on the attention, see the module's
    docstring) the second result is counts [1 + len(variants), T]: how many
    of the forced keys of each query this layer's own selection, and each
    variant's, picked as well."""
    H, rank = fam["heads"], fam["rank"]
    dn, dr, dv, eps = fam["dn"], fam["dr"], fam["dv"], fam["eps"]
    inv_np, att, score_factor = rope_frequencies(hf)
    scale = (dn + dr) ** -0.5 * score_factor
    select = selection(fam, hf, broken)
    others = tuple(selection(fam, hf, v) for v in variants)
    G = math.gcd(H, HEAD_GROUP)
    main_half_split = broken == "half_split_rope"

    def attention(h, a, qr, c, k_pe, allowed, lw):
        """h + attention, a group of G heads and a block of queries at a
        time; every group's part goes through its rows of Wo at once."""
        T = a.shape[0]
        inv = jnp.asarray(inv_np)
        wq_b = _w(lw["wq_b"]).reshape(-1, H // G, G * (dn + dr))
        wkv_b = _w(lw["wkv_b"]).reshape(rank, H // G, G * (dn + dv))
        wo = _w(lw["wo"]).reshape(H // G, G * dv, -1)

        def group(h, xs):
            q = (qr @ xs["wq_b"]).reshape(T, G, dn + dr)
            q_nope = q[..., :dn]
            q_pe = _rope(q[..., dn:], inv, att, main_half_split)
            kv_up = (c @ xs["wkv_b"]).reshape(T, G, dn + dv)
            k_nope, v = kv_up[..., :dn], kv_up[..., dn:]

            def block(rows):
                qn_b, qp_b, ok_b = rows
                s = (jnp.einsum("thd,shd->hts", qn_b, k_nope)
                     + jnp.einsum("thd,sd->hts", qp_b, k_pe)) * scale
                s = jnp.where(ok_b[None], s, -jnp.inf)
                return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

            out = _blocked(block, (q_nope, q_pe, allowed), QUERY_BLOCK)
            return h + out.reshape(T, G * dv) @ xs["wo"], None

        h, _ = jax.lax.scan(group, h, {
            "wq_b": jnp.moveaxis(wq_b, 1, 0),
            "wkv_b": jnp.moveaxis(wkv_b, 1, 0), "wo": wo})
        return h

    def dense_mlp(m, lw):
        g, u = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
        d = _w(lw["down"])
        F = g.shape[-1]
        step = math.gcd(F, MLP_SLICE)

        def piece(acc, xs):
            return acc + _swiglu(m, xs["g"], xs["u"], xs["d"]), None

        out, _ = jax.lax.scan(piece, jnp.zeros_like(m), {
            "g": jnp.moveaxis(g.reshape(-1, F // step, step), 1, 0),
            "u": jnp.moveaxis(u.reshape(-1, F // step, step), 1, 0),
            "d": d.reshape(F // step, step, -1)})
        return out

    moe_mlp = moe_block(fam, broken)

    def layer(h, lw, sets=None):
        inv = jnp.asarray(inv_np)
        a = _rms(h, _w(lw["ln1"]), eps)
        qr = _rms(a @ _w(lw["wq_a"]), _w(lw["q_a_norm"]), eps)
        kv = a @ _w(lw["wkv_a"])                                 # [T, rank+dr]
        c = kv[:, :rank]
        if broken != "no_kv_norm":
            c = _rms(c, _w(lw["kv_norm"]), eps)
        k_pe = kv[:, None, rank:]                                # [T, 1, dr]
        if broken != "k_pe_unrotated":
            k_pe = _rope(k_pe, inv, att, main_half_split)
        if broken == "fp8_cache":
            c, k_pe = _fp8(c), _fp8(k_pe)
        allowed = seen = select(a, qr, lw)
        if sets is not None:
            allowed = mask_of(sets, a.shape[0])
            seen = jnp.stack([jnp.sum(own & allowed, -1) for own in
                              (seen,) + tuple(f(a, qr, lw) for f in others)])
        h = attention(h, a, qr, c, k_pe[:, 0], allowed, lw)
        m = _rms(h, _w(lw["ln2"]), eps)
        return h + (moe_mlp(m, lw) if moe else dense_mlp(m, lw)), seen

    return jax.jit(layer)


def forward(params: dict, hf: dict, tokens, broken=None,
            keep_selection: bool = False, forced=None,
            variants=()) -> tuple:
    """→ (final hidden states [T, D] float32, the selection of every layer
    as a list of [T, T] bool arrays, or None unless ``keep_selection``).
    ``forced``: per layer the sets [T, k] the attention has to read; the
    list then holds each layer's counts [1 + len(variants), T] instead."""
    fam = family(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    h = embed_rows(params, tokens)
    dense = make_layer(fam, hf, False, broken, variants)
    sparse = make_layer(fam, hf, True, broken, variants)
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    picked = [] if keep_selection or forced is not None else None
    for li in range(n_layers):
        layer = dense if li < fam["first_dense"] else sparse
        sets = None if forced is None else jnp.asarray(forced[li], jnp.int32)
        h, seen = layer(h, _layer_weights(params, li, fam), sets)
        if picked is not None:
            picked.append(np.asarray(seen))
    return h, picked


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None, precision: str = "highest") -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it."""
    with jax.default_matmul_precision(precision):
        h, _ = forward(params, hf, tokens, broken)
        return np.asarray(head_logits(params, hf, h[-last:],
                                      family(hf)["eps"]), np.float32)


def selected_sets(params: dict, hf: dict, tokens) -> list:
    """Per layer, allowed [T, T]: row t marks the keys S_t that query t
    reads. For tests that hold the program's selection to the reference's."""
    with jax.default_matmul_precision("highest"):
        return forward(params, hf, tokens, keep_selection=True)[1]
