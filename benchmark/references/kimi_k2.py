"""The plain reference of the Kimi-K2 block (``model_type: kimi_k2``: MLA with
the q-LoRA pair, every query attending every earlier row, and the v3
``noaux_tc`` MoE at this family's sizes): the forward pass only. The
comparison and its tolerance are ``reference.compare`` /
``reference.TOL_STD``, the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no
absorbed form, no key blocks, no scan over layers, no fused layout. The
family's checkpoints run under DeepSeek-V3's modelling code
(``DeepseekV3ForCausalLM``: ``DeepseekV3Attention``, ``MoEGate``,
``DeepseekV3MoE``) as the writer knows it — there is no network here, so
every line is stated, for a reader who has the file to check. With H heads,
dn = ``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``,
rank = ``kv_lora_rank``:

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
      rope:  pairs (2i, 2i+1) rotated by pos·inv_freq[i] (INTERLEAVED); yarn:
             inv_freq blends theta^(-2i/dr) and the same over `factor` along
             a linear ramp between the correction dims of beta_fast /
             beta_slow over original_max_position_embeddings; cos and sin
             times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
             (= 1 at the published 1 / 1), mscale(s, m) = 0.1·m·ln(s) + 1
      s      = (q_nope·k_nope + q_pe·k_pe) · (dn+dr)^-0.5 · mscale(factor,
               mscale_all_dim)²   (1.4159² at the published factor 64),
               causal softmax over EVERY earlier position
      h     += (softmax(s)·v) · Wo
      m      = RMSNorm(h)
      layer < first_k_dense_replace:
              h += (silu(m·Wg) * (m·Wu)) · Wd      at intermediate_size
      else:   p = sigmoid(m·Wr) over ALL published experts (float32)
              choice = p + e_score_correction_bias; with n_group > 1 the
              groups are limited as in v3 (per group the sum of its two
              best, the topk_group best groups keep their choice, the rest
              are set to 0); the published n_group = topk_group = 1 limits
              nothing: the num_experts_per_tok best of all the experts
              weights = p of the chosen (not choice), divided by their sum
              (norm_topk_prob), times routed_scaling_factor
              h += Σ_{chosen e held here} w_e · expert_e(m) + shared(m)
    logits = RMSNorm(h) · W_head

**The expert share.** ``n_routed_experts`` counts the experts held here,
``n_routed_experts_published`` the router's width, ``expert_share_index``
which share this is: the experts [index·held, (index+1)·held). The router,
its bias, the top-k and the renormalisation are over all the published
experts; what a chosen expert that lives elsewhere would add is left out, as
in the program, and that partial result goes on to the next layer. The
shared expert is whole. The vocabulary is the slice the file states.

It reads the engine's own parameter tree (``mla.param_shapes`` names; int8 as
q·scale, ``wkv_b`` as stored; gate|up split where ``fuse_stacked_matmuls``
joined them). It is blocked so that an 8,200-token prompt fits beside the
engine: queries in blocks (the scores never span more than a block of
queries), heads in groups, one expert and one slice of the dense MLP at a
time, one layer at a time.

It refuses what it does not compute: a ``model_type`` other than
``kimi_k2``, no ``q_lora_rank``, ``scoring_func`` other than ``sigmoid``,
``topk_method`` other than ``noaux_tc``, ``attention_bias``, rope scaling
other than yarn, a multi-token-prediction layer.

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states, which the comparison has
to tell from the program's). ``fp8_activations`` rounds the activation
operand of every matmul of a layer (projections, dense and shared MLPs,
routed experts; bf16 in the program) to float8_e4m3fn under one scale a
row, as an FP8 serving stack does; ``int4_weights`` rounds those matmuls'
weights (int8 in the program) to 4 bits under one scale per 128 input
rows and output column, ``quant.quantize_array_grouped``'s rule. The router
(float32 everywhere), the embedding and the head stay as stored.
``logits_for(..., precision="default")`` runs every matmul in the device's
default precision (bf16 passes on a TPU): the program's own precision, so
that one reads INSIDE the tolerance.

Departures from the published model, each shared with the program:
- text only: the vision tower of the later members of the family is not
  part of this configuration, and the engine is handed token ids;
- no multi-token-prediction layer (``num_nextn_predict_layers`` 0);
- one chip's share of the experts and of the vocabulary, as above;
- weights are the int8-rounded ones the engine holds.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_score_mscale", "rope_half_split", "no_q_norm",
             "no_kv_norm", "softmax_router", "unit_routing_weights",
             "no_routed_scaling", "group_limited_8_4", "no_shared_expert",
             "prefix_dropped", "causal_off_by_one", "router_cut_to_share")

CONTROLS = ("fp8_activations", "int4_weights")


def _fp8_rows(x):
    """x rounded to float8_e4m3fn, each row under its own scale."""
    top = jnp.finfo(jnp.float8_e4m3fn).max.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _int4_groups(w, group: int = 128):
    """w [..., D, F] rounded to 15 levels, one scale per ``group`` rows of D
    and column of F (all of D where ``group`` does not divide it)."""
    D, F = w.shape[-2:]
    g = group if D % group == 0 else D
    w = w.reshape(w.shape[:-2] + (D // g, g, F))
    scale = jnp.maximum(jnp.max(jnp.abs(w), -2, keepdims=True), 1e-30) / 7
    return (jnp.clip(jnp.round(w / scale), -7, 7) * scale).reshape(
        w.shape[:-3] + (D, F))


def _rounding(control) -> tuple:
    """→ (activations → activations, weights → weights) of a layer's
    matmuls under ``control``; the identity for anything else."""
    def same(x):
        return x
    return {"fp8_activations": (_fp8_rows, same),
            "int4_weights": (same, _int4_groups)}.get(control, (same, same))


# queries whose attention scores [heads of a group, block, T] exist at once,
# heads a group holds, and the width of a dense MLP's slice
QUERY_BLOCK = 128
HEAD_GROUP = 16
MLP_SLICE = 2048


def breakages_for(hf: dict) -> tuple:
    """Those of BREAKAGES that change this configuration's mathematics:
    all of them, but v3's eight groups where the router's width does not
    divide into eight groups of two or more, and the router cut to the
    share where no share is cut (every expert is held here)."""
    fam = family(hf)
    fits = {"group_limited_8_4": (fam["experts"] % 8 == 0
                                  and fam["experts"] >= 16),
            "router_cut_to_share": fam["top_k"] <= fam["held"] < fam["experts"]}
    return tuple(b for b in BREAKAGES if fits.get(b, True))


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "kimi_k2":
        raise ValueError(f"the kimi_k2 reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "q_lora_rank (none)": not hf.get("q_lora_rank"),
        "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
        "n_routed_experts (none)": not hf.get("n_routed_experts"),
        "num_nextn_predict_layers": bool(hf.get("num_nextn_predict_layers")),
    }
    if any(refused.values()):
        raise ValueError("the kimi_k2 reference does not compute this "
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
        "held": held,
        "experts": int(hf.get("n_routed_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_tok"]),
        "groups": int(hf["n_group"]),
        "top_groups": int(hf["topk_group"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "first_dense": int(hf["first_k_dense_replace"]),
        "shared": (int(hf.get("n_shared_experts") or 0)
                   * int(hf["moe_intermediate_size"])),
        "routed_scaling": float(hf["routed_scaling_factor"]),
    }


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
        raise ValueError(f"the kimi_k2 reference has no {kind!r} rope "
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
    half-split (llama's, the ``rope_half_split`` breakage): lane i with
    lane i + dr/2."""
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
        "wkv_b", "wo")}
    if li >= fam["first_dense"]:
        names = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
                 "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    else:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def allowed_keys(t, T: int, broken=None):
    """→ [len(t), T] bool: the keys each of the queries at positions ``t``
    reads. Every earlier position and its own; the breakages read less."""
    s = jnp.arange(T)[None, :]
    t = t[:, None]
    if broken == "causal_off_by_one":
        # the row the step has just written is not read (position 0 has
        # nothing else to read)
        return (s < t) | ((t == 0) & (s == 0))
    ok = s <= t
    if broken == "prefix_dropped":
        # a chunk of T // 2 queries reads its own keys only
        chunk = max(1, T // 2)
        ok = ok & (s >= t // chunk * chunk)
    return ok


def moe_block(fam: dict, broken=None):
    """→ f(m [T, D] f32, an expert layer's weights) → the layer's MLP output
    [T, D]: what the experts held here add for the tokens routed to them,
    plus the shared expert."""
    groups, top_groups = fam["groups"], fam["top_groups"]
    if broken == "group_limited_8_4":       # v3's published groups
        groups, top_groups = 8, 4
    act, wt = _rounding(broken)

    def swiglu(m, g, u, d):
        return act(jax.nn.silu(m @ wt(g)) * (m @ wt(u))) @ wt(d)

    def routing(m, lw):
        """→ weight [T, experts]: each token's mixing weight for every
        published expert, 0 where it is not chosen."""
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        logits = m @ _w(lw["router"])
        if broken == "softmax_router":      # v2's scores, v2's use of them
            p = jax.nn.softmax(logits, -1)
            top_p, top_i = jax.lax.top_k(p, K)
        else:
            p = jax.nn.sigmoid(logits)
            choice = p + _w(lw["router_bias"])[None, :]
            if broken == "router_cut_to_share":
                # the share's own fault: the router sliced with the
                # experts, so every token's top-k is among those held here
                here = ((jnp.arange(E) >= fam["first_held"])
                        & (jnp.arange(E) < fam["first_held"] + fam["held"]))
                choice = jnp.where(here[None, :], choice, -jnp.inf)
            if groups > 1:
                per = choice.reshape(T, groups, E // groups)
                best2, _ = jax.lax.top_k(per, 2)
                _, keep = jax.lax.top_k(best2.sum(-1), top_groups)
                kept = jnp.zeros((T, groups), bool).at[
                    jnp.arange(T)[:, None], keep].set(True)
                choice = jnp.where(kept[..., None], per, 0.0).reshape(T, E)
            _, top_i = jax.lax.top_k(choice, K)
            top_p = jnp.take_along_axis(p, top_i, axis=1)
            if fam["norm_topk"]:
                top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
        if broken != "no_routed_scaling":
            top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        return jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)

    def moe_mlp(m, lw):
        weight = routing(m, lw)
        m = act(m)
        # this chip's experts: the columns of the experts it holds
        weight = weight[:, fam["first_held"]:fam["first_held"] + fam["held"]]
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])

        def expert(acc, x):
            g, u = (_split(None, x["gu"]) if fused
                    else _split(x["gu"], None))
            return acc + x["w"][:, None] * swiglu(m, g, u, _w(x["down"])), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                              {"gu": gu, "down": lw["moe_down"],
                               "w": weight.T})
        if fam["shared"] and broken != "no_shared_expert":
            g, u = _split((lw.get("sh_gate"), lw.get("sh_up")),
                          lw.get("sh_gateup"))
            out = out + swiglu(m, g, u, _w(lw["sh_down"]))
        return out
    return moe_mlp


def make_layer(fam: dict, hf: dict, moe: bool, broken=None):
    """→ jitted f(h [T, D] f32, layer weights) → h, for a dense layer or
    for an expert layer."""
    H, rank = fam["heads"], fam["rank"]
    dn, dr, dv, eps = fam["dn"], fam["dr"], fam["dv"], fam["eps"]
    inv_np, att, score_factor = rope_frequencies(hf)
    if broken == "no_score_mscale":
        score_factor = 1.0
    scale = (dn + dr) ** -0.5 * score_factor
    G = math.gcd(H, HEAD_GROUP)
    half_split = broken == "rope_half_split"
    act, wt = _rounding(broken)

    def attention(h, qr, c, k_pe, lw):
        """h + attention, a group of G heads and a block of queries at a
        time; every group's part goes through its rows of Wo at once."""
        T = qr.shape[0]
        inv = jnp.asarray(inv_np)
        wq_b = wt(_w(lw["wq_b"])).reshape(-1, H // G, G * (dn + dr))
        wkv_b = _w(lw["wkv_b"]).reshape(rank, H // G, G * (dn + dv))
        wo = wt(_w(lw["wo"])).reshape(H // G, G * dv, -1)
        qr, c = act(qr), act(c)

        def group(h, xs):
            q = (qr @ xs["wq_b"]).reshape(T, G, dn + dr)
            q_nope = q[..., :dn]
            q_pe = _rope(q[..., dn:], inv, att, half_split)
            kv_up = (c @ xs["wkv_b"]).reshape(T, G, dn + dv)
            k_nope, v = kv_up[..., :dn], kv_up[..., dn:]

            def block(rows):
                qn_b, qp_b, t_b = rows
                s = (jnp.einsum("thd,shd->hts", qn_b, k_nope)
                     + jnp.einsum("thd,sd->hts", qp_b, k_pe)) * scale
                s = jnp.where(allowed_keys(t_b, T, broken)[None], s, -jnp.inf)
                return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

            out = _blocked(block, (q_nope, q_pe, jnp.arange(T)), QUERY_BLOCK)
            return h + act(out.reshape(T, G * dv)) @ xs["wo"], None

        h, _ = jax.lax.scan(group, h, {
            "wq_b": jnp.moveaxis(wq_b, 1, 0),
            "wkv_b": jnp.moveaxis(wkv_b, 1, 0), "wo": wo})
        return h

    def dense_mlp(m, lw):
        g, u = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
        g, u, d = wt(g), wt(u), wt(_w(lw["down"]))
        m = act(m)
        F = g.shape[-1]
        step = math.gcd(F, MLP_SLICE)

        def piece(acc, xs):
            hidden = jax.nn.silu(m @ xs["g"]) * (m @ xs["u"])
            return acc + act(hidden) @ xs["d"], None

        out, _ = jax.lax.scan(piece, jnp.zeros_like(m), {
            "g": jnp.moveaxis(g.reshape(-1, F // step, step), 1, 0),
            "u": jnp.moveaxis(u.reshape(-1, F // step, step), 1, 0),
            "d": d.reshape(F // step, step, -1)})
        return out

    moe_mlp = moe_block(fam, broken)

    def layer(h, lw):
        inv = jnp.asarray(inv_np)
        a = act(_rms(h, _w(lw["ln1"]), eps))
        qr = a @ wt(_w(lw["wq_a"]))
        if broken != "no_q_norm":
            qr = _rms(qr, _w(lw["q_a_norm"]), eps)
        kv = a @ wt(_w(lw["wkv_a"]))                             # [T, rank+dr]
        c = kv[:, :rank]
        if broken != "no_kv_norm":
            c = _rms(c, _w(lw["kv_norm"]), eps)
        k_pe = _rope(kv[:, None, rank:], inv, att, half_split)   # [T, 1, dr]
        h = attention(h, qr, c, k_pe[:, 0], lw)
        m = _rms(h, _w(lw["ln2"]), eps)
        return h + (moe_mlp(m, lw) if moe else dense_mlp(m, lw))

    return jax.jit(layer)


_MOE_ONLY = ("softmax_router", "unit_routing_weights", "no_routed_scaling",
             "group_limited_8_4", "no_shared_expert", "router_cut_to_share")
_LAYERS: dict = {}


def _layer(fam: dict, hf: dict, moe: bool, broken):
    """``make_layer``, built once per configuration, kind and breakage."""
    key = (json.dumps(hf, sort_keys=True), moe, broken)
    if key not in _LAYERS:
        _LAYERS[key] = make_layer(fam, hf, moe, broken)
    return _LAYERS[key]


def forward(params: dict, hf: dict, tokens, broken=None) -> jax.Array:
    """→ the final hidden states [T, D] float32 (before the last norm)."""
    fam = family(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    h = embed_rows(params, tokens)
    # a breakage of the expert block leaves the dense layer as it is
    dense = _layer(fam, hf, False, None if broken in _MOE_ONLY else broken)
    sparse = _layer(fam, hf, True, broken)
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    for li in range(n_layers):
        layer = dense if li < fam["first_dense"] else sparse
        h = layer(h, _layer_weights(params, li, fam))
    return h


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None, precision: str = "highest") -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it. ``broken``: a breakage
    or a control. ``precision="default"`` is the served precision (bf16
    passes on a TPU), not a breakage."""
    with jax.default_matmul_precision(precision):
        h = forward(params, hf, tokens, broken)
        return np.asarray(head_logits(params, hf, h[-last:],
                                      family(hf)["eps"]), np.float32)
