"""The plain reference of the dots3_note block (dots3-note-prev): latent
attention in TWO geometries in one model, the forward pass only. The
comparison and its tolerance are ``reference.compare`` / ``reference.TOL_STD``,
the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no
absorbed form, no scan over layers, no fused layout, no batching. The catalog
gives the family's ``config.json`` keys and a one-line description, not its
modelling code, so every line is stated here for a reader who has the code to
check; three readings are assumptions (below). ``x`` is the stream, ``n(.)``
RMSNorm with eps ``rms_norm_eps``, D = ``hidden_size``.

    x = embed[tokens]
    per layer i, of the kind layer_types[i]:

    "full_attention" (F): DeepSeek-V3.2's block at this model's sizes, with
    H = num_attention_heads, dn | dr = qk_nope | qk_rope_head_dim,
    dv = v_head_dim, rank = kv_lora_rank, rq = q_lora_rank, theta = rope_theta,
    J = index_n_heads, dI = index_head_dim, K = index_topk:
      a      = n(x)
      qr     = n(a.Wq_a) * sqrt(D / rq)                    (assumed reading 1)
      q      = qr.Wq_b -> [T, H, dn+dr] = q_nope | q_pe
      kv     = a.Wkv_a -> [T, rank+dr]
      c      = n(kv[:rank]) * sqrt(D / rank)               (assumed reading 1)
      k_pe   = rope(kv[rank:])               (one head, shared by all heads)
      c.Wkv_b -> [T, H, dn+dv] = k_nope | v
      q_pe   = rope(q_pe)
      rope:  pairs (2i, 2i+1) rotated by pos * theta^(-2i/dr) (INTERLEAVED);
             rope_scaling is null: no yarn, no mscale
      indexer (reads the rescaled qr):
        qI   = qr.WqI_b -> [T, J, dI]
        kI   = LayerNorm(a.WkI; weight, bias, eps 1e-6) -> [T, dI], one head
        rope on the FIRST dr lanes of qI and kI, HALF-SPLIT (lane i with lane
             i + dr/2), the F layers' frequencies
        w    = a.Ww * J^-0.5 * dI^-0.5 -> [T, J]
        I[t, s] = sum_j w[t, j] * relu(qI[t, j].kI[s])         for s <= t
        S_t  = the indices of the min(K, t+1) largest I[t, s], exact
      s      = (q_nope.k_nope + q_pe.k_pe) * (dn+dr)^-0.5, softmax over S_t
      o      = softmax(s).v                                      [T, H, dv]

    "sliding_attention" (S): the same latent form at the swa_* sizes
    (Hs heads, rank_s, rq_s, dn_s | dr_s, dv_s, theta_s = swa_rope_theta), NO
    indexer; query t attends the keys s with t - W < s <= t,
    W = sliding_window_size: the query's own position counts, W keys
    (assumed reading 3); the scale is (dn_s+dr_s)^-0.5.

    both kinds:
      g      = sigmoid(a.Wg) -> [T, H]; o_h <- g_h * o_h   (assumed reading 2)
      x     += o.Wo
      m      = n(x)
      layer < first_k_dense_replace:
              x += (silu(m.Wg') * (m.Wu)) . Wd            at intermediate_size
      else:   p = sigmoid(m.Wr) over ALL published experts (float32)
              choice = p + e_score_correction_bias (one group); its
              num_experts_per_tok best; weights = p of the chosen (not
              choice), divided by their sum (norm_topk_prob), times
              routed_scaling_factor
              x += sum_{chosen e held here} w_e * expert_e(m) + shared(m)
    logits = n(x) . W_head

**Assumed readings** (``assumed`` in the configuration file):
1. ``apply_mla_qkv_lora_rescale: true`` is the convention of the one public
   family with such a switch (LongCat-Flash's ``mla_scale_q_lora`` /
   ``mla_scale_kv_lora``): the normed q latent times sqrt(D / q_lora_rank),
   the normed kv latent times sqrt(D / kv_lora_rank), per geometry.
2. ``attention_gate_type: "headwise"`` is the headwise variant of gated
   attention (arXiv:2505.06708): one sigmoid scalar a head from the layer's
   normed input, on the attention output before Wo, no bias.
3. ``sliding_window_size: 513`` counts the query's own position.

**The expert share** is ``references/deepseek_v32.py``'s: ``n_routed_experts``
counts the experts held here, ``n_routed_experts_published`` the router's
width, ``expert_share_index`` which share; the router, its bias, the top-k and
the renormalisation are over all the published experts, and what a chosen
expert that lives elsewhere would add is left out, as in the program.

It reads the engine's own parameter tree (``mla.param_shapes`` names: the F
layers' leaves ``layers.<leaf>`` [F layers, ...], the S layers'
``layers.swa_<leaf>`` [S layers, ...], norms [layers, D], MLPs as v3's; int8
as q.scale; gate|up split where ``fuse_stacked_matmuls`` joined them), one
layer at a time, blocked so that a 33k-token prompt fits beside the engine:
queries in blocks, heads in groups, one expert at a time. An F layer computes
every causal score of a block of queries and softmaxes over the selected
ones; an S layer reads the block's ``block + W - 1`` keys. ``logits_for`` keeps
its last ``ANSWERS_KEPT`` answers: the harness asks for the same sequence
under the same weights before its window and after it.

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states, which the comparison has to
tell from the program's). ``int4_weights`` rounds the weights of the matmuls
that the program holds in int8 (the q-LoRA pair, ``Wkv_a``, ``Wo``, the
indexer's three, the dense and shared MLPs, the routed experts) to 4 bits
under one scale per 128 input rows and output column,
``quant.quantize_array_grouped``'s rule; what the program holds in bf16
(``Wkv_b``, the gates, the router, the norms) and the embedding and head stay
as stored. ``logits_for(..., precision="default")`` runs every matmul in the
device's default precision (bf16 passes on a TPU), the program's own: the
witness that reads INSIDE the tolerance, and what the random model's steps
(top-8 of the router, top-K of the indexer) alone cost a bf16 program.

Departures from the published code, each shared with the program: text only
(the vision and audio towers and the multi-token-prediction module of the
description are not in ``config`` and not served); index keys and queries in
the precision stated here, no Hadamard rotation (as deepseek_v32); weights
are the int8-rounded ones the engine holds.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_shared_expert", "unit_routing_weights",
             "no_gate", "swa_full_theta", "window_minus_one",
             "window_plus_one", "no_q_rescale", "no_kv_rescale",
             "swa_selected", "no_selection", "absent_expert_added")

CONTROLS = ("int4_weights",)


def _int4_groups(w, group: int = 128):
    """w [..., D, F] rounded to 15 levels, one scale per ``group`` rows of D
    and column of F (all of D where ``group`` does not divide it)."""
    D, F = w.shape[-2:]
    g = group if D % group == 0 else D
    w = w.reshape(w.shape[:-2] + (D // g, g, F))
    scale = jnp.maximum(jnp.max(jnp.abs(w), -2, keepdims=True), 1e-30) / 7
    return (jnp.clip(jnp.round(w / scale), -7, 7) * scale).reshape(
        w.shape[:-3] + (D, F))


def _weights(control):
    """→ f(a matmul's int8-held weights, dequantised) under ``control``: the
    identity for anything but ``int4_weights``."""
    return _int4_groups if control == "int4_weights" else (lambda w: w)


# what served logits (bf16, int8 weights, random weights) do not show at
# every width, so breakages_for does not ask for it: one key of 513 more or
# less and one absent expert's part under the seeded rule's damped experts
# read what a sound program reads (0.06 against 0.07 on the chip at 32,832
# tokens); the other geometry's theta and unit routing weights stand outside
# the tolerance at the published widths (0.26, 0.40) and inside it at the
# fixture's (a window of 21 positions, top-2 of 8 experts). Measured under
# llama.MIXED_SEEDED: PERF.md section 6, PR 42
FINE = ("unit_routing_weights", "swa_full_theta", "window_minus_one",
        "window_plus_one", "absent_expert_added")

INDEX_NORM_EPS = 1e-6
# queries whose scores exist at once, heads a group holds, a dense MLP's slice
QUERY_BLOCK = 64
INDEX_QUERY_BLOCK = 32      # [block, J, T] index dots: 270 MB at 33k keys
HEAD_GROUP = 8
MLP_SLICE = 2048


def breakages_for(hf: dict) -> tuple:
    """Those that the tolerance has to catch on served logits (bf16, int8
    weights): all but FINE. tests/test_dots3_note.py holds every one of
    BREAKAGES in float32, where the engine and this file agree to 2e-6."""
    family(hf)
    return tuple(b for b in BREAKAGES if b not in FINE)


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "dots3_note":
        raise ValueError(f"the dots3_note reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
        "rope_scaling": bool(hf.get("rope_scaling")),
        "n_group": int(hf.get("n_group") or 1) != 1,
        "attention_gate_type": any(
            hf.get(k, "headwise") != "headwise"
            for k in ("attention_gate_type", "swa_attention_gate_type")),
    }
    if any(refused.values()):
        raise ValueError("the dots3_note reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    layers = int(hf["num_hidden_layers"])
    held = int(hf["n_routed_experts"])
    D = int(hf["hidden_size"])
    rescale = bool(hf.get("apply_mla_qkv_lora_rescale"))

    def geometry(p: str, heads: str) -> dict:
        rq, rank = int(hf[p + "q_lora_rank"]), int(hf[p + "kv_lora_rank"])
        return {"heads": int(hf[heads]), "rq": rq, "rank": rank,
                "dn": int(hf[p + "qk_nope_head_dim"]),
                "dr": int(hf[p + "qk_rope_head_dim"]),
                "dv": int(hf[p + "v_head_dim"]),
                "theta": float(hf[p + "rope_theta"]),
                "q_scale": math.sqrt(D / rq) if rescale else 1.0,
                "kv_scale": math.sqrt(D / rank) if rescale else 1.0}

    return {
        "layers": layers,
        "kinds": tuple("S" if t == "sliding_attention" else "F"
                       for t in hf["layer_types"][:layers]),
        "F": geometry("", "num_attention_heads"),
        "S": geometry("swa_", "swa_num_attention_heads"),
        "window": int(hf["sliding_window_size"]),
        "eps": float(hf["rms_norm_eps"]),
        "J": int(hf["index_n_heads"]), "dI": int(hf["index_head_dim"]),
        "topk": int(hf["index_topk"]),
        "held": held,
        "experts": int(hf.get("n_routed_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "first_dense": int(hf.get("first_k_dense_replace") or 0),
        "shared": (int(hf.get("n_shared_experts") or 0)
                   * int(hf["moe_intermediate_size"])),
        "routed_scaling": float(hf.get("routed_scaling_factor") or 1.0),
    }


def _inv_freq(theta: float, d: int):
    return jnp.asarray((1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64)
                                        / d)).astype(np.float32))


def _rope(x, inv_freq, half_split: bool = False, first=0):
    """x: [T, heads, dr], positions first..first+T-1. Interleaved: pairs
    (2i, 2i+1); half-split: lane i with lane i + dr/2."""
    T, _, d = x.shape
    ang = ((first + jnp.arange(T)).astype(jnp.float32)[:, None]
           * inv_freq[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if half_split:
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _blocked(fn, rows: tuple, block: int):
    """fn over blocks of ``block`` leading rows of each array of ``rows``
    (padded with zero rows, whose results are dropped), one block at a
    time; fn is also given the block's first row index."""
    T = rows[0].shape[0]
    n = -(-T // block)
    pad = n * block - T
    split = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, block) + a.shape[1:]) for a in rows)
    out = jax.lax.map(lambda xs: fn(xs[0], xs[1:]),
                      (jnp.arange(n) * block,) + split)
    return out.reshape((n * block,) + out.shape[2:])[:T]


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names, still as stored
    (int8 and scale apart until the jitted layer dequantises them): the
    attention leaves from the stack of the layer's kind, at its index among
    the layers of that kind."""
    def get(name, i):
        w = params.get(f"layers.{name}")
        if w is None:
            return None
        return (w.q[i], w.scale[i]) if hasattr(w, "q") else w[i]
    kind = fam["kinds"][li]
    ai = fam["kinds"][:li].count(kind)
    prefix = "swa_" if kind == "S" else ""
    out = {n: get(n, li) for n in ("ln1", "ln2")}
    out.update({n: get(prefix + n, ai) for n in (
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "wg",
        "idx_wq_b", "idx_wk", "idx_k_norm_w", "idx_k_norm_b", "idx_w")})
    if li >= fam["first_dense"]:
        names = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
                 "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    else:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def selection(fam: dict, broken=None):
    """→ f(a [T, D], qr [T, rq], layer weights) → allowed [T, T] bool:
    allowed[t, s] says that query t of an F layer may read key s."""
    J, dI, K = fam["J"], fam["dI"], fam["topk"]
    dr = fam["F"]["dr"]
    inv = _inv_freq(fam["F"]["theta"], dr)
    wt = _weights(broken)

    def select(a, qr, lw):
        T = a.shape[0]
        if broken == "no_selection":
            return jnp.tril(jnp.ones((T, T), bool))
        wq = wt(_w(lw["idx_wq_b"]))
        k = a @ wt(_w(lw["idx_wk"]))
        mu = jnp.mean(k, -1, keepdims=True)
        var = jnp.mean((k - mu) ** 2, -1, keepdims=True)
        k = ((k - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
             * _w(lw["idx_k_norm_w"]) + _w(lw["idx_k_norm_b"]))
        kI = jnp.concatenate(
            [_rope(k[:, None, :dr], inv, True)[:, 0], k[:, dr:]], -1)
        w = (a @ wt(_w(lw["idx_w"]))) * (J ** -0.5 * dI ** -0.5)  # [T, J]
        k_sel = min(K, T)

        def block(t0, rows):
            qr_b, w_b = rows                         # [b, rq], [b, J]
            t_b = t0 + jnp.arange(qr_b.shape[0])
            q_b = (qr_b @ wq).reshape(-1, J, dI)
            q_b = jnp.concatenate(
                [_rope(q_b[..., :dr], inv, True, first=t0), q_b[..., dr:]],
                -1)
            dots = jax.nn.relu(jnp.einsum("tjd,sd->tjs", q_b, kI))
            score = jnp.einsum("tj,tjs->ts", w_b, dots)           # [b, T]
            seen = jnp.arange(T)[None, :] <= t_b[:, None]
            score = jnp.where(seen, score, -jnp.inf)
            top, idx = jax.lax.top_k(score, k_sel)
            picked = jnp.zeros(score.shape, bool).at[
                jnp.arange(score.shape[0])[:, None], idx].set(top > -jnp.inf)
            return picked & seen

        return _blocked(block, (qr, w), INDEX_QUERY_BLOCK)

    return select


def moe_block(fam: dict, broken=None):
    """→ f(m [T, D] f32, an expert layer's weights) → the layer's MLP output
    [T, D]: what the experts held here add for the tokens routed to them,
    plus the shared expert."""
    wt = _weights(broken)

    def moe_mlp(m, lw):
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        p = jax.nn.sigmoid(m @ _w(lw["router"]))
        _, top_i = jax.lax.top_k(p + _w(lw["router_bias"])[None, :], K)
        top_p = jnp.take_along_axis(p, top_i, axis=1)
        if fam["norm_topk"]:
            top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
        top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)
        lo, held = fam["first_held"], fam["held"]
        mine = weight[:, lo:lo + held]
        if broken == "absent_expert_added" and E > held:
            # the first expert that lives elsewhere, run here all the same
            # (on the first held expert's weights: its own are not here)
            away = (lo + held) % E
            mine = mine.at[:, 0].add(weight[:, away])
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])

        def expert(acc, x):
            g, u = (_split(None, x["gu"]) if fused
                    else _split(x["gu"], None))
            y = _swiglu(m, wt(g), wt(u), wt(_w(x["down"])))
            return acc + x["w"][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                              {"gu": gu, "down": lw["moe_down"],
                               "w": mine.T})
        if fam["shared"] and broken != "no_shared_expert":
            g, u = _split((lw.get("sh_gate"), lw.get("sh_up")),
                          lw.get("sh_gateup"))
            out = out + _swiglu(m, wt(g), wt(u), wt(_w(lw["sh_down"])))
        return out
    return moe_mlp


def make_layer(fam: dict, kind: str, moe: bool, broken=None):
    """→ jitted f(x [T, D] f32, layer weights, allowed_before) → (x, allowed):
    one layer of ``kind`` with a dense or an expert MLP. ``allowed`` is the
    [T, T] selection of an F layer (handed on: the ``swa_selected`` breakage
    makes an S layer read the last F layer's)."""
    geo = fam[kind]
    H, rank, rq = geo["heads"], geo["rank"], geo["rq"]
    dn, dr, dv, eps = geo["dn"], geo["dr"], geo["dv"], fam["eps"]
    theta = (fam["F"]["theta"] if broken == "swa_full_theta"
             else geo["theta"])
    inv = _inv_freq(theta, dr)
    scale = (dn + dr) ** -0.5
    q_scale = 1.0 if broken == "no_q_rescale" else geo["q_scale"]
    kv_scale = 1.0 if broken == "no_kv_rescale" else geo["kv_scale"]
    window = fam["window"] + {"window_minus_one": -1,
                              "window_plus_one": 1}.get(broken, 0)
    select = selection(fam, broken)
    wt = _weights(broken)
    G = math.gcd(H, HEAD_GROUP)
    windowed = kind == "S" and broken != "swa_selected"

    def attention(x, a, qr, c, k_pe, allowed, lw):
        """x + gated attention, a group of G heads and a block of queries at
        a time; every group's part goes through its rows of Wo at once."""
        T = a.shape[0]
        wq_b = wt(_w(lw["wq_b"])).reshape(-1, H // G, G * (dn + dr))
        wkv_b = _w(lw["wkv_b"]).reshape(rank, H // G, G * (dn + dv))
        wo = wt(_w(lw["wo"])).reshape(H // G, G * dv, -1)
        gate = jnp.ones((T, H), jnp.float32)
        if broken != "no_gate":
            gate = jax.nn.sigmoid(a @ _w(lw["wg"]))               # [T, H]
        back = window - 1

        def group(x, xs):
            q = (qr @ xs["wq_b"]).reshape(T, G, dn + dr)
            q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv)
            kv_up = (c @ xs["wkv_b"]).reshape(T, G, dn + dv)
            k_nope, v = kv_up[..., :dn], kv_up[..., dn:]

            def full(t0, rows):
                qn_b, qp_b, ok_b = rows
                s = (jnp.einsum("thd,shd->hts", qn_b, k_nope)
                     + jnp.einsum("thd,sd->hts", qp_b, k_pe)) * scale
                s = jnp.where(ok_b[None], s, -jnp.inf)
                return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

            if windowed:
                # the keys a block can reach: its own and window - 1 before
                front = lambda z: jnp.pad(       # noqa: E731
                    z, ((back, 0),) + ((0, 0),) * (z.ndim - 1))
                kn_p, kp_p, v_p = front(k_nope), front(k_pe), front(v)

                def near(t0, rows):
                    qn_b, qp_b = rows
                    b = qn_b.shape[0]
                    take = lambda z: jax.lax.dynamic_slice_in_dim(  # noqa
                        z, t0, b + back, axis=0)
                    kn, kp, vv = take(kn_p), take(kp_p), take(v_p)
                    s = (jnp.einsum("thd,shd->hts", qn_b, kn)
                         + jnp.einsum("thd,sd->hts", qp_b, kp)) * scale
                    kpos = (t0 - back + jnp.arange(b + back))[None, :]
                    qpos = (t0 + jnp.arange(b))[:, None]
                    ok = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - window)
                    s = jnp.where(ok[None], s, -jnp.inf)
                    return jnp.einsum("hts,shd->thd",
                                      jax.nn.softmax(s, -1), vv)

                pad = -T % QUERY_BLOCK
                tail = lambda z: jnp.pad(        # noqa: E731
                    z, ((0, pad),) + ((0, 0),) * (z.ndim - 1))
                kn_p, kp_p, v_p = tail(kn_p), tail(kp_p), tail(v_p)
                out = _blocked(near, (q_nope, q_pe), QUERY_BLOCK)
            else:
                out = _blocked(full, (q_nope, q_pe, allowed), QUERY_BLOCK)
            out = out * xs["gate"][:, :, None]
            return x + out.reshape(T, G * dv) @ xs["wo"], None

        x, _ = jax.lax.scan(group, x, {
            "wq_b": jnp.moveaxis(wq_b, 1, 0),
            "wkv_b": jnp.moveaxis(wkv_b, 1, 0), "wo": wo,
            "gate": jnp.moveaxis(gate.reshape(T, H // G, G), 1, 0)})
        return x

    def dense_mlp(m, lw):
        g, u = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
        g, u, d = wt(g), wt(u), wt(_w(lw["down"]))
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

    def layer(x, lw, allowed_before):
        a = _rms(x, _w(lw["ln1"]), eps)
        qr = _rms(a @ wt(_w(lw["wq_a"])), _w(lw["q_a_norm"]), eps) * q_scale
        kv = a @ wt(_w(lw["wkv_a"]))                            # [T, rank+dr]
        c = _rms(kv[:, :rank], _w(lw["kv_norm"]), eps) * kv_scale
        k_pe = _rope(kv[:, None, rank:], inv)[:, 0]             # [T, dr]
        allowed = select(a, qr, lw) if kind == "F" else allowed_before
        x = attention(x, a, qr, c, k_pe, allowed, lw)
        m = _rms(x, _w(lw["ln2"]), eps)
        return x + (moe_mlp(m, lw) if moe else dense_mlp(m, lw)), allowed

    return jax.jit(layer)


def forward(params: dict, hf: dict, tokens, broken=None):
    """→ the final hidden states [T, D] float32."""
    fam = family(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = embed_rows(params, tokens)
    T = tokens.shape[0]
    layers = {}
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    # what an S layer of the swa_selected breakage reads before any F layer
    allowed = jnp.tril(jnp.ones((T, T), bool)) if broken == "swa_selected" \
        else None
    for li in range(n_layers):
        key = (fam["kinds"][li], li >= fam["first_dense"])
        if key not in layers:
            layers[key] = make_layer(fam, key[0], key[1], broken)
        x, allowed = layers[key](x, _layer_weights(params, li, fam), allowed)
        if broken != "swa_selected":
            allowed = None               # an S layer reads its window
    return x


# The harness asks the same question twice: ``run.py`` holds its probes to
# this file before the window and again after it, and a probe that serves the
# same tokens again hands in the same sequence. The answer is a function of
# the weights and the tokens alone, and one forward over a 33k-token probe is
# 36 s of the chip in float32, so the last answers are kept and an identical
# question is answered from them (identical: the same weight arrays, by
# identity, and the same tokens, breakage and precision). What the second
# comparison is for, the ENGINE's state after the window, is held as before:
# the served tokens and logprobs are new each time.
_ANSWERS: list = []
ANSWERS_KEPT = 4


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None, precision: str = "highest") -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it; ``broken`` is a breakage
    or a control."""
    asked = (tuple(int(t) for t in tokens), int(last), broken, precision,
             json.dumps(hf, sort_keys=True))
    weights = tuple(params.items())
    for held, question, answer in _ANSWERS:
        if question == asked and len(held) == len(weights) and all(
                a[0] == b[0] and a[1] is b[1]
                for a, b in zip(held, weights)):
            return answer.copy()
    with jax.default_matmul_precision(precision):
        x = forward(params, hf, tokens, broken)
        answer = np.asarray(head_logits(params, hf, x[-last:],
                                        family(hf)["eps"]), np.float32)
    _ANSWERS.append((weights, asked, answer))
    del _ANSWERS[:-ANSWERS_KEPT]
    return answer.copy()
