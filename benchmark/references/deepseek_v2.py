"""The plain reference of the DeepSeek-V2 block (MLA + the deepseek MoE):
the forward pass only. The comparison and its tolerance are
``reference.compare`` / ``reference.TOL_STD``, the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no
absorbed form, no scan over layers, no fused layout. It follows Hugging Face
``transformers``' ``DeepseekV2`` modelling code (``modeling_deepseek_v2.py``
and ``_compute_yarn_parameters``), the public implementation that
``tests/test_mla.py`` pins ``engine/models/mla.py`` to. With H heads,
dn = ``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``,
rank = ``kv_lora_rank``:

    h = embed[tokens]
    per layer:
      a      = RMSNorm(h)
      q      = a·Wq → [T, H, dn+dr] = q_nope | q_pe
      kv     = a·Wkv_a → [T, rank+dr]
      c      = RMSNorm(kv[:rank]; kv_norm)         (the latent)
      k_pe   = rope(kv[rank:])                     (one head, shared by all)
      c·Wkv_b → [T, H, dn+dv] = k_nope | v
      q_pe   = rope(q_pe)
      rope:  pairs (2i, 2i+1) rotated by pos·inv_freq[i] (INTERLEAVED, not
             llama's half-split); yarn: inv_freq blends base^(-2i/dr) and the
             same over `factor` along a linear ramp between the correction
             dims of beta_fast / beta_slow over
             original_max_position_embeddings; cos and sin are multiplied by
             mscale(factor, mscale) / mscale(factor, mscale_all_dim), with
             mscale(s, m) = 0.1·m·ln(s) + 1
      s      = (q_nope·k_nope + q_pe·k_pe) · (dn+dr)^-0.5, causal softmax
      h     += (softmax(s)·v) · Wo
      m      = RMSNorm(h)
      layer < first_k_dense_replace:
              h += (silu(m·Wg) * (m·Wu)) · Wd      at intermediate_size
      else:   p = softmax(m·Wr) over all experts (float32); the top
              num_experts_per_tok of p, used as they are (no
              renormalisation), times routed_scaling_factor
              h += Σ_k w_k · expert_k(m) + shared(m)
              shared: one SwiGLU of width n_shared_experts ×
              moe_intermediate_size, added with no gate
    logits = RMSNorm(h) · W_head

It reads the engine's own parameter tree (``mla.param_shapes`` names; int8
as q·scale, ``wkv_b`` as stored in full precision; gate|up split where
``fuse_stacked_matmuls`` joined them), one layer and one expert at a time.

It refuses what it does not compute: ``q_lora_rank`` set (the q-LoRA pair of
the full V2), ``topk_method`` other than ``greedy``, ``scoring_func`` other
than ``softmax``, ``norm_topk_prob`` true, ``attention_bias``, rope scaling
other than yarn, and ``deepseek_v3``.

Departures from the published code: none from ``transformers``' in the
mathematics; weights are the int8-rounded ones the engine holds. **One open
question, written down and not settled here**: the model repository's own
``modeling_deepseek.py`` (DeepSeek-V2-Lite's ``auto_map``) also multiplies
the softmax scale by mscale(factor, mscale_all_dim)² when ``mscale_all_dim``
is set (1.59 at Lite's factor 40 and 0.707). ``transformers``'
``DeepseekV2Attention`` does not, ``mla.softmax_scale`` applies it for
``deepseek_v3`` only, and this reference follows ``transformers``. With
random weights the two differ by a temperature of the attention scores
that both sides share; which of them the released checkpoint wants is for
the ``model_config`` PR that serves real weights to decide (PERF.md §7).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_shared_expert", "unit_routing_weights",
             "no_kv_norm", "half_split_rope", "k_pe_unrotated")
_MOE_ONLY = ("no_shared_expert", "unit_routing_weights")


def breakages_for(hf: dict) -> tuple:
    """Those of BREAKAGES that change this configuration's mathematics."""
    moe = family(hf)["experts"] > 0
    return tuple(b for b in BREAKAGES if moe or b not in _MOE_ONLY)


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "deepseek_v2":
        raise ValueError(f"the deepseek_v2 reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "topk_method": hf.get("topk_method", "greedy") != "greedy",
        "scoring_func": hf.get("scoring_func", "softmax") != "softmax",
        "norm_topk_prob": bool(hf.get("norm_topk_prob")),
        "attention_bias": bool(hf.get("attention_bias")),
    }
    if any(refused.values()):
        raise ValueError("the deepseek_v2 reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    experts = int(hf.get("n_routed_experts") or 0)
    return {
        "layers": int(hf["num_hidden_layers"]),
        "heads": int(hf["num_attention_heads"]),
        "rank": int(hf["kv_lora_rank"]),
        "dn": int(hf["qk_nope_head_dim"]), "dr": int(hf["qk_rope_head_dim"]),
        "dv": int(hf["v_head_dim"]),
        "eps": float(hf["rms_norm_eps"]),
        "experts": experts,
        "top_k": int(hf.get("num_experts_per_tok") or 0),
        "first_dense": (int(hf.get("first_k_dense_replace") or 0)
                        if experts else int(hf["num_hidden_layers"])),
        "shared": (int(hf.get("n_shared_experts") or 0)
                   * int(hf.get("moe_intermediate_size") or 0)),
        "routed_scaling": float(hf.get("routed_scaling_factor") or 1.0),
    }


def _mscale(scale: float, m: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_frequencies(hf: dict) -> tuple:
    """→ (inv_freq [dr/2] float32, the factor on cos and sin)."""
    d, base = int(hf["qk_rope_head_dim"]), float(hf["rope_theta"])
    pos = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling")
    if not rs:
        return (1.0 / pos).astype(np.float32), 1.0
    kind = rs.get("rope_type", rs.get("type"))
    if kind != "yarn":
        raise ValueError(f"the deepseek_v2 reference has no {kind!r} rope "
                         "scaling")
    factor = float(rs["factor"])
    if rs.get("attention_factor"):
        att = float(rs["attention_factor"])
    elif rs.get("mscale") and rs.get("mscale_all_dim"):
        att = (_mscale(factor, float(rs["mscale"]))
               / _mscale(factor, float(rs["mscale_all_dim"])))
    else:
        att = _mscale(factor)
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
    return inv.astype(np.float32), att


def _rope(x, inv_freq, att, broken=None):
    """x: [T, heads, dr], positions 0..T-1; pairs (2i, 2i+1) rotated."""
    T, _, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * att, jnp.sin(ang)[:, None, :] * att
    if broken == "half_split_rope":     # llama.py's convention, misapplied
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names, still as stored
    (int8 and scale apart until the jitted layer dequantises them). The
    attention stacks run over all layers, the dense MLP's over the first
    ``first_dense`` and the experts' over the rest."""
    def get(name, i):
        w = params.get(f"layers.{name}")
        if w is None:
            return None
        return (w.q[i], w.scale[i]) if hasattr(w, "q") else w[i]
    out = {n: get(n, li) for n in ("ln1", "ln2", "wq", "wkv_a", "kv_norm",
                                   "wkv_b", "wo")}
    if li >= fam["first_dense"]:
        names = ("router", "moe_gate", "moe_up", "moe_gateup", "moe_down",
                 "sh_gate", "sh_up", "sh_gateup", "sh_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    elif fam["experts"]:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    else:
        out.update({n: get(n, li) for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def make_layer(fam: dict, hf: dict, moe: bool, broken=None):
    """→ jitted f(h [T, D] f32, layer weights) → h, for a dense layer or
    for an expert layer."""
    H, rank = fam["heads"], fam["rank"]
    dn, dr, dv, eps = fam["dn"], fam["dr"], fam["dv"], fam["eps"]
    inv_np, att = rope_frequencies(hf)

    def layer(h, lw):
        T = h.shape[0]
        inv = jnp.asarray(inv_np)
        a = _rms(h, _w(lw["ln1"]), eps)
        q = (a @ _w(lw["wq"])).reshape(T, H, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv, att, broken)
        kv = a @ _w(lw["wkv_a"])                                 # [T, rank+dr]
        c = kv[:, :rank]
        if broken != "no_kv_norm":
            c = _rms(c, _w(lw["kv_norm"]), eps)
        k_pe = kv[:, None, rank:]                                # [T, 1, dr]
        if broken != "k_pe_unrotated":
            k_pe = _rope(k_pe, inv, att, broken)
        kv_up = (c @ _w(lw["wkv_b"])).reshape(T, H, dn + dv)
        k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_pe, k_pe[:, 0])) * (dn + dr) ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        att_out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
        h = h + att_out.reshape(T, H * dv) @ _w(lw["wo"])
        m = _rms(h, _w(lw["ln2"]), eps)
        if not moe:
            g, u = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
            return h + _swiglu(m, g, u, _w(lw["down"]))
        E, K = fam["experts"], fam["top_k"]
        probs = jax.nn.softmax(m @ _w(lw["router"]), -1)          # [T, E]
        top_p, top_i = jax.lax.top_k(probs, K)
        top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)             # [T, E]

        # one expert at a time: its weights dequantised inside the step
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
        return h + out

    return jax.jit(layer)


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None) -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it."""
    fam = family(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = embed_rows(params, tokens)
        dense = make_layer(fam, hf, False, broken)
        sparse = make_layer(fam, hf, True, broken)
        n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
        for li in range(n_layers):
            layer = dense if li < fam["first_dense"] else sparse
            h = layer(h, _layer_weights(params, li, fam))
        return np.asarray(head_logits(params, hf, h[-last:], fam["eps"]),
                          np.float32)
