"""The plain reference of the llama family's block, and the comparison that
decides ``correct``.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes), a full causal
forward over the whole sequence: no kernels, no cache, no batching, no
fused or padded layouts. It follows the published modelling code of
Mistral / Mixtral / Qwen2-MoE (Hugging Face ``transformers``):

    h   = embed[tokens]
    per layer:
      a = RMSNorm(h) ; q,k,v = a·Wq(+bq), a·Wk(+bk), a·Wv(+bv)
      q,k = rope(q), rope(k)            (half-split rotation, theta from config)
      h  += softmax(q·kᵀ/√d + causal)·v · Wo     (GQA: kv heads repeated)
      m = RMSNorm(h)
      dense:  h += (silu(m·Wg) * (m·Wu)) · Wd
      MoE:    p = softmax(m·Wr) over all experts; top-k of p;
              norm_topk_prob → the k weights renormalised to sum 1
              h += Σ_k w_k · expert_k(m)  [+ sigmoid(m·Ws) · shared(m)]
    logits = RMSNorm(h) · W_head

It reads the engine's own parameter tree — the weights the engine serves,
int8 dequantised as q·scale, fused tensors split where the program's
``fuse_stacked_matmuls`` joined them — one layer and one expert at a time,
so that it fits beside the engine. What it trusts is therefore the stored
weights and their layout; every operation on them is its own.

Departures from the published code: none in the mathematics; weights are
the int8-rounded ones the engine holds (the engine is not compared with an
unquantised model: quantisation error is a deployment choice, not a fault).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# The engine computes in bf16 (8 mantissa bits) with float32 accumulation;
# the reference in float32 throughout. Each of the 2L residual additions
# takes a branch output with a relative rounding error near 2^-8, so the
# final hidden state differs by roughly sqrt(2L)·2^-8 ≈ 3% of its norm at
# L=32 (2% at L=12), and the logits by that share of their own spread.
# Measured on the chip (PR 27): see PERF.md section 6. The tolerance is a
# quarter of the reference logits' standard deviation at the position:
# three to eight times what bf16 explains, and under what a fault costs —
# leaving out one layer, the shared expert or the routing weights moves
# the logits by most of a standard deviation or more (shown on the CPU in
# selftest.py at tiny widths, where the same three breakages must fail).
TOL_STD = 0.25

BREAKAGES = ("drop_layer", "no_shared_expert", "unit_routing_weights")


def breakages_for(hf: dict) -> tuple:
    """Those of BREAKAGES that change this configuration's mathematics."""
    fam = family(hf)
    return tuple(b for b in BREAKAGES if b == "drop_layer"
                 or (b == "no_shared_expert" and fam["shared"])
                 or (b == "unit_routing_weights" and fam["experts"]))


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    mt = hf["model_type"]
    heads = int(hf["num_attention_heads"])
    hidden = int(hf["hidden_size"])
    moe = mt in ("qwen2_moe", "mixtral")
    out = {
        "hidden": hidden, "heads": heads,
        "kv_heads": int(hf.get("num_key_value_heads", heads)),
        "head_dim": int(hf.get("head_dim") or hidden // heads),
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf["rope_theta"]),
        "layers": int(hf["num_hidden_layers"]),
        # Qwen2 / Qwen2-MoE modelling code has the qkv bias unconditionally
        "qkv_bias": bool(hf.get("attention_bias", mt in ("qwen2",
                                                         "qwen2_moe"))),
        "experts": 0, "top_k": 0, "norm_topk": True, "shared": 0,
    }
    if moe:
        out["experts"] = int(hf.get("num_experts")
                             or hf.get("num_local_experts"))
        out["top_k"] = int(hf["num_experts_per_tok"])
        # Mixtral renormalises always; Qwen2-MoE by its config key
        out["norm_topk"] = (True if mt == "mixtral"
                            else bool(hf.get("norm_topk_prob", False)))
        out["shared"] = int(hf.get("shared_expert_intermediate_size") or 0)
    windowed = (hf.get("use_sliding_window") if "use_sliding_window" in hf
                else hf.get("sliding_window"))
    if windowed or hf.get("rope_scaling"):
        raise ValueError("the reference has no sliding window or rope "
                         "scaling; this configuration needs them")
    return out


def _f32(w, idx=None):
    """A weight, or its leading-axis slice, as float32; an int8 tensor is
    q·scale (the program's QuantizedArray: attributes ``q`` and ``scale``,
    per-output-channel, no groups)."""
    if hasattr(w, "q"):
        if getattr(w, "group", 0) or getattr(w, "packed4", False):
            raise ValueError("the reference reads int8 per-channel weights")
        q, s = (w.q, w.scale) if idx is None else (w.q[idx], w.scale[idx])
        return q.astype(jnp.float32) * s.astype(jnp.float32)
    return (w if idx is None else w[idx]).astype(jnp.float32)


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names, still as stored
    (int8 + scale stay apart until the jitted layer dequantises them)."""
    def get(name):
        w = params.get(f"layers.{name}")
        if w is None:
            return None
        return (w.q[li], w.scale[li]) if hasattr(w, "q") else w[li]
    names = ["ln1", "ln2", "wq", "wk", "wv", "wqkv", "wo", "bq", "bk", "bv",
             "gate", "up", "gateup", "down", "router", "moe_gate", "moe_up",
             "moe_gateup", "moe_down", "sh_gate", "sh_up", "sh_gateup",
             "sh_down", "sh_router"]
    return {n: w for n in names if (w := get(n)) is not None}


def _w(x) -> jax.Array:
    if isinstance(x, tuple):
        return x[0].astype(jnp.float32) * x[1].astype(jnp.float32)
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, H, d]; rotate_half convention, positions 0..T-1."""
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _split(pair, fused):
    """(gate, up) from separate tensors, or from the fused one, which
    ``fuse_stacked_matmuls`` joined as gate|up along the last axis."""
    if fused is None:
        return _w(pair[0]), _w(pair[1])
    w = _w(fused)
    half = w.shape[-1] // 2
    return w[..., :half], w[..., half:]


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def make_layer(fam: dict, broken=None):
    """→ jitted f(h [T, D] f32, layer weights) → h."""
    H, KVH, d = fam["heads"], fam["kv_heads"], fam["head_dim"]
    eps, theta = fam["eps"], fam["theta"]

    def layer(h, lw):
        T = h.shape[0]
        a = _rms(h, _w(lw["ln1"]), eps)
        if "wqkv" in lw:
            w = _w(lw["wqkv"])
            wq, wk, wv = (w[:, :H * d], w[:, H * d:(H + KVH) * d],
                          w[:, (H + KVH) * d:])
        else:
            wq, wk, wv = _w(lw["wq"]), _w(lw["wk"]), _w(lw["wv"])
        q, k, v = a @ wq, a @ wk, a @ wv
        if fam["qkv_bias"]:
            q, k, v = q + _w(lw["bq"]), k + _w(lw["bk"]), v + _w(lw["bv"])
        q = _rope(q.reshape(T, H, d), theta)
        k = _rope(k.reshape(T, KVH, d), theta)
        v = v.reshape(T, KVH, d)
        k = jnp.repeat(k, H // KVH, axis=1)
        v = jnp.repeat(v, H // KVH, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        att = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
        h = h + att.reshape(T, H * d) @ _w(lw["wo"])
        m = _rms(h, _w(lw["ln2"]), eps)
        if not fam["experts"]:
            g, u = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
            return h + _swiglu(m, g, u, _w(lw["down"]))
        E, K = fam["experts"], fam["top_k"]
        probs = jax.nn.softmax(m @ _w(lw["router"]), -1)          # [T, E]
        top_p, top_i = jax.lax.top_k(probs, K)
        if fam["norm_topk"]:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)             # [T, E]

        # one expert at a time: its weights dequantised inside the step
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])
        xs = {"gu": gu, "down": lw["moe_down"], "w": weight.T}

        def expert(acc, x):
            if fused:
                w = _w(x["gu"])
                F = w.shape[-1] // 2
                g, u = w[:, :F], w[:, F:]
            else:
                g, u = _w(x["gu"][0]), _w(x["gu"][1])
            y = _swiglu(m, g, u, _w(x["down"]))
            return acc + x["w"][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m), xs)
        if fam["shared"] and broken != "no_shared_expert":
            g, u = _split((lw.get("sh_gate"), lw.get("sh_up")),
                          lw.get("sh_gateup"))
            gate = jax.nn.sigmoid(m @ _w(lw["sh_router"]))        # [T, 1]
            out = out + gate * _swiglu(m, g, u, _w(lw["sh_down"]))
        return h + out

    return jax.jit(layer)


def embed_rows(params: dict, tokens) -> jax.Array:
    """The embedding rows of ``tokens`` as float32 (int8: q·scale per row)."""
    emb = params["embed"]
    if hasattr(emb, "q"):
        return (emb.q[tokens].astype(jnp.float32)
                * emb.scale[tokens].astype(jnp.float32))
    return emb[tokens].astype(jnp.float32)


def head_logits(params: dict, hf: dict, h, eps: float) -> jax.Array:
    """RMSNorm(h) · W_head → float32 [rows, V], the head dequantised a
    slice at a time."""
    x = _rms(h, _f32(params["final_norm"]), eps)
    head = params.get("lm_head")
    chunks = []
    V = int(hf["vocab_size"])
    step = 16384
    for lo in range(0, V, step):
        if head is None:          # tied: the embedding, transposed
            w = _f32(params["embed"], slice(lo, lo + step)).T
        elif hasattr(head, "q"):
            w = (head.q[:, lo:lo + step].astype(jnp.float32)
                 * head.scale[..., lo:lo + step].astype(jnp.float32))
        else:
            w = head[:, lo:lo + step].astype(jnp.float32)
        chunks.append(x @ w)
    return jnp.concatenate(chunks, -1)


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None) -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it."""
    fam = family(hf)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = embed_rows(params, tokens)
        layer = make_layer(fam, broken)
        n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
        for li in range(n_layers):
            h = layer(h, _layer_weights(params, li, fam))
        return np.asarray(head_logits(params, hf, h[-last:], fam["eps"]),
                          np.float32)


def compare(params: dict, hf: dict, prompt: list, served_ids: list,
            served_logprobs: list, broken=None, forward=None) -> dict:
    """Holds one served greedy continuation to the reference (this
    module's ``logits_for``, or as ``forward`` that of the module the
    configuration names, ``references/<name>.py``; the comparison and the
    tolerance are the same for every family): at every
    served position the served token's logprob must be within the
    tolerance of the reference's logprob for that token, and that token's
    reference logit within the same tolerance of the reference maximum
    (random-weight logits are near-tied, so token equality would be
    noise). → {"ok", "worst_logprob_err_std", "worst_argmax_gap_std", ...}
    with errors in units of the reference logits' standard deviation."""
    n = len(served_ids)
    seq = list(prompt) + list(served_ids[:-1])
    logits = (forward or logits_for)(params, hf, seq, n, broken)  # [n, V]
    worst_lp = worst_gap = 0.0
    for i, (tok, lp) in enumerate(zip(served_ids, served_logprobs)):
        row = logits[i].astype(np.float64)
        std = float(row.std())
        ref_lp = row - (row.max() + math.log(np.exp(row - row.max()).sum()))
        worst_lp = max(worst_lp, abs(ref_lp[tok] - lp) / std)
        worst_gap = max(worst_gap, (row.max() - row[tok]) / std)
    return {"ok": bool(worst_lp <= TOL_STD and worst_gap <= TOL_STD),
            "worst_logprob_err_std": worst_lp,
            "worst_argmax_gap_std": worst_gap,
            "logits_std": float(logits.std()), "tol_std": TOL_STD,
            "positions": n}
