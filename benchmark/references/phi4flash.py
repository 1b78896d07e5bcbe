"""The plain reference of the SambaY decoder-hybrid-decoder block
(``model_type: phi4flash``, Phi-4-mini-flash-reasoning): the forward pass
only. The comparison and its tolerance are ``reference.compare`` /
``reference.TOL_STD``, the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence with a sequential scan over
time: no cache, no ring, no kernel, no padded heads, no scan over layers.
Source: Ren et al., "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation" (arXiv:2507.06607), whose abstract names
this model as SambaY with differential attention, and the model
repository's ``modeling_phi4flash.py``, both from memory (no network here).

With D = ``hidden_size``, H / KVH query / key-value heads of dh = D / H,
F = ``intermediate_size``, W = ``sliding_window``, and the Mamba-1 sizes
Di = 2 D, N = 16 states, d_conv = 4, R = ceil(D / 16):

    h = embed[tokens]                          no positional encoding anywhere
    every layer l:  h += Mixer_l(LN(h));  h += MLP(LN'(h))
      LN, LN' = LayerNorm with weight and bias, eps layer_norm_eps
      MLP(x)  = (up * silu(gate)) @ W2,  [gate | up] = x @ W1   (no bias)
    logits = LN_f(h) @ embed^T                 (tied head)

The mixer by index, with half = L/2 (see "Departures" for odd halves):

    l < half, even   Mamba-1:  x, z = split(a @ W_in)
                     x  = silu(conv1d_causal(x; w [Di, 4], b))
                     dt, B, C = split(x @ W_x)            (R + N + N, no bias)
                     Delta = softplus(dt @ W_dt + b_dt);  A = -exp(A_log) [Di, N]
                     s_t = exp(Delta_t A) * s_{t-1} + (Delta_t x_t) (x) B_t
                     y_t = s_t . C_t + D * x_t
                     out = (y * silu(z)) @ W_out
    l < half, odd    window differential attention: q, k, v = split(a @ W_qkv
                     + b) (H dh + KVH dh + KVH dh); position t sees the W keys
                     t-W+1 .. t
    l = half         Mamba-1 as above, and it exports its memory
                     m_t = s_t . C_t + D * x_t  (BEFORE the silu(z) gate)
    l = half + 1     full differential attention, causal over the whole
                     context; its K and V are the model's only full-length cache
    l > half+1, even gated memory unit: out = (m * silu(a @ W_g)) @ W_o, m the
                     exporting layer's at the same position
    l > half+1, odd  cross differential attention: q = a @ W_q + b only; keys
                     and values are the full layer's, causal over the whole
                     context; own lambda, sub-norm and output projection

Differential attention (all three): adjacent heads pair up. Query heads
(2j, 2j+1) = (q1, q2) for j < H/2; key heads (2i, 2i+1) = (k1, k2) and the
value [v_2i | v_2i+1] (2 dh wide) for i < KVH/2; pair j reads pair
j // (H / KVH).  A^s = softmax(q^s k^s^T / sqrt(dh)), causal (and windowed);
o_j = A^1 v - lambda A^2 v;
lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
lambda_init = 0.8 - 0.6 exp(-0.3 l);
o_j <- RMSNorm_{2dh}(o_j; eps layer_norm_eps, weight) * (1 - lambda_init);
out = concat_j(o_j) @ W_out + b.

It reads the engine's own parameter tree (``models/sambay.py``
``param_shapes``: one stack per layer kind, ``layers.<kind>.<leaf>``; int8
as q * scale; ``A_log`` stored [N, Di], ``conv_w`` [4, Di], the lambda
vectors as rows lq1, lk1, lq2, lk2 of ``lam``), one layer at a time. What
it trusts is the stored weights and their layout; every operation on them
is its own.

Departures from the published code: weights are the int8-rounded ones the
engine holds; for a depth whose half is odd (tiny test models) the split
moves down to the even number below, as ``sambay.layer_kinds`` does, so
that a model of 6 layers has one layer of every kind (the published depth
32 is not affected). Nothing else that I know of; what the catalog's
``config`` does not carry is listed under ``assumed`` in
``benchmark/configs/phi4-mini-flash.json``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _f32, _w, embed_rows

BREAKAGES = ("drop_layer", "no_window", "window_off_by_one", "lambda_zero",
             "no_subnorm", "memory_after_gate", "cross_reads_window_kv",
             "no_conv", "no_dt_bias", "no_skip_D")
_NEEDS = {"no_window": "window", "window_off_by_one": "window",
          "memory_after_gate": "gmu", "cross_reads_window_kv": "cross"}
# the layer kinds whose mathematics a breakage changes (``drop_layer``
# changes none: it leaves a layer out)
_AFFECTS = {"no_window": ("window",), "window_off_by_one": ("window",),
            "lambda_zero": ("window", "full", "cross"),
            "no_subnorm": ("window", "full", "cross"),
            "memory_after_gate": ("export",),
            "cross_reads_window_kv": ("window", "full"),
            "no_conv": ("mamba", "export"), "no_dt_bias": ("mamba", "export"),
            "no_skip_D": ("mamba", "export")}


def layer_kinds(hf: dict) -> tuple:
    L = int(hf["num_hidden_layers"])
    half = (L // 2) & ~1
    kinds = []
    for l in range(L):
        if l < half:
            kinds.append("mamba" if l % 2 == 0 else "window")
        elif l <= half + 1:
            kinds.append("export" if l == half else "full")
        else:
            kinds.append("gmu" if l % 2 == 0 else "cross")
    return tuple(kinds)


def breakages_for(hf: dict) -> tuple:
    """Those of BREAKAGES that change this configuration's mathematics
    (``cross_reads_window_kv`` needs both a window and a cross layer)."""
    kinds = set(layer_kinds(hf))
    return tuple(b for b in BREAKAGES
                 if _NEEDS.get(b, "export") in kinds
                 and (b != "cross_reads_window_kv" or "window" in kinds))


def family(hf: dict) -> dict:
    D, H = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    return {"D": D, "H": H, "KVH": int(hf.get("num_key_value_heads", H)),
            "dh": int(hf.get("head_dim") or D // H),
            "eps": float(hf.get("layer_norm_eps", 1e-5)),
            "W": int(hf["sliding_window"]),
            "Di": int(hf.get("mamba_expand", 2)) * D,
            "N": int(hf.get("mamba_d_state", 16)),
            "K": int(hf.get("mamba_d_conv", 4)),
            "R": int(hf.get("mamba_dt_rank") or -(-D // 16))}


def _layer_weights(params: dict, kind: str, i: int) -> dict:
    """Layer ``i`` of the ``kind`` stack under plain names, still as stored
    (int8 + scale stay apart until the jitted layer dequantises them)."""
    out = {}
    prefix = f"layers.{kind}."
    for name, w in params.items():
        if name.startswith(prefix):
            out[name[len(prefix):]] = ((w.q[i], w.scale[i])
                                       if hasattr(w, "q") else w[i])
    return out


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mlp(h, lw, eps):
    a = _ln(h, _w(lw["ln2_w"]), _w(lw["ln2_b"]), eps)
    gu = a @ _w(lw["mlp_gateup"])
    F = gu.shape[-1] // 2
    return h + (gu[:, F:] * jax.nn.silu(gu[:, :F])) @ _w(lw["mlp_down"])


def _mamba(a, lw, fam, broken):
    """-> (the mixer's output [T, D], the memory m [T, Di])."""
    T = a.shape[0]
    Di, N, K, R = fam["Di"], fam["N"], fam["K"], fam["R"]
    xz = a @ _w(lw["ssm_in"])
    x, z = xz[:, :Di], xz[:, Di:]
    if broken != "no_conv":
        w = _w(lw["conv_w"])                                     # [K, Di]
        xp = jnp.concatenate([jnp.zeros((K - 1, Di), jnp.float32), x])
        x = _w(lw["conv_b"]) + sum(w[k] * xp[k:k + T] for k in range(K))
    x = jax.nn.silu(x)
    dbc = x @ _w(lw["ssm_x"])
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    dt = dt @ _w(lw["ssm_dt"])
    if broken != "no_dt_bias":
        dt = dt + _w(lw["dt_b"])
    delta = jax.nn.softplus(dt)                                  # [T, Di]
    A = -jnp.exp(_w(lw["A_log"])).T                              # [Di, N]

    def step(s, xs):
        d_t, x_t, b_t, c_t = xs
        s = jnp.exp(d_t[:, None] * A) * s + (d_t * x_t)[:, None] * b_t[None]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((Di, N), jnp.float32),
                        (delta, x, B, C))
    if broken != "no_skip_D":
        y = y + _w(lw["D"]) * x
    gated = y * jax.nn.silu(z)
    return gated @ _w(lw["ssm_out"]), (gated if broken == "memory_after_gate"
                                       else y)


def _diff_attention(q, k, v, lw, lam_init, fam, broken, window=None):
    """q [T, H*dh], k / v [S, KVH*dh] (S = T: causal) -> [T, D]."""
    T, H, KVH, dh = q.shape[0], fam["H"], fam["KVH"], fam["dh"]
    q = q.reshape(T, H // 2, 2, dh)
    k = k.reshape(T, KVH // 2, 2, dh)
    v = v.reshape(T, KVH // 2, 2 * dh)
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)                   # pair j reads j // rep
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("tjsd,ujsd->jstu", q, k) / math.sqrt(dh)
    t, u = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = u <= t
    if window is not None and broken != "no_window":
        seen = seen & (u > t - window
                       - (1 if broken == "window_off_by_one" else 0))
    s = jnp.where(seen[None, None], s, -jnp.inf)
    att = jnp.einsum("jstu,ujd->tjsd", jax.nn.softmax(s, -1), v)
    lam = _w(lw["lam"])
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
           - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam_init)
    if broken == "lambda_zero":
        lam = 0.0
    o = att[:, :, 0] - lam * att[:, :, 1]                        # [T, H/2, 2dh]
    if broken != "no_subnorm":
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                               + fam["eps"]) * _w(lw["subnorm"]))
    o = o * (1.0 - lam_init)
    return o.reshape(T, -1) @ _w(lw["attn_out"]) + _w(lw["attn_out_b"])


def make_layer(kind: str, fam: dict, broken=None):
    """-> jitted f(h [T, D], layer weights, shared, lambda_init) ->
    (h, shared); shared carries the memory ``m`` and the K / V that later
    layers read. One function per (kind, sizes, breakage), kept, so that
    the layers of a kind share one compilation."""
    return _make_layer(kind, tuple(sorted(fam.items())),
                       broken if kind in _AFFECTS.get(broken, ()) else None)


@functools.lru_cache(maxsize=None)
def _make_layer(kind: str, sizes: tuple, broken):
    fam = dict(sizes)
    eps = fam["eps"]
    Hq, Hkv = fam["H"] * fam["dh"], fam["KVH"] * fam["dh"]

    def f(h, lw, shared, lam_init):
        a = _ln(h, _w(lw["ln1_w"]), _w(lw["ln1_b"]), eps)
        if kind in ("mamba", "export"):
            out, m = _mamba(a, lw, fam, broken)
            if kind == "export":
                shared = dict(shared, m=m)
        elif kind in ("window", "full"):
            qkv = a @ _w(lw["attn_qkv"]) + _w(lw["attn_qkv_b"])
            q, k, v = (qkv[:, :Hq], qkv[:, Hq:Hq + Hkv], qkv[:, Hq + Hkv:])
            out = _diff_attention(q, k, v, lw, lam_init, fam, broken,
                                  window=fam["W"] if kind == "window"
                                  else None)
            if kind == "full" and broken != "cross_reads_window_kv":
                shared = dict(shared, k=k, v=v)
            if kind == "window" and broken == "cross_reads_window_kv":
                shared = dict(shared, k=k, v=v)
        elif kind == "gmu":
            out = ((shared["m"] * jax.nn.silu(a @ _w(lw["gmu_in"])))
                   @ _w(lw["gmu_out"]))
        else:
            q = a @ _w(lw["cross_q"]) + _w(lw["cross_q_b"])
            out = _diff_attention(q, shared["k"], shared["v"], lw,
                                  lam_init, fam, broken)
        return _mlp(h + out, lw, eps), shared

    return jax.jit(f)


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None) -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it."""
    fam = family(hf)
    kinds = layer_kinds(hf)
    if broken == "drop_layer":
        kinds = kinds[:-1]
    tokens = jnp.asarray(tokens, jnp.int32)
    seen = {k: 0 for k in set(kinds)}
    with jax.default_matmul_precision("highest"):
        h = embed_rows(params, tokens)
        shared = {}
        for l, kind in enumerate(kinds):
            h, shared = make_layer(kind, fam, broken)(
                h, _layer_weights(params, kind, seen[kind]), shared,
                0.8 - 0.6 * math.exp(-0.3 * l))
            seen[kind] += 1
        x = _ln(h[-last:], _f32(params["final_norm"]),
                _f32(params["final_norm_b"]), fam["eps"])
        head = params.get("lm_head")
        V, step, chunks = int(hf["vocab_size"]), 16384, []
        for lo in range(0, V, step):
            if hf.get("tie_word_embeddings", True) or head is None:
                w = _f32(params["embed"], slice(lo, lo + step)).T
            elif hasattr(head, "q"):
                w = (head.q[:, lo:lo + step].astype(jnp.float32)
                     * head.scale[..., lo:lo + step].astype(jnp.float32))
            else:
                w = head[:, lo:lo + step].astype(jnp.float32)
            chunks.append(x @ w)
        return np.asarray(jnp.concatenate(chunks, -1), np.float32)
