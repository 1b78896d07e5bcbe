"""The plain reference of the Granite 4.0-H block (``model_type:
granitemoehybrid``: Mamba-2 layers beside grouped-query attention layers with
no positional term, softmax-over-the-picked-logits experts with an ungated
shared expert after EVERY layer): the forward pass only. The comparison and
its tolerance are ``reference.compare`` / ``reference.TOL_STD``, the same for
every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full forward over the whole sequence: the state-space recurrence a token at
a time exactly as written below (no chunks, no matmul form, no kernel), the
attention layers by full causal softmax (no cache, no key blocks), one layer
at a time (no scan over layers), one expert at a time. The mechanism is
Mamba-2's (Dao & Gu, arXiv:2405.21060) inside the published ``transformers``
modelling code of GraniteMoeHybrid; there is no network here, so every line
is stated, for a reader who has that code to check. D = ``hidden_size``.

    h = embedding_multiplier * embed[tokens]
    per layer:   h += residual_multiplier * Mix(RMSNorm(h; ln1))
                 h += residual_multiplier * (Experts(u) + Shared(u)),
                                             u = RMSNorm(h; ln2)
    logits = RMSNorm(h; final_norm) embed^T / logits_scaling     (tied head)

**Mix, a layer whose ``layer_types`` entry is "mamba"** — H = ``mamba_n_heads``
heads of P = ``mamba_d_head`` lanes, N = ``mamba_d_state``, one B / C group,
d_inner = H P, taps = ``mamba_d_conv``, x the normed input:

    [z | xBC | dt] = x W_in                   (D -> d_inner | d_inner + 2N | H)
    xBC_t = silu( sum_{j < taps} w[j] * xBC_{t - taps + 1 + j} + b )
                          depthwise, causal (zeros before position 0)
                          -> x'_t [H, P] | B_t [N] | C_t [N]
    dt_t = softplus(dt_t + dt_bias) [H] ;  A = -exp(A_log) [H]
    S_0 = 0 [H, P, N] float32, and per head for t = 1..T:
        S_t = exp(dt_t A) S_{t-1} + dt_t x'_t (x) B_t
        y_t = S_t C_t + D x'_t
    Mix = RMSNorm_{d_inner}(y_t * silu(z_t); norm) W_out    (gate, THEN one norm
                                          over all d_inner lanes: one group)

**Mix, a layer whose entry is "attention"** — Hq = ``num_attention_heads``,
KVH = ``num_key_value_heads``, Dh = D / Hq:

    q, k, v = x W_q, x W_k, x W_v          (no bias, NO rotation: "nope")
    causal softmax(q k^T * attention_multiplier) v over every earlier position
    (``attention_multiplier`` = 1/128 at the published sizes, NOT Dh^-1/2)
    Mix = concat_h(.) W_o

**Experts** — ``l = u W_r`` over the ``num_local_experts``; the
``num_experts_per_tok`` largest of l; ``w = softmax`` over those logits alone;
``sum_e w_e expert_e(u)``, an expert ``(silu(u W_g) * (u W_u)) W_d`` at
``intermediate_size``. **Shared** — the same SwiGLU at
``shared_intermediate_size``, added as it is (no gate).

It reads the engine's own parameter tree (``models/granite_hybrid.py``
``param_shapes`` names: the M layers' leaves ``layers.ssd_*`` at the layer's
index among the M layers, the A layers' ``layers.wq`` ... among the A layers,
norms and experts at the layer's own; int8 as q * scale; q|k|v and gate|up
split where ``fuse_stacked_matmuls`` joined them).

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states, which the comparison has to
tell from the program's). ``int4_weights`` rounds the weights of every matmul
the program holds in int8 to 4 bits under one scale per 128 input rows and
output column, ``quant.quantize_array_grouped``'s rule; the router, the
embedding and the head stay as stored.

**Leaves** (``leaves_for`` / ``TAPPED`` / ``LEAF_TOL``). Two breakages change
what the cache HOLDS by more than they may change a logit under seeded
weights: the state rounded to bf16 every token, and the keys rotated. So the
reference also gives the first M layer's state after the last token and the
first A layer's key row of every token, which a check holds the engine's own
cache leaves to (``kv["ssd"][0, slot]`` through ``ssd.state_to_hpn``, the
pool's ``k`` rows): ``leaf_error`` inside ``LEAF_TOL`` for the unbroken
reference, outside it for the breakage.

Departures from the published model, each shared with the program (the
configuration file lists them under ``assumed``): ``intermediate_size`` is
read as one expert's width; the in-projection's columns are z | xBC | dt;
``A_log`` / ``dt_bias`` / ``D`` are one scalar a head; ``time_step_limit`` is
(0, inf), so dt is not clamped; weights are the int8-rounded ones the engine
holds.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _rope, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "score_scale_sqrt", "rotated",
             "softmax_before_pick", "no_shared_expert", "residual_one",
             "embedding_one", "logits_one", "no_D", "no_dt_bias",
             "no_conv_bias", "norm_before_gate", "decay_without_dt",
             "state_bf16")

CONTROLS = ("int4_weights",)

# the breakages a cache leaf shows where served logits may not (module
# docstring, "Leaves"): which leaf of ``leaves_for`` each moves, and the
# relative error (``leaf_error``) a sound program's leaf stays inside. Set
# between two readings at the published widths on the chip, 1,500 tokens + 8
# steps (my chip run, PR 59, ``granite_moe_hybrid_check.py`` seed 59): the
# state 0.0043 off the reference's and 0.0168 off the bf16-rounded one
# (0.0044 and 0.0059 at rehearsal widths and 56 tokens on the CPU: too few
# tokens to tell them apart there); the key rows 0.022 and, rotated, 1.229
# (0.031 and 1.40). The served logits read 0.077 there, 0.185 under the bf16
# state and 0.247 under the rotation: inside the tolerance of 0.25, which is
# why these two are held by their leaves
TAPPED = {"state_bf16": "ssd", "rotated": "k"}
LEAF_TOL = {"ssd": 0.007, "k": 0.1}

_SSD_ONLY = ("no_D", "no_dt_bias", "no_conv_bias", "norm_before_gate",
             "decay_without_dt", "state_bf16")
_ATTN_ONLY = ("score_scale_sqrt", "rotated")


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
    """-> f(a stored weight) -> float32: ``_w``, and under ``int4_weights``
    what the program holds in int8 (a ``(q, scale)`` pair) rounded on."""
    if control != "int4_weights":
        return _w
    return lambda w: _int4_groups(_w(w)) if isinstance(w, tuple) else _w(w)


def breakages_for(hf: dict) -> tuple:
    """Those of BREAKAGES that served logits have to show: all of them but
    ``state_bf16``, which the state's leaf shows and a logit does not
    (``TAPPED``). ``rotated`` stays listed: logits show it at rehearsal
    widths (``selftest.py``), and at the published ones, where it read 0.247
    of a tolerance of 0.25, its leaf does."""
    family(hf)
    return tuple(b for b in BREAKAGES if b != "state_bf16")


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "granitemoehybrid":
        raise ValueError(f"the granite_moe_hybrid reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "position_embedding_type":
            hf.get("position_embedding_type", "nope") != "nope",
        "mamba_n_groups": int(hf.get("mamba_n_groups") or 1) != 1,
        "mamba_proj_bias": bool(hf.get("mamba_proj_bias")),
        "mamba_conv_bias (false)": not hf.get("mamba_conv_bias", True),
        "attention_bias": bool(hf.get("attention_bias")),
    }
    if any(refused.values()):
        raise ValueError("the granite_moe_hybrid reference does not compute "
                         "this configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    n = int(hf["num_hidden_layers"])
    D, Hq = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    H, P = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    return {
        "layers": n,
        "kinds": tuple("M" if t == "mamba" else "A"
                       for t in hf["layer_types"][:n]),
        "H": H, "P": P, "N": int(hf["mamba_d_state"]),
        "taps": int(hf["mamba_d_conv"]),
        "heads": Hq, "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": D // Hq,
        "attn_mult": float(hf["attention_multiplier"]),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "theta": float(hf.get("rope_theta") or 10000.0),
        "experts": int(hf["num_local_experts"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "emb_mult": float(hf["embedding_multiplier"]),
        "res_mult": float(hf["residual_multiplier"]),
        "logits_scaling": float(hf["logits_scaling"]),
    }


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names, still as stored
    (int8 and scale apart until the jitted layer dequantises them)."""
    def get(name, i):
        w = params.get(f"layers.{name}")
        if w is None:
            return None
        return (w.q[i], w.scale[i]) if hasattr(w, "q") else w[i]
    kind = fam["kinds"][li]
    ai = sum(1 for k in fam["kinds"][:li] if k == kind)
    out = {n: get(n, li) for n in (
        "ln1", "ln2", "router", "moe_gate", "moe_up", "moe_gateup",
        "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")}
    if kind == "M":
        out.update({n: get(f"ssd_{n}", ai) for n in (
            "in", "conv", "conv_b", "dt_bias", "A_log", "D", "norm", "out")})
    else:
        out.update({n: get(n, ai) for n in ("wq", "wk", "wv", "wqkv", "wo")})
    return {n: w for n, w in out.items() if w is not None}


def _gate_up(wt, pair, fused):
    """(gate, up) through ``wt`` from separate tensors, or from the fused
    one, which ``fuse_stacked_matmuls`` joined as gate|up along the last
    axis."""
    if fused is None:
        return wt(pair[0]), wt(pair[1])
    w = wt(fused)
    return w[..., :w.shape[-1] // 2], w[..., w.shape[-1] // 2:]


def mamba_mix(fam: dict, broken=None):
    """-> f(x [T, D] f32 the normed input, the layer's weights) -> ([T, D],
    the state after the last token [H, P, N]): the recurrence a token at a
    time."""
    H, P, N, taps, eps = (fam["H"], fam["P"], fam["N"], fam["taps"],
                          fam["eps"])
    di, cd = H * P, H * P + 2 * N
    wt = _weights(broken)

    def mix(x, lw):
        T = x.shape[0]
        proj = x @ wt(lw["in"])                       # [T, 2 di + 2N + H]
        z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
        w = wt(lw["conv"])                                       # [taps, cd]
        past = jnp.concatenate([jnp.zeros((taps - 1, cd)), xbc])
        xbc = sum(w[j] * past[j:j + T] for j in range(taps))
        if broken != "no_conv_bias":
            xbc = xbc + wt(lw["conv_b"])
        xbc = jax.nn.silu(xbc)
        xs = xbc[:, :di].reshape(T, H, P)
        B, C = xbc[:, di:di + N], xbc[:, di + N:]
        if broken != "no_dt_bias":
            dt = dt + wt(lw["dt_bias"])
        dt = jax.nn.softplus(dt)                                 # [T, H]
        A = -jnp.exp(wt(lw["A_log"]))
        decay = (jnp.broadcast_to(jnp.exp(A), dt.shape)
                 if broken == "decay_without_dt" else jnp.exp(dt * A))

        def token(S, xs_t):
            x_t, dt_t, a_t, B_t, C_t = xs_t
            S = (a_t[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
            if broken == "state_bf16":
                # an explicit rounding: XLA drops a convert to bf16 and
                # back as excess precision it is allowed to keep
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, jnp.einsum("hpn,n->hp", S, C_t)

        S, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                            (xs, dt, decay, B, C))               # [T, H, P]
        if broken != "no_D":
            y = y + wt(lw["D"])[None, :, None] * xs
        y, gate = y.reshape(T, di), jax.nn.silu(z)
        y = (_rms(y, wt(lw["norm"]), eps) * gate
             if broken == "norm_before_gate"
             else _rms(y * gate, wt(lw["norm"]), eps))
        return y @ wt(lw["out"]), S
    return mix


def attention_mix(fam: dict, broken=None):
    """-> f(x [T, D] f32, the layer's weights) -> ([T, D], the key row of
    every token [T, KVH Dh]): full causal grouped-query attention with no
    positional term."""
    Hq, KVH, Dh = fam["heads"], fam["kv_heads"], fam["head_dim"]
    wt = _weights(broken)
    scale = Dh ** -0.5 if broken == "score_scale_sqrt" else fam["attn_mult"]

    def mix(x, lw):
        T = x.shape[0]
        if "wqkv" in lw:
            qkv = x @ wt(lw["wqkv"])
            q, k, v = (qkv[:, :Hq * Dh], qkv[:, Hq * Dh:(Hq + KVH) * Dh],
                       qkv[:, (Hq + KVH) * Dh:])
        else:
            q, k, v = x @ wt(lw["wq"]), x @ wt(lw["wk"]), x @ wt(lw["wv"])
        q = q.reshape(T, Hq, Dh)
        k, v = k.reshape(T, KVH, Dh), v.reshape(T, KVH, Dh)
        if broken == "rotated":
            q, k = _rope(q, fam["theta"]), _rope(k, fam["theta"])
        g = Hq // KVH
        s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, KVH, g, Dh), k) * scale
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
        return out.reshape(T, Hq * Dh) @ wt(lw["wo"]), k.reshape(T, KVH * Dh)
    return mix


def moe_mlp(fam: dict, broken=None):
    """-> f(m [T, D] f32, the layer's weights) -> [T, D]: the picked experts
    under the softmax of their own logits, plus the shared expert."""
    wt = _weights(broken)

    def mlp(m, lw):
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        logits = m @ wt(lw["router"])
        if broken == "softmax_before_pick":
            top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
        else:
            top_l, top_i = jax.lax.top_k(logits, K)
            top_p = jax.nn.softmax(top_l, -1)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])

        def expert(acc, x):
            g, u = (_gate_up(wt, None, x["gu"]) if fused
                    else _gate_up(wt, x["gu"], None))
            return (acc + x["w"][:, None] * _swiglu(m, g, u, wt(x["down"])),
                    None)

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                              {"gu": gu, "down": lw["moe_down"],
                               "w": weight.T})
        if broken != "no_shared_expert":
            g, u = _gate_up(wt, (lw.get("sh_gate"), lw.get("sh_up")),
                            lw.get("sh_gateup"))
            out = out + _swiglu(m, g, u, wt(lw["sh_down"]))
        return out
    return mlp


def make_layer(fam: dict, kind: str, broken=None):
    """-> jitted f(h [T, D] f32, layer weights) -> (h, the mix's leaf)."""
    mix = (mamba_mix if kind == "M" else attention_mix)(fam, broken)
    experts = moe_mlp(fam, broken)
    wt = _weights(broken)
    rm = 1.0 if broken == "residual_one" else fam["res_mult"]

    def layer(h, lw):
        delta, leaf = mix(_rms(h, wt(lw["ln1"]), fam["eps"]), lw)
        h = h + rm * delta
        return h + rm * experts(_rms(h, wt(lw["ln2"]), fam["eps"]), lw), leaf
    return jax.jit(layer)


_LAYERS: dict = {}


def _layer(fam: dict, hf: dict, kind: str, broken):
    """``make_layer``, built once per configuration, kind and breakage; a
    breakage of one block leaves the other as it is."""
    if ((broken in _SSD_ONLY and kind != "M")
            or (broken in _ATTN_ONLY and kind != "A")):
        broken = None
    key = (json.dumps(hf, sort_keys=True), kind, broken)
    if key not in _LAYERS:
        _LAYERS[key] = make_layer(fam, kind, broken)
    return _LAYERS[key]


def forward(params: dict, hf: dict, tokens, broken=None,
            leaves=None) -> jax.Array:
    """-> the final hidden states [T, D] float32 (before the last norm).
    ``leaves``: a dict that takes the first M layer's state after the last
    token (``"ssd"``) and the first A layer's key rows (``"k"``)."""
    fam = family(hf)
    h = embed_rows(params, jnp.asarray(tokens, jnp.int32))
    if broken != "embedding_one":
        h = fam["emb_mult"] * h
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    for li in range(n_layers):
        kind = fam["kinds"][li]
        h, leaf = _layer(fam, hf, kind, broken)(
            h, _layer_weights(params, li, fam))
        if leaves is not None:
            leaves.setdefault({"M": "ssd", "A": "k"}[kind], leaf)
    return h


def leaves_for(params: dict, hf: dict, tokens, broken=None) -> dict:
    """What the cache holds after ``tokens``, float32: ``"ssd"`` the first M
    layer's state [H, P, N], ``"k"`` the first A layer's key row of every
    token [T, KVH Dh] (module docstring, "Leaves")."""
    leaves: dict = {}
    with jax.default_matmul_precision("highest"):
        forward(params, hf, tokens, broken, leaves)
    return {k: np.asarray(v, np.float32) for k, v in leaves.items()}


def leaf_error(held, want) -> float:
    """|held - want| / |want|, over the whole leaf."""
    held, want = (np.asarray(a, np.float64) for a in (held, want))
    return float(np.linalg.norm(held - want) / np.linalg.norm(want))


def logits_for(params: dict, hf: dict, tokens, last: int,
               broken=None, precision: str = "highest") -> np.ndarray:
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it. ``precision="default"``
    is the served precision (bf16 passes on a TPU), not a breakage."""
    fam = family(hf)
    with jax.default_matmul_precision(precision):
        h = forward(params, hf, tokens, broken)
        out = head_logits(params, hf, h[-last:], fam["eps"])
        if broken != "logits_one":
            out = out / fam["logits_scaling"]
        return np.asarray(out, np.float32)
