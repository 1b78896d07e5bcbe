"""The plain reference of the Kimi-Linear block (``model_type: kimi_linear``:
Kimi-Delta-Attention layers beside NoPE latent-attention layers, DeepSeek-V3's
sigmoid-routed experts): the forward pass only. The comparison and its
tolerance are ``reference.compare`` / ``reference.TOL_STD``, the same for
every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full forward over the whole sequence: the delta rule a token at a time
exactly as written below (no chunked form, no WY/UT matrices, no kernel), the
latent layers by full causal softmax (no cache, no absorbed form, no key
blocks), one layer at a time (no scan over layers), one expert at a time. The
mechanism is the Kimi Linear technical report's (arXiv:2510.26692); there is
no network here, so every line is stated, for a reader who has the published
modelling code to check. D = hidden_size; layers numbered from 1 as
``linear_attn_config``'s lists number them.

    h = embed[tokens]
    per layer:   h += Mix(RMSNorm(h; ln1)) ;  h += MLP(RMSNorm(h; ln2))

**Mix, a layer in ``kda_layers``** — H = ``linear_attn_config.num_heads``
heads of d = ``linear_attn_config.head_dim`` lanes, P = H d, x the normed
input, taps = ``short_conv_kernel_size``:

    [q~ | k~ | v~] = x W_in                             (D -> 3P, no bias)
    c_t = silu( sum_{j < taps} w[j] * [q~|k~|v~]_{t - taps + 1 + j} )
                                 depthwise, causal (zeros before position 0)
    q_t = q_t / sqrt(|q_t|^2 + 1e-6) * d^-1/2 ;  k_t = k_t / sqrt(|k_t|^2 + 1e-6)
                                                          per head
    [fa | ga | b] = x W_low                             (D -> d + d + H)
    g_t = -exp(A_log[h]) * softplus(fa W_fb + dt_bias)  (<= 0; per head AND lane)
    beta_t = sigmoid(b)                                 per head
    S_0 = 0 [H, d, d] float32, and per head for t = 1..T:
        S' = Diag(exp g_t) S_{t-1}
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t
    y_t = RMSNorm_d(o_t; onorm, eps) * sigmoid(ga W_gb + gb_bias)     per head
    Mix = concat_h(y_t) W_o                             (P -> D)

**Mix, a layer in ``full_attn_layers``** — H = ``num_attention_heads``, dn =
``qk_nope_head_dim``, dr = ``qk_rope_head_dim``, dv = ``v_head_dim``, rank =
``kv_lora_rank``:

    q = x W_q -> [T, H, dn + dr]                         (no q-LoRA)
    [c | k_pe] = x W_kva -> rank + dr ;  c = RMSNorm(c; kv_norm)
    [k_nope | v] = c W_kvb -> [T, H, dn + dv] ;  k = [k_nope | k_pe], k_pe
    shared by all heads and NOT rotated, nor are q's last dr lanes
    (``mla_use_nope``: no positional term anywhere in the model)
    causal softmax over every earlier position at scale (dn + dr)^-1/2
    Mix = concat_h(softmax(s) v) W_o

**MLP** — layer <= ``first_k_dense_replace``: ``(silu(m W_g) * (m W_u)) W_d`` at
``intermediate_size``. Else: ``p = sigmoid(m W_r)`` over ALL published experts
(float32); the ``num_experts_per_token`` best of ``p +
e_score_correction_bias`` (one group); weights = p of the chosen, divided by
their sum (``moe_renormalize``), times ``routed_scaling_factor``;
``sum_{chosen e held here} w_e expert_e(m) + shared(m)``, experts and the
shared one SwiGLU at ``moe_intermediate_size``.

    logits = RMSNorm(h; final_norm) W_head

**The expert share.** ``num_experts`` counts the experts held here,
``num_experts_published`` the router's width, ``expert_share_index`` which
share this is: the experts [index * held, (index + 1) * held). The router, its
bias, the top-k and the renormalisation are over all the published experts;
what a chosen expert that lives elsewhere would add is left out, as in the
program, and that partial result goes on to the next layer. The shared expert
is whole. The vocabulary is the slice the file states.

It reads the engine's own parameter tree (``models/kimi_linear.py``
``param_shapes`` names: the K layers' leaves ``layers.kda_*`` at the layer's
index among the K layers, the F layers' ``layers.wq`` ... among the F layers;
int8 as q * scale; gate|up split where ``fuse_stacked_matmuls`` joined them).

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states, which the comparison has
to tell from the program's). ``int4_weights`` rounds the weights of every
matmul the program holds in int8 (the K layers' ``kda_in`` / ``kda_wo``, the
F layers' projections, the dense, shared and routed MLPs) to 4 bits under
one scale per 128 input rows and output column,
``quant.quantize_array_grouped``'s rule; the router, the low-rank pairs, the
embedding and the head stay as stored.

**Leaves** (``leaves_for`` / ``TAPPED`` / ``LEAF_TOL``). Two breakages change
what the cache HOLDS by far more than they change a logit under seeded
weights: the state rounded to bf16 every token (2^-9 of itself a token: ~3%
of the state after 648 tokens, ~0.01 of a logit's standard deviation) and
the pe lanes rotated (a softmax over hundreds of random keys averages
random values either way). So the reference also gives the first K layer's
state after the last token and the first F layer's pe lanes of every token,
which a check holds the engine's own cache leaves to (``kv["kda"][0,
slot]``, the pool rows' lanes behind the latent): ``leaf_error`` inside
``LEAF_TOL`` for the unbroken reference, outside it for the breakage.

Departures from the published model, each shared with the program (the
configuration file lists them under ``assumed``): the low-rank pairs' inner
width is ``head_dim``; ``W_gb`` carries a bias, the convolutions none; the L2
norm's eps is 1e-6; key and value head sizes are both ``head_dim``; the
router has a correction bias; one chip's share of the experts and of the
vocabulary; weights are the int8-rounded ones the engine holds.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_decay", "no_delta", "beta_one", "no_qk_norm",
             "no_conv", "no_output_gate", "state_bf16", "pe_rotated",
             "no_shared_expert", "no_renorm", "decay_per_head",
             "unit_routing_weights")

CONTROLS = ("int4_weights",)

# the breakages a cache leaf shows where served logits may not (module
# docstring, "Leaves"): which leaf of ``leaves_for`` each moves, and the
# relative error (``leaf_error``) a sound program's leaf stays inside. Set
# between two readings at the published widths on the chip, 640 tokens + 8
# steps, two seeds (my chip runs, PR 54): the state 0.0049 / 0.0049 off the
# reference's and 0.0162 / 0.0160 off the bf16-rounded one (0.0052 and
# 0.0091 at rehearsal widths on the CPU); the pe lanes 0.0105 / 0.0099 and,
# rotated, 1.146 / 1.143 (0.020 and 1.42)
TAPPED = {"state_bf16": "kda", "pe_rotated": "pe"}
LEAF_TOL = {"kda": 0.007, "pe": 0.1}

L2_EPS = 1e-6
_KDA_ONLY = ("no_decay", "no_delta", "beta_one", "no_qk_norm", "no_conv",
             "no_output_gate", "state_bf16", "decay_per_head")
_MOE_ONLY = ("no_shared_expert", "no_renorm", "unit_routing_weights")


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
    """Those of BREAKAGES that served logits have to show at this
    configuration: all of them, but the shared expert where there is none,
    and not ``state_bf16``, which the state's leaf shows and a logit does
    not (``TAPPED``)."""
    fam = family(hf)
    fits = {"no_shared_expert": fam["shared"] > 0, "state_bf16": False}
    return tuple(b for b in BREAKAGES if fits.get(b, True))


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "kimi_linear":
        raise ValueError(f"the kimi_linear reference does not compute "
                         f"{hf['model_type']!r}")
    refused = {
        "q_lora_rank": bool(hf.get("q_lora_rank")),
        "mla_use_nope (false)": not hf.get("mla_use_nope"),
        "rope_scaling": bool(hf.get("rope_scaling")),
        "moe_router_activation_func":
            hf.get("moe_router_activation_func", "sigmoid") != "sigmoid",
        "num_expert_group": int(hf.get("num_expert_group") or 1) != 1,
        "num_nextn_predict_layers": bool(hf.get("num_nextn_predict_layers")),
    }
    if any(refused.values()):
        raise ValueError("the kimi_linear reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    n = int(hf["num_hidden_layers"])
    lin = hf["linear_attn_config"]
    kda = {int(i) for i in lin["kda_layers"] if int(i) <= n}
    held = int(hf["num_experts"])
    return {
        "layers": n,
        "kinds": tuple("K" if i in kda else "F" for i in range(1, n + 1)),
        "kda_heads": int(lin["num_heads"]), "kda_dim": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "heads": int(hf["num_attention_heads"]),
        "rank": int(hf["kv_lora_rank"]),
        "dn": int(hf["qk_nope_head_dim"]), "dr": int(hf["qk_rope_head_dim"]),
        "dv": int(hf["v_head_dim"]),
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf.get("rope_theta") or 10000.0),
        "held": held,
        "experts": int(hf.get("num_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_token"]),
        "norm_topk": bool(hf.get("moe_renormalize", True)),
        "first_dense": int(hf["first_k_dense_replace"]),
        "shared": (int(hf.get("num_shared_experts") or 0)
                   * int(hf["moe_intermediate_size"])),
        "routed_scaling": float(hf["routed_scaling_factor"]),
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
    out = {n: get(n, li) for n in ("ln1", "ln2")}
    if kind == "K":
        out.update({n: get(f"kda_{n}", ai) for n in (
            "in", "conv", "low", "fb", "A_log", "dt_bias", "gb", "gb_bias",
            "onorm", "wo")})
    else:
        out.update({n: get(n, ai) for n in (
            "wq", "wkv_a", "kv_norm", "wkv_b", "wo")})
    if li >= fam["first_dense"]:
        names = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
                 "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    else:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def _swiglu(m, g, u, d):
    return (jax.nn.silu(m @ g) * (m @ u)) @ d


def _gate_up(wt, pair, fused):
    """(gate, up) through ``wt`` from separate tensors, or from the fused
    one, which ``fuse_stacked_matmuls`` joined as gate|up along the last
    axis."""
    if fused is None:
        return wt(pair[0]), wt(pair[1])
    w = wt(fused)
    return w[..., :w.shape[-1] // 2], w[..., w.shape[-1] // 2:]


def kda_mix(fam: dict, broken=None):
    """-> f(x [T, D] f32 the normed input, the layer's weights) -> ([T, D],
    the state after the last token [H, d, d]): the delta rule a token at a
    time."""
    H, d, taps, eps = (fam["kda_heads"], fam["kda_dim"], fam["taps"],
                       fam["eps"])
    P = H * d
    wt = _weights(broken)

    def mix(x, lw):
        T = x.shape[0]
        qkv = x @ wt(lw["in"])                                   # [T, 3P]
        if broken != "no_conv":
            w = wt(lw["conv"])                                   # [taps, 3P]
            past = jnp.concatenate([jnp.zeros((taps - 1, 3 * P)), qkv])
            qkv = sum(w[j] * past[j:j + T] for j in range(taps))
        qkv = jax.nn.silu(qkv)
        q, k, v = (a.reshape(T, H, d) for a in jnp.split(qkv, 3, -1))
        if broken != "no_qk_norm":
            q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
            k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        q = q * d ** -0.5
        low = x @ wt(lw["low"])
        fa, ga, b = low[:, :d], low[:, d:2 * d], low[:, 2 * d:]
        g = -jnp.exp(wt(lw["A_log"]))[None, :, None] * jax.nn.softplus(
            fa @ wt(lw["fb"]) + wt(lw["dt_bias"])).reshape(T, H, d)
        if broken == "decay_per_head":     # one decay a head: its lanes' mean
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        alpha = jnp.ones_like(g) if broken == "no_decay" else jnp.exp(g)
        beta = (jnp.ones((T, H)) if broken == "beta_one"
                else jax.nn.sigmoid(b))

        def token(S, xs):
            q_t, k_t, v_t, a_t, b_t = xs                 # [H, d] ... [H]
            S = a_t[:, :, None] * S                      # Diag(alpha) S
            seen = jnp.einsum("hkv,hk->hv", S, k_t)      # S'^T k
            if broken == "no_delta":
                seen = jnp.zeros_like(seen)
            S = S + b_t[:, None, None] * k_t[:, :, None] * (
                v_t - seen)[:, None, :]
            if broken == "state_bf16":
                # an explicit rounding: XLA drops a convert to bf16 and
                # back as excess precision it is allowed to keep
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        S, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32),
                            (q, k, v, alpha, beta))              # [T, H, d]
        y = _rms(o, wt(lw["onorm"]), eps)
        if broken != "no_output_gate":
            z = ga @ wt(lw["gb"]) + wt(lw["gb_bias"])
            y = y * jax.nn.sigmoid(z).reshape(T, H, d)
        return y.reshape(T, P) @ wt(lw["wo"]), S
    return mix


def _rotate(x, theta):
    """The ``pe_rotated`` breakage: x [T, heads, dr] rotated by position,
    pairs (2i, 2i+1), as DeepSeek's latent block does and this one does
    not."""
    T, _, dr = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def latent_mix(fam: dict, broken=None):
    """-> f(x [T, D] f32, the layer's weights) -> ([T, D], the pe lanes of
    every token's cached row [T, dr]): full causal latent attention with no
    positional term."""
    H, rank, dn, dr, dv = (fam["heads"], fam["rank"], fam["dn"], fam["dr"],
                           fam["dv"])
    wt = _weights(broken)

    def mix(x, lw):
        T = x.shape[0]
        q = (x @ wt(lw["wq"])).reshape(T, H, dn + dr)
        kv = x @ wt(lw["wkv_a"])
        c = _rms(kv[:, :rank], wt(lw["kv_norm"]), fam["eps"])
        k_pe = kv[:, None, rank:]                                # [T, 1, dr]
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        if broken == "pe_rotated":
            q_pe, k_pe = _rotate(q_pe, fam["theta"]), _rotate(k_pe,
                                                              fam["theta"])
        up = (c @ wt(lw["wkv_b"])).reshape(T, H, dn + dv)
        k_nope, v = up[..., :dn], up[..., dn:]
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
             + jnp.einsum("thd,sd->hts", q_pe, k_pe[:, 0])) * (dn + dr) ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
        return out.reshape(T, H * dv) @ wt(lw["wo"]), k_pe[:, 0]
    return mix


def moe_mlp(fam: dict, broken=None):
    """-> f(m [T, D] f32, an expert layer's weights) -> [T, D]: what the
    experts held here add for the tokens routed to them, plus the shared
    expert."""
    wt = _weights(broken)

    def mlp(m, lw):
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        p = jax.nn.sigmoid(m @ wt(lw["router"]))
        _, top_i = jax.lax.top_k(p + wt(lw["router_bias"])[None, :], K)
        top_p = jnp.take_along_axis(p, top_i, axis=1)
        if fam["norm_topk"] and broken != "no_renorm":
            top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
        top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)
        # this chip's experts: the columns of the experts it holds
        weight = weight[:, fam["first_held"]:fam["first_held"] + fam["held"]]
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
        if fam["shared"] and broken != "no_shared_expert":
            g, u = _gate_up(wt, (lw.get("sh_gate"), lw.get("sh_up")),
                            lw.get("sh_gateup"))
            out = out + _swiglu(m, g, u, wt(lw["sh_down"]))
        return out
    return mlp


def make_layer(fam: dict, kind: str, moe: bool, broken=None):
    """-> jitted f(h [T, D] f32, layer weights) -> (h, the mix's leaf), for
    a layer of ``kind`` with a dense or an expert MLP."""
    mix = (kda_mix if kind == "K" else latent_mix)(fam, broken)
    experts = moe_mlp(fam, broken)
    wt = _weights(broken)

    def layer(h, lw):
        delta, leaf = mix(_rms(h, wt(lw["ln1"]), fam["eps"]), lw)
        h = h + delta
        m = _rms(h, wt(lw["ln2"]), fam["eps"])
        if moe:
            return h + experts(m, lw), leaf
        g, u = _gate_up(wt, (lw.get("gate"), lw.get("up")), lw.get("gateup"))
        return h + _swiglu(m, g, u, wt(lw["down"])), leaf
    return jax.jit(layer)


_LAYERS: dict = {}


def _layer(fam: dict, hf: dict, kind: str, moe: bool, broken):
    """``make_layer``, built once per configuration, kind and breakage; a
    breakage of one block leaves the others as they are."""
    if ((broken in _KDA_ONLY and kind != "K")
            or (broken == "pe_rotated" and kind != "F")
            or (broken in _MOE_ONLY and not moe)):
        broken = None
    key = (json.dumps(hf, sort_keys=True), kind, moe, broken)
    if key not in _LAYERS:
        _LAYERS[key] = make_layer(fam, kind, moe, broken)
    return _LAYERS[key]


def forward(params: dict, hf: dict, tokens, broken=None,
            leaves=None) -> jax.Array:
    """-> the final hidden states [T, D] float32 (before the last norm).
    ``leaves``: a dict that takes the first K layer's state after the last
    token (``"kda"``) and the first F layer's pe lanes (``"pe"``)."""
    fam = family(hf)
    h = embed_rows(params, jnp.asarray(tokens, jnp.int32))
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    for li in range(n_layers):
        kind = fam["kinds"][li]
        layer = _layer(fam, hf, kind, li >= fam["first_dense"], broken)
        h, leaf = layer(h, _layer_weights(params, li, fam))
        if leaves is not None:
            leaves.setdefault({"K": "kda", "F": "pe"}[kind], leaf)
    return h


def leaves_for(params: dict, hf: dict, tokens, broken=None) -> dict:
    """What the cache holds after ``tokens``, float32: ``"kda"`` the first
    K layer's state [H, d, d], ``"pe"`` the pe lanes of the first F layer's
    row of every token [T, dr] (module docstring, "Leaves")."""
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
    with jax.default_matmul_precision(precision):
        h = forward(params, hf, tokens, broken)
        out = np.asarray(head_logits(params, hf, h[-last:],
                                     family(hf)["eps"]), np.float32)
    if broken is not None and not np.isfinite(out).all():
        # a breakage that overflows (no_qk_norm at 128 lanes: |k|^2 >> 1
        # makes I - beta k k^T expansive) is as wrong as can be, and the
        # comparison's max() passes a NaN over: flat logits, which it
        # reads as infinitely far
        out = np.zeros_like(out)
    return out
