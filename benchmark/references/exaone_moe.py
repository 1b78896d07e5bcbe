"""The plain reference of the exaone_moe block (K-EXAONE-236B-A23B) and of its
multi-token-prediction module: the forward pass only. The comparison and its
tolerance are ``reference.compare`` / ``reference.TOL_STD``, the same for
every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no scan
over layers, no fused layout, no batching. The catalog gives the family's
``config.json`` keys, not its modelling code; the family's public code on this
machine is ``transformers/models/exaone4/modeling_exaone4.py`` (4.57.6), whose
keys ``layer_types`` / ``sliding_window_pattern`` / ``sliding_window`` these
are, and this file follows it. ``x`` is the stream, ``N(.)`` RMSNorm with eps
``rms_norm_eps``, D = ``hidden_size``, d = ``head_dim``, H / KVH the head
counts (ONE geometry for both kinds of layer), W = ``sliding_window``.

    x = embed[tokens]
    per layer l, of the kind layer_types[l] (L = sliding_attention,
    G = full_attention):
      q = N_q(x.Wq) [T, H, d]   k = N_k(x.Wk) [T, KVH, d]   v = x.Wv [T, KVH, d]
            (N_q, N_k: RMSNorm over the d lanes of a head; NO norm on x)
      L: rope(theta, all d lanes, HF half-split pairs) on q and k;
         keys t-W+1..t          G: NO rope (NoPE); keys 0..t
      s_tj = q_t.k_j / sqrt(d), head h reads kv head h // (H/KVH), plain
            softmax (no sink, no value scale)
      x += N_a(attn.Wo)                       (post_attention_layernorm)
      mlp_layer_types[l] == dense:  M = (silu(x.Wg) * (x.Wu)).Wd at
            intermediate_size
      sparse: p = sigmoid(x.Wr) over ALL published experts (float32)
            choice = p + e_score_correction_bias (one group); its
            num_experts_per_tok best; w = p of the chosen (not choice),
            divided by their sum (norm_topk_prob), times
            routed_scaling_factor
            M = shared(x) + sum_{chosen e held here} w_e * expert_e(x),
            SwiGLU at moe_intermediate_size, the shared one
            num_shared_experts times as wide
      x += N_f(M)                             (post_feedforward_layernorm)
    logits = N(x) . W_head                                       (untied)

**The multi-token-prediction module** (``mtp_logits_for``; the form of the
family that published the key ``num_nextn_predict_layers``, DeepSeek-V3, as
its public serving code runs it): for a position p whose next token is known,

    u_p  = W_eh . [ N_e(embed[x_{p+1}]) ; N_h(h_p) ]      h_p = N(x_p), the
           main model's last hidden state as its head reads it
    u'_p = Block_G(u)_p       one block of the kind mtp_layer_types[0] (full
           attention, NoPE) over its OWN rows 0..p, its M an expert layer
    draft logits for the token at p + 2 = N_mtp(u'_p) . W_head
           (embedding and head the main model's)

**Assumed readings** (``assumed`` in the configuration file): the norm
placement and the input-norm-free sub-layers (exaone4's, not confirmed for
``exaone_moe``); NoPE in the full layers (exaone4's rule for a hybrid model;
the model card says the same); the window counts the query's own position;
the router's bias term and its use for the choice alone (the one-group
``noaux_tc`` form ``kimi_k2`` / ``mimo_v2`` use); the module's form and its
expert MLP (no key says dense or sparse); the checkpoint's tensor names; text
only.

**The expert share** is ``references/deepseek_v32.py``'s: ``num_experts``
counts the experts held here, ``num_experts_published`` the router's width,
``expert_share_index`` which share; the router, its bias, the top-k and the
renormalisation are over all the published experts, what a chosen expert that
lives elsewhere would add is left out, the shared expert is whole. The same
in the module's block. ``uncut_moe`` is the layer over ALL the experts from
the shares' stacks, for the test that the shares add up.

It reads the engine's own parameter tree (``models/mimo.py`` ``param_shapes``:
the G layers' leaves ``layers.<leaf>``, the L layers' ``layers.swa_<leaf>``,
norms [layers, D], ``dense_*``, the expert stacks, ``mtp.<leaf>`` with a
leading axis of 1; int8 as q.scale; q|k|v and gate|up split where
``fuse_stacked_matmuls`` joined them), one layer and one expert at a time.

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states): ``int4_weights`` rounds
the weights of the matmuls that the program holds in int8 to 4 bits under one
scale per 128 input rows and output column, ``quant.quantize_array_grouped``'s
rule. ``logits_for(..., precision="default")`` runs every matmul in the
device's default precision (the program's own): the witness that reads
INSIDE the tolerance.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_qk_norm", "rope_on_full", "no_rope_on_window",
             "pre_norm", "window_plus_one", "window_minus_one",
             "no_shared_expert", "no_routed_scale", "no_router_bias",
             "unit_routing_weights", "absent_expert_added",
             "mtp_no_hnorm", "mtp_concat_swapped", "mtp_unshifted",
             "mtp_reads_main_rows")
# the module's: they move the draft logits alone (``mtp_logits_for``), which
# the served ``correct`` never sees (lockstep acceptance hides a wrong
# drafter: it only slows), so ``references/exaone_moe_check.py`` and
# tests/test_exaone_moe.py hold them
MTP_BREAKAGES = tuple(b for b in BREAKAGES if b.startswith("mtp_"))
CONTROLS = ("int4_weights",)

# What served logits (bf16, int8 weights, random weights) do not show with
# room to spare, so breakages_for does not ask for it: (logprob error,
# argmax gap) in standard deviations of the logits against TOL_STD 0.25, at
# the published widths on the chip, a 640-token prompt and 13 tokens through
# prefill, a hit and eight two-row steps (my chip run, PR 50, seed 51:
# ``exaone_moe_check.py`` under the seeded rule as it stands,
# llama.OUTPUT_NORMED_SEEDED; the program itself read 0.018 / 0.000 there,
# the float32 reference in bf16 0.022 / 0.000, the int4 control 0.849 /
# 0.701). One key more or less in a window of 128 is small by nature; the
# four that turn on the ROUTED experts alone are small by the rule: their
# down-projection is seeded at a quarter, because a flipped top-8 choice is
# what parted the bf16 program from the reference (0.15 on one probe of five
# at fan_in^-0.5, where these read 0.25-0.72: PERF.md section 6). The other
# six read 0.38-1.90 on the main logits, and the module's four 0.53-4.46 on
# the draft's. tests/test_exaone_moe.py holds every one of BREAKAGES in
# float32.
FINE_MEASURED = {"window_plus_one": (0.095, 0.078),
                 "window_minus_one": (0.164, 0.108),
                 "no_routed_scale": (0.064, 0.000),
                 "no_router_bias": (0.064, 0.075),
                 "unit_routing_weights": (0.175, 0.191),
                 "absent_expert_added": (0.038, 0.000)}
FINE = tuple(FINE_MEASURED)

MLP_SLICE = 4096
SEQ_PAD = 128


def _int4_groups(w, group: int = 128):
    D, F = w.shape[-2:]
    g = group if D % group == 0 else D
    w = w.reshape(w.shape[:-2] + (D // g, g, F))
    scale = jnp.maximum(jnp.max(jnp.abs(w), -2, keepdims=True), 1e-30) / 7
    return (jnp.clip(jnp.round(w / scale), -7, 7) * scale).reshape(
        w.shape[:-3] + (D, F))


def _weights(control):
    return _int4_groups if control == "int4_weights" else (lambda w: w)


def breakages_for(hf: dict) -> tuple:
    """Those that the tolerance has to catch on the served MAIN logits (bf16,
    int8 weights): all but the module's and FINE."""
    family(hf)
    return tuple(b for b in BREAKAGES
                 if b not in FINE and b not in MTP_BREAKAGES)


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "exaone_moe":
        raise ValueError(f"the exaone_moe reference does not compute "
                         f"{hf['model_type']!r}")
    rp = hf.get("rope_parameters") or {}
    refused = {
        "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
        "rope_parameters": rp.get("rope_type", "default") != "default",
        "n_group": int(hf.get("n_group") or 1) != 1,
        "num_nextn_predict_layers":
            int(hf.get("num_nextn_predict_layers") or 0) > 1,
    }
    if any(refused.values()):
        raise ValueError("the exaone_moe reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    layers = int(hf["num_hidden_layers"])
    held = int(hf["num_experts"])
    mlps = list(hf["mlp_layer_types"][:layers])
    return {
        "layers": layers,
        "kinds": tuple("S" if t == "sliding_attention" else "F"
                       for t in hf["layer_types"][:layers]),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]),
        "d": int(hf["head_dim"]),
        "theta": float(rp.get("rope_theta", 1e6)),
        "window": int(hf["sliding_window"]),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "held": held,
        "experts": int(hf.get("num_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "shared": int(hf.get("num_shared_experts") or 0),
        "first_sparse": next((i for i, m in enumerate(mlps)
                              if m == "sparse"), layers),
        "routed_scaling": float(hf.get("routed_scaling_factor") or 1.0),
        "mtp": int(hf.get("num_nextn_predict_layers") or 0),
    }


def _rope(x, theta: float):
    """x: [T, heads, d], positions 0..T-1, every lane rotated: lane i with
    lane i + d/2 (HF's half-split)."""
    T, _, d = x.shape
    inv = jnp.asarray((1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64)
                                       / d)).astype(np.float32))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _take(params: dict, name: str, i: int):
    """Entry ``i`` of the stack ``name``, as stored (int8 and scale apart
    until a jitted layer dequantises them); None where there is none."""
    w = params.get(name)
    if w is None:
        return None
    return (w.q[i], w.scale[i]) if hasattr(w, "q") else w[i]


_ATTN = ("wq", "wk", "wv", "wqkv", "wo", "q_norm", "k_norm")
_MOE = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
        "moe_down", "sh_gate", "sh_up", "sh_gateup", "sh_down")


def _layer_weights(params: dict, li: int, fam: dict) -> dict:
    """Layer ``li``'s tensors under their plain names: the attention leaves
    from the stack of the layer's kind, at its index among the layers of
    that kind."""
    kind = fam["kinds"][li]
    ai = fam["kinds"][:li].count(kind)
    prefix = "layers.swa_" if kind == "S" else "layers."
    out = {n: _take(params, "layers." + n, li) for n in ("ln1", "ln2")}
    out.update({n: _take(params, prefix + n, ai) for n in _ATTN})
    if li >= fam["first_sparse"]:
        out.update({n: _take(params, "layers." + n, li - fam["first_sparse"])
                    for n in _MOE})
    else:
        out.update({n: _take(params, f"layers.dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def mtp_weights(params: dict) -> dict:
    """The module's tensors under their plain names (``mtp.<leaf>``, a stack
    of one layer)."""
    out = {n[len("mtp."):]: _take(params, n, 0) for n in params
           if n.startswith("mtp.")}
    if "eh_proj" not in out:
        raise ValueError("this parameter tree holds no multi-token-"
                         "prediction module (an engine built without "
                         "--spec-k holds none)")
    return out


def moe_block(fam: dict, broken=None, experts=None):
    """→ f(m [T, D] f32, an expert layer's weights) → the layer's MLP output
    [T, D]: the shared expert, and what the experts held here add for the
    tokens routed to them. ``experts``: (first, count) of the held share
    where it is not the configuration's (``uncut_moe``)."""
    wt = _weights(broken)
    lo, held = experts or (fam["first_held"], fam["held"])

    def moe_mlp(m, lw, shared=True):
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        p = jax.nn.sigmoid(m @ _w(lw["router"]))
        bias = _w(lw["router_bias"])[None, :]
        if broken == "no_router_bias":
            bias = jnp.zeros_like(bias)
        _, top_i = jax.lax.top_k(p + bias, K)
        top_p = jnp.take_along_axis(p, top_i, axis=1)
        if fam["norm_topk"]:
            top_p = top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20)
        if broken != "no_routed_scale":
            top_p = top_p * fam["routed_scaling"]
        if broken == "unit_routing_weights":
            top_p = jnp.ones_like(top_p)
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], top_i].set(top_p)
        mine = weight[:, lo:lo + held]
        if broken == "absent_expert_added" and E > held:
            # the first expert that lives elsewhere, run here all the same
            # (on the first held expert's weights: its own are not here)
            mine = mine.at[:, 0].add(weight[:, (lo + held) % E])
        fused = "moe_gateup" in lw
        gu = lw["moe_gateup"] if fused else (lw["moe_gate"], lw["moe_up"])

        def expert(acc, x):
            g, u = (_split(None, x["gu"]) if fused
                    else _split(x["gu"], None))
            y = _swiglu(m, wt(g), wt(u), wt(_w(x["down"])))
            return acc + x["w"][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                              {"gu": gu, "down": lw["moe_down"], "w": mine.T})
        if fam["shared"] and shared and broken != "no_shared_expert":
            g, u = _split((lw.get("sh_gate"), lw.get("sh_up")),
                          lw.get("sh_gateup"))
            out = out + _swiglu(m, wt(g), wt(u), wt(_w(lw["sh_down"])))
        return out
    return moe_mlp


def uncut_moe(fam: dict, m, shares: list):
    """The expert layer over ALL the published experts, from the shares'
    weights (``shares[i]``: the layer's weights as share i holds them): the
    shared expert once and every share's routed part."""
    n = fam["experts"] // fam["held"]
    out = jnp.zeros_like(m)
    for i in range(n):
        out = out + moe_block(fam, experts=(i * fam["held"], fam["held"]))(
            m, shares[i], shared=(i == 0))
    return out


_LAYERS: dict = {}


def make_layer(fam: dict, kind: str, moe: bool, broken=None):
    """→ jitted f(x [T, D] f32, the layer's weights, other=None) → (x, (k,
    v)): the layer's key and value rows ride along for
    ``mtp_reads_main_rows``, which hands a main layer's in as ``other``, to
    attend over INSTEAD of the layer's own. One function a (sizes, kind,
    MLP, breakage), kept: the main model's layers compile once whatever
    breakage of the module is asked for next."""
    memo = (json.dumps(fam, sort_keys=True), kind, moe, broken)
    if memo not in _LAYERS:
        _LAYERS[memo] = _make_layer(fam, kind, moe, broken)
    return _LAYERS[memo]


def _make_layer(fam: dict, kind: str, moe: bool, broken):
    H, KVH, d = fam["heads"], fam["kv_heads"], fam["d"]
    eps, W, theta = fam["eps"], fam["window"], fam["theta"]
    per = H // KVH
    if kind == "S":
        W += {"window_plus_one": 1, "window_minus_one": -1}.get(broken, 0)
    rope = ((kind == "S" and broken != "no_rope_on_window")
            or (kind == "F" and broken == "rope_on_full"))
    pre = broken == "pre_norm"          # the norms moved to the inputs
    wt = _weights(broken)
    moe_mlp = moe_block(fam, broken) if moe else None

    def layer(x, lw, other=None):
        T = x.shape[0]
        a = _rms(x, _w(lw["ln1"]), eps) if pre else x
        if "wqkv" in lw:
            w = wt(_w(lw["wqkv"]))
            wq, wk, wv = (w[:, :H * d], w[:, H * d:(H + KVH) * d],
                          w[:, (H + KVH) * d:])
        else:
            wq, wk, wv = (wt(_w(lw[n])) for n in ("wq", "wk", "wv"))
        q = (a @ wq).reshape(T, H, d)
        k = (a @ wk).reshape(T, KVH, d)
        v = (a @ wv).reshape(T, KVH, d)
        if broken != "no_qk_norm":
            q = _rms(q, _w(lw["q_norm"]), eps)
            k = _rms(k, _w(lw["k_norm"]), eps)
        if rope:
            q, k = _rope(q, theta), _rope(k, theta)
        own = (k, v)
        if other is not None:
            k, v = other
        pos = jnp.arange(T)
        mask = pos[None, :] <= pos[:, None]
        if kind == "S":
            mask = mask & (pos[None, :] > pos[:, None] - W)
        outs = []
        for kh in range(KVH):           # the query heads of one kv head
            s = jnp.einsum("thd,sd->hts", q[:, kh * per:(kh + 1) * per],
                           k[:, kh]) / math.sqrt(d)
            s = jnp.where(mask[None], s, -jnp.inf)
            outs.append(jnp.einsum("hts,sd->thd", jax.nn.softmax(s, -1),
                                   v[:, kh]))
        o = jnp.concatenate(outs, 1).reshape(T, H * d) @ wt(_w(lw["wo"]))
        x = x + (o if pre else _rms(o, _w(lw["ln1"]), eps))
        m = _rms(x, _w(lw["ln2"]), eps) if pre else x
        if moe:
            out = moe_mlp(m, lw)
        else:
            gw, uw = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
            gw, uw, dw = wt(gw), wt(uw), wt(_w(lw["down"]))
            out = jnp.zeros_like(m)
            for lo in range(0, gw.shape[-1], MLP_SLICE):
                sl = slice(lo, lo + MLP_SLICE)
                out = out + (jax.nn.silu(m @ gw[:, sl])
                             * (m @ uw[:, sl])) @ dw[sl]
        return x + (out if pre else _rms(out, _w(lw["ln2"]), eps)), own

    return jax.jit(layer)


def forward(params: dict, hf: dict, tokens, broken=None):
    """→ (the final stream [T, D] float32 before the last norm, the key and
    value rows of the last full layer)."""
    fam = family(hf)
    T = len(tokens)
    # whole multiples of SEQ_PAD rows, so that sequences of nearby lengths
    # (a probe and its continuation; a check's step after step) run the
    # same compiled layers: every layer is causal and every other
    # operation is a row's own, so rows behind the sequence change nothing
    # before them
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, -T % SEQ_PAD))
    x = embed_rows(params, tokens)
    rows = None
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    for li in range(n_layers):
        kind, moe = fam["kinds"][li], li >= fam["first_sparse"]
        x, own = make_layer(fam, kind, moe, broken)(
            x, _layer_weights(params, li, fam))
        if kind == "F":
            rows = (own[0][:T], own[1][:T])
    return x[:T], rows


# The harness asks the same question twice (``run.py`` holds its probes to
# this file before the window and again after it): the last answers are kept
# and an identical question (the same weight arrays, by identity, the same
# tokens, breakage and precision) is answered from them.
_ANSWERS: list = []
ANSWERS_KEPT = 4


def _answered(fn):
    def ask(params: dict, hf: dict, tokens, last: int, broken=None,
            precision: str = "highest") -> np.ndarray:
        asked = (fn.__name__, tuple(int(t) for t in tokens), int(last),
                 broken, precision, json.dumps(hf, sort_keys=True))
        weights = tuple(params.items())
        for held, question, answer in _ANSWERS:
            if question == asked and len(held) == len(weights) and all(
                    a[0] == b[0] and a[1] is b[1]
                    for a, b in zip(held, weights)):
                return answer.copy()
        with jax.default_matmul_precision(precision):
            answer = np.asarray(fn(params, hf, tokens, int(last), broken),
                                np.float32)
        _ANSWERS.append((weights, asked, answer))
        del _ANSWERS[:-ANSWERS_KEPT]
        return answer.copy()
    ask.__doc__ = fn.__doc__
    return ask


@_answered
def logits_for(params, hf, tokens, last, broken):
    """Float32 logits [last, V] of the last ``last`` positions of one
    sequence, by the full forward over all of it; ``broken`` is a breakage
    or a control."""
    x, _rows = forward(params, hf, tokens, broken)
    return head_logits(params, hf, x[-last:], family(hf)["eps"])


@_answered
def mtp_logits_for(params, hf, tokens, last, broken):
    """Float32 DRAFT logits [last, V], teacher-forced on ``tokens`` [T]: row
    i is the module's guess at the token at p + 2 for p = T - 1 - last + i,
    the last ``last`` positions whose next token is known (the last row: p =
    T - 2, the guess at the token after the sequence). By the full forward of
    the main model and of the module over all of it."""
    fam = family(hf)
    eps = fam["eps"]
    if not fam["mtp"]:
        raise ValueError("this configuration declares no multi-token-"
                         "prediction module")
    tokens = jnp.asarray(tokens, jnp.int32)
    x, rows = forward(params, hf, tokens, None if broken in MTP_BREAKAGES
                      else broken)
    mw = mtp_weights(params)
    h = _rms(x, _w(params["final_norm"]), eps)[:-1]          # h_p, p < T - 1
    nxt = tokens[:-1] if broken == "mtp_unshifted" else tokens[1:]
    e = _rms(embed_rows(params, nxt), _w(mw["enorm"]), eps)
    if broken != "mtp_no_hnorm":
        h = _rms(h, _w(mw["hnorm"]), eps)
    cat = [h, e] if broken == "mtp_concat_swapped" else [e, h]
    u = jnp.concatenate(cat, -1) @ _weights(broken)(_w(mw["eh_proj"]))
    block = make_layer(fam, "F", True,
                       None if broken in MTP_BREAKAGES else broken)
    n = u.shape[0]
    pad = lambda a: jnp.pad(                              # noqa: E731
        a, ((0, -n % SEQ_PAD),) + ((0, 0),) * (a.ndim - 1))
    other = None
    if broken == "mtp_reads_main_rows":
        other = (pad(rows[0][:-1]), pad(rows[1][:-1]))
    u, _own = block(pad(u), {k: w for k, w in mw.items() if k in
                             _ATTN + _MOE + ("ln1", "ln2")}, other)
    # the module's own final norm, then the model's head
    return head_logits({**params, "final_norm": mw["final_norm"]}, hf,
                       u[n - last:n], eps)
