"""The plain reference of the mimo_v2 block (MiMo-V2.5): grouped-query
attention in TWO geometries in one model, a learned sink in the window
layers' softmax, the forward pass only. The comparison and its tolerance are
``reference.compare`` / ``reference.TOL_STD``, the same for every family.

Plain ``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``,
a full causal forward over the whole sequence: no cache, no kernel, no scan
over layers, no fused layout, no batching. The catalog gives the family's
``config.json`` keys and a one-line description, not its modelling code, so
every line is stated here for a reader who has the code to check; the readings
the config does not settle are assumptions (below). ``x`` is the stream,
``n(.)`` RMSNorm with eps ``layernorm_epsilon``, D = ``hidden_size``.

    x = embed[tokens]
    per layer l, of the kind hybrid_layer_pattern[l] (0 = F, full; 1 = S,
    sliding), with (H, KVH, theta) = (num_attention_heads,
    num_key_value_heads, rope_theta) for F and (swa_num_attention_heads,
    swa_num_key_value_heads, swa_rope_theta) for S; dk = head_dim,
    dv = v_head_dim, r = the even part of floor(dk * partial_rotary_factor),
    W = sliding_window:
      a     = n(x)
      q     = a.Wq -> [T, H, dk]      k = a.Wk -> [T, KVH, dk]
      v     = attention_value_scale * (a.Wv) -> [T, KVH, dv]   (no bias, no
              q/k norm)
      rope on lanes [0, r) of every q and k head: lane i with lane i + r/2
              (half-split), rotated by pos * theta^(-2i/r); lanes [r, dk) pass
      s_tj  = q_t.k_j / sqrt(dk), query head h reading kv head h // (H/KVH)
      F:  p = softmax_j(s) over j <= t
      S:  p_tj = exp(s_tj) / (exp(b_h) + sum_j' exp(s_tj')) over
              t - W < j <= t, b_h the head's learned sink (add_swa_attention_
              sink_bias): it takes mass and adds no value
      x    += (sum_j p_tj v_j -> [T, H*dv]) . Wo
      m     = n(x)
      moe_layer_freq[l] == 0:  x += (silu(m.Wg) * (m.Wu)) . Wd at
              intermediate_size
      else:   p = sigmoid(m.Wr) over ALL published experts (float32)
              choice = p + e_score_correction_bias (one group); its
              num_experts_per_tok best; weights = p of the chosen (not
              choice), divided by their sum (norm_topk_prob), times
              routed_scaling_factor (null: 1); no shared expert
              x += sum_{chosen e held here} w_e * expert_e(m), experts SwiGLU
              at moe_intermediate_size
    logits = n(x) . W_head                                     (untied)

**Assumed readings** (``assumed`` in the configuration file):
1. the sink's form: in the softmax's denominator only, one scalar a query
   head of the window layers, as the one public family with such a term has
   it (gpt-oss's ``sinks``); ``add_full_attention_sink_bias: false``: the
   full layers have none.
2. ``attention_value_scale`` multiplies the value states (before the
   probabilities meet them; the same as scaling the attention output).
3. ``sliding_window: 128`` counts the query's own position: 128 keys.
4. ``partial_rotary_factor`` 0.334 of 192 lanes is 64 rotating lanes, the
   FIRST of each head, in HF's half-split pairing within them, frequencies
   theta^(-2i/64); the other 128 lanes carry no position.
5. ``attention_chunk_size: 128`` and ``hybrid_block_size: null`` are not
   read: the window layers slide (the catalog's description: "SWA(128)"),
   they are not chunked.
6. text only: the vision tower, the audio encoder and the three
   multi-token-prediction layers of the description are not in ``config``
   and not served.
7. ``attention_projection_layout: "fused_qkv"`` is a checkpoint layout
   (q | k | v along the out axis), not mathematics.

**The expert share** is ``references/deepseek_v32.py``'s: ``n_routed_experts``
counts the experts held here, ``n_routed_experts_published`` the router's
width, ``expert_share_index`` which share; the router, its bias, the top-k and
the renormalisation are over all the published experts, and what a chosen
expert that lives elsewhere would add is left out, as in the program.

It reads the engine's own parameter tree (``models/mimo.py``
``param_shapes``: the F layers' leaves ``layers.<leaf>`` [F layers, ...], the
S layers' ``layers.swa_<leaf>`` [S layers, ...] with ``swa_sink``
[S layers, H], norms [layers, D], ``dense_*``, the expert stacks as v3's;
int8 as q.scale; q|k|v and gate|up split where ``fuse_stacked_matmuls``
joined them), one layer at a time, blocked so that a 33k-token prompt fits
beside the engine: the query heads of one kv head at a time, queries in
blocks (a window layer's block reads its ``block + W - 1`` keys), a dense
MLP in slices, one expert at a time. An expert is run on the rows routed to
it, gathered up to EXPERT_ROWS_SHARE of the sequence (the sum is the same:
a row not routed to an expert has weight 0 there); a sequence that routes
more rows than that to one expert runs every row through every expert.
``logits_for`` keeps its last ``ANSWERS_KEPT`` answers: the harness asks for
the same sequence under the same weights before its window and after it.

**Controls** (``CONTROLS``; not breakages: the same mathematics at the next
precision below the one the configuration states, which the comparison has to
tell from the program's). ``int4_weights`` rounds the weights of the matmuls
that the program holds in int8 (q, k, v and output projections of both
kinds, the dense MLP, the routed experts) to 4 bits under one scale per 128
input rows and output column, ``quant.quantize_array_grouped``'s rule; the
router, the sinks, the norms, the embedding and the head stay as stored.
``logits_for(..., precision="default")`` runs every matmul in the device's
default precision (bf16 passes on a TPU), the program's own: the witness
that reads INSIDE the tolerance.

Departures from the published code, each shared with the program: text only;
weights are the int8-rounded ones the engine holds.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from reference import _rms, _split, _swiglu, _w, embed_rows, head_logits

BREAKAGES = ("drop_layer", "no_sink", "sink_on_full", "sink_as_key",
             "window_plus_one", "window_minus_one", "swa_full_theta",
             "rope_all_lanes", "no_value_scale", "swa_kv_heads_as_full",
             "v_at_key_stride", "no_router_bias", "unit_routing_weights",
             "absent_expert_added")
# ISSUE 46's names for two of them, where this file says what is computed
ALIASES = {"swa_kv_heads_4": "swa_kv_heads_as_full",
           "v_dim_192": "v_at_key_stride"}

CONTROLS = ("int4_weights",)

# What served logits (bf16, int8 weights, random weights) do not show with
# room to spare, so breakages_for does not ask for it: (logprob error,
# argmax gap) in standard deviations of the logits against TOL_STD 0.25, at
# the published widths on the chip, a 32,832-token probe, depth 1 + 12 (my
# chip run, PR 46, seed 460001; the program itself read 0.029 / 0.000 there
# and 0.03-0.09 over the seeds, the float32 reference in bf16 0.038). One
# key more or less in a window of 128, a sink on 3 of 13 layers and what one
# absent expert of 256 would add are small by nature; a dropped layer is one
# of thirteen equal voices; the value scale and the router's bias move the
# logits by 0.21-0.27, which is no room on either side of 0.25. Scaling the
# seeded rule does not part them: breakages and the program's own bf16 error
# grow together (wo x 2: no_value_scale 1.19, the program 0.22; x 3: the
# program 0.35, outside). tests/test_mimo_v2.py holds every one in float32,
# and the CPU fixture (6 layers) catches these three in served logits too.
FINE_MEASURED = {"window_plus_one": (0.089, 0.000),
                 "window_minus_one": (0.083, 0.000),
                 "sink_on_full": (0.033, 0.000),
                 "absent_expert_added": (0.049, 0.000),
                 "drop_layer": (0.138, 0.003),
                 "no_value_scale": (0.214, 0.177),
                 "no_router_bias": (0.274, 0.097)}
FINE = tuple(FINE_MEASURED)

# queries whose scores exist at once, a dense MLP's slice, and the share of
# a sequence's rows one expert is gathered (at least EXPERT_ROWS_MIN)
QUERY_BLOCK = 64
MLP_SLICE = 2048
EXPERT_ROWS_SHARE = 8
EXPERT_ROWS_MIN = 64


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


def breakages_for(hf: dict) -> tuple:
    """Those that the tolerance has to catch on served logits (bf16, int8
    weights): all but FINE (at the published widths they read 0.46-2.87,
    the int4 control 0.47: PERF.md section 6, PR 46).
    tests/test_mimo_v2.py holds every one of BREAKAGES in float32."""
    family(hf)
    return tuple(b for b in BREAKAGES if b not in FINE)


def family(hf: dict) -> dict:
    """The sizes the mathematics needs, from the published config keys."""
    if hf["model_type"] != "mimo_v2":
        raise ValueError(f"the mimo_v2 reference does not compute "
                         f"{hf['model_type']!r}")
    rs = hf.get("rope_scaling") or {}
    refused = {
        "topk_method": hf.get("topk_method", "noaux_tc") != "noaux_tc",
        "scoring_func": hf.get("scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
        "rope_scaling": rs.get("rope_type", rs.get("type", "default"))
        != "default",
        "n_group": int(hf.get("n_group") or 1) != 1,
        "n_shared_experts": bool(hf.get("n_shared_experts")),
        "add_full_attention_sink_bias": bool(
            hf.get("add_full_attention_sink_bias")),
    }
    if any(refused.values()):
        raise ValueError("the mimo_v2 reference does not compute this "
                         "configuration's "
                         + ", ".join(k for k, v in refused.items() if v))
    layers = int(hf["num_hidden_layers"])
    held = int(hf["n_routed_experts"])
    dk = int(hf["head_dim"])
    rot = int(dk * float(hf["partial_rotary_factor"]))
    freq = list(hf["moe_layer_freq"][:layers])

    def geometry(p: str, theta: str) -> dict:
        return {"heads": int(hf[p + "num_attention_heads"]),
                "kv_heads": int(hf[p + "num_key_value_heads"]),
                "dk": int(hf[p + "head_dim"]),
                "dv": int(hf[p + "v_head_dim"]),
                "theta": float(hf[theta])}

    return {
        "layers": layers,
        "kinds": tuple("S" if p else "F"
                       for p in hf["hybrid_layer_pattern"][:layers]),
        "F": geometry("", "rope_theta"),
        "S": geometry("swa_", "swa_rope_theta"),
        "rot": rot - rot % 2,
        "window": int(hf["sliding_window"]),
        "sink": bool(hf.get("add_swa_attention_sink_bias")),
        "value_scale": float(hf.get("attention_value_scale") or 1.0),
        "eps": float(hf.get("layernorm_epsilon", 1e-5)),
        "held": held,
        "experts": int(hf.get("n_routed_experts_published") or held),
        "first_held": int(hf.get("expert_share_index") or 0) * held,
        "top_k": int(hf["num_experts_per_tok"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "first_dense": next((i for i, f in enumerate(freq) if f), layers),
        "routed_scaling": float(hf.get("routed_scaling_factor") or 1.0),
    }


def _rope(x, theta: float, rot: int):
    """x: [T, heads, dk], positions 0..T-1: the first ``rot`` lanes of every
    head rotated, lane i with lane i + rot/2; the others pass."""
    T = x.shape[0]
    inv = jnp.asarray((1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64)
                                       / rot)).astype(np.float32))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rot:]], -1)


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
    out.update({n: get(prefix + n, ai)
                for n in ("wq", "wk", "wv", "wqkv", "wo", "sink")})
    if kind == "F":
        # what the sink_on_full breakage gives a full layer: the first
        # window layer's
        out["sink"] = get("swa_sink", 0)
    if li >= fam["first_dense"]:
        names = ("router", "router_bias", "moe_gate", "moe_up", "moe_gateup",
                 "moe_down")
        out.update({n: get(n, li - fam["first_dense"]) for n in names})
    else:
        out.update({n: get(f"dense_{n}", li)
                    for n in ("gate", "up", "gateup", "down")})
    return {n: w for n, w in out.items() if w is not None}


def moe_block(fam: dict, broken=None):
    """→ f(m [T, D] f32, an expert layer's weights) → the layer's MLP output
    [T, D]: what the experts held here add for the tokens routed to them."""
    wt = _weights(broken)

    def moe_mlp(m, lw):
        T, E, K = m.shape[0], fam["experts"], fam["top_k"]
        p = jax.nn.sigmoid(m @ _w(lw["router"]))
        bias = _w(lw["router_bias"])[None, :]
        if broken == "no_router_bias":
            bias = jnp.zeros_like(bias)
        _, top_i = jax.lax.top_k(p + bias, K)
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
        cap = min(T, max(EXPERT_ROWS_MIN, -(-T // EXPERT_ROWS_SHARE)))

        def run(x, rows, w):
            g, u = (_split(None, x["gu"]) if fused
                    else _split(x["gu"], None))
            return w[:, None] * _swiglu(rows, wt(g), wt(u),
                                        wt(_w(x["down"])))

        def gathered(acc, x):
            # the rows routed to this expert (weight != 0), padded with
            # row 0 at weight 0
            at = jnp.nonzero(x["w"], size=cap, fill_value=0)[0]
            w = jnp.where(jnp.arange(cap) < jnp.sum(x["w"] != 0),
                          x["w"][at], 0.0)
            return acc.at[at].add(run(x, m[at], w)), None

        def every_row(acc, x):
            return acc + run(x, m, x["w"]), None

        xs = {"gu": gu, "down": lw["moe_down"], "w": mine.T}
        fits = jnp.max(jnp.sum(mine != 0, axis=0)) <= cap
        return jax.lax.cond(
            fits,
            lambda: jax.lax.scan(gathered, jnp.zeros_like(m), xs)[0],
            lambda: jax.lax.scan(every_row, jnp.zeros_like(m), xs)[0])
    return moe_mlp


def make_layer(fam: dict, kind: str, moe: bool, broken=None):
    """→ jitted f(x [T, D] f32, the layer's weights) → x."""
    g = dict(fam[kind])
    H, KVH, dk, dv = g["heads"], g["kv_heads"], g["dk"], g["dv"]
    eps, rot, W = fam["eps"], fam["rot"], fam["window"]
    theta = g["theta"]
    if kind == "S":
        W += {"window_plus_one": 1, "window_minus_one": -1}.get(broken, 0)
        if broken == "swa_full_theta":
            theta = fam["F"]["theta"]
    if broken == "rope_all_lanes":
        rot = dk
    value_scale = 1.0 if broken == "no_value_scale" else fam["value_scale"]
    # the query heads a kv head serves; under swa_kv_heads_as_full a window
    # layer's heads are grouped as a full layer's (over its first kv heads)
    groups = KVH
    if kind == "S" and broken == "swa_kv_heads_as_full":
        groups = fam["F"]["kv_heads"]
    per = H // groups
    has_sink = fam["sink"] and (
        (kind == "S" and broken != "no_sink")
        or (kind == "F" and broken == "sink_on_full"))
    wt = _weights(broken)
    moe_mlp = moe_block(fam, broken) if moe else None

    def layer(x, lw):
        T = x.shape[0]
        a = _rms(x, _w(lw["ln1"]), eps)
        if "wqkv" in lw:
            w = wt(_w(lw["wqkv"]))
            wq, wk, wv = (w[:, :H * dk], w[:, H * dk:(H + KVH) * dk],
                          w[:, (H + KVH) * dk:])
        else:
            wq, wk, wv = (wt(_w(lw[n])) for n in ("wq", "wk", "wv"))
        wo = wt(_w(lw["wo"]))
        k = _rope((a @ wk).reshape(T, KVH, dk), theta, rot)
        v_flat = value_scale * (a @ wv)                       # [T, KVH*dv]
        if broken == "v_at_key_stride":
            # head kh's values read at lane dk*kh of the row, the keys'
            # stride, and not at dv*kh (around the row's end)
            v = jnp.stack([jnp.roll(v_flat, -dk * kh, axis=1)[:, :dv]
                           for kh in range(KVH)], axis=1)
        else:
            v = v_flat.reshape(T, KVH, dv)
        sink = (_w(lw["sink"]).reshape(-1)[:H] if has_sink else None)
        # a window layer's query block reads the block's keys and the W - 1
        # before them; a full layer's, every key up to the block's end
        band = kind == "S"
        KB = QUERY_BLOCK + W - 1 if band else T

        def group(acc, kh):
            hs = kh * per + jnp.arange(per)
            wq_g = jnp.take(wq.reshape(-1, H, dk), hs, axis=1)
            q = _rope(jnp.einsum("td,dhe->the", a, wq_g), theta, rot)
            k_h, v_h = k[:, kh], v[:, kh]
            if band:
                k_h = jnp.pad(k_h, ((W - 1, QUERY_BLOCK), (0, 0)))
                v_h = jnp.pad(v_h, ((W - 1, QUERY_BLOCK), (0, 0)))

            def block(q0, rows):
                (qb,) = rows                              # [QB, per, dk]
                lo = q0 if band else 0        # in the padded frame
                ks = jax.lax.dynamic_slice_in_dim(k_h, lo, KB, axis=0)
                vs = jax.lax.dynamic_slice_in_dim(v_h, lo, KB, axis=0)
                s = jnp.einsum("qhd,sd->hqs", qb, ks) / math.sqrt(dk)
                kpos = (lo - (W - 1 if band else 0)
                        + jnp.arange(KB))[None, :]
                qpos = (q0 + jnp.arange(QUERY_BLOCK))[:, None]
                mask = (kpos <= qpos) & (kpos >= 0) & (kpos < T)
                if band:
                    mask = mask & (kpos > qpos - W)
                s = jnp.where(mask[None], s, -jnp.inf)
                mx = jnp.max(s, -1, keepdims=True)
                if sink is not None:
                    b = sink[hs][:, None, None]
                    mx = jnp.maximum(mx, b)
                e = jnp.where(mask[None], jnp.exp(s - mx), 0.0)
                den = jnp.sum(e, -1, keepdims=True)
                out = jnp.einsum("hqs,sd->qhd", e, vs)
                if sink is not None:
                    es = jnp.exp(b - mx)
                    den = den + es
                    if broken == "sink_as_key" and kind == "S":
                        # the sink as one more key, with the value of the
                        # oldest key of the row's window
                        first = jnp.clip(qpos[:, 0] - (W - 1), 0, T - 1)
                        v0 = jnp.take(v[:, kh], first, axis=0)   # [QB, dv]
                        out = out + jnp.moveaxis(es, 0, 1) * v0[:, None, :]
                return out / jnp.moveaxis(den, 0, 1)

            att = _blocked(block, (q,), QUERY_BLOCK)      # [T, per, dv]
            wo_g = jnp.take(wo.reshape(H, dv, -1), hs, axis=0)
            return acc + jnp.einsum("the,hed->td", att, wo_g), None

        att, _ = jax.lax.scan(group, jnp.zeros_like(x), jnp.arange(groups))
        x = x + att
        m = _rms(x, _w(lw["ln2"]), eps)
        if moe:
            return x + moe_mlp(m, lw)
        gw, uw = _split((lw.get("gate"), lw.get("up")), lw.get("gateup"))
        gw, uw, dw = wt(gw), wt(uw), wt(_w(lw["down"]))
        F = gw.shape[-1]
        out = jnp.zeros_like(m)
        for lo in range(0, F, MLP_SLICE):
            sl = slice(lo, lo + MLP_SLICE)
            out = out + (jax.nn.silu(m @ gw[:, sl]) * (m @ uw[:, sl])) @ dw[sl]
        return x + out

    return jax.jit(layer)


def forward(params: dict, hf: dict, tokens, broken=None):
    """→ the final hidden states [T, D] float32."""
    fam = family(hf)
    broken = ALIASES.get(broken, broken)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = embed_rows(params, tokens)
    layers = {}
    n_layers = fam["layers"] - (1 if broken == "drop_layer" else 0)
    for li in range(n_layers):
        key = (fam["kinds"][li], li >= fam["first_dense"])
        if key not in layers:
            layers[key] = make_layer(fam, key[0], key[1], broken)
        x = layers[key](x, _layer_weights(params, li, fam))
    return x


# The harness asks the same question twice: ``run.py`` holds its probes to
# this file before the window and again after it, and a probe that serves the
# same tokens again hands in the same sequence. The answer is a function of
# the weights and the tokens alone, and one forward over a 33k-token probe is
# seconds of the chip in float32, so the last answers are kept and an
# identical question is answered from them (identical: the same weight
# arrays, by identity, and the same tokens, breakage and precision). What the
# second comparison is for, the ENGINE's state after the window, is held as
# before: the served tokens and logprobs are new each time.
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
