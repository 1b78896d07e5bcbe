"""Operations and bytes of DeepSeek-V3.2's two sparse-attention stages in a
decode step, from the configuration's shapes and the step's two counters
(``ctx_tokens`` = Σ live context over the step's sequences, ``sel_tokens`` =
Σ min(context, index_topk); the ``decode`` flight records carry both). What
is counted is what the algorithm has to do, once per layer:

* **selection** (``dsa_select``): every live position's index key is read
  once per sequence that owns it (dI lanes of 2 bytes: the implementation
  shares no read between sequences of one document, so none is discounted)
  and scored by J heads: 2·J·dI operations for the dots and 2·J for
  relu·w summed. The exact top-k is counted as no operations and no bytes:
  a lower bound, so the share can only be understated by it;
* **sparse attention** (``sparse_attention``): each selected latent row is
  read once (rank + rope lanes of 2 bytes; the pool's pad lanes are not
  needed) and used by H heads in the absorbed form: 2·(rank+rope) for the
  score and 2·rank for probs·c.

Queries, head weights and outputs are left out of the bytes (a few hundred
kilobytes a step against hundreds of megabytes of rows).
"""

from __future__ import annotations

import json
import os
import re
import statistics


def shapes(hf: dict) -> dict:
    return {"L": int(hf["num_hidden_layers"]),
            "H": int(hf["num_attention_heads"]),
            "rank": int(hf["kv_lora_rank"]),
            "dr": int(hf["qk_rope_head_dim"]),
            "J": int(hf["index_n_heads"]), "dI": int(hf["index_head_dim"]),
            "topk": int(hf["index_topk"])}


def select_step(hf: dict, ctx_tokens: float,
                bytes_per_value: float = 2.0) -> dict:
    """The selection of one decode step, all layers."""
    s = shapes(hf)
    return {"flops": s["L"] * ctx_tokens * (2 * s["J"] * s["dI"]
                                            + 2 * s["J"]),
            "bytes": s["L"] * ctx_tokens * s["dI"] * bytes_per_value}


def sparse_attention_step(hf: dict, sel_tokens: float,
                          bytes_per_value: float = 2.0) -> dict:
    """The attention over the selected rows of one decode step, all
    layers."""
    s = shapes(hf)
    row = s["rank"] + s["dr"]
    return {"flops": s["L"] * sel_tokens * s["H"] * (2 * row
                                                     + 2 * s["rank"]),
            "bytes": s["L"] * sel_tokens * row * bytes_per_value}


# ---------------------------------------------------------- the traced ops

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "deepseek-v3.2.json")
PROGRAM = "jit_decode_k"


def served_config() -> tuple:
    """→ (published keys of the configuration these readers belong to, its
    ``--max-model-len``)."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    flags = config["deployment"]["flags"]
    return config, int(flags[flags.index("--max-model-len") + 1])


# A stage that a Pallas kernel serves is read by the kernel's ``name=``, as
# ``layer_metrics/kernel.paged_attention_ms.py`` reads its kernel. The
# selection's two: the index scores (``engine/index_scores.py``, PR 36) and
# the exact top-k (``engine/select_compact.py``, PR 34; its result is
# ``[B, K]``, which no shape of ``stage_patterns`` holds). The sparse read
# is plain XLA: no op carries the name ISSUE 31 reserved for it.
KERNELS = {"dsa_select": ("%index_scores", "%dsa_select_compact"),
           "sparse_attention": ("%sparse_latent_attention",)}


def stage_patterns(engine: dict) -> dict:
    """The device ops of a decode step's two stages where no kernel names
    them, told apart by their result shapes (the stages are plain XLA, and
    the profiler of JAX 0.9.0 / libtpu 0.0.34 writes no ``op_name``, so
    ``ctx["trace"]["scopes"]`` is empty). With B = the decode batch
    (``max_num_seqs``), S = the table's positions (``max_model_len``),
    K = ``index_topk``, bs = the block size:

    * ``dsa_select``: every op whose result has the batch first and the
      table's length in it — the index keys gathered by block
      ``[B·S/bs, bs, dI]``, the block ids and slots ``[B, S/bs, bs]`` /
      ``[B·S/bs]``, the scores, the mask and the sort of the top-k
      ``[B, S]``;
    * ``sparse_attention``: the gathered rows ``[B·K, W]``, the scores and
      probabilities ``[B, H, K]``, and ``[B, H, rank]``: probs·c, and with
      it the absorbed query q_nope·W_k, which has the same shape and is not
      in the stage's scope (33 MB of ``wkv_b`` a layer: 1.4% of the stage's
      time in the traced run of PR 31, PERF.md §5). A 2-D ``[B, K]`` result
      is not taken: K is also the shared expert's width.

    No op of a prefill has the decode batch first (its chunks have 256
    rows, its query blocks 32: 32·K is not B·K), and no other op of a
    decode step has S or S/bs in its shape (checked against the traced
    run's whole op table: PERF.md §5 lists the names matched).
    → {"dsa_select": regex, "sparse_attention": regex}, or {} where the
    engine is not this configuration's."""
    hf, table = served_config()
    batch, block = engine.get("max_num_seqs"), engine.get("kv_block_size")
    if not batch or not block or table % block:
        return {}
    s = shapes(hf)
    B, S, K = batch, table, min(s["topk"], table)
    return {
        "dsa_select": re.compile(
            rf"\[{B},(\d+,)*{S}(,\d+)*\]|\[{B},{S // block},\d+(,\d+)*\]"
            rf"|\[{B * S // block}(,\d+)*\]"),
        "sparse_attention": re.compile(
            rf"\[{B},{s['H']},({K}|{s['rank']})\]|\[{B * K}(,\d+)+\]"),
    }


def stage_ops(ctx: dict, stage: str) -> list:
    """[name, seconds, count] of the traced ops that are ``stage``'s: those
    a kernel of KERNELS names, and those the shapes tell. A kernel's call
    counts where its result has the decode batch first: a prefill chunk
    selects through the same ``dsa_select_compact`` for its 256 rows
    (``s32[256, K]`` against the step's ``s32[B, K]``), and the stage is a
    decode step's. Where a kernel serves ``sparse_attention`` the shapes
    are not asked (its result is the ``[B, H, rank]`` that the absorbed
    query also has); the selection keeps them beside its kernels, because
    the ``[B, S]`` ops around those (the scores' relayout, the order bits,
    the packed key, the runs table) stay plain XLA."""
    ops = (ctx.get("trace") or {}).get("ops", ())
    batch = (ctx.get("engine") or {}).get("max_num_seqs")
    named = [op for op in ops if op[0].startswith(KERNELS[stage])
             and (not batch or f"[{batch}," in op[0])]
    if named and stage == "sparse_attention":
        return named
    pattern = stage_patterns(ctx.get("engine") or {}).get(stage)
    if pattern is None:
        return named
    return named + [op for op in ops if op not in named
                    and pattern.search(op[0])]


def stage_seconds_per_step(ctx: dict, stage: str):
    """Device seconds of ``stage``'s ops per dispatch of the served decode
    program, over the profiler's window; None where there is nothing to
    read."""
    seconds = sum(sec for _, sec, _ in stage_ops(ctx, stage))
    steps = sum(n for name, _, n in (ctx.get("trace") or {}).get(
        "programs", ()) if name == PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps


def stage_roofline_pct(ctx: dict, stage: str):
    """100 × (the least time the chip could take for the stage's share of a
    median decode step of the window) / (its measured device time per
    step). The step's counters come from the ``decode`` flight records
    (before the profiler starts), the peaks from ``peaks.py`` by the
    device's kind."""
    measured = stage_seconds_per_step(ctx, stage)
    if measured is None:
        return None
    counter = "ctx_tokens" if stage == "dsa_select" else "sel_tokens"
    counts = [r[counter] / max(1, r.get("K", 1)) for r in ctx["flight"]
              if r["kind"] == "decode" and r.get(counter)]
    if not counts:
        return None
    import jax
    import peaks
    hf, _ = served_config()
    cost = (select_step if stage == "dsa_select"
            else sparse_attention_step)(hf, statistics.median(counts))
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / measured
