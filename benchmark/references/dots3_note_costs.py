"""Operations and bytes of dots3-note-prev's attention stages in a decode
step, from the configuration's shapes and the step's counters (the ``decode``
flight records: ``ctx_tokens`` = sum of the live context over the step's
sequences, ``sel_tokens`` = sum of min(context, index_topk), ``win_tokens`` =
sum of min(context, sliding_window_size)), and the device ops that are each
stage's. What is counted is what the model needs, once per layer of the kind:

* **selection** (``dsa_select``) and **sparse attention**
  (``sparse_attention``) of the full-attention layers: as
  ``references/deepseek_v32_costs.py`` counts them, at this model's shapes
  and over its full-attention layers alone;
* **the window read** (``swa_latent``) of the sliding-attention layers: each
  of the min(context, 513) rows of a sequence's window is read once a layer
  (1,152 lanes of 2 bytes, the padded row: the kernel reads whole rows) and
  used by 64 heads in the absorbed form, 2·(rank+rope) for the score and
  2·rank for probs·c: the rows the model needs, not the blocks an
  implementation touches (at most 34 blocks of 16 = 544 rows).

Queries, gates and outputs are left out of the bytes.
"""

from __future__ import annotations

import json
import os
import re
import statistics

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "dots3-note-prev.json")
PROGRAM = "jit_decode_k"
# the window read is the paged-attention kernel in its one-head latent form;
# the full-attention layers of this model never call it (they select)
SWA_KERNEL = "%paged_attention"
# stages a Pallas kernel serves, by the kernel's ``name=`` (the selection's
# index scores and its exact top-k)
KERNELS = {"dsa_select": ("%index_scores", "%dsa_select_compact"),
           "sparse_attention": ("%sparse_latent_attention",)}


def shapes(hf: dict) -> dict:
    kinds = hf["layer_types"][:int(hf["num_hidden_layers"])]
    return {"LF": kinds.count("full_attention"),
            "LS": kinds.count("sliding_attention"),
            "H": int(hf["num_attention_heads"]),
            "rank": int(hf["kv_lora_rank"]),
            "dr": int(hf["qk_rope_head_dim"]),
            "J": int(hf["index_n_heads"]), "dI": int(hf["index_head_dim"]),
            "topk": int(hf["index_topk"]),
            "Hs": int(hf["swa_num_attention_heads"]),
            "rank_s": int(hf["swa_kv_lora_rank"]),
            "dr_s": int(hf["swa_qk_rope_head_dim"]),
            "window": int(hf["sliding_window_size"])}


def select_step(hf: dict, ctx_tokens: float,
                bytes_per_value: float = 2.0) -> dict:
    """The selection of one decode step, all full-attention layers."""
    s = shapes(hf)
    return {"flops": s["LF"] * ctx_tokens * (2 * s["J"] * s["dI"]
                                             + 2 * s["J"]),
            "bytes": s["LF"] * ctx_tokens * s["dI"] * bytes_per_value}


def sparse_attention_step(hf: dict, sel_tokens: float,
                          bytes_per_value: float = 2.0) -> dict:
    """The attention over the selected rows of one decode step, all
    full-attention layers."""
    s = shapes(hf)
    row = s["rank"] + s["dr"]
    return {"flops": s["LF"] * sel_tokens * s["H"] * (2 * row
                                                      + 2 * s["rank"]),
            "bytes": s["LF"] * sel_tokens * row * bytes_per_value}


def swa_read_step(hf: dict, win_tokens: float,
                  bytes_per_value: float = 2.0) -> dict:
    """The window read of one decode step, all sliding-attention layers."""
    s = shapes(hf)
    row = s["rank_s"] + s["dr_s"]
    lanes = -(-row // 128) * 128
    return {"flops": s["LS"] * win_tokens * s["Hs"] * (2 * row
                                                       + 2 * s["rank_s"]),
            "bytes": s["LS"] * win_tokens * lanes * bytes_per_value}


STEP_COST = {"dsa_select": (select_step, "ctx_tokens"),
             "sparse_attention": (sparse_attention_step, "sel_tokens"),
             "swa_latent": (swa_read_step, "win_tokens")}


def served_config() -> tuple:
    """→ (the configuration these readers belong to, its
    ``--max-model-len``)."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    flags = config["deployment"]["flags"]
    return config, int(flags[flags.index("--max-model-len") + 1])


def stage_patterns(engine: dict) -> dict:
    """The device ops of the two stages of a full-attention layer where no
    kernel names them, by their result shapes, as
    ``deepseek_v32_costs.stage_patterns`` tells them (B the decode batch, S
    the table's positions, K = index_topk, bs the block size): the
    selection's results have the batch first and S, S/bs or B·S/bs in them;
    the sparse read's are ``[B·K, W]``, ``[B, H, K]`` and ``[B, H, rank]``.
    No op of a sliding-attention layer has them (its heads are Hs = 64, its
    table 34 entries). → {} where the engine is not this configuration's."""
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
    """[name, seconds, count] of the traced ops that are ``stage``'s; a
    kernel's call counts where its result has the decode batch first (a
    prefill chunk selects through the same ``dsa_select_compact``)."""
    ops = (ctx.get("trace") or {}).get("ops", ())
    if stage == "swa_latent":
        return [op for op in ops if op[0].startswith(SWA_KERNEL)]
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
    program, over the profiler's window; None: nothing to read."""
    seconds = sum(sec for _, sec, _ in stage_ops(ctx, stage))
    steps = sum(n for name, _, n in (ctx.get("trace") or {}).get(
        "programs", ()) if name == PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps


def stage_roofline_pct(ctx: dict, stage: str):
    """100 × (the least time the chip could take for the stage's share of a
    median decode step of the window) / (its measured device time a step);
    the step's counter from the ``decode`` flight records, the peaks from
    ``peaks.py`` by the device's kind."""
    measured = stage_seconds_per_step(ctx, stage)
    if measured is None:
        return None
    cost_of, counter = STEP_COST[stage]
    counts = [r[counter] / max(1, r.get("K", 1)) for r in ctx["flight"]
              if r["kind"] == "decode" and r.get(counter)]
    if not counts:
        return None
    import jax
    import peaks
    hf, _ = served_config()
    cost = cost_of(hf, statistics.median(counts))
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / measured
