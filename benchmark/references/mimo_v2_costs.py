"""Operations and bytes of the four attention reads of a model of two
grouped-query geometries (a configuration that names the reference
``mimo_v2``), counted from the mathematics, whatever implements them, and
the traced Pallas calls the two decode reads are held against.

A layer of a kind has H query heads over KVH key/value heads, keys of dk and
values of dv lanes; a cached row of one layer is KVH·(dk + dv) values of 2
bytes, the heads side by side and unpadded (``models/mimo.py``: at the
published sizes 64 query heads over 4 key/value heads in a full layer, key
rows of 768 and value rows of 512 lanes, 2,560 B; 64 over 8 with a sink in the
softmax's denominator in a window layer, 1,536 and 1,024 lanes, 5,120 B; no
lane of the row is padding, so none is counted). Every (query, key) pair
costs each query head 2·dk operations for the score and 2·dv for probs·v.

* **the full read** of a decode step (``decode`` flight records carry
  ``ctx_tokens`` = the sum of the live context over the step's sequences):
  every live row is read once a full layer by the sequence it belongs to.
  Sequences that share a prefix hold the same blocks, and each reads them
  for itself: every slot's own read of shared rows counts.
* **the window read** of a decode step (``win_tokens`` = the sum of
  min(context, window)): the rows the model needs, once a window layer, not
  the blocks an implementation touches (at most 9 blocks of 16 = 144 rows).
* **the full prefill read** of a chunk of ``rows`` queries whose table holds
  ``live`` rows: the causal pairs, and every live row read once a full layer.
* **the window prefill read** of the same chunk: at most ``window`` pairs a
  query, and the chunk's rows and the ``window - 1`` before them read once a
  window layer.

Queries, outputs and the sinks are left out of the bytes. Only the two decode
reads have readers (``layer_metrics/kernel.gqa_{full,window}_*``): a traced
window of this family's cell holds ~3 admissions, one time in twenty none, and
a reading that is sometimes absent may not be listed (PERF.md section 7).
"""

from __future__ import annotations

import json
import os
import statistics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the plain reference a configuration of this family names
REFERENCE = "mimo_v2"
DECODE_PROGRAM = "jit_decode_k"
PREFILL_PROGRAM = "jit_prefill"
# the Pallas calls of the four reads, by their ``name=`` (models/mimo.py)
KERNELS = {"gqa_full": "%gqa_full_read", "gqa_window": "%gqa_window_read",
           "gqa_full_prefill": "%gqa_full_prefill",
           "gqa_window_prefill": "%gqa_window_prefill"}


def shapes(hf: dict) -> dict:
    kinds = hf["hybrid_layer_pattern"][:int(hf["num_hidden_layers"])]

    def geometry(p: str) -> dict:
        return {"H": int(hf[p + "num_attention_heads"]),
                "KVH": int(hf[p + "num_key_value_heads"]),
                "dk": int(hf[p + "head_dim"]),
                "dv": int(hf[p + "v_head_dim"])}

    return {"F": dict(geometry(""), layers=kinds.count(0)),
            "S": dict(geometry("swa_"), layers=kinds.count(1)),
            "window": int(hf["sliding_window"])}


def _read(g: dict, pairs: float, rows_read: float,
          bytes_per_value: float) -> dict:
    """``pairs`` (query, key) pairs and ``rows_read`` cached rows in every
    layer of the geometry ``g``."""
    return {"flops": g["layers"] * pairs * g["H"] * (2 * g["dk"]
                                                     + 2 * g["dv"]),
            "bytes": (g["layers"] * rows_read * g["KVH"]
                      * (g["dk"] + g["dv"]) * bytes_per_value)}


def full_read_step(hf: dict, ctx_tokens: float,
                   bytes_per_value: float = 2.0) -> dict:
    """The full layers' read of one decode step."""
    return _read(shapes(hf)["F"], ctx_tokens, ctx_tokens, bytes_per_value)


def window_read_step(hf: dict, win_tokens: float,
                     bytes_per_value: float = 2.0) -> dict:
    """The window layers' read of one decode step."""
    return _read(shapes(hf)["S"], win_tokens, win_tokens, bytes_per_value)


def full_prefill_chunk(hf: dict, rows: float, live: float,
                       bytes_per_value: float = 2.0) -> dict:
    """The full layers' read of a prefill chunk: ``rows`` queries, the last
    of a table of ``live`` rows."""
    pairs = rows * (live - rows) + rows * (rows + 1) / 2
    return _read(shapes(hf)["F"], pairs, live, bytes_per_value)


def window_prefill_chunk(hf: dict, rows: float, live: float,
                         bytes_per_value: float = 2.0) -> dict:
    """The window layers' read of the same chunk."""
    s = shapes(hf)
    first = live - rows                     # the chunk's first position
    pairs = sum(min(first + t + 1, s["window"]) for t in range(int(rows)))
    return _read(s["S"], pairs, min(live, rows + s["window"] - 1),
                 bytes_per_value)


STEP_COST = {"gqa_full": (full_read_step, "ctx_tokens"),
             "gqa_window": (window_read_step, "win_tokens")}


# ---------------------------------------------------------- the traced ops

def served_config(ctx: dict):
    """→ the published keys of the configuration that is being served: the
    one of BENCHMARK.json that names this family's reference and whose
    deployment gives the engine ``ctx`` shows; None where there is none
    (another family's cell)."""
    engine = ctx.get("engine") or {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    for file in files:
        with open(os.path.join(ROOT, file)) as f:
            config = json.load(f)
        flags = (config.get("deployment") or {}).get("flags") or []

        def flag(name):
            return int(flags[flags.index(name) + 1]) if name in flags else None

        if config.get("reference") == REFERENCE and all(
                flag(f"--{key.replace('_', '-')}") == engine.get(key)
                for key in ("max_num_seqs", "num_kv_blocks", "kv_block_size")):
            return config
    return None


def kernel_seconds(ctx: dict, stage: str) -> tuple:
    """→ (seconds, calls) of the Pallas calls of ``stage`` in the profiler's
    window."""
    ops = [op for op in (ctx.get("trace") or {}).get("ops", ())
           if op[0].startswith(KERNELS[stage])]
    return sum(sec for _, sec, _ in ops), sum(n for _, _, n in ops)


def stage_seconds_per_step(ctx: dict, stage: str):
    """Device seconds of a decode read's Pallas calls (all the layers of its
    kind) per dispatch of the served decode program, over the profiler's
    window; None: nothing to read (no such kernel in the trace: another
    family, or a parent without it)."""
    seconds, _ = kernel_seconds(ctx, stage)
    steps = sum(n for name, _, n in (ctx.get("trace") or {}).get(
        "programs", ()) if name == DECODE_PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps


def stage_roofline_pct(ctx: dict, stage: str):
    """100 × (the least time the chip could take for the read's share of a
    median decode step of the window) / (its measured device time a step);
    the step's counter from the ``decode`` flight records, the peaks from
    ``peaks.py`` by the device's kind."""
    measured = stage_seconds_per_step(ctx, stage)
    hf = served_config(ctx)
    if measured is None or hf is None:
        return None
    cost_of, counter = STEP_COST[stage]
    counts = [r[counter] / max(1, r.get("K", 1)) for r in ctx["flight"]
              if r["kind"] == "decode" and r.get(counter)]
    if not counts:
        return None
    import jax
    import peaks
    cost = cost_of(hf, statistics.median(counts))
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / measured
