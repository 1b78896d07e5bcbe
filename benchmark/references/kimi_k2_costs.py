"""Operations and bytes of the two attention forms of a latent-attention
model without an indexer (a configuration that names the reference
``kimi_k2``), counted from the mathematics, whatever implements them, and
the traced Pallas calls they are held against.

* **prefill attention** (the expanded form): every causal (query, key) pair costs each
  of the H heads 2·(dn+dr) operations for the score and 2·dv for probs·v,
  and every prefilled row is expanded through ``wkv_b`` ONCE,
  2·rank·H·(dn+dv) operations (a chunked prefill expands the cached prefix
  again for every chunk: that is the implementation's, not the algorithm's,
  and is not counted, so the share is understated by it). Bytes: each chunk
  reads the live rows of its table once, rank+rope lanes of 2 bytes (the
  pool's pad lanes are not needed).
* **decode read** (the absorbed form; ``decode`` flight records carry
  ``ctx_tokens`` = Σ live context over the step's sequences): each live row
  is read once a layer, rank+rope lanes of 2 bytes, and used by H heads:
  2·(rank+rope) operations for the score and 2·rank for probs·c.

Queries, outputs and ``wkv_b`` itself are left out of the bytes (megabytes
against hundreds of megabytes of rows).
"""

from __future__ import annotations

import json
import os
import statistics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the plain reference a configuration of this family names
REFERENCE = "kimi_k2"
PREFILL_PROGRAM = "jit_prefill"
DECODE_PROGRAM = "jit_decode_k"
# the Pallas calls of the two forms, by their ``name=``
PREFILL_KERNEL = "%mla_prefill"
DECODE_KERNEL = "%paged_attention"
# rows of the table one call of the prefill kernel walks
# (mla.MLA_KEY_BLOCK)
KEY_BLOCK = 2048


def shapes(hf: dict) -> dict:
    return {"L": int(hf["num_hidden_layers"]),
            "H": int(hf["num_attention_heads"]),
            "rank": int(hf["kv_lora_rank"]),
            "dn": int(hf["qk_nope_head_dim"]),
            "dr": int(hf["qk_rope_head_dim"]),
            "dv": int(hf["v_head_dim"])}


def prefill_attention(hf: dict, key_tokens: float, rows: float,
                      rows_read: float, bytes_per_value: float = 2.0) -> dict:
    """The attention of prefilled ``rows`` that attended ``key_tokens``
    (query, key) pairs and read ``rows_read`` cached rows, all layers."""
    s = shapes(hf)
    pair = 2 * (s["dn"] + s["dr"]) + 2 * s["dv"]
    expand = 2 * s["rank"] * s["H"] * (s["dn"] + s["dv"])
    return {"flops": s["L"] * (key_tokens * s["H"] * pair + rows * expand),
            "bytes": (s["L"] * rows_read * (s["rank"] + s["dr"])
                      * bytes_per_value)}


def decode_read(hf: dict, ctx_tokens: float,
                bytes_per_value: float = 2.0) -> dict:
    """The absorbed attention of one decode step over ``ctx_tokens`` live
    rows, all layers."""
    s = shapes(hf)
    row = s["rank"] + s["dr"]
    return {"flops": s["L"] * ctx_tokens * s["H"] * (2 * row + 2 * s["rank"]),
            "bytes": s["L"] * ctx_tokens * row * bytes_per_value}


# ---------------------------------------------------------- the traced ops

def served_config(ctx: dict):
    """→ (published keys, ``--prefill-chunk``) of the configuration that is
    being served: the one of BENCHMARK.json that names this family's
    reference and whose deployment gives the engine ``ctx`` shows; None
    where there is none (another family's cell)."""
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
            return config, flag("--prefill-chunk")
    return None


def _ops(ctx: dict, kernel: str) -> tuple:
    """→ (seconds, calls) of the Pallas calls named ``kernel`` in the
    profiler's window."""
    ops = [op for op in (ctx.get("trace") or {}).get("ops", ())
           if op[0].startswith(kernel)]
    return sum(sec for _, sec, _ in ops), sum(n for _, _, n in ops)


def _dispatches(ctx: dict, program: str) -> int:
    return sum(n for name, _, n in (ctx.get("trace") or {}).get(
        "programs", ()) if name == program)


def prefill_seconds_per_dispatch(ctx: dict):
    """Device seconds of the prefill attention's Pallas calls (by name:
    the expansion, the scores and the running softmax of every key block)
    per dispatch of the prefill program (one chunk, all layers), over the
    profiler's window; the XLA ops around them (the rows' gather from the
    pool, the queries' layout, the final division) are not told from other
    ops of their shapes and are left out. None where there is nothing to
    read (no such kernel in the trace: another family, or the parent of
    PR 37)."""
    seconds, _ = _ops(ctx, PREFILL_KERNEL)
    chunks = _dispatches(ctx, PREFILL_PROGRAM)
    if not seconds or not chunks:
        return None
    return seconds / chunks


def prefill_roofline_pct(ctx: dict):
    """100 × (the least time the chip could take for a chunk's attention)
    / (the kernel's measured device time per chunk), work and time of the
    SAME chunks: those of the profiler's window. The kernel is called once
    a (layer, chunk, live key block), so its calls over layers × dispatches
    are the key blocks n a chunk walked, and its live length stands
    between (n − 1)·KEY_BLOCK and n·KEY_BLOCK: the work is counted at the
    least length a whole chunk can have there, (n − 1)·KEY_BLOCK + chunk
    (so the share is understated, as it is by the cached prefix's
    re-expansion). The peaks are ``peaks.py``'s."""
    found = served_config(ctx)
    measured = prefill_seconds_per_dispatch(ctx)
    if found is None or measured is None:
        return None
    hf, chunk = found
    _, calls = _ops(ctx, PREFILL_KERNEL)
    walks = shapes(hf)["L"] * _dispatches(ctx, PREFILL_PROGRAM)
    live = max(chunk,
               (calls / walks - 1) * KEY_BLOCK + min(chunk, KEY_BLOCK))
    pairs = chunk * (live - chunk) + chunk * (chunk + 1) / 2
    return _share(prefill_attention(hf, pairs, chunk, live), measured)


def decode_seconds_per_step(ctx: dict):
    """Device seconds of the paged-attention kernel per dispatch of the
    decode program, over the profiler's window."""
    seconds, _ = _ops(ctx, DECODE_KERNEL)
    steps = _dispatches(ctx, DECODE_PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps


def decode_roofline_pct(ctx: dict):
    """100 × (the least time the chip could take for the latent read of a
    median decode step of the window) / (the kernel's measured device time
    per step)."""
    found = served_config(ctx)
    measured = decode_seconds_per_step(ctx)
    if found is None or measured is None:
        return None
    counts = [r["ctx_tokens"] / max(1, r.get("K", 1)) for r in ctx["flight"]
              if r["kind"] == "decode" and r.get("ctx_tokens")]
    if not counts:
        return None
    return _share(decode_read(found[0], statistics.median(counts)), measured)


# The spelling a kernel's reader calls whatever configuration's shapes the
# kernel runs at (``layer_metrics/kernel.mla_decode_roofline_pct.py`` finds
# this module in ``ctx["costs"]``): the stages priced here by the calls
# that are theirs, and the share of a stage by its name.
KERNELS = {"mla_decode": DECODE_KERNEL}


def stage_roofline_pct(ctx: dict, stage: str):
    return {"mla_decode": decode_roofline_pct}[stage](ctx)


def _share(cost: dict, measured: float):
    import jax
    import peaks
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / measured
