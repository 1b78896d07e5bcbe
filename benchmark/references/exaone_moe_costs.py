"""Operations and bytes of the three attention reads of a decode step of a
model with a resident multi-token-prediction drafter (a configuration that
names the reference ``exaone_moe``), counted from the mathematics, whatever
implements them, and the traced Pallas calls they are held against.

A layer has H query heads over KVH key/value heads of d lanes, ONE geometry
for both kinds and for the module's block; a cached row of one layer is
KVH·2d values of 2 bytes (4,096 B at the published sizes; the heads side by
side, no lane of the row is padding). Every (query, key) pair costs each query
head 2·d operations for the score and 2·d for probs·v.

A step scores ``rows`` adjacent rows a slot (2: the last token and the
draft). **A slot's cached rows are read ONCE a layer and step however many
rows of that slot are scored**: the rows of one slot read the same context
but for the newest row or two, so the mathematics needs each cached row once.
A program that reads them once a scored row (the two-row step did until
PR 52: in verify's shape each row was a sequence of its own to the paged
kernel) therefore shows as a share UNDER its roofline, not over it; since
PR 52 the served step reads a slot's rows once under the kernel for both
scored rows, which is the work counted here.

* **the full read** (``decode`` flight records carry ``ctx_tokens`` = the sum
  over the step's slots of the context up to the slot's last row): every live
  row once a full layer of the main model;
* **the module's read**: the same rows of the module's own layer, once;
* **the window read** (``win_tokens`` = the sum of min(context, window +
  rows - 1): the union of the rows' windows, 129 rows at the published
  window of 128 and two scored rows; not the 9 or 10 blocks a slot's ring
  holds, not once a row): once a sliding layer.

Pairs: each of a slot's rows attends the context (or the window), so the
operations are the bytes' rows times the rows scored a slot. Queries and
outputs are left out of the bytes.
"""

from __future__ import annotations

import json
import os
import statistics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the plain reference a configuration of this family names
REFERENCE = "exaone_moe"
# the served decode program of a resident drafter (engine/core.py)
DECODE_PROGRAM = "jit_decode_mtp"
# the Pallas calls of the three reads, by their ``name=`` (models/mimo.py)
KERNELS = {"gqa_full": "%gqa_full_read", "gqa_window": "%gqa_window_read",
           "mtp_read": "%mtp_full_read"}


def shapes(hf: dict) -> dict:
    kinds = hf["layer_types"][:int(hf["num_hidden_layers"])]
    g = {"H": int(hf["num_attention_heads"]),
         "KVH": int(hf["num_key_value_heads"]), "d": int(hf["head_dim"])}
    return {"gqa_full": dict(g, layers=kinds.count("full_attention")),
            "gqa_window": dict(g, layers=kinds.count("sliding_attention")),
            "mtp_read": dict(g, layers=int(
                hf.get("num_nextn_predict_layers") or 0)),
            "window": int(hf["sliding_window"])}


def read_step(hf: dict, stage: str, rows_read: float, rows_a_slot: float,
              bytes_per_value: float = 2.0) -> dict:
    """One decode step's read of ``stage``: ``rows_read`` cached rows, each
    once a layer, attended by ``rows_a_slot`` scored rows."""
    g = shapes(hf)[stage]
    return {"flops": (g["layers"] * rows_read * rows_a_slot * g["H"]
                      * 4 * g["d"]),
            "bytes": (g["layers"] * rows_read * g["KVH"] * 2 * g["d"]
                      * bytes_per_value)}


# the flight record's counter of the cached rows a stage reads
COUNTER = {"gqa_full": "ctx_tokens", "mtp_read": "ctx_tokens",
           "gqa_window": "win_tokens"}


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


def stage_seconds_per_step(ctx: dict, stage: str):
    """Device seconds of a read's Pallas calls (all the layers of its kind)
    per dispatch of the served decode program, over the profiler's window;
    None: nothing to read (no such kernel or program in the trace: another
    family, or a parent without it)."""
    trace = ctx.get("trace") or {}
    seconds = sum(sec for name, sec, _ in trace.get("ops", ())
                  if name.startswith(KERNELS[stage]))
    steps = sum(n for name, _, n in trace.get("programs", ())
                if name == DECODE_PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps


def stage_roofline_pct(ctx: dict, stage: str):
    """100 × (the least time the chip could take for the read's share of a
    median decode step of the window) / (its measured device time a step);
    the step's counters from the ``decode`` flight records, the peaks from
    ``peaks.py`` by the device's kind."""
    measured = stage_seconds_per_step(ctx, stage)
    hf = served_config(ctx)
    if measured is None or hf is None:
        return None
    steps = [r for r in ctx["flight"] if r["kind"] == "decode"
             and r.get(COUNTER[stage]) and r.get("rows")]
    if not steps:
        return None
    import jax
    import peaks
    cost = read_step(
        hf, stage, statistics.median(r[COUNTER[stage]] for r in steps),
        statistics.median(r["rows"] / r["batch_fill"] for r in steps))
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / measured
