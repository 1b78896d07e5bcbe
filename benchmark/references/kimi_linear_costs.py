"""Least operations and bytes of Kimi-Linear-48B's three kinds of attention
work, from the configuration's shapes and the engine's counters: the same
work whatever implements it.

* ``kda_step`` (a decode step's delta-rule update, all K layers): each live
  slot's state ``[H, d, d]`` float32 is read once and written once per
  layer, with its token's q, k, v, decay and output rows (float32) beside
  it; per state cell seven operations (the decay's multiplication, a
  multiplication and an addition each for ``S'^T k``, the rank-one update
  and ``S^T q``).
* ``kda_chunk`` (a prompt's walk over its chunks, all K layers): what the
  Pallas call of that name runs, the state's walk of the chunked (WY / UT)
  form at the chunk size served (``engine/kda.py`` ``CHUNK``): per head and
  live chunk of C rows the four matmuls ``W S``, ``Q~ S``, ``B U`` and
  ``K^^T U`` (2 C d d each but ``B U``'s 2 C C d) and the state's decay;
  their operands ``W``, ``Uv``, ``Q~``, ``K^`` ``[C, d]``, ``B`` ``[C, C]``,
  the decay's d values and the output ``[C, d]`` once, float32; the state
  in and out once a head and dispatch, not once a chunk: it stays on the
  chip between chunks. The chunks are those that hold a prompt row
  (``scan_tokens`` of the ``prefill`` records), not the padded bucket's.
  The matrices ``A``, ``B`` and ``(I + A)^-1`` are built before the call,
  since PR 55 by a Pallas kernel of their own (``%kda_prepare*``, read by
  ``layer_metrics/kernel.kda_prepare_ms.py``, which gives it no share: no
  peak of ``peaks.py`` prices its exponentials and small HIGHEST matmuls);
  their time and their work are both left out of this stage.
* ``latent read`` (a decode step's absorbed attention, the 7 F layers): each
  live latent row (576 values, bf16) once a layer, used by all 32 heads:
  per row and head 2 x 576 operations for the score and 2 x 512 for
  ``probs . c``. It is the stage every family with latent attention prices
  as ``mla_decode`` (the paged-attention kernel in its one-head latent form,
  here 32 query heads x 640 lanes): ``kernel.mla_decode_roofline_pct`` asks
  it by that name.

The counters: ``decode`` flight records carry ``ctx_tokens`` and
``batch_fill`` (live slots), ``prefill`` records ``scan_tokens``. Only what
the algorithm must touch is counted (no padded bucket, no slot that is not
live, no lane-replicated operand), and float32 matmuls are priced at the
bf16 peak, so a share cannot pass 100. The kernels are read by their Pallas
``name=`` (``%kda_step*``, ``%kda_chunk*``, ``%paged_attention*``). A program
without them, as the parent of PR 54 has none: nothing to read.
"""

from __future__ import annotations

import json
import os
import statistics

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "kimi-linear-48b.json")
DECODE, PREFILL = "jit_decode_k", "jit_prefill"
CHUNK = 64            # engine/kda.py CHUNK, the chunk size served
CELL_OPS = 7


def served_config() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def shapes(hf: dict) -> dict:
    lin = hf["linear_attn_config"]
    n = int(hf["num_hidden_layers"])
    k_layers = sum(1 for i in lin["kda_layers"] if int(i) <= n)
    return {"H": int(lin["num_heads"]), "d": int(lin["head_dim"]),
            "k_layers": k_layers, "f_layers": n - k_layers,
            "heads": int(hf["num_attention_heads"]),
            "rank": int(hf["kv_lora_rank"]),
            "dr": int(hf["qk_rope_head_dim"])}


def kda_step_cost(hf: dict, live_slots: float) -> dict:
    s = shapes(hf)
    cells = s["H"] * s["d"] * s["d"]
    rows = 6 * 4 * s["H"] * s["d"]          # q, k, v, decay, beta, o
    return {"flops": live_slots * s["k_layers"] * CELL_OPS * cells,
            "bytes": live_slots * s["k_layers"] * (2 * 4 * cells + rows)}


def kda_chunk_cost(hf: dict, rows: float) -> dict:
    """One prefill dispatch of ``rows`` prompt rows."""
    s = shapes(hf)
    d, C = s["d"], CHUNK
    chunks = -(-rows // C)
    per_chunk_flops = 3 * 2 * C * d * d + 2 * C * C * d + d * d
    per_chunk_bytes = 4 * (5 * C * d + C * C + d)
    heads = s["k_layers"] * s["H"]
    return {"flops": heads * chunks * per_chunk_flops,
            "bytes": heads * (chunks * per_chunk_bytes + 2 * 4 * d * d)}


def latent_read_cost(hf: dict, ctx_tokens: float,
                     bytes_per_value: float = 2.0) -> dict:
    s = shapes(hf)
    row = s["rank"] + s["dr"]
    return {"flops": (s["f_layers"] * ctx_tokens * s["heads"]
                      * (2 * row + 2 * s["rank"])),
            "bytes": s["f_layers"] * ctx_tokens * row * bytes_per_value}


# ---------------------------------------------------------- the traced ops

KERNELS = {"kda_step": ("%kda_step", DECODE),
           "kda_chunk": ("%kda_chunk", PREFILL),
           "latent_read": ("%paged_attention", DECODE)}


# the latent read under the name every family's costs module prices it by
# (``layer_metrics/kernel.mla_decode_roofline_pct.py`` finds this module in
# ``ctx["costs"]`` and asks ``stage_roofline_pct(ctx, "mla_decode")``)
KERNELS["mla_decode"] = KERNELS["latent_read"]


def _seconds(ctx: dict, kernel: str) -> float:
    return sum(sec for name, sec, _ in (ctx.get("trace") or {}).get(
        "ops", ()) if name.startswith(kernel))


def kernel_ms(ctx: dict, which: str):
    """Device time of the ops named after ``which``'s kernel per dispatch of
    the program that runs it, all layers together, in ms; None in a trace
    without this family's kernels."""
    kernel, program = KERNELS[which]
    if not _seconds(ctx, "%kda_step"):
        return None         # another family's programs
    seconds = _seconds(ctx, kernel)
    n = sum(c for name, _, c in (ctx.get("trace") or {}).get(
        "programs", ()) if name == program)
    if not seconds or not n:
        return None
    return seconds / n * 1e3


def _median(records: list, key: str):
    values = [r[key] / max(1, r.get("K", 1)) for r in records if r.get(key)]
    return statistics.median(values) if values else None


def cost_of(ctx: dict, which: str):
    """The least work of a median decode step (a mean prefill dispatch) of
    the window, from the flight records before the profiler starts."""
    hf = served_config()
    flight = ctx.get("flight", ())
    decode = [r for r in flight if r["kind"] == "decode"]
    if which == "kda_chunk":
        rows = [r["scan_tokens"] for r in flight
                if r["kind"] == "prefill" and r.get("scan_tokens")]
        return kda_chunk_cost(hf, statistics.fmean(rows)) if rows else None
    if which == "kda_step":
        live = _median(decode, "batch_fill")
        return None if live is None else kda_step_cost(hf, live)
    ctx_tokens = _median(decode, "ctx_tokens")
    return None if ctx_tokens is None else latent_read_cost(hf, ctx_tokens)


def stage_roofline_pct(ctx: dict, stage: str):
    return roofline_pct(ctx, {"mla_decode": "latent_read"}.get(stage, stage))


def roofline_pct(ctx: dict, which: str):
    ms = kernel_ms(ctx, which)
    cost = cost_of(ctx, which) if ms is not None else None
    if cost is None:
        return None
    import jax
    import peaks
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / (ms / 1e3)
