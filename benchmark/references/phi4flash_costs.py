"""Least operations and bytes of Phi-4-mini-flash-reasoning's three new
kinds of work, from the configuration's shapes and the engine's counters:
the same work whatever implements it.

* ``ssm_step`` (a decode step's recurrent update, all state-space layers):
  each live slot's state ``[d_inner, d_state]`` float32 is read and written
  once per layer, with its token's dt, x and y rows (float32) beside it;
  per state cell one exp, three multiplications and two additions.
* ``ssm_scan`` (a prompt's selective scan, all state-space layers): per
  prompt token and layer the same cell arithmetic, and the dt, x and y rows
  in and out; the state stays on the chip between tokens and is counted
  once per prompt.
* ``hybrid_attention`` (a decode step's attention over the two caches):
  every layer that reads the full-length cache (the full layer and the
  cross layers) reads each live context row once, ``ctx_tokens`` rows of K
  and of V; every window layer reads ``win_tokens`` = sum min(context,
  window) rows of its own; per row and query head 2 dh operations for the
  score and 4 dh for probs.v (the value is 2 dh wide).

The counters: ``decode`` flight records carry ``ctx_tokens``,
``win_tokens`` and ``batch_fill`` (live slots), ``prefill`` records
``scan_tokens``. Only what the algorithm must touch is counted (no padded
bucket, no slot that is not live, no lane-replicated operand), so a share
cannot pass 100. The kernels are read by their Pallas ``name=``
(``%ssm_step*``, ``%ssm_scan*``, ``%paged_attention*`` with the decode
batch first in the result's shape: a prefill's cross layers call the same
kernel for one row). A program without them, or without the counters, as
the parent of PR 35 has neither: nothing to read.
"""

from __future__ import annotations

import json
import os
import statistics

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "phi4-mini-flash.json")
DECODE, PREFILL = "jit_decode_k", "jit_prefill"
CELL_OPS = 6          # exp, 3 mul, 2 add per state cell and token


def served_config() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def shapes(hf: dict) -> dict:
    D, H = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    L = int(hf["num_hidden_layers"])
    half = (L // 2) & ~1
    return {"Di": int(hf.get("mamba_expand", 2)) * D,
            "N": int(hf.get("mamba_d_state", 16)), "H": H,
            "KVH": int(hf.get("num_key_value_heads", H)),
            "dh": int(hf.get("head_dim") or D // H),
            "ssm_layers": half // 2 + 1, "window_layers": half // 2,
            "paged_readers": 1 + (L - half - 2) // 2,
            "window": int(hf["sliding_window"])}


def ssm_step_cost(hf: dict, live_slots: float) -> dict:
    s = shapes(hf)
    cells = s["Di"] * s["N"]
    per_slot_layer = 2 * 4 * cells + 3 * 4 * s["Di"]
    return {"flops": live_slots * s["ssm_layers"] * CELL_OPS * cells,
            "bytes": live_slots * s["ssm_layers"] * per_slot_layer}


def ssm_scan_cost(hf: dict, tokens: float) -> dict:
    s = shapes(hf)
    cells = s["Di"] * s["N"]
    return {"flops": tokens * s["ssm_layers"] * CELL_OPS * cells,
            "bytes": s["ssm_layers"] * (tokens * 3 * 4 * s["Di"]
                                        + 2 * 4 * cells)}


def hybrid_attention_cost(hf: dict, ctx_tokens: float, win_tokens: float,
                          bytes_per_value: float = 2.0) -> dict:
    s = shapes(hf)
    rows = (s["paged_readers"] * ctx_tokens
            + s["window_layers"] * win_tokens)
    return {"flops": rows * s["H"] * 6 * s["dh"],
            "bytes": rows * 2 * s["KVH"] * s["dh"] * bytes_per_value}


# ---------------------------------------------------------- the traced ops

def kernel_seconds_per_dispatch(ctx: dict, kernel: str, program: str,
                                batch_first: bool = False):
    """Device seconds of the ops named ``kernel*`` per dispatch of
    ``program`` in the profiler's window; ``batch_first``: only those whose
    result has the decode batch as its first dimension."""
    trace = ctx.get("trace") or {}
    ops = [op for op in trace.get("ops", ()) if op[0].startswith(kernel)]
    if batch_first:
        batch = (ctx.get("engine") or {}).get("max_num_seqs")
        ops = [op for op in ops if f"[{batch}," in op[0]]
    seconds = sum(sec for _, sec, _ in ops)
    n = sum(c for name, _, c in trace.get("programs", ()) if name == program)
    if not seconds or not n:
        return None
    return seconds / n


KERNELS = {"ssm_step": ("%ssm_step", DECODE, False),
           "ssm_scan": ("%ssm_scan", PREFILL, False),
           "hybrid_attention": ("%paged_attention", DECODE, True)}


def kernel_ms(ctx: dict, which: str):
    if which == "hybrid_attention" and not any(
            "win_tokens" in r for r in ctx.get("flight", ())):
        return None         # another family's paged attention
    seconds = kernel_seconds_per_dispatch(ctx, *KERNELS[which])
    return None if seconds is None else seconds * 1e3


def _median(records: list, key: str):
    values = [r[key] / max(1, r.get("K", 1)) for r in records if r.get(key)]
    return statistics.median(values) if values else None


def cost_of(ctx: dict, which: str):
    """The least work of a median decode step (a mean prefill) of the
    window, from the flight records before the profiler starts."""
    hf = served_config()
    flight = ctx.get("flight", ())
    decode = [r for r in flight if r["kind"] == "decode"
              and "win_tokens" in r]
    if which == "ssm_scan":
        tokens = [r["scan_tokens"] for r in flight
                  if r["kind"] == "prefill" and r.get("scan_tokens")]
        return (ssm_scan_cost(hf, statistics.fmean(tokens))
                if tokens else None)
    if which == "ssm_step":
        live = _median(decode, "batch_fill")
        return None if live is None else ssm_step_cost(hf, live)
    ctx_tokens = _median(decode, "ctx_tokens")
    win_tokens = _median(decode, "win_tokens")
    if ctx_tokens is None or win_tokens is None:
        return None
    return hybrid_attention_cost(hf, ctx_tokens, win_tokens)


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
