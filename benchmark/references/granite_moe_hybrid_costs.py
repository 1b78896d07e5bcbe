"""Least operations and bytes of granite-4.0-h-small's two state-space
kernels, from the configuration's shapes and the engine's counters: the same
work whatever implements it.

* ``ssd_step`` (a decode step's Mamba-2 update, all M layers): each live
  slot's state ``[H, P, N]`` float32 is read once and written once per layer,
  with its token's x, dt, decay and output rows and B, C (float32) beside it;
  per state cell five operations (the decay's multiplication, a
  multiplication and an addition for ``dt x (x) B``, a multiplication and an
  addition for ``S C``).
* ``ssd_chunk`` (a prompt's walk over its chunks, all M layers): the chunked
  (matmul) form at the chunk size served (``engine/ssd.py`` ``CHUNK``): per
  live chunk of C rows ``C B^T`` once (2 C C N, shared by the heads) and per
  head the three matmuls ``(C B^T * L) (dt X)`` (2 C C P), ``C S^T`` and
  ``X^T B`` (2 C P N each) and the ``[C, C]`` decay matrix (an exponential,
  a multiplication); their operands once: x (bf16) and y (float32) ``[C, P]``
  a head, B and C ``[C, N]`` float32 and dt ``[C]`` a head; the state in and
  out once a head and DISPATCH, not once a chunk: it stays on the chip
  between chunks. The chunks are those that hold a prompt row (``ssd_chunks``
  of the ``prefill`` records), not the padded bucket's, and a record's rows
  went in ``ceil(scan_tokens / --prefill-chunk)`` dispatches.

The counters: ``decode`` flight records carry ``batch_fill`` (live slots),
``prefill`` records ``scan_tokens`` and ``ssd_chunks``. Only what the
algorithm must touch is counted (no padded bucket, no slot that is not live,
no lane-replicated operand, no second read of B and C by another block of
heads), and float32 matmuls are priced at the bf16 peak, so a share cannot
pass 100. The kernels are read by their Pallas ``name=`` (``%ssd_step*``,
``%ssd_chunk*``). A program without them, as the parent of PR 59 has none:
nothing to read.
"""

from __future__ import annotations

import json
import os
import statistics

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "granite-4.0-h-small.json")
DECODE, PREFILL = "jit_decode_k", "jit_prefill"
CHUNK = 128           # engine/ssd.py CHUNK, the chunk size served
CELL_OPS = 5

KERNELS = {"ssd_step": ("%ssd_step", DECODE),
           "ssd_chunk": ("%ssd_chunk", PREFILL)}


def served_config() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def shapes(hf: dict) -> dict:
    n = int(hf["num_hidden_layers"])
    return {"H": int(hf["mamba_n_heads"]), "P": int(hf["mamba_d_head"]),
            "N": int(hf["mamba_d_state"]),
            "m_layers": sum(1 for t in hf["layer_types"][:n]
                            if t == "mamba")}


def dispatch_rows(config: dict) -> int:
    """Rows of the largest prefill dispatch: ``--prefill-chunk`` of the
    deployment's flags (0: a prompt goes whole)."""
    flags = config["deployment"]["flags"]
    return (int(flags[flags.index("--prefill-chunk") + 1])
            if "--prefill-chunk" in flags else 0)


def ssd_step_cost(hf: dict, live_slots: float) -> dict:
    s = shapes(hf)
    cells = s["H"] * s["P"] * s["N"]
    rows = 4 * (2 * s["H"] * s["P"] + 2 * s["H"] + 2 * s["N"])
    return {"flops": live_slots * s["m_layers"] * CELL_OPS * cells,
            "bytes": live_slots * s["m_layers"] * (2 * 4 * cells + rows)}


def ssd_chunk_cost(hf: dict, chunks: float) -> dict:
    """One prefill dispatch whose rows lie in ``chunks`` chunks."""
    s = shapes(hf)
    H, P, N, C = s["H"], s["P"], s["N"], CHUNK
    per_head = 2 * C * C * P + 2 * 2 * C * P * N + 2 * C * C
    flops = chunks * (2 * C * C * N + H * per_head)
    moved = (chunks * (H * C * P * (2 + 4) + 2 * 4 * C * N + 4 * C * H)
             + H * 2 * 4 * P * N)
    return {"flops": s["m_layers"] * flops, "bytes": s["m_layers"] * moved}


def _seconds(ctx: dict, kernel: str) -> float:
    return sum(sec for name, sec, _ in (ctx.get("trace") or {}).get(
        "ops", ()) if name.startswith(kernel))


def stage_seconds_per_step(ctx: dict, stage: str):
    """Device time of the ops named after ``stage``'s kernel per dispatch of
    the program that runs it, all layers together, in seconds; None in a
    trace without them."""
    kernel, program = KERNELS[stage]
    seconds = _seconds(ctx, kernel)
    n = sum(c for name, _, c in (ctx.get("trace") or {}).get(
        "programs", ()) if name == program)
    if not seconds or not n:
        return None
    return seconds / n


def cost_of(ctx: dict, stage: str):
    """The least work of a median decode step (a mean prefill dispatch) of
    the window, from the flight records before the profiler starts."""
    config = served_config()
    flight = ctx.get("flight", ())
    if stage == "ssd_step":
        live = [r["batch_fill"] / max(1, r.get("K", 1)) for r in flight
                if r["kind"] == "decode" and r.get("batch_fill")]
        return ssd_step_cost(config, statistics.median(live)) if live \
            else None
    prefills = [r for r in flight
                if r["kind"] == "prefill" and r.get("ssd_chunks")]
    if not prefills:
        return None
    most = dispatch_rows(config)
    dispatches = sum(-(-r["scan_tokens"] // most) if most else 1
                     for r in prefills)
    return ssd_chunk_cost(
        config, sum(r["ssd_chunks"] for r in prefills) / dispatches)


def stage_roofline_pct(ctx: dict, stage: str):
    seconds = stage_seconds_per_step(ctx, stage)
    cost = cost_of(ctx, stage) if seconds is not None else None
    if cost is None:
        return None
    import jax
    import peaks
    try:
        least, _ = peaks.roofline_s(cost["flops"], cost["bytes"],
                                    jax.devices()[0].device_kind)
    except KeyError:
        return None
    return 100.0 * least / seconds
