"""Kernels: device time of ``ssd_chunk`` (Mamba-2, ``engine/ssd.py``:
the state's walk over a prompt's chunks in matmul form) per dispatch of the
prefill program, all state-space layers together, in ms: the seconds of the
stage's ops over the dispatches of the program that runs them in the
profiler's window. Which ops and which program are the stage's is said by the
configuration's costs module (``ctx["costs"]``, found by
``run.costs_module``), in its ``KERNELS`` and ``stage_seconds_per_step``:
``references/granite_moe_hybrid_costs.py`` prices it today. A cell whose
family prices no ``ssd_chunk`` stage, or a trace without its ops (the parent of
PR 59 has no such kernel): nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "ssd_chunk" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    seconds = costs.stage_seconds_per_step(ctx, "ssd_chunk")
    return None if seconds is None else seconds * 1e3
