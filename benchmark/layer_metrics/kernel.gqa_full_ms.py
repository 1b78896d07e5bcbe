"""Kernels: device time of the full-attention layers' grouped-query read
(``gqa_full``: the paged-attention kernel under the name ``gqa_full_read``,
over a sequence's whole table) per decode step, all such layers together, in
ms: the seconds of the stage's ops over the dispatches of the served decode
program in the profiler's window. Which ops and which program are the
stage's at a configuration's shapes is said by that configuration's costs
module (``ctx["costs"]``, found by ``run.costs_module``), in its ``KERNELS``
and ``stage_seconds_per_step``: ``references/mimo_v2_costs.py`` and
``references/exaone_moe_costs.py`` price it today. A cell whose family
prices no ``gqa_full`` stage, or a trace without its ops: nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "gqa_full" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    seconds = costs.stage_seconds_per_step(ctx, "gqa_full")
    return None if seconds is None else seconds * 1e3
