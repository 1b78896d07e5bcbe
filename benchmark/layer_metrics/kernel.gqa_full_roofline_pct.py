"""Kernels: share of its roofline that the full-attention layers' grouped-query
read (``gqa_full``: the paged-attention kernel under the name
``gqa_full_read``, over a sequence's whole table) reaches in a decode step,
in %: the least time the chip could take for the operations and bytes the
mathematics needs in a median decode step of the window (from the ``decode``
flight records' ``ctx_tokens`` and the configuration's shapes, against
``peaks.py``: the larger of bytes over the HBM peak and operations over the
MXU's) over the stage's measured device time per step
(``kernel.gqa_full_ms``). What is counted, at which shapes, is said by the
configuration's costs module (``ctx["costs"]``, found by
``run.costs_module``), in its ``stage_roofline_pct``:
``references/mimo_v2_costs.py`` and ``references/exaone_moe_costs.py`` price
it today. Only what the algorithm must touch is counted, so the share cannot
pass 100. A cell whose family prices no ``gqa_full`` stage, or a run without
its ops or counters: nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "gqa_full" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    return costs.stage_roofline_pct(ctx, "gqa_full")
