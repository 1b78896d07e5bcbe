"""Kernels: share of its roofline that the attention over the selected rows
(``sparse_attention``: every layer that selects) reaches in a decode step,
in %: the least time the chip could take for the operations and bytes the
mathematics needs in a median decode step of the window (from the ``decode``
flight records' ``sel_tokens`` and the configuration's shapes, against
``peaks.py``: the larger of bytes over the HBM peak and operations over the
MXU's) over the stage's measured device time per step
(``kernel.sparse_attention_ms``). What is counted, at which shapes, is said
by the configuration's costs module (``ctx["costs"]``, found by
``run.costs_module``), in its ``stage_roofline_pct``:
``references/deepseek_v32_costs.py`` and ``references/dots3_note_costs.py``
price it today. Only what the algorithm must touch is counted, so the share
cannot pass 100. A cell whose family prices no ``sparse_attention`` stage,
or a run without its ops or counters: nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "sparse_attention" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    return costs.stage_roofline_pct(ctx, "sparse_attention")
