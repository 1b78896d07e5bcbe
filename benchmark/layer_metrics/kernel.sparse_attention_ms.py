"""Kernels: device time of the attention over the selected rows
(``sparse_attention``: every layer that selects) per decode step, all such
layers together, in ms: the seconds of the stage's ops over the dispatches
of the served decode program in the profiler's window. Which ops and which
program are the stage's at a configuration's shapes is said by that
configuration's costs module (``ctx["costs"]``, found by
``run.costs_module``), in its ``KERNELS`` and ``stage_seconds_per_step``:
``references/deepseek_v32_costs.py`` and ``references/dots3_note_costs.py``
price it today. A cell whose family prices no ``sparse_attention`` stage, or
a trace without its ops: nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "sparse_attention" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    seconds = costs.stage_seconds_per_step(ctx, "sparse_attention")
    return None if seconds is None else seconds * 1e3
