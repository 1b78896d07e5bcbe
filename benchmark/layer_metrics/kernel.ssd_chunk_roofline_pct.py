"""Kernels: share of its roofline that ``ssd_chunk`` (Mamba-2, ``engine/ssd.py``)
reaches, in %: the least time the chip could take for the operations and
bytes the mathematics needs (the configuration's costs module,
``ctx["costs"]``, in its ``stage_roofline_pct``: from the configuration's
shapes and the flight records' counters, against ``peaks.py``: the larger of
bytes over the HBM peak and operations over the MXU's) over the stage's
measured device time (``kernel.ssd_chunk_ms``). Only what the algorithm must
touch is counted (no padded chunk, no slot that is not live), so the share
cannot pass 100. A cell whose family prices no ``ssd_chunk`` stage, or a run
without its ops or counters: nothing to read."""


def read(ctx):
    costs = ctx.get("costs")
    if "ssd_chunk" not in getattr(costs, "KERNELS", ()):
        return None         # this cell's family prices no such stage
    return costs.stage_roofline_pct(ctx, "ssd_chunk")
