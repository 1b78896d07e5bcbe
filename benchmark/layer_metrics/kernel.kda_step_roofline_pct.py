"""Kernels: share of its roofline that ``kda_step`` (kimi_linear) reaches, in %:
the least time the chip could take to read and write every live slot's
float32 state once a layer (``references/kimi_linear_costs.py``, from the
configuration's shapes and the ``decode`` records' ``batch_fill``, against
``peaks.py``) over its measured device time (``kernel.kda_step_ms``). Only
what the algorithm must touch is counted. A program without the kernel or
the counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_linear_costs as costs


def read(ctx):
    return costs.roofline_pct(ctx, "kda_step")
