"""Kernels: share of its roofline that ``hybrid_attention`` (phi4flash) reaches, in %:
the least time the chip could take for the work
(``references/phi4flash_costs.py``, from the configuration's shapes and the
engine's counters, against ``peaks.py``) over its measured device time
(``kernel.hybrid_attention_ms``). Only what the algorithm must touch is counted. A
program without the kernel or the counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import phi4flash_costs as costs


def read(ctx):
    return costs.roofline_pct(ctx, "hybrid_attention")
