"""Kernels: share of its roofline that ``kda_chunk`` (kimi_linear) reaches, in %:
the least time the chip could take for the walk's four matmuls a live chunk
and their operands once (``references/kimi_linear_costs.py``, from the
configuration's shapes and the ``prefill`` records' ``scan_tokens``, against
``peaks.py``) over its measured device time (``kernel.kda_chunk_ms``). Only
what the algorithm must touch is counted: a padded bucket's empty chunks
are walked and not counted. A program without the kernel or the counters:
nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_linear_costs as costs


def read(ctx):
    return costs.roofline_pct(ctx, "kda_chunk")
