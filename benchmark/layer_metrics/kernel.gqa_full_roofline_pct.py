"""Kernels: share of its roofline that the full-attention layers' read reaches in
a decode step, in %: every live row of every sequence, 2,560 B a layer (each
slot's own read of shared rows counts; no lane of a row is padding), over the
HBM peak (or its operations over the MXU's, if more) against
kernel.gqa_full_ms (mimo-v2.5; ``references/mimo_v2_costs.py``). A program
without the kernel or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import mimo_v2_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "gqa_full")
