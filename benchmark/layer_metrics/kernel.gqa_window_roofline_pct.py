"""Kernels: share of its roofline that the sliding-attention layers' window read
reaches in a decode step, in %: min(context, 128) rows of 5,120 B a sequence
and layer (the rows the model needs, not the 9 blocks the ring holds) over the
HBM peak (or its operations over the MXU's, if more) against
kernel.gqa_window_ms (mimo-v2.5; ``references/mimo_v2_costs.py``). A program
without the kernel or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import mimo_v2_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "gqa_window")
