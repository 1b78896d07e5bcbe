"""Kernels: device time of the sliding layers' window read per two-row decode
step, all six layers together, in ms: the paged-attention kernel under the
name ``gqa_window_read`` over a ring view of 9 window-pool blocks, once a
scored row (k-exaone-236b; ``references/exaone_moe_costs.py``). A program
without the kernel or the two-row step: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "gqa_window")
    return None if seconds is None else seconds * 1e3
