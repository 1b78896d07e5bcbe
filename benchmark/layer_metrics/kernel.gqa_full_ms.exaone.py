"""Kernels: device time of the main model's full-attention read per two-row
decode step, both full layers together, in ms: the paged-attention kernel
under the name ``gqa_full_read``, 64 query heads over 8 key/value heads of 128
lanes, NoPE, over a sequence's whole table, once a scored row
(k-exaone-236b; ``references/exaone_moe_costs.py``, where what is counted is
said). A program without the kernel or the two-row step: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "gqa_full")
    return None if seconds is None else seconds * 1e3
