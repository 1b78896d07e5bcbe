"""Kernels: device time of the full-attention layers' read per decode step, all
three layers together, in ms: the paged-attention kernel under the name
``gqa_full_read``, 64 query heads over 4 key/value heads, key rows of 768 and
value rows of 512 lanes over a sequence's whole table (mimo-v2.5;
``references/mimo_v2_costs.py``, where what is counted is said). A program
without the kernel or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import mimo_v2_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "gqa_full")
    return None if seconds is None else seconds * 1e3
