"""Kernels: device time of the sliding-attention layers' window read per decode
step, all ten layers together, in ms: the paged-attention kernel under the
name ``gqa_window_read``, 64 query heads over 8 key/value heads with a sink in
the softmax's denominator, key rows of 1,536 and value rows of 1,024 lanes over
a ring of at most 9 window-pool blocks a sequence (mimo-v2.5;
``references/mimo_v2_costs.py``, where what is counted is said). A program
without the kernel or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import mimo_v2_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "gqa_window")
    return None if seconds is None else seconds * 1e3
