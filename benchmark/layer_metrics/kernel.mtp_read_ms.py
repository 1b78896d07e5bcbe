"""Kernels: device time of the multi-token-prediction block's full-attention
read per two-row decode step, in ms: the paged-attention kernel under the name
``mtp_full_read`` (a name of its own, so that the trace tells it from the main
model's two full layers) over the module's OWN rows of a sequence's whole
table (k-exaone-236b; ``references/exaone_moe_costs.py``). A program without a
resident drafter: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "mtp_read")
    return None if seconds is None else seconds * 1e3
