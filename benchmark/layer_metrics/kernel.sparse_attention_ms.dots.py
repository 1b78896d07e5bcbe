"""Kernels: device time of the full-attention layers' sparse_attention stage per decode step, all three layers together, in ms, at this configuration's shapes (dots3-note-prev; ``references/dots3_note_costs.py``, where
what is counted and which ops are the stage's is said). A program without
the stage or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import dots3_note_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "sparse_attention")
    return None if seconds is None else seconds * 1e3
