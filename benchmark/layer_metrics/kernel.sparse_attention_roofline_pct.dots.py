"""Kernels: share of its roofline that the full-attention layers' sparse_attention stage reaches in a decode step, in %, at this configuration's shapes (dots3-note-prev; ``references/dots3_note_costs.py``, where
what is counted and which ops are the stage's is said). A program without
the stage or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import dots3_note_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "sparse_attention")
