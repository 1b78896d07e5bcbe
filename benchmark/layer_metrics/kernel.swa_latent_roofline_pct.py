"""Kernels: share of its roofline that the sliding-attention layers' window read reaches in a decode step, in %: min(context, 513) rows of 2,304 B a sequence and layer over the HBM peak (or its operations over the MXU's, if more) against kernel.swa_latent_ms (dots3-note-prev; ``references/dots3_note_costs.py``, where
what is counted and which ops are the stage's is said). A program without
the stage or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import dots3_note_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "swa_latent")
