"""Kernels: device time of the sliding-attention layers' window read per decode step, all six layers together, in ms: the paged-attention kernel in its one-head latent form, 64 heads x 1,152 lanes over at most 34 blocks a sequence (dots3-note-prev; ``references/dots3_note_costs.py``, where
what is counted and which ops are the stage's is said). A program without
the stage or its counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import dots3_note_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "swa_latent")
    return None if seconds is None else seconds * 1e3
