"""Kernels: share of its roofline that the paged-attention kernel reaches in
its one-head latent form at kimi_linear's shapes (32 query heads x 640
lanes, 7 layers), in %: the least time the chip could take to read each live
latent row once a layer and use it for every head
(``references/kimi_linear_costs.latent_read_cost``, from the ``decode``
flight records' ``ctx_tokens``, against ``peaks.py``) over the kernel's
measured device time per step. A trace without this family's kernels or
records without the counter: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_linear_costs as costs


def read(ctx):
    return costs.roofline_pct(ctx, "latent_read")
