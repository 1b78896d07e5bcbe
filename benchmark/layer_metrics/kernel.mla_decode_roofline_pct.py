"""Kernels: share of its roofline that the paged-attention kernel reaches in
its one-head latent form (64 query heads × 640 lanes), in %: the least time
the chip could take to read each live latent row once a layer and use it for
every head (``references/kimi_k2_costs.decode_read``, from the ``decode``
flight records' ``ctx_tokens``, against ``peaks.py``: the larger of bytes
over the HBM peak and operations over the MXU's) over the kernel's measured
device time per step (``kernel.paged_attention_ms``). A trace without the
kernel or records without the counter: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_k2_costs as costs


def read(ctx):
    return costs.decode_roofline_pct(ctx)
