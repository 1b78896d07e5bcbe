"""Kernels: share of its roofline that a dense latent-attention prefill
chunk's attention reaches, in %: the least time the chip could take for the
chunk's causal pairs and ONE expansion of its rows
(``references/kimi_k2_costs.py``, against ``peaks.py``) over the measured
device time of the kernel that did them (``kernel.mla_prefill_ms``). Work and
time are those of the same chunks, the ones the profiler's window caught:
the kernel runs once a (layer, chunk, live key block), so its calls per
layer and dispatch tell how long the chunks' tables were. Only what the
algorithm must do is counted (the cached prefix's re-expansion for every
chunk is not, and a chunk's length is taken at the foot of its last key
block), so the share cannot pass 100. A program without the walk, or
another family's cell: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_k2_costs as costs


def read(ctx):
    return costs.prefill_roofline_pct(ctx)
