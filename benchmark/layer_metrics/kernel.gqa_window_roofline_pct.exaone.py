"""Kernels: share of its roofline that the sliding layers' window read reaches
in a two-row decode step, in %: min(context, 129) rows of 4,096 B a sequence
and layer, the union of the two rows' windows ONCE (not the 9 blocks the ring
holds, not once a row), over the HBM peak (or its operations over the MXU's,
if more) against kernel.gqa_window_ms.exaone (k-exaone-236b;
``references/exaone_moe_costs.py``). Nothing to read without the kernel."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "gqa_window")
