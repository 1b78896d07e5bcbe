"""Kernels: share of its roofline that the multi-token-prediction block's read
reaches in a two-row decode step, in %: every live row of the module's own
layer ONCE (4,096 B), over the HBM peak (or its operations over the MXU's, if
more) against kernel.mtp_read_ms (k-exaone-236b;
``references/exaone_moe_costs.py``). Nothing to read without the kernel."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "mtp_read")
