"""Kernels: share of its roofline that the main model's full-attention read
reaches in a two-row decode step, in %: every live row of every sequence ONCE
a full layer (4,096 B; a program that reads a slot's rows once a scored row
reads under it), over the HBM peak (or its operations over the MXU's, if more)
against kernel.gqa_full_ms.exaone (k-exaone-236b;
``references/exaone_moe_costs.py``). Nothing to read without the kernel."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import exaone_moe_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "gqa_full")
