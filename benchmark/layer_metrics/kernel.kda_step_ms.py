"""Kernels: device time of ``kda_step`` (kimi_linear) per dispatch of the decode
program, all 20 delta-attention layers together, in ms: what
``references/kimi_linear_costs.py`` ``KERNELS`` names, read by the kernel's
``name=``. A program without it: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_linear_costs as costs


def read(ctx):
    return costs.kernel_ms(ctx, "kda_step")
