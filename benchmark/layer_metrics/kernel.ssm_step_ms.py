"""Kernels: device time of ``ssm_step`` (phi4flash) per dispatch of the program
that runs it, all layers together, in ms: what
``references/phi4flash_costs.py`` ``KERNELS`` names, read by the kernel's
``name=``. A program without it: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import phi4flash_costs as costs


def read(ctx):
    return costs.kernel_ms(ctx, "ssm_step")
