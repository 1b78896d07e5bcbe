"""Kernels: share of its roofline that DeepSeek Sparse Attention's
``sparse_attention`` stage reaches in a decode step, in %: the least time the chip
could take for the stage's operations and bytes
(``references/deepseek_v32_costs.py``, from the step's ``ctx_tokens`` /
``sel_tokens`` and the configuration's shapes, against ``peaks.py``) over
the stage's measured device time per step (``kernel.sparse_attention_ms``). Only
what the algorithm must read is counted, so the share cannot pass 100. A
program without the stage or without the counters: nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import deepseek_v32_costs as costs


def read(ctx):
    return costs.stage_roofline_pct(ctx, "sparse_attention")
