"""Kernels: device time of DeepSeek Sparse Attention's ``dsa_select`` stage per
decode step, all layers of the step together, in ms: the seconds of the
stage's ops (named by their result shapes in
``references/deepseek_v32_costs.stage_patterns``, where the reasons are)
over the dispatches of the served decode program ``jit_decode_k`` in the
profiler's window. A program without the stage (no indexer; the parent of
PR 31): nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import deepseek_v32_costs as costs


def read(ctx):
    seconds = costs.stage_seconds_per_step(ctx, "dsa_select")
    return None if seconds is None else seconds * 1e3
