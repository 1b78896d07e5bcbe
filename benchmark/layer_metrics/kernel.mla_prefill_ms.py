"""Kernels: device time of a dense latent-attention prefill chunk's attention
(the key-block walk of ``mla._dense_chunk``), all layers of the chunk
together, in ms: the seconds of its Pallas calls (by ``name=``: a key
block's expansion through ``wkv_b``, its scores and the running softmax)
over the dispatches of the prefill program ``jit_prefill`` in the profiler's
window. The XLA ops around the calls (the rows' gather from the pool, the
queries' layout, the final division) are not counted: their shapes are
other ops' too. A program without the walk (another family; the parent of
PR 37): nothing to read."""

# benchmark/ is on sys.path wherever a reader is loaded (run.py, selftest.py)
from references import kimi_k2_costs as costs


def read(ctx):
    seconds = costs.prefill_seconds_per_dispatch(ctx)
    return None if seconds is None else seconds * 1e3
