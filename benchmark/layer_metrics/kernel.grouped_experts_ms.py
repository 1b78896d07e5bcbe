"""Kernels: device time of the grouped expert matmuls per prefill, all
layers and both matmuls (gate|up, down) of the dispatch together: the
seconds of the ops named ``%grouped_experts*`` (the Pallas kernel's
``name=``) over the dispatches of the served prefill program ``jit_prefill``
in the profiler's window. The mean over the window's mix of buckets, in ms.
The sort, the row gather and the combine around the kernel are XLA ops and
are not in it. Time, not a roofline share: the routed operation counts
(``costs.py`` ``experts="routed"``) are not wired to a reader yet (PERF.md
section 7). A program without the kernel (before PR 32, a dense-form
bucket, a model without experts) or no prefill in the window: nothing to
read."""

KERNEL = "%grouped_experts"
PROGRAM = "jit_prefill"


def read(ctx):
    trace = ctx["trace"]
    seconds = sum(s for name, s, _ in trace.get("ops", ())
                  if name.startswith(KERNEL))
    prefills = sum(n for name, _, n in trace.get("programs", ())
                   if name == PROGRAM)
    if not seconds or not prefills:
        return None
    return seconds / prefills * 1e3
