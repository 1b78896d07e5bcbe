"""Kernels: device time of the paged-attention kernel per decode step, all
layers of the step together: the seconds of the ops named
``%paged_attention*`` (the Pallas kernel's ``name=``) over the dispatches of
the served decode program ``jit_decode_k`` in the profiler's window. Time,
not yet a roofline share: the bytes a step's attention has to read need the
live context per step, which no flight record carries (PERF.md section 7)."""

KERNEL = "%paged_attention"
PROGRAM = "jit_decode_k"


def read(ctx):
    trace = ctx["trace"]
    seconds = sum(s for name, s, _ in trace.get("ops", ())
                  if name.startswith(KERNEL))
    steps = sum(n for name, _, n in trace.get("programs", ())
                if name == PROGRAM)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e3
