"""Share of the device's busy time that the prefill program takes, in %: the
seconds of ``jit_prefill``'s dispatches over ``busy_s`` of the profiler's
window (the rest is the decode program, the block copies and what runs
between programs). No prefill dispatch in the window: nothing to read."""

PROGRAM = "jit_prefill"


def read(ctx):
    trace = ctx["trace"]
    seconds = sum(s for name, s, _ in trace.get("programs", ())
                  if name == PROGRAM)
    busy = trace.get("busy_s")
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
