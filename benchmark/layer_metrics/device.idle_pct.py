"""Share of the profiler's window in which no operation ran on the device:
1 − union of the device-op intervals / window, from ``trace_reduce.py``."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
