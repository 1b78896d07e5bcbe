"""Peak device memory in use over the process, as a share of what the device
lets JAX use: ``memory_stats()`` after the window."""


def read(ctx):
    mem = ctx.get("memory") or {}
    if not mem.get("bytes_limit") or "peak_bytes_in_use" not in mem:
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
