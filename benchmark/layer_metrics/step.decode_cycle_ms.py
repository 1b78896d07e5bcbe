"""Median host-clock cycle of the decode loop, from one harvest to the next:
``device_ms + host_gap_ms`` of the ``decode`` flight records. At one step per
dispatch the program records ``device_ms = 0.0`` by construction, so only the
sum is sound; no host/device split may be read from these records."""

import statistics


def read(ctx):
    cycles = [r.get("device_ms", 0.0) + r["host_gap_ms"]
              for r in ctx["flight"] if r["kind"] == "decode"]
    return statistics.median(cycles) if cycles else None
