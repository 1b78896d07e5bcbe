"""Median ``build_ms + dispatch_ms`` of the ``decode`` flight records: the host
time of one cycle spent preparing the step (slot walk, block tables, sampling
keys, host-to-device transfers) and in the jitted call until it returns. Phase
clock of the engine loop; a program without it has nothing to read."""

import statistics


def read(ctx):
    prep = [r["build_ms"] + r["dispatch_ms"] for r in ctx["flight"]
            if r["kind"] == "decode" and "build_ms" in r]
    return statistics.median(prep) if prep else None
