"""Median ``yield_ms`` of the ``decode`` flight records: what the engine loop
gave, per cycle, to everything else on the shared event loop (HTTP handlers,
detokenisation, SSE writes). Phase clock of the engine loop; a program without
it has nothing to read."""

import statistics


def read(ctx):
    gave = [r["yield_ms"] for r in ctx["flight"]
            if r["kind"] == "decode" and "yield_ms" in r]
    return statistics.median(gave) if gave else None
