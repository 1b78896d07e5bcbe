"""Median ``post_ms`` of the ``decode`` flight records: per-slot bookkeeping
after the tokens are on the host (block registration and growth, emits,
finishes, the flight record). Phase clock of the engine loop; a program
without it has nothing to read."""

import statistics


def read(ctx):
    post = [r["post_ms"] for r in ctx["flight"]
            if r["kind"] == "decode" and "post_ms" in r]
    return statistics.median(post) if post else None
