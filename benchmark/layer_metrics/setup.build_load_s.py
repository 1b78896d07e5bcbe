"""Seconds of set-up the process spent COMPILING its programs or reading them
from the persistent cache: ``built_ms - built_trace_ms`` of the window's first
cycle record (``decode`` / ``ragged`` / ``verify``), the engine's build log's
running totals over every XLA program built before it
(``engine/flight_recorder.py`` ``BuildLog``: ``built_ms`` is trace + lowering
+ the backend call, ``built_trace_ms`` the first two). The cache's policy, a
seed passed as an argument or fewer programs shorten it. A run that is the
tree's first (cold) reads large here by design: that is the compile. A
program whose cycle records lack the fields (no build log) has nothing to
read."""

CYCLES = ("decode", "ragged", "verify")


def read(ctx):
    first = next((r for r in ctx["flight"] if r["kind"] in CYCLES), None)
    if first is None or not {"built_ms", "built_trace_ms"} <= set(first):
        return None
    return (first["built_ms"] - first["built_trace_ms"]) / 1e3
