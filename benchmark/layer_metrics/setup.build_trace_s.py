"""Seconds of set-up the process spent TRACING and LOWERING its programs:
``built_trace_ms`` of the window's first cycle record (``decode`` /
``ragged`` / ``verify``), the engine's build log's running total of Python
trace plus lowering over every XLA program built before it
(``engine/flight_recorder.py`` ``BuildLog``). No cache saves this part; only
the code shortens it. A counter read as Prometheus reads one: the first
record of the window holds all of set-up. A program whose cycle records lack
the field (no build log) has nothing to read."""

CYCLES = ("decode", "ragged", "verify")


def read(ctx):
    first = next((r for r in ctx["flight"] if r["kind"] in CYCLES), None)
    if first is None or "built_trace_ms" not in first:
        return None
    return first["built_trace_ms"] / 1e3
