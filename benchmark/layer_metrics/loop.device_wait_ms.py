"""Median ``wait_ms`` of the ``decode`` flight records whose cycle issued no
prefill (``admits == 0``): what the engine loop blocked on the device for one
decode step, by the loop's phase clock (``engine/flight_recorder.py``). A cycle
that admitted someone also waits for that prefill, so it is left out. A
program without the phase clock records no ``wait_ms``: nothing to read."""

import statistics


def read(ctx):
    waits = [r["wait_ms"] for r in ctx["flight"]
             if r["kind"] == "decode" and "wait_ms" in r
             and r.get("admits", 0) == 0]
    return statistics.median(waits) if waits else None
