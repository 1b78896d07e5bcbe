"""Share of the decode cycles' time that the phases do not tile:
100 * |sum(cycle) - sum(phases)| / sum(cycle) over the ``decode`` flight
records, the cycle being ``device_ms + host_gap_ms`` (two timestamps) and the
phases the clock's accumulated intervals. Near 0 (rounding) while every line
of the loop runs in exactly one phase; it grows when a code path escapes the
clock. A program without the phase clock has nothing to read."""

PHASES = ("sweep", "admit", "build", "dispatch", "wait", "post", "complete",
          "yield")


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "wait_ms" in r]
    cycle = sum(r["device_ms"] + r["host_gap_ms"] for r in records)
    if not cycle:
        return None
    phases = sum(r.get(f"{p}_ms", 0.0) for r in records for p in PHASES)
    return 100.0 * abs(cycle - phases) / cycle
