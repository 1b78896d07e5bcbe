"""95th percentile of the decode loop's host-clock cycle, harvest to harvest:
``device_ms + host_gap_ms`` of the ``decode`` flight records, of which
``step.decode_cycle_ms`` is the median. The engine-side tail of the gap
between tokens: a cycle that dispatched a neighbour's prefill, or waited one
out, is this long for every decoding request."""

import stats


def read(ctx):
    cycles = [r.get("device_ms", 0.0) + r["host_gap_ms"]
              for r in ctx["flight"] if r["kind"] == "decode"]
    return stats.percentile(cycles, 95.0) if cycles else None
