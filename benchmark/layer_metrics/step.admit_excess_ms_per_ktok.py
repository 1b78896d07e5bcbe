"""What an admitted prompt token costs the decoding neighbours: the time of
all ``decode`` cycles beyond what as many admission-free cycles would have
taken, per 1,000 prompt tokens the cycles' prefills were dispatched for —
(sum(cycle) - n * median(cycle where admits == 0)) / (sum(admit_tokens) /
1000). Summed over ALL cycles, because the stall lands where it lands: the
step in flight is harvested one cycle after the prefill was dispatched behind
it. The median admission-free cycle stands for a cycle nothing disturbed; the
later cycles' own stalls are in the sum, not in it. A program whose records
lack ``admit_tokens`` has nothing to read, nor a window with no admission."""

import statistics


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "admit_tokens" in r]
    tokens = sum(r["admit_tokens"] for r in records)
    quiet = [r["device_ms"] + r["host_gap_ms"] for r in records
             if not r["admits"]]
    if not tokens or not quiet:
        return None
    total = sum(r["device_ms"] + r["host_gap_ms"] for r in records)
    return ((total - len(records) * statistics.median(quiet))
            / (tokens / 1000.0))
