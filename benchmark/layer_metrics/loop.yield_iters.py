"""Mean ``yield_iters`` of the ``decode`` flight records: the event-loop
iterations the engine loop's yield ran per cycle before the shared loop was
quiet (or the drain's bound was reached). A mean, not a median: most cycles
meet no arrival and run one, so the median is 1 by construction and the mean
says how often, and how far, a chain of hops was drained inside a cycle. A
program whose records lack the field (a single ``sleep(0)`` a cycle) has
nothing to read."""

import statistics


def read(ctx):
    ran = [r["yield_iters"] for r in ctx["flight"]
           if r["kind"] == "decode" and "yield_iters" in r]
    return statistics.fmean(ran) if ran else None
