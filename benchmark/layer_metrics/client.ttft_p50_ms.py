"""Median time from sending a request to its first streamed token, in the
closed-loop cells, where the callers keep the system saturated and a tail
swings with the smallest change: there it is a reading, not a judged metric
(the open-loop cell judges ``ttft_p50_ms``). Whole traced window."""

import statistics


def read(ctx):
    ttft = ctx["load"]["ttft_ms"]
    return statistics.median(ttft) if ttft else None
