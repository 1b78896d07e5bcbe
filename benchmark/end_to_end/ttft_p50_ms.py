"""Median time to the first streamed token, from when a request was due
(open loop) or sent (closed loop), over requests that started in the window."""

import statistics


def read(ctx):
    ttft = ctx["load"]["ttft_ms"]
    return statistics.median(ttft) if ttft else None
