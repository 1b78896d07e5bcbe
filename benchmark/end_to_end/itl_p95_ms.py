"""95th percentile of the gaps between consecutive streamed tokens of one
request, pooled over every request, counted where the later token arrived
inside the window."""

import stats


def read(ctx):
    itl = ctx["load"]["itl_ms"]
    return stats.percentile(itl, 95.0) if itl else None
