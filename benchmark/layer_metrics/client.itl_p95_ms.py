"""95th percentile of the pooled gaps between a request's streamed tokens, in
the closed-loop cells (see ``client.ttft_p50_ms``). With few sizes of prefill
the gaps fall into a few clusters and this percentile can sit between two of
them: it flipped between 305 and 360 ms from run to run (my chip runs,
PR 27), which is why it carries no bound there."""

import stats


def read(ctx):
    itl = ctx["load"]["itl_ms"]
    return stats.percentile(itl, 95.0) if itl else None
