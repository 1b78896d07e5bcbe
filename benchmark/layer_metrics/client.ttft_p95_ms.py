"""As ``client.ttft_p50_ms``: the 95th percentile, or the highest percentile
with ten samples beyond it."""

import stats


def read(ctx):
    ttft = ctx["load"]["ttft_ms"]
    if not ttft:
        return None
    return stats.percentile(ttft, stats.supported_tail(len(ttft)))
