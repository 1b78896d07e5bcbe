"""Mean share of the decode slots that held a sequence, over the ``decode``
flight records of the window: ``batch_fill`` / ``max_num_seqs``."""

import statistics


def read(ctx):
    fills = [r["batch_fill"] for r in ctx["flight"] if r["kind"] == "decode"]
    if not fills:
        return None
    return 100.0 * statistics.fmean(fills) / ctx["engine"]["max_num_seqs"]
