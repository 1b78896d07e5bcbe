"""95th percentile of ``queue_wait_ms`` over the engine's ``prefill`` flight
records of the window: how long an admitted request had waited in the
engine's queue. Host clock, inside the program."""

import stats


def read(ctx):
    waits = [r["queue_wait_ms"] for r in ctx["flight"]
             if r["kind"] == "prefill" and "queue_wait_ms" in r]
    return stats.percentile(waits, 95.0) if waits else None
