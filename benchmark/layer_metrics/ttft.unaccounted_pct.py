"""Share of the server-side time to the first token that its stages do not
tile: 100 * sum|server_ms - (ingest_ms + queue_wait_ms + prefill_ms +
first_token_wait_ms)| / sum(server_ms) over the ``first_token`` flight
records that have an origin. Near 0 (rounding) while the five stamps are the
ones the stages are cut at; it grows when a path stamps one of them elsewhere.
The request's check, as ``loop.unaccounted_pct`` is the cycle's. A program
without the record has nothing to read."""

STAGES = ("ingest_ms", "queue_wait_ms", "prefill_ms", "first_token_wait_ms")


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "first_token" and "server_ms" in r]
    total = sum(r["server_ms"] for r in records)
    if not total:
        return None
    return 100.0 * sum(abs(r["server_ms"] - sum(r[s] for s in STAGES))
                       for r in records) / total
