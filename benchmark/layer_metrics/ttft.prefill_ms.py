"""Median ``prefill_ms`` of the ``first_token`` flight records: from the
request's slot and KV plan to the return of its admission's last prefill
dispatch (every chunk of a chunked prefill; about 0 where the prompt rode the
decode batch as a lane). Host clock inside the engine loop: the dispatches'
calls, not the device's work, which ``first_token_wait_ms`` waits out. A
program without the record has nothing to read."""

import statistics


def read(ctx):
    ms = [r["prefill_ms"] for r in ctx["flight"]
          if r["kind"] == "first_token" and "prefill_ms" in r]
    return statistics.median(ms) if ms else None
