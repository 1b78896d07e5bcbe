"""What no stamp up to the engine's emit covers: the load generator's median
time to the first token (its own clock, a process apart; the whole window)
less the median ``server_ms`` of the ``first_token`` flight records. What is
left is the client's own loop and connect, the accept, everything before the
first byte is read, and the way back: emit to write
(``frontend.emit_to_write_ms``) to the client's read. A difference of two
medians: it locates, it does not add exactly. A program without the record
has nothing to read."""

import statistics


def read(ctx):
    ttft = ctx["load"]["ttft_ms"]
    server = [r["server_ms"] for r in ctx["flight"]
              if r["kind"] == "first_token" and "server_ms" in r]
    if not ttft or not server:
        return None
    return statistics.median(ttft) - statistics.median(server)
