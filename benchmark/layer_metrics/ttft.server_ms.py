"""Median ``server_ms`` of the engine's ``first_token`` flight records: from
the moment the request's first byte reached the serving process (the HTTP
protocol's stamp, the origin of its trace) to the engine's emit of its first
token. One record a request, taken by its own ``t``, however long the request
then runs. A request submitted with no trace has no origin and no
``server_ms``; a program without the record has nothing to read."""

import statistics


def read(ctx):
    ms = [r["server_ms"] for r in ctx["flight"]
          if r["kind"] == "first_token" and "server_ms" in r]
    return statistics.median(ms) if ms else None
