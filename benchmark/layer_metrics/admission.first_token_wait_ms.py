"""Median duration of the ``engine.first_token`` span: from the return of the
request's prefill dispatch to the engine's first emit (at one step per
dispatch the token is fetched behind the next decode step). Source: the
program's tracer. A zero-length ``engine.first_token`` is the marker older
programs put on the trace, not this span: nothing to read there."""

import statistics


def read(ctx):
    ms = [s["ms"] for t in ctx["spans"] for s in t.get("spans", ())
          if s["name"] == "engine.first_token" and s["ms"] > 0]
    return statistics.median(ms) if ms else None
