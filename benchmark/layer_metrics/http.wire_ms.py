"""Median duration of the ``http.wire`` span: from the request's first byte
at the connection's protocol to the handler's entry — the HTTP parser and the
event-loop hops that wake the handler's task, on the thread the engine loop
shares. Source: the program's tracer, over the requests whose traces finished
before the profiler started (so only the cells whose requests are short list
it). A program without the span has nothing to read."""

import statistics


def read(ctx):
    ms = [s["ms"] for t in ctx["spans"] for s in t.get("spans", ())
          if s["name"] == "http.wire"]
    return statistics.median(ms) if ms else None
