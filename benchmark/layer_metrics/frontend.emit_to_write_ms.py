"""Median time from the engine's first emit (the end of the
``engine.first_token`` span) to the ``stream.first_write`` event, the first
token chunk written to the HTTP response: the output queue, the backend's
detokenisation, the SSE encoding and the event loop's turn. Source: the
program's tracer. Where the engine's spans and the front end's sit in two
trace dicts of one request (roles in separate processes) they are joined by
``request_id`` and brought to one clock through ``start_epoch``."""

import statistics


def _marks(traces, name, end):
    """request_id → epoch ms of the first ``name`` span (its end or start)."""
    out = {}
    for t in traces:
        for s in t.get("spans", ()):
            if s["name"] == name and (s["ms"] > 0 or not end):
                at = s["at_ms"] + (s["ms"] if end else 0.0)
                out.setdefault(t["request_id"], (t["start_epoch"], at))
                break
    return out


def read(ctx):
    emitted = _marks(ctx["spans"], "engine.first_token", end=True)
    written = _marks(ctx["spans"], "stream.first_write", end=False)
    ms = [1e3 * (written[r][0] - emitted[r][0])
          + written[r][1] - emitted[r][1]
          for r in written if r in emitted]
    return statistics.median(ms) if ms else None
