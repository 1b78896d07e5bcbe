"""Host-clock milliseconds of admission per thousand prompt tokens
prefilled: sum of ``host_ms`` over sum of ``planned_tokens`` / 1000, over the
``prefill`` flight records of the window. It holds the dispatch and whatever
the host waited for; it is not device time."""


def read(ctx):
    recs = [r for r in ctx["flight"] if r["kind"] == "prefill"]
    tokens = sum(r["planned_tokens"] for r in recs)
    if not tokens:
        return None
    return sum(r["host_ms"] for r in recs) / (tokens / 1000.0)
