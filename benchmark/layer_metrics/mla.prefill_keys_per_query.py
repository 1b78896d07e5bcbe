"""Keys a prefilled row attended, mean over the window's requests: sum of
``key_tokens`` over sum of ``planned_tokens`` of the ``prefill`` flight
records (a cold prompt of n tokens reads (n+1)/2 a row). Records without the
counter (before PR 37) or a family that records 0: nothing to read."""


def read(ctx):
    recs = [r for r in ctx["flight"]
            if r["kind"] == "prefill" and r.get("key_tokens")]
    rows = sum(r["planned_tokens"] for r in recs)
    return sum(r["key_tokens"] for r in recs) / rows if rows else None
