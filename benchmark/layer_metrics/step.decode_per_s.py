"""Decode dispatches per second of window: the count of ``decode`` flight
records (by ``records_total``, so none is lost to the ring) over the window's
length. A count, exact. Of DISPATCHES: one token a live slot on the one-row
step, 0 to 2 tokens a slot on a resident drafter's two-row step, where
tokens a second = this x live slots x ``spec.tokens_per_step``, the reading
beside it."""


def read(ctx):
    n = sum(1 for r in ctx["flight"] if r["kind"] == "decode")
    return n / ctx["window_s"] if n else None
