"""Decode dispatches per second of window: the count of ``decode`` flight
records (by ``records_total``, so none is lost to the ring) over the window's
length. A count, exact."""


def read(ctx):
    n = sum(1 for r in ctx["flight"] if r["kind"] == "decode")
    return n / ctx["window_s"] if n else None
