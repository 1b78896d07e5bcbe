"""Share of the live context that a window layer reads in the decode steps:
100 * sum(win_tokens) / sum(ctx_tokens) over the ``decode`` flight records.
``win_tokens`` is the sum over a step's slots of min(context, window): the
host's arithmetic on positions, a descriptor of the traffic that feeds
``kernel.hybrid_attention_roofline_pct`` its bytes, not a reading of what
the device read. A program that records no ``win_tokens`` (before PR 35), or
a model without window layers of bounded rows (the two counters are then
equal by construction): nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "win_tokens" in r]
    total = sum(r["ctx_tokens"] for r in records)
    if not total or all(r["win_tokens"] == r["ctx_tokens"] for r in records):
        return None
    return 100.0 * sum(r["win_tokens"] for r in records) / total
