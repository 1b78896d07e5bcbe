"""Live latent rows a decode step reads, in thousands: the mean over the
``decode`` flight records that applied a step of ``ctx_tokens`` (Σ live
context over the step's sequences) per step. Records without the counter:
nothing to read."""

import statistics


def read(ctx):
    values = [r["ctx_tokens"] / max(1, r.get("K", 1)) for r in ctx["flight"]
              if r["kind"] == "decode" and r.get("ctx_tokens")]
    return statistics.fmean(values) / 1e3 if values else None
