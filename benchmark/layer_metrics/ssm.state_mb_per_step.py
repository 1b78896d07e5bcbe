"""Recurrent state read and written per decode step, in MB (10^6 bytes):
the mean ``state_bytes`` of the ``decode`` flight records that applied a
step (each live slot's state and conv inputs of every state-space layer,
once in and once out). A program that records no ``state_bytes`` (before
PR 35) or a model without such layers (0 by construction): nothing to
read."""

import statistics


def read(ctx):
    values = [r["state_bytes"] for r in ctx["flight"]
              if r["kind"] == "decode" and r.get("state_bytes")]
    return statistics.fmean(values) / 1e6 if values else None
