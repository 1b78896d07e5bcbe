"""Median duration of the program's ``preprocess`` span (template, tokenizer,
request mapping) over the requests whose traces finished in the traced
window. Source: the program's tracer (what ``/traces`` serves)."""

import statistics


def read(ctx):
    ms = [s["ms"] for t in ctx["spans"] for s in t.get("spans", ())
          if s["name"] == "preprocess"]
    return statistics.median(ms) if ms else None
