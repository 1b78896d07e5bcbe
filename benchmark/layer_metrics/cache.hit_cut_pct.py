"""Admission + KV blocks: share of the prefix-cache matches that was given up
because the window layers' rows before the match's boundary were gone:
100 * sum(hit_cut_tokens) / sum(hit_tokens + hit_cut_tokens) over the window's
``prefill`` flight records (docs/hybrid_cache.md, the hit rule). 0.0 where every
hit was taken whole. A program that records no ``hit_cut_tokens``, or no match
in the window: nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "prefill" and "hit_cut_tokens" in r]
    matched = sum(r["hit_tokens"] + r["hit_cut_tokens"] for r in records)
    if not matched:
        return None
    return 100.0 * sum(r["hit_cut_tokens"] for r in records) / matched
