"""Admission + KV blocks: the most window-pool blocks that any one sequence
held (a layer's) in the decode steps of the window: max(win_blocks_live) over
the ``decode`` flight records. The bound is the ring, ceil(window / block) + 1
= 34 at 513 / 16, at any context length (docs/hybrid_cache.md, R1). A program
that records no ``win_blocks_live``, or a model whose window rows are not pool
blocks (the counter is then 0): nothing to read."""


def read(ctx):
    live = [r["win_blocks_live"] for r in ctx["flight"]
            if r["kind"] == "decode" and r.get("win_blocks_live")]
    return float(max(live)) if live else None
