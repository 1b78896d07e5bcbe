"""Share of the prompt tokens that were encoded on a worker thread and not
on the thread the engine loop shares: 100 x the ``tokens`` of the
``tokenize`` spans whose ``offthread`` attribute is true over the ``tokens``
of all of them, over the requests whose traces finished in the traced
window. Source: the program's tracer (the ``preprocess`` span's child). A
program without the span, or a window in which no request finished, has
nothing to read."""


def read(ctx):
    spans = [s.get("attrs", {}) for t in ctx["spans"]
             for s in t.get("spans", ()) if s["name"] == "tokenize"]
    total = sum(a.get("tokens", 0) for a in spans)
    if not total:
        return None
    off = sum(a.get("tokens", 0) for a in spans if a.get("offthread"))
    return 100.0 * off / total
