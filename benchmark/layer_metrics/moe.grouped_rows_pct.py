"""Kernels: share of the computed prompt rows whose expert layers ran in the
grouped form (the routed experts only, ``grouped_experts``) and not dense
over every expert: 100 * sum(grouped_rows) / sum(prompt - hit_device) over
the window's ``prefill`` flight records. ``grouped_rows`` is written by the
engine from the same chooser the model code asks
(``models/llama.py experts_run_grouped``: static row count of the dispatched
program, expert shapes, layout), so the share says which prefill buckets of
the traffic lie above the crossover: ~100 where prompts are long, 0.0 where
every bucket is below it, on a mesh, or on a model without experts. A
program that records no ``grouped_rows`` (before PR 32), or no prefill in
the window: nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "prefill" and "grouped_rows" in r]
    rows = sum(r["prompt"] - r.get("hit_device", 0) for r in records)
    if not records or rows <= 0:
        return None
    return 100.0 * sum(r["grouped_rows"] for r in records) / rows
