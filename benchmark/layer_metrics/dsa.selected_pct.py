"""Share of the live context that the decode steps' attention read:
100 * sum(sel_tokens) / sum(ctx_tokens) over the ``decode`` flight records.
``ctx_tokens`` is the sum over a step's slots of the live context,
``sel_tokens`` of min(context, index_topk): about 12% at 17k-token contexts
and the published top-2048. Both are the host's arithmetic on each slot's
position and the configuration's ``index_topk``: the share describes the
traffic (it feeds the two roofline shares their bytes) and shows that the
engine was built with an indexer; it does not show what the device read
(that is ``kernel.dsa_select_ms`` / ``kernel.sparse_attention_ms``, whose
ops exist only in a program that sorts [B, table] scores and gathers B·K
rows, and ``references/deepseek_v32_check.py``, which compares the sets). A
program that records no ``sel_tokens`` (before PR 31), or a model with no
indexer (the two counters are then equal by construction): nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "sel_tokens" in r]
    total = sum(r["ctx_tokens"] for r in records)
    if not total or all(r["sel_tokens"] == r["ctx_tokens"] for r in records):
        return None
    return 100.0 * sum(r["sel_tokens"] for r in records) / total
