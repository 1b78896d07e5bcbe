"""Kernels: share of the prefill chunks' query blocks whose sparse attention
ran: 100 * sum(dsa_blocks_run) / sum(dsa_blocks) over the window's
``prefill`` flight records. A prefill chunk of a model with an indexer
selects and reads a block of ``DSA_QUERY_BLOCK`` (32) queries at a time
(``models/mla.py _sparse_chunk``): index scores over the whole table, the
exact top-k, the gather of the selected rows and the attend over them, per
block and layer. The walk stops at the last block that holds a live query,
so a chunk of ``true_len`` rows in a 256-row program runs ceil(true_len / 32)
of its 8 blocks. The engine counts both from the model's own arithmetic
(``mla.sparse_query_blocks``, the bound the program computes from
``true_len``), per layer, summed over an admission's chunks. 100 where every
chunk is full (a cold prompt's chunks but the last); ~69 where a hit leaves
a question of 64-256 tokens in one 256-row chunk: the reading says how much
of the fixed-shape walk the traffic needs, and 100 minus it what the dynamic
bound saves. A program that records no ``dsa_blocks`` (before PR 48), a
model with no indexer, or no prefill in the window: nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "prefill" and r.get("dsa_blocks")]
    blocks = sum(r["dsa_blocks"] for r in records)
    if not blocks:
        return None
    return 100.0 * sum(r["dsa_blocks_run"] for r in records) / blocks
