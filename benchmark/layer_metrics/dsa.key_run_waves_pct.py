"""Kernels: share of the index-key waves of the decode steps that were read
as ONE copy: 100 * sum(key_run_waves) / sum(key_waves) over the ``decode``
flight records. The ``index_scores`` kernel (``engine/index_scores.py``)
streams each live slot's index keys from the pool in waves of
``key_wave_blocks`` blocks (64 at the published widths: 256 KB); a wave
whose blocks lie adjacent in the pool is one copy, any other one copy per
4 KB block, which is bound by the copies issued and not by their bytes. The
engine counts both from the dispatch's own tables with the kernel's
predicate (``attention.wave_contig_table``) at the kernel's depth, per layer
(every layer's tables differ by an offset only). High where the prefix cache
holds a shared document as one run of blocks (16 of a slot's 17 waves in
``docqa-closed``); it falls as the pool fragments. A program that records no
``key_waves`` (before PR 36), or a model with no indexer: nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and r.get("key_waves")]
    waves = sum(r["key_waves"] for r in records)
    if not waves:
        return None
    return 100.0 * sum(r["key_run_waves"] for r in records) / waves
