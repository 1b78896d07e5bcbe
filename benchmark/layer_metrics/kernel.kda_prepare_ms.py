"""Kernels: device time of ``kda_prepare`` (kimi_linear: a prompt's chunk
matrices, everything of the chunked delta rule that does not depend on the
carried state, built a (chunk, head) tile at a time in VMEM) per dispatch of
the prefill program, all 20 delta-attention layers and every chunk of the
padded bucket together, in ms: the ops named after the kernel's ``name=``
over the dispatches of ``jit_prefill``. With ``kernel.kda_chunk_ms`` (the
walk) it is all of ``engine/kda.py`` that a prefill runs. A program without
the kernel, as every one before PR 55: nothing to read."""

KERNEL, PROGRAM = "%kda_prepare", "jit_prefill"


def read(ctx):
    trace = ctx.get("trace") or {}
    seconds = sum(sec for name, sec, _ in trace.get("ops", ())
                  if name.startswith(KERNEL))
    dispatches = sum(n for name, _, n in trace.get("programs", ())
                     if name == PROGRAM)
    if not seconds or not dispatches:
        return None
    return seconds / dispatches * 1e3
