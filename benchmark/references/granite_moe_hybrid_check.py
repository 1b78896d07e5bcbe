#!/usr/bin/env python3
"""Builder's check of a granitemoehybrid configuration beside the probe: the
breakages of ``references/granite_moe_hybrid.py`` at the configuration's own
widths and depth, which ``selftest.py`` shows at rehearsal widths only.

    chiprun --timeout 3000 -- python3 benchmark/references/granite_moe_hybrid_check.py   # the configuration, on the chip
    JAX_PLATFORMS=cpu python3 benchmark/references/granite_moe_hybrid_check.py --fixture tiny-granite-moe-hybrid --tokens 48

It builds the configuration's engine with its deployment flags (no HTTP) and
drives the engine's own compiled programs by hand, slot 0 (``selftest.greedy``:
the prompt through the prefill program in its dispatches of ``--prefill-chunk``
rows — ``ssd_chunk`` and the flash prefill — then decode steps through
``ssd_step`` and the cache), and holds
the served tokens' logprobs to the reference's full forward
(``reference.compare`` / ``TOL_STD``, unchanged): ``main`` has to read inside
the tolerance, the reference in the device's default precision too
(``witness``: the same mathematics at the program's own precision); every
breakage and control has to read outside it. Then the cache's own leaves
(slot 0's state in the first M layer, its key rows in the first A layer's
pool) against the reference's (``leaves_for`` / ``LEAF_TOL``):
inside for the unbroken reference, outside for the breakages that move a
leaf (``TAPPED``: the state rounded to bf16, the keys rotated), which
count as caught by either reading. The verdict is all of it: ``main``, the
witness and the leaves hold, and nothing listed goes uncaught. One JSON line
per reading (``CHECK {...}``); numbers from a CPU run are not device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]


def say(**reading) -> None:
    print("CHECK " + json.dumps(reading), flush=True)


def build(config: dict, seed: int):
    """The configuration's engine as the launcher builds it from the
    deployment's flags, seeded weights made on the device."""
    import dataclasses
    import run as bench_run
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.launch import run as launcher
    hf = bench_run.hf_config(config)
    e = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *config["deployment"]["flags"]]))
    return hf, EngineCore(ModelConfig.from_hf_config(hf),
                          dataclasses.replace(e, seed=seed))


def engine_leaves(core, hf: dict, n: int) -> dict:
    """Slot 0's cache leaves after ``n`` tokens, as ``leaves_for`` names
    them: the first M layer's state as [H, P, N], and the first A layer's
    key rows (``serve``'s table: blocks 1.., so token t lies at row
    block_size + t)."""
    import numpy as np
    from dynamo_tpu.engine import ssd
    bs = core.cfg.kv_block_size
    return {"ssd": np.asarray(ssd.state_to_hpn(
                core.kv["ssd"][0, 0], int(hf["mamba_n_heads"])), np.float32),
            "k": np.asarray(core.kv["k"][0, bs:bs + n], np.float32)}


def serve(core, prompt: list, n: int) -> tuple:
    """``selftest.greedy`` for an engine that takes a prompt in dispatches
    of ``--prefill-chunk`` rows: the prompt through the prefill program a
    chunk at a time (slot 0, blocks 1..), then n - 1 decode steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import selftest
    C = core.cfg.prefill_chunk
    if not C or len(prompt) <= C:
        return selftest.greedy(core, prompt, n)
    table = np.arange(1, core.M + 1, dtype=np.int32)
    f32, i32 = jnp.float32, jnp.int32
    for lo in range(0, len(prompt), C):
        piece = prompt[lo:lo + C]
        padded = np.zeros((C,), np.int32)
        padded[:len(piece)] = piece
        tok, lp, core.kv = core._prefill_jit(
            core.params, core.kv, jnp.asarray(padded), jnp.asarray(table),
            jnp.asarray(lo, i32), jnp.asarray(len(piece), i32),
            jax.random.PRNGKey(0), jnp.asarray(0.0, f32),
            jnp.asarray(0, i32), jnp.asarray(1.0, f32))
    # the decode steps are greedy's own: replay them from the first token
    ids, lps = [int(tok)], [float(lp)]
    from dynamo_tpu.engine.sampling import make_slot_keys
    B = core.B
    tables = np.zeros((B, core.M), np.int32)
    tables[0] = table
    for step in range(n - 1):
        tokens, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        tokens[0], pos[0] = ids[-1], len(prompt) + step
        keys = make_slot_keys(0, jnp.zeros((B,), jnp.int32),
                              jnp.zeros((B,), jnp.int32))
        toks, lpb, core.kv = core._decode_jit(
            core.params, core.kv, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(tables), keys, jnp.zeros((B,), f32),
            jnp.zeros((B,), i32), jnp.ones((B,), f32))
        ids.append(int(toks[0]))
        lps.append(float(lpb[0]))
    return ids, lps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="granite-4.0-h-small")
    ap.add_argument("--fixture", help="a tiny configuration instead "
                    "(benchmark/fixtures/<name>.json)")
    ap.add_argument("--tokens", type=int, default=1500)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=59)
    ap.add_argument("--only", default=None,
                    help="comma-separated breakages (default: all; '': none)")
    opts = ap.parse_args()
    import numpy as np
    import reference
    import run as bench_run
    import selftest
    t0 = time.monotonic()
    if opts.fixture:
        config = selftest.load_fixture(opts.fixture)
    else:
        config = bench_run.load_config(bench_run.load_benchmark(),
                                       opts.config)
    ref = bench_run.reference_module(config)
    hf, core = build(config, opts.seed)
    prompt = np.random.default_rng(opts.seed).integers(
        0, int(hf["vocab_size"]), size=opts.tokens).tolist()
    ids, lps = serve(core, prompt, opts.steps)
    say(what="served", tokens=opts.tokens, steps=opts.steps,
        build_and_serve_s=round(time.monotonic() - t0, 1))

    def held(**kw):
        rep = reference.compare(core.params, hf, prompt, ids, lps, **kw)
        return {k: rep[k] for k in ("ok", "worst_logprob_err_std",
                                    "worst_argmax_gap_std")}
    main_rep = held(forward=ref.logits_for)
    say(what="main", **main_rep)
    wit = held(forward=lambda *a: ref.logits_for(*a, precision="default"))
    say(what="witness[default precision]", **wit)
    ok = main_rep["ok"] and wit["ok"]
    seq = list(prompt) + list(ids[:-1])       # what the cache has taken in
    mine = engine_leaves(core, hf, len(seq))

    def leaf_errors(broken=None):
        want = ref.leaves_for(core.params, hf, seq, broken)
        return {k: ref.leaf_error(mine[k], want[k]) for k in mine}
    errs = leaf_errors()
    leaves_ok = all(errs[k] <= ref.LEAF_TOL[k] for k in errs)
    say(what="leaves", ok=leaves_ok, tol=ref.LEAF_TOL, **errs)
    names = (sorted(set(ref.breakages_for(hf)) | set(ref.TAPPED),
                    key=ref.BREAKAGES.index) + list(ref.CONTROLS)
             if opts.only is None
             else [b for b in opts.only.split(",") if b])
    unseen = []
    for broken in names:
        rep = held(forward=ref.logits_for, broken=broken)
        caught = not rep["ok"]
        if broken in ref.TAPPED:
            leaf = ref.TAPPED[broken]
            rep[f"leaf_{leaf}"] = leaf_errors(broken)[leaf]
            rep["caught_by_leaf"] = rep[f"leaf_{leaf}"] > ref.LEAF_TOL[leaf]
            caught = caught or rep["caught_by_leaf"]
        kind = "control" if broken in ref.CONTROLS else "broken"
        say(what=f"{kind}[{broken}]", caught=caught, **rep)
        if not caught:
            unseen.append(broken)
    ok = ok and leaves_ok and not unseen
    say(what="verdict", ok=ok, not_caught=unseen,
        seconds=round(time.monotonic() - t0, 1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
