#!/usr/bin/env python3
"""Builder's check of a kimi_linear configuration beside the probe: the
breakages of ``references/kimi_linear.py`` at the configuration's own widths
and depth, which ``selftest.py`` shows at rehearsal widths only.

    chiprun --timeout 3000 -- python3 benchmark/references/kimi_linear_check.py   # the configuration, on the chip
    JAX_PLATFORMS=cpu python3 benchmark/references/kimi_linear_check.py --fixture tiny-kimi-linear --tokens 48

It builds the configuration's engine with its deployment flags (no HTTP) and
drives the engine's own compiled programs by hand, slot 0 (``selftest.greedy``:
the prompt through the prefill program — ``kda_chunk`` and the blocked latent
prefill — then decode steps through ``kda_step`` and the cache), and holds
the served tokens' logprobs to the reference's full forward
(``reference.compare`` / ``TOL_STD``, unchanged): ``main`` has to read inside
the tolerance, the reference in the device's default precision too
(``witness``: the same mathematics at the program's own precision); every
breakage and control has to read outside it. Then the cache's own leaves
(slot 0's state in the first K layer, the pe lanes of its rows in the first
F layer's pool) against the reference's (``leaves_for`` / ``LEAF_TOL``):
inside for the unbroken reference, outside for the breakages that move a
leaf (``TAPPED``: the state rounded to bf16, the pe lanes rotated), which
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
    them: the first K layer's state, and the pe lanes of the first F
    layer's pool rows (``selftest.greedy``'s table: blocks 1.., so token t
    lies at row block_size + t)."""
    import numpy as np
    rank, dr = int(hf["kv_lora_rank"]), int(hf["qk_rope_head_dim"])
    bs = core.cfg.kv_block_size
    return {"kda": np.asarray(core.kv["kda"][0, 0], np.float32),
            "pe": np.asarray(core.kv["kv"][0, bs:bs + n, rank:rank + dr],
                             np.float32)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="kimi-linear-48b")
    ap.add_argument("--fixture", help="a tiny configuration instead "
                    "(benchmark/fixtures/<name>.json)")
    ap.add_argument("--tokens", type=int, default=640)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=54)
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
    ids, lps = selftest.greedy(core, prompt, opts.steps)
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
