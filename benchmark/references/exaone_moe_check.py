#!/usr/bin/env python3
"""Builder's check of an exaone_moe configuration's resident drafter, beside
the probe: what the served comparison cannot see, this does. ``correct``
holds the MAIN head's logits alone, and lockstep acceptance hides a wrong
drafter completely (a wrong draft is rejected: the stream is the same, only
slower).

    chiprun -- python3 benchmark/references/exaone_moe_check.py            # the configuration, on the chip
    JAX_PLATFORMS=cpu python3 benchmark/references/exaone_moe_check.py --fixture tiny-exaone-moe --tokens 40

It builds the configuration's engine with its deployment flags (no HTTP) and
drives the engine's own compiled programs by hand, slot 0: the prompt through
the prefill program in its chunks, then two-row steps whose second row is the
module's own draft (rejected, with seeded weights) or, every other step, the
token the first row samples (accepted: the step is first run to learn it, on
a copy of nothing: the pool's rows at those positions are rewritten by the
second run, which is what a rewind is). The DRAFT logits of every program are
carried out of it by a tap on ``mimo._draft_logits`` (a host callback; the
programs are otherwise the served ones) and held to
``references/exaone_moe.py`` ``mtp_logits_for``, teacher-forced on the tokens
that were actually consumed:

* ``draft[prefill]``: the first draft, after the last chunk;
* ``draft[step i row r]``: the draft behind each row that the loop would
  keep (row 0's after a rejection, row 1's after an acceptance);
* each breakage of the module (``MTP_BREAKAGES``) and of the main model: its
  distance from the engine's draft logits, as the tolerance would see it;
* ``main``: the probe's own comparison of the sampled tokens
  (``reference.compare``), through the two-row path.

Distances are ``reference.compare``'s, on the engine's draft in place of a
served token (its logprob by both, and the reference's gap to its own
maximum), in standard deviations of the reference's row, against
``reference.TOL_STD`` (0.25: bf16 explains a few hundredths, a fault most of
a standard deviation). One JSON line per reading (``CHECK {...}``);
numbers from a CPU run are not device numbers.
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


class Tap:
    """Every call of ``mimo._draft_logits`` inside a compiled program hands
    its logits to the host, in program order. Installed BEFORE the programs
    are traced (an engine's jits trace on their first call)."""

    def __init__(self):
        from dynamo_tpu.engine.models import mimo
        self.mimo, self.real, self.calls = mimo, mimo._draft_logits, []

    def __enter__(self):
        import jax
        import numpy as np

        def sink(logits):
            self.calls.append(np.asarray(logits, np.float32))

        def tapped(params, u, cfg):
            out = self.real(params, u, cfg)
            jax.debug.callback(sink, out, ordered=True)
            return out

        self.mimo._draft_logits = tapped
        return self

    def __exit__(self, *exc):
        self.mimo._draft_logits = self.real

    def take(self):
        """The one call since the last take."""
        import jax
        jax.effects_barrier()
        (out,), self.calls = self.calls, []
        return out


def distance(got, want) -> float:
    """How far the engine's row of draft logits ``got`` stands from the
    reference's ``want``, as ``reference.compare`` measures a served token:
    the engine's draft (its argmax) must have the reference's logprob within
    the tolerance of the engine's own, and the reference's logit within the
    tolerance of the reference's maximum; the larger of the two, in standard
    deviations of the reference's row."""
    import numpy as np
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    tok = int(np.argmax(got))

    def logprob(row):
        return row[tok] - (row.max() + np.log(np.exp(row - row.max()).sum()))
    return float(max(abs(logprob(want) - logprob(got)),
                     want.max() - want[tok]) / want.std())


def tables_for(core, tokens: int):
    """Slot 0's tables as the engine's dispatches carry them: paged block b
    = b + 1, window block b = b + 1 too (ids of a pool of its own), every
    block held (a hand-driven sequence releases nothing) → the prefill
    table [2M]; ``decode_tables`` makes a step's [B, M + R] from it."""
    import numpy as np
    M, bs = core.M, core.cfg.kv_block_size
    need = -(-tokens // bs)
    if need > min(M, core.kv["win_k"].shape[1] // bs - 1):
        raise SystemExit(f"{tokens} tokens need {need} blocks of each pool")
    table = np.zeros((2 * M,), np.int32)
    table[:need] = table[M:M + need] = np.arange(1, need + 1)
    return table


def decode_tables(core, table, pos: int):
    """The decode tables of a step whose first row is at ``pos``: the ring
    holds logical window block b at entry b % R, the newest R blocks up to
    the second row's."""
    import numpy as np
    M, R, bs = core.M, core.R, core.cfg.kv_block_size
    out = np.zeros((core.B, M + R), np.int32)
    out[0, :M] = table[:M]
    last = (pos + 1) // bs
    for b in range(max(0, last - R + 1), last + 1):
        out[0, M + b % R] = table[M + b]
    return out


def prefill(core, tap: Tap, prompt: list, table, start: int = 0):
    """``prompt[start:]`` through the prefill program in the engine's
    chunks, over the rows [0, start) already in the pools → (token,
    logprob, draft, the last chunk's draft logits [V])."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = core.cfg
    rest = prompt[start:]
    chunk = cfg.prefill_chunk or cfg.bucket_for(len(rest))
    f32, i32 = jnp.float32, jnp.int32
    for lo in range(0, len(rest), chunk):
        piece = rest[lo:lo + chunk]
        padded = np.zeros((chunk,), np.int32)
        padded[:len(piece)] = piece
        nxt = rest[lo + chunk] if lo + chunk < len(rest) else -1
        tok, lp, core.kv, draft = core._prefill_jit(
            core.params, core.kv, jnp.asarray(padded), jnp.asarray(table),
            jnp.asarray(start + lo, i32), jnp.asarray(len(piece), i32),
            jax.random.PRNGKey(0), jnp.asarray(0.0, f32), jnp.asarray(0, i32),
            jnp.asarray(1.0, f32), jnp.asarray(nxt, i32))
        logits = tap.take()
    return int(tok), float(lp), int(draft), logits


def step(core, tap: Tap, table, pos: int, last: int, draft: int):
    """One two-row step of slot 0: rows (last, draft) at pos, pos + 1 →
    (tokens [2], logprobs [2], drafts [2], draft logits [2, V])."""
    import jax.numpy as jnp
    import numpy as np
    B = core.B
    tokens = np.zeros((B, 2), np.int32)
    tokens[0] = (last, draft)
    positions = np.zeros((B,), np.int32)
    positions[0] = pos
    zeros = jnp.zeros((B,), jnp.int64)
    toks, lps, core.kv, drafts = core._verify_jit(
        core.params, core.kv, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(decode_tables(core, table, pos)), zeros, zeros,
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.float32))
    logits = tap.take().reshape(B, 2, -1)[0]
    return (np.asarray(toks)[0].tolist(), np.asarray(lps)[0].tolist(),
            np.asarray(drafts)[0].tolist(), logits)


def drive(core, tap: Tap, prompt: list, steps: int, hit: int = 0,
          accept=lambda i: i % 2 == 1) -> dict:
    """The prompt (its first ``hit`` tokens by a prefill of their own: what
    a prefix hit finds in the blocks) and ``steps`` two-row steps, of which
    those ``accept`` names are given the token their first row samples as
    their draft. → {"ids", "logprobs": the emitted stream, "drafts": [(what
    it stands behind, the consumed sequence, the draft logits row)]}."""
    table = tables_for(core, len(prompt) + 2 * steps + 2)
    if hit:
        # the producer's rows: another sequence's, whose token after the
        # prefix was its own. The module's row before the boundary was made
        # from that token, so the engine cuts a hit back by one block
        # (llm/kv/hybrid.py rows_read_next_token) and computes it again
        prefill(core, tap, prompt[:hit], table)
        hit -= core.cfg.kv_block_size
    tok, lp, draft, logits = prefill(core, tap, prompt, table, start=hit)
    ids, lps = [tok], [lp]
    seen = [("prefill", list(prompt) + [tok], logits)]
    pos = len(prompt)
    for i in range(steps):
        if accept(i):
            # learn the first row's sample, then run the step with it as
            # the draft: the first run's rows are rewritten (a rewind)
            draft = step(core, tap, table, pos, ids[-1], draft)[0][0]
        toks, lp2, drafts, logits = step(core, tap, table, pos, ids[-1],
                                         draft)
        took = 2 if toks[0] == draft else 1
        ids += toks[:took]
        lps += lp2[:took]
        pos += took
        draft = drafts[took - 1]
        seen.append((f"step {i} row {took - 1}", list(prompt) + ids,
                     logits[took - 1]))
    return {"ids": ids, "logprobs": lps, "drafts": seen}


def build(config: dict, seed: int, spec_k: int = 1, dtype=None, flags=()):
    """The configuration's engine, through the launcher's flags."""
    import dataclasses
    import run as bench_run
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.launch import run as launcher
    hf = bench_run.hf_config(config)
    engine_cfg = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *config["deployment"]["flags"], *flags]))
    engine_cfg = dataclasses.replace(engine_cfg, seed=seed, spec_k=spec_k)
    kw = {} if dtype is None else {"param_dtype": dtype}
    return hf, EngineCore(ModelConfig.from_hf_config(hf), engine_cfg, **kw)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="k-exaone-236b")
    ap.add_argument("--fixture", help="a tiny configuration instead "
                    "(benchmark/fixtures/<name>.json)")
    ap.add_argument("--tokens", type=int, default=640)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--only", default=None,
                    help="comma-separated breakages (default: all; '': none)")
    opts = ap.parse_args()
    import numpy as np
    import reference
    import run as bench_run
    t0 = time.monotonic()
    if opts.fixture:
        with open(os.path.join(BENCH, "fixtures",
                               f"{opts.fixture}.json")) as f:
            config = json.load(f)
    else:
        config = bench_run.load_config(bench_run.load_benchmark(),
                                       opts.config)
    ref = bench_run.reference_module(config)
    ok = True
    with Tap() as tap:
        hf, core = build(config, opts.seed)
        rng = np.random.default_rng(opts.seed)
        prompt = rng.integers(0, int(hf["vocab_size"]),
                              size=opts.tokens).tolist()
        # a third of the prompt as a hit's prefix (whole blocks)
        hit = opts.tokens // 3 // core.cfg.kv_block_size \
            * core.cfg.kv_block_size
        out = drive(core, tap, prompt, opts.steps, hit=hit)
    say(what="served", tokens=opts.tokens, hit=hit, steps=opts.steps,
        emitted=len(out["ids"]), build_and_serve_s=round(
            time.monotonic() - t0, 1))
    rep = reference.compare(core.params, hf, prompt, out["ids"],
                            out["logprobs"], forward=ref.logits_for)
    say(what="main", **{k: rep[k] for k in (
        "ok", "worst_logprob_err_std", "worst_argmax_gap_std")})
    ok = ok and rep["ok"]
    # the witness: the reference itself in the device's default precision
    # (bf16 passes on a TPU), which has to read INSIDE the tolerance
    wit = reference.compare(
        core.params, hf, prompt, out["ids"], out["logprobs"],
        forward=lambda *a: ref.logits_for(*a, precision="default"))
    say(what="witness[default precision]", **{k: wit[k] for k in (
        "ok", "worst_logprob_err_std", "worst_argmax_gap_std")})
    worst = 0.0
    for where, seq, logits in out["drafts"]:
        want = ref.mtp_logits_for(core.params, hf, seq, 1)[0]
        d = distance(logits, want)
        worst = max(worst, d)
        say(what=f"draft[{where}]", distance_std=d,
            argmax_same=bool(int(np.argmax(logits)) == int(np.argmax(want))),
            ok=d <= reference.TOL_STD)
    ok = ok and worst <= reference.TOL_STD
    say(what="draft", worst_distance_std=worst, tol_std=reference.TOL_STD,
        ok=worst <= reference.TOL_STD)
    names = (list(ref.BREAKAGES) + list(ref.CONTROLS) if opts.only is None
             else [b for b in opts.only.split(",") if b])
    # the last draft kept: behind an accepted row, over the whole history
    where, seq, logits = out["drafts"][-1]
    for broken in names:
        want = ref.mtp_logits_for(core.params, hf, seq, 1, broken)[0]
        d = distance(logits, want)
        main = reference.compare(core.params, hf, prompt, out["ids"],
                                 out["logprobs"], broken=broken,
                                 forward=ref.logits_for)
        say(what=f"breakage[{broken}]", draft_distance_std=d,
            draft_told_apart=d > reference.TOL_STD,
            main_logprob_err_std=main["worst_logprob_err_std"],
            main_argmax_gap_std=main["worst_argmax_gap_std"],
            main_told_apart=not main["ok"])
    say(what="done", ok=ok, seconds=round(time.monotonic() - t0, 1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
