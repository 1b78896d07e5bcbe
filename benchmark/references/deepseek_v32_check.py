#!/usr/bin/env python3
"""Builder's check of a deepseek_v32 configuration's selection, beside the
probe: what the served comparison (``reference.compare`` on logits) cannot
tell apart under random weights, this does.

    chiprun -- python3 benchmark/references/deepseek_v32_check.py            # the configuration, on the chip
    JAX_PLATFORMS=cpu python3 benchmark/references/deepseek_v32_check.py --fixture tiny-deepseek-v32 --tokens 96

It builds the configuration's engine with its deployment flags (no HTTP),
serves one seeded prompt as long as the cell's probe greedily through the
engine's own prefill and decode programs, and records the positions every
query's ``mla._select`` chose, in every layer (a tap on that function: a
host callback that carries the sets out of the compiled programs; the
programs are otherwise the served ones). Then, against
``references/deepseek_v32.py``:

* ``free``: the probe's own comparison, the reference selecting for itself;
* ``forced``: the reference's attention reads the ENGINE's sets, so that
  everything but the selection is compared on equal terms, and per layer
  the share of the engine's keys that the reference's own float32 selection
  (on the same hidden states) picked too: the overlap of the two sets;
* the same overlap for each selection breakage (what a wrong selection
  would read), and each breakage's and control's distance from the engine
  as the probe would see it.

One JSON line per reading (``CHECK {...}``); numbers from a CPU run are not
device numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

SELECTION_BREAKAGES = ("no_selection", "recent_window", "index_no_relu",
                       "index_unweighted", "index_rope_interleaved")


def say(**reading) -> None:
    print("CHECK " + json.dumps(reading), flush=True)


class Tap:
    """Every call of ``mla._select`` inside a compiled program hands its
    positions and validity to the host, in program order."""

    def __init__(self, mla):
        self.mla, self.real, self.calls = mla, mla._select, []

    def __enter__(self):
        import jax
        import numpy as np

        def sink(pos, valid):
            self.calls.append(np.where(np.asarray(valid), np.asarray(pos),
                                       -1).astype(np.int32))

        def tapped(scores, live, topk, slots):
            pos, valid, rows = self.real(scores, live, topk, slots)
            jax.debug.callback(sink, pos, valid, ordered=True)
            return pos, valid, rows

        self.mla._select = tapped
        return self

    def __exit__(self, *exc):
        self.mla._select = self.real

    def take(self, layers: int) -> list:
        """The calls since the last take as [layers, queries, k]: a program
        selects layer by layer, a block of queries at a time."""
        import jax
        import numpy as np
        jax.effects_barrier()
        calls, self.calls = self.calls, []
        per = len(calls) // layers
        return np.stack([np.concatenate(calls[li * per:(li + 1) * per])
                         for li in range(layers)])


def serve(core, tap: Tap, prompt: list, n: int) -> tuple:
    """n greedy tokens through the engine's prefill (in its chunks) and
    decode programs, slot 0. → (ids, logprobs, sets [L, len(prompt)+n-1, k]
    of key positions, -1 = none)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dynamo_tpu.engine.sampling import make_slot_keys
    cfg, L = core.cfg, core.model_cfg.num_layers
    chunk = cfg.prefill_chunk or cfg.bucket_for(len(prompt))
    table = np.zeros((core.M,), np.int32)
    need = -(-(len(prompt) + n) // cfg.kv_block_size)
    table[:need] = np.arange(1, need + 1)
    f32, i32 = jnp.float32, jnp.int32
    sets = []
    for lo in range(0, len(prompt), chunk):
        piece = prompt[lo:lo + chunk]
        padded = np.zeros((chunk,), np.int32)
        padded[:len(piece)] = piece
        tok, lp, core.kv = core._prefill_jit(
            core.params, core.kv, jnp.asarray(padded), jnp.asarray(table),
            jnp.asarray(lo, i32), jnp.asarray(len(piece), i32),
            jax.random.PRNGKey(0), jnp.asarray(0.0, f32), jnp.asarray(0, i32),
            jnp.asarray(1.0, f32))
        sets.append(tap.take(L)[:, :len(piece)])
    ids, lps = [int(tok)], [float(lp)]
    tables = np.zeros((core.B, core.M), np.int32)
    tables[0] = table
    for step in range(n - 1):
        tokens = np.zeros((core.B,), np.int32)
        pos = np.zeros((core.B,), np.int32)
        tokens[0], pos[0] = ids[-1], len(prompt) + step
        keys = make_slot_keys(0, jnp.zeros((core.B,), jnp.int32),
                              jnp.zeros((core.B,), jnp.int32))
        toks, lpb, core.kv = core._decode_jit(
            core.params, core.kv, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(tables), keys, jnp.zeros((core.B,), f32),
            jnp.zeros((core.B,), i32), jnp.ones((core.B,), f32))
        ids.append(int(toks[0]))
        lps.append(float(lpb[0]))
        sets.append(tap.take(L)[:, :1])
    return ids, lps, np.concatenate(sets, axis=1)


def build(config: dict, hf: dict, fixture: bool, seed: int):
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.launch import run as launcher
    cfg = ModelConfig.from_hf_config(hf)
    if fixture:
        ecfg = EngineConfig(max_model_len=256, num_kv_blocks=64,
                            max_num_seqs=2, quantization="int8", seed=seed)
    else:
        ecfg = dataclasses.replace(launcher.engine_config(
            launcher.build_parser().parse_args(
                ["in=http", "out=jax", *config["deployment"]["flags"]])),
            seed=seed)
    return cfg, EngineCore(cfg, ecfg)


def check(core, hf: dict, ref, tap: Tap, prompt: list, others: list,
          variants: tuple = SELECTION_BREAKAGES) -> None:
    """Serves ``prompt`` and prints every reading."""
    import jax
    import numpy as np
    import reference
    import run as bench_run
    K, n = int(hf["index_topk"]), bench_run.PROBE_OUTPUT_TOKENS
    t0 = time.time()
    ids, lps, sets = serve(core, tap, prompt, n)
    say(served=ids, engine_s=round(time.time() - t0, 1),
        sets=list(sets.shape),
        selected_by_last_query=float((sets[:, -1] >= 0).sum(-1).mean()))

    def reading(name, forward):
        t1 = time.time()
        rep = reference.compare(core.params, hf, prompt, ids, lps,
                                forward=forward)
        say(reading=name, logprob_err_std=rep["worst_logprob_err_std"],
            argmax_gap_std=rep["worst_argmax_gap_std"], ok=rep["ok"],
            tol_std=rep["tol_std"], seconds=round(time.time() - t1, 1))

    reading("free", ref.logits_for)
    counts = {}

    def forced(params, hf_, seq, last, broken=None):
        with jax.default_matmul_precision("highest"):
            h, seen = ref.forward(params, hf_, seq, forced=list(sets),
                                  variants=variants)
            counts["seen"] = np.stack(seen)          # [L, 1 + variants, T]
            return np.asarray(reference.head_logits(
                params, hf_, h[-last:], ref.family(hf_)["eps"]), np.float32)

    reading("forced", forced)
    # the overlap where the selection binds: queries with more than K keys
    size = (sets >= 0).sum(-1)                                  # [L, T]
    binds = np.arange(sets.shape[1]) >= K
    if binds.any():
        for vi, name in enumerate(("reference",) + tuple(variants)):
            share = counts["seen"][:, vi][:, binds] / size[:, binds]
            say(overlap_with_engine_pct=name,
                per_layer_mean=[round(100 * float(x), 3)
                                for x in share.mean(-1)],
                per_layer_min=[round(100 * float(x), 3)
                               for x in share.min(-1)],
                served_positions_mean=round(
                    100 * float(share[:, -(n - 1):].mean()), 3),
                queries=int(binds.sum()))
    for name in others:
        if name == "default_matmul_precision":
            reading(name, lambda p, h_, s, last, broken=None: ref.logits_for(
                p, h_, s, last, precision="default"))
        else:
            reading(name, lambda p, h_, s, last, broken=None, b=name:
                    ref.logits_for(p, h_, s, last, b))
    stats = jax.devices()[0].memory_stats() or {}
    say(peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="deepseek-v3.2")
    ap.add_argument("--fixture", help="a fixtures/<name>.json instead (CPU)")
    ap.add_argument("--tokens", type=int, default=16448)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--layers", type=int, help="fixture only: its depth")
    ap.add_argument("--only", help="comma-separated readings beside free "
                    "and forced (default: every breakage and control)")
    opts = ap.parse_args(argv)
    if opts.fixture:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np
    import run as bench_run
    from dynamo_tpu.engine.models import mla
    if opts.fixture:
        with open(os.path.join(BENCH, "fixtures",
                               f"{opts.fixture}.json")) as f:
            config = json.load(f)
    else:
        bench_run.enable_cache()
        config = bench_run.load_config(bench_run.load_benchmark(),
                                       opts.config)
    ref = bench_run.reference_module(config)
    hf = bench_run.hf_config(config)
    if opts.layers:
        hf["num_hidden_layers"] = opts.layers
    say(config=opts.fixture or opts.config, tokens=opts.tokens,
        seed=opts.seed, device=jax.devices()[0].device_kind)
    others = ([b for b in opts.only.split(",") if b]
              if opts.only is not None
              else list(ref.breakages_for(hf)) + list(ref.CONTROLS)
              + ["default_matmul_precision"])
    with Tap(mla) as tap:
        cfg, core = build(config, hf, bool(opts.fixture), opts.seed)
        rng = np.random.default_rng(opts.seed ^ 0x9e0be)
        prompt = rng.integers(0, cfg.vocab_size, size=opts.tokens).tolist()
        check(core, hf, ref, tap, prompt, others)
    return 0


if __name__ == "__main__":
    sys.exit(main())
