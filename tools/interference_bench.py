"""Prefill-interference measurement: the physical quantity behind the
reference's "+30% throughput/GPU from disaggregation" claim
(reference docs/architecture.md:57), measured for THIS hardware.

On a TPU core, programs serialize — a prefill dispatch time-slices the
decode stream rather than contending for execution units the way
co-resident CUDA kernels do. So the disagg win on TPU decomposes into
measurable terms, and this tool measures them all on-chip with the
chained-dispatch slope protocol (utils/timing.py):

  1. t_step(B): decode step time at the serving batch.
  2. t_pf(ISL): one prompt's prefill program time, swept over ISL.
  3. The interleave check: a chain alternating [prefill, K-step decode]
     must cost t_pf + K*t_step (serialization additivity; if it costs
     MORE, there is real cross-dispatch interference — cache/HBM
     residency effects — and the excess is reported).

From these, steady state (every slot serves ISL prefill + GEN decode):
  mixed chip decode tok/s  = B*GEN / (B*t_pf + GEN*t_step)
  split decode chip tok/s  = B / t_step      (prefill moved off-chip)
and the decode-slot STALL a co-located prefill injects (the ITL spike a
user sees) is t_pf itself.

Usage: python tools/interference_bench.py [isl ...]   (default 512 2048 4096)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import EngineConfig, bench_model_config
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.sampling import make_slot_keys
    from dynamo_tpu.utils.timing import slope_per_unit

    isls = [int(a) for a in sys.argv[1:]] or [512, 2048, 4096]
    B = int(os.environ.get("IB_BATCH", "32"))
    GEN = int(os.environ.get("IB_GEN", "256"))
    mcfg = bench_model_config(os.environ.get("IB_MODEL", "1b"))
    max_isl = max(isls)
    bs = 16
    max_len = max_isl + GEN + 64
    bps = (max_len + bs - 1) // bs
    ecfg = EngineConfig(
        max_model_len=max_len, kv_block_size=bs,
        num_kv_blocks=B * bps + (max_isl + bs - 1) // bs + 4,
        max_num_seqs=B,
        prefill_buckets=sorted(set(isls)), decode_steps_per_dispatch=16,
        quantization="int8")
    core = EngineCore(mcfg, ecfg, attn_impl="auto",
                      param_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    K = ecfg.decode_steps_per_dispatch

    # occupy all B slots mid-decode at seq ~= 512 (KV-realistic)
    for i in range(B):
        blocks = core.kv_manager.pool.alloc_uninit(bps)
        table = np.zeros((core.M,), np.int32)
        table[:len(blocks)] = blocks
        core._block_tables[i, :] = table
        core._tokens[i] = 7
        core._positions[i] = 512
    temp = jnp.asarray(np.full((B,), 0.7, np.float32))
    topk = jnp.asarray(np.zeros((B,), np.int32))
    topp = jnp.asarray(np.ones((B,), np.float32))
    seeds = jnp.asarray(np.zeros((B,), np.int64))
    planned, pmask = core._planned_zero
    key = make_slot_keys(0, jnp.asarray([0]), jnp.asarray(0))[0]

    def decode_dispatch(toks_in):
        steps0 = jnp.asarray(np.full((B,), 512, np.int64))
        toks, _lp, core.kv = core._decode_k_jit(
            core.params, core.kv, toks_in,
            jnp.asarray(np.full((B,), 512, np.int32)),
            jnp.array(core._block_tables), seeds, steps0,
            temp, topk, topp, planned, pmask, core._base_key)
        return toks[-1]

    def prefill_dispatch(isl, prompt, table):
        tok, _lp, core.kv = core._prefill_jit(
            core.params, core.kv, prompt, table,
            jnp.asarray(0, jnp.int32), jnp.asarray(isl, jnp.int32),
            key, jnp.asarray(0.7, jnp.float32), jnp.asarray(0, jnp.int32),
            jnp.asarray(1.0, jnp.float32))
        return tok

    t0h = jnp.asarray(core._tokens.copy())

    def chain_decode(m):
        toks = t0h
        t0 = time.monotonic()
        for _ in range(m):
            toks = decode_dispatch(toks)
        np.asarray(toks)
        return time.monotonic() - t0

    # warm + measure decode
    chain_decode(2)
    t_dispatch = slope_per_unit(chain_decode, 4, 16, reps=3)
    t_step = t_dispatch / K

    out = {"B": B, "GEN": GEN, "K": K,
           "t_step_ms": round(t_step * 1e3, 3),
           "decode_only_tok_per_s": round(B / t_step, 1),
           "isl": {}}
    # ONE scratch block run reused by every ISL's prefill probe
    blocks = core.kv_manager.pool.alloc_uninit((max_isl + bs - 1) // bs)
    assert blocks is not None, "scratch blocks"
    table = np.zeros((core.M,), np.int32)
    table[:len(blocks)] = blocks
    table_j = jnp.asarray(table)
    for isl in isls:
        prompt = jnp.asarray(
            rng.integers(1, mcfg.vocab_size, isl).astype(np.int32))

        def chain_pf(m, prompt=prompt, table_j=table_j, isl=isl):
            t0 = time.monotonic()
            tok = None
            for _ in range(m):
                tok = prefill_dispatch(isl, prompt, table_j)
            np.asarray(tok)
            return time.monotonic() - t0

        chain_pf(2)
        t_pf = slope_per_unit(chain_pf, 2, 8, reps=3)

        def chain_mixed(m, prompt=prompt, table_j=table_j, isl=isl):
            toks = t0h
            t0 = time.monotonic()
            for _ in range(m):
                prefill_dispatch(isl, prompt, table_j)
                toks = decode_dispatch(toks)
            np.asarray(toks)
            return time.monotonic() - t0

        chain_mixed(2)
        t_mixed = slope_per_unit(chain_mixed, 2, 8, reps=3)
        excess = t_mixed - (t_pf + t_dispatch)

        mixed_rate = B * GEN / (B * t_pf + GEN * t_step)
        out["isl"][isl] = {
            "t_pf_ms": round(t_pf * 1e3, 2),
            "itl_spike_ms": round(t_pf * 1e3, 2),
            "interleave_excess_ms": round(excess * 1e3, 2),
            "interleave_excess_pct": round(
                100 * excess / (t_pf + t_dispatch), 1),
            "mixed_decode_tok_per_s": round(mixed_rate, 1),
            "split_decode_gain": round((B / t_step) / mixed_rate, 2),
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
