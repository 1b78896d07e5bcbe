"""Serving benchmark — prints ONE JSON line for the driver.

Measures steady-state decode throughput of the continuous-batching engine on
whatever accelerator JAX sees (the driver runs this on one real TPU chip).
Model: Llama-3.2-1B-class shapes, random bf16 weights (weights don't change
the math's cost). The loop includes the real host-side scheduler path
(per-step token fetch + block-table updates), not just raw XLA step time.

Baseline context (BASELINE.md): the north-star target is ≥2000 decode
tok/s/chip for 70B on a v5e-64 pod; `vs_baseline` reports value/2000 so the
driver has a consistent scalar across rounds.

Env knobs: BENCH_BATCH (default 128), BENCH_STEPS (128), BENCH_PROMPT (128),
BENCH_MODEL (1b|tiny|8b|70b_tp8shard|moe|qwen2moe|mla — 8b is Llama-3-8B
geometry, mla is DeepSeek-V2-Lite-class (experts cut 64→8);
random weights; at int8 the weights are ~8 GB of the 16 GB HBM, so pick
BENCH_BATCH/LEN so KV fits: B=64 with default lengths, B=128 with
BENCH_HARVEST<=8; 70b_tp8shard is the per-chip slice of 70B under the
production TP-8 pspecs — its headline is NET of modeled ICI collectives),
BENCH_ATTN (auto|pallas|xla), BENCH_HARVEST (default
32) — decode steps fused per dispatch (EngineConfig.decode_steps_per_dispatch):
sampled tokens chain on device and the host harvests once per dispatch,
amortizing device→host latency. BENCH_PIPELINE (default 1): defer each
dispatch's harvest one dispatch so the device→host copy overlaps the next
dispatch's compute (EngineConfig.decode_dispatch_pipeline); set 0 for the
older harvest-then-dispatch measurement mode.
"""

import json
import os
import statistics
import sys
import time

# Peak specs per device kind for roofline accounting (public TPU specs:
# bf16 MXU TFLOP/s, int8 TOP/s, HBM GB/s). Matched by substring of
# jax.devices()[0].device_kind; a v5e chip reports "TPU v5 lite".
DEVICE_PEAKS = {
    "v5 lite": (197e12, 394e12, 819e9),     # v5e
    "v5litepod": (197e12, 394e12, 819e9),
    "v4": (275e12, 275e12, 1228e9),
    "v5p": (459e12, 918e12, 2765e9),
    "v6 lite": (918e12, 1836e12, 1640e9),   # v6e / Trillium
    "v6e": (918e12, 1836e12, 1640e9),
}


# chain lengths for the device-truth slope (shared so main() can center
# the slope's marginal seq window on the wall loop's)
SLOPE_M1, SLOPE_M2 = 2, 6


def spec_mode_k() -> int:
    """Speculative-decoding bench mode (--spec[=K] or BENCH_SPEC=K):
    0 = off. One parse home for main() and the smoke tests."""
    k = int(os.environ.get("BENCH_SPEC", "0"))
    for a in sys.argv[1:]:
        if a == "--spec":
            k = k or 4
        elif a.startswith("--spec="):
            k = int(a.split("=", 1)[1])
    return k


def pp_mode() -> int:
    """Pipeline-parallel bench mode (--pp[=N] or BENCH_PP=N): 0 = off.
    One parse home for main() and the smoke tests. Measures the
    token-interleaved stage ring's steady-state step."""
    n = int(os.environ.get("BENCH_PP", "0"))
    for a in sys.argv[1:]:
        if a == "--pp":
            n = n or 2
        elif a.startswith("--pp="):
            n = int(a.split("=", 1)[1])
    return n


def run_pp_bench(pp: int) -> dict:
    """Token-interleaved pipeline decode measurement.

    The K-step dispatch (`pp_decode_k_forward`: pp microbatches
    round-robin the stage ring, utilization K·pp/(K·pp+pp-1)) on a
    pp-stage mesh; per-step device time comes from the chained-dispatch
    slope (utils/timing.py — the same protocol as the baseline row, so
    constants and fetch costs cancel).

    Reports the step time, the schedule's analytic utilization/bubble,
    greedy-token equality with the single-device step chain, and the
    modeled DCN boundary economics (parallel/ici_model.pp_step_model)
    for the cross-host deployment the CPU mesh stands in for."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.parallel.ici_model import pp_step_model
    from dynamo_tpu.parallel.pipeline_parallel import (
        make_pp_mesh, place_pp, pp_bubble_fraction, pp_decode_k_forward,
        pp_dispatch_ticks, pp_dispatch_utilization)
    from dynamo_tpu.utils.timing import slope_per_unit

    if len(jax.devices()) < pp:
        return {"skipped": f"pp={pp} needs {pp} devices, have "
                           f"{len(jax.devices())} — dryrun on the CPU "
                           f"mesh (BENCH_FORCE_CPU=1) or a real pod"}

    B = int(os.environ.get("BENCH_PP_BATCH", "8"))
    K = int(os.environ.get("BENCH_PP_HARVEST", "8"))
    # decode at realistic context depth: the interleave pays in
    # ROW-SCALED work (attention/KV reads at depth), while the per-tick
    # weight stream is row-independent (each rank re-reads its L/pp
    # stack per tick regardless of microbatch rows)
    seq0 = int(os.environ.get("BENCH_PP_SEQ", "1024"))
    mcfg = ModelConfig(vocab_size=2048, hidden_size=256,
                       intermediate_size=1024, num_layers=8,
                       num_heads=8, num_kv_heads=4, head_dim=32,
                       max_position_embeddings=4096)
    bs = 16
    blocks_per_seq = (seq0 + K * (SLOPE_M2 + 1) + bs - 1) // bs + 1
    statics = llama.ModelStatics(cfg=mcfg, block_size=bs, attn_impl="xla")
    params = llama.init_params(mcfg, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    kv0 = llama.init_kv_cache(mcfg, B * blocks_per_seq + 2, bs,
                              dtype=jnp.float32)
    mesh = make_pp_mesh(pp)
    pparams, pkv = place_pp(params, kv0, mesh, mcfg)

    rng = np.random.default_rng(0)
    # disjoint per-slot tables, as the engine's allocator guarantees
    tables = jnp.asarray(
        np.arange(1, B * blocks_per_seq + 1, dtype=np.int32).reshape(
            B, blocks_per_seq))
    toks0 = jnp.asarray(rng.integers(1, mcfg.vocab_size, size=B)
                        .astype(np.int32))
    pos0 = seq0
    seeds = jnp.asarray(np.zeros(B, np.int64))
    temp = jnp.zeros((B,), jnp.float32)        # greedy
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    planned = jnp.zeros((K, B), jnp.int32)
    pmask = jnp.zeros((K, B), bool)

    fn_single = jax.jit(llama.decode_forward, static_argnums=5)
    fn_v2 = jax.jit(
        lambda pr, kv, t, p, s0: pp_decode_k_forward(
            pr, kv, t, p, tables, seeds, s0, temp, topk, topp,
            planned, pmask, statics, mesh, K, 0))

    def single_device_tokens(n_steps):
        kv = kv0
        t = toks0
        p = jnp.full((B,), pos0, jnp.int32)
        out = []
        for _ in range(n_steps):
            lg, kv = fn_single(params, kv, t, p, tables, statics)
            t = jnp.argmax(lg, -1).astype(jnp.int32)
            p = p + 1
            out.append(t)
        return np.asarray(jnp.stack(out))

    def v2_tokens(n_dispatch):
        kv = pkv
        t = toks0
        p = jnp.full((B,), pos0, jnp.int32)
        s0 = jnp.zeros((B,), np.int64)
        out = []
        for _ in range(n_dispatch):
            tk, _lp, kv = fn_v2(pparams, kv, t, p, s0)
            t = tk[-1]
            p = p + K
            s0 = s0 + K
            out.append(np.asarray(tk))
        return np.concatenate(out, axis=0)

    # greedy-token equality with the single-device chain (the serving
    # contract the tier-1 tests pin; here it guards the bench itself
    # from timing a diverged program)
    tokens_match = bool(np.array_equal(single_device_tokens(K),
                                       v2_tokens(1)))

    def chain_v2(m):
        kv = pkv
        t = toks0
        p = jnp.full((B,), pos0, jnp.int32)
        s0 = jnp.zeros((B,), np.int64)
        t0 = time.monotonic()
        for _ in range(m):
            tk, _lp, kv = fn_v2(pparams, kv, t, p, s0)
            t = tk[-1]
            p = p + K
            s0 = s0 + K
        np.asarray(t)
        return time.monotonic() - t0

    m1, m2 = SLOPE_M1, SLOPE_M2
    v2_step_s = max(slope_per_unit(chain_v2, m1, m2) / K, 1e-9)
    ticks = pp_dispatch_ticks(pp, K)
    # per-tick device time, for the DCN boundary model: one interleaved
    # dispatch is `ticks` uniform ticks
    tick_s = v2_step_s * K / ticks
    return {
        "pp": pp,
        "batch": B,
        "K": K,
        "seq": seq0,
        "microbatch": B // pp,
        "geometry": {"hidden": mcfg.hidden_size,
                     "layers": mcfg.num_layers,
                     "vocab": mcfg.vocab_size},
        "v2_interleaved_step_ms": round(v2_step_s * 1e3, 3),
        "tokens_match": tokens_match,
        "dispatch_ticks": ticks,
        "utilization_model": round(pp_dispatch_utilization(pp, K), 4),
        "bubble_fraction": round(pp_bubble_fraction(pp, K), 4),
        "per_stage_utilization": [
            round(pp_dispatch_utilization(pp, K), 4)] * pp,
        "device_tick_ms": round(tick_s * 1e3, 3),
        "dcn": pp_step_model(B, mcfg.hidden_size, pp, K, tick_s),
    }


def ragged_mode() -> bool:
    """Unified-ragged-dispatch bench mode (--ragged or BENCH_RAGGED=1):
    mixed-traffic A/B between the split prefill/decode program path and
    the one-program ragged path (ISSUE 10). One parse home for main()
    and the smoke tests."""
    on = os.environ.get("BENCH_RAGGED", "0") != "0"
    return on or any(a == "--ragged" for a in sys.argv[1:])


def run_ragged_bench(mcfg) -> dict:
    """Mixed-traffic A/B: the SAME staggered prompt workload served by
    (a) the split path — per-bucket prefill programs + the batched
    decode program, composed on the host — and (b) the unified ragged
    path, where ONE compiled program carries prefill chunks and decode
    rows together (engine/ragged.py; docs/ragged_attention.md).

    Reported: dispatches issued per emitted token (the batch-boundary
    bubble count), the ragged path's tokens-per-dispatch fill and
    mixed-batch ratios, COMPILED-program counts (jit cache entries
    actually populated — the compile-time + program-HBM footprint), and
    each path's compile wall. Token streams are compared up to each
    request's first numeric boundary (ragged admissions derive the
    first token through the ragged program — the lane-prefill numeric
    contract; every stream is exact past admission by the per-row
    bit-exactness the ragged tests gate)."""
    import asyncio

    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling

    B = int(os.environ.get("BENCH_RAGGED_BATCH", "4"))
    n_req = int(os.environ.get("BENCH_RAGGED_REQUESTS", str(3 * B)))
    p_len = int(os.environ.get("BENCH_RAGGED_PROMPT", "48"))
    max_new = int(os.environ.get("BENCH_RAGGED_NEW", "16"))
    rows = int(os.environ.get("BENCH_RAGGED_SEQ_ROWS", "16"))
    bs = int(os.environ.get("BENCH_RAGGED_KV_BS", "16"))
    max_len = p_len + max_new + 2 * bs
    blocks = B * ((max_len + bs - 1) // bs) + n_req + 2
    base = dict(max_model_len=max_len, kv_block_size=bs,
                num_kv_blocks=blocks, max_num_seqs=B,
                prefill_buckets=sorted({p_len // 2, p_len, max_len}),
                seed=0)

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, mcfg.vocab_size,
                            size=int(l)).tolist()
               for l in rng.integers(p_len // 3, p_len + 1,
                                     size=n_req)]

    async def serve_one(core, prompt, rid):
        req = EngineRequest(rid=rid, prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=max_new, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, payload = await asyncio.wait_for(req.out_queue.get(),
                                                   120)
            if item is FINISH_SENTINEL:
                return toks, req
            toks.append(item)

    async def drive(core, workload):
        # staggered submission: later requests admit while earlier
        # ones decode, so prefill work genuinely contends with decode
        # dispatches (the mixed-traffic shape the ragged batch packs)
        async def delayed(i):
            await asyncio.sleep(0.02 * i)
            return await serve_one(core, workload[i], f"r{i}")
        return await asyncio.gather(*[delayed(i)
                                      for i in range(n_req)])

    def run_path(cfg, workload=None) -> dict:
        core = EngineCore(mcfg, cfg, attn_impl="auto",
                          param_dtype=jnp.bfloat16)

        async def run_all():
            res = await drive(core, workload if workload is not None
                              else prompts)
            await core.stop()
            return res

        t0 = time.monotonic()
        results = asyncio.run(run_all())
        wall_s = time.monotonic() - t0
        kinds = core.flight.stats().get("kinds", {})
        emitted = sum(len(t) for t, _ in results)
        # compiled-program count: jit cache entries actually populated
        # (each prefill bucket shape is its own executable)
        jits = [core._prefill_jit, core._decode_jit, core._decode_k_jit,
                core._verify_jit, core._ragged_jit, core._merge_jit]
        compiled = sum(int(f._cache_size()) for f in jits
                       if f is not None and hasattr(f, "_cache_size"))
        return {
            "core": core,
            "streams": [t for t, _ in results],
            "boundaries": [list(r.numeric_boundaries)
                           for _, r in results],
            "emitted": emitted,
            "wall_s": wall_s,
            "dispatches": (core.ragged_dispatches
                           if cfg.ragged_dispatch else
                           kinds.get("prefill", 0)
                           + kinds.get("decode", 0)),
            "kinds": kinds,
            "compiled_programs": compiled,
        }

    split = run_path(EngineConfig(**base, decode_steps_per_dispatch=1))
    rag = run_path(EngineConfig(**base, ragged_dispatch=True,
                                ragged_max_seq_rows=rows))
    rcore = rag["core"]

    # stream agreement up to each request's first numeric boundary
    # (the lane-admission contract; tests/test_ragged_attention.py
    # gates full exactness against a lane-mode reference)
    exact_to_boundary = True
    for ts, tr, bounds in zip(split["streams"], rag["streams"],
                              rag["boundaries"]):
        bound = min(bounds) if bounds else min(len(ts), len(tr))
        if ts[:bound] != tr[:bound]:
            exact_to_boundary = False
    out = {
        "requests": n_req,
        "emitted_tokens": rag["emitted"],
        "split_dispatches": split["dispatches"],
        "ragged_dispatches": rag["dispatches"],
        "split_dispatches_per_token": round(
            split["dispatches"] / max(split["emitted"], 1), 4),
        "ragged_dispatches_per_token": round(
            rag["dispatches"] / max(rag["emitted"], 1), 4),
        "ragged_fill_ratio": round(
            rcore.ragged_rows_total
            / max(rcore.ragged_dispatches
                  * rcore.cfg.ragged_max_tokens, 1), 4),
        "ragged_mixed_ratio": round(
            rcore.ragged_mixed_dispatches
            / max(rcore.ragged_dispatches, 1), 4),
        "ragged_dispatches_saved": rcore.ragged_dispatches_saved,
        "split_compiled_programs": split["compiled_programs"],
        "ragged_compiled_programs": rag["compiled_programs"],
        "split_wall_s": round(split["wall_s"], 3),
        "ragged_wall_s": round(rag["wall_s"], 3),
        "tokens_exact_to_boundary": exact_to_boundary,
    }
    print(f"# ragged A/B: dispatches {out['split_dispatches']} -> "
          f"{out['ragged_dispatches']}, compiled programs "
          f"{out['split_compiled_programs']} -> "
          f"{out['ragged_compiled_programs']}, fill "
          f"{out['ragged_fill_ratio']}, mixed "
          f"{out['ragged_mixed_ratio']}", file=sys.stderr)

    spec_k = spec_mode_k()
    if spec_k > 0:
        # --ragged --spec combination leg (round 11): the SAME
        # staggered workload — repetitive prompts so the n-gram
        # drafter engages — served by (a) the split SPEC path
        # (per-bucket prefill + decode + the dedicated verify program)
        # and (b) the unified ragged path with spec spans riding the
        # one compiled program. The measured story: dispatches per
        # emitted token, accepted draft tokens per dispatch, compiled
        # programs (must stay 1), and the wave-prefetch hit ratio.
        period = max(2, p_len // 8)
        spec_prompts = []
        for l in rng.integers(p_len // 2, p_len + 1, size=n_req):
            pat = rng.integers(1, mcfg.vocab_size,
                               size=period).tolist()
            spec_prompts.append((pat * (int(l) // period + 1))[:int(l)])
        sp_split = run_path(EngineConfig(**base,
                                         decode_steps_per_dispatch=1,
                                         spec_k=spec_k),
                            workload=spec_prompts)
        sp_rag = run_path(EngineConfig(**base, ragged_dispatch=True,
                                       ragged_max_seq_rows=rows,
                                       spec_k=spec_k),
                          workload=spec_prompts)
        sc, rc = sp_split["core"], sp_rag["core"]
        split_disp = (sp_split["dispatches"]
                      + sp_split["kinds"].get("verify", 0))
        exact = True
        for ts, tr, bounds in zip(sp_split["streams"],
                                  sp_rag["streams"],
                                  sp_rag["boundaries"]):
            bound = min(bounds) if bounds else min(len(ts), len(tr))
            if ts[:bound] != tr[:bound]:
                exact = False
        out["spec"] = {
            "spec_k": spec_k,
            "emitted_tokens": sp_rag["emitted"],
            "split_spec_dispatches": split_disp,
            "ragged_spec_dispatches": sp_rag["dispatches"],
            "split_spec_dispatches_per_token": round(
                split_disp / max(sp_split["emitted"], 1), 4),
            "ragged_spec_dispatches_per_token": round(
                sp_rag["dispatches"] / max(sp_rag["emitted"], 1), 4),
            "split_accepted_per_dispatch": round(
                sc.spec_accepted_tokens / max(split_disp, 1), 4),
            "ragged_accepted_per_dispatch": round(
                rc.spec_accepted_tokens / max(sp_rag["dispatches"], 1),
                4),
            "ragged_spec_rows": rc.ragged_spec_rows,
            "ragged_spec_accepted": rc.spec_accepted_tokens,
            "split_spec_accepted": sc.spec_accepted_tokens,
            "ragged_compiled_programs": sp_rag["compiled_programs"],
            "prefetch_hit_ratio": round(
                rc.ragged_prefetched_waves
                / max(rc.ragged_first_waves, 1), 4),
            "tokens_exact_to_boundary": exact,
        }
        print(f"# ragged --spec leg: dispatches/token "
              f"{out['spec']['split_spec_dispatches_per_token']} -> "
              f"{out['spec']['ragged_spec_dispatches_per_token']}, "
              f"accepted/dispatch "
              f"{out['spec']['split_accepted_per_dispatch']} -> "
              f"{out['spec']['ragged_accepted_per_dispatch']}, "
              f"prefetch hit {out['spec']['prefetch_hit_ratio']}",
              file=sys.stderr)
    return out


def kv_frag_mode() -> bool:
    """Contiguity A/B bench mode (--kv-frag or BENCH_KV_FRAG=1): the
    same decode workload over the run-allocator's contiguous layout vs
    a deliberately fragmented permutation of the SAME blocks (ISSUE 5).
    One parse home for main() and the smoke tests."""
    return (os.environ.get("BENCH_KV_FRAG", "0") != "0"
            or "--kv-frag" in sys.argv[1:])


def run_kv_frag_bench(core, batch, blocks_per_seq, pos0, *,
                      temp, topk, topp, seeds, device_time) -> dict:
    """Measure what physical contiguity buys the decode step. The main
    run's slots already hold the run-allocator's layout (consecutive
    block ids per sequence); the fragmented variant reverses each
    sequence's table row — same blocks, same KV bytes, but descending
    ids can never satisfy the kernel's wave-coalescing predicate
    (attention.wave_contig_table), so every wave degrades to per-block
    DMAs. Reported always: the CPU-side DMA-copy counts the kernel
    issues for each layout (the acceptance gate: coalescing must cut
    issued copies >= 2x on the contiguous pool). On real hardware with
    device timing enabled: the chained-dispatch step-time delta, which
    rides in the same record."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.attention import dma_copy_counts
    from dynamo_tpu.utils.timing import slope_per_unit

    contig = core._block_tables.copy()
    frag = contig.copy()
    frag[:, :blocks_per_seq] = frag[:, :blocks_per_seq][:, ::-1]
    seq_lens = np.full((batch,), pos0 + 1, np.int32)
    kw = dict(block_size=core.cfg.kv_block_size,
              pool_blocks=core.cfg.num_kv_blocks,
              dual_stream=not core.is_mla)
    c_contig = dma_copy_counts(contig, seq_lens, **kw)
    c_frag = dma_copy_counts(frag, seq_lens, **kw)
    res = {
        "seq_len": int(seq_lens[0]),
        "dma_copies_contig": c_contig["copies"],
        "dma_copies_frag": c_frag["copies"],
        "dma_copies_per_wave_contig": round(
            c_contig["copies_per_wave"], 3),
        "dma_copies_per_wave_frag": round(c_frag["copies_per_wave"], 3),
        "coalesced_waves": c_contig["coalesced_waves"],
        "waves": c_contig["waves"],
        "dma_copy_ratio": round(
            c_frag["copies"] / max(c_contig["copies"], 1), 3),
    }
    if device_time and core._decode_k_jit is not None \
            and jax.devices()[0].platform != "cpu":
        K = core.cfg.decode_steps_per_dispatch
        planned, pmask = core._planned_zero

        def chain_for(tables):
            tb = jnp.asarray(tables)

            def chain(m):
                core._positions[:] = pos0
                toks_k = None
                t0 = time.monotonic()
                for _ in range(m):
                    steps0 = jnp.asarray(np.full(
                        (batch,), core._positions[0], np.int64))
                    tokens_in = (jnp.array(core._tokens)
                                 if toks_k is None else toks_k[-1])
                    toks_k, _lps, core.kv = core._decode_k_jit(
                        core.params, core.kv, tokens_in,
                        jnp.array(core._positions), tb, seeds, steps0,
                        temp, topk, topp, planned, pmask, core._base_key)
                    core._positions[:] += K
                np.asarray(toks_k)
                return time.monotonic() - t0

            return max(slope_per_unit(chain, SLOPE_M1, SLOPE_M2) / K,
                       1e-9)

        t_contig = chain_for(contig)
        t_frag = chain_for(frag)
        res.update(
            device_step_ms_contig=round(t_contig * 1e3, 3),
            device_step_ms_frag=round(t_frag * 1e3, 3),
            device_step_speedup=round(t_frag / t_contig, 3))
    return res


def kv_disk_mode() -> bool:
    """Disk-KV-tier bench mode (--kv-disk or BENCH_KV_DISK=1): measures
    warm-restart TTFT vs cold (ISSUE 3). One parse home for main() and
    the smoke tests."""
    return (os.environ.get("BENCH_KV_DISK", "0") != "0"
            or "--kv-disk" in sys.argv[1:])


def run_kv_disk_bench(mcfg) -> dict:
    """Warm-restart TTFT for the persistent disk (G3) KV tier: run one
    request through an engine with host+disk tiers, stop it (graceful
    stop flushes host→disk), then build a FRESH engine pointed at the
    same --kv-disk-dir and serve the same prompt — the prefix onboards
    from disk instead of recomputing. Reports cold vs warm TTFT, the
    disk hit depth, and whether the warm token stream was bit-exact.

    Compile noise control: ONE prefill bucket (every admission compiles
    the same shape) and a throwaway warmup request per engine life, so
    both measured TTFTs are steady-state scheduler+compute, not XLA
    compile time."""
    import asyncio
    import shutil
    import tempfile

    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling

    prompt_len = int(os.environ.get("BENCH_KV_DISK_PROMPT", "96"))
    bs = 16
    blocks = prompt_len // bs
    keep_dir = os.environ.get("BENCH_KV_DISK_DIR")
    disk_dir = keep_dir or tempfile.mkdtemp(prefix="kvdisk-bench-")
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(1, mcfg.vocab_size,
                                           size=prompt_len)]
    warm_prompt = [int(t) for t in rng.integers(1, mcfg.vocab_size,
                                                size=prompt_len)]

    def make_core():
        ecfg = EngineConfig(
            max_model_len=prompt_len + 64, kv_block_size=bs,
            num_kv_blocks=6 * (blocks + 4), max_num_seqs=2,
            prefill_buckets=[prompt_len + 64],
            host_kv_blocks=4 * (blocks + 2),
            kv_disk_dir=disk_dir, kv_disk_blocks=8 * (blocks + 2))
        return EngineCore(mcfg, ecfg, attn_impl="xla",
                          param_dtype=jnp.float32)

    async def serve(core, p, rid):
        req = EngineRequest(rid=rid, prompt=list(p),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=4, eos_ids=frozenset())
        t0 = time.monotonic()
        await core.submit(req)
        ttft = None
        toks = []
        while True:
            item, _ = await req.out_queue.get()  # dynalint: ok DL007 in-process bench harness owns both ends; a timeout would skew measured ITL
            if ttft is None:
                ttft = time.monotonic() - t0
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        return ttft, toks, req.prefix_hit_tokens

    async def run_once():
        core = make_core()
        await serve(core, warm_prompt, "warmup")   # compile + steady state
        ttft, toks, hit = await serve(core, prompt, "measured")
        onboards = core.disk_onboards
        await core.stop()                          # flushes host → disk
        return ttft, toks, hit, onboards, len(core.disk_store)

    try:
        cold_ttft, cold_toks, cold_hit, _, spilled = asyncio.run(run_once())
        warm_ttft, warm_toks, warm_hit, onboards, _ = asyncio.run(run_once())
    finally:
        if not keep_dir:
            shutil.rmtree(disk_dir, ignore_errors=True)
    return {
        "prompt_len": prompt_len,
        "cold_ttft_ms": round(cold_ttft * 1e3, 2),
        "warm_ttft_ms": round(warm_ttft * 1e3, 2),
        "ttft_speedup": round(cold_ttft / max(warm_ttft, 1e-9), 3),
        "cold_hit_tokens": cold_hit,
        "warm_hit_tokens": warm_hit,
        "disk_blocks_after_cold": spilled,
        "warm_restart_onboards": onboards,
        "tokens_bit_exact": cold_toks == warm_toks,
    }


def kv_remote_mode() -> bool:
    """Fleet-KV-fabric bench mode (--kv-remote or BENCH_KV_REMOTE=1):
    cold-prefill vs remote-fetch TTFT A/B over loopback tcp (ISSUE 6).
    One parse home for main() and the smoke tests."""
    return (os.environ.get("BENCH_KV_REMOTE", "0") != "0"
            or "--kv-remote" in sys.argv[1:])


def run_kv_remote_bench(mcfg) -> dict:
    """Remote-fetch TTFT for the fleet KV fabric (llm/kv/fabric.py):
    worker A prefills a prompt and evicts it to disk; worker B (cold)
    recomputes the same prompt; worker C fetches A's prefix over a REAL
    loopback kv_fabric RPC (discovery daemon + bus + tcp dial-back) and
    onboards it. Reports cold vs remote TTFT, bit-exactness of the two
    token streams, and the admission model's PREDICTED fetch/recompute/
    crossover next to the MEASURED ones — the honesty check on the gate
    that decides when a remote hit is worth taking.

    Compile noise control as in run_kv_disk_bench: one prefill bucket +
    a throwaway warmup request per engine life."""
    import asyncio
    import shutil
    import tempfile

    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling
    from dynamo_tpu.llm.kv.fabric import KvFabric
    from dynamo_tpu.runtime.distributed import DistributedRuntime, Endpoint
    from dynamo_tpu.runtime.server import DiscoveryServer

    prompt_len = int(os.environ.get("BENCH_KV_REMOTE_PROMPT", "96"))
    bs = 16
    blocks = prompt_len // bs
    root = tempfile.mkdtemp(prefix="kvremote-bench-")
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(1, mcfg.vocab_size,
                                           size=prompt_len)]
    warm_prompt = [int(t) for t in rng.integers(1, mcfg.vocab_size,
                                                size=prompt_len)]

    def make_core(sub):
        ecfg = EngineConfig(
            max_model_len=prompt_len + 64, kv_block_size=bs,
            num_kv_blocks=6 * (blocks + 4), max_num_seqs=2,
            prefill_buckets=[prompt_len + 64],
            host_kv_blocks=4 * (blocks + 2),
            kv_disk_dir=os.path.join(root, sub),
            kv_disk_blocks=8 * (blocks + 2))
        return EngineCore(mcfg, ecfg, attn_impl="xla",
                          param_dtype=jnp.float32)

    async def serve(core, p, rid):
        req = EngineRequest(rid=rid, prompt=list(p),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=4, eos_ids=frozenset())
        t0 = time.monotonic()
        await core.submit(req)
        ttft = None
        toks = []
        while True:
            item, _ = await req.out_queue.get()  # dynalint: ok DL007 in-process bench harness owns both ends; a timeout would skew measured ITL
            if ttft is None:
                ttft = time.monotonic() - t0
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        return ttft, toks, req.prefix_hit_tokens

    async def run():
        # worker A: seed the prompt's prefix onto disk
        core_a = make_core("a")
        await serve(core_a, warm_prompt, "warmupA")
        await serve(core_a, prompt, "seed")
        await core_a.stop()                # graceful stop flushes → disk

        srv = DiscoveryServer(host="127.0.0.1")
        await srv.start()
        rt_a = rt_c = None
        fab_a = fab_c = None
        try:
            rt_a = await DistributedRuntime.connect(srv.address)
            fab_a = await KvFabric.attach(
                core_a, rt_a,
                Endpoint.parse_path(rt_a, "dyn://bench/worker/generate"))
            wid_a = rt_a.worker_id

            # worker B: cold recompute baseline
            core_b = make_core("b")
            await serve(core_b, warm_prompt, "warmupB")
            cold_ttft, cold_toks, _ = await serve(core_b, prompt, "cold")
            await core_b.stop()

            # worker C: fabric fetch of A's prefix over loopback tcp
            core_c = make_core("c")
            rt_c = await DistributedRuntime.connect(srv.address)
            fab_c = await KvFabric.attach(
                core_c, rt_c,
                Endpoint.parse_path(rt_c, "dyn://bench/worker/generate"))
            await serve(core_c, warm_prompt, "warmupC")
            # the warmup's XLA compile dominates the measured prefill
            # rate; reset and take one steady-state sample so the
            # admission model prices recompute honestly
            core_c.total_prefill_tokens = 0
            steady = [int(t) for t in rng.integers(
                1, mcfg.vocab_size, size=prompt_len)]
            await serve(core_c, steady, "steadyC")
            hashes = [h for h, _t, _p
                      in core_a.disk_store.registered_entries()]
            fab_c.store.note_peer_stored(wid_a, hashes)
            # record what the auto gate WOULD decide on this rig's
            # measured link and prefill rate, then force-admit so the
            # A/B measures the fetch path either way — the predicted-
            # vs-measured crossover below is the model's honesty check
            link = fab_c.links.get(wid_a)
            gate = fab_c.gate
            auto_admit = gate.admit(len(hashes), link)
            gate.mode = "always"
            remote_ttft, remote_toks, remote_hit = await serve(
                core_c, prompt, "remote")
            n_fetched = remote_hit // bs

            # --- dataplane-vs-JSON A/B (ISSUE 12 satellite): the same
            # hash run fetched over the native data plane and over the
            # base64-over-JSON fallback — fetch wall + bytes copied.
            # REPEAT_FETCHES batches several fetches per sample so the
            # systematic JSON overhead (base64 both ways + JSON parse of
            # the bulk payload + 33% more wire bytes) dominates loopback
            # jitter. The legs' samples alternate, so a burst of host
            # load lands on both, and each leg reports its median: with
            # 8 benches at once on 8 cores the medians kept their order
            # in 24 of 24 runs, where min-of-16 flipped in 2 and
            # back-to-back min-of-3 legs in about 1 of 3 even unloaded.
            REPEAT_FETCHES, SAMPLES = 5, 16

            async def time_sample(fetch):
                t0 = time.monotonic()
                for _ in range(REPEAT_FETCHES):
                    blobs = await fetch(wid_a, hashes)
                    if blobs is None:
                        raise RuntimeError(
                            "native dataplane unavailable for the "
                            "kv-remote A/B leg (toolchain missing?)")
                return time.monotonic() - t0, sum(len(b) for b in blobs)

            dp_walls, js_walls = [], []
            for _ in range(SAMPLES):
                wall, dp_bytes = await time_sample(
                    fab_c._fetch_blobs_native)
                dp_walls.append(wall)
                wall, js_bytes = await time_sample(fab_c._fetch_blobs_json)
                js_walls.append(wall)
            dp_ms = statistics.median(dp_walls) * 1e3
            js_ms = statistics.median(js_walls) * 1e3
            predicted_fetch_s = gate.modeled_fetch_s(max(n_fetched, 1),
                                                     link)
            predicted_rec_s = gate.modeled_recompute_s(max(n_fetched, 1))
            predicted_cross = gate.crossover_blocks(link)
            # measured crossover from the measured A/B: per-block gain g
            # includes the amortized RTT, so per-block link gain is
            # g + rtt/n and the depth where RTT is paid back is
            # rtt / (g + rtt/n)
            measured_gain_s = cold_ttft - remote_ttft
            g = measured_gain_s / max(n_fetched, 1)
            per_block_gain = g + link.rtt_s / max(n_fetched, 1)
            measured_cross = (link.rtt_s / per_block_gain
                              if per_block_gain > 0 else float("inf"))
            await core_c.stop()
            return {
                "prompt_len": prompt_len,
                "cold_ttft_ms": round(cold_ttft * 1e3, 2),
                "remote_ttft_ms": round(remote_ttft * 1e3, 2),
                "ttft_speedup": round(cold_ttft / max(remote_ttft, 1e-9),
                                      3),
                "remote_hit_tokens": remote_hit,
                "fetched_blocks": n_fetched,
                "peer_fetches": fab_c.peer_fetches_total,
                "tokens_bit_exact": cold_toks == remote_toks,
                "admission_auto_verdict": ("admit" if auto_admit
                                           else "reject"),
                "measured_link_gbps": round(link.gbps, 4),
                "measured_link_rtt_ms": round(link.rtt_s * 1e3, 3),
                "predicted_fetch_ms": round(predicted_fetch_s * 1e3, 2),
                "predicted_recompute_ms": (
                    None if predicted_rec_s == float("inf")
                    else round(predicted_rec_s * 1e3, 2)),
                "predicted_crossover_blocks": (
                    None if predicted_cross == float("inf")
                    else round(predicted_cross, 2)),
                "measured_crossover_blocks": (
                    None if measured_cross == float("inf")
                    else round(measured_cross, 2)),
                # dataplane A/B leg (x REPEAT_FETCHES per sample)
                "dataplane_fetch_ms": round(dp_ms, 3),
                "json_fetch_ms": round(js_ms, 3),
                "dataplane_bytes": dp_bytes,
                "json_bytes": js_bytes,
                "dataplane_vs_json_speedup": round(
                    js_ms / max(dp_ms, 1e-9), 3),
                "dataplane_fetches_total": fab_c.dataplane_fetches_total,
                "dataplane_fallbacks_total":
                    fab_c.dataplane_fallbacks_total,
            }
        finally:
            for fab in (fab_c, fab_a):
                if fab is not None:
                    await fab.close()
            for rt in (rt_c, rt_a):
                if rt is not None:
                    await rt.shutdown()
            await srv.close()

    try:
        return asyncio.run(run())
    finally:
        shutil.rmtree(root, ignore_errors=True)


def disagg_stream_mode() -> bool:
    """Streaming-handoff bench mode (--disagg-stream or
    BENCH_DISAGG_STREAM=1): streamed vs monolithic P→D KV handoff TTFT
    A/B over real loopback TCP (llm/kv/stream.py). One parse home for
    main() and the smoke tests."""
    return (os.environ.get("BENCH_DISAGG_STREAM", "0") != "0"
            or "--disagg-stream" in sys.argv[1:])


def run_disagg_stream_bench(mcfg) -> dict:
    """Streamed vs monolithic disagg KV handoff TTFT (llm/kv/stream.py):
    two independent decode+prefill engine pairs (same geometry/seed →
    identical weights) serve the same prompts through remote prefill
    over the real TCP wire plane — one pair with per-layer streaming,
    one with the monolithic payload. Reports min-of-N TTFT per leg, the
    MEASURED transfer-hidden time the streaming consumer banked
    (engine-side hidden-work clock), and the overlap model's PREDICTED
    exposed transfer next to it — the honesty check on the pricing the
    router and AdmissionGate use (exposed_transfer_s).

    Compile noise control as in run_kv_remote_bench: one prefill bucket
    + a throwaway warmup request through the FULL disagg path per engine
    pair (compiles the leg's own scatter program)."""
    import asyncio

    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.llm.disagg import (DisaggEngine, DisaggregatedRouter,
                                       PrefillWorker)
    from dynamo_tpu.llm.kv.stream import exposed_transfer_s
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import EngineContext

    prompt_len = int(os.environ.get("BENCH_DISAGG_STREAM_PROMPT", "96"))
    bs = 16
    ITERS = int(os.environ.get("BENCH_DISAGG_STREAM_ITERS", "3"))
    rng = np.random.default_rng(23)

    def make_prompt():
        return [int(t) for t in rng.integers(1, mcfg.vocab_size,
                                             size=prompt_len)]

    def make_core():
        ecfg = EngineConfig(
            max_model_len=prompt_len + 64, kv_block_size=bs,
            num_kv_blocks=6 * (prompt_len // bs + 4), max_num_seqs=2,
            prefill_buckets=[prompt_len + 64])
        return EngineCore(mcfg, ecfg, attn_impl="xla",
                          param_dtype=jnp.float32)

    def make_request(prompt, rid):
        pre = PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True))
        return Context(pre, ctx=EngineContext(rid))

    async def serve_ttft(eng, prompt, rid):
        t0 = time.monotonic()
        stream = await eng.generate(make_request(prompt, rid))
        ttft = None
        toks = []
        async for a in stream:
            if a.data is not None and a.data.token_ids:
                if ttft is None:
                    ttft = time.monotonic() - t0
                toks.extend(a.data.token_ids)
        return ttft, toks

    async def run_leg(layer_stream, prompts):
        rt = DistributedRuntime.in_process()
        core_p, core_d = make_core(), make_core()
        router = DisaggregatedRouter(rt, "bench",
                                     max_local_prefill_length=0,
                                     conditional=False)
        eng = DisaggEngine(core_d, rt, router, device_plane=False,
                           layer_stream=layer_stream)
        worker = await PrefillWorker(core_p, rt).start()
        try:
            leg = "stream" if layer_stream else "mono"
            # warmup through the FULL disagg path: compiles prefill,
            # handoff gather, and this leg's scatter program
            await serve_ttft(eng, make_prompt(), f"warm-{leg}")
            ttfts, tok_runs = [], []
            for i, p in enumerate(prompts):
                ttft, toks = await serve_ttft(eng, p, f"{leg}-{i}")
                ttfts.append(ttft)
                tok_runs.append(toks)
            if eng.remote_failures:
                raise RuntimeError(
                    f"{leg} leg fell back to local prefill "
                    f"({eng.remote_failures}x) — the A/B would compare "
                    f"different paths; refusing to publish")
            return {
                "ttft_ms": min(ttfts) * 1e3,
                "tokens": tok_runs,
                "hidden_s": core_d.disagg_stream_hidden_s,
                "exposed_s": core_d.disagg_stream_exposed_s,
                "stream_admits": core_d.disagg_stream_admits,
                "stream_fallbacks": core_d.disagg_stream_fallbacks,
            }
        finally:
            await worker.stop()
            await core_p.stop()
            await core_d.stop()
            await rt.shutdown()

    async def run():
        prompts = [make_prompt() for _ in range(ITERS)]
        mono = await run_leg(False, prompts)
        streamed = await run_leg(True, prompts)
        # predicted exposed transfer at the measured wire wall: the
        # monolithic leg's full transfer is (hidden + exposed)-free, so
        # model it from the streamed leg's own wall — serial transfer
        # T = hidden + exposed as measured, pipeline depth = layers
        per_admit = max(streamed["stream_admits"], 1)
        t_serial = (streamed["hidden_s"] + streamed["exposed_s"]) \
            / per_admit
        predicted_exposed_s = exposed_transfer_s(
            t_serial, mcfg.num_layers,
            streamed["hidden_s"] / per_admit)
        return {
            "prompt_len": prompt_len,
            "iters": ITERS,
            "layers": mcfg.num_layers,
            "mono_ttft_ms": round(mono["ttft_ms"], 2),
            "stream_ttft_ms": round(streamed["ttft_ms"], 2),
            "ttft_speedup": round(mono["ttft_ms"]
                                  / max(streamed["ttft_ms"], 1e-9), 3),
            "tokens_bit_exact": streamed["tokens"] == mono["tokens"],
            "stream_admits": streamed["stream_admits"],
            "stream_fallbacks": streamed["stream_fallbacks"],
            "transfer_hidden_ms": round(
                streamed["hidden_s"] / per_admit * 1e3, 3),
            "transfer_exposed_ms": round(
                streamed["exposed_s"] / per_admit * 1e3, 3),
            "predicted_exposed_ms": round(predicted_exposed_s * 1e3, 3),
        }

    return asyncio.run(run())


def run_spec_bench(core, batch, prompt_len, prompts, spec_k,
                   n_dispatch, device_time) -> dict:
    """Speculative serving measurement (ISSUE 2 satellite): drive the
    engine's REAL verify dispatch (`core._verify_jit` — the [B, k+1]
    flattened paged scorer) with the prompt-lookup drafter over each
    slot's live history, greedy sampling. Reports measured acceptance and
    the effective tok/s (= emitted tokens / wall time: a verify dispatch
    emits 1..k+1 tokens per slot for ~one batched step's weight read),
    plus the device-truth verify-step slope under the same protocol as
    the baseline row (utils/timing.py)."""
    import numpy as np
    import jax.numpy as jnp

    from dynamo_tpu.engine.spec import PromptLookupDrafter, accept_lockstep
    from dynamo_tpu.utils.timing import slope_per_unit

    Tv = spec_k + 1
    drafter = PromptLookupDrafter()
    # reset the decode front to the prompt end: verify rows rewrite each
    # position before any same-or-later row attends it, so the stale
    # baseline KV beyond the front is never read (engine rollback rule)
    pos = np.full((batch,), prompt_len, np.int32)
    hist = [list(map(int, prompts[i])) + [int(core._tokens[i])]
            for i in range(batch)]
    temp0 = jnp.zeros((batch,), jnp.float32)
    topk0 = jnp.zeros((batch,), jnp.int32)
    topp1 = jnp.ones((batch,), jnp.float32)
    seeds = jnp.asarray(np.zeros((batch,), np.int64))

    def dispatch(tokens, positions):
        toks_T, _lps, core.kv = core._verify_jit(
            core.params, core.kv, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(core._block_tables),
            seeds, jnp.asarray(positions.astype(np.int64)),
            temp0, topk0, topp1)
        return toks_T

    tokens = np.zeros((batch, Tv), np.int32)
    for i in range(batch):
        tokens[i, 0] = hist[i][-1]
    np.asarray(dispatch(tokens, pos))          # compile dispatch

    emitted = drafted = accepted = 0
    t0 = time.monotonic()
    for _ in range(n_dispatch):
        tokens = np.zeros((batch, Tv), np.int32)
        dlists = []
        for i in range(batch):
            d = drafter.draft(hist[i], spec_k)
            dlists.append(d)
            tokens[i, 0] = hist[i][-1]
            if d:
                tokens[i, 1:1 + len(d)] = d
        out = np.asarray(dispatch(tokens, pos))   # ONE fetch per dispatch
        for i in range(batch):
            m, em = accept_lockstep(dlists[i], out[i])
            hist[i].extend(em)
            pos[i] += m + 1
            emitted += m + 1
            drafted += len(dlists[i])
            accepted += m
    dt = time.monotonic() - t0
    res = {
        "k": spec_k,
        "sampling": "greedy",
        "workload": "tiled-8 repetitive prompts (drafter best case)",
        "drafted": drafted,
        "accepted": accepted,
        "acceptance_rate": round(accepted / drafted, 4) if drafted else 0.0,
        "accepted_per_step": round(accepted / (n_dispatch * batch), 3),
        "emitted_per_step": round(emitted / (n_dispatch * batch), 3),
        "effective_tok_per_s": round(emitted / dt, 1),
    }
    if device_time:
        def chain(m):
            p = np.full((batch,), prompt_len, np.int32)
            toks = None
            tc = time.monotonic()
            for _ in range(m):
                toks = dispatch(tokens, p)
                p += Tv
            np.asarray(toks)                   # the one barrier fetch
            return time.monotonic() - tc

        step_s = max(slope_per_unit(chain, SLOPE_M1, SLOPE_M2), 1e-9)
        res["device_verify_step_ms"] = round(step_s * 1e3, 3)
        # effective ceiling: measured emitted-per-dispatch over the
        # device-truth verify step time
        res["effective_device_tok_per_s"] = round(
            emitted / n_dispatch / step_s, 1)
    return res


def _device_peaks(device_kind: str):
    dk = device_kind.lower()
    for key, peaks in DEVICE_PEAKS.items():
        if key in dk:
            return peaks
    raise ValueError(
        f"no peak specs for device kind {device_kind!r}: add it to "
        f"DEVICE_PEAKS with its source (known: {sorted(DEVICE_PEAKS)})")


def _param_bytes(params) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(params))


def _matmul_flops_per_token(mcfg) -> float:
    """2·(matmul weight count) per token: qkv + wo + mlp per layer, + lm
    head. Embedding lookup is free; attention score/update flops are
    accounted separately (they scale with seq len). MoE geometries are
    counted dense over the experts — ALL E experts per token, which is
    what a decode step executes (engine moe_mlp, run_experts_dense) —
    plus any shared expert (earlier MoE history lines understated
    this). A single-device prefill of llama.GROUPED_MIN_ROWS rows or
    more executes only the routed top-k of them (run_experts_grouped):
    a prefill figure from this count overstates what the chip did there.
    Hybrid deepseek sparsity: the first_k_dense prefix runs its own
    dense MLP; MLA attention counts the latent projections plus the
    ABSORBED per-token wkv_b contractions (models/mla.py decode)."""
    D, F = mcfg.hidden_size, mcfg.intermediate_size
    H, KVH, Dh = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim
    L = mcfg.num_layers
    k_dense = getattr(mcfg, "first_k_dense", 0)
    if getattr(mcfg, "num_experts", 0) > 0:
        moe = (mcfg.num_experts * 3 * D * F
               + 3 * D * getattr(mcfg, "shared_expert_size", 0)
               + D * mcfg.num_experts)          # router
        dense_f = getattr(mcfg, "dense_intermediate_size", 0) or F
        mlp_total = (L - k_dense) * moe + k_dense * 3 * D * dense_f
    else:
        mlp_total = L * 3 * D * F
    rank = getattr(mcfg, "kv_lora_rank", 0)
    if rank > 0:
        dn = mcfg.qk_nope_head_dim
        dr = mcfg.qk_rope_head_dim
        dv = mcfg.v_head_dim
        ql = getattr(mcfg, "q_lora_rank", 0)
        q = (D * ql + ql * H * (dn + dr)) if ql else D * H * (dn + dr)
        attn = (q + D * (rank + dr)               # wkv_a
                + H * rank * (dn + dv)            # absorbed wkv_b
                + H * dv * D)                     # wo
    else:
        attn = D * (H + 2 * KVH) * Dh + H * Dh * D
    return 2.0 * (L * attn + mlp_total + D * mcfg.vocab_size)


def _attn_seq_flops_per_token(mcfg) -> float:
    """Attention score+update flops per token PER CACHED POSITION across
    all layers (multiplied by avg seq len by the callers). llama: q·k
    and p·v over H heads of Dh. MLA absorbed decode: scores contract
    (rank+dr) lanes and the update contracts rank lanes per head."""
    rank = getattr(mcfg, "kv_lora_rank", 0)
    if rank > 0:
        dr = mcfg.qk_rope_head_dim
        return (2.0 * mcfg.num_heads * (2 * rank + dr)
                * mcfg.num_layers)
    return 4.0 * mcfg.num_heads * mcfg.head_dim * mcfg.num_layers


def device_timing(core, mcfg, batch, pos0, *,
                  temp, topk, topp, seeds):
    """Per-step DEVICE time for the real fused-K decode dispatch, via the
    chained-dispatch slope method (utils/timing.py): time m1 vs m2
    chained dispatches, each ending in ONE token fetch as the barrier; the
    difference cancels the fetch and every constant overhead. Returns a
    dict of device-truth metrics.

    `pos0` anchors the sequence window: positions are RESET to pos0 before
    every chain so each chain covers [pos0, pos0 + m*K]. Round-3's bug
    (VERDICT r3 weak #1): positions were left to grow monotonically across
    chains, so the slope timed attention at seq ~288→1050 while the wall
    loop ran at avg ~224 — for KV-dominated geometries (1B at B=128) that
    overstated device step time by ~50% and made wall "exceed" the device
    ceiling. The marginal dispatches m1..m2 now run at positions
    pos0+m1·K .. pos0+m2·K; their midpoint is reported as
    `device_avg_seq` and used for the KV-traffic roofline terms."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.utils.timing import slope_per_unit

    K = core.cfg.decode_steps_per_dispatch
    planned, pmask = core._planned_zero
    m1, m2 = SLOPE_M1, SLOPE_M2
    avg_seq_len = pos0 + K * (m1 + m2) // 2

    def chain(m):
        core._positions[:] = pos0
        toks_k = None
        t0 = time.monotonic()
        for _ in range(m):
            steps0 = jnp.asarray(np.full((batch,), core._positions[0],
                                         np.int64))
            tokens_in = (jnp.array(core._tokens) if toks_k is None
                         else toks_k[-1])
            toks_k, _lps, core.kv = core._decode_k_jit(
                core.params, core.kv,
                tokens_in, jnp.array(core._positions),
                jnp.array(core._block_tables), seeds, steps0,
                temp, topk, topp, planned, pmask, core._base_key)
            core._positions[:] += K
        np.asarray(toks_k)                 # the one barrier fetch
        return time.monotonic() - t0

    step_s = max(slope_per_unit(chain, m1, m2) / K, 1e-9)

    res = {
        "device_step_ms": round(step_s * 1e3, 3),
        "device_tok_per_s": round(batch / step_s, 1),
        "device_avg_seq": int(avg_seq_len),
    }
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # forced-CPU smoke: the chain above proves the protocol runs;
        # a share of a TPU's peak is never computed from a CPU timing
        return res
    peak_bf16, _peak_int8, peak_hbm = _device_peaks(dev.device_kind)
    pbytes = _param_bytes(core.params)
    # bytes per token across all layers, straight from the pool arrays —
    # covers int8 pools (and their scale arrays) without dtype special
    # cases
    ntok = next(iter(core.kv.values())).shape[1]
    kv_bytes = (batch * avg_seq_len
                * sum(a.nbytes for a in core.kv.values()) / ntok)
    # weight-only int8 dequantizes into bf16 MXU matmuls → bf16 peak
    flops = batch * (_matmul_flops_per_token(mcfg)
                     + _attn_seq_flops_per_token(mcfg) * avg_seq_len)
    res.update({
        "weights_gb": round(pbytes / 1e9, 3),
        # weight reads alone vs HBM peak: the decode roofline at small B
        "weights_read_bw_util": round(pbytes / step_s / peak_hbm, 3),
        # all modeled HBM traffic (weights + KV reads) vs peak
        "hbm_util": round((pbytes + kv_bytes) / step_s / peak_hbm, 3),
        "mfu": round(flops / step_s / peak_bf16, 4),
    })
    return res


def device_prefill_timing(core, prompt_len, prefill_args_walk):
    """Device time per whole-prompt prefill via the same chained-dispatch
    slope (prefill_jit donates+returns kv, so dispatches chain on device
    with no host sync until the final token fetch).

    ``prefill_args_walk`` is the FULL list of per-chunk dispatch args for
    one prompt (one entry when chunking is off). One slope unit = the
    whole chunk walk, normalized by prompt_len — timing only the padded
    final chunk wildly distorts the metric when prompt_len % C is small
    (ADVICE r5)."""
    import numpy as np

    from dynamo_tpu.utils.timing import slope_per_unit

    def chain(m):
        tok = None
        t0 = time.monotonic()
        for _ in range(m):
            for args in prefill_args_walk:
                tok, _lp, core.kv = core._prefill_jit(
                    core.params, core.kv, *args)
        np.asarray(tok)
        return time.monotonic() - t0

    # deep chains: the first dispatch after an idle gap carries start-up
    # cost that short chains do not amortize
    per_prefill_s = max(slope_per_unit(chain, 4, 12), 1e-9)
    return {
        "device_prefill_ms": round(per_prefill_s * 1e3, 2),
        "device_prefill_tok_per_s": round(prompt_len / per_prefill_s, 1),
        "device_prefill_chunks": len(prefill_args_walk),
    }


def _metric_name(model: str, batch: int, quant: str,
                 kv_quant: str) -> str:
    """The ONE metric-name rule. The 70b_tp8shard gate metric keeps its
    fixed judge-facing name for the default int8 config; any other
    quantization suffixes it — an int4 or int8-KV run must NOT post to
    the int8 gate history."""
    # qwen2moe / mla model names already carry their family — no prefix
    family = {"moe": "mixtral_", "qwen2moe": "",
              "mla": "deepseek_", "tiny_mla": "deepseek_"}.get(
                  model, "llama")
    name = (f"decode_tok_per_s_chip_{family}{model}_b{batch}"
            + ("" if quant == "none" else f"_{quant}")
            + ("" if kv_quant == "none" else "_kv8"))
    if model == "70b_tp8shard":
        name = ("decode_tok_per_s_chip_llama70b_tp8shard"
                + ("" if quant == "int8" else f"_{quant}")
                + ("" if kv_quant == "none" else "_kv8"))
    return name


def main() -> None:
    # BENCH_FORCE_CPU=1: hermetic CPU run (smoke tests) — counts and
    # correctness only; every other run needs a TPU and says so below.
    force_cpu = os.environ.get("BENCH_FORCE_CPU", "0") != "0"
    if force_cpu:
        import sys as _sys
        _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from __graft_entry__ import force_cpu_devices
        # --pp needs a virtual multi-device mesh (the 8-device dryrun
        # precedent, tests/conftest.py); plain runs keep 1 device
        force_cpu_devices(max(1, pp_mode()))

    import numpy as np
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    if not force_cpu and platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU and JAX found platform {platform!r}; "
            "no CPU number is reported as a device metric "
            "(BENCH_FORCE_CPU=1 is the smoke-test mode)")

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import make_slot_keys
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "128"))
    prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
    # default = the BASELINE config-4 north-star configuration (70B TP-8
    # per-chip shard, headline net of modeled ICI) — the number the judge
    # gates on. BENCH_MODEL=1b for the small-model serving headline.
    model = os.environ.get("BENCH_MODEL", "70b_tp8shard")
    attn = os.environ.get("BENCH_ATTN", "auto")
    harvest = int(os.environ.get("BENCH_HARVEST", "32"))
    pipeline = os.environ.get("BENCH_PIPELINE", "1") != "0"
    # int8 weight-only is the default: the reference's headline numbers are
    # FP8-quantized serving (R1-Distill-Llama-70B FP8), so quantized is the
    # comparable configuration; BENCH_QUANT=none for full-precision runs
    quant = os.environ.get("BENCH_QUANT", "int8")
    # KV-cache quantization (none|int8): halves the decode KV read
    # stream — the dominant HBM term at long seq (PERF.md long-context)
    kv_quant = os.environ.get("BENCH_KV_QUANT", "none")
    # device-side slope timing (adds ~9 extra chained dispatches)
    device_time = os.environ.get("BENCH_DEVICE", "1") != "0"
    # speculative decoding mode (--spec[=K] / BENCH_SPEC): measure the
    # verify-dispatch path next to the baseline row
    spec_k = spec_mode_k()

    # geometry table shared with tools/decode_profile.py — ONE home
    # (dynamo_tpu/engine/config.py bench_model_config). 8b anchors the
    # 70B TP-8 extrapolation (BASELINE.md config 2); moe times the
    # dense-over-experts int8 einsum path serving mixtral/qwen3-moe.
    from dynamo_tpu.engine.config import bench_model_config
    mcfg = bench_model_config(model)
    # budget: the wall loop's last position (compile dispatch + n_dispatch
    # timed dispatches) and the device-timing slope window (positions reset
    # to pos0 per chain, reaching pos0 + M2·K — when pos0 clamps to 0 the
    # slope window can extend PAST the wall end, so take the max of both)
    n_dispatch = max(steps // harvest, 1)
    wall_end = prompt_len + (n_dispatch + 1) * harvest
    wall_avg = prompt_len + harvest * (n_dispatch + 2) / 2.0
    pos0 = max(int(wall_avg) - harvest * (SLOPE_M1 + SLOPE_M2) // 2, 0)
    slope_end = pos0 + SLOPE_M2 * harvest
    # spec mode restarts the decode front at prompt_len and advances up
    # to k+1 positions per dispatch (acceptance loop + slope chains)
    spec_end = (prompt_len + (max(n_dispatch + 1, SLOPE_M2) + 1)
                * (spec_k + 1)) if spec_k > 0 else 0
    max_len = max(wall_end, slope_end if device_time else 0,
                  spec_end) + 64
    # int8 pools need 32-token blocks (int8 sublane tile; attention.py
    # pallas_supported). Small-C geometries (the 70B TP-8 shard's 1 kv
    # head, C=128) are DMA-latency-bound at 16 — a 64-token block
    # quadruples the per-DMA payload (round-5 probe: kernel 132 → 81
    # us/call, device step 29.3 → 22.8 ms at the gate config), so the
    # gate geometry defaults to 64. BENCH_KV_BS overrides either way.
    small_c = mcfg.num_kv_heads * mcfg.head_dim <= 128
    default_bs = "64" if small_c else ("32" if kv_quant == "int8" else "16")
    bs = int(os.environ.get("BENCH_KV_BS", default_bs))
    prefill_chunk = int(os.environ.get("BENCH_PREFILL_CHUNK", "0"))
    blocks_per_seq = (max_len + bs - 1) // bs
    ecfg = EngineConfig(
        max_model_len=max_len, kv_block_size=bs,
        num_kv_blocks=batch * blocks_per_seq + 2, max_num_seqs=batch,
        prefill_buckets=sorted({prompt_len, max_len,
                                prefill_chunk or prompt_len}),
        # long-context MoE prefill: dense-over-E expert activations at
        # whole-prompt N OOM the chip (measured: MLA 12K B=16 needs
        # 16.0 of 15.75 GB) — BENCH_PREFILL_CHUNK routes the prompt
        # through the engine's chunked-prefill path instead
        prefill_chunk=prefill_chunk,
        decode_steps_per_dispatch=harvest, quantization=quant,
        kv_quantization=kv_quant, spec_k=spec_k)

    dev = jax.devices()[0]
    print(f"# bench on {dev.platform}:{dev.device_kind} model={model} "
          f"B={batch} steps={steps} prompt={prompt_len} attn={attn}",
          file=sys.stderr)

    core = EngineCore(mcfg, ecfg, attn_impl=attn, param_dtype=jnp.bfloat16)

    rng = np.random.default_rng(0)
    statics = core.statics

    # --- manual slot setup (bypass asyncio; measure the step loop itself)
    prompts = rng.integers(1, mcfg.vocab_size, size=(batch, prompt_len))
    if spec_k > 0:
        # repetition-friendly prompts (tiled 8-token patterns): the
        # prompt-lookup drafter needs n-gram repeats to propose anything;
        # decode COST is content-independent, so the baseline row is
        # unaffected — the spec sub-dict labels the workload
        pat = rng.integers(1, mcfg.vocab_size, size=(batch, 8))
        prompts = np.tile(pat, (1, (prompt_len + 7) // 8))[:, :prompt_len]
    warmed = False
    t_prefill0 = time.monotonic()
    for i in range(batch):
        blocks = core.kv_manager.pool.alloc_uninit(blocks_per_seq)
        table = np.zeros((core.M,), np.int32)
        table[:len(blocks)] = blocks
        core._block_tables[i, :] = table
        key = make_slot_keys(0, jnp.asarray([0]), jnp.asarray(0))[0]
        # chunked prompt walk when BENCH_PREFILL_CHUNK is set (the
        # engine's _chunked_prefill shape: fixed C-token dispatches
        # continuing at start_pos) — long-context MoE prefill OOMs
        # whole-prompt (see ecfg comment)
        C = ecfg.prefill_chunk or prompt_len
        prefill_args_walk = []
        for lo in range(0, prompt_len, C):
            piece = prompts[i][lo:lo + C]
            padded = np.zeros((C,), np.int32)
            padded[:len(piece)] = piece
            args = (
                jnp.asarray(padded), jnp.asarray(table),
                jnp.asarray(lo, jnp.int32),
                jnp.asarray(len(piece), jnp.int32),
                key, jnp.asarray(0.7, jnp.float32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(1.0, jnp.float32))
            prefill_args_walk.append(args)
            tok, lp, core.kv = core._prefill_jit(
                core.params, core.kv, *args)
        core._tokens[i] = int(tok)
        core._positions[i] = prompt_len
        if not warmed:
            # first call paid XLA compilation; time steady-state prefill
            warmed = True
            t_prefill0 = time.monotonic()
    jax.block_until_ready(next(iter(core.kv.values())))
    prefill_s = time.monotonic() - t_prefill0
    prefill_batch = max(batch - 1, 1)   # first (compile) prefill untimed

    # --- timed decode loop (host loop included, as in real serving):
    # K steps per dispatch, one [K, B] token harvest per dispatch — the
    # engine's _decode_step_multi shape
    temp = jnp.asarray(np.full((batch,), 0.7, np.float32))
    topk = jnp.asarray(np.zeros((batch,), np.int32))
    topp = jnp.asarray(np.ones((batch,), np.float32))
    seeds = jnp.asarray(np.zeros((batch,), np.int64))

    pending = None
    chain = None        # device [B] last-token array from the prior dispatch

    def dispatch_once(step_i):
        nonlocal pending, chain
        if harvest > 1:
            steps0 = jnp.asarray(np.full((batch,), step_i, np.int64))
            # jnp.array copies — the host mirrors are mutated while a
            # pipelined dispatch may still be executing
            tokens_in = (chain if pipeline and chain is not None
                         else jnp.array(core._tokens))
            planned, pmask = core._planned_zero  # no lane-prefill in bench
            toks_k, _lps, core.kv = core._decode_k_jit(
                core.params, core.kv,
                tokens_in, jnp.array(core._positions),
                jnp.array(core._block_tables), seeds, steps0,
                temp, topk, topp, planned, pmask, core._base_key)
            core._positions[:] += harvest
            if pipeline:
                # chain the next dispatch off device tokens; harvest the
                # PREVIOUS batch while this one computes (the engine's
                # decode_dispatch_pipeline shape)
                chain = toks_k[-1]
                prev, pending = pending, toks_k
                if prev is not None:
                    harvested = np.asarray(prev)
                    core._tokens[:] = harvested[-1]
                    return harvested
                return None
            toks_k = np.asarray(toks_k)  # ONE host fetch per K tokens
            core._tokens[:] = toks_k[-1]
            return toks_k
        keys = make_slot_keys(0, seeds,
                              jnp.asarray(np.full((batch,), step_i,
                                                  np.int64)))
        toks, _lps, core.kv = core._decode_jit(
            core.params, core.kv,
            jnp.asarray(core._tokens), jnp.asarray(core._positions),
            jnp.asarray(core._block_tables), keys, temp, topk, topp)
        toks = np.asarray(toks)  # host fetch, like the real loop
        core._tokens[:] = toks
        core._positions[:] += 1
        return toks

    dispatch_once(0)  # compile
    if pipeline and harvest > 1 and pending is not None:
        np.asarray(pending)  # settle the warmup dispatch outside the timer
        pending = None
    t0 = time.monotonic()
    for s in range(1, n_dispatch + 1):
        out = dispatch_once(s * harvest)
        if pipeline and harvest > 1 and s > 1:
            assert out is not None           # steady state harvests s-1
    if pipeline and harvest > 1 and pending is not None:
        np.asarray(pending)                  # drain the last batch
        pending = None
    dt = time.monotonic() - t0
    steps = n_dispatch * harvest  # actual tokens per slot timed

    tok_per_s = batch * steps / dt

    device_extra = {}
    if device_time and core._decode_k_jit is not None:
        # pos0 (computed with max_len above) centers the slope's marginal
        # seq window on the wall loop's average position, so both time the
        # same KV working set (VERDICT r3 weak #1 — the old code let
        # positions drift, which overstated device step time for
        # KV-dominated geometries)
        device_extra.update(device_timing(
            core, mcfg, batch, pos0,
            temp=temp, topk=topk, topp=topp, seeds=seeds))
        device_extra.update(device_prefill_timing(
            core, prompt_len, prefill_args_walk))

    spec_res = None
    if spec_k > 0:
        # after the baseline + device timing so their numbers are settled
        # before the spec loop rewrites the decode front
        spec_res = run_spec_bench(core, batch, prompt_len, prompts,
                                  spec_k, n_dispatch, device_time)

    kv_disk_res = None
    if kv_disk_mode():
        # independent small engine pair (same model geometry, same seed
        # → identical weights): cold serve + graceful stop (flush), then
        # a fresh engine warm-starting from the same disk dir
        kv_disk_res = run_kv_disk_bench(mcfg)

    kv_remote_res = None
    if kv_remote_mode():
        # independent three-engine loopback setup (seed → cold → fetch):
        # the fabric A/B plus the admission model's predicted-vs-
        # measured crossover honesty check
        kv_remote_res = run_kv_remote_bench(mcfg)

    disagg_stream_res = None
    if disagg_stream_mode():
        # independent two-pair loopback setup (streamed vs monolithic
        # P→D handoff over real TCP): min-of-N TTFT A/B + the measured
        # transfer-hidden time vs the overlap model's prediction
        disagg_stream_res = run_disagg_stream_bench(mcfg)

    kv_frag_res = None
    if kv_frag_mode():
        # after the baseline/device rows (the frag leg rewrites block
        # tables and positions); the contiguous leg IS the layout the
        # run-tracking allocator gave the main run's slots
        kv_frag_res = run_kv_frag_bench(
            core, batch, blocks_per_seq, pos0, temp=temp, topk=topk,
            topp=topp, seeds=seeds, device_time=device_time)

    pp_res = None
    if pp_mode() > 0:
        # independent small pp-mesh setup (its own geometry — the
        # baseline row above is untouched): the interleaved
        # steady-state step time + the modeled DCN story
        pp_res = run_pp_bench(pp_mode())

    ragged_res = None
    if ragged_mode():
        # independent two-engine A/B (same geometry/seed → identical
        # weights): the split prefill/decode program path vs the
        # unified ragged dispatch over one staggered mixed workload
        ragged_res = run_ragged_bench(mcfg)

    # device truth is the headline number; the wall loop (host scheduler
    # + token fetches) rides along in extra. The wall throughput can
    # never exceed the per-step device ceiling when both time the same
    # program over the same seq window — if it does, the accounting is
    # broken and the bench must fail LOUDLY rather than publish it.
    wall_tok_per_s = tok_per_s
    device_tok = device_extra.get("device_tok_per_s")
    if device_tok:
        if (dev.platform != "cpu"
                and wall_tok_per_s > 1.10 * device_tok):
            raise RuntimeError(
                f"accounting error: wall {wall_tok_per_s:.0f} tok/s "
                f"exceeds the device ceiling {device_tok:.0f} tok/s "
                f"(device_step_ms={device_extra.get('device_step_ms')}, "
                f"avg_seq={device_extra.get('device_avg_seq')}) by >10% "
                f"— the two must time the same program over the same "
                f"seq window; refusing to publish")
        headline = min(wall_tok_per_s, device_tok)
    else:
        headline = wall_tok_per_s

    ici_extra = {}
    if model == "70b_tp8shard":
        # the per-chip-shard geometry measures compute+HBM only; the
        # headline must be NET of the modeled per-layer TP-8 ICI
        # collectives (parallel/ici_model.py books the full serial cost)
        from dynamo_tpu.parallel.ici_model import (tp_decode_step_s,
                                                   tp_decode_sensitivity)
        ici_s = tp_decode_step_s(batch, mcfg.hidden_size,
                                 mcfg.num_layers, 8)
        sens = tp_decode_sensitivity(batch, mcfg.hidden_size,
                                     mcfg.num_layers, 8, headline)
        net = sens["nominal"]
        ici_extra = {
            "ici_step_ms": round(ici_s * 1e3, 3),
            "per_chip_tok_per_s_no_ici": round(headline, 1),
            "ici_model": "2 psums/layer + embed psum, [B,8192] bf16, "
                         "TP-8 @ 100 GB/s effective + 5us/collective",
            "ici_sensitivity": sens["band"],
            "ici_worst_corner_tok_per_s": sens["worst"],
        }
        headline = net

    metric = _metric_name(model, batch, quant, kv_quant)
    result = {
        "metric": metric,
        "value": round(headline, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(headline / 2000.0, 3),
        "extra": {
            "platform": dev.platform,
            "wall_tok_per_s": round(wall_tok_per_s, 1),
            "step_ms": round(1e3 * dt / steps, 2),
            "prefill_s_total": round(prefill_s, 2),
            "prefill_tok_per_s": round(
                prefill_batch * prompt_len / prefill_s, 1),
            "attn_impl": attn,
            "steps_per_dispatch": harvest,
            "pipelined": pipeline,
            **device_extra,
            **ici_extra,
        },
    }
    if spec_res is not None:
        # spec provenance rides the record of this run: acceptance +
        # effective tok/s next to the baseline row
        result["spec"] = spec_res
    if kv_disk_res is not None:
        # disk (G3) tier provenance: warm-restart TTFT vs cold
        result["kv_disk"] = kv_disk_res
    if kv_remote_res is not None:
        # fleet-fabric (G4) provenance: remote-fetch TTFT vs cold +
        # predicted/measured admission crossover
        result["kv_remote"] = kv_remote_res
    if disagg_stream_res is not None:
        # streaming-handoff provenance: streamed vs monolithic TTFT,
        # measured transfer-hidden-ms next to the predicted exposed
        # transfer (ISSUE 18)
        result["disagg_stream"] = disagg_stream_res
    if kv_frag_res is not None:
        # contiguity provenance: DMA-copy counts (always) + device
        # step-time A/B per layout
        result["kv_frag"] = kv_frag_res
    if pp_res is not None:
        # pipeline-parallel provenance: interleaved step time,
        # per-stage utilization, modeled DCN boundary economics
        result["pp"] = pp_res
    if ragged_res is not None:
        # unified-ragged-dispatch provenance: dispatches/token and
        # compiled-program count A/B vs the split path, fill + mixed
        # ratios (ISSUE 10)
        result["ragged"] = ragged_res
    print(json.dumps(result))


if __name__ == "__main__":
    # a failure is a traceback and a non-zero exit: no result line, no
    # replayed history, no file written into the checkout
    main()
