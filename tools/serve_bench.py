"""End-to-end engine-loop serving benchmark: N requests stream through the
real EngineCore asyncio loop (admissions, continuous batching, harvests),
reporting wall-clock throughput and TTFT/ITL percentiles — RAW and NET of
the measured device→host fetch stalls.

Why the decomposition: the engine MEASURES the wall time its synchronous
fetches actually stall the loop (EngineCore.host_stall_s, the running
total of the loop's ``wait`` phase — an async copy that already landed,
or a host-value "fetch", measures ~0 by construction, so nothing is
modeled); this tool samples that clock at
each request's submit / first-token / finish and subtracts the in-window
delta — what the scheduler's own decisions cost. Raw numbers are printed
beside it; nothing is hidden.

Usage: python tools/serve_bench.py [n_requests] [max_num_seqs] [lanes]
"""

import asyncio
import json
import statistics
import sys
import time

sys.path.insert(0, ".")

import numpy as np
import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig, bench_model_config
from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from dynamo_tpu.engine.sampling import SlotSampling

PROMPT = 128
GEN = 64


def measure_rtt(reps: int = 15) -> float:
    """Median seconds for one device→host value fetch of a small array —
    the per-round-trip cost a synchronous fetch adds to the loop."""
    x = jnp.arange(64, dtype=jnp.int32)
    times = []
    for i in range(reps + 2):
        y = x + i                      # fresh value: no fetch caching
        t0 = time.monotonic()
        np.asarray(y)
        times.append(time.monotonic() - t0)
    return statistics.median(times[2:])   # first reps warm compile/queue


def pct(xs, p):
    xs = sorted(xs)
    return xs[min(int(len(xs) * p), len(xs) - 1)]


def main():
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    n_req = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    slots = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    lanes = int(sys.argv[3]) if len(sys.argv) > 3 else 0

    mcfg = bench_model_config("1b")
    max_len = PROMPT + GEN + 64
    ecfg = EngineConfig(
        max_model_len=max_len, kv_block_size=16,
        num_kv_blocks=slots * ((max_len + 15) // 16) + 2,
        max_num_seqs=slots, prefill_buckets=[PROMPT, max_len],
        decode_steps_per_dispatch=16, decode_dispatch_pipeline=True,
        lane_prefill_max_tokens=lanes, quantization="int8")
    core = EngineCore(mcfg, ecfg, attn_impl="auto",
                      param_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 32000, PROMPT).tolist() for _ in range(n_req)]

    gens = [int(g) for g in rng.integers(GEN // 2, GEN * 2, n_req)]
    gaps = rng.exponential(0.15, n_req)     # paced arrivals (open loop-ish)

    rtt = measure_rtt()
    platform = jax.devices()[0].platform

    async def one(i, delay=0.0):
        if delay:
            await asyncio.sleep(delay)
        req = EngineRequest(rid=f"r{i}", prompt=prompts[i],
                            sampling=SlotSampling(temperature=0.7, seed=i),
                            max_new_tokens=gens[i], eos_ids=frozenset())
        stall0 = core.host_stall_s
        t0 = time.monotonic()
        await core.submit(req)
        n = 0
        ttft = ttft_host = None
        stall_first = stall0
        while True:
            item, _ = await req.out_queue.get()  # dynalint: ok DL007 in-process bench harness owns both ends; a timeout would skew measured ITL
            if item is FINISH_SENTINEL:
                dt = time.monotonic() - t0
                gen_stall = core.host_stall_s - stall_first
                itl = ((dt - ttft) / max(n - 1, 1)) if ttft else None
                itl_host = (max(dt - ttft - gen_stall, 0.0)
                            / max(n - 1, 1)) if ttft else None
                return n, ttft, ttft_host, itl, itl_host
            if ttft is None:
                ttft = time.monotonic() - t0
                stall_first = core.host_stall_s
                # every measured fetch stall in the window blocked the
                # single-threaded loop, delaying this first token
                ttft_host = max(ttft - (stall_first - stall0), 0.0)
            n += 1

    async def run():
        # warm the compiles with one request end-to-end
        _ = await one(0)
        stall_base = core.host_stall_s
        t0 = time.monotonic()
        arrivals = np.cumsum(gaps)
        outs = await asyncio.gather(
            *[one(i, delay=float(arrivals[i])) for i in range(n_req)])
        dt = time.monotonic() - t0
        await core.stop()
        total = sum(n for n, *_ in outs)
        ttfts = [t for _, t, *_ in outs if t is not None]
        ttfts_host = [t for _, _, t, *_ in outs if t is not None]
        itls = [x for *_, x, _ in outs if x is not None]
        itls_host = [x for *_, x in outs if x is not None]
        print(f"lanes={lanes}: {n_req} reqs x ({PROMPT}p+{GEN}g), "
              f"slots={slots}: {total} tokens in {dt:.1f}s = "
              f"{total / dt:.0f} tok/s | rtt={rtt * 1e3:.0f}ms "
              f"({platform})\n"
              f"  raw : TTFT p50 {pct(ttfts, .5):.2f}s "
              f"p95 {pct(ttfts, .95):.2f}s | "
              f"ITL p50 {pct(itls, .5) * 1e3:.0f}ms\n"
              f"  host: TTFT p50 {pct(ttfts_host, .5) * 1e3:.0f}ms "
              f"p95 {pct(ttfts_host, .95) * 1e3:.0f}ms | "
              f"ITL p50 {pct(itls_host, .5) * 1e3:.0f}ms "
              f"(net of {core.host_stall_s - stall_base:.1f}s measured "
              f"fetch stall)\n"
              f"  lane_admissions={core.lane_admissions} "
              f"prefill_tok={core.total_prefill_tokens}")
        if platform != "cpu":
            # one JSON line a harness can keep (nothing is written into
            # the checkout)
            print(json.dumps({
                "metric": "serving_ttft_p50_host_ms",
                "value": round(pct(ttfts_host, .5) * 1e3, 1),
                "unit": "ms",
                "extra": {
                    "platform": platform,
                    "ttft_p95_host_ms": round(pct(ttfts_host, .95) * 1e3, 1),
                    "ttft_p50_raw_s": round(pct(ttfts, .5), 3),
                    "itl_p50_host_ms": round(pct(itls_host, .5) * 1e3, 1),
                    "rtt_ms": round(rtt * 1e3, 1),
                    "host_stall_s": round(
                        core.host_stall_s - stall_base, 2),
                    "n_requests": n_req, "slots": slots, "lanes": lanes,
                    "tok_per_s_wall": round(total / dt, 1),
                },
            }))

    asyncio.run(run())


if __name__ == "__main__":
    main()
