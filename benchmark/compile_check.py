#!/usr/bin/env python3
"""Builder's rehearsal, no chip: compile a configuration's prefill and
decode programs at their real sizes for a described TPU v5e and print what
each needs (``memory_analysis``), which is how a configuration's
``--num-kv-blocks`` is fixed. A build, not a timing; nothing runs.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py --config mistral-7b \
        --buckets 1024 --num-kv-blocks 2800

It mirrors ``EngineCore._compile_jits`` (prefill / decode over the model's
forward + ``sample_tokens``) on shapes only, with the attention kernels
forced (``attn_impl="pallas"``) and the program's TPU test answered "yes",
because code that asks ``jax.devices()`` sees the CPU here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--buckets", default="1024")
    ap.add_argument("--num-kv-blocks", type=int, required=True)
    ap.add_argument("--max-num-seqs", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=4096)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--init", action="store_true",
                    help="also compile the largest init+quantize program")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import run as bench_run
    from dynamo_tpu.engine import attention
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.quant import (_quantize_named,
                                         init_params_quantized, unpack_params)
    from dynamo_tpu.engine.sampling import sample_tokens

    jax.config.update("jax_enable_compilation_cache", False)
    attention._on_tpu = llama._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    config = bench_run.load_config(bench_run.load_benchmark(), opts.config)
    cfg = ModelConfig.from_hf_config(bench_run.hf_config(config))
    statics = llama.ModelStatics(cfg=cfg, block_size=opts.block_size,
                                 attn_impl="pallas")

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg))
    kv = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, opts.num_kv_blocks, opts.block_size))
    size = lambda tree: sum(x.size * x.dtype.itemsize  # noqa: E731
                            for x in jax.tree.leaves(tree))
    report = {"config": opts.config, "weights_bytes": size(params),
              "kv_pool_bytes": size(kv), "num_kv_blocks": opts.num_kv_blocks,
              "kv_bytes_per_token": size(kv) // (opts.num_kv_blocks
                                                 * opts.block_size),
              "programs": {}}
    params, kv = placed(params), placed(kv)
    M = opts.max_model_len // opts.block_size
    B = opts.max_num_seqs
    i32, f32 = jnp.int32, jnp.float32

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def prefill(params, kv, tokens, table, start, n, key, t, k, p):
        logits, kv = llama.prefill_forward(unpack_params(params), kv, tokens,
                                           table, start, n, statics)
        tok, lp = sample_tokens(logits[None, :], key[None], t[None],
                                k[None], p[None])
        return tok[0], lp[0], kv

    def decode(params, kv, tokens, pos, tables, keys, t, k, p):
        logits, kv = llama.decode_forward(unpack_params(params), kv, tokens,
                                          pos, tables, statics)
        toks, lps = sample_tokens(logits, keys, t, k, p)
        return toks, lps, kv

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    jobs = {f"prefill-{b}": (prefill, (
        params, kv, s((b,), i32), s((M,), i32), s((), i32), s((), i32), key,
        s((), f32), s((), i32), s((), f32)))
        for b in (int(x) for x in opts.buckets.split(",") if x)}
    jobs[f"decode-B{B}"] = (decode, (
        params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
        s((B,) + key.shape, key.dtype), s((B,), f32), s((B,), i32),
        s((B,), f32)))
    if opts.init:
        shapes = llama.param_shapes(cfg)
        name = max(shapes, key=lambda n: int(jnp.prod(jnp.array(shapes[n]))))

        def build(sub):
            w = llama.init_one_param(cfg, name, shapes[name], sub,
                                     jnp.bfloat16)
            return _quantize_named(name, w, True, "lm_head" not in shapes, 8)
        jobs[f"init[{name}]"] = (build, (key,))
    for tag, (fn, args) in jobs.items():
        t0 = time.monotonic()
        donate = (1,) if tag.startswith(("prefill", "decode")) else ()
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        m = compiled.memory_analysis()
        report["programs"][tag] = {
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
            "compile_s": round(time.monotonic() - t0, 1)}
        print(f"# {tag}: {report['programs'][tag]}", flush=True)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
