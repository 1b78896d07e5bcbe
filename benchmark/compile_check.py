#!/usr/bin/env python3
"""Builder's rehearsal, no chip: compile a configuration's prefill and
decode programs at their real sizes for a described TPU v5e and print what
each needs (``memory_analysis``), which is how a configuration's
``--num-kv-blocks`` and depth cut are fixed. A build, not a timing; nothing
runs.

    JAX_PLATFORMS=cpu python3 benchmark/compile_check.py --config mistral-7b \
        --buckets 1024 [--layers N] [--init] [launcher flags ...]

``--config`` is a name in ``BENCHMARK.json`` or the path of a configuration
file that is not in it yet. The engine is the one the launcher would build
from the file's ``deployment.flags`` (further launcher flags on this
command line are appended, so ``--num-kv-blocks 3200`` tries another pool),
and everything architecture-specific comes from ``EngineCore``'s own
dispatch: the model module (``models.module_for``), its parameter tree, the
arrays of its cache (``module.engine_cache``: the paged pool from the flag,
a window pool's blocks derived as the engine derives them, per-slot rings
and state by ``--max-num-seqs``) and the two step functions that
``EngineCore._compile_jits`` makes, lowered on shapes only. The attention
kernels are forced (``attn_impl="pallas"``) and the program's TPU test is
answered "yes", because code that asks ``jax.devices()`` sees the CPU here.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def engine_shell(cfg, engine_cfg):
    """An ``EngineCore`` with no weights and a cache of shapes only: the
    attributes its ``_compile_jits`` and ``_prefill_table`` read, set as
    ``EngineCore.__init__`` sets them on one chip with no mesh, then its own
    step functions. → (core, layout, window-pool blocks); ``core.kv`` is
    what ``engine_cache`` would build, as shapes.

    A resident drafter stays out of it: under ``--spec-k 1`` a model with a
    multi-token-prediction module is served by a prefill program with the
    module's tail and a two-row step (``core.resident_drafter``), and this
    shell builds the plain one-row programs over the same weights and pools;
    the served ones' builds are tests/test_tpu_compile_exaone.py -k served."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, module_for
    if engine_cfg.kv_block_size == 0:
        engine_cfg = dataclasses.replace(
            engine_cfg, kv_block_size=engine_cfg.auto_kv_block_size(
                cfg, engine_cfg.kv_quantization))
    core = object.__new__(EngineCore)
    core.cfg, core.mesh, core.pp = engine_cfg, None, 1
    core.model_mod = module_for(cfg)
    core.M = engine_cfg.max_blocks_per_seq
    core.statics = llama.ModelStatics(
        cfg=cfg, block_size=engine_cfg.kv_block_size, attn_impl="pallas",
        kv_coalesce=engine_cfg.kv_contig_alloc, table_blocks=core.M)
    beside = {}

    def cache():
        kv, beside["layout"], beside["win_blocks"] = \
            core.model_mod.engine_cache(cfg, engine_cfg, jnp.bfloat16, 1)
        return kv
    core.kv = jax.eval_shape(cache)
    layout = beside["layout"]
    # per-slot state behind the prefill table: what the cache's layout says,
    # whatever the family, as EngineCore.__init__ takes it
    core.is_hybrid = layout is not None and layout.has_state
    # a window pool's ids ride behind a table's M paged ones: R a decode
    # table's, M a prefill's
    core.has_window_pool = layout is not None and layout.window_pool
    core.R = layout.ring_blocks if core.has_window_pool else 0
    core._compile_jits()
    return core, layout, beside["win_blocks"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--buckets", default="1024")
    ap.add_argument("--layers", type=int, default=None,
                    help="try another depth than the file's")
    ap.add_argument("--init", action="store_true",
                    help="also compile the largest init+quantize program")
    opts, launcher_flags = ap.parse_known_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import run as bench_run
    from dynamo_tpu.engine import attention
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.models import llama, module_for
    from dynamo_tpu.engine.quant import (_quantize_named,
                                         init_params_quantized)
    from dynamo_tpu.launch import run as launcher

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    if os.path.exists(opts.config):
        with open(opts.config) as f:
            config = json.load(f)
    else:
        config = bench_run.load_config(bench_run.load_benchmark(),
                                       opts.config)
    hf = bench_run.hf_config(config)
    if opts.layers:
        hf["num_hidden_layers"] = opts.layers
    cfg = ModelConfig.from_hf_config(hf)
    # "is this a TPU?" is answered yes wherever the served family asks: a
    # model module binds attention's test under its own name at import
    for mod in (attention, llama, module_for(cfg)):
        if hasattr(mod, "_on_tpu"):
            mod._on_tpu = lambda: True
    flags = list(config.get("deployment", {}).get("flags", ()))
    engine_cfg = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *flags, *launcher_flags]))
    if engine_cfg.quantization != "int8":
        raise SystemExit("compile_check builds int8 weights; the "
                         f"deployment asks for {engine_cfg.quantization!r}")
    core, layout, win_blocks = engine_shell(cfg, engine_cfg)
    engine_cfg = core.cfg
    blocks, bsz = engine_cfg.num_kv_blocks, engine_cfg.kv_block_size

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg))
    kv = core.kv
    size = lambda tree: sum(x.size * x.dtype.itemsize  # noqa: E731
                            for x in jax.tree.leaves(tree))
    report = {"config": opts.config, "layers": cfg.num_layers,
              "model_module": core.model_mod.__name__,
              "weights_bytes": size(params),
              "kv_pool_bytes": size(kv), "num_kv_blocks": blocks,
              "window_pool_blocks": win_blocks,
              "cache_bytes": {name: size(x) for name, x in kv.items()},
              "programs": {}}
    if layout is None:      # one uniform paged pool: a token has one size
        report["kv_bytes_per_token"] = size(kv) // (blocks * bsz)
    params, kv = placed(params), placed(kv)
    # the tables as the engine lays them out: _prefill_table's own shape,
    # and _block_tables' [B, M + R]
    prefill_table = core._prefill_table([], 0).shape
    B = engine_cfg.max_num_seqs
    i32, f32 = jnp.int32, jnp.float32

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    jobs = {f"prefill-{b}": (core._prefill_jit, (
        params, kv, s((b,), i32), s(prefill_table, i32), s((), i32),
        s((), i32), key, s((), f32), s((), i32), s((), f32)))
        for b in (int(x) for x in opts.buckets.split(",") if x)}
    jobs[f"decode-B{B}"] = (core._decode_jit, (
        params, kv, s((B,), i32), s((B,), i32), s((B, core.M + core.R), i32),
        s((B,) + key.shape, key.dtype), s((B,), f32), s((B,), i32),
        s((B,), f32)))
    if opts.init:
        shapes = core.model_mod.param_shapes(cfg)
        name = max(shapes, key=lambda n: math.prod(shapes[n]))

        def build(sub):
            w = llama.init_one_param(cfg, name, shapes[name], sub,
                                     jnp.bfloat16)
            return _quantize_named(name, w, True, "lm_head" not in shapes, 8)
        jobs[f"init[{name}]"] = (jax.jit(build), (key,))
    for tag, (fn, args) in jobs.items():
        t0 = time.monotonic()
        compiled = fn.lower(*args).compile()
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
