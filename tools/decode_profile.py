"""Per-component DEVICE timing for the decode step on real hardware.

Times each stage with a chained in-jit `lax.fori_loop` (N-pass slope):
f(N2) - f(N1) wall time with a single value fetch as the barrier cancels
the fetch and constant dispatch overheads (utils/timing.py).

Components:
  layers      — transformer stack only (embed + _run_layers, no lm head)
  layers+head — plus the logits projection
  full        — plus sampling (the real serving step content)
  attn        — paged attention isolated (the stack with MLP/proj removed
                is not expressible, so this times paged_attention directly
                on pool-shaped inputs)

Usage: python tools/decode_profile.py [batch ...]   (default 16 64 128)
Env: PROF_MODEL (1b|8b — 8b weighs ~8 GB int8, so pass explicit batches
     that keep batch*(seq+256) KV inside the remaining HBM: B<=32 at
     seq 512 with bf16 KV; the 1b default batch list OOMs at 8b),
     PROF_QUANT (int8|none, default int8), PROF_SEQ (kv len, default
     512), PROF_ATTN (auto|pallas|xla), PROF_TABLES (random|contig,
     default random — the historical layout; "contig" gives each slot a
     consecutive block run, the run-tracking allocator's layout, so the
     kernel's wave-coalesced DMA path engages; the header line reports
     the DMA copies/wave either way so the two layouts are comparable).
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def slope_time(fn, args, n1=8, n2=40, reps=3):
    """fn(n, *args) -> array; per-iteration seconds via slope (protocol
    home: dynamo_tpu.utils.timing.slope_per_unit)."""
    from dynamo_tpu.utils.timing import slope_per_unit

    np.asarray(fn(n2, *args))            # compile the long variant too

    def once(n):
        t0 = time.monotonic()
        np.asarray(fn(n, *args))
        return time.monotonic() - t0

    return slope_per_unit(once, n1, n2, reps=reps)


def main():
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from functools import partial

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.sampling import make_slot_keys, sample_tokens

    batches = [int(a) for a in sys.argv[1:]] or [16, 64, 128]
    quant = os.environ.get("PROF_QUANT", "int8")
    kv_quant = os.environ.get("PROF_KV", "none")   # none|int8 KV pool
    seq = int(os.environ.get("PROF_SEQ", "512"))
    attn_impl = os.environ.get("PROF_ATTN", "auto")
    model = os.environ.get("PROF_MODEL", "1b")
    # long-context sweeps past the geometry's RoPE table: PROF_MAXPOS
    # raises max_position_embeddings (table cost is linear and tiny)
    maxpos = int(os.environ.get("PROF_MAXPOS", "0"))

    # geometry shared with bench.py (ONE home; unknown names raise —
    # no silent 1B fallback under a mislabeled header)
    from dynamo_tpu.engine.config import bench_model_config
    mcfg = bench_model_config(model)
    if maxpos:
        import dataclasses
        mcfg = dataclasses.replace(mcfg, max_position_embeddings=maxpos)
    if seq >= mcfg.max_position_embeddings:
        # positions stay pinned at `seq` throughout the profile chains
        # (the fori body never advances them), so the only alias hazard
        # is the decode position itself falling past the RoPE table
        raise SystemExit(
            f"PROF_SEQ={seq} >= the {model!r} geometry's "
            f"max_position_embeddings={mcfg.max_position_embeddings}; "
            f"the decode position would silently alias past the RoPE "
            f"table (ADVICE r3). Use a geometry that covers the sweep.")
    dev = jax.devices()[0]
    print(f"# {dev.platform}:{dev.device_kind} model={model} quant={quant} "
          f"kv={kv_quant} seq={seq} attn={attn_impl}", file=sys.stderr)

    for batch in batches:
        # int8 pools need 32-token blocks (int8 sublane tile)
        bs = 32 if kv_quant == "int8" else 16
        bps = (seq + 256 + bs - 1) // bs
        ecfg = EngineConfig(max_model_len=seq + 256, kv_block_size=bs,
                            num_kv_blocks=batch * bps + 2,
                            max_num_seqs=batch, prefill_buckets=[128],
                            quantization=quant, kv_quantization=kv_quant)
        core = EngineCore(mcfg, ecfg, attn_impl=attn_impl,
                          param_dtype=jnp.bfloat16)
        statics = core.statics
        rng = np.random.default_rng(0)
        layout = os.environ.get("PROF_TABLES", "random")
        if layout == "contig":
            # the run-tracking allocator's layout: one consecutive run
            # per slot (wraps at the pool end for oversized sweeps)
            t = (np.arange(batch * core.M).reshape(batch, core.M)
                 % (ecfg.num_kv_blocks - 1)) + 1
            tables_np = t.astype(np.int32)
        else:
            tables_np = rng.integers(
                1, ecfg.num_kv_blocks, size=(batch, core.M)).astype(
                    np.int32)
        from dynamo_tpu.engine.attention import dma_copy_counts
        dma = dma_copy_counts(
            tables_np, np.full((batch,), seq + 1, np.int32),
            block_size=bs, pool_blocks=ecfg.num_kv_blocks,
            dual_stream=mcfg.kv_lora_rank == 0)
        print(f"# tables={layout} dma_copies/wave="
              f"{dma['copies_per_wave']:.2f} "
              f"({dma['coalesced_waves']}/{dma['waves']} waves "
              f"coalesced)", file=sys.stderr)
        tables = jnp.asarray(tables_np, jnp.int32)
        positions = jnp.asarray(np.full((batch,), seq, np.int32))
        tokens = jnp.asarray(rng.integers(1, 1000, size=(batch,)), jnp.int32)
        params, kv = core.params, core.kv

        @partial(jax.jit, static_argnums=0)
        def run_layers(n, params, kv, tokens, positions, tables):
            def body(i, carry):
                kv, toks, acc = carry
                logits, kv = llama.decode_forward(
                    params, kv, toks, positions, tables, statics)
                # feed a data-dependent token back so XLA can't hoist
                return (kv,
                        jnp.argmax(logits[:, :1000], -1).astype(jnp.int32),
                        acc + logits[:, 0])
            _kv, toks, acc = jax.lax.fori_loop(
                0, n, body, (kv, tokens, jnp.zeros((tokens.shape[0],))))
            return acc

        # stack WITHOUT the lm head: argmax over the raw hidden state
        @partial(jax.jit, static_argnums=0)
        def run_stack_only(n, params, kv, tokens, positions, tables):
            emb_dim = mcfg.hidden_size

            def body(i, carry):
                kv, toks, acc = carry
                x = llama._embed(params, toks, mcfg)
                x, kv = llama._run_layers(
                    params, kv, x, positions,
                    tables[jnp.arange(toks.shape[0]), positions // bs] * bs
                    + positions % bs,
                    mcfg,
                    _attn_fn(params, kv, positions, tables))
                return (kv,
                        jnp.argmax(x[:, :1000], -1).astype(jnp.int32),
                        acc + x[:, 0])
            _kv, toks, acc = jax.lax.fori_loop(
                0, n, body, (kv, tokens, jnp.zeros((tokens.shape[0],))))
            return acc

        def _attn_fn(params, kv, positions, tables):
            from dynamo_tpu.engine.attention import paged_attention
            scale = mcfg.head_dim ** -0.5
            seq_lens = positions + 1

            def attn(q, _k, _v, k_flat, v_flat, li, sliding):
                nb = k_flat.shape[0] // (mcfg.num_layers * bs)
                return paged_attention(q, k_flat, v_flat,
                                       tables + li * nb, seq_lens,
                                       block_size=bs, scale=scale,
                                       impl=statics.attn_impl,
                                       kv_heads=mcfg.num_kv_heads)
            return attn

        @partial(jax.jit, static_argnums=0)
        def run_full(n, params, kv, tokens, positions, tables):
            keys0 = jnp.asarray(np.zeros((batch,), np.int64))
            temp = jnp.full((batch,), 0.7, jnp.float32)
            topk = jnp.zeros((batch,), jnp.int32)
            topp = jnp.ones((batch,), jnp.float32)

            def body(i, carry):
                kv, toks, acc = carry
                logits, kv = llama.decode_forward(
                    params, kv, toks, positions, tables, statics)
                keys = make_slot_keys(0, keys0, i.astype(jnp.int64))
                toks2, lps = sample_tokens(logits, keys, temp, topk, topp)
                return kv, toks2, acc + lps
            _kv, toks, acc = jax.lax.fori_loop(
                0, n, body, (kv, tokens, jnp.zeros((tokens.shape[0],))))
            return acc

        args = (params, kv, tokens, positions, tables)
        t_stack = slope_time(run_stack_only, args)
        t_layers = slope_time(run_layers, args)
        t_full = slope_time(run_full, args)
        print(f"B={batch:4d}  stack={t_stack*1e3:7.3f}ms  "
              f"+head={t_layers*1e3:7.3f}ms  "
              f"+sample={t_full*1e3:7.3f}ms  "
              f"head={(t_layers-t_stack)*1e3:7.3f}ms  "
              f"sample={(t_full-t_layers)*1e3:7.3f}ms  "
              f"tok/s={batch/t_full:9.1f}")


if __name__ == "__main__":
    main()
