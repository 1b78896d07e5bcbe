"""granite-4.0-h-small's deviceless builds for a described ``v5e:2x2`` (the
helpers and fixtures are ``tests/test_tpu_compile.py``'s): the two Mamba-2
kernels (``engine/ssd.py``) and the two served programs, whole, at the
published widths and the cell's flags, with the slot behind the prefill
table. A file of its own so that ``--dist loadfile`` can give it to another
worker than ``test_tpu_compile.py``'s."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import (A, _benchmark_hf, _compile,  # noqa: F401
                              one_chip, topo)

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("kernel", ["ssd_chunk-1024", "ssd_step"])
def test_ssd_kernels_build_at_the_published_sizes(one_chip, kernel):
    """128 heads of 64 lanes x 128 states: a prompt's dispatch of 1,024 rows
    (bf16 x, float32 everything else, the state [64, 128, 128] in and out),
    and the decode update of 64 slots in place inside the nine layers' state
    array."""
    from dynamo_tpu.engine import ssd
    H, P, N, B, L = 128, 64, 128, 64, 9
    held = ssd.state_shape(H, P, N)
    assert held == (64, 128, 128)
    if kernel != "ssd_step":
        T = int(kernel.split("-")[1])
        compiled = _compile(
            ssd.ssd_chunk, one_chip, ((T, H, P), BF16), ((T, H), F32),
            ((T, H), F32), ((T, N), F32), ((T, N), F32), (held, F32),
            ((), jnp.int32))
        assert "ssd_chunk" in compiled.as_text()
        # x in, y out, and the small transposes before the call
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27
        return
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((B, H, P), BF16), ((B, H), F32), ((B, H), F32),
                ((B, N), F32), ((B, N), F32), ((L * B,) + held, F32),
                ((), jnp.int32))]
    compiled = jax.jit(ssd.ssd_step, donate_argnums=(5,)).lower(
        *args).compile()
    assert "ssd_step" in compiled.as_text()
    # the state is rewritten where it lies: no second copy of 2.4 GB
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= L * B * H * P * N * 4
    assert m.temp_size_in_bytes < 64 * 2 ** 20


def granite_shell(monkeypatch):
    """granite-4.0-h-small's engine as ``benchmark/compile_check.py`` builds
    one (no weights, no pool: the attributes ``_compile_jits`` reads), with
    ``is_hybrid`` set from the cache's layout as ``EngineCore.__init__``
    sets it."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import granite_hybrid, llama, module_for
    from dynamo_tpu.engine.quant import init_params_quantized
    from dynamo_tpu.launch import run as launcher
    for mod in (A, llama, granite_hybrid):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        flags = json.load(f)["deployment"]["flags"]
    cfg = ModelConfig.from_hf_config(
        _benchmark_hf("configs/granite-4.0-h-small.json"))
    e = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *flags]))
    assert module_for(cfg) is granite_hybrid
    assert granite_hybrid.layer_plan(cfg) == (
        tuple("MMMMMA"), 1, tuple("MMMM"))
    core = object.__new__(EngineCore)
    core.cfg, core.mesh, core.pp = e, None, 1
    core.model_mod = granite_hybrid
    layout = granite_hybrid.cache_layout(cfg, e.kv_block_size)
    core.is_hybrid = layout.has_state
    core.M = e.max_blocks_per_seq
    core.has_window_pool, core.R = False, 0
    core.statics = llama.ModelStatics(
        cfg=cfg, block_size=e.kv_block_size, attn_impl="pallas",
        kv_coalesce=e.kv_contig_alloc, table_blocks=core.M)
    core._compile_jits()
    params = jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg))
    kv = jax.eval_shape(lambda: granite_hybrid.engine_cache(
        cfg, e, jnp.bfloat16)[0])
    return cfg, e, core, layout, params, kv


@pytest.mark.parametrize("program", ["prefill-1024", "decode-B64"])
def test_granite_served_programs_build_at_the_published_sizes(
        one_chip, monkeypatch, program):
    """The cell's prefill dispatch (slot behind the table's 560 entries) and
    its decode step of 64 slots, one whole period (a scan of five Mamba-2
    layers, the attention layer, a scan of four), all 72 experts a layer in
    int8, 16,384 K/V blocks and 9 x 64 states: every new read is its Pallas
    kernel under its own name, weights + pool + state fill over 60% of the
    chip and the program fits beside them."""
    cfg, e, core, layout, params, kv = granite_shell(monkeypatch)
    place = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip), t)
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    M, B = e.max_blocks_per_seq, e.max_num_seqs
    assert (M, B, kv["k"].shape, kv["ssd"].shape, kv["conv"].shape) == (
        560, 64, (1, 16384 * 16, 1024), (9, 64, 64, 128, 128),
        (9, 64, 3, 8448))
    assert e.prefill_chunk == 1024
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    if program == "prefill-1024":
        compiled = core._prefill_jit.lower(
            place(params), place(kv), s((1024,), i32), s((M + 1,), i32),
            s((), i32), s((), i32), key, s((), f32), s((), i32),
            s((), f32)).compile()
        names = ("ssd_chunk", "flash_prefill", "grouped_experts")
    else:
        keys = jax.eval_shape(lambda: jax.random.split(
            jax.random.PRNGKey(0), B))
        compiled = core._decode_jit.lower(
            place(params), place(kv), s((B,), i32), s((B,), i32),
            s((B, M), i32), s(keys.shape, keys.dtype), s((B,), f32),
            s((B,), i32), s((B,), f32)).compile()
        names = ("ssd_step", "paged_attention")
    text = compiled.as_text()
    assert all(n in text for n in names), [n for n in names if n not in text]
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes
    limit = 16 * 2 ** 30
    print(program, "argument_bytes", held, "temp_bytes",
          m.temp_size_in_bytes, "custom_calls", text.count("tpu_custom_call"))
    assert 0.60 * limit < held and held + m.temp_size_in_bytes < 0.95 * limit
