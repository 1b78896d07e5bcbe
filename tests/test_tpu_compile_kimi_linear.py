"""Kimi-Linear-48B's deviceless builds for a described ``v5e:2x2`` (the
helpers and fixtures are ``tests/test_tpu_compile.py``'s): the two
delta-attention kernels (``engine/kda.py``) and the two served programs,
whole, at the published widths and the cell's flags. ``benchmark/
compile_check.py`` builds this configuration's programs WITHOUT the slot
behind the prefill table (its shell assigns ``is_hybrid`` by a family's
name), so the builds that ``benchmark/configs/kimi-linear-48b.json``'s
``memory_analysis`` quotes are these. A file of its own so that ``--dist
loadfile`` can give it to another worker than ``test_tpu_compile.py``'s."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import (A, _benchmark_hf, _compile,  # noqa: F401
                              one_chip, topo)

F32 = jnp.float32


@pytest.mark.parametrize("kernel", ["kda_chunk-1024", "kda_chunk-4096",
                                    "kda_step"])
def test_delta_attention_kernels_build_at_the_published_sizes(one_chip,
                                                              kernel):
    """32 heads of 128 x 128 float32 state: the prompt's two kernels (the
    chunk matrices, then the walk) at the smallest and the largest prefill
    bucket, and the decode update of 64 slots in place inside the twenty
    layers' state array."""
    from dynamo_tpu.engine import kda
    H, d, B, L = 32, 128, 64, 20
    if kernel != "kda_step":
        T = int(kernel.split("-")[1])
        compiled = _compile(
            kda.kda_chunk, one_chip, ((T, H, d), F32), ((T, H, d), F32),
            ((T, H, d), F32), ((T, H, d), F32), ((T, H), F32),
            ((H, d, d), F32))
        text = compiled.as_text()
        assert "kda_chunk" in text and "kda_prepare" in text
        # the chunk matrices are built in VMEM: no XLA form of them (42
        # fusions and a 15-trip loop a layer, 2.23 GB of traffic at 1,024
        # rows) can come back unseen. What is left in HBM is what the walk
        # takes, 196 KiB a (chunk, head) tile, and its output: 605 MB at
        # 4,096 rows, 51 MB at 1,024
        entry = text[text.index("ENTRY"):]
        assert " fusion(" not in entry and " while(" not in entry
        assert compiled.cost_analysis()["bytes accessed"] < 0.6e6 * T
        assert compiled.memory_analysis().temp_size_in_bytes < {
            1024: 2 ** 26, 4096: 5 * 2 ** 27}[T]
        return
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((B, H, d), F32), ((B, H, d), F32), ((B, H, d), F32),
                ((B, H, d), F32), ((B, H), F32), ((L * B, H, d, d), F32),
                ((), jnp.int32))]
    compiled = jax.jit(kda.kda_step, donate_argnums=(5,)).lower(
        *args).compile()
    assert "kda_step" in compiled.as_text()
    # the state is rewritten where it lies: no second copy of 2.7 GB
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= L * B * H * d * d * 4
    assert m.temp_size_in_bytes < 64 * 2 ** 20


def kimi_linear_shell(monkeypatch):
    """kimi-linear-48b's engine as ``benchmark/compile_check.py`` builds one
    (no weights, no pool: the attributes ``_compile_jits`` reads), with
    ``is_hybrid`` set from the cache's layout as ``EngineCore.__init__``
    sets it."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import kimi_linear, llama, mla, module_for
    from dynamo_tpu.engine.quant import init_params_quantized
    from dynamo_tpu.launch import run as launcher
    for mod in (A, llama, kimi_linear):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b.json")) as f:
        flags = json.load(f)["deployment"]["flags"]
    cfg = ModelConfig.from_hf_config(
        _benchmark_hf("configs/kimi-linear-48b.json"))
    e = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *flags]))
    assert module_for(cfg) is kimi_linear
    assert mla.layer_plan(cfg) == (1, ("K", "K", "F", "K"), 6, ("K", "F"))
    core = object.__new__(EngineCore)
    core.cfg, core.mesh, core.pp = e, None, 1
    core.model_mod = kimi_linear
    layout = kimi_linear.cache_layout(cfg, e.kv_block_size)
    core.is_hybrid = layout.has_state
    core.M = e.max_blocks_per_seq
    core.has_window_pool, core.R = False, 0
    core.statics = llama.ModelStatics(
        cfg=cfg, block_size=e.kv_block_size, attn_impl="pallas",
        kv_coalesce=e.kv_contig_alloc, table_blocks=core.M)
    core._compile_jits()
    params = jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg))
    kv = jax.eval_shape(lambda: kimi_linear.engine_cache(
        cfg, e, jnp.bfloat16)[0])
    return cfg, e, core, layout, params, kv


@pytest.mark.parametrize("program", ["prefill-1024", "decode-B64"])
def test_kimi_linear_served_programs_build_at_the_published_sizes(
        one_chip, monkeypatch, program):
    """The cell's prefill chunk (slot behind the table's 464 entries) and
    its decode step of 64 slots, all 27 layers (one dense, six scanned
    periods K K F K, two left over), int8 weights, 16,384 latent blocks and
    20 x 64 states: every new read is its Pallas kernel under its own name,
    weights + pool + state fill over 60% of the chip and the program fits
    beside them."""
    cfg, e, core, layout, params, kv = kimi_linear_shell(monkeypatch)
    place = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip), t)
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    M, B = e.max_blocks_per_seq, e.max_num_seqs
    assert (M, B, kv["kv"].shape, kv["kda"].shape) == (
        464, 64, (7, 16384 * 16, 640), (20, 64, 32, 128, 128))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    if program == "prefill-1024":
        compiled = core._prefill_jit.lower(
            place(params), place(kv), s((1024,), i32), s((M + 1,), i32),
            s((), i32), s((), i32), key, s((), f32), s((), i32),
            s((), f32)).compile()
        names = ("kda_chunk", "mla_prefill")
    else:
        keys = jax.eval_shape(lambda: jax.random.split(
            jax.random.PRNGKey(0), B))
        compiled = core._decode_jit.lower(
            place(params), place(kv), s((B,), i32), s((B,), i32),
            s((B, M), i32), s(keys.shape, keys.dtype), s((B,), f32),
            s((B,), i32), s((B,), f32)).compile()
        names = ("kda_step", "paged_attention")
    text = compiled.as_text()
    assert all(n in text for n in names), [n for n in names if n not in text]
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes
    limit = 16 * 2 ** 30
    print(program, "argument_bytes", held, "temp_bytes",
          m.temp_size_in_bytes, "custom_calls", text.count("tpu_custom_call"))
    assert 0.60 * limit < held and held + m.temp_size_in_bytes < 0.95 * limit
