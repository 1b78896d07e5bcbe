"""Deviceless compiles of the main path's Pallas kernels for a described
TPU v5e (``v5e:2x2``, one device) at published widths.

Interpret mode cannot see what the chip's compiler refuses — a slice off
the tiling, a kernel over its VMEM budget — so each kernel is lowered and
compiled here for the real target with no chip attached. Nothing runs:
these say a kernel BUILDS, never what it computes or how fast.

Everything that touches the TPU compiler lives in this ONE file, behind a
module-scoped fixture: only the xdist worker that is handed this file
loads libtpu, and every worker collects the same tests.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine import attention as A
from dynamo_tpu.engine.config import bench_model_config

CFG_1B = bench_model_config("1b")     # Llama-3.2-1B published widths
CFG_8B = bench_model_config("8b")     # Llama-3-8B published widths
# google/gemma-2b attention widths (MQA: 8 heads over one 256-wide KV
# head), the narrow end of what the ragged gate prices
CFG_GEMMA_2B = dataclasses.replace(CFG_1B, num_heads=8, num_kv_heads=1,
                                   head_dim=256)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: skip, loudly
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described device, with the persistent
    compile cache off around every compile of this module: an entry
    written for a described device cannot be read back without a chip,
    and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile fn for the described chip; the kernel must be in the
    program (a Pallas call lowers to a ``tpu_custom_call``)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(cfg, block_size, num_blocks, kv_int8):
    C = cfg.num_kv_heads * cfg.head_dim
    lanes = C + A.KV_SCALE_LANES if kv_int8 else C
    return ((num_blocks * block_size, lanes),
            jnp.int8 if kv_int8 else jnp.bfloat16)


def _decode_case(one_chip, cfg, block_size, kv_int8, B=8, max_len=4096,
                 num_blocks=2048):
    M = max_len // block_size
    pool = _pool(cfg, block_size, num_blocks, kv_int8)
    assert A.pallas_supported(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                              block_size, kv_dtype=pool[1])

    def fn(q, k, v, bt, sl):
        return A.paged_attention_pallas(
            q, k, v, bt, sl, block_size=block_size,
            scale=cfg.head_dim ** -0.5)

    _compile(fn, one_chip,
             ((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16), pool, pool,
             ((B, M), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("cfg,block_size,kv_int8", [
    (CFG_1B, 16, False), (CFG_1B, 32, True), (CFG_8B, 16, False),
    (CFG_8B, 32, True),
], ids=["1b-bf16", "1b-int8kv", "8b-bf16", "8b-int8kv"])
def test_paged_decode_kernel_compiles(one_chip, cfg, block_size, kv_int8):
    _decode_case(one_chip, cfg, block_size, kv_int8)


@pytest.mark.parametrize("form", ["latent-chunk64", "per-block"])
def test_paged_decode_kernel_loops_compile(one_chip, form):
    """The kernel's sequence loop and its block copies (one traced body,
    unrolled by the lowering) build for the chip at the deepest wave a
    cell serves (kimi-k2's latent row, 64 blocks, v aliased) and where
    every wave takes the per-block path. Two rows a sequence:
    test_tpu_compile_exaone.py."""
    bs = 16
    if form == "latent-chunk64":
        B, H, d, lanes, M = 16, 64, 640, 640, 1600
        kw = dict(v_lanes=512, chunk_blocks=64)
    else:
        B, H, d, lanes, M = 64, 32, 128, 1024, 256
        kw = dict(coalesce=False)

    def fn(q, k, v, bt, sl):
        return A.paged_attention_pallas(q, k, v, bt, sl, block_size=bs,
                                        scale=0.1, **kw)

    pool = ((4096 * bs, lanes), jnp.bfloat16)
    _compile(fn, one_chip, ((B, H, d), jnp.bfloat16), pool, pool,
             ((B, M), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("cfg", [CFG_1B, CFG_8B],
                         ids=["Dh64-1b", "Dh128-8b"])
def test_flash_prefill_kernel_compiles(one_chip, cfg):
    T = 1024
    assert A.flash_prefill_supported(cfg.num_heads, cfg.num_kv_heads,
                                     cfg.head_dim)

    def fn(q, k, v, start, n):
        return A.flash_prefill(q, k, v, scale=cfg.head_dim ** -0.5,
                               start_pos=start, seq_len=n)

    _compile(fn, one_chip,
             ((T, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
             ((T, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
             ((T, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
             ((), jnp.int32), ((), jnp.int32))


def _ragged_case(one_chip, cfg, block_size, kv_int8, max_rows, S=8,
                 max_len=4096, num_blocks=2048):
    M = max_len // block_size
    TT = S + 2 * max_rows               # EngineConfig's auto capacity
    pool = _pool(cfg, block_size, num_blocks, kv_int8)

    def fn(q, k, v, bt, starts, counts, lens):
        return A.ragged_paged_attention_pallas(
            q, k, v, bt, starts, counts, lens, block_size=block_size,
            scale=cfg.head_dim ** -0.5, max_rows=max_rows)

    _compile(fn, one_chip,
             ((TT, cfg.num_heads, cfg.head_dim), jnp.bfloat16), pool, pool,
             ((S, M), jnp.int32), ((S,), jnp.int32), ((S,), jnp.int32),
             ((S,), jnp.int32))


def _ragged_boundary(cfg, block_size, kv_dtype) -> int:
    """Largest per-sequence row budget ragged_supported accepts."""
    rows = 0
    while A.ragged_supported(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                             block_size, rows + 1, kv_dtype=kv_dtype):
        rows += 1
        assert rows < 4096, "ragged_supported never refuses"
    return rows


def test_ragged_kernel_compiles_at_a_supported_budget(one_chip):
    assert A.ragged_supported(CFG_1B.num_heads, CFG_1B.num_kv_heads,
                              CFG_1B.head_dim, 16, 32,
                              kv_dtype=jnp.bfloat16)
    _ragged_case(one_chip, CFG_1B, 16, False, max_rows=32)


@pytest.mark.parametrize("cfg,block_size,kv_int8,below,default_fits", [
    (CFG_1B, 16, False, (8, 16, 24, 32), False),
    (CFG_1B, 32, True, (8, 16, 24, 32), False),
    (CFG_8B, 16, False, (8, 16), False),
    (CFG_GEMMA_2B, 16, False, (64,), True),
], ids=["1b-bf16", "1b-int8kv", "8b-bf16", "gemma2b-mqa"])
def test_ragged_supported_geometries_all_compile(
        one_chip, cfg, block_size, kv_int8, below, default_fits):
    """ragged_supported is the gate ``attn_impl="auto"`` trusts, from
    both sides: every row budget it accepts, up to its boundary, must
    build, and a third beyond its boundary the compiler must really be
    out of VMEM — the gate may stop a little early, never late, and
    never far from where Mosaic stops. At 1B and 8B widths the default
    budget (64) sits beyond the boundary, which is why --ragged
    resolves to the XLA path there (ROADMAP open item)."""
    dt = jnp.int8 if kv_int8 else jnp.bfloat16
    top = _ragged_boundary(cfg, block_size, dt)
    assert (top >= 64) == default_fits, top
    for rows in (*below, top):
        assert rows <= top
        _ragged_case(one_chip, cfg, block_size, kv_int8, rows)
    with pytest.raises(Exception, match="(?i)vmem"):
        _ragged_case(one_chip, cfg, block_size, kv_int8, -(-top * 4 // 3))


def test_ragged_gate_refuses_head_counts_off_the_sublane_tiling(one_chip):
    """Qwen2.5-1.5B attention widths (12 heads over 2 KV heads of 128):
    the decode kernel builds, the ragged kernel's per-sequence q-window
    slice does not — so ragged_supported must say no, whatever the row
    budget."""
    cfg = dataclasses.replace(CFG_1B, num_heads=12, num_kv_heads=2,
                              head_dim=128)
    _decode_case(one_chip, cfg, 16, False)
    assert not A.ragged_supported(12, 2, 128, 16, 8, kv_dtype=jnp.bfloat16)
    with pytest.raises(Exception, match="aligned to tiling"):
        _ragged_case(one_chip, cfg, 16, False, max_rows=8)


def test_int8_lm_head_kernel_compiles_at_llama_vocab(one_chip):
    from dynamo_tpu.engine.lm_head import lm_head_int8
    D, V = CFG_1B.hidden_size, CFG_1B.vocab_size
    assert V == 128256
    _compile(lm_head_int8, one_chip, ((8, D), jnp.bfloat16),
             ((D, V), jnp.int8), ((1, V), jnp.float32))


def test_grouped_int4_matmul_compiles_at_8b_ffn(one_chip):
    from dynamo_tpu.engine.quant_matmul import (GROUP, grouped_int4_matmul,
                                                grouped_kernel_eligible)
    D, F = CFG_8B.hidden_size, CFG_8B.intermediate_size
    assert (D, F) == (4096, 14336)
    assert grouped_kernel_eligible(8, D, F, GROUP)
    _compile(grouped_int4_matmul, one_chip, ((8, D), jnp.bfloat16),
             ((D // 2, F), jnp.int8), ((D // GROUP, F), jnp.float32))


def test_decode_kernel_compiles_per_tp_shard_on_four_chips(topo, one_chip):
    """The compiler refuses to partition a Mosaic kernel, so under a tp
    mesh the engine runs it per shard (llama._per_tp_shard): at 1B
    widths over the four described chips that program must build, with
    the kernel in it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.parallel.sharding import AXES
    cfg = CFG_1B
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4, 1, 1), AXES)
    statics = llama.ModelStatics(cfg=cfg, block_size=16,
                                 attn_impl="pallas", mesh=mesh)
    assert statics.tp == 4
    B, M = 8, 256
    pool, dt = _pool(cfg, 16, 2048, False)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def fn(q, k, v, bt, sl):
        return llama._paged_attention(statics, q, k, v, bt, sl, None,
                                      cfg.head_dim ** -0.5)

    compiled = jax.jit(fn).lower(
        sds((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16,
            P(None, "tp", None)),
        sds(pool, dt, P(None, "tp")), sds(pool, dt, P(None, "tp")),
        sds((B, M), jnp.int32, P()), sds((B,), jnp.int32, P())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(NotImplementedError, match="partition"):
        # what the engine did before: the kernel handed to GSPMD whole
        whole = llama.ModelStatics(cfg=cfg, block_size=16,
                                   attn_impl="pallas")
        jax.jit(lambda q, k, v, bt, sl: llama._paged_attention(
            whole, q, k, v, bt, sl, None, cfg.head_dim ** -0.5)).lower(
                sds((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16,
                    P(None, "tp", None)),
                sds(pool, dt, P(None, "tp")), sds(pool, dt, P(None, "tp")),
                sds((B, M), jnp.int32, P()),
                sds((B,), jnp.int32, P())).compile()


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "qwen2moe"])
def test_engine_step_programs_carry_stable_names(one_chip, monkeypatch, moe):
    """The engine's own prefill and decode programs (``_prefill_jit`` /
    ``_decode_k_jit`` at one step per dispatch, the ones the benchmark's
    cells serve) name their
    parts with ``jax.named_scope`` and their Pallas calls with ``name=``,
    so a trace reduction finds them whatever the compiler calls its
    fusions. Narrow widths, kernel-supported geometry, two layers."""
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama
    # code that asks jax.devices() sees the CPU here
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    cfg = ModelConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=64,
        max_position_embeddings=1024,
        **(dict(num_experts=4, num_experts_per_tok=2, moe_norm_topk=False,
                shared_expert_size=512) if moe else {}))
    B, M, T = 8, 16, 128
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=16, num_kv_blocks=64,
        max_num_seqs=B, prefill_buckets=[T]), attn_impl="pallas")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32
    decode = core._decode_k_jit.lower(
        params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
        s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
        s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_),
        s(key.shape, key.dtype)).compile().as_text()
    prefill = core._prefill_jit.lower(
        params, kv, s((T,), i32), s((M,), i32), s((), i32), s((), i32),
        s(key.shape, key.dtype), s((), f32), s((), i32),
        s((), f32)).compile().as_text()
    mlp = (["moe_mlp/run_experts_dense", "moe_mlp/shared_expert/swiglu"]
           if moe else ["swiglu"])
    for text, fn, top, kernel in (
            (decode, "decode_k", "decode", "paged_attention"),
            (prefill, "prefill", "prefill", "flash_prefill")):
        assert "tpu_custom_call" in text
        for scope in [f"jit({fn})/{top}/", "/lm_head/", "/sampling/",
                      f"/attention/{kernel}/pallas_call"] + mlp:
            assert scope in text, (top, scope)


# deepseek_v32 at narrow widths, 128-lane index keys: 4 index heads keep the
# gathered form of the index scores, 8 take the kernel (index_scores_supported)
V32_NARROW = {
    "model_type": "deepseek_v32", "vocab_size": 2048, "hidden_size": 256,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "q_lora_rank": 128, "kv_lora_rank": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 32, "v_head_dim": 64, "index_n_heads": 4,
    "index_head_dim": 128, "index_topk": 64, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "expert_share_index": 1, "n_group": 2,
    "topk_group": 1, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 1024}


def _benchmark_hf(path):
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", path)) as f:
        hf = json.load(f)
    extras = ("source", "reduced", "assumed", "deployment",
              "memory_analysis", "notes", "reference")
    return {k: v for k, v in hf.items() if k not in extras}


# kimi-k2.7-code's cell: table positions, prefill chunk, slots
KIMI_TABLE, KIMI_CHUNK, KIMI_SLOTS = 25600, 1024, 16


# The hybrid MLA models of this file's builds (a dense prefix, then expert
# layers: mla._run_layers' two scans), each at two dense and three expert
# layers — so that a stack of every layer (5), of one kind (2 or 3) and a
# layer alone (1) differ in their leading dimension — with int8 weights, as
# the benchmark's cells serve them.
_HYBRID_DEPTH = {"num_hidden_layers": 5, "first_k_dense_replace": 2}
_HYBRID_MODELS = ("deepseek_v32-narrow", "kimi-k2.7-code",
                  "tiny-deepseek-v2")
_HYBRID_COMPILED = {}       # (model, program) -> (cfg, params, kv, text)


def _hybrid_lowered(model, program, place, attn_impl):
    """→ (cfg, params, kv, the lowered ``program``: "prefill" or "decode").
    ``place`` makes an argument's ShapeDtypeStruct from a shape and a
    dtype. The narrow deepseek_v32 and the benchmark's tiny-deepseek-v2
    through an engine's own ``_prefill_jit`` / ``_decode_k_jit``;
    kimi-k2.7-code at the published widths and the cell's shapes (a
    25,600-position table, a 1,024-token chunk, 16 slots) through the
    model's forwards, nothing placed."""
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, mla
    from dynamo_tpu.engine.quant import init_params_quantized
    i32, f32 = jnp.int32, jnp.float32

    def placed(tree):
        return jax.tree.map(lambda x: place(x.shape, x.dtype), tree)

    if model == "kimi-k2.7-code":
        cfg = ModelConfig.from_hf_config(dict(
            _benchmark_hf("configs/kimi-k2.7-code.json"), **_HYBRID_DEPTH))
        bs, blocks, M = 16, 4096, KIMI_TABLE // 16
        statics = llama.ModelStatics(cfg=cfg, block_size=bs,
                                     attn_impl=attn_impl)
        params = placed(jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
            dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg)))
        kv = placed(jax.eval_shape(
            lambda: mla.init_kv_cache(cfg, blocks, bs)))
        if program == "prefill":
            lowered = jax.jit(lambda p, c, t, bt, sp, tl: mla.prefill_forward(
                p, c, t, bt, sp, tl, statics)).lower(
                params, kv, place((KIMI_CHUNK,), i32), place((M,), i32),
                place((), i32), place((), i32))
        else:
            lowered = jax.jit(lambda p, c, t, pos, bt: mla.decode_forward(
                p, c, t, pos, bt, statics)).lower(
                params, kv, place((KIMI_SLOTS,), i32),
                place((KIMI_SLOTS,), i32), place((KIMI_SLOTS, M), i32))
        return cfg, params, kv, lowered
    # dense_down [k, F, D] apart from wo [L, H·v, D]: F is not H·v
    hf = (dict(V32_NARROW, intermediate_size=768)
          if model == "deepseek_v32-narrow"
          else _benchmark_hf(f"fixtures/{model}.json"))
    cfg = ModelConfig.from_hf_config(dict(hf, **_HYBRID_DEPTH))
    B, M, T = 8, 16, 128
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=16, num_kv_blocks=64,
        max_num_seqs=B, prefill_buckets=[T], quantization="int8"),
        attn_impl=attn_impl)
    params, kv = placed((core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = place(key.shape, key.dtype)
    if program == "prefill":
        lowered = core._prefill_jit.lower(
            params, kv, place((T,), i32), place((M,), i32), place((), i32),
            place((), i32), key, place((), f32), place((), i32),
            place((), f32))
    else:
        lowered = core._decode_k_jit.lower(
            params, kv, place((B,), i32), place((B,), i32),
            place((B, M), i32), place((B,), i32), place((B,), i32),
            place((B,), f32), place((B,), i32), place((B,), f32),
            place((1, B), i32), place((1, B), jnp.bool_), key)
    return cfg, params, kv, lowered


def _hybrid_compiled(model, program, one_chip, monkeypatch):
    """→ (cfg, params, kv, the program's compiled text for the described
    chip, kernels on), built once for the tests of this file that read it."""
    from dynamo_tpu.engine.models import llama
    if (model, program) not in _HYBRID_COMPILED:
        # code that asks jax.devices() sees the CPU here
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        monkeypatch.setattr(llama, "_on_tpu", lambda: True)
        cfg, params, kv, lowered = _hybrid_lowered(
            model, program, lambda shape, dtype: jax.ShapeDtypeStruct(
                shape, dtype, sharding=one_chip), "pallas")
        _HYBRID_COMPILED[model, program] = (
            cfg, params, kv, lowered.compile().as_text())
    return _HYBRID_COMPILED[model, program]


def test_sparse_attention_programs_carry_stable_names(one_chip, monkeypatch):
    """deepseek_v32 on models/mla.py: the engine's prefill and decode
    programs compile for the chip at narrow widths with both caches, and
    name the three stages of DeepSeek Sparse Attention — ``indexer``,
    ``dsa_select``, ``sparse_attention`` — beside the scopes every model
    has. The gathers and einsums are XLA's; the exact top-k is the Pallas
    call ``dsa_select_compact`` (a threshold search and a compaction of
    ONE packed key, engine/select_compact.py): under ``dsa_select`` no
    program sorts anything, by score or otherwise."""
    import re
    _cfg, _params, kv, decode = _hybrid_compiled(
        "deepseek_v32-narrow", "decode", one_chip, monkeypatch)
    prefill = _hybrid_compiled(
        "deepseek_v32-narrow", "prefill", one_chip, monkeypatch)[3]
    assert set(kv) == {"kv", "idx"}
    for text, fn, top in ((decode, "decode_k", "decode"),
                          (prefill, "prefill", "prefill")):
        for scope in (f"jit({fn})/{top}/", "/lm_head/", "/sampling/",
                      "/indexer/", "/dsa_select/", "/sparse_attention/",
                      "run_experts_dense"):
            assert scope in text, (top, scope)
        select = [line for line in text.splitlines()
                  if "/dsa_select/" in line]
        assert not [line for line in select
                    if re.search(r"[\])}] sort\(", line)], top
        calls = [line for line in select if "tpu_custom_call" in line
                 and "dsa_select_compact" in line]
        assert calls, top
        # order bits and one packed key in, one array out: no score, and
        # nothing rides along
        for line in calls:
            operands = re.search(r" custom-call\(([^)]*)\)", line).group(1)
            assert len(operands.split(",")) == 2, (top, line)
            assert re.search(r"= s32\[", line), (top, line)


def test_index_scores_come_straight_from_the_pool_in_decode(one_chip,
                                                           monkeypatch):
    """With index heads on the sublane tiling (8 here, 64 published) the
    decode program of a deepseek_v32 engine scores the index keys in the
    Pallas call ``index_scores`` under ``dsa_select``, handed the pool
    whole: no index key is gathered by block and no ``[B, S, dI]`` copy of
    them exists. The prefill chunk keeps the gathered form: its compiled
    text is the one it has with the kernel ruled out."""
    import re
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, mla
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    cfg = ModelConfig.from_hf_config(dict(V32_NARROW, index_n_heads=8))
    B, M, T, bs, dI = 8, 16, 128, 16, 128
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=bs, num_kv_blocks=64,
        max_num_seqs=B, prefill_buckets=[T]), attn_impl="pallas")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32

    def decode_text():
        return core._decode_k_jit.lower(
            params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
            s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
            s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_),
            s(key.shape, key.dtype)).compile().as_text()

    def prefill_text():      # lowered: the compiled text names line numbers
        return core._prefill_jit.lower(
            params, kv, s((T,), i32), s((M,), i32), s((), i32), s((), i32),
            s(key.shape, key.dtype), s((), f32), s((), i32),
            s((), f32)).as_text()

    def kernel_calls(text):      # (the text also names this test's frames)
        return [line for line in text.splitlines()
                if "tpu_custom_call" in line and "index_scores" in line]

    decode, prefill = decode_text(), prefill_text()
    calls = kernel_calls(decode)
    assert calls and all("/dsa_select/" in line for line in calls)
    # the pool of both layers goes in as it lies, [L * rows, dI]
    rows = 2 * 64 * bs
    assert all(f"bf16[{rows},{dI}]" in line for line in calls), calls[0]
    gathered = re.compile(
        rf"bf16\[{B * M},{bs},{dI}\]|bf16\[{B},{M * bs},{dI}\]"
        rf"|bf16\[{B},{M},{bs},{dI}\]")
    assert not [line for line in decode.splitlines()
                if gathered.search(line.split(" = ")[-1].split("(")[0])]
    assert "dsa_select_compact" in decode
    assert "dsa_select_compact" in prefill and "index_scores" not in prefill
    # the chunk's one shared table: gathered once ([M, bs, dI]), as before
    monkeypatch.setattr(mla, "index_scores_supported", lambda *a: False)
    core._compile_jits()
    assert prefill_text() == prefill
    assert not kernel_calls(decode_text())


def test_index_scores_kernel_builds_at_the_published_sizes(one_chip):
    """``index_scores`` at DeepSeek-V3.2's decode step as the benchmark's
    cell serves it: 64 slots, 64 index heads of 128, tables of 1,088
    blocks of 16 into a seven-layer pool of 12,288 blocks, at the depth
    ``key_wave_blocks`` gives (64) and at the two it was measured against."""
    from dynamo_tpu.engine.index_scores import (index_scores_pallas,
                                                 key_wave_blocks)
    B, J, dI, bs, M, rows = 64, 64, 128, 16, 1088, 7 * 12288 * 16
    assert key_wave_blocks(M, bs, dI) == 64
    for depth in (None, 16, 32):
        text = jax.jit(lambda q, w, k, t, n, depth=depth: index_scores_pallas(
            q, w, k, t, n, block_size=bs, chunk_blocks=depth)).lower(
            *[jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in (((B, J, dI), jnp.bfloat16),
                                   ((B, J), jnp.float32),
                                   ((rows, dI), jnp.bfloat16),
                                   ((B, M), jnp.int32), ((B,), jnp.int32))]
        ).compile().as_text()
        assert "tpu_custom_call" in text and "index_scores" in text
        assert f"bf16[{B},{M * bs},{dI}]" not in text


def test_window_latent_read_builds_at_the_published_sizes(one_chip):
    """dots3-note-prev's window layers in a decode step as the benchmark's
    cell serves them (models/mla.py ``attn_s``): the paged-attention kernel
    in its one-head latent form at 64 heads x 1,152 lanes (rank 1,024 + rope
    64, padded), v aliased to the first 1,024 lanes, over a table of 34
    window-pool blocks a sequence with a lower bound (``win_lo``), one wave
    of 34 blocks, into a six-layer pool of 9,395 blocks."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.models import mla
    cfg = ModelConfig.from_hf_config(
        _benchmark_hf("configs/dots3-note-prev.json"))
    geo = cfg.swa_geometry()
    B, H, W, bs = 64, geo.num_heads, mla.latent_row_lanes(geo), 16
    R = mla.swa_ring_blocks(cfg, bs)
    assert (H, W, R, geo.kv_lora_rank) == (64, 1152, 34, 1024)
    assert A.pallas_supported(H, 1, W, bs, kv_dtype=jnp.bfloat16)

    def fn(q, pool, tables, lens, lo):
        return A.paged_attention_pallas(
            q, pool, pool, tables, lens, block_size=bs, scale=256 ** -0.5,
            win_lo=lo, v_lanes=geo.kv_lora_rank,
            chunk_blocks=max(A.ATTN_CHUNK_BLOCKS, R))

    text = _compile(fn, one_chip, ((B, H, W), jnp.bfloat16),
                    ((6 * 9395 * bs, W), jnp.bfloat16), ((B, R), jnp.int32),
                    ((B,), jnp.int32), ((B,), jnp.int32)).as_text()
    assert "paged_attention" in text
    assert f"bf16[{B},{H},{geo.kv_lora_rank}]" in text      # probs . c


@pytest.mark.parametrize("family", ["tiny-dense", "tiny-qwen2moe",
                                    "tiny-deepseek-v2", "tiny-phi4flash"])
def test_other_families_never_reach_the_index_scores_kernel(family, one_chip,
                                                            monkeypatch):
    """The four families without an indexer lower their decode and prefill
    programs (kernels on, as on the chip) without a call into
    engine/index_scores.py: what PR 36 added is not in their programs.
    (Their lowered text on the CPU was byte-equal to the parent's when the
    kernel went in: CHANGES.md, PR 36.)"""
    import json
    from dynamo_tpu.engine import index_scores
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, mla

    def never(*a, **k):
        raise AssertionError("index_scores reached")

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    monkeypatch.setattr(mla, "index_scores_pallas", never)
    monkeypatch.setattr(index_scores, "index_scores_pallas", never)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "fixtures",
                           family + ".json")) as f:
        hf = json.load(f)
    extras = ("source", "reduced", "assumed", "deployment",
              "memory_analysis", "notes", "reference")
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in hf.items() if k not in extras})
    B, T = 4, 128
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=16, num_kv_blocks=64,
        max_num_seqs=B, prefill_buckets=[T], quantization="int8", seed=1))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    i32, f32 = jnp.int32, jnp.float32
    decode = core._decode_k_jit.lower(
        params, kv, s((B,), i32), s((B,), i32), s((B, core.M), i32),
        s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
        s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_), key).as_text()
    prefill = core._prefill_jit.lower(
        params, kv, s((T,), i32), s((core.M,), i32), s((), i32), s((), i32),
        key, s((), f32), s((), i32), s((), f32)).as_text()
    assert "index_scores" not in decode and "index_scores" not in prefill


@pytest.mark.parametrize("rows, values", [(64, 1), (32, 1), (64, 2)],
                         ids=["decode-64", "prefill-32", "two-values"])
def test_select_compact_builds_at_the_published_sizes(one_chip, rows, values):
    """``dsa_select_compact`` at DeepSeek-V3.2's table (17,408 positions,
    top 2,048): one packed key for a decode step's 64 slots and a prefill
    block's 32 queries, and the two-value form of a pool whose ids do not
    fit beside the position."""
    from dynamo_tpu.engine.select_compact import compact_top_k
    s = jax.ShapeDtypeStruct((rows, 17408), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda o, *v: compact_top_k(o, v, 2048)).lower(
        s, *[s] * values).compile().as_text()
    assert "dsa_select_compact" in text and "tpu_custom_call" in text
    assert not [line for line in text.splitlines() if " sort(" in line]


@pytest.mark.parametrize("case", ["kernel-qwen-widths", "prefill-1024",
                                  "prefill-2048", "decode"])
def test_routed_experts_run_grouped_in_prefill(one_chip, monkeypatch, case):
    """Above the crossover the engine's prefill program of a Qwen-like
    model (experts top-2 of 6, unnormalised, a gated shared expert, int8
    weights, the fused gate|up stack) runs the routed experts through the
    Pallas call ``grouped_experts`` under the scope ``moe_mlp``: no
    ``[E, N, ·]`` dense-over-experts intermediate, and the int8 stacks
    reach the kernel as int8 (no dequantised ``[E, D, F]`` copy). The
    decode program keeps ``run_experts_dense``. The kernel alone also
    builds at the benchmark's Qwen1.5-MoE widths."""
    import re

    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.grouped_matmul import grouped_matmul
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.engine.quant import QuantizedArray
    if case == "kernel-qwen-widths":
        E, D, F, pairs = 60, 2048, 1408, 8192
        for K, N in ((D, 2 * F), (F, D)):
            _compile(lambda x, q, s, g: grouped_matmul(
                x, QuantizedArray(q, s), g), one_chip,
                ((pairs, K), jnp.bfloat16), ((E, K, N), jnp.int8),
                ((E, 1, N), jnp.float32), ((E,), jnp.int32))
        return
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    E, D, F = 6, 256, 384
    cfg = ModelConfig(
        vocab_size=2048, hidden_size=D, intermediate_size=F,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=64,
        max_position_embeddings=4096, num_experts=E, num_experts_per_tok=2,
        moe_norm_topk=False, shared_expert_size=512)
    B, T = 8, 2048 if case == "prefill-2048" else 1024
    core = EngineCore(cfg, EngineConfig(
        max_model_len=T + 64, kv_block_size=16, num_kv_blocks=T // 16 + 8,
        max_num_seqs=B, prefill_buckets=[T], quantization="int8"),
        attn_impl="pallas")
    M = core.M
    assert not core.statics.sharded
    assert isinstance(core.params["layers.moe_gateup"], QuantizedArray)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32
    if case == "decode":
        text = core._decode_k_jit.lower(
            params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
            s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
            s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_),
            s(key.shape, key.dtype)).compile().as_text()
        assert "moe_mlp/run_experts_dense" in text
        # the kernel's instruction and scope, not the bare word: the
        # text's file-name table may hold tests/test_grouped_experts.py
        # where that file traced shared code first in this process
        assert not re.search(r"%grouped_experts|run_experts_grouped/", text)
        return
    text = core._prefill_jit.lower(
        params, kv, s((T,), i32), s((M,), i32), s((), i32), s((), i32),
        s(key.shape, key.dtype), s((), f32), s((), i32),
        s((), f32)).compile().as_text()
    assert re.search(r"moe_mlp/run_experts_grouped/.*grouped_experts", text)
    assert "moe_mlp/shared_expert/swiglu" in text
    assert "moe_mlp/run_experts_dense" not in text
    # nothing dense over the experts: no [E, T, 2F | F | D] of any dtype
    assert not re.search(rf"\[{E},{T},({2 * F}|{F}|{D})\]", text)
    # the expert stacks enter as int8 and are never widened whole
    assert re.search(rf"s8\[2,{E},{D},{2 * F}\]", text)
    # … nor copied layer by layer out of the stack for the custom call
    assert not re.search(rf"s8\[{E},({D},{2 * F}|{F},{D})\]", text)
    assert not re.search(
        rf"(bf16|f32|f16|s32)\[(\d+,)?{E},({D},{2 * F}|{F},{D})\]", text)


def test_hybrid_cache_programs_carry_stable_names(one_chip, monkeypatch):
    """phi4flash on models/sambay.py: the engine's prefill and decode
    programs compile for the chip at narrow widths with the three kinds of
    cache, and name the new mixers — ``mamba`` (``causal_conv``, and the
    Pallas calls ``ssm_scan`` in prefill, ``ssm_step`` in decode), ``gmu``,
    ``window_attention``, ``full_attention``, ``cross_attention`` — beside
    the scopes every model has. The window layers' decode is the paged
    kernel over the slot's ring (33 blocks at the published sizes, 3 here),
    the full layer's prefill the flash kernel, and the window layers'
    prefill never holds a [T, S] float32 score tensor: a block of 256
    queries against the 256 + window keys it can reach."""
    import re
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, sambay
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    monkeypatch.setattr(sambay, "_on_tpu", lambda: True)
    cfg = ModelConfig.from_hf_config({
        "model_type": "phi4flash", "vocab_size": 2048, "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 6,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "sliding_window": 32, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True})
    B, M, T, W = 8, 64, 1024, 32
    core = EngineCore(cfg, EngineConfig(
        max_model_len=1024, kv_block_size=16, num_kv_blocks=96,
        max_num_seqs=B, prefill_buckets=[T]), attn_impl="pallas")
    R = (W // 16 + 1) * 16
    assert {k: (v.shape, v.dtype) for k, v in core.kv.items()} == {
        "k": ((1, 96 * 16, 128), jnp.bfloat16),
        "v": ((1, 96 * 16, 128), jnp.bfloat16),
        "win_k": ((1, B, R, 128), jnp.bfloat16),
        "win_v": ((1, B, R, 128), jnp.bfloat16),
        "ssm": ((2, 8, 16, 512), jnp.float32),
        "conv": ((2, 8, 3, 512), jnp.bfloat16)}

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32
    decode = core._decode_k_jit.lower(
        params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
        s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
        s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_),
        s(key.shape, key.dtype)).compile().as_text()
    prefill = core._prefill_jit.lower(
        params, kv, s((T,), i32), s((M + 1,), i32), s((), i32), s((), i32),
        s(key.shape, key.dtype), s((), f32), s((), i32),
        s((), f32)).compile().as_text()
    common = ["/lm_head/", "/sampling/", "/mamba/causal_conv/", "/gmu/",
              "/window_attention/", "/full_attention/", "/cross_attention/",
              "/swiglu/"]
    for text, fn, top, own in (
            (decode, "decode_k", "decode",
             ["/mamba/ssm_step/", "/window_attention/paged_attention/",
              "/full_attention/paged_attention/",
              "/cross_attention/paged_attention/"]),
            (prefill, "prefill", "prefill",
             ["/mamba/ssm_scan/", "/full_attention/flash_prefill/",
              "/cross_attention/paged_attention/"])):
        for scope in [f"jit({fn})/{top}/"] + common + own:
            assert scope in text, (top, scope)
    for text, kernel in ((decode, "ssm_step"), (prefill, "ssm_scan")):
        assert [line for line in text.splitlines()
                if "tpu_custom_call" in line and f"%{kernel}" in line
                and f"/mamba/{kernel}/" in line], kernel
    # the window layers' prefill: no float32 array of T x (ring + T)
    # scores, whatever the heads in front of it
    window = [line for line in prefill.splitlines()
              if "/window_attention/" in line]
    assert window
    S = R + T                      # the ring's rows before the chunk's
    last_dims = set()
    for line in window:
        for dims in re.findall(r"f32\[([\d,]+)\]", line):
            dims = [int(d) for d in dims.split(",")]
            # scores are [.., queries, keys]: never the chunk against all
            # of its keys, never the whole table
            assert dims[-1] != S and not (T in dims and S in dims), line
            if len(dims) >= 3:
                last_dims.add(dims[-1])
    assert 256 + W in last_dims, last_dims      # a block's reach


@pytest.mark.parametrize("kernel", ["ssm_scan", "ssm_step"])
def test_state_space_kernels_build_at_the_published_sizes(one_chip, kernel):
    """Phi-4-mini-flash-reasoning's widths (d_inner 5120, 16 states): the
    prompt scan at the 4,096-token bucket, and the decode update of 64
    slots in place inside the nine layers' state array."""
    from dynamo_tpu.engine import ssm
    f32, Di, N = jnp.float32, 5120, 16
    if kernel == "ssm_scan":
        T = 4096
        _compile(ssm.ssm_scan, one_chip, ((T, Di), f32), ((T, Di), f32),
                 ((T, N), f32), ((T, N), f32), ((N, Di), f32), ((N, Di), f32))
        return
    B, L = 64, 9
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((B, Di), f32), ((B, Di), f32), ((B,), f32), ((B, N), f32),
                ((B, N), f32), ((N, Di), f32), ((L * B, N, Di), f32),
                ((), jnp.int32))]
    compiled = jax.jit(ssm.ssm_step, donate_argnums=(6,)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the state is rewritten where it lies: no second copy of 189 MB
    assert compiled.memory_analysis().alias_size_in_bytes >= L * B * N * Di * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def _shape_dims(text):
    """Every array shape named in a compiled program's text, as tuples."""
    import re
    return {tuple(int(d) for d in m.split(","))
            for m in re.findall(r"\[(\d+(?:,\d+)+)\]", text)}


@pytest.mark.parametrize("program", ["prefill-1024", "decode-B16"])
def test_dense_latent_attention_builds_at_the_published_widths(
        one_chip, monkeypatch, program):
    """kimi-k2.7-code's prefill chunk and decode step (models/mla.py, no
    indexer) at the published widths and the cell's shapes — 64 heads, a
    25,600-position table, a 1,024-token chunk, 16 slots — at the depth of
    two dense and three expert layers (``_hybrid_lowered``; the layers are
    scanned: depth adds nothing to a build), int8 weights. The prefill
    walks the table by key blocks through
    the Pallas call ``mla_prefill`` inside a loop with a traced trip count;
    the decode reads the pool in the ``paged_attention`` kernel's one-head
    form at the latent wave depth (64 blocks). Neither holds an array of
    heads × chunk × table or of heads × table × head_dim in any layout or
    dtype."""
    from dynamo_tpu.engine.models import mla
    cfg, _params, _kv, text = _hybrid_compiled(
        "kimi-k2.7-code", program.split("-")[0], one_chip, monkeypatch)
    assert (cfg.num_heads, cfg.kv_lora_rank, cfg.hidden_size,
            cfg.router_width, cfg.num_experts) == (64, 512, 7168, 384, 12)
    S, T = KIMI_TABLE, KIMI_CHUNK
    assert program in (f"prefill-{T}", f"decode-B{KIMI_SLOTS}")
    if program.startswith("prefill"):
        assert "mla_prefill" in text and "while" in text
    else:
        assert "paged_attention" in text
        # a wave's double buffer: 2 x 1,024 rows of the pool
        assert mla.latent_wave_blocks(16) == 64
    assert "tpu_custom_call" in text
    H, widths = cfg.num_heads, (128, 192, 256, 320)
    heads = {H} | {H * w for w in widths}
    for dims in _shape_dims(text):
        # the table's positions beside the heads (or heads × a head width):
        # heads × chunk × table, heads × table × head_dim, any order
        assert not (S in dims and heads & set(dims)), dims
        assert not (set(dims) >= {H, T, S}), dims


@pytest.mark.parametrize("family", ["tiny-dense", "tiny-qwen2moe",
                                    "tiny-deepseek-v32", "tiny-phi4flash"])
def test_other_families_never_reach_the_blocked_dense_prefill(
        family, one_chip, monkeypatch):
    """The four families that are not dense latent attention lower their
    decode and prefill programs (kernels on, as on the chip) without
    entering ``mla._dense_chunk`` or asking for the latent decode's waves
    (``mla.latent_wave_blocks``, the one line PR 37 changed in the dense
    branch of ``decode_forward``):
    what PR 37 added is not in their programs (their lowered text on the
    CPU is byte-equal to the parent's: CHANGES.md, PR 37)."""
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, mla

    def never(*a, **k):
        raise AssertionError("the dense latent-attention branch reached")

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(llama, "_on_tpu", lambda: True)
    monkeypatch.setattr(mla, "_dense_chunk", never)
    monkeypatch.setattr(mla, "latent_wave_blocks", never)
    cfg = ModelConfig.from_hf_config(
        _benchmark_hf(f"fixtures/{family}.json"))
    B, T = 4, 128
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=16, num_kv_blocks=64,
        max_num_seqs=B, prefill_buckets=[T], quantization="int8", seed=1))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, kv = jax.tree.map(lambda x: s(x.shape, x.dtype),
                              (core.params, core.kv))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    i32, f32 = jnp.int32, jnp.float32
    M = core.M
    core._decode_k_jit.lower(
        params, kv, s((B,), i32), s((B,), i32), s((B, M), i32),
        s((B,), i32), s((B,), i32), s((B,), f32), s((B,), i32),
        s((B,), f32), s((1, B), i32), s((1, B), jnp.bool_), key)
    core._prefill_jit.lower(
        params, kv, s((T,), i32), s((M,), i32), s((), i32), s((), i32),
        key, s((), f32), s((), i32), s((), f32))


def _outside_fusions(text):
    """(result dtype, result dims, opcode, line) of every instruction of a
    compiled program's text that stands outside its fused computations: in
    the entry, a loop's body or condition. What a fusion holds inside (a
    layer's ``dynamic-slice`` of a stack among them) is read by the
    fusion's consumer in place; what stands outside is an array of its own
    in memory."""
    import re
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
        elif line.startswith("}"):
            inside = None
        elif inside is not None and inside not in fused:
            m = re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                         r"([\w\-]+)\(", line)
            if m:
                yield (m.group(1), tuple(int(d) for d in m.group(2).split(",")
                                         if d), m.group(3), line)


def _layer_stacks(params, cfg):
    """→ (the arrays of the attention stacks, which hold every layer:
    leading dimension L; those of the stacks of one layer kind: k or
    L - k), each {(dtype, shape)}, matrices only."""
    L, k = cfg.num_layers, cfg.first_k_dense
    assert len({1, k, L - k, L}) == 4
    whole, kind = set(), set()
    for name, w in params.items():
        if not name.startswith("layers."):
            continue
        for x in jax.tree.leaves(w):
            if x.ndim >= 3:
                (whole if x.shape[0] == L else kind).add(
                    (jnp.dtype(x.dtype).name, tuple(x.shape)))
    assert whole and all(s[0] in (k, L - k) for _d, s in kind)
    return whole, kind


_HLO_DTYPES = {"int8": "s8", "bfloat16": "bf16", "float32": "f32"}


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("model", _HYBRID_MODELS)
def test_hybrid_scans_copy_no_part_of_an_attention_stack(
        model, program, one_chip, monkeypatch):
    """A hybrid MLA model's two scans (mla._run_layers: a dense prefix,
    then expert layers) leave the ``[L, ...]`` attention stacks whole
    beside them and read layer ``li`` in place. In the program compiled
    for the chip no instruction outside the fusions — no ``slice``,
    ``dynamic-slice``, ``copy``, nor a fusion of one — RESULTS in an array
    of a stack's trailing shape behind a leading ``k`` or ``L - k`` (the
    parent's ``stack[n][:k]`` / ``stack[n][k:]``: 4.1 of a 39.2 ms decode
    step at the DeepSeek-V3.2 widths, PERF.md PR 36), and none in a layer
    of an int8 stack alone: every int8 weight goes into its consumer's
    fusion from where it lies. (``wkv_b``, bf16, is relaid a layer for
    the absorbed einsums, as before: mla._split_wkv_b.)"""
    cfg, params, _kv, text = _hybrid_compiled(model, program, one_chip,
                                              monkeypatch)
    L, k = cfg.num_layers, cfg.first_k_dense
    whole, kind = _layer_stacks(params, cfg)
    parts = {(d, (n,) + s[1:]) for d, s in whole for n in (k, L - k)}
    layers = {(d, lead + s[1:]) for d, s in whole if d == "int8"
              for lead in ((), (1,))
              if not any(s[1:] == t[1:] for _d, t in kind)}
    forbidden = {(_HLO_DTYPES[d], shape)
                 for d, shape in (parts - kind) | layers}
    seen = False
    for dtype, dims, op, line in _outside_fusions(text):
        if op in ("parameter", "get-tuple-element"):
            continue
        seen = True
        assert (dtype, dims) not in forbidden, line[:300]
    assert seen and "while" in text


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("model", _HYBRID_MODELS)
def test_hybrid_scans_lower_no_slice_of_an_attention_stack(model, program):
    """The same programs as JAX lowers them here, for the CPU (no chip
    described, no kernel): no ``stablehlo.slice`` takes an ``[L, ...]``
    attention stack as its operand — vectors (``ln1``, the norms) and
    scales among them — and each stack reaches a loop whole, where a
    ``dynamic_slice`` reads the layer."""
    import re
    cfg, params, _kv, lowered = _hybrid_lowered(
        model, program, jax.ShapeDtypeStruct, "xla")
    L = cfg.num_layers
    mlir = {"int8": "i8", "bfloat16": "bf16", "float32": "f32"}
    stacks = {"tensor<" + "x".join(map(str, x.shape)) + "x"
              + mlir[jnp.dtype(x.dtype).name] + ">"
              for name, w in params.items() if name.startswith("layers.")
              for x in jax.tree.leaves(w) if x.shape[0] == L}
    assert len(stacks) >= 6
    text = lowered.as_text()
    sliced = [line for line in text.splitlines()
              if re.search(r"stablehlo\.slice ", line)
              and line.split(" : (")[-1].split(")")[0] in stacks]
    assert not sliced, sliced[0][:300]
    for stack in stacks:
        assert re.search(r"stablehlo\.dynamic_slice .*\(" + re.escape(stack),
                         text), stack


# ---- mimo_v2: grouped-query reads of two geometries (models/mimo.py) ------

def _mimo_cfg():
    from dynamo_tpu.engine.config import ModelConfig
    cfg = ModelConfig.from_hf_config(_benchmark_hf("configs/mimo-v2.5.json"))
    geo = cfg.swa_gqa_geometry()
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.v_head_dim) == (64, 4, 192, 128)
    assert (geo.num_heads, geo.num_kv_heads, geo.head_dim,
            geo.v_head_dim) == (64, 8, 192, 128)
    return cfg, geo


@pytest.mark.parametrize("kind", ["full", "window"])
def test_gqa_decode_reads_build_at_the_published_sizes(one_chip, kind):
    """MiMo-V2.5's two decode reads as the benchmark's cell serves them
    (models/mimo.py ``decode_forward``): 64 slots, 64 query heads of 192
    lanes over value heads of 128. Full: 4 kv heads (rows of 768 | 512
    lanes: the ROW lies on 128-lane tiles, a head's key at lane 192 x kh
    does not), tables of 2,272 blocks of 16 (36,352 tokens) into a
    three-layer pool of 24,576 blocks, waves of 512 rows. Window: 8 kv
    heads (rows of 1,536 | 1,024), a ring of 9 window-pool blocks with a
    lower bound and the sink, one wave, a ten-layer pool of 2,650."""
    from dynamo_tpu.engine.models import mimo, mla
    cfg, geo = _mimo_cfg()
    B, bs = 64, 16
    R = mla.swa_ring_blocks(cfg, bs)
    assert R == 9 and mimo.decode_kernels_tile(cfg, bs)
    c, M, layers, blocks, name, chunk = (
        (cfg, 36352 // bs, 3, 24576, "gqa_full_read",
         mimo.GQA_WAVE_ROWS // bs) if kind == "full" else
        (geo, R, 10, 2650, "gqa_window_read", R))
    ck, cv = mimo.row_lanes(c)

    def fn(q, k, v, tables, lens, lo, sink):
        return A.paged_attention(
            q, k, v, tables, lens, block_size=bs, scale=192 ** -0.5,
            impl="pallas", kv_heads=c.num_kv_heads, v_dim=c.v_head_dim,
            chunk_blocks=chunk, name=name,
            **({"win_lo": lo, "sink": sink} if kind == "window" else {}))

    text = _compile(fn, one_chip, ((B, 64, 192), jnp.bfloat16),
                    ((layers * blocks * bs, ck), jnp.bfloat16),
                    ((layers * blocks * bs, cv), jnp.bfloat16),
                    ((B, M), jnp.int32), ((B,), jnp.int32),
                    ((B,), jnp.int32), ((64,), jnp.float32)).as_text()
    assert name in text
    assert f"bf16[{B},{M * bs}," not in text        # no gathered table


@pytest.mark.parametrize("kind", ["full", "window"])
def test_gqa_prefill_reads_build_at_the_published_sizes(one_chip, kind):
    """The two prefill reads of a 256-token chunk (models/mimo.py
    ``_full_chunk`` / ``_window_chunk``): the full layers' key-block walk
    over a 36,352-token table in the flash kernel's partial form, keys of
    192 and values of 128 lanes; the window layers' chunk + 127 rows from
    the window pool with the window and the sink."""
    from dynamo_tpu.engine.models import mimo
    from dynamo_tpu.engine.models.llama import ModelStatics
    cfg, geo = _mimo_cfg()
    bs, T, M = 16, 256, 36352 // 16
    statics = ModelStatics(cfg=cfg, block_size=bs, attn_impl="pallas")
    assert mimo._kernel_form(statics, True, "flash prefill") is True
    c, layers, blocks = (cfg, 3, 24576) if kind == "full" else (geo, 10, 2650)
    ck, cv = mimo.row_lanes(c)

    def fn(q, k, v, table, start, seq_len, sink):
        if kind == "full":
            return mimo._full_chunk(q, k, v, 1, table, start, seq_len, cfg,
                                    bs, True)
        return mimo._window_chunk(q, k, v, 7, table, start, seq_len, geo,
                                  cfg.swa_window, bs, sink, True)

    text = _compile(fn, one_chip, ((T, 64, 192), jnp.bfloat16),
                    ((layers, blocks * bs, ck), jnp.bfloat16),
                    ((layers, blocks * bs, cv), jnp.bfloat16),
                    ((M,), jnp.int32), ((), jnp.int32), ((), jnp.int32),
                    ((64,), jnp.float32)).as_text()
    assert f"gqa_{kind}_prefill" in text
    # no layer's slice of the pool is copied to be read from
    assert f"bf16[{blocks * bs},{ck}]" not in text
    assert f"bf16[1,{blocks * bs},{ck}]" not in text
