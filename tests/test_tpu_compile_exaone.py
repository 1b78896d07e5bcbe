"""K-EXAONE's deviceless builds for a described ``v5e:2x2`` (the helpers and
fixtures are ``tests/test_tpu_compile.py``'s): the two served programs of a
resident multi-token-prediction drafter, whole, at the published widths, the
three decode reads of its two-row step (one pass over a slot's cache for both
rows), and the paged kernel's traced program at one row a sequence, held to
what it was before it took ``rows``. A file of its own so that
``--dist loadfile`` can give its ~2 minutes to another worker than
``test_tpu_compile.py``'s, the longest file of tier 1."""

import hashlib
import os

import jax
import jax.numpy as jnp
import pytest

from test_tpu_compile import (A, _benchmark_hf, _compile,  # noqa: F401
                              one_chip, topo)

def _exaone_shell(monkeypatch):
    """k-exaone-236b's engine as ``benchmark/compile_check.py`` builds one
    (no weights, no pool: the attributes ``_compile_jits`` reads), with the
    drafter resident as ``EngineCore.__init__`` sets it under the
    deployment's ``--spec-k 1``, and both pools at the ENGINE's sizes."""
    import json
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import llama, mimo
    from dynamo_tpu.engine.quant import init_params_quantized
    from dynamo_tpu.launch import run as launcher
    for mod in (A, llama, mimo):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "k-exaone-236b.json")) as f:
        flags = json.load(f)["deployment"]["flags"]
    cfg = ModelConfig.from_hf_config(
        _benchmark_hf("configs/k-exaone-236b.json"))
    e = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *flags]))
    assert (cfg.mtp_layers, e.spec_k, e.prefill_chunk) == (1, 1, 512)
    core = object.__new__(EngineCore)
    core.cfg, core.mesh, core.pp = e, None, 1
    core.model_mod, core.resident_drafter = llama, True
    core.statics = llama.ModelStatics(
        cfg=cfg, block_size=e.kv_block_size, attn_impl="pallas",
        kv_coalesce=e.kv_contig_alloc, table_blocks=e.max_blocks_per_seq)
    core._compile_jits()
    layout = llama.cache_layout(cfg, e.kv_block_size)
    win = layout.window_pool_blocks(e.num_kv_blocks, e.max_num_seqs,
                                    e.prefill_chunk)
    params = jax.eval_shape(lambda: llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg))
    kv = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, e.num_kv_blocks, e.kv_block_size, win_blocks=win))
    return cfg, e, core, layout, params, kv


@pytest.mark.parametrize("program", ["prefill-512", "decode_mtp-B64"])
def test_exaone_served_programs_build_at_the_published_sizes(
        one_chip, monkeypatch, program):
    """K-EXAONE's two served programs, whole, for the described chip: the
    chunk prefill with the module's tail and the two-row step of 64 slots
    (128 rows through 2 F + 6 S layers, both pools and the module's block),
    int8 weights, 20,480 paged and 2,731 window blocks (a ring of 10: the
    window's nine and the row a chained step may run ahead). Every read is its
    Pallas kernel under its own name, the module's apart from the model's,
    and in the step a call is 64 sequences of two rows each (query tiles of
    2 x 64 sublanes), not 128 of one; weights + pools fill 60% of the chip
    and the program fits beside them."""
    cfg, e, core, layout, params, kv = _exaone_shell(monkeypatch)
    place = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=one_chip), t)
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    i32, f32, i64 = jnp.int32, jnp.float32, jnp.int64
    M, B, R = e.max_blocks_per_seq, e.max_num_seqs, layout.ring_blocks
    assert (M, B, R, kv["k"].shape[0], kv["win_k"].shape[0]) == (
        449, 64, 10, 3, 6)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = s(key.shape, key.dtype)
    if program == "prefill-512":
        compiled = core._prefill_jit.lower(
            place(params), place(kv), s((512,), i32), s((2 * M,), i32),
            s((), i32), s((), i32), key, s((), f32), s((), i32), s((), f32),
            s((), i32)).compile()
        names = ("gqa_full_prefill", "gqa_window_prefill",
                 "mtp_full_prefill")
    else:
        # the loop's form: the carry of the dispatch before and the mask
        compiled = core._verify_jit.lower(
            place(params), place(kv), s((B, 2), i32), s((B,), i32),
            s((B, M + R), i32), s((B,), i64), s((B,), i64), s((B,), f32),
            s((B,), i32), s((B,), f32), (s((B,), i32),) * 3,
            s((B,), jnp.bool_)).compile()
        names = ("gqa_full_read", "gqa_window_read", "mtp_full_read")
    text = compiled.as_text()
    if program == "decode_mtp-B64":
        # the kernels' sparse-slotted queries: [slots, rows x heads, lanes]
        assert f"bf16[{B},128,1024]" in text
        assert f"bf16[{2 * B},64,1024]" not in text
    assert all(n in text for n in names), [n for n in names if n not in text]
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes
    limit = 16 * 2 ** 30
    assert 0.60 * limit < held and held + m.temp_size_in_bytes < 0.9 * limit


@pytest.mark.parametrize("read", ["gqa_full_read", "mtp_full_read",
                                  "gqa_window_read"])
def test_exaone_two_row_reads_build_at_the_published_sizes(one_chip, read):
    """The three decode reads of the two-row step as the cell serves them:
    64 sequences of two rows (``rows=2``: a slot's cache fetched once for
    both, the query tile [G, 2 x 64, 1024]), 64 query heads over 8 kv heads
    of 128 lanes, rows of 1,024 | 1,024 lanes. Full and the module's: tables
    of 449 blocks into the three-layer paged pool of 20,480; window: a ring
    of 10 blocks of the six-layer pool of 2,731 with a lower bound, no
    sink."""
    from dynamo_tpu.engine.models import mimo
    slots, rows, bs, H, KVH, d = 64, 2, 16, 64, 8, 128
    window = read == "gqa_window_read"
    M, layers, blocks, chunk = ((10, 6, 2731, 10) if window else
                                (449, 3, 20480, mimo.GQA_WAVE_ROWS // bs))

    def fn(q, k, v, tables, lens, lo):
        return A.paged_attention(
            q, k, v, tables, lens, block_size=bs, scale=d ** -0.5,
            impl="pallas", kv_heads=KVH, v_dim=d, chunk_blocks=chunk,
            name=read, rows=rows, **({"win_lo": lo} if window else {}))

    text = _compile(fn, one_chip, ((slots * rows, H, d), jnp.bfloat16),
                    ((layers * blocks * bs, KVH * d), jnp.bfloat16),
                    ((layers * blocks * bs, KVH * d), jnp.bfloat16),
                    ((slots, M), jnp.int32), ((slots,), jnp.int32),
                    ((slots * rows,), jnp.int32)).as_text()   # a bound a row
    assert read in text
    assert f"bf16[{slots},{rows * H},{KVH * d}]" in text    # the query tile
    assert f"bf16[{slots * rows},{M * bs}," not in text     # no gathered table


# sha256 of the traced program (``jax.make_jaxpr``: the wrapper's ops and the
# kernel's body, op for op, without source lines) of three ``rows == 1`` calls
# of the paged kernel: taken on the commit before it learned ``rows`` and
# again, with ``rows`` at 1 throughout, when the kernel's sequence group and a
# wave's block copies became loops on the device (PR 57: a body of 1,087-1,221
# lines where the unrolled one had 3,544-4,068; the outputs bit-equal)
ONE_ROW_PROGRAMS = {
    "llama": (
        "791343932fbb8328c98f1b1cd2b9c1467d020f0877f7c50a363dd0ec0a9ea659",
        dict(chunk_blocks=4), (32, 128), (1024, 1024)),
    "mla": (
        "6a77e88d0e5f56b1d81a91c1d4b6cc21c804952d55f4f20c3055fe6ab445e3df",
        dict(kv_heads=1, v_lanes=512, chunk_blocks=4), (16, 640), (640, 640)),
    "mimo": (
        "f15e100ba12b17e220db605a2222a6e420fdefef1b1f5ed63cbaad8a36c2856e",
        dict(kv_heads=4, v_dim=128, chunk_blocks=4, name="gqa_window_read"),
        (16, 192), (768, 512)),
}


@pytest.mark.parametrize("caller", list(ONE_ROW_PROGRAMS))
def test_one_row_a_sequence_traces_to_the_program_it_was(caller):
    """``rows`` defaults to 1, and at 1 the call is the program every other
    caller had: llama's plain read, mla's absorbed decode (v aliases k),
    mimo's window read (value heads of their own width, a lower bound, a
    sink, a name). Eight cells' decode steps are this program."""
    sha, kw, (H, d), (ck, cv) = ONE_ROW_PROGRAMS[caller]
    B, M, ntok = 4, 8, 64 * 16
    s = jax.ShapeDtypeStruct
    args = [s((B, H, d), jnp.bfloat16), s((ntok, ck), jnp.bfloat16),
            s((ntok, cv), jnp.bfloat16), s((B, M), jnp.int32),
            s((B,), jnp.int32)]
    if caller == "mimo":
        args += [s((B,), jnp.int32), s((H,), jnp.float32)]

    def fn(q, k, v, tables, lens, *lo_sink):
        extra = dict(zip(("win_lo", "sink"), lo_sink))
        return A.paged_attention(q, k, v, tables, lens, block_size=16,
                                 scale=0.1, impl="pallas", **kw, **extra)

    text = str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == sha
