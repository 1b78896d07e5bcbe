"""MLA (deepseek_v2) model module: HF torch parity for prefill and the
ABSORBED decode over the paged latent-KV cache, chunked-prefill
equivalence, and the latent cache geometry.

Commit-1 scope (ROUND4.md round-5 plan brought forward): the pure model
module with the llama-compatible forward contract; engine/serving
integration and the deepseek MoE variants follow. The family stays
rejected in from_hf_config until the engine serves it.
"""

import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine import mla_prefill
from dynamo_tpu.engine.models import mla
from dynamo_tpu.engine.models.llama import ModelStatics

BS = 8
NUM_BLOCKS = 16


def _cfg(q_lora: int = 0) -> ModelConfig:
    return ModelConfig(
        model_type="deepseek_v2", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=24,                     # qk dim (nope+rope) — scale base
        max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        q_lora_rank=q_lora, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)


def _statics(cfg):
    return ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")


def _to_hf(params, cfg):
    """Our stacked params -> HF DeepseekV2 state dict (torch [out, in])."""
    import torch

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    sd = {"model.embed_tokens.weight": t(params["embed"]),
          "model.norm.weight": t(params["final_norm"]),
          "lm_head.weight": t(params["lm_head"]).T.contiguous()}
    per = {"ln1": "input_layernorm.weight",
           "ln2": "post_attention_layernorm.weight",
           "kv_norm": "self_attn.kv_a_layernorm.weight"}
    mat = {"wq": "self_attn.q_proj.weight",
           "wq_a": "self_attn.q_a_proj.weight",
           "wq_b": "self_attn.q_b_proj.weight",
           "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
           "wkv_b": "self_attn.kv_b_proj.weight",
           "wo": "self_attn.o_proj.weight",
           "gate": "mlp.gate_proj.weight",
           "up": "mlp.up_proj.weight",
           "down": "mlp.down_proj.weight"}
    if cfg.q_lora_rank > 0:
        per["q_a_norm"] = "self_attn.q_a_layernorm.weight"
    for i in range(cfg.num_layers):
        for k, hf in per.items():
            if f"layers.{k}" in params:
                sd[f"model.layers.{i}.{hf}"] = t(params[f"layers.{k}"][i])
        for k, hf in mat.items():
            if f"layers.{k}" in params:
                sd[f"model.layers.{i}.{hf}"] = t(
                    params[f"layers.{k}"][i]).T.contiguous()
    return sd


@pytest.fixture(scope="module", params=[0, 12],
                ids=["q_proj", "q_lora"])
def mla_setup(request):
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM
    cfg = _cfg(q_lora=request.param)
    params = mla.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank or None,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        # all-dense: every layer below first_k_dense_replace uses the
        # plain MLP — the MoE variants are out of this commit's scope
        first_k_dense_replace=cfg.num_layers,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        tie_word_embeddings=False, attention_bias=False,
        attn_implementation="eager")
    hf = DeepseekV2ForCausalLM(hf_cfg)
    missing, unexpected = hf.load_state_dict(_to_hf(params, cfg),
                                             strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    hf.eval()
    return cfg, params, hf


def test_latent_cache_row_geometry():
    cfg = _cfg()
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    assert set(kv) == {"kv"}
    # per-token row = compressed latent + rope-k, padded to a 128-lane
    # multiple (latent_row_lanes — the Pallas block-DMA alignment); at
    # the real 512+64 geometry that is 640 lanes vs H*(192+128) for an
    # expanded cache — the serving win
    assert kv["kv"].shape == (2, NUM_BLOCKS * BS,
                              mla.latent_row_lanes(cfg))
    assert mla.latent_row_lanes(cfg) == 128       # pad128(16 + 8)
    big = dataclasses.replace(cfg, kv_lora_rank=512, qk_rope_head_dim=64)
    assert mla.latent_row_lanes(big) == 640
    # int8 pads too: pad128(576 + 128) = 768 — the alignment that lets
    # the sectioned-int8 kernel mode block-DMA the rows
    assert mla.latent_row_lanes(big, "int8") == 768


def test_mla_prefill_matches_hf(mla_setup):
    import torch
    cfg, params, hf = mla_setup
    rng = np.random.default_rng(9)
    tokens = rng.integers(1, cfg.vocab_size, size=21).tolist()
    with torch.no_grad():
        ref = hf(torch.tensor([tokens])).logits[0, -1].numpy()
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 32
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    logits, kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(logits), ref,
                               rtol=3e-4, atol=3e-4)


def test_mla_decode_matches_hf_teacher_forced(mla_setup):
    """The ABSORBED decode (latent-row reads only) must equal HF's
    expanded-cache attention step for step."""
    import torch
    cfg, params, hf = mla_setup
    rng = np.random.default_rng(10)
    tokens = rng.integers(1, cfg.vocab_size, size=12).tolist()
    steps = 6
    with torch.no_grad():
        ref_all = hf(torch.tensor(
            [tokens + [5] * steps])).logits[0].numpy()
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 32
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    _lg, kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    tables = table[None, :T // BS]
    for s in range(steps):
        pos = jnp.asarray([len(tokens) + s], jnp.int32)
        lg, kv = mla.decode_forward(
            params, kv, jnp.asarray([5], jnp.int32), pos,
            jnp.asarray(tables), _statics(cfg))
        np.testing.assert_allclose(
            np.asarray(lg[0]), ref_all[len(tokens) + s],
            rtol=4e-4, atol=4e-4, err_msg=f"decode step {s}")


def test_mla_chunked_prefill_matches_whole():
    """Two prefill chunks through the latent pool == one whole-prompt
    prefill (the start_pos > 0 path that chunked prefill and prefix
    reuse share)."""
    cfg = _cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(6),
                             dtype=jnp.float32)
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, cfg.vocab_size, size=24).tolist()
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:4] = np.arange(1, 5)

    kv1 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 32
    padded = np.zeros((T,), np.int32)
    padded[:24] = tokens
    want, kv1 = mla.prefill_forward(
        params, kv1, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(24, jnp.int32),
        _statics(cfg))

    kv2 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    c1 = np.zeros((16,), np.int32)
    c1[:16] = tokens[:16]
    _g, kv2 = mla.prefill_forward(
        params, kv2, jnp.asarray(c1), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(16, jnp.int32),
        _statics(cfg))
    c2 = np.zeros((16,), np.int32)
    c2[:8] = tokens[16:]
    got, kv2 = mla.prefill_forward(
        params, kv2, jnp.asarray(c2), jnp.asarray(table),
        jnp.asarray(16, jnp.int32), jnp.asarray(8, jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kv2["kv"]),
                               np.asarray(kv1["kv"]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mscale,mscale_all", [(0.707, 0.707), (1.0, 0.5)],
                         ids=["v2-style", "att!=1"])
def test_mla_yarn_rope_matches_hf(mscale, mscale_all):
    """yarn rope scaling (every released DeepSeek-V2 checkpoint): the
    NTK frequency blend AND the inferred attention factor must match HF
    — v2's mscale == mscale_all_dim gives factor 1.0, the second case
    forces a non-unit cos/sin scaling so the wiring can't be skipped."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    from dynamo_tpu.engine.config import RopeScaling
    cfg = _cfg()
    rs = {"rope_type": "yarn", "factor": 4.0, "mscale": mscale,
          "mscale_all_dim": mscale_all, "beta_fast": 32, "beta_slow": 1,
          "original_max_position_embeddings": 64}
    cfg.rope_scaling = RopeScaling(
        rope_type="yarn", factor=4.0, mscale=mscale,
        mscale_all_dim=mscale_all, beta_fast=32, beta_slow=1,
        original_max_position_embeddings=64)
    params = mla.init_params(cfg, jax.random.PRNGKey(8), dtype=jnp.float32)
    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        first_k_dense_replace=cfg.num_layers,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=rs, tie_word_embeddings=False,
        attention_bias=False, attn_implementation="eager")
    hf = DeepseekV2ForCausalLM(hf_cfg)
    missing, unexpected = hf.load_state_dict(_to_hf(params, cfg),
                                             strict=False)
    assert not missing and not unexpected
    hf.eval()

    rng = np.random.default_rng(12)
    tokens = rng.integers(1, cfg.vocab_size, size=90).tolist()
    with torch.no_grad():
        ref = hf(torch.tensor([tokens])).logits[0, -1].numpy()
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 96                 # > original_max 64: the extrapolated regime
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    logits, _kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(logits), ref,
                               rtol=4e-4, atol=4e-4)


def test_mla_rope_params_edges():
    """attention_factor overrides the mscale inference (HF priority),
    and non-yarn scaling types reject loudly instead of serving
    unscaled positions."""
    from dynamo_tpu.engine.config import RopeScaling
    cfg = _cfg()
    cfg.rope_scaling = RopeScaling(
        rope_type="yarn", factor=4.0, mscale=0.5, mscale_all_dim=1.0,
        original_max_position_embeddings=64, attention_factor=1.25)
    _inv, att = mla.rope_params(cfg)
    assert att == 1.25
    cfg.rope_scaling = RopeScaling(rope_type="linear", factor=4.0)
    with pytest.raises(ValueError, match="not implemented"):
        mla.rope_params(cfg)


@pytest.mark.asyncio
async def test_mla_engine_serves_end_to_end():
    """EngineCore dispatches to the MLA module (kv_lora_rank > 0): the
    full scheduler — paged latent pool, continuous batching, multi-step
    decode dispatch, prefix reuse — serves greedy requests, and a repeat
    prompt gets a device-tier prefix hit through the latent rows."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling
    cfg = _cfg()
    core = EngineCore(
        cfg,
        EngineConfig(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                     max_num_seqs=2, prefill_buckets=[32, 64],
                     decode_steps_per_dispatch=4),
        attn_impl="xla", param_dtype=jnp.float32)
    assert core.is_mla and set(core.kv) == {"kv"}
    assert core.wire_kv_heads == 1

    async def run(rid):
        req = EngineRequest(rid=rid, prompt=list(range(2, 40)),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=8, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await req.out_queue.get()
            if item is FINISH_SENTINEL:
                break
            toks.append(item)
        return toks, req.prefix_hit_tokens

    try:
        toks1, hit1 = await run("m1")
        assert len(toks1) == 8 and hit1 == 0
        toks2, hit2 = await run("m2")
        assert toks2 == toks1          # deterministic greedy
        assert hit2 >= 24              # latent-row prefix reuse engaged
    finally:
        await core.stop()


def test_mla_engine_unsupported_combinations_refuse():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    cfg = _cfg()
    base = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32])
    with pytest.raises(NotImplementedError, match="int4"):
        EngineCore(cfg, EngineConfig(**base, quantization="int4"),
                   attn_impl="xla", param_dtype=jnp.float32)
    del make_mesh   # tp/ep/sp meshes all work now (tests below)


async def _greedy_tokens(core, rid, prompt, n=8):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=n, eos_ids=frozenset())
    await core.submit(req)
    toks = []
    while True:
        item, _ = await req.out_queue.get()
        if item is FINISH_SENTINEL:
            break
        toks.append(item)
    return toks


@pytest.mark.asyncio
async def test_mla_engine_serves_sharded():
    """MLA over a tp×ep mesh: head-sharded q/kv_b/wo projections,
    replicated latent pool, expert-parallel MoE stacks — the full
    deepseek MoE geometry serves through EngineCore and reproduces the
    single-chip greedy tokens (the GSPMD layout must be a pure
    performance choice, not a numerics one)."""
    import jax as _jax
    if len(_jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    cfg = _moe_cfg(n_group=2, topk_group=1, scaling=2.5)
    params = mla.init_params(cfg, jax.random.PRNGKey(50),
                             dtype=jnp.float32)
    ecfg = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32, 64],
                decode_steps_per_dispatch=4)
    prompt = list(range(2, 40))
    ref_core = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                          attn_impl="xla", param_dtype=jnp.float32)
    try:
        want = await _greedy_tokens(ref_core, "ref", prompt)
    finally:
        await ref_core.stop()
    core = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                      attn_impl="xla", param_dtype=jnp.float32,
                      mesh=make_mesh(dp=1, tp=2, sp=1, ep=2))
    try:
        sh = core.params["layers.wkv_b"].sharding
        assert not sh.is_fully_replicated      # heads actually sharded
        assert core.kv["kv"].sharding.is_fully_replicated
        got = await _greedy_tokens(core, "tp", prompt)
    finally:
        await core.stop()
    assert got == want


def test_mla_int8_kv_sectioned_scale_isolates_magnitude_skew():
    """THE scenario the sectioned encoding exists for: k_pe is an
    UNNORMALIZED projection output while c_kv is RMSNormed, so real
    checkpoints can carry 10-50x magnitude skew between the sections.
    With a 20x-hot k_pe, the c_kv reconstruction error must stay at
    its OWN absmax resolution — a shared absmax would leave it ~6
    effective levels (the review finding this test pins)."""
    from dynamo_tpu.engine.attention import (KV_SCALE_LANES,
                                             dequant_kv_rows_sections,
                                             quantize_kv_rows_sections)
    rng = np.random.default_rng(80)
    rank, dr = 16, 8
    c = rng.standard_normal((64, rank)).astype(np.float32)
    k_pe = rng.standard_normal((64, dr)).astype(np.float32) * 20.0
    x = jnp.asarray(np.concatenate([c, k_pe], axis=1))
    rows = quantize_kv_rows_sections(x, (rank, dr))
    assert rows.shape == (64, rank + dr + KV_SCALE_LANES)
    deq = np.asarray(dequant_kv_rows_sections(rows, (rank, dr),
                                              jnp.float32))
    # each section's error bounded by ITS absmax/127 half-step
    c_scale = np.abs(c).max(axis=1) / 127.0
    pe_scale = np.abs(k_pe).max(axis=1) / 127.0
    assert (np.abs(deq[:, :rank] - c)
            <= c_scale[:, None] * 0.51 + 1e-7).all()
    assert (np.abs(deq[:, rank:] - k_pe)
            <= pe_scale[:, None] * 0.51 + 1e-6).all()
    # single-section degenerates to the llama encoding exactly
    from dynamo_tpu.engine.attention import quantize_kv_rows
    one = quantize_kv_rows_sections(x, (rank + dr,))
    np.testing.assert_array_equal(np.asarray(one),
                                  np.asarray(quantize_kv_rows(x)))


def test_mla_int8_kv_teacher_forced_accuracy_gate():
    """int8 latent rows (in-row (e, m) scales, one pair per c_kv/k_pe
    section — the pool never lane-shards) vs the f32 pool,
    TEACHER-FORCED per the established gate (test_kv_quant.py
    rationale: free-running greedy compounds one near-tie flip into
    total divergence on random tiny weights). The latent row is the
    ONLY cache MLA has, so this also gates the absorbed-decode read
    path."""
    from dynamo_tpu.engine.attention import KV_SCALE_LANES
    cfg = _cfg()
    rng = np.random.default_rng(60)
    params = mla.init_params(cfg, jax.random.PRNGKey(61),
                             dtype=jnp.float32)
    statics = _statics(cfg)
    T, steps = 32, 24
    nblocks = (T + steps + BS - 1) // BS + 1
    kv_bf = mla.init_kv_cache(cfg, nblocks + 1, BS, dtype=jnp.float32)
    kv_q8 = mla.init_kv_cache(cfg, nblocks + 1, BS, quantization="int8")
    C = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert kv_q8["kv"].dtype == jnp.int8
    # pad128(values + scale lanes) — the kernel's DMA alignment
    assert kv_q8["kv"].shape[-1] == -(-(C + KV_SCALE_LANES) // 128) * 128
    prompt = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(T,)),
                         jnp.int32)
    table = jnp.asarray(np.arange(1, nblocks + 1), jnp.int32)
    lg_bf, kv_bf = mla.prefill_forward(params, kv_bf, prompt, table,
                                       jnp.asarray(0), jnp.asarray(T),
                                       statics)
    lg_q8, kv_q8 = mla.prefill_forward(params, kv_q8, prompt, table,
                                       jnp.asarray(0), jnp.asarray(T),
                                       statics)
    match = 0
    max_rel = 0.0
    tok = int(jnp.argmax(lg_bf))
    for s in range(steps):
        pos = jnp.asarray([T + s], jnp.int32)
        toks = jnp.asarray([tok], jnp.int32)
        tables = table[None, :]
        out_bf, kv_bf = mla.decode_forward(params, kv_bf, toks, pos,
                                           tables, statics)
        out_q8, kv_q8 = mla.decode_forward(params, kv_q8, toks, pos,
                                           tables, statics)
        a, b = np.asarray(out_bf[0]), np.asarray(out_q8[0])
        match += int(a.argmax() == b.argmax())
        max_rel = max(max_rel, float(np.abs(a - b).max() / a.std()))
        tok = int(a.argmax())               # teacher-forced from f32
    rate = match / steps
    assert rate >= 0.9, f"teacher-forced argmax match {rate:.2f}"
    assert max_rel < 0.15, f"logit error {max_rel:.3f} of logit spread"


@pytest.mark.asyncio
async def test_mla_int8_kv_serving_end_to_end():
    """EngineCore serves MLA on an int8 latent pool — the refusal is
    gone; streams finish and prefix reuse still engages through the
    quantized rows (block hashing is token-keyed, format-agnostic)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    cfg = _cfg()
    core = EngineCore(
        cfg,
        EngineConfig(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                     max_num_seqs=2, prefill_buckets=[32, 64],
                     decode_steps_per_dispatch=4, kv_quantization="int8"),
        attn_impl="xla", param_dtype=jnp.float32)
    assert core.kv["kv"].dtype == jnp.int8
    assert core.wire_kv_heads == 1
    try:
        toks1 = await _greedy_tokens(core, "q1", list(range(2, 40)))
        assert len(toks1) == 8
        toks2 = await _greedy_tokens(core, "q2", list(range(2, 40)))
        assert toks2 == toks1              # deterministic greedy
    finally:
        await core.stop()


def test_mla_int8_weights_teacher_forced_accuracy_gate():
    """int8 weights through the MLA forward (quant._LAYER_MATMULS now
    carries wq_a/wq_b/wkv_a and the deepseek dense prefix; wkv_b stays
    full precision for the absorbed einsums), two gates:

    1. PLUMBING (tight): the fused-dequant forward == the same forward
       run on explicitly dequantized weights, to float tolerance — a
       wrong scale axis or a missed leaf fails this at any geometry.
    2. ACCURACY: prefill logit cosine > 0.998 and per-step decode
       cosine > 0.99 vs the f32 tree, teacher-forced. Looser than
       llama's 0.999 (test_quant.py) by design: the q-LoRA path chains
       wq_a->wq_b (two quantized matmuls), wkv_a squeezes through the
       rank-16 latent bottleneck, and — decode-specific — the two runs
       CACHE different latent rows (each written by its own weights),
       so the pools themselves diverge step by step on top of the
       per-step rounding. A plumbing failure sits far below 0.99;
       gate 1 pins exactness. The hybrid MoE path (incl.
       QuantizedArray slicing in the split scans) is served end-to-end
       by the next test."""
    from dynamo_tpu.engine.quant import QuantizedArray, quantize_params
    cfg = _cfg(q_lora=12)                  # exercise wq_a/wq_b quant
    rng = np.random.default_rng(70)
    params = mla.init_params(cfg, jax.random.PRNGKey(71),
                             dtype=jnp.float32)
    qparams = quantize_params(dict(params))
    assert isinstance(qparams["layers.wq_b"], QuantizedArray)
    assert not isinstance(qparams["layers.wkv_b"], QuantizedArray)
    statics = _statics(cfg)
    T, steps = 32, 24
    nblocks = (T + steps + BS - 1) // BS + 1
    kv_bf = mla.init_kv_cache(cfg, nblocks + 1, BS, dtype=jnp.float32)
    kv_q = mla.init_kv_cache(cfg, nblocks + 1, BS, dtype=jnp.float32)
    prompt = jnp.asarray(rng.integers(2, cfg.vocab_size, size=(T,)),
                         jnp.int32)
    table = jnp.asarray(np.arange(1, nblocks + 1), jnp.int32)
    def cos(a, b):
        return float(np.dot(a, b)
                     / (np.linalg.norm(a) * np.linalg.norm(b)))

    # gate 1: fused dequant == explicit dequant (plumbing)
    deq = {k: (v.dequantize(jnp.float32)
               if isinstance(v, QuantizedArray) else v)
           for k, v in qparams.items()}
    kv_a = mla.init_kv_cache(cfg, nblocks + 1, BS, dtype=jnp.float32)
    kv_b = mla.init_kv_cache(cfg, nblocks + 1, BS, dtype=jnp.float32)
    lg_fused, _ = mla.prefill_forward(qparams, kv_a, prompt, table,
                                      jnp.asarray(0), jnp.asarray(T),
                                      statics)
    lg_deq, _ = mla.prefill_forward(deq, kv_b, prompt, table,
                                    jnp.asarray(0), jnp.asarray(T),
                                    statics)
    np.testing.assert_allclose(np.asarray(lg_fused), np.asarray(lg_deq),
                               rtol=2e-4, atol=2e-4)

    # gate 2: accuracy vs f32, teacher-forced
    lg_bf, kv_bf = mla.prefill_forward(params, kv_bf, prompt, table,
                                       jnp.asarray(0), jnp.asarray(T),
                                       statics)
    lg_q, kv_q = mla.prefill_forward(qparams, kv_q, prompt, table,
                                     jnp.asarray(0), jnp.asarray(T),
                                     statics)
    assert cos(np.asarray(lg_bf), np.asarray(lg_q)) > 0.998
    tok = int(jnp.argmax(lg_bf))
    for s in range(steps):
        pos = jnp.asarray([T + s], jnp.int32)
        toks = jnp.asarray([tok], jnp.int32)
        tables = table[None, :]
        out_bf, kv_bf = mla.decode_forward(params, kv_bf, toks, pos,
                                           tables, statics)
        out_q, kv_q = mla.decode_forward(qparams, kv_q, toks, pos,
                                         tables, statics)
        c = cos(np.asarray(out_bf[0]), np.asarray(out_q[0]))
        assert c > 0.99, f"decode step {s}: cos {c:.5f}"
        tok = int(np.asarray(out_bf[0]).argmax())


@pytest.mark.asyncio
async def test_mla_int8_weights_serving_end_to_end():
    """EngineCore serves MLA with quantization="int8" (streaming
    init->quantize path dispatches to mla.param_shapes) — and together
    with an int8 latent pool: the full low-precision serving stack."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.quant import QuantizedArray
    cfg = _moe_cfg(n_group=2, topk_group=1, scaling=2.5)
    core = EngineCore(
        cfg,
        EngineConfig(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                     max_num_seqs=2, prefill_buckets=[32, 64],
                     decode_steps_per_dispatch=4, quantization="int8",
                     kv_quantization="int8"),
        attn_impl="xla", param_dtype=jnp.float32)
    assert isinstance(core.params["layers.wq"], QuantizedArray)
    assert core.kv["kv"].dtype == jnp.int8
    try:
        toks = await _greedy_tokens(core, "qw", list(range(2, 40)))
        assert len(toks) == 8
        assert all(0 <= t < cfg.vocab_size for t in toks)
    finally:
        await core.stop()


def test_mla_sp_ring_prefill_matches_whole():
    """The latent-row ring (parallel/ring_attention.ring_attention_mla):
    sequence-parallel prefill over an sp=2 mesh must reproduce the
    plain whole-prompt prefill — logits AND every scattered latent row
    (the pool is what decode reads later). tp=2 as well, so the
    head-sharded q_lat and the replicated row chunks cross shardings."""
    from dynamo_tpu.parallel.sharding import make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg = _cfg(q_lora=12)
    params = mla.init_params(cfg, jax.random.PRNGKey(55),
                             dtype=jnp.float32)
    rng = np.random.default_rng(56)
    tokens = rng.integers(1, cfg.vocab_size, size=56).tolist()
    T = 64                                  # divides sp=2
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)

    kv1 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    want, kv1 = mla.prefill_forward(
        params, kv1, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))

    mesh = make_mesh(dp=1, tp=2, sp=2)
    kv2 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    got, kv2 = mla.prefill_forward_sp(
        params, kv2, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(len(tokens), jnp.int32), _statics(cfg), mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kv2["kv"]),
                               np.asarray(kv1["kv"]),
                               rtol=2e-5, atol=2e-5)


def test_mla_sp_ring_sub_chunked_matches_whole(monkeypatch):
    """The hop body's sub-chunk streaming (bounded [H, Tl, sub] score
    transients at long context) is exact: with RING_SUB_CHUNK forced
    tiny so every hop runs multiple sub-steps, the sp prefill still
    equals the whole-prompt run."""
    from dynamo_tpu.parallel import ring_attention as ra
    from dynamo_tpu.parallel.sharding import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    monkeypatch.setattr(ra, "RING_SUB_CHUNK", 8)   # Sl=32 → 4 sub-steps
    cfg = _cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(58),
                             dtype=jnp.float32)
    rng = np.random.default_rng(59)
    tokens = rng.integers(1, cfg.vocab_size, size=50).tolist()
    T = 64
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    kv1 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    want, _ = mla.prefill_forward(
        params, kv1, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    kv2 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    got, _ = mla.prefill_forward_sp(
        params, kv2, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(len(tokens), jnp.int32), _statics(cfg),
        make_mesh(dp=1, tp=1, sp=2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.asyncio
async def test_mla_sp_int8_kv_matches_single_chip():
    """sp ring + int8 latent pool: the ring round-trips its fresh rows
    through the sectioned encoding so prefill attention sees exactly
    the rows decode will read — greedy continuation must equal the
    single-chip int8-KV engine's (the invariant the non-sp paths keep
    by gathering from the pool)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = _cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(62),
                             dtype=jnp.float32)
    ecfg = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[64, 128],
                sp_min_prefill_tokens=32, decode_steps_per_dispatch=4,
                kv_quantization="int8")
    prompt = list(range(2, 60))
    ref = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                     attn_impl="xla", param_dtype=jnp.float32)
    try:
        want = await _greedy_tokens(ref, "ref", prompt)
    finally:
        await ref.stop()
    core = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                      attn_impl="xla", param_dtype=jnp.float32,
                      mesh=make_mesh(dp=1, tp=1, sp=2))
    try:
        got = await _greedy_tokens(core, "sp8", prompt)
    finally:
        await core.stop()
    assert got == want


@pytest.mark.asyncio
async def test_mla_engine_serves_over_sp_mesh():
    """EngineCore's sp dispatch path (model_mod.prefill_forward_sp) with
    MLA: a long prompt takes the ring prefill and the greedy
    continuation equals the single-chip engine's."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = _cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(57),
                             dtype=jnp.float32)
    ecfg = dict(max_model_len=128, kv_block_size=8, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[64, 128],
                sp_min_prefill_tokens=32, decode_steps_per_dispatch=4)
    prompt = list(range(2, 60))             # 58 tokens >= sp_min 32
    ref = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                     attn_impl="xla", param_dtype=jnp.float32)
    try:
        want = await _greedy_tokens(ref, "ref", prompt)
    finally:
        await ref.stop()
    core = EngineCore(cfg, EngineConfig(**ecfg), params=dict(params),
                      attn_impl="xla", param_dtype=jnp.float32,
                      mesh=make_mesh(dp=1, tp=1, sp=2))
    assert core._prefill_sp_jit is not None
    try:
        got = await _greedy_tokens(core, "sp", prompt)
    finally:
        await core.stop()
    assert got == want


@pytest.mark.asyncio
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
async def test_mla_host_tier_multi_turn_offload_onboard(kv_quant):
    """MLA latent rows through the host KV tier (the last MLA refusal):
    generate, offload on finish, wipe the device reuse pool, resubmit —
    the host tier restores the latent prefix and the continuation is
    identical. Latent rows ship as one opaque wire "head" whole-row
    (full precision AND int8 + in-row scales), so the round trip is
    bit-exact (mirrors test_kv_offload.py's llama equivalence test)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling
    cfg = _cfg()
    ecfg = EngineConfig(max_model_len=64, kv_block_size=4,
                        num_kv_blocks=32, max_num_seqs=2,
                        prefill_buckets=[32, 64], host_kv_blocks=16,
                        kv_quantization=kv_quant)
    core = EngineCore(cfg, ecfg, attn_impl="xla", param_dtype=jnp.float32)
    host = core.offload_engine.host_pool
    assert host.opaque_rows and host.num_kv_heads == 1
    prompt = list(range(1, 13))            # 3 full blocks

    async def run_once(rid):
        req = EngineRequest(rid=rid, prompt=list(prompt),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=4, eos_ids=frozenset())
        await core.submit(req)
        toks = []
        while True:
            item, _ = await req.out_queue.get()
            if item is FINISH_SENTINEL:
                return toks, req.prefix_hit_tokens
            toks.append(item)

    try:
        toks1, hit1 = await run_once("h1")
        assert hit1 == 0
        await core.offload_engine.drain()
        assert core.offload_engine.offloaded_blocks_total >= 2
        # arena holds latent rows under the pool's own key
        assert set(host._arena) == {"kv"}
        core.kv_manager.pool.reset()       # only the host tier remains
        toks2, hit2 = await run_once("h2")
        assert hit2 >= 8                   # host-tier latent restore
        assert toks2 == toks1
        assert core.host_onboards == 1
    finally:
        await core.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("plane,kv_quant", [
    ("device", "none"), ("wire", "none"),
    ("device", "int8"), ("wire", "int8"),
], ids=["device", "wire", "device-int8", "wire-int8"])
async def test_mla_disagg_remote_prefill_matches_local(plane, kv_quant):
    """PD disaggregation with MLA pools: a prefill engine hands the
    latent rows to a decode engine over the device plane (in-process
    ICI analog) or the TCP wire plane — whole rows as one opaque wire
    head, full-precision and int8 — and greedy tokens equal the
    aggregated single-engine run. Exercises the key-agnostic wire codec
    ("keys" header) and the replicated stacked-sharding path."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.llm.disagg import (DisaggEngine, DisaggregatedRouter,
                                       PrefillWorker)
    from dynamo_tpu.llm.engines.jax_engine import JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime import Context
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import EngineContext
    cfg = _cfg()

    def mk():
        return EngineCore(
            cfg,
            EngineConfig(max_model_len=128, kv_block_size=8,
                         num_kv_blocks=48, max_num_seqs=2,
                         prefill_buckets=[16, 32, 64, 128],
                         kv_quantization=kv_quant),
            attn_impl="xla", param_dtype=jnp.float32)

    def req(rid):
        pre = PreprocessedRequest(
            token_ids=list(range(2, 39)),
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(greedy=True))
        return Context(pre, ctx=EngineContext(rid))

    async def collect(stream):
        toks = []
        async for a in stream:
            if a.data is not None and a.data.token_ids:
                toks.extend(a.data.token_ids)
        return toks

    local_core = mk()
    try:
        want = await collect(
            await JaxEngine(local_core).generate(req("want")))
    finally:
        await local_core.stop()
    assert len(want) == 8

    rt = DistributedRuntime.in_process()
    prefill_core, decode_core = mk(), mk()
    router = DisaggregatedRouter(rt, "tiny-mla",
                                 max_local_prefill_length=0,
                                 conditional=False)
    engine = DisaggEngine(decode_core, rt, router,
                          device_plane=(plane == "device"))
    worker = await PrefillWorker(prefill_core, rt).start()
    try:
        got = await collect(
            await engine.generate(req(f"mla-{plane}-{kv_quant}")))
        assert got == want
        assert engine.remote_prefills == 1 and engine.remote_failures == 0
        assert prefill_core.total_prefill_tokens == 37
        assert decode_core.total_prefill_tokens == 0
        if plane == "device":
            assert engine.device_transfers == 1
        else:
            assert engine.device_transfers == 0
    finally:
        await worker.stop()
        await prefill_core.stop()
        await decode_core.stop()
        await rt.shutdown()


def _moe_cfg(n_group=0, topk_group=0, scaling=1.0) -> ModelConfig:
    return ModelConfig(
        model_type="deepseek_v2", vocab_size=256, hidden_size=64,
        intermediate_size=48,            # moe expert F
        num_layers=3, num_heads=4, num_kv_heads=4, head_dim=24,
        max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, num_experts_per_tok=2, moe_norm_topk=False,
        first_k_dense=1, dense_intermediate_size=128,
        shared_expert_size=96,           # = 2 shared * moe F 48
        routed_scaling=scaling, n_group=n_group, topk_group=topk_group)


def _to_hf_moe(params, cfg):
    """Extend _to_hf with the deepseek MoE naming: dense prefix layers
    carry mlp.*_proj; MoE layers carry mlp.gate (router, [E, D]),
    mlp.experts.{e}.*_proj, mlp.shared_experts.*_proj."""
    import torch

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    sd = _to_hf(params, cfg)
    k = cfg.first_k_dense
    for i in range(k):
        for ours, hf in (("dense_gate", "gate_proj"),
                         ("dense_up", "up_proj"),
                         ("dense_down", "down_proj")):
            sd[f"model.layers.{i}.mlp.{hf}.weight"] = t(
                params[f"layers.{ours}"][i]).T.contiguous()
    for j in range(cfg.num_layers - k):
        i = k + j
        sd[f"model.layers.{i}.mlp.gate.weight"] = t(
            params["layers.router"][j]).T.contiguous()
        for e in range(cfg.num_experts):
            for ours, hf in (("moe_gate", "gate_proj"),
                             ("moe_up", "up_proj"),
                             ("moe_down", "down_proj")):
                sd[f"model.layers.{i}.mlp.experts.{e}.{hf}.weight"] = t(
                    params[f"layers.{ours}"][j][e]).T.contiguous()
        for ours, hf in (("sh_gate", "gate_proj"), ("sh_up", "up_proj"),
                         ("sh_down", "down_proj")):
            sd[f"model.layers.{i}.mlp.shared_experts.{hf}.weight"] = t(
                params[f"layers.{ours}"][j]).T.contiguous()
    return sd


@pytest.mark.parametrize("n_group,topk_group,scaling", [
    (0, 0, 1.0),          # -Lite: greedy routing
    (2, 1, 2.5),          # -V2/-Chat: group-limited greedy + scaling
], ids=["greedy", "group_limited"])
def test_mla_deepseek_moe_matches_hf(n_group, topk_group, scaling):
    """The full deepseek MoE block vs HF: hybrid first_k_dense prefix,
    softmax-scores routing WITHOUT renormalization, routed_scaling,
    additive (ungated) shared experts, and group-limited greedy for the
    -V2 shapes — teacher-forced logits through prefill AND the absorbed
    decode."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM
    cfg = _moe_cfg(n_group, topk_group, scaling)
    params = mla.init_params(cfg, jax.random.PRNGKey(14),
                             dtype=jnp.float32)
    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.dense_intermediate_size,
        moe_intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        n_routed_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=2, first_k_dense_replace=cfg.first_k_dense,
        topk_method=("group_limited_greedy" if n_group else "greedy"),
        n_group=n_group or None, topk_group=topk_group or None,
        routed_scaling_factor=scaling, norm_topk_prob=False,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        tie_word_embeddings=False, attention_bias=False,
        attn_implementation="eager")
    hf = DeepseekV2ForCausalLM(hf_cfg)
    missing, unexpected = hf.load_state_dict(_to_hf_moe(params, cfg),
                                             strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    hf.eval()

    rng = np.random.default_rng(15)
    tokens = rng.integers(1, cfg.vocab_size, size=12).tolist()
    steps = 5
    with torch.no_grad():
        ref_all = hf(torch.tensor(
            [tokens + [7] * steps])).logits[0].numpy()

    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 32
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    lg, kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(lg), ref_all[len(tokens) - 1],
                               rtol=5e-4, atol=5e-4)
    tables = table[None, :T // BS]
    for s in range(steps):
        pos = jnp.asarray([len(tokens) + s], jnp.int32)
        lg, kv = mla.decode_forward(
            params, kv, jnp.asarray([7], jnp.int32), pos,
            jnp.asarray(tables), _statics(cfg))
        np.testing.assert_allclose(
            np.asarray(lg[0]), ref_all[len(tokens) + s],
            rtol=5e-4, atol=5e-4, err_msg=f"decode step {s}")


def test_deepseek_v2_checkpoint_roundtrip(tmp_path):
    """config.json + safetensors (HF deepseek naming, fused MoE hybrid)
    -> from_hf_config + load_llama_params reproduce the params exactly:
    the checkpoint-level deepseek_v2 gate is open."""
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.engine.weights import load_llama_params
    cfg = _moe_cfg(n_group=2, topk_group=1, scaling=2.5)
    cfg.q_lora_rank = 12         # exercise the q-LoRA names too
    params = mla.init_params(cfg, jax.random.PRNGKey(21),
                             dtype=jnp.float32)
    sd = {k: np.ascontiguousarray(v.numpy())
          for k, v in _to_hf_moe(params, cfg).items()}
    save_file(sd, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "deepseek_v2", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.dense_intermediate_size,
        "moe_intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": 2,
        "first_k_dense_replace": cfg.first_k_dense,
        "topk_method": "group_limited_greedy", "n_group": 2,
        "topk_group": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": False,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": False}))

    parsed = ModelConfig.from_model_dir(str(tmp_path))
    assert parsed.kv_lora_rank == cfg.kv_lora_rank
    assert parsed.num_experts == cfg.num_experts
    assert parsed.intermediate_size == cfg.intermediate_size
    assert parsed.dense_intermediate_size == cfg.dense_intermediate_size
    assert parsed.shared_expert_size == 2 * cfg.intermediate_size
    assert parsed.first_k_dense == 1 and parsed.n_group == 2
    assert parsed.routed_scaling == 2.5 and not parsed.moe_norm_topk

    loaded = load_llama_params(str(tmp_path), parsed, dtype=jnp.float32)
    assert set(loaded) == set(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(loaded[k]),
                                   np.asarray(params[k]),
                                   rtol=0, atol=0, err_msg=k)


def test_deepseek_unsupported_variants_reject():
    with pytest.raises(ValueError, match="topk_method"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v2", "n_routed_experts": 8,
            "kv_lora_rank": 16, "topk_method": "noaux_tc"})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v2", "n_routed_experts": 8,
            "kv_lora_rank": 16, "norm_topk_prob": True})
    with pytest.raises(ValueError, match="scoring_func"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v3", "scoring_func": "softmax"})
    with pytest.raises(ValueError, match="topk_method"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v3", "topk_method": "greedy"})
    with pytest.raises(ValueError, match="rope_interleave"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v3", "rope_interleave": False})
    with pytest.raises(ValueError, match="quantization_config"):
        ModelConfig.from_hf_config({
            "model_type": "deepseek_v3",
            "quantization_config": {"quant_method": "fp8"}})


# ---------------------------------------------------------------------------
# deepseek_v3: sigmoid noaux_tc routing, yarn mscale² score scale
# ---------------------------------------------------------------------------


def _v3_cfg() -> ModelConfig:
    return ModelConfig(
        model_type="deepseek_v3", vocab_size=256, hidden_size=64,
        intermediate_size=48,            # moe expert F
        num_layers=3, num_heads=4, num_kv_heads=4, head_dim=24,
        max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        num_experts=4, num_experts_per_tok=2, moe_norm_topk=True,
        moe_routing="sigmoid_noaux",
        first_k_dense=1, dense_intermediate_size=128,
        shared_expert_size=48,           # = 1 shared * moe F 48
        routed_scaling=2.5, n_group=2, topk_group=1)


def _to_hf_v3(params, cfg):
    """_to_hf_moe plus the v3 router bias buffer (persistent, so HF
    expects it in the state dict)."""
    import torch
    sd = _to_hf_moe(params, cfg)
    k = cfg.first_k_dense
    for j in range(cfg.num_layers - k):
        sd[f"model.layers.{k + j}.mlp.gate.e_score_correction_bias"] = \
            torch.tensor(np.asarray(params["layers.router_bias"][j],
                                    np.float32))
    return sd


def _hf_v3(cfg, params, rope_scaling=None):
    import torch  # noqa: F401 — importorskip at callers
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM
    hf_cfg = DeepseekV3Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.dense_intermediate_size
        or cfg.intermediate_size,
        moe_intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank or None,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        n_routed_experts=cfg.num_experts or 4,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=1,
        first_k_dense_replace=(cfg.first_k_dense if cfg.num_experts
                               else cfg.num_layers),
        n_group=cfg.n_group or 1, topk_group=cfg.topk_group or 1,
        routed_scaling_factor=cfg.routed_scaling,
        norm_topk_prob=cfg.moe_norm_topk,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        rope_scaling=rope_scaling, tie_word_embeddings=False,
        attention_bias=False, attn_implementation="eager")
    hf = DeepseekV3ForCausalLM(hf_cfg)
    sd = (_to_hf_v3(params, cfg) if cfg.num_experts
          else _to_hf(params, cfg))
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    hf.eval()
    return hf


def test_mla_deepseek_v3_moe_matches_hf():
    """v3 noaux_tc routing vs HF DeepseekV3ForCausalLM: sigmoid scores,
    bias-corrected top-2-sum group selection, renormalized top-k
    weights from the UNBIASED scores, routed_scaling — teacher-forced
    through prefill AND the absorbed decode. The bias buffer is
    RANDOMIZED so biased-choice-vs-unbiased-weights cannot silently
    collapse into one tensor."""
    torch = pytest.importorskip("torch")
    cfg = _v3_cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(31),
                             dtype=jnp.float32)
    params["layers.router_bias"] = jax.random.normal(
        jax.random.PRNGKey(32),
        params["layers.router_bias"].shape, dtype=jnp.float32) * 0.5
    hf = _hf_v3(cfg, params)

    rng = np.random.default_rng(33)
    tokens = rng.integers(1, cfg.vocab_size, size=13).tolist()
    steps = 5
    with torch.no_grad():
        ref_all = hf(torch.tensor(
            [tokens + [9] * steps])).logits[0].numpy()

    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 32
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    lg, kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(lg), ref_all[len(tokens) - 1],
                               rtol=5e-4, atol=5e-4)
    tables = table[None, :T // BS]
    for s in range(steps):
        pos = jnp.asarray([len(tokens) + s], jnp.int32)
        lg, kv = mla.decode_forward(
            params, kv, jnp.asarray([9], jnp.int32), pos,
            jnp.asarray(tables), _statics(cfg))
        np.testing.assert_allclose(
            np.asarray(lg[0]), ref_all[len(tokens) + s],
            rtol=5e-4, atol=5e-4, err_msg=f"decode step {s}")


def test_mla_v3_yarn_score_scale_matches_hf():
    """v3 yarn applies mscale(factor, mscale_all_dim)² to the SCORE
    scale (HF DeepseekV3Attention.__init__) — with mscale ==
    mscale_all_dim the cos/sin attention factor is 1.0, so only this
    path carries the correction; skipping it shifts every logit."""
    torch = pytest.importorskip("torch")
    from dynamo_tpu.engine.config import RopeScaling
    cfg = _v3_cfg()
    cfg.num_experts = 0
    cfg.intermediate_size = 128
    cfg.first_k_dense = 0
    cfg.dense_intermediate_size = 0
    cfg.shared_expert_size = 0
    rs = {"rope_type": "yarn", "factor": 4.0, "mscale": 1.0,
          "mscale_all_dim": 1.0, "beta_fast": 32, "beta_slow": 1,
          "original_max_position_embeddings": 64}
    cfg.rope_scaling = RopeScaling(
        rope_type="yarn", factor=4.0, mscale=1.0, mscale_all_dim=1.0,
        beta_fast=32, beta_slow=1,
        original_max_position_embeddings=64)
    assert mla.softmax_scale(cfg) > (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    params = mla.init_params(cfg, jax.random.PRNGKey(34),
                             dtype=jnp.float32)
    hf = _hf_v3(cfg, params, rope_scaling=rs)

    rng = np.random.default_rng(35)
    tokens = rng.integers(1, cfg.vocab_size, size=90).tolist()
    with torch.no_grad():
        ref = hf(torch.tensor([tokens])).logits[0, -1].numpy()
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    T = 96                 # > original_max 64: the extrapolated regime
    padded = np.zeros((T,), np.int32)
    padded[:len(tokens)] = tokens
    table = np.zeros((NUM_BLOCKS,), np.int32)
    table[:T // BS] = np.arange(1, 1 + T // BS)
    logits, _kv = mla.prefill_forward(
        params, kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), jnp.asarray(len(tokens), jnp.int32),
        _statics(cfg))
    np.testing.assert_allclose(np.asarray(logits), ref,
                               rtol=4e-4, atol=4e-4)


def test_deepseek_v3_config_class_defaults():
    """A minimal re-saved v3 config (to_diff_dict omits class-default
    keys) must parse to the full V3 geometry, not a dense llama."""
    parsed = ModelConfig.from_hf_config({"model_type": "deepseek_v3"})
    assert parsed.kv_lora_rank == 512 and parsed.q_lora_rank == 1536
    assert parsed.qk_nope_head_dim == 128
    assert parsed.qk_rope_head_dim == 64 and parsed.v_head_dim == 128
    assert parsed.num_experts == 256
    assert parsed.intermediate_size == 2048          # expert F
    assert parsed.dense_intermediate_size == 18432
    assert parsed.num_experts_per_tok == 8
    assert parsed.n_group == 8 and parsed.topk_group == 4
    assert parsed.first_k_dense == 3
    assert parsed.routed_scaling == 2.5
    assert parsed.shared_expert_size == 2048         # 1 shared expert
    assert parsed.moe_routing == "sigmoid_noaux"
    assert parsed.moe_norm_topk                      # v3 default TRUE


def test_deepseek_v3_checkpoint_roundtrip(tmp_path):
    """v3 config.json + safetensors (incl. the router bias buffer and
    an MTP layer at index L that must be SKIPPED) -> from_hf_config +
    load_llama_params reproduce the params exactly."""
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.engine.weights import load_llama_params
    cfg = _v3_cfg()
    params = mla.init_params(cfg, jax.random.PRNGKey(36),
                             dtype=jnp.float32)
    params["layers.router_bias"] = jax.random.normal(
        jax.random.PRNGKey(37),
        params["layers.router_bias"].shape, dtype=jnp.float32)
    sd = {k: np.ascontiguousarray(v.numpy())
          for k, v in _to_hf_v3(params, cfg).items()}
    # MTP head (num_nextn_predict_layers=1): attention-shaped names at
    # layer index L — the loader must skip them, not stack them
    L = cfg.num_layers
    sd[f"model.layers.{L}.self_attn.kv_a_layernorm.weight"] = \
        np.ones((cfg.kv_lora_rank,), np.float32)
    sd[f"model.layers.{L}.enorm.weight"] = np.ones((cfg.hidden_size,),
                                                   np.float32)
    save_file(sd, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "deepseek_v3", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.dense_intermediate_size,
        "moe_intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": 1,
        "first_k_dense_replace": cfg.first_k_dense,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling,
        "norm_topk_prob": True, "num_nextn_predict_layers": 1,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": False}))

    parsed = ModelConfig.from_model_dir(str(tmp_path))
    assert parsed.moe_routing == "sigmoid_noaux"
    assert parsed.moe_norm_topk and parsed.routed_scaling == 2.5
    assert parsed.shared_expert_size == cfg.intermediate_size

    loaded = load_llama_params(str(tmp_path), parsed, dtype=jnp.float32)
    assert set(loaded) == set(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(loaded[k]),
                                   np.asarray(params[k]),
                                   rtol=0, atol=0, err_msg=k)


# ---------------------------------------------------------------------------
# The blocked dense prefill (mla._dense_chunk) and kimi_k2 (PR 37;
# docs/mla_dense.md)
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmark")


def _plain_dense_chunk(q_nope, q_pe, lp, kv_flat, table_l, start_pos,
                       seq_len, cfg, bsz, scale):
    """The parent's dense form, as the plain expression: the WHOLE table
    gathered, expanded in float32, one [H, T, S] score array."""
    T = q_nope.shape[0]
    rank, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    f32 = jnp.float32
    rows = jnp.take(kv_flat.reshape(-1, bsz, kv_flat.shape[-1]), table_l,
                    axis=0).reshape(-1, kv_flat.shape[-1])
    S = rows.shape[0]
    c, k_pe = rows[:, :rank].astype(f32), rows[:, rank:rank + dr].astype(f32)
    w_k, w_v = mla._split_wkv_b(lp, cfg)
    k_nope = jnp.einsum("sr,hrd->hsd", c, w_k.astype(f32))
    v = jnp.einsum("sr,hrd->hsd", c, w_v.astype(f32))
    scores = (jnp.einsum("thd,hsd->hts", q_nope.astype(f32), k_nope)
              + jnp.einsum("thd,sd->hts", q_pe.astype(f32), k_pe)) * scale
    qpos = (start_pos + jnp.arange(T))[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = (kpos <= qpos) & (kpos < seq_len)
    probs = jax.nn.softmax(jnp.where(mask[None], scores, mla.NEG_INF), -1)
    return jnp.einsum("hts,hsd->thd", probs, v)


_CHUNK_CASES = {
    # name: (T bucket, start_pos, true_len, table blocks in order)
    "whole_prompt": (32, 0, 32, [1, 2, 3, 4, 5, 6, 7, 8]),
    "cached_prefix": (16, 40, 16, [1, 2, 3, 4, 5, 6, 7, 8]),
    "true_len_under_bucket": (32, 16, 11, [1, 2, 3, 4, 5, 6, 7, 8]),
    "length_on_a_block_edge": (16, 16, 16, [1, 2, 3, 4, 5, 6, 7, 8]),
    "length_on_a_key_block_edge": (16, 8, 8, [1, 2, 3, 4, 5, 6, 7, 8]),
    "fragmented_table": (16, 24, 13, [9, 3, 14, 1, 7, 12, 2, 5]),
    "table_not_a_multiple_of_the_key_block": (16, 24, 16,
                                              [4, 5, 6, 7, 8, 9, 10]),
    # the kernel's tile classes (engine/mla_prefill.py; row tiles of 8, key
    # blocks of 16): three key blocks wholly under the chunk and live
    "interior_key_blocks_only": (16, 48, 16, [1, 2, 3, 4, 5, 6, 7, 8]),
    # the chunk starts inside a row tile; in the second key block's frame
    # the first queries sit at negative positions and read nothing there
    "start_inside_a_tile": (32, 4, 28, [1, 2, 3, 4, 5, 6, 7, 8]),
    # live length 19: the edge lies in row tile 16..23, which the query
    # tiles from 24 on (padding rows, two_query_tiles) see wholly below
    # their diagonal
    "edge_below_the_diagonal": (32, 16, 3, [1, 2, 3, 4, 5, 6, 7, 8]),
    # live length 24: the last row tile is full, its key block half empty
    "length_on_a_tile_edge": (16, 8, 16, [1, 2, 3, 4, 5, 6, 7, 8]),
    # a query tile of 64 rows: the kernel lays its body out in four
    # sub-ranges of 16 (mla_prefill.Q_SPLIT), each with its own mask offset
    "query_sub_ranges": (64, 24, 61, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]),
}


def _chunk_args(case, dtype, seed):
    """→ (the arguments of ``_dense_chunk`` / ``_plain_dense_chunk`` up to
    the implementation for one of ``_CHUNK_CASES``, its true length)."""
    T, start, true_len, blocks = _CHUNK_CASES[case]
    cfg = _cfg(q_lora=12)
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    kv_flat = jax.random.normal(
        keys[0], (NUM_BLOCKS * BS, mla.latent_row_lanes(cfg))).astype(dtype)
    lp = {"wkv_b": (jax.random.normal(
        keys[1], (cfg.kv_lora_rank, H * (dn + cfg.v_head_dim)))
        * cfg.kv_lora_rank ** -0.5).astype(dtype)}
    q_nope = jax.random.normal(keys[2], (T, H, dn)).astype(dtype)
    q_pe = jax.random.normal(keys[3], (T, H, dr)).astype(dtype)
    return (q_nope, q_pe, lp, kv_flat, jnp.asarray(blocks, jnp.int32),
            jnp.asarray(start), jnp.asarray(start + true_len), cfg, BS,
            mla.softmax_scale(cfg)), true_len


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "cached_prefix", "fragmented_table", "length_on_a_block_edge",
    "length_on_a_key_block_edge", "table_not_a_multiple_of_the_key_block",
    "true_len_under_bucket", "whole_prompt"])
def test_blocked_dense_chunk_equals_the_plain_dense_form(case, dtype,
                                                         monkeypatch):
    """The key-block walk (two blocks of the pool a key block here, so
    every case crosses several) against the whole-table expression, on the
    valid query rows: float32 to rounding, bf16 to bf16's."""
    monkeypatch.setattr(mla, "MLA_KEY_BLOCK", 2 * BS)
    args, true_len = _chunk_args(case, dtype, seed=3)
    cfg = args[7]
    got = np.asarray(jax.jit(
        lambda *a: mla._dense_chunk(*a, cfg, BS, mla.softmax_scale(cfg),
                                    "xla"))(*args[:7]), np.float32)
    want = np.asarray(_plain_dense_chunk(*args), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[:true_len], want[:true_len], rtol=tol,
                               atol=tol)


# the cases put under the kernel: float32 to rounding, two of them in bf16
_KERNEL_CASES = [(case, jnp.float32) for case in (
    "cached_prefix", "fragmented_table", "true_len_under_bucket",
    "interior_key_blocks_only", "whole_prompt", "start_inside_a_tile",
    "edge_below_the_diagonal", "length_on_a_tile_edge", "query_sub_ranges")
] + [("start_inside_a_tile", jnp.bfloat16),
     ("query_sub_ranges", jnp.bfloat16)]


@pytest.mark.parametrize("q_tile", [1024, 8], ids=["one_query_tile",
                                                    "two_query_tiles"])
@pytest.mark.parametrize(
    "case, dtype", _KERNEL_CASES,
    ids=[case if dtype == jnp.float32 else f"{case}_bfloat16"
         for case, dtype in _KERNEL_CASES])
def test_blocked_dense_chunk_through_the_kernel(case, dtype, q_tile,
                                                monkeypatch):
    """The same walk with each key block expanded, attended and merged in
    the Pallas kernel ``mla_prefill`` (interpreted here; two row tiles a
    key block, the state carried from one call to the next): the form the
    chip runs. The cases put every class of tile under it — interior
    (no mask), diagonal, live edge, skipped — and every row of the bucket is
    held to the plain form, the padding rows past ``true_len`` too (they
    are queries like any other to both)."""
    monkeypatch.setattr(mla, "MLA_KEY_BLOCK", 2 * BS)
    for module in (mla, mla_prefill):
        monkeypatch.setattr(module, "K_TILE", 8)
        monkeypatch.setattr(module, "Q_TILE", q_tile)
    args, _ = _chunk_args(case, dtype, seed=4)
    got = np.asarray(mla._dense_chunk(*args, "pallas_interpret"), np.float32)
    want = np.asarray(_plain_dense_chunk(*args), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_blocked_prefill_work_follows_the_live_length(monkeypatch):
    """The walk's trip count is ceil(live length / key block), whatever the
    table's capacity: rows past the live length are never read (NaNs
    there change nothing)."""
    monkeypatch.setattr(mla, "MLA_KEY_BLOCK", 2 * BS)
    cfg = _cfg()
    H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    W = mla.latent_row_lanes(cfg)
    kv_flat = jax.random.normal(keys[0], (NUM_BLOCKS * BS, W))
    table = jnp.arange(1, 13, dtype=jnp.int32)      # capacity 96
    # positions 32.. live nowhere: poison their blocks (5 and up)
    poisoned = kv_flat.at[5 * BS:].set(jnp.nan)
    lp = {"wkv_b": jax.random.normal(
        keys[1], (cfg.kv_lora_rank, H * (dn + cfg.v_head_dim)))}
    q_nope = jax.random.normal(keys[2], (16, H, dn))
    q_pe = jax.random.normal(keys[3], (16, H, dr))
    run = lambda pool: np.asarray(mla._dense_chunk(  # noqa: E731
        q_nope, q_pe, lp, pool, table, jnp.asarray(16), jnp.asarray(32),
        cfg, BS, mla.softmax_scale(cfg), "xla"))
    assert np.isfinite(run(poisoned)).all()
    np.testing.assert_array_equal(run(poisoned), run(kv_flat))
    text = jax.jit(lambda: mla._dense_chunk(
        q_nope, q_pe, lp, kv_flat, table, jnp.asarray(16), jnp.asarray(32),
        cfg, BS, mla.softmax_scale(cfg), "xla")).lower().as_text()
    assert "while" in text          # a loop with a traced trip count


@pytest.fixture(scope="module")
def kimi_ref():
    """benchmark/references/kimi_k2.py (it imports the benchmark's
    ``reference`` module by its bare name)."""
    sys.path.insert(0, _BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_kimi_k2",
            os.path.join(_BENCH, "references", "kimi_k2.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(_BENCH)


def _fixture_hf(name: str, **over) -> dict:
    with open(os.path.join(_BENCH, "fixtures", name + ".json")) as f:
        hf = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "reference"):
        hf.pop(key)
    return dict(hf, **over)


def _kimi_hf(**over) -> dict:
    return _fixture_hf("tiny-kimi-k2", **over)


def _kimi_setup(hf: dict, seed: int = 1):
    cfg = ModelConfig.from_hf_config(hf)
    params = mla.init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=jnp.float32)
    # a router bias that matters (it is zero at initialisation)
    params["layers.router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["layers.router_bias"].shape)
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, 16, dtype=jnp.float32)
    return cfg, params, kv, ModelStatics(cfg=cfg, block_size=16,
                                         attn_impl="xla")


def _std_err(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / want.std())


def test_kimi_k2_parses_onto_the_v3_block_with_one_group():
    cfg = ModelConfig.from_hf_config(_kimi_hf())
    assert (cfg.model_type, cfg.is_deepseek_v3, cfg.moe_routing,
            cfg.n_group, cfg.topk_group, cfg.index_topk) == (
        "kimi_k2", True, "sigmoid_noaux", 1, 1, 0)
    assert (cfg.num_experts, cfg.num_experts_total, cfg.router_width,
            cfg.first_k_dense, cfg.routed_scaling) == (12, 24, 24, 1, 2.827)
    assert cfg.moe_norm_topk and cfg.num_nextn_predict_layers == 0
    # the v3 score scale: mscale(64, 1)^2 on top of (dn+dr)^-0.5
    m = 0.1 * np.log(64.0) + 1.0
    assert abs(mla.softmax_scale(cfg) - 24 ** -0.5 * m * m) < 1e-9
    # the family's own checkpoint names: the v3 router bias among them
    from dynamo_tpu.engine.weights import _layer_map_for
    names = _layer_map_for(cfg)
    assert names["mlp.gate.e_score_correction_bias"] == ("router_bias",
                                                          False)
    assert names["self_attn.kv_a_proj_with_mqa.weight"] == ("wkv_a", True)
    # the file's own values, not v3's class defaults
    for key in ("n_routed_experts", "n_group", "routed_scaling_factor",
                "first_k_dense_replace"):
        hf = _kimi_hf()
        hf.pop(key)
        with pytest.raises(ValueError, match=key):
            ModelConfig.from_hf_config(hf)


@pytest.mark.parametrize("key", ["n_routed_experts", "num_local_experts",
                                 "num_experts"])
def test_unknown_expert_family_is_refused_by_name(key):
    with pytest.raises(ValueError, match="some_new_moe"):
        ModelConfig.from_hf_config({
            "model_type": "some_new_moe", "vocab_size": 128,
            "hidden_size": 64, "num_attention_heads": 4, key: 8})
    # a dense unknown family still parses as the llama block
    assert ModelConfig.from_hf_config({
        "model_type": "some_new_dense", "vocab_size": 128,
        "hidden_size": 64, "num_attention_heads": 4}).num_experts == 0


def _kimi_engine_logits(hf, tokens, chunk=16, prefilled=48,
                        monkeypatch=None):
    """Prefill by chunks, then decode through the cache: the logits of the
    last prefilled position and of every decoded one."""
    cfg, params, kv, statics = _kimi_setup(hf)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, prefilled, chunk):
            logits, kv = mla.prefill_forward(
                params, kv, jnp.asarray(tokens[lo:lo + chunk], jnp.int32),
                table, jnp.asarray(lo), jnp.asarray(chunk), statics)
        out.append(logits)
        for pos in range(prefilled, len(tokens)):
            logits, kv = mla.decode_forward(
                params, kv, jnp.asarray([tokens[pos], 0]),
                jnp.asarray([pos, 0]),
                jnp.stack([table, jnp.zeros_like(table)]), statics)
            out.append(logits[0])
    return params, np.stack([np.asarray(x) for x in out])


def test_kimi_k2_chunked_prefill_then_decode_equals_the_reference(
        kimi_ref, monkeypatch):
    """Logits, not tokens, in float32: the engine's blocked prefill (three
    chunks, key blocks of 32 rows) and its absorbed decode through the
    cache against the reference's full forward (1e-6 of the logits'
    standard deviation apart; held to 1e-4), and every breakage of the
    reference hundreds of times that tolerance away (the router's are the
    nearest: the routed experts write into the stream at half of
    fan_in^-0.5, llama.SHARE_SEEDED)."""
    monkeypatch.setattr(mla, "MLA_KEY_BLOCK", 32)
    hf = _kimi_hf()
    tokens = np.random.default_rng(3).integers(0, hf["vocab_size"], size=55)
    params, got = _kimi_engine_logits(hf, tokens)
    want = kimi_ref.logits_for(params, hf, tokens.tolist(), 8)
    assert _std_err(got, want) < 1e-4
    breakages = kimi_ref.breakages_for(hf)
    assert set(breakages) == set(kimi_ref.BREAKAGES)
    for broken in breakages:
        wrong = kimi_ref.logits_for(params, hf, tokens.tolist(), 8,
                                    broken=broken)
        assert _std_err(got, wrong) > 0.05, broken


@pytest.mark.parametrize("control", ["fp8_activations", "int4_weights",
                                     "default_precision"])
def test_kimi_k2_reference_controls_are_the_same_mathematics_lower(
        kimi_ref, control):
    """The reference's controls (the next precision below the served one:
    activations in float8, weights in 4 bits) move its logits, and by less
    than leaving a layer out does; its default-precision pass is float32
    on the CPU. An unknown name rounds nothing."""
    hf = _kimi_hf()
    tokens = np.random.default_rng(5).integers(
        0, hf["vocab_size"], size=40).tolist()
    _, params, _, _ = _kimi_setup(hf)
    want = kimi_ref.logits_for(params, hf, tokens, 8)
    if control == "default_precision":
        got = kimi_ref.logits_for(params, hf, tokens, 8, precision="default")
        assert _std_err(got, want) < 1e-4
        act, wt = kimi_ref._rounding("drop_layer")
        x = jnp.linspace(-3.0, 3.0, 64).reshape(2, 32)
        assert (act(x) == x).all() and (wt(x) == x).all()
        return
    assert control in kimi_ref.CONTROLS
    assert control not in kimi_ref.breakages_for(hf)
    off = _std_err(kimi_ref.logits_for(params, hf, tokens, 8, control), want)
    dropped = _std_err(kimi_ref.logits_for(params, hf, tokens, 8,
                                           "drop_layer"), want)
    assert 1e-3 < off < dropped
    if control == "int4_weights":
        w = jax.random.normal(jax.random.PRNGKey(0), (3, 256, 8))
        q = kimi_ref._int4_groups(w)
        # 15 levels a group of 128 rows and column, the largest kept
        for g in (q[1, :128, 3], q[2, 128:, 0]):
            assert len(np.unique(np.asarray(g))) <= 15
        assert float(jnp.abs(q - w).max()) <= float(jnp.abs(w).max()) / 14
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 1e-3
        r = kimi_ref._fp8_rows(x)
        assert float(jnp.abs(r / x - 1).max()) < 2 ** -3     # its own scale
        assert float(jnp.abs(r - x).max()) > 0


def test_mla_prefill_roofline_takes_work_and_time_from_the_same_chunks(
        monkeypatch):
    """benchmark/references/kimi_k2_costs.py on a small recorded trace:
    the kernel's calls per layer and dispatch tell the key blocks the
    traced chunks walked, so the share is the same whichever lengths the
    profiler's window caught at one speed of the kernel; the configuration
    is the one whose deployment the engine shows; another engine, or a
    trace without the kernel, reads nothing."""
    import types
    monkeypatch.syspath_prepend(_BENCH)      # references/, peaks.py
    from references import kimi_k2_costs as costs
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    engine = {"max_num_seqs": 16, "num_kv_blocks": 26624,
              "kv_block_size": 16}
    config, chunk = costs.served_config({"engine": engine})
    assert (config["model_type"], chunk) == ("kimi_k2", 1024)
    L = config["num_hidden_layers"]

    def ctx(blocks, chunks, us_a_call=1000.0):
        calls = blocks * L * chunks
        return {"engine": engine, "trace": {
            "programs": [["jit_prefill", 1.0, chunks]],
            "ops": [["%mla_prefill.7 f32[64,1024,128] custom-call",
                     0.75 * calls * us_a_call * 1e-6, 0.75 * calls],
                    ["%mla_prefill.6 f32[64,1024,128] custom-call",
                     0.25 * calls * us_a_call * 1e-6, 0.25 * calls],
                    ["%fusion.1 bf16[1024,64,192] fusion", 9.0, calls]]}}

    ms = costs.prefill_seconds_per_dispatch(ctx(6, 40)) * 1e3
    assert ms == pytest.approx(6 * L * 1000.0 * 1e-3)    # the calls alone
    short, long_ = (costs.prefill_roofline_pct(ctx(n, 40)) for n in (4, 10))
    # a table of 3.5 or of 9.5 key blocks, at the same time a call:
    # the share moves by the expansion's part alone, not by the length
    assert 0 < short < 100 and abs(long_ / short - 1) < 0.15
    live = (6 - 1) * costs.KEY_BLOCK + 1024
    cost = costs.prefill_attention(
        config, 1024 * (live - 1024) + 1024 * 1025 / 2, 1024, live)
    want = 100 * cost["flops"] / 197e12 / (ms / 1e3)
    assert costs.prefill_roofline_pct(ctx(6, 40)) == pytest.approx(want)
    assert costs.prefill_roofline_pct(ctx(6, 7)) == pytest.approx(want)
    other = dict(ctx(6, 40), engine=dict(engine, max_num_seqs=64))
    assert costs.prefill_roofline_pct(other) is None
    assert costs.decode_roofline_pct(other) is None
    bare = {"engine": engine, "trace": {"programs": [["jit_prefill", 1, 4]],
                                        "ops": []}}
    assert costs.prefill_roofline_pct(bare) is None
    assert costs.prefill_seconds_per_dispatch(bare) is None


def test_the_32_shares_of_a_one_group_router_add_up_to_the_uncut_layer(
        kimi_ref):
    """Guide "model-configs" section 4 at a 384-like width: 96 experts in
    one group, 32 shares of 3, the top 8; what the shares give, the shared
    expert counted once, adds up to the uncut layer — in the engine, and
    against the uncut reference."""
    E, shares = 96, 32
    held = E // shares
    base = dict(n_routed_experts_published=E, num_experts_per_tok=8,
                num_hidden_layers=2)
    hf_whole = _kimi_hf(**dict(base, n_routed_experts=E))
    cfg_whole, params, _, _ = _kimi_setup(hf_whole)
    m = jax.random.normal(jax.random.PRNGKey(5), (24, cfg_whole.hidden_size))
    stack = {k[len("layers."):]: v for k, v in params.items()
             if k.startswith("layers.")}
    names = ("router", "router_bias", "moe_gate", "moe_up", "moe_down",
             "sh_gate", "sh_up", "sh_down")
    lp = {n: stack[n][0] for n in names}
    with jax.default_matmul_precision("highest"):
        whole = mla._moe_mlp(m, lp, cfg_whole)
        shared_only = mla._moe_mlp(
            m, dict(lp, moe_down=jnp.zeros_like(lp["moe_down"])), cfg_whole)
        total = -(shares - 1) * shared_only
        for share in range(shares):
            hf = _kimi_hf(**dict(base, n_routed_experts=held,
                                 expert_share_index=share))
            cfg = ModelConfig.from_hf_config(hf)
            assert (cfg.num_experts, cfg.router_width, cfg.n_group) == (
                held, E, 1)
            lp_share = dict(lp, **{n: lp[n][held * share:held * (share + 1)]
                                   for n in ("moe_gate", "moe_up",
                                             "moe_down")})
            part = mla._moe_mlp(m, lp_share, cfg)
            total = total + part
            if share in (0, 17, 31):
                # the reference, given the same share, gives the same part
                want = kimi_ref.moe_block(kimi_ref.family(hf))(m, lp_share)
                assert _std_err(part, want) < 1e-4
        uncut = kimi_ref.moe_block(kimi_ref.family(hf_whole))(m, lp)
    assert _std_err(whole, uncut) < 1e-4
    assert _std_err(total, uncut) < 1e-4
    # one share really leaves most of the layer out
    assert _std_err(part, uncut) > 0.05


@pytest.mark.asyncio
async def test_kimi_k2_serves_and_records_the_keys_it_read():
    """Through EngineCore: chunked prefill then decode; the prefill record
    carries key_tokens = Σ keys each prefilled row attended, decode records
    ctx_tokens. Another family's records carry 0."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,
                                        EngineRequest)
    from dynamo_tpu.engine.sampling import SlotSampling
    cfg = ModelConfig.from_hf_config(_kimi_hf())
    core = EngineCore(cfg, EngineConfig(
        max_model_len=128, kv_block_size=16, num_kv_blocks=64,
        max_num_seqs=2, prefill_buckets=[64], prefill_chunk=16),
        attn_impl="xla", param_dtype=jnp.float32)
    assert core.is_mla and not core.is_hybrid
    try:
        prompt = np.random.default_rng(2).integers(
            0, cfg.vocab_size, size=37).tolist()
        req = EngineRequest(rid="r1", prompt=prompt,
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=4, eos_ids=frozenset())
        await core.submit(req)
        n = 0
        while True:
            item, _ = await req.out_queue.get()
            if item is FINISH_SENTINEL:
                break
            n += 1
        assert n == 4
        records = core.flight.dump()
        prefill = [r for r in records if r["kind"] == "prefill"]
        assert prefill[-1]["key_tokens"] == 37 * 38 // 2
        assert prefill[-1]["scan_tokens"] == 0
        decode = [r for r in records if r["kind"] == "decode"]
        assert decode and decode[0]["ctx_tokens"] >= 38
    finally:
        await core.stop()


_KIMI_ENGINE = dict(max_model_len=128, kv_block_size=16, num_kv_blocks=64,
                    max_num_seqs=2, prefill_buckets=[32, 64])


@pytest.mark.asyncio
@pytest.mark.parametrize("option", [
    {"ragged_dispatch": True}, {"spec_k": 2}, {"kv_quantization": "int8"},
    {"host_kv_blocks": 8}, {"prefill_chunk": 16},
    {"decode_steps_per_dispatch": 4}],
    ids=lambda o: next(iter(o)))
async def test_kimi_k2_takes_what_mla_carries_without_an_indexer(option):
    """docs/mla_dense.md's matrix, one line a claim: kimi_k2 has no index
    keys, so every path models/mla.py serves a latent-only pool with is
    open to it — ragged dispatch, speculative verify, an int8 latent pool,
    the host tier, chunked prefill, several steps a dispatch — and serves
    the plain engine's greedy tokens (an int8 pool: its own)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig.from_hf_config(_kimi_hf())
    params = mla.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=41).tolist()
    tokens = []
    for over in ({}, option):
        core = EngineCore(cfg, EngineConfig(**dict(_KIMI_ENGINE, **over)),
                          params=dict(params), attn_impl="xla",
                          param_dtype=jnp.float32)
        assert core.is_mla and core.wire_kv_heads == 1
        try:
            tokens.append(await _greedy_tokens(core, "k", prompt, n=6))
        finally:
            await core.stop()
    assert len(tokens[1]) == 6
    if "kv_quantization" not in option:
        assert tokens[1] == tokens[0]


@pytest.mark.asyncio
async def test_kimi_k2_meshes_whole_model_yes_one_share_no():
    """One chip's share of the experts refuses every mesh at build (the
    share IS this chip's part of an expert-parallel layer); the whole
    router's experts serve over a tp×ep mesh with the single chip's
    tokens."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    with pytest.raises(NotImplementedError, match="expert share"):
        EngineCore(ModelConfig.from_hf_config(_kimi_hf()),
                   EngineConfig(**_KIMI_ENGINE), attn_impl="xla",
                   param_dtype=jnp.float32, mesh=make_mesh(tp=2))
    cfg = ModelConfig.from_hf_config(_kimi_hf(n_routed_experts=24))
    assert cfg.num_experts_total == 0 and cfg.num_experts == 24
    params = mla.init_params(cfg, jax.random.PRNGKey(8), dtype=jnp.float32)
    prompt = list(range(2, 40))
    tokens = []
    for mesh in (None, make_mesh(dp=1, tp=2, sp=1, ep=2)):
        core = EngineCore(cfg, EngineConfig(**_KIMI_ENGINE),
                          params=dict(params), attn_impl="xla",
                          param_dtype=jnp.float32, mesh=mesh)
        try:
            tokens.append(await _greedy_tokens(core, "m", prompt, n=6))
        finally:
            await core.stop()
    assert tokens[1] == tokens[0]


# ---------------------------------------------------------------------------
# Hybrid models (a dense prefix, then expert layers): two scans over ONE
# [L, ...] stack of every attention weight
# ---------------------------------------------------------------------------

_ATTN_STACKS = ("ln1", "ln2", "wq", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                "kv_norm", "wkv_b", "wo", "idx_wq_b", "idx_wk",
                "idx_k_norm_w", "idx_k_norm_b", "idx_w")
_DENSE_STACKS = ("dense_gate", "dense_up", "dense_down", "dense_gateup")
_HYBRID_STACKS = {       # the fixture's attention stacks, by name
    "tiny-deepseek-v2": {"ln1", "ln2", "wq", "wkv_a", "kv_norm", "wkv_b",
                         "wo"},
    "tiny-deepseek-v32": {"ln1", "ln2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                          "kv_norm", "wkv_b", "wo", "idx_wq_b", "idx_wk",
                          "idx_k_norm_w", "idx_k_norm_b", "idx_w"},
    "tiny-kimi-k2": {"ln1", "ln2", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                     "kv_norm", "wkv_b", "wo"},
}


class _PerLayerLoop:
    """The parent's arithmetic, plainly: a Python loop over the layers, the
    layer's attention tensors taken as ``stack[n][i]`` (a static index, made
    before the layer is traced) and the stacks of its own kind as
    ``stack[n][i - first]``. Stands in for ``lax.scan`` over
    ``{"lp", "i"}`` and for the body's ``lax.dynamic_index_in_dim``, and
    notes every (leading dimension, layer) read. The layer's body still
    compiles as ONE program, a scan of one, as the served body does: the
    CPU fuses a compiled body otherwise than it runs the same operations
    one by one, and the two differ in the last bit."""

    def __init__(self, monkeypatch):
        self.reads, self.layer = [], None
        self.scan, self.index = jax.lax.scan, jax.lax.dynamic_index_in_dim
        monkeypatch.setattr(jax.lax, "scan", self._scan)
        monkeypatch.setattr(jax.lax, "dynamic_index_in_dim", self._index)

    def _scan(self, f, init, xs=None, *a, **k):
        if not (isinstance(xs, dict) and set(xs) == {"lp", "i"}):
            return self.scan(f, init, xs, *a, **k)
        carry = init
        for j, li in enumerate(np.asarray(xs["i"])):
            self.layer = int(li)
            one = jax.tree.map(lambda w: w[j:j + 1], xs)
            # a function of its own a layer: scan keeps the traced body of
            # a function it has seen, with layer 0's tensors in it
            carry, _ = self.scan(lambda c, x: f(c, x), carry, one)
        self.layer = None
        return carry, None

    def _index(self, w, index, axis=0, keepdims=True):
        if self.layer is None:
            return self.index(w, index, axis, keepdims)
        assert axis == 0 and not keepdims
        self.reads.append((w.shape[0], self.layer))
        return w[self.layer]


def _three_chunks_and_a_decode_step(params, cfg, tokens):
    """→ the logits behind a prefill of tokens[:16] (the first chunk: a
    whole prompt), behind the third chunk of 16 over the cache of the two
    before it, and of a decode step behind that."""
    statics = ModelStatics(cfg=cfg, block_size=16, attn_impl="xla")
    table = jnp.arange(1, 9, dtype=jnp.int32)
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, 16, dtype=jnp.float32)
    out = []
    for lo in (0, 16, 32):
        logits, kv = mla.prefill_forward(
            params, kv, jnp.asarray(tokens[lo:lo + 16], jnp.int32),
            table, jnp.asarray(lo), jnp.asarray(16), statics)
        out.append(logits)
    logits, kv = mla.decode_forward(
        params, kv, jnp.asarray([tokens[48], 0]), jnp.asarray([48, 0]),
        jnp.stack([table, jnp.zeros_like(table)]), statics)
    return [np.asarray(x) for x in (out[0], out[2], logits[0])]


@pytest.fixture
def executables_dropped():
    """Forwards run operation by operation, and a program a layer: a case
    below leaves ~3,500 memory mappings of compiled code in the process
    (measured; ``/proc/self/maps``), which may hold 65,530
    (``vm.max_map_count``) — past it XLA's next compile is a segmentation
    fault, and this file alone crossed it. Dropping JAX's caches unmaps
    them (4,108 → 696 behind one case)."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize("family,weights", [
    (f, w) for f in sorted(_HYBRID_STACKS) for w in ("float32", "int8")
] + [("two-dense", "float32")])
def test_hybrid_scans_read_the_whole_stacks_like_a_per_layer_loop(
        family, weights, monkeypatch, executables_dropped):
    """A hybrid model's two scans take no ``stack[n][:k]`` / ``stack[n][k:]``
    copy of an attention stack: the stacks stay whole beside them and the
    body reads layer ``li`` in place. A prefill, a chunk of a chunked
    prefill and a decode step behind it give logits BIT-equal to the per-layer loop over
    ``stack[n][i]`` (``_PerLayerLoop``), on the engine's own parameter
    tree, in float32 and with int8 weights (``q`` and ``scale`` are read at
    the same layer). ``two-dense`` is DeepSeek-V2's fixture with two dense
    and three expert layers, so that a layer's index among its kind and
    among all layers differ on both sides.

    The tree keeps its keys and shapes: the benchmark's references
    (benchmark/reference.py, benchmark/references/deepseek_v32.py,
    kimi_k2.py, deepseek_v2.py) take the ENGINE's parameters by name and
    GLOBAL layer index — ``params["layers.wo"]`` is ``[L, H·v, D]`` and
    layer ``li`` is ``[li]`` — so a tree re-laid by layer kind would break
    ``correct`` in every MLA cell."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.quant import QuantizedArray
    deep = family == "two-dense"
    hf = (_fixture_hf("tiny-deepseek-v2", num_hidden_layers=5,
                      first_k_dense_replace=2) if deep
          else _fixture_hf(family))
    cfg = ModelConfig.from_hf_config(hf)
    L, k = cfg.num_layers, cfg.first_k_dense
    assert 0 < k < L and cfg.num_experts > 0
    core = EngineCore(cfg, EngineConfig(
        max_model_len=256, kv_block_size=16, num_kv_blocks=32,
        max_num_seqs=2, prefill_buckets=[64], seed=1,
        quantization="none" if weights == "float32" else "int8"),
        attn_impl="xla", param_dtype=jnp.float32)
    params = core.params
    # what the references read: every attention stack whole, [L, ...],
    # under its name; one stack per layer kind for the rest
    attn = _HYBRID_STACKS["tiny-deepseek-v2" if deep else family]
    shapes = mla.param_shapes(cfg)
    for tree in (shapes, {n: w.shape for n, w in params.items()}):
        layers = {n[len("layers."):]: s for n, s in tree.items()
                  if n.startswith("layers.")}
        assert {n for n in layers if n in _ATTN_STACKS} == attn
        for n, shape in layers.items():
            assert shape[0] == (L if n in attn else
                                k if n in _DENSE_STACKS else L - k), n
    assert all(tuple(params["layers." + n].shape) == shapes["layers." + n]
               for n in attn)
    assert isinstance(params["layers.wo"], QuantizedArray) == (
        weights == "int8")
    assert not isinstance(params["layers.wkv_b"], QuantizedArray)

    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, size=49)
    got = _three_chunks_and_a_decode_step(params, cfg, tokens)
    loop = _PerLayerLoop(monkeypatch)
    want = _three_chunks_and_a_decode_step(params, cfg, tokens)
    monkeypatch.undo()
    # the loop read each whole stack (leading dimension L) at every layer,
    # and nothing else through the index
    assert {r[0] for r in loop.reads} == {L}
    assert sorted({r[1] for r in loop.reads}) == list(range(L))
    leaves = len(jax.tree.leaves(
        {n: params["layers." + n] for n in attn}))
    assert len(loop.reads) == 4 * L * leaves      # three chunks and a step
    for name, a, b in zip(("prefill", "chunked prefill", "decode"),
                          got, want):
        assert np.isfinite(a).all() and a.std() > 0, name
        assert np.array_equal(a, b), (name, float(np.abs(a - b).max()))
