"""phi4flash (SambaY: state-space, window and shared-cache layers) on
``engine/models/sambay.py``, held to ``benchmark/references/phi4flash.py``
in float32 on the tiny fixture: the layer kinds, the three kinds of cache
and who may touch them, the slot's lifecycle through the engine, the
refusals. The reference has no state to forget; the faults of the engine's
bookkeeping (a state that is not reset, a row written for a slot that is
not live, padding that leaks into the state) are held here.
"""

import asyncio
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import ssm
from dynamo_tpu.engine.block_copy import move_blocks
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import sambay
from dynamo_tpu.engine.models.llama import ModelStatics
from dynamo_tpu.llm.kv.pool import KvBlockManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 8                     # window 8: a ring of 2 blocks, 16 rows
M = 16                     # blocks a table holds: 128 positions
SLOTS = 3
TOL_STD = 1e-4
EXTRAS = ("source", "reduced", "assumed", "deployment", "reference",
          "memory_analysis", "notes")


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_phi4flash",
            os.path.join(BENCH, "references", "phi4flash.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


def _hf(**over) -> dict:
    with open(os.path.join(BENCH, "fixtures", "tiny-phi4flash.json")) as f:
        hf = json.load(f)
    return dict({k: v for k, v in hf.items() if k not in EXTRAS}, **over)


def _published() -> dict:
    with open(os.path.join(BENCH, "configs", "phi4-mini-flash.json")) as f:
        hf = json.load(f)
    return {k: v for k, v in hf.items() if k not in EXTRAS}


def _setup(hf=None, seed: int = 1, num_blocks: int = 1 + SLOTS * M):
    cfg = ModelConfig.from_hf_config(hf or _hf())
    params = sambay.init_params(cfg, jax.random.PRNGKey(seed),
                                dtype=jnp.float32)
    kv = sambay.init_kv_cache(cfg, num_blocks, BS, SLOTS, dtype=jnp.float32)
    return cfg, params, kv, ModelStatics(cfg=cfg, block_size=BS,
                                         attn_impl="xla")


def _tokens(cfg, n: int, seed: int = 3) -> list:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).tolist()


def _table(slot: int) -> jnp.ndarray:
    """Slot s owns the blocks 1 + s*M .. (s+1)*M of the paged pool."""
    return jnp.arange(1 + slot * M, 1 + (slot + 1) * M, dtype=jnp.int32)


_prefill_jit = jax.jit(sambay.prefill_forward, static_argnums=(6,))
_decode_jit = jax.jit(sambay.decode_forward, static_argnums=(5,))


def _prefill(params, kv, statics, tokens, start=0, pad_to=32, slot=0):
    padded = np.zeros(pad_to, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return _prefill_jit(
            params, kv, jnp.asarray(padded), _table(slot),
            jnp.asarray(start, jnp.int32),
            jnp.asarray(len(tokens), jnp.int32), statics,
            jnp.asarray(slot, jnp.int32))


def _decode(params, kv, statics, rows: dict):
    """One step; rows: slot -> (token, position); the others are not
    live (the trash table)."""
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, M), np.int32)
    for slot, (tok, p) in rows.items():
        tokens[slot], pos[slot] = tok, p
        tables[slot] = np.asarray(_table(slot))
    with jax.default_matmul_precision("highest"):
        return _decode_jit(
            params, kv, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(tables), statics)


def _err_std(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / want.std())


def _slot_state(kv, slot: int) -> dict:
    return {"ssm": np.asarray(kv["ssm"][:, slot]),
            "conv": np.asarray(kv["conv"][:, slot]),
            "win_k": np.asarray(kv["win_k"][:, slot]),
            "win_v": np.asarray(kv["win_v"][:, slot])}


# ------------------------------------------------------------ the model

@pytest.mark.parametrize("layers, want", [
    (32, ["mamba", "window"] * 8 + ["export", "full"] + ["gmu", "cross"] * 7),
    (8, ["mamba", "window", "mamba", "window", "export", "full", "gmu",
         "cross"]),
    (6, ["mamba", "window", "export", "full", "gmu", "cross"]),
    (4, ["mamba", "window", "export", "full"]),
])
def test_layer_kinds_by_index(ref, layers, want):
    hf = _hf(num_hidden_layers=layers)
    assert list(sambay.layer_kinds(ModelConfig.from_hf_config(hf))) == want
    assert list(ref.layer_kinds(hf)) == want


def test_from_hf_config_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(_published())
    assert cfg.is_sambay and cfg.model_type == "phi4flash"
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == (
        2560, 32, 40, 20, 64, 10240, 200064)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank, cfg.sliding_window) == (5120, 16, 4, 160, 512)
    assert cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-5
    kinds = sambay.layer_kinds(cfg)
    assert [kinds.count(k) for k in sambay.KINDS] == [8, 8, 1, 1, 7, 7]
    shapes = sambay.param_shapes(cfg)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert 3.8e9 < n < 3.9e9, n         # 3.85 B with the tied embedding


@pytest.mark.parametrize("bad, match", [
    ({"mb_per_layer": 1}, "mb_per_layer"),
    ({"num_hidden_layers": 7}, "even"),
    ({"sliding_window": None}, "sliding_window"),
    ({"hidden_size": 96, "num_attention_heads": 6}, "128 lanes"),
])
def test_from_hf_config_refuses_a_phi4flash_it_cannot_run(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**bad))


@pytest.mark.parametrize("keys, match", [
    ({"mb_per_layer": 2}, "mb_per_layer"),
    ({"linear_attn_config": {"kda_layers": [1, 2]}}, "linear_attn_config"),
    ({"layer_types": ["full_attention", "linear_attention"]},
     "linear_attention"),
])
def test_an_unknown_family_with_state_keys_is_not_parsed_as_llama(keys,
                                                                  match):
    hf = {"model_type": "some_hybrid", "hidden_size": 64,
          "num_attention_heads": 4, "num_hidden_layers": 2,
          "vocab_size": 128, **keys}
    with pytest.raises(ValueError, match=match) as e:
        ModelConfig.from_hf_config(hf)
    assert "some_hybrid" in str(e.value)
    # the families that are served keep parsing
    assert ModelConfig.from_hf_config(
        {"model_type": "gemma2", "hidden_size": 64, "num_attention_heads": 4,
         "num_hidden_layers": 2, "vocab_size": 128, "layer_types": [
             "sliding_attention", "full_attention"]}).sliding_window == 4096


def test_seeded_weights_of_the_state_space_layer():
    from dynamo_tpu.engine.quant import QuantizedArray, quantize_params
    cfg, params, _, _ = _setup()
    a_log = np.asarray(params["layers.mamba.A_log"])
    np.testing.assert_allclose(np.exp(a_log[0, :, 0]), np.arange(1, 17),
                               rtol=1e-6)
    assert (np.asarray(params["layers.export.D"]) == 1).all()
    dt = np.log1p(np.exp(np.asarray(params["layers.mamba.dt_b"])))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert 0.05 < float(np.asarray(params["layers.window.lam"]).std()) < 0.2
    q = quantize_params(params)
    int8 = {n for n, w in q.items() if isinstance(w, QuantizedArray)}
    kept = {n.rsplit(".", 1)[-1] for n in q if n not in int8}
    assert {"A_log", "D", "dt_b", "conv_w", "conv_b", "lam", "subnorm",
            "ln1_w", "ln1_b", "attn_qkv_b"} <= kept
    assert {"layers.mamba.ssm_in", "layers.mamba.ssm_x",
            "layers.export.ssm_dt", "layers.window.attn_qkv",
            "layers.cross.cross_q", "layers.gmu.gmu_in",
            "layers.full.mlp_gateup", "embed", "lm_head"} <= int8


def test_a_checkpoint_round_trips_through_the_loader(tmp_path):
    """engine/weights.py: the checkpoint names of every layer kind map
    onto the stacks and back; an unknown tensor and a missing one fail."""
    from safetensors.numpy import load_file, save_file
    from dynamo_tpu.engine import weights
    cfg, params, _, _ = _setup()
    weights.save_sambay_hf_style(params, cfg, str(tmp_path))
    path = os.path.join(str(tmp_path), "model.safetensors")
    tensors = load_file(path)
    assert tensors["model.layers.0.attn.conv1d.weight"].shape == (128, 1, 4)
    assert tensors["model.layers.1.attn.Wqkv.weight"].shape == (128, 64)
    assert tensors["model.layers.7.attn.Wqkv.weight"].shape == (64, 64)
    assert tensors["model.layers.6.attn.in_proj.weight"].shape == (128, 64)
    assert tensors["model.layers.4.attn.A_log"].shape == (128, 16)
    back = weights.load_params_auto(str(tmp_path), cfg, dtype=jnp.float32)
    assert set(back) == set(params)
    for name, w in params.items():
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(w),
                                      err_msg=name)
    save_file(dict(tensors, **{"model.layers.0.attn.rotary.inv_freq":
                               np.zeros(4, np.float32)}), path)
    with pytest.raises(ValueError, match="no place"):
        weights.load_sambay_params(str(tmp_path), cfg)
    tensors.pop("model.layers.5.attn.inner_cross_attn.lambda_k2")
    save_file(tensors, path)
    with pytest.raises(ValueError, match="lacks 1"):
        weights.load_sambay_params(str(tmp_path), cfg)


def test_prefill_then_decode_through_the_caches_equals_the_full_forward(ref):
    """21 prompt tokens (past the window of 8 and past the ring's 16 rows),
    then 14 decoded one by one: every step's logits are the reference's
    full forward over the sequence so far."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 35)
    want = ref.logits_for(params, hf, seq, 15)
    logits, kv = _prefill(params, kv, statics, seq[:21], slot=1)
    assert _err_std(logits, want[0]) < TOL_STD
    for i, pos in enumerate(range(21, 35)):
        logits, kv = _decode(params, kv, statics, {1: (seq[pos], pos)})
        assert _err_std(logits[1], want[i + 1]) < TOL_STD, pos


def test_decode_from_position_zero_starts_from_the_zero_state(ref):
    """A prompt fed token by token through the decode program (lane
    prefill) on a slot that still holds its predecessor's state and rows:
    position 0 starts from zero, the window grows from one key."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 30, seed=9), slot=0)
    seq = _tokens(cfg, 20, seed=4)
    want = ref.logits_for(params, hf, seq, 20)
    for pos, tok in enumerate(seq):
        logits, kv = _decode(params, kv, statics, {0: (tok, pos)})
        assert _err_std(logits[0], want[pos]) < TOL_STD, pos


def test_padding_leaves_the_state_at_true_len(ref):
    """The same 19 tokens in a bucket of 32 and of 64: the same state,
    conv inputs and ring rows, and the same next step."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 20)
    la, kva = _prefill(params, kv, statics, seq[:19], pad_to=32)
    lb, kvb = _prefill(params, kv, statics, seq[:19], pad_to=64)
    assert _err_std(la, lb) < TOL_STD
    for key, a in _slot_state(kva, 0).items():
        np.testing.assert_allclose(a, _slot_state(kvb, 0)[key], atol=1e-5,
                                   err_msg=key)
    want = ref.logits_for(params, hf, seq, 1)
    logits, _ = _decode(params, kvb, statics, {0: (seq[19], 19)})
    assert _err_std(logits[0], want[0]) < TOL_STD


@pytest.mark.parametrize("cuts", [(16, 32), (8, 16, 24, 32), (5, 21)])
def test_chunked_prefill_equals_whole_prefill(cuts):
    """Chunks that end on and off block, window and ring boundaries carry
    the state, the conv inputs and the ring rows to the next chunk."""
    cfg, params, kv, statics = _setup()
    seq = _tokens(cfg, 37)
    whole, kvw = _prefill(params, kv, statics, seq, pad_to=64, slot=2)
    lo, kvc = 0, kv
    for hi in cuts + (37,):
        logits, kvc = _prefill(params, kvc, statics, seq[lo:hi], start=lo,
                               slot=2)
        lo = hi
    assert _err_std(logits, whole) < TOL_STD
    for key, a in _slot_state(kvw, 2).items():
        np.testing.assert_allclose(a, _slot_state(kvc, 2)[key], atol=1e-5,
                                   err_msg=key)


def test_two_sequences_of_unequal_length_in_one_decode_batch(ref):
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    a, b = _tokens(cfg, 30, seed=5), _tokens(cfg, 12, seed=6)
    _, kv = _prefill(params, kv, statics, a[:25], slot=0)
    _, kv = _prefill(params, kv, statics, b[:7], slot=2)
    wa = ref.logits_for(params, hf, a, 5)
    wb = ref.logits_for(params, hf, b, 5)
    for i in range(5):
        logits, kv = _decode(params, kv, statics,
                             {0: (a[25 + i], 25 + i), 2: (b[7 + i], 7 + i)})
        assert _err_std(logits[0], wa[i]) < TOL_STD
        assert _err_std(logits[2], wb[i]) < TOL_STD


def test_a_row_that_is_not_live_keeps_its_state_and_rows():
    """A slot that sits a dispatch out (its table aimed at the trash
    block) is untouched by it: state, conv inputs, ring rows."""
    cfg, params, kv, statics = _setup()
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 20), slot=1)
    before = _slot_state(kv, 1)
    _, kv = _decode(params, kv, statics, {0: (5, 0)})
    for key, a in before.items():
        assert (a == _slot_state(kv, 1)[key]).all(), key
    assert np.abs(_slot_state(kv, 0)["ssm"]).max() > 0


def test_a_reused_slot_sees_nothing_of_its_predecessor():
    cfg, params, kv, statics = _setup()
    b = _tokens(cfg, 11, seed=8)
    fresh, _ = _prefill(params, kv, statics, b, slot=1)
    _, used = _prefill(params, kv, statics, _tokens(cfg, 30, seed=7), slot=1)
    again, used = _prefill(params, used, statics, b, slot=1)
    assert _err_std(again, fresh) < TOL_STD
    one, _ = _decode(params, used, statics, {1: (3, 11)})
    _, clean = _prefill(params, kv, statics, b, slot=1)
    two, _ = _decode(params, clean, statics, {1: (3, 11)})
    assert _err_std(one[1], two[1]) < TOL_STD


def test_every_breakage_moves_the_reference(ref):
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    seq = _tokens(cfg, 40)
    want = ref.logits_for(params, hf, seq, 8)
    assert set(ref.breakages_for(hf)) == set(ref.BREAKAGES)
    for broken in ref.BREAKAGES:
        got = ref.logits_for(params, hf, seq, 8, broken=broken)
        assert _err_std(got, want) > 0.25, broken
    assert ref.breakages_for(_hf(num_hidden_layers=4)) == tuple(
        b for b in ref.BREAKAGES
        if b not in ("memory_after_gate", "cross_reads_window_kv"))


# ---------------------------------------------------------- the kernels

def _scan_by_hand(dt, x, b, c, a, h):
    ys = []
    for t in range(dt.shape[0]):
        h = np.exp(dt[t][None] * a) * h + (dt[t] * x[t])[None] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0))
    return np.stack(ys), h


def _ssm_inputs(T, di=256, n=16, seed=0):
    rng = np.random.default_rng(seed)
    return (np.abs(rng.normal(size=(T, di))).astype(np.float32) * 0.2,
            rng.normal(size=(T, di)).astype(np.float32),
            rng.normal(size=(T, n)).astype(np.float32),
            rng.normal(size=(T, n)).astype(np.float32),
            -np.abs(rng.normal(size=(n, di))).astype(np.float32) * 4,
            rng.normal(size=(n, di)).astype(np.float32))


@pytest.mark.parametrize("T, true_len", [(16, 16), (21, 13), (136, 130)])
def test_ssm_scan_is_the_recurrence_and_stops_at_true_len(T, true_len):
    dt, x, b, c, a, h0 = _ssm_inputs(T)
    dt[true_len:] = 0
    y, h = ssm.ssm_scan(*map(jnp.asarray, (dt, x, b, c, a, h0)),
                        interpret=True)
    wy, wh = _scan_by_hand(dt[:true_len], x[:true_len], b, c, a, h0)
    np.testing.assert_allclose(np.asarray(y)[:true_len], wy, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), wh, rtol=2e-5, atol=2e-5)


def test_ssm_step_rewrites_one_layer_of_the_state_in_place():
    dt, x, b, c, a, _ = _ssm_inputs(8, seed=2)
    state = np.random.default_rng(5).normal(size=(24, 16, 256)).astype(
        np.float32)
    keep = np.ones(8, np.float32)
    keep[2] = 0                       # slot 2 starts from the zero state
    dt[5] = 0                         # slot 5 is not live
    y, new = ssm.ssm_step(*map(jnp.asarray, (dt, x, keep, b, c, a, state)),
                          jnp.int32(1), interpret=True)
    new = np.asarray(new)
    assert (new[:8] == state[:8]).all() and (new[16:] == state[16:]).all()
    assert (new[8 + 5] == state[8 + 5]).all()
    for s in range(8):
        wy, wh = _scan_by_hand(dt[s:s + 1], x[s:s + 1], b[s:s + 1],
                               c[s:s + 1], a, state[8 + s] * keep[s])
        np.testing.assert_allclose(new[8 + s], wh, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y)[s], wy[0], rtol=2e-5,
                                   atol=2e-5)


# ------------------------------------------------ the cache, by its kinds

def test_blocks_and_bytes_by_kind_at_the_published_widths():
    """A 7,000-token context holds 438 paged blocks of the one full layer
    and 33 ring blocks of each window layer, whatever its length; the
    cell's pool by kind is 2.43 + 1.38 + 0.21 GB where one row kind for
    all nine attention layers would be 21.9 GB."""
    cfg = ModelConfig.from_hf_config(_published())
    layout = sambay.cache_layout(cfg, 16)
    assert layout.ring_blocks == 33
    assert (layout.paged_layers, layout.readers_of_paged,
            layout.window_layers, layout.state_layers) == (1, 8, 8, 9)
    assert layout.blocks_by_kind(7000) == {"paged": 438, "window": 33,
                                           "state": 1}
    assert layout.blocks_by_kind(100)["window"] == 7
    by = layout.bytes_by_kind(29696, 64)
    assert by["paged"] == 29696 * 16 * 5120 == 2_432_696_320
    assert by["window"] == 8 * 64 * 528 * 5120 == 1_384_120_320
    assert by["state"] == 64 * 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert 0.20e9 < by["state"] < 0.22e9
    uniform = 9 * 5120 * 29696 * 16
    assert 21.8e9 < uniform < 22.0e9
    kv = jax.eval_shape(lambda: sambay.init_kv_cache(cfg, 29696, 16, 64))
    assert {k: v.shape for k, v in kv.items()} == {
        "k": (1, 475136, 1280), "v": (1, 475136, 1280),
        "win_k": (8, 64, 528, 1280), "win_v": (8, 64, 528, 1280),
        "ssm": (9, 64, 16, 5120), "conv": (9, 64, 3, 5120)}
    assert kv["ssm"].dtype == jnp.float32
    assert sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in kv.values()) == sum(by.values())


def test_the_defrag_copy_moves_paged_blocks_only():
    cfg, params, kv, statics = _setup()
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 30), slot=1)
    before = {k: np.asarray(v) for k, v in kv.items()}
    src = [int(b) for b in _table(1)[:4]]
    dst = [41, 42, 43, 44]
    moved = move_blocks(kv, src, dst, BS)
    for key in ("win_k", "win_v", "ssm", "conv"):
        assert (np.asarray(moved[key]) == before[key]).all(), key
    for key in ("k", "v"):
        paged = np.asarray(moved[key]).reshape(1, -1, BS, before[key].shape[-1])
        was = before[key].reshape(paged.shape)
        assert (paged[:, dst] == was[:, src]).all()
        assert np.abs(was[:, src]).max() > 0


def test_a_manager_with_state_matches_and_registers_no_prefix():
    cfg = ModelConfig.from_hf_config(_hf())
    stored = []
    with_state = KvBlockManager(
        32, BS, layout=sambay.cache_layout(cfg, BS), prefer_native=False,
        on_stored=lambda *a: stored.append(a))
    plain = KvBlockManager(32, BS, prefer_native=False)
    prompt = list(range(40))
    for manager, hit in ((with_state, 0), (plain, 32)):
        plan = manager.prepare_prefill(prompt)
        n = manager.register_full_blocks(plan.all_blocks, plan.seq, 0)
        manager.pool.release(plan.all_blocks)
        again = manager.prepare_prefill(prompt)
        assert again.hit_tokens == hit and len(again.hit_blocks) == hit // BS
        assert n == (0 if manager is with_state else 5)
    assert not stored and not with_state.enable_reuse


# ------------------------------------------------------------ the engine

def _engine_cfg(**over) -> EngineConfig:
    base = dict(max_model_len=128, kv_block_size=BS, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32, 64])
    return EngineConfig(**dict(base, **over))


def _engine(params, cfg, **over):
    from dynamo_tpu.engine.core import EngineCore
    return EngineCore(cfg, _engine_cfg(**over), params=dict(params),
                      attn_impl="xla", param_dtype=jnp.float32)


async def _serve(core, rid, prompt, n=6):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid=rid, prompt=list(prompt),
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=n, eos_ids=frozenset())
    await core.submit(req)
    toks, lps = [], []
    while True:
        item, lp = await req.out_queue.get()
        if item is FINISH_SENTINEL:
            break
        toks.append(item)
        lps.append(lp)
    return toks, lps, req


def _held_to_the_reference(ref, params, hf, prompt, toks, lps):
    logits = ref.logits_for(params, hf, list(prompt) + toks[:-1], len(toks))
    for tok, lp, row in zip(toks, lps, logits):
        row = row.astype(np.float64)
        ref_lp = row[tok] - (row.max() + np.log(
            np.exp(row - row.max()).sum()))
        assert abs(ref_lp - lp) < 10 * TOL_STD * row.std()
        assert row.max() - row[tok] < 10 * TOL_STD * row.std()


@pytest.mark.parametrize("over", [{}, {"prefill_chunk": 16,
                                       "prefill_buckets": [16, 64]},
                                  {"decode_steps_per_dispatch": 4},
                                  {"decode_steps_per_dispatch": 2,
                                   "lane_prefill_max_tokens": 64}],
                         ids=["whole", "chunked", "k4", "lanes"])
async def test_engine_serves_what_the_reference_computes(ref, over):
    """EngineCore end to end, the loop's own programs and bookkeeping: the
    served tokens and logprobs are the reference's; the same prompt again
    takes no prefix hit; the flight records carry the three new counters;
    the pool is whole again at the end; neither disagg plane and no fabric
    is accepted."""
    from dynamo_tpu.engine.core import EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompt = _tokens(cfg, 29, seed=12)
    core = _engine(params, cfg, **over)
    free = core.kv_manager.pool.free_blocks
    try:
        with jax.default_matmul_precision("highest"):
            if "lane_prefill_max_tokens" in over:
                # a lane rides a batch that is already decoding
                rider = asyncio.create_task(
                    _serve(core, "w", _tokens(cfg, 9, seed=2), n=60))
                while not any(s is not None and s.ready for s in core.slots):
                    await asyncio.sleep(0.01)
                toks, lps, req = await _serve(core, "a", prompt, n=10)
                await rider
                assert core.lane_admissions >= 1
            else:
                toks, lps, req = await _serve(core, "a", prompt, n=10)
            again, lps2, req2 = await _serve(core, "b", prompt, n=10)
        _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        assert again == toks
        np.testing.assert_allclose(lps2, lps, atol=1e-4)
        assert req.prefix_hit_tokens == 0 and req2.prefix_hit_tokens == 0
        records = core.flight.dump()
        prefill = [r for r in records if r["kind"] == "prefill"]
        assert all(r["hit_device"] == 0 for r in prefill)
        assert prefill[-1]["scan_tokens"] == 29
        decode = [r for r in records
                  if r["kind"] == "decode" and r["batch_fill"]]
        layout = core.kv_manager.layout
        for r in decode:
            assert 0 < r["win_tokens"] <= r["ctx_tokens"]
            assert r["win_tokens"] <= 8 * r["emitted"]
            assert r["state_bytes"] == (2 * r["emitted"] * layout.state_bytes
                                        * layout.state_layers)
        assert decode[-1]["win_tokens"] < decode[-1]["ctx_tokens"]
        assert core.kv_manager.pool.free_blocks == free
        assert core.kv_manager.pool.used_blocks == 0
        with pytest.raises(NotImplementedError, match="hand-off"):
            await core.submit(EngineRequest(
                rid="d", prompt=prompt, max_new_tokens=2,
                sampling=SlotSampling(temperature=0.0), eos_ids=frozenset(),
                handoff=object()))
        with pytest.raises(NotImplementedError, match="fabric"):
            core.attach_kv_fabric(object())
    finally:
        await core.stop()


async def test_a_slot_is_re_admitted_while_a_step_is_in_flight(ref):
    """Two slots, three requests: the short one finishes and the queued
    one takes its slot while the long one keeps a step in flight (the
    one-step-ahead pipeline). Each stream is the reference's, so no state
    or window row crossed from the slot's predecessor and no step ran
    twice on a state."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompts = [_tokens(cfg, n, seed=s) for n, s in ((27, 1), (11, 2), (19, 3))]
    core = _engine(params, cfg)
    try:
        with jax.default_matmul_precision("highest"):
            outs = await asyncio.gather(
                _serve(core, "long", prompts[0], n=30),
                _serve(core, "short", prompts[1], n=4),
                _serve(core, "queued", prompts[2], n=12))
        for prompt, (toks, lps, _) in zip(prompts, outs):
            _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        assert outs[1][2].slot == outs[2][2].slot or core.B == 2
        decode = [r for r in core.flight.dump() if r["kind"] == "decode"]
        assert sum(r["chained"] for r in decode) > 0
    finally:
        await core.stop()


async def test_a_preempted_sequence_recomputes_its_state(ref):
    """A pool too small for both sequences: one is preempted, its slot's
    state and rows are dropped with it, and the recompute re-derives them
    from the grown prompt. Both streams stay the reference's."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompts = [_tokens(cfg, 30, seed=21), _tokens(cfg, 30, seed=22)]
    core = _engine(params, cfg, num_kv_blocks=14, prefill_buckets=[32, 64,
                                                                   128])
    try:
        with jax.default_matmul_precision("highest"):
            outs = await asyncio.gather(
                _serve(core, "a", prompts[0], n=36),
                _serve(core, "b", prompts[1], n=36))
        assert core.preemptions > 0, "contention never preempted"
        for prompt, (toks, lps, _) in zip(prompts, outs):
            assert len(toks) == 36
            _held_to_the_reference(ref, params, hf, prompt, toks, lps)
    finally:
        await core.stop()


@pytest.mark.parametrize("over, match", [
    ({"ragged_dispatch": True}, "--ragged"),
    ({"spec_k": 2}, "--spec-k"),
    ({"kv_quantization": "int8"}, "--kv-quantization"),
    ({"host_kv_blocks": 8}, "--host-kv-blocks"),
    ({"tp": 2}, "meshes"),
    ({"quantization": "int4"}, "int4"),
])
def test_engine_refuses_what_cannot_carry_a_slots_state(over, match):
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig.from_hf_config(_hf())
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(cfg, _engine_cfg(**over), attn_impl="xla",
                   param_dtype=jnp.float32)
