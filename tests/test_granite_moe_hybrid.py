"""granitemoehybrid (Mamba-2 layers beside NoPE grouped-query layers, experts
after every layer) on ``engine/models/granite_hybrid.py``, held to
``benchmark/references/granite_moe_hybrid.py`` in float32 on the tiny fixture:
the two kernels against the recurrence, the layer plan, the parser, the
K/V rows + state cache and who may touch it, the slot's lifecycle through the
engine, the refusals, the loader. The reference has no state to forget; the
faults of the engine's bookkeeping (a state that is not reset, a step applied
to a slot that is not live, padding that leaks into the state) are held here.
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

from dynamo_tpu.engine import ssd
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import granite_hybrid as gh
from dynamo_tpu.engine.models import module_for
from dynamo_tpu.engine.models.llama import ModelStatics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 8
M = 16                     # blocks a table holds: 128 positions
SLOTS = 3
TOL_STD = 1e-4
EXTRAS = ("source", "reduced", "assumed", "deployment", "reference",
          "memory_analysis", "notes")


def _load_reference():
    """``benchmark/references/granite_moe_hybrid.py``, as ``run.py`` loads
    it (``benchmark/`` on the path while it imports ``reference``)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_granite_moe_hybrid",
            os.path.join(BENCH, "references", "granite_moe_hybrid.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _file(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as f:
        hf = json.load(f)
    return {k: v for k, v in hf.items() if k not in EXTRAS}


def _hf(**over) -> dict:
    return dict(_file("fixtures/tiny-granite-moe-hybrid.json"), **over)


@pytest.fixture(scope="module")
def model():
    """The fixture's ten layers in float32, and the two programs jitted
    once for the module."""
    hf = _hf()
    cfg = ModelConfig.from_hf_config(hf)
    params = gh.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    kv = gh.init_kv_cache(cfg, 1 + SLOTS * M, BS, SLOTS, dtype=jnp.float32)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla",
                           table_blocks=M)
    prefill = jax.jit(gh.prefill_forward, static_argnums=(6,))
    decode = jax.jit(gh.decode_forward, static_argnums=(5,))
    return hf, cfg, params, kv, statics, prefill, decode


def _tokens(cfg, n: int, seed: int = 3) -> list:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).tolist()


def _table(slot: int) -> jnp.ndarray:
    return jnp.arange(1 + slot * M, 1 + (slot + 1) * M, dtype=jnp.int32)


def _prefill(model, kv, tokens, start=0, pad_to=32, slot=0):
    _, _, params, _, statics, prefill, _ = model
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    return prefill(params, kv, jnp.asarray(padded), _table(slot),
                   jnp.int32(start), jnp.int32(len(tokens)), statics, slot)


def _decode(model, kv, rows: dict):
    """rows: slot -> (token, position); the other slots are not live."""
    _, _, params, _, statics, _, decode = model
    tok = np.zeros((SLOTS,), np.int32)
    pos = np.zeros((SLOTS,), np.int32)
    tables = np.zeros((SLOTS, M), np.int32)
    for slot, (t, p) in rows.items():
        tok[slot], pos[slot], tables[slot] = t, p, np.asarray(_table(slot))
    return decode(params, kv, jnp.asarray(tok), jnp.asarray(pos),
                  jnp.asarray(tables), statics)


def _err_std(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / want.std())


def _slot_state(kv, slot: int) -> dict:
    return {"ssd": np.asarray(kv["ssd"][:, slot]),
            "conv": np.asarray(kv["conv"][:, slot])}


# ------------------------------------------------------------- the kernels

def _ssd_inputs(T, H, P, N, true_len=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)) - 2.0)
    if true_len is not None:
        dt = jnp.where(jnp.arange(T)[:, None] < true_len, dt, 0.0)
    A = -jnp.exp(jnp.linspace(0.0, 2.7, H))
    return (x, dt, dt * A, jax.random.normal(ks[2], (T, N)),
            jax.random.normal(ks[3], (T, N)),
            jax.random.normal(ks[4], (H, P, N)))


@pytest.mark.parametrize("T, H, P, N, true_len", [
    (300, 8, 16, 16, None),       # three chunks, the last one cut
    (256, 4, 16, 32, 70),         # a chunk that is wholly padding
    (256, 4, 64, 128, 200),       # the published head: two heads a lane group
    (64, 2, 128, 16, None),       # a head that fills the lanes
])
def test_ssd_chunk_is_the_recurrence(T, H, P, N, true_len):
    x, dt, a, b, c, s0 = _ssd_inputs(T, H, P, N, true_len)
    y0, S0 = ssd.ssd_recurrence(x, dt, a, b, c, s0)
    y1, S1 = ssd.ssd_chunk(x, dt, a, b, c, ssd.state_from_hpn(s0), true_len,
                           interpret=True)
    n = true_len or T
    assert float(jnp.abs(y0[:n] - y1[:n]).max() / jnp.abs(y0).max()) < 1e-4
    S1 = ssd.state_to_hpn(S1, H)
    assert float(jnp.abs(S0 - S1).max() / jnp.abs(S0).max()) < 1e-5


def test_ssd_chunk_takes_a_decay_that_underflows():
    """dt A = -40 a token: exp(cum_i) * exp(-cum_j) would overflow float32
    after three tokens; every exponent taken is <= 0."""
    x, dt, a, b, c, s0 = _ssd_inputs(128, 4, 16, 16)
    a = jnp.full_like(a, -40.0)
    y0, S0 = ssd.ssd_recurrence(x, dt, a, b, c, s0)
    y1, S1 = ssd.ssd_chunk(x, dt, a, b, c, ssd.state_from_hpn(s0),
                           interpret=True)
    assert bool(jnp.isfinite(y1).all())
    assert float(jnp.abs(y0 - y1).max() / jnp.abs(y0).max()) < 1e-4
    assert float(jnp.abs(S0 - ssd.state_to_hpn(S1, 4)).max()) < 1e-4


def test_ssd_step_updates_one_layer_in_place():
    """Slot 1 starts from zero (decay 0), slot 2 is not live (decay 1, dt 0)
    and keeps its state; the other layers' rows are not touched."""
    B, H, P, N, L = 4, 8, 16, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H)))
    decay = jnp.exp(dt * -jnp.exp(jnp.linspace(0.0, 2.7, H)))
    decay = decay.at[1].set(0.0).at[2].set(1.0)
    dt = dt.at[2].set(0.0)
    b, c = jax.random.normal(ks[2], (B, N)), jax.random.normal(ks[3], (B, N))
    st = jax.random.normal(ks[4], (L, B, H, P, N))
    held = ssd.state_from_hpn(st).reshape(
        (L * B,) + ssd.state_shape(H, P, N))
    y, new = ssd.ssd_step(x, dt, decay, b, c, held, jnp.int32(1),
                          interpret=True)
    new = ssd.state_to_hpn(new.reshape((L, B) + new.shape[1:]), H)
    want = (decay[:, :, None, None] * st[1]
            + (dt[:, :, None] * x)[..., None] * b[:, None, None, :])
    np.testing.assert_allclose(new[1], want, atol=1e-5)
    np.testing.assert_allclose(
        y, jnp.einsum("bhpn,bn->bhp", want, c), atol=1e-4)
    assert (new[0] == st[0]).all() and (new[2] == st[2]).all()
    assert (new[1, 2] == st[1, 2]).all()


def test_the_held_layout_round_trips():
    s = jax.random.normal(jax.random.PRNGKey(0), (3, 128, 64, 128))
    held = ssd.state_from_hpn(s)
    assert held.shape == (3, 64, 128, 128) == (3,) + ssd.state_shape(
        128, 64, 128)
    assert (ssd.state_to_hpn(held, 128) == s).all()
    # two heads' lanes side by side, the states on sublanes
    assert (held[0, 5, 7, 64:] == s[0, 11, :, 7]).all()


# ----------------------------------------------------- the parser, the plan

def test_from_hf_config_reads_the_catalog_rows_keys():
    hf = _file("configs/granite-4.0-h-small.json")
    cfg = ModelConfig.from_hf_config(hf)
    assert module_for(cfg) is gh and cfg.has_ssd and not cfg.is_sambay
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (
        10, 4096, 100352)
    assert (cfg.ssd_num_heads, cfg.ssd_head_dim, cfg.ssd_d_state,
            cfg.ssd_conv_kernel) == (128, 64, 128, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size,
            cfg.shared_expert_size, cfg.moe_norm_topk) == (
        72, 10, 768, 1536, True)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 16.0)
    # the score scale 1/128, not 128^-1/2, through the field gemma2 reads
    assert cfg.query_pre_attn_scalar ** -0.5 == 1 / 128
    assert cfg.nope_full and cfg.tie_word_embeddings
    assert gh.layer_kinds(cfg) == tuple("MMMMMAMMMM")
    shapes = gh.param_shapes(cfg)
    assert shapes["layers.ssd_in"] == (9, 4096, 8192 + 8448 + 128)
    assert shapes["layers.moe_gate"] == (10, 72, 4096, 768)
    assert "lm_head" not in shapes
    layout = gh.cache_layout(cfg, 16)
    assert (layout.paged_layers, layout.state_layers, layout.row_bytes,
            layout.state_bytes) == (1, 9, 4096, 4194304 + 50688)
    assert layout.has_state


@pytest.mark.parametrize("layers, want", [
    (10, (tuple("MMMMMA"), 1, tuple("MMMM"))),
    (20, (tuple("MMMMMAMMMM"), 2, ())),
    (40, (tuple("MMMMMAMMMM"), 4, ())),
    (26, (tuple("MMMMMAMMMM"), 2, tuple("MMMMMA"))),
])
def test_layer_plan_scans_the_published_period(layers, want):
    kinds = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    cfg = ModelConfig.from_hf_config(
        _hf(num_hidden_layers=layers, layer_types=kinds))
    assert gh.layer_plan(cfg) == want


@pytest.mark.parametrize("over, match", [
    ({"layer_types": ["mamba"] * 10}, "without a mamba layer or without"),
    ({"layer_types": ["mamba", "attention", "gated"] * 4}, "of kind gated"),
    ({"layer_types": ["mamba", "attention"]}, "names 2 layers"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_expand": 4}, "mamba_expand"),
    ({"attention_bias": True}, "attention_bias"),
    ({"residual_multiplier": None}, "needs residual_multiplier"),
])
def test_from_hf_config_refuses_what_the_block_does_not_run(over, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**over))


def test_an_unknown_family_with_mamba_layers_is_not_parsed_as_llama():
    with pytest.raises(ValueError, match="granitemoehybrid"):
        ModelConfig.from_hf_config(_hf(model_type="bamba"))


def test_seeded_ladders_give_half_lives_from_under_a_token_to_hundreds():
    cfg = ModelConfig.from_hf_config(_file("configs/granite-4.0-h-small.json"))
    key = jax.random.PRNGKey(0)
    A = np.exp(np.asarray(gh.init_one_param(
        cfg, "layers.ssd_A_log", (9, 128), key)))
    dt = np.asarray(jax.nn.softplus(gh.init_one_param(
        cfg, "layers.ssd_dt_bias", (9, 128), key)))
    half = np.log(2) / (A * dt)
    assert A.min() == pytest.approx(1.0) and A.max() == pytest.approx(16.0)
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1 + 1e-6
    assert half.min() < 1.0 and half.max() > 300.0


# ------------------------------------------- the engine against the reference

def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        ref, model):
    hf, cfg, _, kv, *_ = model
    seq = _tokens(cfg, 34)
    logits, kv = _prefill(model, kv, seq[:29], slot=1)
    want = ref.logits_for(model[2], hf, seq, 6)
    assert _err_std(logits, want[0]) < TOL_STD
    for i in range(29, 34):
        logits, kv = _decode(model, kv, {1: (seq[i], i)})
        assert _err_std(logits[1], want[i - 28]) < TOL_STD


def test_decode_from_position_zero_starts_from_the_zero_state(ref, model):
    hf, cfg, params, kv, *_ = model
    seq = _tokens(cfg, 4, seed=9)
    _, kv = _prefill(model, kv, _tokens(cfg, 30, seed=7), slot=0)
    want = ref.logits_for(params, hf, seq, 4)
    for i, t in enumerate(seq):
        logits, kv = _decode(model, kv, {0: (t, i)})
        assert _err_std(logits[0], want[i]) < TOL_STD


def test_padding_leaves_the_state_at_true_len(ref, model):
    hf, cfg, params, kv, *_ = model
    seq = _tokens(cfg, 20)
    la, kva = _prefill(model, kv, seq[:19], pad_to=32)
    lb, kvb = _prefill(model, kv, seq[:19], pad_to=160)
    assert _err_std(la, lb) < TOL_STD
    for key, a in _slot_state(kva, 0).items():
        np.testing.assert_allclose(a, _slot_state(kvb, 0)[key], atol=1e-5,
                                   err_msg=key)
    want = ref.logits_for(params, hf, seq, 1)
    logits, _ = _decode(model, kvb, {0: (seq[19], 19)})
    assert _err_std(logits[0], want[0]) < TOL_STD


@pytest.mark.parametrize("cuts", [(16, 32), (8, 16, 24, 32), (5, 21), (2, 20)])
def test_a_prompt_in_several_dispatches_equals_one(model, cuts):
    """Dispatches that end on and off block boundaries, one shorter than the
    convolution's taps: the state and the conv inputs go on to the next."""
    _, cfg, _, kv, *_ = model
    seq = _tokens(cfg, 37)
    whole, kvw = _prefill(model, kv, seq, pad_to=64, slot=2)
    lo, kvc = 0, kv
    for hi in cuts + (37,):
        logits, kvc = _prefill(model, kvc, seq[lo:hi], start=lo, slot=2)
        lo = hi
    assert _err_std(logits, whole) < TOL_STD
    for key, a in _slot_state(kvw, 2).items():
        np.testing.assert_allclose(a, _slot_state(kvc, 2)[key], atol=1e-5,
                                   err_msg=key)


def test_two_sequences_of_unequal_length_in_one_decode_batch(ref, model):
    hf, cfg, params, kv, *_ = model
    a, b = _tokens(cfg, 28, seed=5), _tokens(cfg, 10, seed=6)
    _, kv = _prefill(model, kv, a[:25], slot=0)
    _, kv = _prefill(model, kv, b[:7], slot=2)
    wa = ref.logits_for(params, hf, a, 3)
    wb = ref.logits_for(params, hf, b, 3)
    for i in range(3):
        logits, kv = _decode(model, kv, {0: (a[25 + i], 25 + i),
                                         2: (b[7 + i], 7 + i)})
        assert _err_std(logits[0], wa[i]) < TOL_STD
        assert _err_std(logits[2], wb[i]) < TOL_STD


def test_a_row_that_is_not_live_keeps_its_state(model):
    _, cfg, _, kv, *_ = model
    _, kv = _prefill(model, kv, _tokens(cfg, 20), slot=1)
    before = _slot_state(kv, 1)
    _, kv = _decode(model, kv, {0: (5, 0)})
    for key, a in before.items():
        assert (a == _slot_state(kv, 1)[key]).all(), key
    assert np.abs(_slot_state(kv, 0)["ssd"]).max() > 0


def test_a_reused_slot_sees_nothing_of_its_predecessor(model):
    _, cfg, _, kv, *_ = model
    b = _tokens(cfg, 11, seed=8)
    fresh, _ = _prefill(model, kv, b, slot=1)
    _, used = _prefill(model, kv, _tokens(cfg, 30, seed=7), slot=1)
    again, used = _prefill(model, used, b, slot=1)
    assert _err_std(again, fresh) < TOL_STD
    one, _ = _decode(model, used, {1: (3, 11)})
    _, clean = _prefill(model, kv, b, slot=1)
    two, _ = _decode(model, clean, {1: (3, 11)})
    assert _err_std(one[1], two[1]) < TOL_STD


def test_a_deeper_cut_scans_its_periods(ref):
    """Twenty layers: two whole periods under one scan, runs of five and
    four Mamba-2 layers inside it."""
    kinds = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2
    hf = _hf(num_hidden_layers=20, layer_types=kinds)
    cfg = ModelConfig.from_hf_config(hf)
    params = gh.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
    kv = gh.init_kv_cache(cfg, 1 + M, BS, 1, dtype=jnp.float32)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla",
                           table_blocks=M)
    seq = _tokens(cfg, 21)
    padded = np.zeros((32,), np.int32)
    padded[:20] = seq[:20]
    logits, kv = jax.jit(gh.prefill_forward, static_argnums=(6,))(
        params, kv, jnp.asarray(padded), _table(0), jnp.int32(0),
        jnp.int32(20), statics, 0)
    want = ref.logits_for(params, hf, seq, 2)
    assert _err_std(logits, want[0]) < TOL_STD
    logits, _ = jax.jit(gh.decode_forward, static_argnums=(5,))(
        params, kv, jnp.asarray([seq[20]]), jnp.asarray([20]),
        _table(0)[None], statics)
    assert _err_std(logits[0], want[1]) < TOL_STD


# ------------------------------------------------------------ the breakages

@pytest.mark.parametrize("broken", _load_reference().BREAKAGES)
def test_a_breakage_moves_the_reference_or_a_leaf(ref, model, broken):
    """In float32 the engine stands 1e-5 from the reference; every listed
    breakage stands a thousand times further in the logits, and the state's
    rounding to bf16, which a logit hardly sees, in the state's leaf."""
    hf, cfg, params, *_ = model
    seq = _tokens(cfg, 40)
    assert set(ref.TAPPED) <= set(ref.BREAKAGES)
    assert not set(ref.CONTROLS) & set(ref.BREAKAGES)
    assert set(ref.breakages_for(hf)) == set(ref.BREAKAGES) - {"state_bf16"}
    if broken in ref.breakages_for(hf):
        base = ref.logits_for(params, hf, seq, 4)
        off = ref.logits_for(params, hf, seq, 4, broken)
        assert _err_std(off, base) > 1000 * TOL_STD
    if broken in ref.TAPPED:
        leaf = ref.TAPPED[broken]
        base = ref.leaves_for(params, hf, seq)[leaf]
        off = ref.leaves_for(params, hf, seq, broken)[leaf]
        assert ref.leaf_error(off, base) > 10 * 1e-5


def test_the_served_leaf_is_held_to_the_reference(ref, model):
    """-k leaf: what the cache holds after prefill and decode, float32: the
    first M layer's state and the first A layer's key rows stand 1e-5 from
    the reference's, and outside that from the broken reference's."""
    hf, cfg, params, kv, *_ = model
    seq = _tokens(cfg, 30)
    _, kv = _prefill(model, kv, seq[:26], slot=1)
    for i in range(26, 30):
        _, kv = _decode(model, kv, {1: (seq[i], i)})
    mine = {"ssd": np.asarray(ssd.state_to_hpn(kv["ssd"][0, 1],
                                               cfg.ssd_num_heads)),
            "k": np.asarray(kv["k"][0, (1 + M) * BS:(1 + M) * BS + 30])}
    want = ref.leaves_for(params, hf, seq)
    assert set(mine) == set(want) == set(ref.LEAF_TOL)
    for leaf in mine:
        assert ref.leaf_error(mine[leaf], want[leaf]) < 1e-5, leaf
    for broken, leaf in ref.TAPPED.items():
        off = ref.leaves_for(params, hf, seq, broken)[leaf]
        assert ref.leaf_error(mine[leaf], off) > 1e-4, broken


# ------------------------------------------------------- through the engine

def _engine_cfg(**over) -> EngineConfig:
    base = dict(max_model_len=128, kv_block_size=BS, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32, 64])
    return EngineConfig(**dict(base, **over))


async def _serve(core, rid, prompt, n=4):
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
    return toks, lps


@pytest.mark.parametrize("over", [{}, {"prefill_chunk": 16}])
def test_the_engine_serves_it_and_reuses_a_slot(ref, model, over):
    """The launcher's engine over the module's door: a prompt whole and in
    dispatches of 16, three requests through two slots (a slot is reused
    after another request), each held to the reference; the records carry
    the family's counters and the state's bytes."""
    from dynamo_tpu.engine.core import EngineCore
    hf, cfg, params, *_ = model

    async def go():
        core = EngineCore(cfg, _engine_cfg(**over), params=dict(params),
                          attn_impl="xla", param_dtype=jnp.float32)
        assert core.is_hybrid and core.model_mod is gh
        prompts = [_tokens(cfg, n, seed=20 + n) for n in (41, 23, 37)]
        try:
            first = await asyncio.gather(
                *[_serve(core, f"r{i}", p) for i, p in enumerate(prompts[:2])])
            served = list(first) + [await _serve(core, "r2", prompts[2])]
            for prompt, (toks, lps) in zip(prompts, served):
                logits = ref.logits_for(params, hf, prompt + toks[:-1],
                                        len(toks))
                for tok, lp, row in zip(toks, lps, logits):
                    row = row.astype(np.float64)
                    lse = row.max() + np.log(np.exp(row - row.max()).sum())
                    assert abs(row[tok] - lse - lp) < 10 * TOL_STD * row.std()
            records = core.flight.dump()
            pre = [r for r in records if r["kind"] == "prefill"]
            assert [r["scan_tokens"] for r in pre] == [41, 23, 37]
            assert all(r["ssd_chunks"] >= 1 and r["key_tokens"] ==
                       r["prompt"] * (r["prompt"] + 1) // 2 for r in pre)
            layout = gh.cache_layout(cfg, BS, 4)
            dec = [r for r in records if r["kind"] == "decode"]
            assert dec and all(
                r["state_bytes"] == r["batch_fill"] * 2 * 9
                * layout.state_bytes for r in dec if r["batch_fill"])
        finally:
            await core.stop()
    asyncio.run(go())


def test_prefill_counters_count_live_chunks_only():
    cfg = ModelConfig.from_hf_config(_file("configs/granite-4.0-h-small.json"))
    # 2,500 rows in dispatches of 1,024: 8 + 8 + ceil(452 / 128) chunks
    got = gh.prefill_counters(cfg, 1024, 2500, 2500)
    assert got == {"scan_tokens": 2500, "ssd_chunks": 20,
                   "key_tokens": 2500 * 2501 // 2}
    assert gh.prefill_counters(cfg, 1024, 1024, 1024)["ssd_chunks"] == 8


@pytest.mark.parametrize("over, match", [
    ({"ragged_dispatch": True}, "--ragged"),
    ({"spec_k": 2}, "--spec-k"),
    ({"kv_quantization": "int8"}, "--kv-quantization"),
    ({"host_kv_blocks": 8}, "--host-kv-blocks"),
    ({"tp": 2}, "meshes"),
    ({"quantization": "int4"}, "int4"),
])
def test_refusals_name_each_refused_flag_once(over, match):
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import sambay
    cfg = ModelConfig.from_hf_config(_hf())
    e = _engine_cfg(**over)
    named = gh.refusals(cfg, e, None)
    assert len(named) == 1 and match in named[0]
    if "int4" not in match:
        assert sambay.state_refusals(e, None) == named
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(cfg, e, attn_impl="xla", param_dtype=jnp.float32)


def test_a_checkpoint_round_trips_through_the_loader(tmp_path, model):
    """engine/weights.py: the checkpoint names of both layer kinds map onto
    the stacks and back, the experts' fused gate|up split; a deeper layer's
    tensor is passed over, an unknown tensor and a missing one fail; the
    decay's leaves and the taps stay float32."""
    from safetensors.numpy import load_file, save_file
    from dynamo_tpu.engine import weights
    _, cfg, params, *_ = model
    weights.save_granite_hybrid_hf_style(params, cfg, str(tmp_path))
    path = os.path.join(str(tmp_path), "model.safetensors")
    tensors = load_file(path)
    assert tensors["model.layers.0.mamba.in_proj.weight"].shape == (
        128 + 160 + 8, 64)
    assert tensors["model.layers.0.mamba.conv1d.weight"].shape == (160, 1, 4)
    assert tensors["model.layers.5.self_attn.k_proj.weight"].shape == (32, 64)
    assert tensors[
        "model.layers.3.block_sparse_moe.input_linear.weight"].shape == (
        12, 64, 64)
    assert tensors["model.layers.3.shared_mlp.input_linear.weight"].shape == (
        128, 64)
    assert "model.layers.5.mamba.A_log" not in tensors
    back = weights.load_params_auto(str(tmp_path), cfg, dtype=jnp.float32)
    assert set(back) == set(params)
    for name, w in params.items():
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(w),
                                      err_msg=name)
    bf16 = weights.load_params_auto(str(tmp_path), cfg)
    assert {n for n, w in bf16.items() if w.dtype == jnp.float32} == {
        "layers.ssd_A_log", "layers.ssd_dt_bias", "layers.ssd_D",
        "layers.ssd_conv"}
    save_file(dict(tensors, **{"model.layers.12.mamba.D":
                               np.zeros(8, np.float32)}), path)
    weights.load_granite_hybrid_params(str(tmp_path), cfg)
    save_file(dict(tensors, **{"model.layers.0.mamba.rotary.inv_freq":
                               np.zeros(4, np.float32)}), path)
    with pytest.raises(ValueError, match="no place"):
        weights.load_granite_hybrid_params(str(tmp_path), cfg)
    tensors.pop("model.layers.6.mamba.dt_bias")
    save_file(tensors, path)
    with pytest.raises(ValueError, match="lacks 1"):
        weights.load_granite_hybrid_params(str(tmp_path), cfg)
