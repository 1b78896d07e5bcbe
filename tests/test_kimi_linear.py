"""kimi_linear (Kimi-Delta-Attention layers beside NoPE latent-attention
layers) on ``engine/models/kimi_linear.py``, held to
``benchmark/references/kimi_linear.py`` in float32 on the tiny fixture: the
layer plan, the parser, the latent + state cache and who may touch it, the
slot's lifecycle through the engine, the expert share, the refusals. The
reference has no state to forget; the faults of the engine's bookkeeping (a
state that is not reset, a step applied to a slot that is not live, padding
that leaks into the state) are held here.
"""

import asyncio
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import kimi_linear, mla, module_for
from dynamo_tpu.engine.models.llama import ModelStatics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 8
M = 16                     # blocks a table holds: 128 positions
SLOTS = 3
LAYERS = 10                # K | K K F K | K K F K | K: two periods, one left
TOL_STD = 1e-4
EXTRAS = ("source", "reduced", "assumed", "deployment", "reference",
          "memory_analysis", "notes")


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_kimi_linear",
            os.path.join(BENCH, "references", "kimi_linear.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


def _file(path: str) -> dict:
    with open(os.path.join(BENCH, path)) as f:
        hf = json.load(f)
    return {k: v for k, v in hf.items() if k not in EXTRAS}


def _hf(**over) -> dict:
    """The tiny fixture's widths under the PUBLISHED 27-layer lists (the
    fixture's own stop at 15), cut to ``num_hidden_layers``."""
    hf = _file("fixtures/tiny-kimi-linear.json")
    lists = _file("configs/kimi-linear-48b.json")["linear_attn_config"]
    hf["linear_attn_config"] = dict(
        hf["linear_attn_config"], kda_layers=lists["kda_layers"],
        full_attn_layers=lists["full_attn_layers"])
    return dict(hf, **{"num_hidden_layers": LAYERS, **over})


def _setup(hf=None, seed: int = 1, num_blocks: int = 1 + SLOTS * M):
    cfg = ModelConfig.from_hf_config(hf or _hf())
    params = kimi_linear.init_params(cfg, jax.random.PRNGKey(seed),
                                     dtype=jnp.float32)
    kv = kimi_linear.init_kv_cache(cfg, num_blocks, BS, SLOTS,
                                   dtype=jnp.float32)
    return cfg, params, kv, ModelStatics(cfg=cfg, block_size=BS,
                                         attn_impl="xla", table_blocks=M)


def _tokens(cfg, n: int, seed: int = 3) -> list:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).tolist()


def _table(slot: int) -> jnp.ndarray:
    return jnp.arange(1 + slot * M, 1 + (slot + 1) * M, dtype=jnp.int32)


_prefill_jit = jax.jit(kimi_linear.prefill_forward, static_argnums=(6,))
_decode_jit = jax.jit(kimi_linear.decode_forward, static_argnums=(5,))


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
    return {"kda": np.asarray(kv["kda"][:, slot]),
            "conv": np.asarray(kv["conv"][:, slot])}


# ------------------------------------------------------- plan and parser

PUBLISHED_KINDS = tuple("F" if i in (4, 8, 12, 16, 20, 24, 27) else "K"
                        for i in range(1, 28))


def test_layer_plan_of_the_published_list_ends_short_of_a_period():
    """27 layers, K | K K F K x 6 | K F: the last layer is F where the
    period says K. A period of 4, six whole periods, two layers left: not a
    "period" of 23."""
    cfg = ModelConfig.from_hf_config(
        _file("configs/kimi-linear-48b.json"))
    assert mla.layer_kinds(cfg) == PUBLISHED_KINDS
    assert mla.layer_plan(cfg) == (1, ("K", "K", "F", "K"), 6, ("K", "F"))
    assert mla.layer_plan(ModelConfig.from_hf_config(
        _hf(num_hidden_layers=27))) == mla.layer_plan(cfg)
    # the fixture's 15 layers end short too: K | K K F K x 3 | K F
    tiny = ModelConfig.from_hf_config(_file("fixtures/tiny-kimi-linear.json"))
    assert mla.layer_plan(tiny) == (1, ("K", "K", "F", "K"), 3, ("K", "F"))
    assert module_for(cfg) is kimi_linear
    assert mla.layer_plan(ModelConfig.from_hf_config(_hf())) == (
        1, ("K", "K", "F", "K"), 2, ("K",))


@pytest.mark.parametrize("path, want", [
    ("configs/dots3-note-prev.json", None),
    ("configs/mimo-v2.5.json", None),
    ("configs/k-exaone-236b.json", (1, tuple("SSFS"), 1, tuple("SSF"))),
    ("fixtures/tiny-dots3-note.json", None),
    ("configs/kimi-k2.7-code.json", (1, ("F",), 7, ())),
    # six layers or fewer after the prefix, ending short of their period:
    # the run of S is what repeats furthest, and the rest is unrolled
    ("fixtures/tiny-mimo-v2.json", (1, ("S",), 4, ("F", "S"))),
    ("fixtures/tiny-exaone-moe.json", (1, ("S",), 2, ("F", "S"))),
])
def test_layer_plan_of_the_other_families(path, want):
    """Every configuration the benchmark serves keeps the plan it had under
    the rule before this family came (the smallest period that the whole
    list after the dense prefix repeats, whole periods, the rest): ``want``
    None. Two rehearsal fixtures whose short lists end off their period
    scan their run of window layers instead."""
    cfg = ModelConfig.from_hf_config(_file(path))
    kinds = mla.layer_kinds(cfg)
    k = cfg.first_k_dense if cfg.num_experts > 0 else 0
    rest = kinds[k:]
    p = next(p for p in range(1, len(rest) + 1)
             if all(rest[i] == rest[i % p] for i in range(len(rest))))
    old = (k, rest[:p], len(rest) // p, rest[len(rest) // p * p:])
    assert mla.layer_plan(cfg) == (want or old)
    if path.startswith("configs/"):
        assert mla.layer_plan(cfg) == old


def test_from_hf_config_reads_the_published_keys():
    cfg = ModelConfig.from_hf_config(_file("configs/kimi-linear-48b.json"))
    assert (cfg.num_layers, cfg.hidden_size, cfg.kda_num_heads,
            cfg.kda_head_dim, cfg.kda_conv_kernel) == (27, 2304, 32, 128, 4)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.mla_nope) == (
        0, 512, 128, 64, 128, True)
    assert (cfg.num_experts, cfg.num_experts_total, cfg.num_experts_per_tok,
            cfg.moe_routing, cfg.moe_norm_topk, cfg.routed_scaling,
            cfg.shared_expert_size, cfg.first_k_dense, cfg.n_group) == (
        32, 256, 8, "sigmoid_noaux", True, 2.446, 1024, 1, 1)
    assert (cfg.intermediate_size, cfg.dense_intermediate_size,
            cfg.vocab_size) == (1024, 9216, 20480)
    layout = kimi_linear.cache_layout(cfg, 16)
    assert (layout.paged_layers, layout.state_layers, layout.window_layers,
            layout.has_state, layout.window_pool) == (7, 20, 0, True, False)
    # a slot and layer: 2,097,152 B of state and 3 x 4,096 x 3 conv inputs
    assert layout.state_bytes == 32 * 128 * 128 * 4 + 2 * 3 * 3 * 4096
    assert layout.row_bytes == 640 * 2
    by_kind = layout.bytes_by_kind(16384, 64)
    assert by_kind == {"paged": 7 * 16384 * 16 * 1280, "window": 0,
                       "state": 20 * 64 * layout.state_bytes}
    shapes = kimi_linear.param_shapes(cfg)
    assert shapes["layers.wq"] == (7, 2304, 32 * 192)
    assert shapes["layers.kda_in"] == (20, 2304, 3 * 4096)
    assert shapes["layers.moe_gate"] == (26, 32, 2304, 1024)
    assert shapes["layers.router"] == (26, 2304, 256)
    assert list(shapes)[-1] == "lm_head"


@pytest.mark.parametrize("change, match", [
    ({"kda_layers": [1, 2, 3, 4, 5, 6, 7, 9, 10]}, "AND in full_attn"),
    ({"kda_layers": [1, 2, 3, 5, 6, 7, 9]}, r"do not cover.*missing: 10"),
    ({"full_attn_layers": []}, "do not cover"),
    ({"num_heads": 0}, "num_heads is missing"),
])
def test_from_hf_config_refuses_lists_it_cannot_walk(change, match):
    hf = _hf()
    hf["linear_attn_config"] = dict(hf["linear_attn_config"], **change)
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(hf)


@pytest.mark.parametrize("over, match", [
    ({"q_lora_rank": 8}, "q_lora_rank"),
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"moe_router_activation_func": "softmax"}, "sigmoid"),
    ({"num_expert_group": 4}, "num_expert_group"),
    ({"num_experts_published": 30}, "not a share"),
    ({"kv_lora_rank": None}, "needs kv_lora_rank"),
])
def test_from_hf_config_refuses_what_the_block_does_not_run(over, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**over))


def test_an_unknown_family_with_the_lists_is_not_parsed_as_llama():
    with pytest.raises(ValueError, match="linear_attn_config"):
        ModelConfig.from_hf_config(_hf(model_type="other_linear"))


def test_seeded_decay_gives_half_lives_from_tens_to_thousands():
    cfg = ModelConfig.from_hf_config(_file("configs/kimi-linear-48b.json"))
    key = jax.random.PRNGKey(0)
    a_log = kimi_linear.init_one_param(cfg, "layers.kda_A_log", (20, 32), key)
    dt_b = kimi_linear.init_one_param(cfg, "layers.kda_dt_bias", (20, 4096),
                                      key)
    assert a_log.dtype == dt_b.dtype == jnp.float32
    rate = (np.exp(np.asarray(a_log))[0][:, None]
            * np.log1p(np.exp(np.asarray(dt_b)[0].reshape(32, 128))))
    half = np.log(2) / rate
    assert 8 < half.min() < 30 and 1500 < half.max() < 3500
    conv = kimi_linear.init_one_param(cfg, "layers.kda_conv",
                                      (20, 4, 12288), key)
    assert conv.dtype == jnp.float32


# ------------------------------------------------- against the reference

def test_prefill_then_decode_through_the_cache_equals_the_full_forward(ref):
    """21 prompt tokens, then 14 decoded one by one: every step's logits
    are the reference's full forward (the token recurrence) so far."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 35)
    want = ref.logits_for(params, hf, seq, 15)
    logits, kv = _prefill(params, kv, statics, seq[:21], slot=1)
    assert _err_std(logits, want[0]) < TOL_STD
    for i, pos in enumerate(range(21, 35)):
        logits, kv = _decode(params, kv, statics, {1: (seq[pos], pos)})
        assert _err_std(logits[1], want[i + 1]) < TOL_STD, pos


def test_the_published_depth_walks_its_short_last_period(ref):
    """All 27 tiny layers: six scanned periods and the two layers left."""
    hf = _hf(num_hidden_layers=27)
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 90)
    want = ref.logits_for(params, hf, seq, 3)
    logits, kv = _prefill(params, kv, statics, seq[:88], pad_to=128)
    assert _err_std(logits, want[0]) < TOL_STD
    for i, pos in enumerate((88, 89)):
        logits, kv = _decode(params, kv, statics, {0: (seq[pos], pos)})
        assert _err_std(logits[0], want[i + 1]) < TOL_STD, pos


def test_decode_from_position_zero_starts_from_the_zero_state(ref):
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 30, seed=9), slot=0)
    seq = _tokens(cfg, 12, seed=4)
    want = ref.logits_for(params, hf, seq, 12)
    for pos, tok in enumerate(seq):
        logits, kv = _decode(params, kv, statics, {0: (tok, pos)})
        assert _err_std(logits[0], want[pos]) < TOL_STD, pos


def test_padding_leaves_the_state_at_true_len(ref):
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 20)
    la, kva = _prefill(params, kv, statics, seq[:19], pad_to=32)
    lb, kvb = _prefill(params, kv, statics, seq[:19], pad_to=128)
    assert _err_std(la, lb) < TOL_STD
    for key, a in _slot_state(kva, 0).items():
        np.testing.assert_allclose(a, _slot_state(kvb, 0)[key], atol=1e-5,
                                   err_msg=key)
    want = ref.logits_for(params, hf, seq, 1)
    logits, _ = _decode(params, kvb, statics, {0: (seq[19], 19)})
    assert _err_std(logits[0], want[0]) < TOL_STD


@pytest.mark.parametrize("cuts", [(16, 32), (8, 16, 24, 32), (5, 21), (2, 20)])
def test_chunked_prefill_equals_whole_prefill(cuts):
    """Chunks that end on and off block boundaries, one shorter than the
    convolution's taps: the state and the conv inputs go on to the next."""
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


def test_a_row_that_is_not_live_keeps_its_state():
    cfg, params, kv, statics = _setup()
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 20), slot=1)
    before = _slot_state(kv, 1)
    _, kv = _decode(params, kv, statics, {0: (5, 0)})
    for key, a in before.items():
        assert (a == _slot_state(kv, 1)[key]).all(), key
    assert np.abs(_slot_state(kv, 0)["kda"]).max() > 0


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
    """In float32 the engine stands 1e-5 from the reference; every listed
    breakage, the state's rounding to bf16 too, stands 200 times further.
    Served logits have to show all of them but that rounding, which the
    state's leaf shows (``TAPPED``)."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    seq = _tokens(cfg, 64, seed=5)
    want = ref.logits_for(params, hf, seq, 8)
    assert set(ref.breakages_for(hf)) == set(ref.BREAKAGES) - {
        "state_bf16"} == set(ref.breakages_for(
            _file("configs/kimi-linear-48b.json")))
    assert set(ref.TAPPED) <= set(ref.BREAKAGES)
    assert not set(ref.CONTROLS) & set(ref.BREAKAGES)
    for broken in ref.BREAKAGES:
        got = ref.logits_for(params, hf, seq, 8, broken=broken)
        assert _err_std(got, want) > 200 * TOL_STD, broken


def test_the_served_cache_is_held_to_the_reference_leaf_by_leaf(ref):
    """The engine as it is served (bf16 activations, int8 weights), 640
    tokens through ``kda_chunk`` and 8 steps through ``kda_step``: slot 0's
    float32 state in the first K layer and the pe lanes of its rows in the
    first F layer's pool stand inside ``LEAF_TOL`` of the reference's; a
    state rounded to bf16 every token and rotated pe lanes stand outside
    it; the reference with int4 weights (``CONTROLS``) is outside the
    logits' tolerance."""
    import reference
    import selftest
    from dynamo_tpu.engine.core import EngineCore
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_check",
        os.path.join(BENCH, "references", "kimi_linear_check.py"))
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    hf = dict(_file("fixtures/tiny-kimi-linear.json"),
              num_hidden_layers=4)                      # K K K F
    cfg = ModelConfig.from_hf_config(hf)
    core = EngineCore(cfg, EngineConfig(
        max_model_len=1024, num_kv_blocks=72, max_num_seqs=2,
        quantization="int8", seed=11, prefill_buckets=[1024]))
    assert core.kv["kda"].dtype == jnp.float32
    prompt = _tokens(cfg, 640)
    ids, lps = selftest.greedy(core, prompt, 8)
    seq = prompt + ids[:-1]
    mine = check.engine_leaves(core, hf, len(seq))
    want = ref.leaves_for(core.params, hf, seq)
    assert set(mine) == set(want) == set(ref.LEAF_TOL)
    for leaf, tol in ref.LEAF_TOL.items():
        assert ref.leaf_error(mine[leaf], want[leaf]) < tol, leaf
    for broken, leaf in ref.TAPPED.items():
        off = ref.leaves_for(core.params, hf, seq, broken)[leaf]
        assert ref.leaf_error(mine[leaf], off) > ref.LEAF_TOL[leaf], broken
    sound = reference.compare(core.params, hf, prompt, ids, lps,
                              forward=ref.logits_for)
    assert sound["ok"], sound
    for control in ref.CONTROLS:
        rep = reference.compare(core.params, hf, prompt, ids, lps,
                                broken=control, forward=ref.logits_for)
        assert not rep["ok"], (control, rep)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole(ref):
    """One expert layer's MLP on the same input: what each share's held
    experts add (the engine's ``mla._moe_mlp``, router 24 wide, 3 experts
    held of 24), the shared expert counted once, sums to the uncut
    reference's layer with all 24 experts held."""
    shares, held = 8, 3
    hf = _hf(num_experts=24, num_experts_published=24)
    cfg, params, _, _ = _setup(hf)
    fam = ref.family(hf)
    x = jax.random.normal(jax.random.PRNGKey(4), (13, cfg.hidden_size))
    lw = ref._layer_weights(params, 2, fam)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_mlp(fam)(x, lw)
        lp = {n: params[f"layers.{n}"][1] for n in (
            "router", "router_bias", "moe_gate", "moe_up", "moe_down",
            "sh_gate", "sh_up", "sh_down")}
        total = jnp.zeros_like(whole)
        for i in range(shares):
            part_cfg = dataclasses.replace(
                cfg, num_experts=held, num_experts_total=24,
                expert_share_index=i)
            cut = dict(lp, **{n: lp[n][i * held:(i + 1) * held]
                              for n in ("moe_gate", "moe_up", "moe_down")})
            no_shared = dataclasses.replace(part_cfg, shared_expert_size=0)
            routed = mla._moe_mlp(x, cut, no_shared, sharded=False)
            total = total + routed
        # the shared expert, whole on every chip: counted once
        shared = mla._moe_mlp(x, cut, part_cfg, sharded=False) - routed
    assert _err_std(total + shared, whole) < TOL_STD
    assert float(jnp.abs(shared).max()) > 0


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
                                       "prefill_buckets": [16, 64]}],
                         ids=["whole", "chunked"])
async def test_engine_serves_what_the_reference_computes(ref, over):
    """EngineCore end to end, the loop's own programs and bookkeeping: the
    slot rides behind an MLA model's prefill table (``is_hybrid`` from the
    layout), the served tokens and logprobs are the reference's, the same
    prompt again takes no prefix hit, the flight records carry the
    counters, the pool is whole again at the end, neither disagg plane and
    no fabric is accepted."""
    from dynamo_tpu.engine.core import EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompt = _tokens(cfg, 29, seed=12)
    core = _engine(params, cfg, **over)
    assert core.is_hybrid and core.is_mla and not core.has_window_pool
    assert not core.kv_manager.enable_reuse
    free = core.kv_manager.pool.free_blocks
    try:
        with jax.default_matmul_precision("highest"):
            toks, lps, req = await _serve(core, "a", prompt, n=10)
            again, lps2, req2 = await _serve(core, "b", prompt, n=10)
        _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        assert again == toks
        np.testing.assert_allclose(lps2, lps, atol=1e-4)
        assert req.prefix_hit_tokens == 0 and req2.prefix_hit_tokens == 0
        records = core.flight.dump()
        prefill = [r for r in records if r["kind"] == "prefill"]
        assert all(r["hit_device"] == 0 for r in prefill)
        # an admission's record: its rows, and the chunks of 64 rows that
        # its dispatches walked (two buckets of 16, or one of 32)
        assert prefill[-1]["scan_tokens"] == 29
        assert prefill[-1]["kda_chunks"] == (2 if over else 1)
        assert prefill[-1]["key_tokens"] > 0
        decode = [r for r in records
                  if r["kind"] == "decode" and r["batch_fill"]]
        layout = core.kv_manager.layout
        for r in decode:
            assert r["ctx_tokens"] > 0
            assert r["state_bytes"] == (2 * r["emitted"] * layout.state_bytes
                                        * layout.state_layers)
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
    """Two slots, three requests: the queued one takes the short one's slot
    while the long one keeps a step in flight (the chained step). Each
    stream is the reference's: no state crossed from a slot's predecessor
    and no step ran twice on a state."""
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
        decode = [r for r in core.flight.dump() if r["kind"] == "decode"]
        assert sum(r["chained"] for r in decode) > 0
    finally:
        await core.stop()


async def test_a_preempted_sequence_recomputes_its_state(ref):
    """A pool too small for both sequences: one is preempted, its slot's
    state is dropped with it, and the recompute re-derives it from the
    grown prompt. Both streams stay the reference's."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompts = [_tokens(cfg, 30, seed=21), _tokens(cfg, 30, seed=22)]
    core = _engine(params, cfg, num_kv_blocks=14,
                   prefill_buckets=[32, 64, 128])
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


@pytest.mark.parametrize("family", ["kimi_linear", "phi4flash"])
@pytest.mark.parametrize("over, match", [
    ({"ragged_dispatch": True}, "--ragged"),
    ({"spec_k": 2}, "--spec-k"),
    ({"kv_quantization": "int8"}, "--kv-quantization"),
    ({"host_kv_blocks": 8}, "--host-kv-blocks"),
    ({"tp": 2}, "meshes"),
    ({"quantization": "int4"}, "int4"),
])
def test_the_stateful_families_refuse_by_one_table(family, over, match):
    """What cannot carry a slot's state is refused by name at engine build,
    for both stateful families from ONE table (``sambay.state_refusals``),
    each option named once."""
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.models import sambay
    hf = (_hf() if family == "kimi_linear"
          else _file("fixtures/tiny-phi4flash.json"))
    cfg = ModelConfig.from_hf_config(hf)
    e = _engine_cfg(**over)
    named = module_for(cfg).refusals(cfg, e, None)
    assert len({n.split(" ", 1)[0] for n in named}) == len(named)
    if "int4" not in match:
        assert sambay.state_refusals(e, None) == [
            n for n in named if "int4" not in n]
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(cfg, e, attn_impl="xla", param_dtype=jnp.float32)


def test_a_checkpoint_round_trips_through_the_loader(tmp_path):
    """engine/weights.py: the checkpoint names of both layer kinds map onto
    the fused stacks and back; another share's expert is passed over, an
    unknown tensor and a missing one fail; the decay's leaves and the taps
    stay float32."""
    from safetensors.numpy import load_file, save_file
    from dynamo_tpu.engine import weights
    cfg, params, _, _ = _setup()
    weights.save_kimi_linear_hf_style(params, cfg, str(tmp_path))
    path = os.path.join(str(tmp_path), "model.safetensors")
    tensors = load_file(path)
    assert tensors["model.layers.0.self_attn.q_conv1d.weight"].shape == (
        64, 1, 4)
    assert tensors["model.layers.0.self_attn.f_a_proj.weight"].shape == (
        16, 64)
    assert tensors["model.layers.3.self_attn.q_proj.weight"].shape == (
        4 * 24, 64)
    assert tensors["model.layers.0.mlp.gate_proj.weight"].shape == (128, 64)
    assert "model.layers.1.block_sparse_moe.experts.11.w1.weight" in tensors
    back = weights.load_params_auto(str(tmp_path), cfg, dtype=jnp.float32)
    assert set(back) == set(params)
    for name, w in params.items():
        np.testing.assert_array_equal(np.asarray(back[name]), np.asarray(w),
                                      err_msg=name)
    bf16 = weights.load_params_auto(str(tmp_path), cfg)
    assert {n for n, w in bf16.items() if w.dtype == jnp.float32} == {
        "layers.kda_A_log", "layers.kda_dt_bias", "layers.kda_conv"}
    elsewhere = "model.layers.1.block_sparse_moe.experts.17.w1.weight"
    save_file(dict(tensors, **{elsewhere: np.zeros((32, 64), np.float32)}),
              path)
    weights.load_kimi_linear_params(str(tmp_path), cfg)
    save_file(dict(tensors, **{"model.layers.0.self_attn.rotary.inv_freq":
                               np.zeros(4, np.float32)}), path)
    with pytest.raises(ValueError, match="no place"):
        weights.load_kimi_linear_params(str(tmp_path), cfg)
    tensors.pop("model.layers.5.self_attn.dt_bias")
    save_file(tensors, path)
    with pytest.raises(ValueError, match="lacks 1"):
        weights.load_kimi_linear_params(str(tmp_path), cfg)
