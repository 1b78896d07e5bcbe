"""dots3_note (dots3-note-prev) on models/mla.py: two latent-attention
geometries in one model, the window layers' rows as blocks of a second pool,
and a prefix hit over both kinds of row, held to the benchmark's plain
reference (benchmark/references/dots3_note.py) at tiny widths on the CPU with
seeded random weights (docs/hybrid_cache.md).

Engine and reference both compute in float32 here (float32 parameters and
pools, ``jax.default_matmul_precision("highest")``): what separates them is
the order of float32 sums, a few 1e-6 of the logits' standard deviation.
``TOL_STD`` = 1e-4 fails anything else; the reference's breakages stand at
0.09 to 2.5.
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

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import mla
from dynamo_tpu.engine.models.llama import ModelStatics, seeded_std
from dynamo_tpu.llm.kv.blocks import TokenBlockSequence
from dynamo_tpu.llm.kv.pool import KvBlockManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 16
NUM_BLOCKS = 16
TOL_STD = 1e-4
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def ref():
    """benchmark/references/dots3_note.py (it imports the benchmark's
    ``reference`` module by its bare name)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_dots3_note",
            os.path.join(BENCH, "references", "dots3_note.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


def _hf(**over) -> dict:
    """The fixture: 9 layers F | F S S S | F S S S, window 21 (two blocks
    of 16 and a ring of three), index_topk 16."""
    with open(os.path.join(BENCH, "fixtures", "tiny-dots3-note.json")) as f:
        hf = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "reference"):
        hf.pop(key)
    return dict(hf, **over)


def _setup(hf: dict, seed: int = 1):
    cfg = ModelConfig.from_hf_config(hf)
    params = mla.init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=jnp.float32)
    # a router bias that matters (it is zero at initialisation)
    params["layers.router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["layers.router_bias"].shape)
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")
    return cfg, params, kv, statics


_PREFILL = jax.jit(mla.prefill_forward, static_argnums=(6,))
_DECODE = jax.jit(mla.decode_forward, static_argnums=(5,))


def _tokens(cfg, n: int, seed: int = 3) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=n)


def _prefill(params, kv, statics, tokens, start=0, pad_to=64):
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return _PREFILL(
            params, kv, jnp.asarray(padded), TABLE, jnp.asarray(start),
            jnp.asarray(len(tokens)), statics)


def _decode(params, kv, statics, token, pos):
    with jax.default_matmul_precision("highest"):
        logits, kv = _DECODE(
            params, kv, jnp.asarray([token, 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32),
            jnp.zeros((2, 8), jnp.int32).at[0].set(TABLE), statics)
    return logits[0], kv


def _err_std(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / want.std())


# ------------------------------------------------------------- the config

def _catalog_row() -> dict:
    with open(CATALOG) as f:
        return next(row["config"] for row in map(json.loads, f)
                    if row["name"] == "dots3-note-prev")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_from_hf_config_parses_the_catalog_row_whole():
    cfg = ModelConfig.from_hf_config(_catalog_row())
    assert (cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank,
            cfg.qk_nope_head_dim, cfg.rope_theta) == (128, 512, 1024, 128, 8e7)
    s = cfg.swa_geometry()
    assert (s.num_heads, s.kv_lora_rank, s.q_lora_rank, s.qk_nope_head_dim,
            s.qk_rope_head_dim, s.v_head_dim, s.rope_theta, s.index_topk) == (
                64, 1024, 1024, 192, 64, 128, 5e4, 0)
    assert cfg.swa_window == 513 and cfg.attention_gate
    assert cfg.mla_lora_rescale and cfg.index_topk == 2048
    assert (cfg.n_group, cfg.topk_group, cfg.num_nextn_predict_layers,
            cfg.first_k_dense, cfg.num_experts) == (1, 1, 0, 1, 256)
    kinds = mla.layer_kinds(cfg)
    assert (kinds.count("F"), kinds.count("S")) == (13, 33)
    # F | (F S S S) x 11 | F
    assert mla.layer_plan(cfg) == (1, ("F", "S", "S", "S"), 11, ("F",))
    # a latent row of each geometry, padded to whole lanes
    assert mla.latent_row_lanes(cfg) == 640
    assert mla.latent_row_lanes(s) == 1152


@pytest.mark.parametrize("over, match", [
    ({"layer_types": ["full_attention"] * 4}, "layer_types names 4 layers"),
    ({"layer_types": ["sliding_attention"] + ["full_attention"] * 8},
     "must be full_attention"),
    ({"layer_types": ["full_attention", "chunked_attention"]
      + ["sliding_attention"] * 7}, "chunked_attention"),
    ({"layer_types": ["full_attention"] * 9}, "no sliding_attention"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"swa_kv_lora_rank": None}, "swa_kv_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
])
def test_from_hf_config_refuses_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**over))


def test_a_cut_depth_keeps_the_leading_layer_types():
    cfg = ModelConfig.from_hf_config(_hf(num_hidden_layers=6))
    assert mla.layer_kinds(cfg) == ("F", "F", "S", "S", "S", "F")
    assert mla.layer_plan(cfg) == (1, ("F", "S", "S", "S"), 1, ("F",))


def test_two_stacks_and_two_pools():
    cfg, params, kv, _ = _setup(_hf())
    shapes = mla.param_shapes(cfg)
    assert shapes["layers.wkv_b"] == (3, 32, 4 * 32)
    assert shapes["layers.swa_wkv_b"] == (6, 48, 2 * 40)
    assert shapes["layers.wg"] == (3, 64, 4)
    assert shapes["layers.swa_wg"] == (6, 64, 2)
    assert shapes["layers.idx_wk"] == (3, 64, 16)
    assert "layers.swa_idx_wk" not in shapes
    assert shapes["layers.ln1"] == (9, 64)
    assert {k: v.shape for k, v in kv.items()} == {
        "kv": (3, NUM_BLOCKS * BS, 128), "idx": (3, NUM_BLOCKS * BS, 16),
        "win": (6, NUM_BLOCKS * BS, 128)}
    layout = mla.cache_layout(cfg, BS, 4)
    assert layout.window_pool and not layout.has_state
    assert (layout.ring_blocks, layout.window_reach_blocks) == (3, 2)
    assert (layout.paged_layers, layout.window_layers, layout.window,
            layout.row_bytes) == (3, 6, 21, (128 + 16) * 4)
    with open(os.path.join(BENCH, "fixtures", "tiny-deepseek-v2.json")) as f:
        uniform = ModelConfig.from_hf_config(json.load(f))
    assert mla.cache_layout(uniform, BS) is None


def test_seeded_weights_follow_the_mixed_rule():
    """llama.MIXED_SEEDED: the indexer's rule, the full layers' wo at half of
    it, the window layers' as it is; q_a_norm carries the inverse of its
    rescale, kv_norm stays 1; deepseek-v3.2 keeps SPARSE_SEEDED."""
    cfg = ModelConfig.from_hf_config(_hf())
    assert seeded_std(cfg, "embed", 64) == 1.0
    assert seeded_std(cfg, "layers.wo", 32) == 0.25 * 32 ** -0.5
    assert seeded_std(cfg, "layers.swa_wo", 32) == 0.5 * 32 ** -0.5
    assert seeded_std(cfg, "layers.moe_down", 32) == 0.1 * 32 ** -0.5
    assert seeded_std(cfg, "layers.wg", 64) == 64 ** -0.5
    assert seeded_std(cfg, "layers.swa_wg", 64) == 64 ** -0.5
    params = mla.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert abs(float(params["layers.swa_q_a_norm"].min())
               - (16 / 64) ** 0.5) < 1e-6
    assert abs(float(params["layers.q_a_norm"].max())
               - (24 / 64) ** 0.5) < 1e-6
    assert float(params["layers.kv_norm"].min()) == 1.0
    assert float(params["layers.swa_kv_norm"].max()) == 1.0
    assert float(params["layers.ln1"].min()) == 1.0
    assert abs(float(params["layers.swa_wg"].std()) - 0.125) < 0.02
    with open(os.path.join(BENCH, "fixtures", "tiny-deepseek-v32.json")) as f:
        v32 = ModelConfig.from_hf_config(
            {k: v for k, v in json.load(f).items()
             if k not in ("source", "reduced", "assumed", "deployment",
                          "reference")})
    assert seeded_std(v32, "layers.wo", 32) == 0.5 * 32 ** -0.5


# ------------------------------------------------- against the reference

@pytest.mark.parametrize("n", [10, 21, 22, 40, 60])
def test_prefill_and_decode_match_the_reference(ref, n):
    """Contexts below, at and past the window (21) and past index_topk
    (16): the prefill's last logits, then four decode steps."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, n + 4)
    logits, kv = _prefill(params, kv, statics, seq[:n])
    want = ref.logits_for(params, hf, seq, 5)
    assert _err_std(logits, want[0]) < TOL_STD
    for i in range(4):
        logits, kv = _decode(params, kv, statics, int(seq[n + i]), n + i)
        assert _err_std(logits, want[i + 1]) < TOL_STD


def test_the_ring_wraps_in_decode(ref):
    """44 decode steps from a context of 20: the ring of three blocks is
    gone round, the window and index_topk are crossed on the way."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 64, seed=5)
    _, kv = _prefill(params, kv, statics, seq[:20])
    for pos in range(20, 64):
        logits, kv = _decode(params, kv, statics, int(seq[pos]), pos)
    want = ref.logits_for(params, hf, seq, 1)
    assert _err_std(logits, want[0]) < TOL_STD


def test_every_breakage_moves_the_reference(ref):
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 50)
    logits, _ = _prefill(params, kv, statics, seq)
    for broken in ref.BREAKAGES:
        want = ref.logits_for(params, hf, seq, 1, broken=broken)
        assert _err_std(logits, want[0]) > 0.05, broken
    # what served bf16 logits are held to: all but the five fine ones
    assert set(ref.BREAKAGES) - set(ref.breakages_for(hf)) == set(ref.FINE)
    assert len(ref.breakages_for(hf)) == 7


@pytest.mark.parametrize("change", ["nothing", "a token", "a weight",
                                    "the breakage", "the positions asked"])
def test_the_reference_answers_the_same_question_once(ref, monkeypatch,
                                                      change):
    """The harness holds its probes to the reference before its window and
    after it: the same sequence under the same weight arrays is one forward,
    anything else a new one."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    seq = _tokens(cfg, 30).tolist()
    forwards = []
    forward = ref.forward
    monkeypatch.setattr(ref, "forward", lambda *a, **k: (
        forwards.append(1), forward(*a, **k))[1])
    monkeypatch.setattr(ref, "_ANSWERS", [])
    first = ref.logits_for(params, hf, seq, 2)
    first[:] = 0                         # the caller's copy, not the kept one
    args, kw = [params, hf, seq, 2], {}
    if change == "a token":
        args[2] = seq[:-1] + [(seq[-1] + 1) % cfg.vocab_size]
    elif change == "a weight":
        args[0] = dict(params, final_norm=params["final_norm"] + 0)
    elif change == "the breakage":
        kw["broken"] = "no_gate"
    elif change == "the positions asked":
        args[3] = 3
    again = ref.logits_for(*args, **kw)
    assert len(forwards) == (1 if change == "nothing" else 2)
    assert np.abs(again).max() > 0
    if change in ("nothing", "a weight"):
        monkeypatch.setattr(ref, "_ANSWERS", [])
        np.testing.assert_array_equal(
            again, ref.logits_for(params, hf, seq, 2))


def test_chunked_prefill_equals_whole_prefill_in_every_pool():
    cfg, params, kv, statics = _setup(_hf())
    seq = _tokens(cfg, 56)
    whole_logits, whole = _prefill(params, kv, statics, seq)
    kv2 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    for lo in range(0, 56, 16):
        logits, kv2 = _prefill(params, kv2, statics, seq[lo:lo + 16],
                               start=lo, pad_to=16)
    assert _err_std(logits, whole_logits) < TOL_STD
    for name in ("kv", "idx", "win"):
        np.testing.assert_allclose(np.asarray(kv2[name]),
                                   np.asarray(whole[name]), atol=1e-5)


def test_the_engines_tables_name_the_window_pools_blocks(ref):
    """A prefill table of 2M entries and decode tables of M + R: the window
    layers' rows go to, and come from, the blocks of their own table."""
    hf = _hf()
    cfg, params, kv, _ = _setup(hf)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla",
                           table_blocks=8)
    seq = _tokens(cfg, 45)
    win = np.array([9, 3, 12, 5, 0, 0, 0, 0], np.int32)    # logical 0..3
    padded = np.zeros((64,), np.int32)
    padded[:40] = seq[:40]
    with jax.default_matmul_precision("highest"):
        logits, kv = _PREFILL(
            params, kv, jnp.asarray(padded),
            jnp.concatenate([TABLE, jnp.asarray(win)]), jnp.asarray(0),
            jnp.asarray(40), statics)
        want = ref.logits_for(params, hf, seq, 6)
        assert _err_std(logits, want[0]) < TOL_STD
        # the paged pool's blocks 3 and 5 hold none of the window's rows
        assert float(jnp.abs(kv["win"][:, 4 * BS:5 * BS]).max()) == 0.0
        assert float(jnp.abs(kv["win"][:, 12 * BS:13 * BS]).max()) > 0.0
        ring = np.zeros((2, 3), np.int32)
        for b, bid in enumerate(win[:3]):
            ring[0, b % 3] = bid                # logical block b at b % R
        ring[0, 2 % 3] = win[2]
        tables = np.zeros((2, 8 + 3), np.int32)
        tables[0, :8], tables[0, 8:] = np.asarray(TABLE), ring[0]
        for i in range(5):
            pos = 40 + i
            logits, kv = _DECODE(
                params, kv, jnp.asarray([int(seq[pos]), 0], jnp.int32),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray(tables),
                statics)
            assert _err_std(logits[0], want[i + 1]) < TOL_STD


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Guide "model-configs" section 4: what all 16 shares of 16 experts
    give, the shared expert counted once, adds up to the uncut reference's
    layer output."""
    hf_whole = _hf(n_routed_experts=16, n_routed_experts_published=16)
    cfg_whole, params, _, _ = _setup(hf_whole)
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
        total = -15 * shared_only               # counted once of 16 times
        for share in range(16):
            hf = _hf(n_routed_experts=1, n_routed_experts_published=16,
                     expert_share_index=share)
            lp_share = dict(lp, **{n: lp[n][share:share + 1] for n in
                                   ("moe_gate", "moe_up", "moe_down")})
            part = mla._moe_mlp(m, lp_share, ModelConfig.from_hf_config(hf))
            # the reference, given the same share, gives the same part
            want = ref.moe_block(ref.family(hf))(m, lp_share)
            assert _err_std(part, want) < TOL_STD
            total = total + part
        uncut = ref.moe_block(ref.family(hf_whole))(m, lp)
    assert _err_std(whole, uncut) < TOL_STD
    assert _err_std(total, uncut) < 10 * TOL_STD


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_program_is_one_period_whatever_the_depth(program):
    """The layers run as ONE scan over the periods of layer_types: the
    lowered program of 17 layers (four periods) has as many instructions as
    that of 9 (two), and every stack reaches the loop whole, read at the
    layer's index (no ``slice`` of a stack: the rule of mla._run_layers)."""
    import re

    def lowered(layers):
        kinds = (["full_attention"] + ["sliding_attention"] * 3) * 4
        hf = _hf(num_hidden_layers=layers,
                 layer_types=["full_attention"] + kinds)
        cfg = ModelConfig.from_hf_config(hf)
        statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")
        params = jax.eval_shape(
            lambda: mla.init_params(cfg, jax.random.PRNGKey(0)))
        kv = jax.eval_shape(lambda: mla.init_kv_cache(cfg, NUM_BLOCKS, BS))
        i32 = jnp.int32
        s = jax.ShapeDtypeStruct
        if program == "prefill":
            text = _PREFILL.lower(params, kv, s((32,), i32), s((8,), i32),
                                  s((), i32), s((), i32), statics).as_text()
        else:
            text = _DECODE.lower(params, kv, s((2,), i32), s((2,), i32),
                                 s((2, 8), i32), statics).as_text()
        return params, text

    params, nine = lowered(9)
    _, seventeen = lowered(17)
    assert len(nine.splitlines()) == len(seventeen.splitlines())
    assert "stablehlo.while" in nine
    stacks = {"tensor<" + "x".join(map(str, x.shape)) + "x"
              + {"bfloat16": "bf16"}[jnp.dtype(x.dtype).name] + ">"
              for name, x in params.items() if name.startswith("layers.")}
    sliced = [line for line in nine.splitlines()
              if re.search(r"stablehlo\.slice ", line)
              and line.split(" : (")[-1].split(")")[0] in stacks]
    assert not sliced, sliced[0][:300]


# ------------------------------------------------------ the hit rule alone

def _manager(num_blocks=64, win_blocks=64):
    layout = mla.cache_layout(ModelConfig.from_hf_config(_hf()), BS, 4)
    events = []
    mgr = KvBlockManager(
        num_blocks, BS, layout=layout, win_blocks=win_blocks,
        prefer_native=False,
        on_stored=lambda bid, h, th, ph: events.append(("stored", h)),
        on_removed=lambda hs: events.extend(("removed", h) for h in hs))
    return mgr, events


def _admit(mgr, prompt):
    """Admission as the engine does it, with nothing computed: plan, take
    the window blocks of the prompt, register, slide to the prompt's end."""
    plan = mgr.prepare_prefill(prompt)
    mgr.window_grow(plan.win, plan.hit_tokens // BS, -(-len(prompt) // BS))
    mgr.register_full_blocks(plan.all_blocks, plan.seq, len(plan.hit_blocks))
    mgr.window_register(plan.win, plan.seq, plan.all_blocks, len(prompt))
    mgr.window_slide(plan.win, len(prompt))
    return plan


def _finish(mgr, plan):
    mgr.pool.release(plan.all_blocks)
    mgr.window_release(plan.win)


def _drop_window_blocks(mgr, hashes, gone):
    wp = mgr.win_pool
    for i in gone:
        bid = wp._by_hash[hashes[i]]
        assert wp._meta[bid].refcount == 0
        wp._invalidate(bid)
        wp._free_uninit.add(bid)


def _rule(present, n, reach):
    """The longest boundary <= n whose ``reach`` window blocks before it
    are all present, by trying each."""
    return next(b for b in range(n, -1, -1)
                if all(i in present for i in range(max(0, b - reach), b)))


@pytest.mark.parametrize("gone, shared_blocks, hit_blocks", [
    ((), 6, 6),                 # everything cached: the whole match
    ((0, 1, 2, 3), 6, 6),       # ends ON the retained tail {4, 5}
    ((2, 3), 5, 2),             # ends INSIDE it: block 3 is gone -> 2
    ((2, 3), 3, 2),             # ends BEFORE it, next to a hole -> 2
    ((0, 1, 2, 3), 5, 0),       # nothing before the tail is left -> 0
    ((5,), 6, 5),               # the last block alone is gone -> 5
])
def test_a_hit_is_cut_back_to_where_the_window_rows_are(gone, shared_blocks,
                                                        hit_blocks):
    """R2: a prefix hit of P tokens needs every paged block of [0, P) and
    the window blocks of [P - window, P): two blocks of 16 at window 21."""
    mgr, _ = _manager()
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 500, size=6 * BS).tolist()
    first = _admit(mgr, doc + [7, 8, 9])
    assert first.hit_tokens == 0 and len(first.win.held) == 3   # the ring
    _finish(mgr, first)
    hashes = TokenBlockSequence(BS, doc).sequence_hashes
    _drop_window_blocks(mgr, hashes, gone)
    prompt = doc[:shared_blocks * BS] + rng.integers(0, 500, 20).tolist()
    plan = mgr.prepare_prefill(prompt)
    present = set(range(6)) - set(gone)
    assert hit_blocks == _rule(present, shared_blocks, 2)
    assert plan.hit_tokens == hit_blocks * BS
    assert plan.hit_cut_tokens == (shared_blocks - hit_blocks) * BS
    assert sorted(plan.win.held) == list(range(max(0, hit_blocks - 2),
                                               hit_blocks))
    assert len(plan.hit_blocks) == hit_blocks
    mgr.abort_plan(plan)
    assert mgr.pool.used_blocks == 0 and mgr.win_pool.used_blocks == 0


def test_the_worked_example_of_the_docs():
    """docs/hybrid_cache.md: a 2,048-block document at the published sizes
    (window 513: 33 blocks before a boundary). A hit at its end needs window
    blocks 2,015..2,047; a hit at block 1,000 needs 967..999 and, with the
    body evicted, is cut back to nothing."""
    cfg = ModelConfig.from_hf_config(dict(_hf(), sliding_window_size=513))
    layout = mla.cache_layout(cfg, BS, 2)
    assert (layout.ring_blocks, layout.window_reach_blocks) == (34, 33)
    mgr = KvBlockManager(4096, BS, layout=layout, win_blocks=4096,
                         prefer_native=False)
    hashes = list(range(10_000, 12_048))
    for i, h in enumerate(hashes):
        for pool in (mgr.pool, mgr.win_pool):
            bid, = pool.alloc_uninit(1)
            pool.register(bid, h, h, hashes[i - 1] if i else None)
            pool.release([bid])
    assert mgr._window_cut(hashes, 2048) == 2048
    _drop_window_blocks(mgr, hashes, range(0, 2015))
    assert mgr._window_cut(hashes, 2048) == 2048
    assert mgr._window_cut(hashes, 1000) == 0
    assert mgr._window_cut(hashes, 2047) == 0      # needs 2,014: gone
    _drop_window_blocks(mgr, hashes, [2040])
    assert mgr._window_cut(hashes, 2048) == 0


def test_window_blocks_no_hit_ended_on_go_first_and_the_router_hears():
    """R3: under pressure the window pool takes back a prefix's body and
    finished requests' own tails before a tail that a hit has ended on;
    when such a tail does go, the router is told the paged block is gone,
    and told again when the rows are computed anew."""
    mgr, events = _manager(num_blocks=64, win_blocks=12)
    rng = np.random.default_rng(2)
    doc = rng.integers(0, 500, size=6 * BS).tolist()
    _finish(mgr, _admit(mgr, doc + [1, 2, 3]))
    hashes = TokenBlockSequence(BS, doc).sequence_hashes
    # a hit ends on the document's tail: blocks 4 and 5 become a tail
    second = _admit(mgr, doc + [4, 5, 6])
    assert second.hit_tokens == 6 * BS
    _finish(mgr, second)
    wp = mgr.win_pool
    assert wp.evicted == 0 and wp.released > 0
    # pressure: 9 of the pool's 11 blocks, 5 of them free: the 4 cached
    # ones that are no tail (the body, 0..3) go, and the tail stays
    held = wp.alloc_uninit(9)
    assert wp.has(hashes[4]) and wp.has(hashes[5])
    assert not any(wp.has(h) for h in hashes[:4])
    assert not [e for e in events if e[0] == "removed"]
    third = mgr.prepare_prefill(doc + [7, 8, 9])
    assert third.hit_tokens == 6 * BS and third.hit_cut_tokens == 0
    mgr.abort_plan(third)
    # more pressure: the tail goes, and the router hears of it
    held += wp.alloc_uninit(2)
    assert not wp.has(hashes[5])
    gone = [h for kind, h in events if kind == "removed"]
    assert set(gone) == {hashes[4], hashes[5]}
    assert mgr.window_stats()["window_blocks_evicted"] == 6
    wp.release(held)
    # the paged blocks are all there, the hit is cut back all the same...
    fourth = _admit(mgr, doc + [7, 8, 9])
    assert fourth.hit_tokens == 0 and fourth.hit_cut_tokens == 6 * BS
    # ... and what it computed anew is announced again
    assert [h for kind, h in events if kind == "stored"][-2:] == [
        hashes[4], hashes[5]]
    _finish(mgr, fourth)


# ------------------------------------------------------------ the engine

def _engine_cfg(**over) -> EngineConfig:
    base = dict(max_model_len=128, kv_block_size=BS, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32, 64, 128])
    return EngineConfig(**dict(base, **over))


def _engine(params, cfg, **over):
    from dynamo_tpu.engine.core import EngineCore
    return EngineCore(cfg, _engine_cfg(**over), params=dict(params),
                      attn_impl="xla", param_dtype=jnp.float32)


async def _serve(core, rid, prompt, n=6):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid=rid, prompt=[int(t) for t in prompt],
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=n, eos_ids=frozenset())
    await core.submit(req)
    toks, lps = [], []
    while True:
        item, lp = await asyncio.wait_for(req.out_queue.get(), 120)
        if item is FINISH_SENTINEL:
            break
        toks.append(item)
        lps.append(lp)
    return toks, lps, req


def _held_to_the_reference(ref, params, hf, prompt, toks, lps):
    logits = ref.logits_for(params, hf, list(prompt) + toks[:-1], len(toks))
    for tok, lp, row in zip(toks, lps, logits):
        row = row.astype(np.float64)
        ref_lp = row[tok] - (row.max() + np.log(np.exp(row - row.max()).sum()))
        assert abs(ref_lp - lp) < 10 * TOL_STD * row.std()


@pytest.mark.parametrize("over, match", [
    ({"ragged_dispatch": True}, "--ragged"),
    ({"spec_k": 2}, "--spec-k"),
    ({"kv_quantization": "int8"}, "--kv-quantization"),
    ({"host_kv_blocks": 8}, "--host-kv-blocks"),
    ({"decode_steps_per_dispatch": 4}, "--decode-steps-per-dispatch"),
    ({"tp": 2}, "meshes"),
    ({"quantization": "int4"}, "int4"),
])
def test_engine_refuses_what_cannot_carry_the_window_pool(over, match):
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig.from_hf_config(_hf())
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(cfg, _engine_cfg(**over), attn_impl="xla",
                   param_dtype=jnp.float32)


@pytest.mark.asyncio
async def test_engine_takes_hits_over_both_groups_and_equals_cold(ref):
    """R2 through EngineCore: a document is served once; then prompts whose
    match ends ON the document's retained window tail, INSIDE it and BEFORE
    it (the window blocks of 2 and 3 dropped in between, as pressure would)
    are served by the hit the rule allows, and give the tokens and logprobs
    of an engine without reuse and of the reference."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    warm = _engine(params, cfg, prefill_chunk=16, prefill_buckets=[16])
    # without reuse a window block let go behind one chunk is the next
    # chunk's: each dispatch needs a table of its own
    cold = _engine(params, cfg, enable_prefix_reuse=False,
                   prefill_chunk=16, prefill_buckets=[16])
    doc = _tokens(cfg, 6 * BS, seed=7).tolist()
    hashes = TokenBlockSequence(BS, doc).sequence_hashes
    wp = warm.kv_manager.win_pool
    try:
        with jax.default_matmul_precision("highest"):
            toks, lps, req = await _serve(
                warm, "doc", doc + _tokens(cfg, 5, seed=8).tolist(), n=4)
            assert req.prefix_hit_tokens == 0
            assert all(wp.has(h) for h in hashes)
            _drop_window_blocks(warm.kv_manager, hashes, (2, 3))
            for rid, shared, seed in (("on", 6, 9), ("inside", 5, 10),
                                      ("before", 3, 11)):
                present = {i for i, h in enumerate(hashes) if wp.has(h)}
                prompt = doc[:shared * BS] + _tokens(cfg, 13, seed).tolist()
                toks, lps, req = await _serve(warm, rid, prompt)
                want_toks, want_lps, _ = await _serve(cold, rid, prompt)
                assert req.prefix_hit_tokens == BS * _rule(present, shared, 2)
                assert toks == want_toks, rid
                np.testing.assert_allclose(lps, want_lps, atol=1e-4)
                _held_to_the_reference(ref, params, hf, prompt, toks, lps)
                # what "inside" computed anew is there for "before"
        admits = {r["rid"]: r for r in warm.flight.dump()
                  if r["kind"] == "prefill"}
        assert (admits["on"]["hit_tokens"],
                admits["on"]["hit_cut_tokens"]) == (96, 0)
        assert (admits["inside"]["hit_tokens"],
                admits["inside"]["hit_cut_tokens"]) == (32, 48)
        assert admits["before"]["hit_tokens"] == 48     # 2 was recomputed
        assert admits["doc"]["hit_cut_tokens"] == 0
        assert cold.kv_manager.win_pool.reusable_blocks == 0
        stats = warm.kv_manager.window_stats()
        assert stats["window_blocks_released"] > 0
        assert stats["window_blocks_used"] == 0
    finally:
        await warm.stop()
        await cold.stop()


@pytest.mark.asyncio
async def test_a_context_of_forty_windows_holds_a_ring(ref):
    """R1: 840 tokens of context (40 windows of 21), prefilled by chunks of
    32 and decoded on: no sequence ever holds more than the ring's three
    window blocks a layer in a decode step, nor more than a chunk's and the
    ring's in prefill, and the stream is the reference's."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    core = _engine(params, cfg, max_model_len=1024, num_kv_blocks=128,
                   prefill_chunk=32, prefill_buckets=[32])
    wp = core.kv_manager.win_pool
    # sized from the layout: every slot's ring, one dispatch, and the
    # evictable part, which since PR 46 is bounded by four hit boundaries a
    # slot (2 slots x 4 x a reach of 2) before half the pool (64) or the
    # bytes bound (docs/hybrid_cache.md part three)
    assert wp.num_blocks == 1 + 2 * 3 + (2 + 3) + 16
    prompt = _tokens(cfg, 840, seed=12)
    peak = []
    grow = core.kv_manager.window_grow

    def watched(win, lo, hi):
        ok = grow(win, lo, hi)
        peak.append(len(win.held))
        return ok

    core.kv_manager.window_grow = watched
    try:
        with jax.default_matmul_precision("highest"):
            toks, lps, req = await _serve(core, "long", prompt, n=24)
        _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        decode = [r for r in core.flight.dump() if r["kind"] == "decode"
                  and r["batch_fill"]]
        assert decode and max(r["win_blocks_live"] for r in decode) == 3
        assert max(peak) <= 2 + 3
        assert all(r["win_tokens"] == 21 * r["emitted"] for r in decode)
        assert all(r["sel_tokens"] == 16 * r["emitted"] for r in decode)
        # 54 blocks of context went through; two or three are still cached
        # per ... none is held once the request is gone
        assert wp.used_blocks == 0 and wp.released >= 50
    finally:
        await core.stop()


@pytest.mark.asyncio
async def test_a_preempted_sequence_recomputes_both_groups(ref):
    """A paged pool too small for both sequences: one is preempted, its
    blocks of both pools are released, and the recompute re-derives them
    from the grown prompt. Both streams stay the reference's."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    prompts = [_tokens(cfg, 30, seed=21), _tokens(cfg, 30, seed=22)]
    core = _engine(params, cfg, num_kv_blocks=9)
    try:
        with jax.default_matmul_precision("highest"):
            outs = await asyncio.gather(
                _serve(core, "a", prompts[0], n=40),
                _serve(core, "b", prompts[1], n=40))
        assert core.preemptions > 0, "contention never preempted"
        for prompt, (toks, lps, _) in zip(prompts, outs):
            assert len(toks) == 40
            _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        assert core.kv_manager.win_pool.used_blocks == 0
        assert core.kv_manager.pool.used_blocks == 0
    finally:
        await core.stop()


def test_block_moves_carry_each_group_under_its_own_ids():
    """block_copy.move_blocks: the paged group's arrays (latent rows AND
    index keys) move under the paged ids, the window group's under the
    window pool's, in one program."""
    from dynamo_tpu.engine.block_copy import move_blocks
    cfg, params, kv, statics = _setup(_hf())
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 60))
    before = {k: np.asarray(v) for k, v in kv.items()}
    moved = move_blocks(kv, [1, 2, 3], [9, 10, 11], BS,
                        win_src=[2, 4], win_dst=[13, 12])
    for name in ("kv", "idx"):
        arr = np.asarray(moved[name])
        assert np.abs(before[name][:, BS:4 * BS]).max() > 0
        np.testing.assert_array_equal(arr[:, 9 * BS:12 * BS],
                                      before[name][:, BS:4 * BS])
        np.testing.assert_array_equal(arr[:, 12 * BS:],
                                      before[name][:, 12 * BS:])
    win = np.asarray(moved["win"])
    np.testing.assert_array_equal(win[:, 13 * BS:14 * BS],
                                  before["win"][:, 2 * BS:3 * BS])
    np.testing.assert_array_equal(win[:, 12 * BS:13 * BS],
                                  before["win"][:, 4 * BS:5 * BS])
    np.testing.assert_array_equal(win[:, 9 * BS:12 * BS],
                                  before["win"][:, 9 * BS:12 * BS])
    # without window ids (the benchmark's warm-up): the window group stays
    plain = move_blocks({k: jnp.asarray(v) for k, v in before.items()},
                        [1, 2], [9, 10], BS)
    np.testing.assert_array_equal(np.asarray(plain["win"]), before["win"])


@pytest.mark.asyncio
async def test_engine_defrag_moves_both_groups():
    """Both pools' free space shattered, a sequence admitted over the
    shards, then free runs given back: the idle defrag pass moves the
    sequence's paged blocks AND its window blocks onto runs while it
    decodes, and the stream is that of an engine that never moved."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    over = dict(max_model_len=256, num_kv_blocks=64, prefill_buckets=[64],
                kv_defrag_threshold=0.01)
    core = _engine(params, cfg, **over)
    still = _engine(params, cfg, **dict(over, kv_defrag_threshold=0.0))
    prompt = _tokens(cfg, 50, seed=13)
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    try:
        with jax.default_matmul_precision("highest"):
            base_toks, _, _ = await _serve(still, "base", prompt, n=40)
            pool, wp = core.kv_manager.pool, core.kv_manager.win_pool
            comb = pool.alloc_uninit(63)
            pool.release(comb[::2])
            wcomb = wp.alloc_uninit(wp.num_blocks - 1)
            wp.release(wcomb[::2])
            req = EngineRequest(rid="frag", prompt=[int(t) for t in prompt],
                                sampling=SlotSampling(temperature=0.0),
                                max_new_tokens=40, eos_ids=frozenset())
            await core.submit(req)
            while req.slot < 0 or core._pending is None:
                await asyncio.sleep(0.005)
            held = [b for _i, b in sorted(req.win.held.items())]
            assert pool.count_runs(req.blocks) >= 2
            assert wp.count_runs(held) >= 2
            pool.release(comb[1::2])
            wp.release(wcomb[1::2])
            toks = []
            while True:
                item, _ = await asyncio.wait_for(req.out_queue.get(), 120)
                if item is FINISH_SENTINEL:
                    break
                toks.append(item)
        assert toks == base_toks
        assert core.defrag_passes >= 1
        assert pool.defrag_moves_total >= 2 and wp.defrag_moves_total >= 2
        assert wp.used_blocks == 0
    finally:
        await core.stop()
        await still.stop()
