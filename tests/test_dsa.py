"""DeepSeek-V3.2 (deepseek_v32) on models/mla.py: the lightning indexer, the
second per-token cache for its keys, select-then-attend in prefill and decode,
and one chip's share of the experts, held to the benchmark's plain reference
(benchmark/references/deepseek_v32.py) at tiny widths on the CPU, with seeded
random weights.

Tolerances. Engine and reference both compute in float32 here (float32
parameters and pools, ``jax.default_matmul_precision("highest")``), so what
separates them is the order of float32 sums: a few 1e-6 of the logits'
standard deviation (measured 4e-6 at these sizes). ``TOL_STD`` = 1e-4 leaves
room for another summation order and fails anything else: a dropped term, a
wrong rope convention, bf16 anywhere in the indexer (which moves index scores
by 2^-9 of their size, flips near-tied selections at these widths and with
them the logits by 0.1 to 3 standard deviations — measured while choosing the
fixture, PERF.md section 6, PR 31). The selected sets are compared exactly:
random weights make logits blind to a wrong selection at a long context, the
sets are not.
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

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.models import mla
from dynamo_tpu.engine.models.llama import ModelStatics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 16
NUM_BLOCKS = 16
TOL_STD = 1e-4


@pytest.fixture(scope="module")
def ref():
    """benchmark/references/deepseek_v32.py (it imports the benchmark's
    ``reference`` module by its bare name)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_deepseek_v32",
            os.path.join(BENCH, "references", "deepseek_v32.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


def _hf(**over) -> dict:
    with open(os.path.join(BENCH, "fixtures", "tiny-deepseek-v32.json")) as f:
        hf = json.load(f)
    for key in ("source", "reduced", "assumed", "deployment", "reference"):
        hf.pop(key)
    # the published score scale (mscale_all_dim) and a selection that binds
    # from position 16 on: this file computes in float32, where neither
    # needs the fixture's care for bf16
    hf["rope_scaling"] = dict(hf["rope_scaling"], mscale_all_dim=1)
    hf.update(num_hidden_layers=3, index_topk=16, routed_scaling_factor=2.5,
              num_experts_per_tok=2, topk_group=1)
    return dict(hf, **over)


def _setup(hf: dict, seed: int = 1):
    cfg = ModelConfig.from_hf_config(hf)
    params = mla.init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=jnp.float32)
    # a router bias that matters (it is zero at initialisation)
    params["layers.router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), params["layers.router_bias"].shape)
    kv = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    return cfg, params, kv, ModelStatics(cfg=cfg, block_size=BS,
                                         attn_impl="xla")


def _tokens(cfg, n: int, seed: int = 3) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=n)


TABLE = jnp.arange(1, 9, dtype=jnp.int32)          # 8 blocks: 128 positions


def _prefill(params, kv, statics, tokens, start=0, pad_to=64):
    padded = np.zeros(pad_to, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return mla.prefill_forward(
            params, kv, jnp.asarray(padded), TABLE, jnp.asarray(start),
            jnp.asarray(len(tokens)), statics)


def _decode(params, kv, statics, token, pos):
    """One step of a two-slot batch whose second slot is idle."""
    with jax.default_matmul_precision("highest"):
        logits, kv = mla.decode_forward(
            params, kv, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
            jnp.stack([TABLE, jnp.zeros_like(TABLE)]), statics)
    return logits[0], kv


def _err_std(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / want.std())


def test_from_hf_config_reads_the_indexer_and_the_share():
    cfg = ModelConfig.from_hf_config(_hf())
    assert (cfg.model_type, cfg.index_n_heads, cfg.index_head_dim,
            cfg.index_topk) == ("deepseek_v32", 4, 16, 16)
    assert (cfg.num_experts, cfg.num_experts_total, cfg.router_width,
            cfg.expert_share_index) == (4, 8, 8, 0)
    assert cfg.moe_routing == "sigmoid_noaux" and cfg.is_deepseek_v3
    shapes = mla.param_shapes(cfg)
    assert shapes["layers.router"] == (2, 64, 8)
    assert shapes["layers.router_bias"] == (2, 8)
    assert shapes["layers.moe_gate"] == (2, 4, 64, 32)
    assert shapes["layers.idx_wq_b"] == (3, 24, 4 * 16)
    assert shapes["layers.idx_wk"] == (3, 64, 16)
    assert shapes["layers.idx_w"] == (3, 64, 4)
    # no indexer: the v3 tree and pool are what they were
    v3 = ModelConfig.from_hf_config(
        {k: v for k, v in _hf(model_type="deepseek_v3").items()
         if not k.startswith("index_")})
    assert v3.index_topk == 0
    assert not any(n.startswith("layers.idx_") for n in mla.param_shapes(v3))
    assert set(mla.init_kv_cache(v3, 4, BS)) == {"kv"}


@pytest.mark.parametrize("bad, match", [
    ({"index_topk": None}, "index_topk"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_routed_experts_published": 6}, "does not divide"),
    ({"expert_share_index": 2}, "does not divide"),
    ({"model_type": "deepseek_v3"}, "indexer is deepseek_v32"),
])
def test_from_hf_config_refuses_what_is_not_computed(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**bad))


def test_both_caches_under_one_block_table():
    cfg, params, kv, statics = _setup(_hf())
    assert list(kv) == ["kv", "idx"]
    assert kv["idx"].shape == (3, NUM_BLOCKS * BS, cfg.index_head_dim)
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 40))
    written = np.asarray(jnp.any(kv["idx"] != 0, axis=-1))     # [L, NTOK]
    latent = np.asarray(jnp.any(kv["kv"] != 0, axis=-1))
    # 40 positions of blocks 1..3, in every layer, in both arrays (block 0
    # is the trash block: the chunk's pad rows land there)
    want = np.zeros_like(written[0])
    want[BS:BS + 40] = True
    for li in range(cfg.num_layers):
        assert (written[li][BS:] == want[BS:]).all()
        assert (latent[li][BS:] == want[BS:]).all()


def test_prefill_and_decode_match_the_reference(ref):
    """Prefill, then decode through both caches, against the reference's
    full forward over the whole sequence: logits at every step."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    n, steps = 48, 6
    seq = _tokens(cfg, n + steps)
    logits, kv = _prefill(params, kv, statics, seq[:n])
    got = [logits]
    for i in range(steps):
        logits, kv = _decode(params, kv, statics, seq[n + i], n + i)
        got.append(logits)
    want = ref.logits_for(params, hf, seq.tolist(), steps + 1)
    for i, (g, w) in enumerate(zip(got, want)):
        assert _err_std(g, w) < TOL_STD, (i, _err_std(g, w))


def test_every_breakage_moves_the_reference(ref):
    """The comparison above is not blind: each of the reference's listed
    breakages moves its logits by far more than the tolerance."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    seq = _tokens(cfg, 48).tolist()
    want = ref.logits_for(params, hf, seq, 4)
    for broken in ref.breakages_for(hf):
        got = ref.logits_for(params, hf, seq, 4, broken)
        assert _err_std(got, want) > 100 * TOL_STD, broken


@pytest.fixture(scope="module")
def chk():
    """benchmark/references/deepseek_v32_check.py: the builder's check (it
    puts benchmark/ on the path for the modules it imports when called)."""
    before = list(sys.path)
    sys.path.insert(0, os.path.join(BENCH, "references"))
    try:
        import deepseek_v32_check
        yield deepseek_v32_check
    finally:
        sys.path[:] = before


def _engine_selection(chk, cfg, params, kv, statics, seq, n):
    """The sets the engine selects, per layer and query position: prefill
    of ``n`` tokens, then one decode step per remaining token, carried out
    of the traced layer scans by the check's tap on ``mla._select``."""
    L = cfg.num_layers
    with chk.Tap(mla) as tap:
        _, kv = _prefill(params, kv, statics, seq[:n])
        sets = [tap.take(L)[:, :n]]      # pad rows come after the true ones
        for pos in range(n, len(seq)):
            _, kv = _decode(params, kv, statics, seq[pos], pos)
            sets.append(tap.take(L)[:, :1])       # slot 0; slot 1 is idle
    return [[set(row[row >= 0].tolist()) for row in layer]
            for layer in np.concatenate(sets, axis=1)]


def test_selected_sets_match_the_reference(ref, chk):
    hf = _hf(num_hidden_layers=2)
    cfg, params, kv, statics = _setup(hf)
    n, steps = 40, 3
    seq = _tokens(cfg, n + steps)
    picked = _engine_selection(chk, cfg, params, kv, statics, seq, n)
    allowed = ref.selected_sets(params, hf, seq.tolist())
    assert len(allowed) == cfg.num_layers
    for li, rows in enumerate(allowed):
        for pos in range(len(seq)):
            want = set(np.flatnonzero(rows[pos]).tolist())
            assert len(want) == min(cfg.index_topk, pos + 1)
            assert picked[li][pos] == want, (li, pos)
    # and the selection is one: neither everything nor the last 16
    last = picked[-1][len(seq) - 1]
    assert last != set(range(len(seq) - 16, len(seq)))


def _select_by_stable_sort(scores, live, topk, slots):
    """The definition ``mla._select`` is held to: the plain reference it
    replaced (PR 31), one stable sort by negated score that carries each
    position and its pool row; ties go to the lower position."""
    N, S = scores.shape
    k = min(topk, S)
    neg = jnp.where(live, -scores, jnp.inf)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (N, S))
    neg, pos, rows = jax.lax.sort(
        (neg, pos, jnp.broadcast_to(slots, (N, S))), dimension=1,
        is_stable=True, num_keys=1)
    return pos[:, :k], neg[:, :k] < jnp.inf, rows[:, :k]


def _case_scores(kind: str, N: int, S: int, rng) -> np.ndarray:
    x = rng.standard_normal((N, S)).astype(np.float32)
    if kind == "ties8":          # eight values: hundreds of ties at the cut
        x = np.round(x * 1.5).clip(-4, 3).astype(np.float32)
    elif kind == "equal":
        x = np.full((N, S), 0.25, np.float32)
    elif kind == "zeros":        # −0.0 beside +0.0 (equal), around the cut
        x = np.where(np.abs(x) < 0.8,
                     np.where(rng.random((N, S)) < 0.5, -0.0, 0.0), x
                     ).astype(np.float32)
    elif kind == "nonfinite":    # a live −inf or NaN is never selected
        x[rng.random((N, S)) < 0.3] = -np.inf
        x[rng.random((N, S)) < 0.2] = np.nan
        x[rng.random((N, S)) < 0.05] = np.inf
    elif kind == "relu":         # the indexer's: many exact zeros, ≥ 0
        x = np.maximum(x, 0)
    return x


def _case_live(kind: str, N: int, S: int, rng) -> np.ndarray:
    pos = np.arange(S)[None, :]
    if kind == "lens":           # decode: a length per row, one row empty
        lens = rng.integers(0, S + 1, (N, 1))
        lens[0], lens[-1] = 0, S
        return pos < lens
    if kind == "causal":         # prefill: query n sees up to start + n
        start = S - N
        return (pos <= start + np.arange(N)[:, None]) & (pos < S - 3)
    if kind == "holes":
        return rng.random((N, S)) < 0.6
    return np.ones((N, S), bool)


# name, N, S, k, scores, live, slots form, block size, pool blocks
_SELECT_CASES = [
    ("random", 8, 256, 32, "normal", "all", "table", 16, 64),
    ("random-rows-per-query", 8, 256, 32, "normal", "lens", "tables", 16, 64),
    ("ties-8-values", 8, 256, 32, "ties8", "all", "table", 16, 64),
    ("ties-8-values-holes", 8, 240, 50, "ties8", "holes", "tables", 16, 64),
    ("all-equal", 4, 256, 32, "equal", "all", "table", 16, 64),
    ("all-equal-lens", 4, 256, 32, "equal", "lens", "tables", 16, 64),
    ("signed-zeros", 8, 256, 100, "zeros", "all", "table", 16, 64),
    ("relu-zeros", 8, 256, 200, "relu", "holes", "tables", 16, 64),
    ("nonfinite", 8, 256, 64, "nonfinite", "lens", "tables", 16, 64),
    ("fewer-live-than-k", 8, 256, 200, "normal", "lens", "tables", 16, 64),
    ("k-equals-S", 4, 64, 64, "normal", "holes", "table", 16, 8),
    ("k-above-S", 4, 48, 2048, "ties8", "lens", "tables", 16, 8),
    ("causal-prefill-block", 32, 304, 16, "normal", "causal", "table", 16,
     112),
    ("causal-ties", 32, 304, 16, "ties8", "causal", "table", 16, 112),
    ("S-not-a-power-of-two", 5, 17 * 24, 33, "normal", "lens", "tables", 24,
     40),
    ("S-one-block", 3, 16, 5, "ties8", "all", "table", 16, 4),
    ("rows-as-array-S", 8, 256, 32, "ties8", "holes", "array", 16, 64),
    ("rows-as-array-NS", 8, 256, 32, "ties8", "lens", "arrays", 16, 64),
    ("published-sizes", 3, 17408, 2048, "relu", "lens", "tables", 16,
     7 * 12288),
    ("published-sizes-prefill", 2, 17408, 2048, "ties8", "causal", "table",
     16, 7 * 12288),
    ("pool-overflows-the-key", 4, 17408, 2048, "ties8", "lens", "tables", 16,
     1 << 17),
    ("small-table-large-pool", 8, 256, 32, "normal", "holes", "table", 16,
     1 << 24),
]


@pytest.mark.parametrize("case", _SELECT_CASES, ids=lambda c: c[0])
def test_select_is_the_stable_sorts_top_k(case):
    """``mla._select`` (a threshold search, then a compaction of one packed
    key: ``engine/select_compact.py``, interpreted here) keeps exactly the
    set the stable three-operand sort keeps, ties to the lower position,
    on the same float32 scores."""
    name, N, S, k, kind, mask, form, bsz, pool = case
    rng = np.random.default_rng(sum(map(ord, name)))
    scores = _case_scores(kind, N, S, rng)
    live = _case_live(mask, N, S, rng)
    M = S // bsz
    shape = (N, M) if form in ("tables", "arrays") else (M,)
    blocks = rng.integers(0, pool, shape).astype(np.int32)
    blocks.flat[0] = pool - 1                   # the largest id there is
    table = mla.TableSlots(jnp.asarray(blocks), bsz, pool)
    rows_of = np.asarray(table.rows())
    assert rows_of.shape == shape[:-1] + (S,)
    assert (rows_of // bsz == np.repeat(blocks, bsz, -1)).all()
    slots = table if form in ("table", "tables") else jnp.asarray(rows_of)

    select = jax.jit(mla._select, static_argnums=2)
    pos, valid, rows = map(np.asarray, select(scores, live, k, slots))
    w_pos, w_valid, _ = map(np.asarray, jax.jit(
        _select_by_stable_sort, static_argnums=2)(scores, live, k, rows_of))
    kk = min(k, S)
    assert pos.shape == valid.shape == rows.shape == (N, kk)
    rows_of = np.broadcast_to(rows_of, (N, S))
    for n in range(N):
        got, want = pos[n][valid[n]], w_pos[n][w_valid[n]]
        assert len(set(got.tolist())) == len(got)
        assert set(got.tolist()) == set(want.tolist()), (name, n)
        assert (rows[n][valid[n]] == rows_of[n][got]).all(), (name, n)
    assert ((rows >= 0) & (rows < pool * bsz)).all()
    assert ((pos >= 0) & (pos < S)).all()

    # nothing is sorted; beside the order bits ONE value moves where
    # position and block id fit 32 bits, else two; never a score
    eqns = jax.make_jaxpr(
        lambda s, m: mla._select(s, m, k, slots))(scores, live).eqns
    assert not [e for e in eqns if e.primitive.name == "sort"]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    fits = (form in ("table", "tables")
            and (S - 1).bit_length() + pool.bit_length() <= 32)
    assert [len(e.invars) for e in calls] == [2 if fits else 3]
    assert all(v.aval.dtype == jnp.int32 for e in calls for v in e.invars)


@pytest.mark.parametrize("N, S, k, shared", [
    (8, 256, 32, False), (3, 200, 7, True), (9, 384, 1, False),
    (2, 128, 128, True), (16, 1000, 999, False)],
    ids=["aligned", "ragged-rows-and-lanes", "k-1", "k-S", "nearly-all"])
def test_compact_top_k_on_raw_order_bits(N, S, k, shared):
    """``select_compact.compact_top_k`` alone, on order bits over the whole
    uint32 range (the top bit, all ones, few distinct values) and shapes it
    has to pad: the k best positions, ties to the lower, in position
    order, and every value array carried along."""
    from dynamo_tpu.engine.select_compact import NOT_TAKEN, compact_top_k
    rng = np.random.default_rng(N * S + k)
    order = rng.integers(0, 1 << 32, (N, S), dtype=np.uint64)
    order[:, ::3] = rng.integers(0, 4, (N, len(range(0, S, 3)))) << 30
    order[rng.random((N, S)) < 0.2] = 0
    order[rng.random((N, S)) < 0.05] = 0xFFFFFFFF
    order[0, k // 2:] = 0                        # fewer live than k
    order = order.astype(np.uint32)
    first = rng.integers(0, 0xFFFFFFFF, (S,) if shared else (N, S),
                         dtype=np.uint32)
    second = rng.integers(-2 ** 31, 2 ** 31, (N, S)).astype(np.int32)
    got1, got2 = map(np.asarray, jax.jit(
        lambda o, a, b: compact_top_k(o, (a, b), k, interpret=True))(
        order, first, second))
    assert got1.dtype == np.uint32 and got2.dtype == np.int32
    first = np.broadcast_to(first, (N, S))
    for n in range(N):
        best = np.lexsort((np.arange(S), -order[n].astype(np.int64)))[:k]
        best = np.sort(best[order[n][best] > 0])
        c = len(best)
        assert (got1[n, :c] == first[n][best]).all(), n
        assert (got2[n, :c] == second[n][best]).all(), n
        assert (got1[n, c:] == NOT_TAKEN).all() and not got2[n, c:].any()


# ---- index scores straight from the pool (engine/index_scores.py, PR 36)

_KJ, _KD, _KM, _KPOOL, _KCHUNK = 8, 128, 12, 64, 4   # heads, lanes, table
_KLAYER = 1                        # the tables point into the second layer


def _key_tables(kind: str, rng):
    """→ (tables [B, M] of blocks inside one layer, seq_lens [B]) at block
    size BS, depth _KCHUNK (a wave is 64 positions, a table 3 waves)."""
    run = lambda lo, n=_KM: np.arange(lo, lo + n)            # noqa: E731
    scattered = lambda n=_KM: rng.permutation(_KPOOL)[:n]    # noqa: E731
    wave, cap = _KCHUNK * BS, _KM * BS
    if kind == "contiguous":
        return np.stack([run(3), run(20), run(40)]), [cap, 150, 70]
    if kind == "fragmented":
        return np.stack([scattered() for _ in range(3)]), [cap, 150, 70]
    if kind == "mixed":          # a document's run, then blocks of its own
        return np.stack([np.r_[run(8, 8), scattered(4)],
                         np.r_[scattered(5), run(30, 7)],
                         np.r_[run(8, 6), run(50, 6)]]), [cap, 177, 190]
    if kind == "len-1":
        return np.stack([run(5), scattered()]), [1, 1]
    if kind == "block-edge":
        return np.stack([run(5), scattered(), run(30), scattered()]), [
            BS, BS + 1, 5 * BS, 5 * BS - 1]
    if kind == "wave-edge":
        return np.stack([run(5), scattered(), run(30), scattered()]), [
            wave, wave + 1, 2 * wave, 2 * wave - 1]
    if kind == "table-end":
        return np.stack([run(5), scattered()]), [cap, cap]
    if kind == "run-ends-at-the-pools-last-block":
        # the whole table is the pool's last run; and a run whose live
        # blocks end at the last block while its wave would pass it: the
        # predicate's in_bounds arm sends that wave down the per-block path
        short = np.r_[run(_KPOOL - 2, 2), np.zeros(_KM - 2, np.int64)]
        return np.stack([run(_KPOOL - _KM), short]), [cap, 2 * BS]
    if kind == "two-sequences-share-a-document":
        doc = run(10, 8)
        return np.stack([np.r_[doc, run(40, 4)], np.r_[doc, scattered(4)],
                         np.r_[doc, run(50, 4)]]), [cap, cap, 129]
    if kind == "empty-rows-and-a-ragged-batch":   # 11 rows: two programs
        lens = rng.integers(0, cap + 1, 11)
        lens[[0, 7, 10]] = 0
        return np.stack([run(3) if i % 2 else scattered()
                         for i in range(11)]), lens
    raise KeyError(kind)


_KEY_CASES = ["contiguous", "fragmented", "mixed", "len-1", "block-edge",
              "wave-edge", "table-end", "run-ends-at-the-pools-last-block",
              "two-sequences-share-a-document",
              "empty-rows-and-a-ragged-batch"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", _KEY_CASES)
def test_index_scores_kernel_equals_the_gathered_form(kind, dtype):
    """``index_scores_pallas`` (interpreted) against ``_keys_by_block`` +
    ``_index_scores`` on the same pool: the scores of every live position,
    0 at every other whatever the pool holds there, and in float32 the
    same selected SETS through ``_select``, exactly. The same at other
    depths (one block a wave, the whole table in one), whichever of the
    waves are one copy and whichever per block."""
    from dynamo_tpu.engine.attention import dma_copy_counts
    from dynamo_tpu.engine.index_scores import index_scores_pallas
    rng = np.random.default_rng(sum(map(ord, kind)))
    blocks, lens = _key_tables(kind, rng)
    lens = np.asarray(lens)
    B = len(lens)
    S = _KM * BS
    layers = _KLAYER + 1
    pool = rng.standard_normal((layers * _KPOOL * BS, _KD)).astype(np.float32)
    tables = jnp.asarray(blocks + _KLAYER * _KPOOL, jnp.int32)
    live = np.arange(S)[None, :] < lens[:, None]
    # rows no live position maps to hold NaN: a wave that is one copy
    # reads its tail from the neighbouring blocks, and nothing of them may
    # reach a score
    owned = np.zeros(layers * _KPOOL * BS, bool)
    rows_of = np.asarray(mla.TableSlots(tables, BS, layers * _KPOOL).rows())
    owned[rows_of[live]] = True
    pool[~owned] = np.nan
    idx = jnp.asarray(pool, dtype)
    qI = jnp.asarray(rng.standard_normal((B, _KJ, _KD)), dtype)
    w = jnp.asarray(rng.standard_normal((B, _KJ)), jnp.float32)

    with jax.default_matmul_precision("highest"):
        keys = mla._keys_by_block(idx, tables, BS).reshape(B, S, _KD)
        want = np.where(live, np.asarray(mla._index_scores(qI, w, keys)), 0)
        got = {c: np.asarray(jax.jit(
            lambda c=c: index_scores_pallas(
                qI, w, idx, tables, jnp.asarray(lens), block_size=BS,
                chunk_blocks=c, interpret=True))())
               for c in (_KCHUNK, 12 if dtype == "float32" else 1)}
    for chunk, scores in got.items():
        assert scores.shape == (B, S) and scores.dtype == np.float32
        assert not scores[~live].any(), (kind, chunk)
        np.testing.assert_allclose(scores, want, rtol=2e-5, atol=2e-5)
    # the case walks the path its name says (the kernel's predicate)
    counts = dma_copy_counts(np.asarray(tables), lens, block_size=BS,
                             pool_blocks=layers * _KPOOL,
                             chunk_blocks=_KCHUNK, dual_stream=False)
    if kind == "contiguous":
        assert counts["copies"] == counts["waves"] > 0
    if kind == "fragmented":     # but a wave of one live block is a run
        assert counts["copies"] > 3 * counts["waves"]
    if kind in ("mixed", "two-sequences-share-a-document",
                "run-ends-at-the-pools-last-block"):
        assert 0 < counts["coalesced_waves"] < counts["waves"]
    if dtype == "float32":
        k = 20
        slots = mla.TableSlots(tables, BS, layers * _KPOOL)
        select = jax.jit(mla._select, static_argnums=2)
        pos, valid, _ = map(np.asarray, select(got[_KCHUNK], live, k, slots))
        w_pos, w_valid, _ = map(np.asarray, select(want, live, k, slots))
        for n in range(B):
            assert (set(pos[n][valid[n]].tolist())
                    == set(w_pos[n][w_valid[n]].tolist())), (kind, n)
            assert valid[n].sum() == min(k, lens[n])


@pytest.mark.parametrize("M, bsz, lanes, itemsize, want", [
    (1088, 16, 128, 2, 64),      # DeepSeek-V3.2 as served: 17 waves of 256 KB
    (1088, 16, 128, 4, 32),
    (1024, 16, 128, 2, 64),
    (16, 16, 128, 2, 16),        # a short table is one wave
    (17, 16, 128, 2, 17),
    (131, 16, 128, 2, 1),        # a prime table longer than a wave
    (1088, 64, 128, 2, 16),
], ids=lambda v: str(v))
def test_key_wave_depth_comes_from_the_rows_width(M, bsz, lanes, itemsize,
                                                  want):
    from dynamo_tpu.engine.index_scores import (KEY_WAVE_BYTES,
                                                 key_wave_blocks)
    depth = key_wave_blocks(M, bsz, lanes, itemsize)
    assert depth == want and M % depth == 0
    assert depth == 1 or depth * bsz * lanes * itemsize <= KEY_WAVE_BYTES


def test_index_scores_kernel_is_for_lane_aligned_geometries_on_the_tpu(
        monkeypatch):
    """Where it runs is read from the input: per-row tables on the TPU at
    128-lane keys and a head count on the sublane tiling. The fixture's
    16-lane keys, and every program on the CPU, keep the gathered form;
    so does a prefill chunk (one table shared by its queries) anywhere."""
    from dynamo_tpu.engine import attention as A
    from dynamo_tpu.engine.index_scores import index_scores_supported
    assert index_scores_supported(64, 128, 16)
    assert not index_scores_supported(4, 16, 16)
    assert not index_scores_supported(64, 128, 8)
    assert not index_scores_supported(12, 128, 16)

    def programs(hf):
        cfg, params, kv, statics = _setup(hf)
        B = 2
        tables = jnp.stack([TABLE, jnp.zeros_like(TABLE)])
        decode = jax.make_jaxpr(lambda: mla.decode_forward(
            params, kv, jnp.zeros((B,), jnp.int32),
            jnp.asarray([40, 0]), tables, statics))()
        prefill = jax.make_jaxpr(lambda: mla.prefill_forward(
            params, kv, jnp.zeros((16,), jnp.int32), TABLE,
            jnp.asarray(0), jnp.asarray(16), statics))()
        return str(decode), str(prefill)

    wide = _hf(index_head_dim=128, index_n_heads=8)
    for on_tpu, hf, in_decode in ((False, wide, False), (True, _hf(), False),
                                  (True, wide, True)):
        monkeypatch.setattr(A, "_on_tpu", lambda on_tpu=on_tpu: on_tpu)
        decode, prefill = programs(hf)
        assert ("index_scores" in decode) == in_decode, (on_tpu, in_decode)
        assert "index_scores" not in prefill


def test_key_wave_counts_are_the_kernels_walk():
    """The loop's ``key_waves`` / ``key_run_waves`` (whole-array numpy on
    the dispatch's tables) against ``attention.dma_copy_counts``, which
    walks slot by slot as the kernel does."""
    from dynamo_tpu.engine.attention import dma_copy_counts
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig.from_hf_config(_hf())
    core = EngineCore(cfg, _engine_cfg(max_num_seqs=6, max_model_len=256,
                                       num_kv_blocks=48),
                      attn_impl="xla", param_dtype=jnp.float32)
    assert core._key_wave_blocks == core.M == 16   # one wave at these sizes
    core._key_wave_blocks = 4
    rng = np.random.default_rng(5)
    M, pool = core.M, 48
    tables = np.stack([np.arange(1, 1 + M), rng.permutation(pool)[:M],
                       np.r_[np.arange(20, 30), rng.permutation(16)[:6]],
                       np.arange(pool - M, pool), np.zeros(M, np.int64),
                       np.arange(5, 5 + M)]).astype(np.int32)
    core._positions[:] = [200, 77, 253, 252, 0, 63]
    riders = [object(), object(), object(), object(), None, object()]
    for K in (1, 3):
        want_w = want_r = 0
        for k in range(1, K + 1):
            lens = np.where([r is not None for r in riders],
                            core._positions + k, 0)
            c = dma_copy_counts(tables, lens, block_size=BS,
                                pool_blocks=pool, chunk_blocks=4,
                                dual_stream=False)
            want_w, want_r = want_w + c["waves"], want_r + c["coalesced_waves"]
        got = core._key_wave_counts(tables, riders, K)
        assert got == {"key_waves": want_w, "key_run_waves": want_r}
        assert 0 < got["key_run_waves"] < got["key_waves"]
    plain = EngineCore(dataclasses.replace(cfg, index_topk=0,
                                           model_type="deepseek_v3"),
                       _engine_cfg(), attn_impl="xla",
                       param_dtype=jnp.float32)
    assert plain._key_wave_counts(tables[:2], [object()] * 2, 1) == {}


def test_context_inside_topk_equals_dense_mla():
    """ctx <= index_topk: every live row is selected, and the result is the
    dense path's (the same parameters served with no indexer)."""
    cfg, params, kv, statics = _setup(_hf(index_topk=64))
    dense_cfg = dataclasses.replace(cfg, index_topk=0)
    dense_statics = ModelStatics(cfg=dense_cfg, block_size=BS,
                                 attn_impl="xla")
    dense_kv = mla.init_kv_cache(dense_cfg, NUM_BLOCKS, BS,
                                 dtype=jnp.float32)
    seq = _tokens(cfg, 44)
    a, kv = _prefill(params, kv, statics, seq[:40])
    b, dense_kv = _prefill(params, dense_kv, dense_statics, seq[:40])
    assert _err_std(a, b) < TOL_STD
    for pos in range(40, 44):
        a, kv = _decode(params, kv, statics, seq[pos], pos)
        b, dense_kv = _decode(params, dense_kv, dense_statics, seq[pos], pos)
        assert _err_std(a, b) < TOL_STD
    np.testing.assert_allclose(np.asarray(kv["kv"]),
                               np.asarray(dense_kv["kv"]), atol=1e-5)


def test_chunked_prefill_equals_whole_prefill_in_both_caches():
    cfg, params, kv, statics = _setup(_hf())
    seq = _tokens(cfg, 56)
    whole_logits, whole = _prefill(params, kv, statics, seq)
    kv2 = mla.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    for lo in range(0, 56, 16):
        logits, kv2 = _prefill(params, kv2, statics, seq[lo:lo + 16],
                               start=lo, pad_to=16)
    assert _err_std(logits, whole_logits) < TOL_STD
    for name in ("kv", "idx"):
        np.testing.assert_allclose(np.asarray(kv2[name]),
                                   np.asarray(whole[name]), atol=1e-5)


# a chunk of four query blocks over a cached prefix that ends inside a block
LIVE_TQ, LIVE_T, LIVE_START = 8, 32, 24


@pytest.fixture(scope="module")
def live_walk(chk):
    """One compiled 32-row prefill chunk at a query block of 8 (four
    blocks), ``true_len`` traced, over a 24-token cached prefix: the
    product's walk (under the check's tap on ``_select``) and the walk over
    all four blocks, the rule before PR 48, rebuilt from the same per-block
    body by fixing the bound; and ``_sparse_chunk`` alone, both ways, on
    random operands."""
    # the programs of the tests above are unmapped here and those of this
    # file at the end: the compiled code a worker's process holds is tens of
    # thousands of memory mappings by now, of the 65,530 it may hold, and
    # past them a later compile is a segmentation fault (tests/test_mla.py
    # ``executables_dropped``)
    jax.clear_caches()
    cfg, params, kv, statics = _setup(_hf())
    seq = _tokens(cfg, LIVE_START + LIVE_T, seed=11)
    _, kv = _prefill(params, kv, statics, seq[:LIVE_START])
    rng = np.random.default_rng(5)
    H, NTOK = cfg.num_heads, NUM_BLOCKS * BS

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    operands = dict(
        q_nope=rand(LIVE_T, H, cfg.qk_nope_head_dim),
        q_pe=rand(LIVE_T, H, cfg.qk_rope_head_dim),
        w_k=rand(H, cfg.kv_lora_rank, cfg.qk_nope_head_dim),
        index=(rand(LIVE_T, cfg.index_n_heads, cfg.index_head_dim),
               rand(LIVE_T, cfg.index_n_heads),
               rand(NTOK, cfg.index_head_dim)),
        kv_flat=rand(NTOK, cfg.kv_lora_rank + cfg.qk_rope_head_dim))

    def forward(tokens, true_len):
        with jax.default_matmul_precision("highest"):
            return mla.prefill_forward(params, kv, tokens, TABLE,
                                       jnp.asarray(LIVE_START), true_len,
                                       statics)

    def chunk(true_len):
        return mla._sparse_chunk(
            table_l=TABLE, positions=LIVE_START + jnp.arange(LIVE_T),
            seq_len=LIVE_START + true_len, cfg=cfg, bsz=BS, scale=0.1,
            **operands)

    def all_blocks(T, true_len):
        return LIVE_T // LIVE_TQ, LIVE_T // LIVE_TQ

    full = jnp.asarray(LIVE_T)
    with pytest.MonkeyPatch.context() as patch, chk.Tap(mla) as tap:
        patch.setattr(mla, "DSA_QUERY_BLOCK", LIVE_TQ)
        walks = {"forward": jax.jit(forward), "chunk": jax.jit(chunk)}
        walks["forward"](jnp.zeros(LIVE_T, jnp.int32), full)  # traced here
        walks["chunk"](full)
        patch.setattr(mla, "sparse_query_blocks", all_blocks)
        # (a second jit of one function would share the first's trace)
        walks["forward_all"] = jax.jit(lambda *a: forward(*a))
        walks["chunk_all"] = jax.jit(lambda n: chunk(n))
        walks["forward_all"](jnp.zeros(LIVE_T, jnp.int32), full)
        walks["chunk_all"](full)
    # the compiled programs keep the tap's callback and the patched bound
    tap.take(1)
    yield cfg, seq, tap, walks
    jax.clear_caches()


@pytest.mark.parametrize("true_len", [
    1, LIVE_TQ - 1, LIVE_TQ, LIVE_TQ + 1, LIVE_T - LIVE_TQ, LIVE_T - 1,
    LIVE_T])
def test_a_chunk_walks_its_live_query_blocks_only(live_walk, true_len):
    """A partly filled chunk selects and reads for the query blocks that
    hold a live row, and for no other: ``_select`` runs ceil(true_len / TQ)
    times a layer; the last token's logits and the live rows of both caches
    are bit-equal to the walk over every block; in ``_sparse_chunk``'s own
    result the live rows are bit-equal too and the rows of the blocks that
    did not run are exactly zero."""
    cfg, seq, tap, walks = live_walk
    n_run = -(-true_len // LIVE_TQ)
    # the host's count, at the product's block of 32 and four times the rows
    assert mla.sparse_query_blocks(4 * LIVE_T, 4 * true_len) == (4, n_run)
    tokens = np.zeros(LIVE_T, np.int32)
    tokens[:true_len] = seq[LIVE_START:LIVE_START + true_len]
    logits, kv = walks["forward"](jnp.asarray(tokens),
                                  jnp.asarray(true_len))
    calls = tap.take(cfg.num_layers)       # [L, queries selected for, k]
    assert calls.shape[1] == n_run * LIVE_TQ
    want_logits, want_kv = walks["forward_all"](jnp.asarray(tokens),
                                                jnp.asarray(true_len))
    tap.take(cfg.num_layers)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    live = np.arange(LIVE_START + true_len)
    rows = np.asarray(TABLE)[live // BS] * BS + live % BS
    for name in ("kv", "idx"):
        np.testing.assert_array_equal(np.asarray(kv[name])[:, rows],
                                      np.asarray(want_kv[name])[:, rows])
    ctx = np.asarray(walks["chunk"](jnp.asarray(true_len)))
    want_ctx = np.asarray(walks["chunk_all"](jnp.asarray(true_len)))
    tap.take(1)
    np.testing.assert_array_equal(ctx[:true_len], want_ctx[:true_len])
    assert ctx[:true_len].any(axis=(1, 2)).all()      # every live row ran
    assert not ctx[n_run * LIVE_TQ:].any()
    assert want_ctx[n_run * LIVE_TQ:].any() or n_run == LIVE_T // LIVE_TQ


@pytest.mark.parametrize("T, true_len, want", [
    (256, 160, (8, 5)), (256, 256, (8, 8)), (256, 1, (8, 1)),
    (256, 0, (8, 0)), (256, 225, (8, 8)), (64, 33, (2, 2)),
    (16, 9, (1, 1)), (48, 17, (3, 2))])
def test_sparse_query_blocks_is_one_arithmetic_on_host_and_device(
        T, true_len, want):
    assert mla.sparse_query_blocks(T, true_len) == want
    blocks, run = jax.jit(mla.sparse_query_blocks, static_argnums=0)(
        T, jnp.asarray(true_len, jnp.int32))
    assert (int(blocks), int(run)) == want


def test_block_moves_carry_both_rows():
    """The defrag copy (block_copy.move_blocks) and the block gather /
    scatter move every array of the pool: latent rows and index keys."""
    from dynamo_tpu.engine.block_copy import (gather_blocks, move_blocks,
                                              scatter_blocks)
    cfg, params, kv, statics = _setup(_hf())
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 40))
    before = {k: np.asarray(v) for k, v in kv.items()}
    picked = gather_blocks(kv, jnp.asarray([1, 2], jnp.int32), BS)
    assert set(picked) == {"kv", "idx"}
    moved = move_blocks(kv, [1, 2, 3], [9, 10, 11], BS)
    for name, arr in moved.items():
        arr = np.asarray(arr)
        assert np.abs(before[name][:, BS:4 * BS]).max() > 0
        np.testing.assert_array_equal(arr[:, 9 * BS:12 * BS],
                                      before[name][:, BS:4 * BS])
    back = scatter_blocks(moved, jnp.asarray([12, 13], jnp.int32),
                          {k: jnp.asarray(v) for k, v in picked.items()}, BS)
    for name, arr in back.items():
        np.testing.assert_array_equal(np.asarray(arr)[:, 12 * BS:14 * BS],
                                      before[name][:, BS:3 * BS])


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Guide "model-configs" section 4: what the 2 shares of the 8 experts
    give, with the shared expert counted once, adds up to the uncut layer's
    output — in the engine, and against the uncut reference."""
    hf_whole = _hf(n_routed_experts=8)
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
        parts = []
        for share in range(2):
            cfg = ModelConfig.from_hf_config(_hf(expert_share_index=share))
            held = slice(4 * share, 4 * share + 4)
            lp_share = dict(lp, **{n: lp[n][held] for n in
                                   ("moe_gate", "moe_up", "moe_down")})
            parts.append(mla._moe_mlp(m, lp_share, cfg))
            # the reference, given the same share, gives the same part
            fam = ref.family(_hf(expert_share_index=share))
            want = ref.moe_block(fam)(m, lp_share)
            assert _err_std(parts[-1], want) < TOL_STD
        total = parts[0] + parts[1] - shared_only
        uncut = ref.moe_block(ref.family(hf_whole))(m, lp)
    assert _err_std(whole, uncut) < TOL_STD
    assert _err_std(total, uncut) < TOL_STD
    # each share really leaves something out
    assert _err_std(parts[0], uncut) > 0.05


def _engine_cfg(**over) -> EngineConfig:
    base = dict(max_model_len=128, kv_block_size=BS, num_kv_blocks=64,
                max_num_seqs=2, prefill_buckets=[32, 64])
    return EngineConfig(**dict(base, **over))


@pytest.mark.parametrize("over, match", [
    ({"ragged_dispatch": True}, "--ragged"),
    ({"spec_k": 2}, "--spec-k"),
    ({"kv_quantization": "int8"}, "--kv-quantization"),
    ({"host_kv_blocks": 8}, "--host-kv-blocks"),
    ({"tp": 2}, "meshes"),
    ({"ep": 2}, "meshes"),
])
def test_engine_refuses_what_cannot_carry_the_index_keys(over, match):
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig.from_hf_config(_hf())
    with pytest.raises(NotImplementedError, match=match):
        EngineCore(cfg, _engine_cfg(**over), attn_impl="xla",
                   param_dtype=jnp.float32)


def test_engine_refuses_a_mesh_and_the_forwards_that_do_not_select():
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.parallel.sharding import make_mesh
    cfg, params, kv, statics = _setup(_hf())
    with pytest.raises(NotImplementedError, match="meshes"):
        EngineCore(cfg, _engine_cfg(), attn_impl="xla",
                   param_dtype=jnp.float32, mesh=make_mesh(tp=2))
    tokens = jnp.zeros((8,), jnp.int32)
    with pytest.raises(NotImplementedError, match="ragged"):
        mla.ragged_forward(params, kv, tokens, tokens, TABLE[None],
                           tokens, tokens[:1], tokens[:1], tokens[:1],
                           statics)
    with pytest.raises(NotImplementedError, match="sequence-parallel"):
        mla.prefill_forward_sp(params, kv, tokens, TABLE, jnp.asarray(8),
                               statics, None)
    with pytest.raises(NotImplementedError, match="index-key cache"):
        mla.init_kv_cache(cfg, 4, BS, quantization="int8")


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


@pytest.mark.asyncio
async def test_engine_serves_with_prefix_reuse_and_chunks(ref):
    """EngineCore end to end: a prompt served whole, then a second prompt
    that shares its first 48 tokens served by a prefix-cache hit and a
    chunked suffix, give the tokens and logprobs of a cold engine and of
    the reference; the flight records carry the attention's two counters;
    neither disagg plane is accepted."""
    from dynamo_tpu.engine.core import EngineCore, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    hf = _hf()
    cfg = ModelConfig.from_hf_config(hf)
    params = mla.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)

    def engine(**over):
        return EngineCore(cfg, _engine_cfg(**over), params=dict(params),
                          attn_impl="xla", param_dtype=jnp.float32)

    shared = _tokens(cfg, 48, seed=7).tolist()
    first = shared + _tokens(cfg, 9, seed=8).tolist()
    second = shared + _tokens(cfg, 21, seed=9).tolist()
    warm = engine(prefill_chunk=16, prefill_buckets=[16, 64])
    cold = engine()
    try:
        with jax.default_matmul_precision("highest"):
            await _serve(warm, "a", first)
            toks, lps, req = await _serve(warm, "b", second)
            want_toks, want_lps, cold_req = await _serve(cold, "c", second)
        assert req.prefix_hit_tokens == 48 and cold_req.prefix_hit_tokens == 0
        assert toks == want_toks
        np.testing.assert_allclose(lps, want_lps, atol=1e-4)
        # ... and the reference's: logprob of each served token
        logits = ref.logits_for(warm.params, hf, second + toks[:-1],
                                len(toks))
        for tok, lp, row in zip(toks, lps, logits):
            row = row.astype(np.float64)
            ref_lp = row[tok] - (row.max() + np.log(
                np.exp(row - row.max()).sum()))
            assert abs(ref_lp - lp) < TOL_STD * row.std() * 10
        decode = [r for r in warm.flight.dump() if r["kind"] == "decode"]
        assert decode and all(
            0 < r["sel_tokens"] < r["ctx_tokens"] for r in decode
            if r["batch_fill"])
        one = [r for r in decode if r["batch_fill"] == 1][-1]
        assert one["sel_tokens"] == cfg.index_topk * one["emitted"]
        # the index keys' waves: one a slot at these sizes (a table of 8
        # blocks is one wave), each a run (a prompt's blocks are one
        # allocation)
        assert all(r["key_waves"] == r["key_run_waves"] == r["batch_fill"]
                   for r in decode)
        with pytest.raises(NotImplementedError, match="hand-off"):
            await warm.submit(EngineRequest(
                rid="d", prompt=first, sampling=SlotSampling(temperature=0.0),
                max_new_tokens=2, eos_ids=frozenset(), handoff=object()))
        with pytest.raises(NotImplementedError, match="fabric"):
            warm.attach_kv_fabric(object())
    finally:
        await warm.stop()
        await cold.stop()


@pytest.mark.asyncio
async def test_prefill_records_count_the_query_blocks_that_ran():
    """Every ``prefill`` flight record of a model with an indexer says how
    many query blocks its chunks hold and how many the walk ran (a layer's):
    a cold prompt in chunks, summed over them, then a hit whose suffix is
    one partly filled bucket; the counts are the model's own arithmetic and
    the benchmark's reader gives their share."""
    from dynamo_tpu.engine.core import EngineCore
    spec = importlib.util.spec_from_file_location(
        "reader_dsa_prefill_blocks_run_pct", os.path.join(
            BENCH, "layer_metrics", "dsa.prefill_blocks_run_pct.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    cfg = ModelConfig.from_hf_config(_hf())
    core = EngineCore(cfg, _engine_cfg(max_model_len=256, prefill_chunk=64,
                                       prefill_buckets=[64]),
                      attn_impl="xla", param_dtype=jnp.float32)
    cold = _tokens(cfg, 150, seed=7).tolist()
    hit = cold[:144] + _tokens(cfg, 10, seed=8).tolist()
    try:
        await _serve(core, "a", cold, n=2)
        _, _, req = await _serve(core, "b", hit, n=2)
        assert req.prefix_hit_tokens == 144 and req.prefill_chunks == 1
        a, b = [r for r in core.flight.dump() if r["kind"] == "prefill"]
        # 64 + 64 + 22 rows: three chunks of two blocks, the last runs one
        assert (a["dsa_blocks"], a["dsa_blocks_run"]) == (6, 5)
        assert (b["dsa_blocks"], b["dsa_blocks_run"]) == (2, 1)
        for rec, pieces in ((a, (64, 64, 22)), (b, (10,))):
            counts = [mla.sparse_query_blocks(64, n) for n in pieces]
            assert rec["dsa_blocks"] == sum(c[0] for c in counts)
            assert rec["dsa_blocks_run"] == sum(c[1] for c in counts)
        assert reader.read({"flight": [a, b]}) == pytest.approx(75.0)
        assert reader.read({"flight": [dict(a, kind="decode")]}) is None
        assert reader.read({"flight": [{"kind": "prefill",
                                        "prompt": 9}]}) is None
    finally:
        await core.stop()


@pytest.mark.asyncio
async def test_flight_records_count_context_on_a_model_with_no_indexer():
    from dynamo_tpu.engine.core import EngineCore
    cfg = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                      max_position_embeddings=256)
    core = EngineCore(cfg, _engine_cfg(), attn_impl="xla",
                      param_dtype=jnp.float32)
    try:
        await _serve(core, "l", list(range(3, 33)), n=5)
        decode = [r for r in core.flight.dump() if r["kind"] == "decode"]
        assert decode and all(r["sel_tokens"] == r["ctx_tokens"]
                              for r in decode)
        assert not any("key_waves" in r for r in decode)
        prefill = [r for r in core.flight.dump() if r["kind"] == "prefill"]
        assert prefill and not any(
            "dsa_blocks" in r or "dsa_blocks_run" in r for r in prefill)
        # 30 prompt tokens: the first decode step reads a context of 31
        assert decode[0]["ctx_tokens"] == 31
    finally:
        await core.stop()


def test_the_reference_blocked_equals_the_reference_whole(ref, monkeypatch):
    """On the chip the reference works a 16k-token prompt in blocks of
    queries, groups of heads and slices of the dense MLP; at these sizes it
    works whole. Both give the same logits."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    seq = _tokens(cfg, 54).tolist()
    whole = ref.logits_for(params, hf, seq, 5)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "HEAD_GROUP", 2)
    monkeypatch.setattr(ref, "MLP_SLICE", 32)
    assert _err_std(ref.logits_for(params, hf, seq, 5), whole) < TOL_STD


def test_seeded_weights_of_a_sparse_attention_model():
    """llama.seeded_std: with an indexer the embedding and the projections
    that write into the stream take SPARSE_SEEDED's scales; with one chip's
    share of the experts held and no indexer (since PR 37) the embedding and
    the routed experts' down-projection take SHARE_SEEDED's; every other
    matrix, and every matrix of a model with neither, fan_in^-0.5."""
    from dynamo_tpu.engine.models import llama
    cfg = ModelConfig.from_hf_config(_hf(vocab_size=4096))
    share = dataclasses.replace(cfg, index_topk=0)
    assert share.num_experts_total > 0
    plain = dataclasses.replace(share, num_experts_total=0)
    key = jax.random.PRNGKey(0)

    def std(c, name, shape):
        return float(jnp.std(llama.init_one_param(c, name, shape, key,
                                                  jnp.float32)))

    usual = 256 ** -0.5
    rules = ((cfg, llama.SPARSE_SEEDED), (share, llama.SHARE_SEEDED),
             (plain, {}))
    assert set(llama.SHARE_SEEDED) == {"embed", "moe_down"}
    for c, want in rules:
        embed = want.get("embed", 4096 ** -0.5)
        assert abs(std(c, "embed", (4096, 64)) / embed - 1) < 0.05
        for name, shape, key_ in (
                ("layers.wo", (2, 256, 64), "wo"),
                ("layers.dense_down", (2, 256, 64), "down"),
                ("layers.sh_down", (2, 256, 64), "down"),
                ("layers.moe_down", (2, 4, 256, 64), "moe_down")):
            factor = want.get(key_, 1.0)
            assert abs(std(c, name, shape) / usual / factor - 1) < 0.05
        assert abs(std(c, "layers.wq_b", (2, 256, 64)) / usual - 1) < 0.05


def test_the_check_forces_the_engines_selection_on_the_reference(chk, capsys):
    """benchmark/references/deepseek_v32_check.py on the fixture, in bf16 as
    served: the tap carries the engine's sets out of its compiled programs;
    with them forced on the reference's attention the engine sits well
    inside the tolerance, the reference's own selection shares nearly every
    key with the engine's, and a wrong selection does not."""
    chk.main(["--fixture", "tiny-deepseek-v32", "--tokens", "80",
              "--only", "recent_window"])
    lines = [json.loads(line[len("CHECK "):])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("CHECK ")]
    readings = {r["reading"]: r for r in lines if "reading" in r}
    overlap = {r["overlap_with_engine_pct"]: r for r in lines
               if "overlap_with_engine_pct" in r}
    assert mla._select.__name__ == "_select"         # the tap is gone
    assert readings["forced"]["ok"]
    assert readings["forced"]["logprob_err_std"] < 0.1
    assert not readings["recent_window"]["ok"]
    assert min(overlap["reference"]["per_layer_mean"]) > 95.0
    assert max(overlap["recent_window"]["per_layer_mean"]) < 50.0
    assert max(overlap["no_selection"]["per_layer_mean"]) == 100.0
