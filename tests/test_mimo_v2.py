"""mimo_v2 (MiMo-V2.5) on models/mimo.py: grouped-query attention of two
geometries in one model (kv heads 2 / 4 here, keys of 48 and values of 16
lanes), a learned sink in the window layers' softmax, the window layers' rows
as blocks of a second pool, held to the benchmark's plain reference
(benchmark/references/mimo_v2.py) at tiny widths on the CPU with seeded
random weights (docs/hybrid_cache.md part three).

Engine and reference both compute in float32 here (float32 parameters and
pools, ``jax.default_matmul_precision("highest")``): what separates them is
the order of float32 sums. ``TOL_STD`` = 1e-4 fails anything else.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import attention
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.models import llama, mimo, mla
from dynamo_tpu.engine.models.llama import ModelStatics
from dynamo_tpu.llm.kv.blocks import TokenBlockSequence
# the engine harness is the window-pool family's, whatever the rows
from tests.test_dots3_note import (_engine, _engine_cfg,
                                   _held_to_the_reference, _serve)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BS = 16
NUM_BLOCKS = 16
TOL_STD = 1e-4
TABLE = jnp.arange(1, 9, dtype=jnp.int32)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
EXTRAS = ("source", "reduced", "assumed", "deployment", "reference",
          "memory_analysis")


@pytest.fixture(scope="module")
def ref():
    """benchmark/references/mimo_v2.py (it imports the benchmark's
    ``reference`` module by its bare name)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_mimo_v2",
            os.path.join(BENCH, "references", "mimo_v2.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        yield module
    finally:
        sys.path.remove(BENCH)


def _hf(**over) -> dict:
    """The fixture: 7 layers F | S S S S F S, window 21 (two blocks of 16
    and a ring of three), 4 of 8 experts held."""
    with open(os.path.join(BENCH, "fixtures", "tiny-mimo-v2.json")) as f:
        hf = json.load(f)
    for key in EXTRAS:
        hf.pop(key, None)
    return dict(hf, **over)


def _setup(hf: dict, seed: int = 1):
    cfg = ModelConfig.from_hf_config(hf)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed),
                               dtype=jnp.float32)
    kv = llama.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")
    return cfg, params, kv, statics


_PREFILL = jax.jit(llama.prefill_forward, static_argnums=(6,))
_DECODE = jax.jit(llama.decode_forward, static_argnums=(5,))


def _tokens(cfg, n: int, seed: int = 3) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=n)


def _prefill(params, kv, statics, tokens, start=0, pad_to=64, table=TABLE):
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return _PREFILL(
            params, kv, jnp.asarray(padded), table, jnp.asarray(start),
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
                    if row["name"] == "MiMo-V2.5")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_from_hf_config_parses_the_catalog_row_whole():
    cfg = ModelConfig.from_hf_config(_catalog_row())
    assert (cfg.num_layers, cfg.first_k_dense, cfg.hidden_size) == (
        48, 1, 4096)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.v_head_dim) == (64, 4, 192, 128)
    assert (cfg.swa_num_heads, cfg.swa_num_kv_heads, cfg.swa_head_dim,
            cfg.swa_v_head_dim, cfg.swa_window) == (64, 8, 192, 128, 128)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.rotary_dim) == (
        1e7, 1e4, 64)
    assert cfg.swa_sink and cfg.value_scale == 0.707
    kinds = mla.layer_kinds(cfg)
    assert (kinds.count("F"), kinds.count("S")) == (9, 39)
    assert mla.layer_plan(cfg) == (1, tuple("SSSSFS"), 7, tuple("SSSSF"))
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_routing,
            cfg.intermediate_size, cfg.dense_intermediate_size,
            cfg.routed_scaling) == (256, 8, "sigmoid_noaux", 2048, 16384, 1.0)
    # the whole model's weights: the published count
    total = sum(int(np.prod(s)) for s in llama.param_shapes(cfg).values())
    assert 308e9 < total < 311e9


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_benchmarks_configuration_keeps_every_published_width():
    with open(os.path.join(BENCH, "configs", "mimo-v2.5.json")) as f:
        config = json.load(f)
    row = _catalog_row()
    differ = {k for k, v in row.items() if config.get(k, "absent") != v}
    assert differ == set(config["reduced"])
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in config.items() if k not in EXTRAS})
    assert "".join(mla.layer_kinds(cfg)) == "FSSSSFSSSSSFS"
    assert (cfg.num_experts, cfg.num_experts_total,
            cfg.router_width) == (16, 256, 256)


@pytest.mark.parametrize("over, match", [
    ({"hybrid_layer_pattern": [0, 1, 1]}, "hybrid_layer_pattern names 3"),
    ({"moe_layer_freq": [0, 1]}, "moe_layer_freq names 2"),
    ({"moe_layer_freq": [1] * 13}, "leading layer"),
    ({"hybrid_layer_pattern": [1] * 13}, "leading layer"),
    ({"hybrid_layer_pattern": [0] * 13}, "no window layer"),
    ({"moe_layer_freq": [0, 1, 0] + [1] * 10}, "dense layer behind"),
    ({"add_full_attention_sink_bias": True}, "sink on the full layers"),
    ({"n_shared_experts": 1}, "shared expert"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"scoring_func": "softmax"}, "routing other than sigmoid"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"attention_bias": True}, "attention_bias"),
    ({"swa_head_dim": 32}, "swa_head_dim"),
    ({"num_key_value_heads": 3}, "do not divide"),
    ({"n_routed_experts": 3}, "not a share"),
    ({"expert_share_index": 2}, "outside it"),
    ({"v_head_dim": None}, "needs v_head_dim"),
])
def test_from_hf_config_refuses_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(_hf(**over))


def test_an_unknown_expert_family_is_still_refused_by_name():
    with pytest.raises(ValueError, match="mimo_v3"):
        ModelConfig.from_hf_config(_hf(model_type="mimo_v3"))


def test_a_cut_depth_keeps_the_leading_entries():
    cfg = ModelConfig.from_hf_config(_hf(num_hidden_layers=4))
    assert mla.layer_kinds(cfg) == ("F", "S", "S", "S")
    assert cfg.first_k_dense == 1


def test_two_stacks_and_two_pools():
    cfg, params, kv, _ = _setup(_hf())
    assert params["layers.wk"].shape == (2, 64, 2 * 48)
    assert params["layers.wv"].shape == (2, 64, 2 * 16)
    assert params["layers.swa_wk"].shape == (5, 64, 4 * 48)
    assert params["layers.swa_wv"].shape == (5, 64, 4 * 16)
    assert params["layers.wo"].shape == (2, 4 * 16, 64)
    assert params["layers.swa_sink"].shape == (5, 4)
    assert params["layers.swa_sink"].dtype == jnp.float32
    assert params["layers.dense_gate"].shape == (1, 64, 128)
    assert params["layers.moe_gate"].shape == (6, 4, 64, 32)
    assert params["layers.router"].shape == (6, 64, 8)
    assert {k: v.shape for k, v in kv.items()} == {
        "k": (2, NUM_BLOCKS * BS, 96), "v": (2, NUM_BLOCKS * BS, 32),
        "win_k": (5, NUM_BLOCKS * BS, 192), "win_v": (5, NUM_BLOCKS * BS, 64)}
    layout = llama.cache_layout(cfg, BS, 2)
    assert (layout.row_bytes, layout.window_row_bytes) == (256, 512)
    assert (layout.paged_layers, layout.window_layers, layout.ring_blocks,
            layout.window_reach_blocks) == (2, 5, 3, 2)
    assert llama.cache_layout(ModelConfig.from_hf_config(
        {"model_type": "llama", "hidden_size": 64, "num_attention_heads": 4,
         "num_hidden_layers": 2, "intermediate_size": 128,
         "vocab_size": 64}), BS) is None


def test_quantised_weights_keep_the_sinks_and_take_the_new_matmuls():
    from dynamo_tpu.engine.quant import init_params_quantized
    cfg = ModelConfig.from_hf_config(_hf())
    params = llama.fuse_stacked_matmuls(
        dict(init_params_quantized(cfg, jax.random.PRNGKey(0))), cfg)
    assert params["layers.swa_sink"].dtype == jnp.float32
    for name in ("layers.wqkv", "layers.swa_wqkv", "layers.wo",
                 "layers.swa_wo", "layers.moe_gateup"):
        assert params[name].q.dtype == jnp.int8, name
    assert params["layers.swa_wqkv"].q.shape == (5, 64, 4 * 48 + 4 * 48
                                                 + 4 * 16)
    assert not hasattr(params["layers.router"], "q")


def test_seeded_weights_follow_the_rule_of_this_family():
    cfg, params, _, _ = _setup(_hf(), seed=4)
    rule = llama.GQA_MIXED_SEEDED
    for name, fan_in, factor in (("layers.wq", 64, rule["wq"]),
                                 ("layers.swa_wk", 64, rule["wk"]),
                                 ("layers.swa_wo", 64, rule["swa_wo"]),
                                 ("layers.moe_down", 32, rule["moe_down"])):
        assert llama.seeded_std(cfg, name, fan_in) == pytest.approx(
            factor * fan_in ** -0.5)
        assert float(params[name].std()) == pytest.approx(
            factor * fan_in ** -0.5, rel=0.1)
    sinks = np.asarray(params["layers.swa_sink"])
    assert abs(sinks.mean() - llama.SINK_SEEDED[0]) < 1.0
    assert sinks.std() > 0.3
    assert float(jnp.abs(params["layers.router_bias"]).max()) > 0


def test_a_checkpoint_is_stacked_by_kind(tmp_path):
    """weights.load_llama_params under the assumed tensor names: a fused
    q | k | v tensor a layer at the sizes of the layer's kind, the sinks of
    the window layers in float32, layer 0's dense MLP, and of all the
    published experts the share held here."""
    from safetensors.numpy import save_file
    from dynamo_tpu.engine.weights import load_llama_params, save_hf_style
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    whole = llama.init_params(
        ModelConfig.from_hf_config(_hf(n_routed_experts=8)),
        jax.random.PRNGKey(9), dtype=jnp.float32)
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    out = {"model.embed_tokens.weight": p["embed"],
           "model.norm.weight": p["final_norm"],
           "lm_head.weight": p["lm_head"].T}
    seen = {"F": 0, "S": 0}
    for i, kind in enumerate(mla.layer_kinds(cfg)):
        at, pre = seen[kind], "swa_" if kind == "S" else ""
        seen[kind] += 1
        lay = f"model.layers.{i}."
        out[lay + "input_layernorm.weight"] = p["layers.ln1"][i]
        out[lay + "post_attention_layernorm.weight"] = p["layers.ln2"][i]
        out[lay + "self_attn.qkv_proj.weight"] = np.concatenate(
            [p[f"layers.{pre}{n}"][at].T for n in ("wq", "wk", "wv")])
        out[lay + "self_attn.o_proj.weight"] = p[f"layers.{pre}wo"][at].T
        if kind == "S":
            out[lay + "self_attn.attention_sink_bias"] = p[
                "layers.swa_sink"][at]
        if i == 0:
            for n in ("gate", "up", "down"):
                out[lay + f"mlp.{n}_proj.weight"] = p[
                    f"layers.dense_{n}"][0].T
            continue
        out[lay + "mlp.gate.weight"] = p["layers.router"][i - 1].T
        out[lay + "mlp.gate.e_score_correction_bias"] = p[
            "layers.router_bias"][i - 1]
        for e in range(8):                 # all the published experts
            for n in ("gate", "up", "down"):
                src = (p if e < 4 else whole)[f"layers.moe_{n}"]
                out[lay + f"mlp.experts.{e}.{n}_proj.weight"] = np.asarray(
                    src[i - 1][e], np.float32).T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    loaded = load_llama_params(str(tmp_path), cfg, dtype=jnp.float32)
    assert set(loaded) == set(params)
    for name, want in params.items():
        assert loaded[name].dtype == want.dtype, name
        np.testing.assert_array_equal(np.asarray(loaded[name]),
                                      np.asarray(want), err_msg=name)
    half = load_llama_params(str(tmp_path), cfg, dtype=jnp.bfloat16)
    assert half["layers.swa_sink"].dtype == jnp.float32
    assert half["layers.swa_wq"].dtype == jnp.bfloat16
    with pytest.raises(NotImplementedError, match="mimo_v2"):
        save_hf_style(params, cfg, str(tmp_path / "out"))


# ------------------------------------------------------ the model's reads

@pytest.mark.parametrize("n", [10, 21, 22, 40, 60])
def test_prefill_and_decode_match_the_reference(ref, n):
    """Contexts below, at and past the window (21): the prefill's last
    logits, then four decode steps, through both pools."""
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
    """100 decode steps from a context of 20: the ring of three blocks is
    gone round twice, the window crossed on the way."""
    hf = _hf()
    cfg, params, kv, statics = _setup(hf)
    seq = _tokens(cfg, 120, seed=5)
    _, kv = _prefill(params, kv, statics, seq[:20])
    for pos in range(20, 120):
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
        assert _err_std(logits, want[0]) > 100 * TOL_STD, broken
    for issue_name, name in ref.ALIASES.items():
        np.testing.assert_array_equal(
            ref.logits_for(params, hf, seq, 1, broken=issue_name),
            ref.logits_for(params, hf, seq, 1, broken=name))
    assert set(ref.BREAKAGES) - set(ref.breakages_for(hf)) == set(ref.FINE)
    for control in ref.CONTROLS:
        want = ref.logits_for(params, hf, seq, 1, broken=control)
        assert _err_std(logits, want[0]) > 100 * TOL_STD, control


def test_chunked_prefill_equals_whole_prefill_in_every_pool():
    cfg, params, kv, statics = _setup(_hf())
    seq = _tokens(cfg, 56)
    whole_logits, whole = _prefill(params, kv, statics, seq)
    kv2 = llama.init_kv_cache(cfg, NUM_BLOCKS, BS, dtype=jnp.float32)
    for lo in range(0, 56, 16):
        logits, kv2 = _prefill(params, kv2, statics, seq[lo:lo + 16],
                               start=lo, pad_to=16)
    assert _err_std(logits, whole_logits) < TOL_STD
    for name in ("k", "v", "win_k", "win_v"):
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
    logits, kv = _prefill(params, kv, statics, seq[:40],
                          table=jnp.concatenate([TABLE, jnp.asarray(win)]))
    want = ref.logits_for(params, hf, seq, 6)
    assert _err_std(logits, want[0]) < TOL_STD
    # the paged pool's block 4 holds none of the window's rows, 12 does
    assert float(jnp.abs(kv["win_k"][:, 4 * BS:5 * BS]).max()) == 0.0
    assert float(jnp.abs(kv["win_k"][:, 12 * BS:13 * BS]).max()) > 0.0
    assert float(jnp.abs(kv["k"][:, 12 * BS:13 * BS]).max()) == 0.0
    tables = np.zeros((2, 8 + 3), np.int32)
    tables[0, :8] = np.asarray(TABLE)
    for b, bid in enumerate(win[:3]):
        tables[0, 8 + b % 3] = bid              # logical block b at b % R
    with jax.default_matmul_precision("highest"):
        for i in range(5):
            pos = 40 + i
            logits, kv = _DECODE(
                params, kv, jnp.asarray([int(seq[pos]), 0], jnp.int32),
                jnp.asarray([pos, 0], jnp.int32), jnp.asarray(tables),
                statics)
            assert _err_std(logits[0], want[i + 1]) < TOL_STD


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Guide "model-configs" section 4: what all 16 shares of 16 experts
    give adds up to the uncut reference's layer output (no shared expert to
    count once)."""
    hf_whole = _hf(n_routed_experts=16, n_routed_experts_published=16,
                   num_experts_per_tok=4)
    cfg_whole, params, _, _ = _setup(hf_whole)
    m = jax.random.normal(jax.random.PRNGKey(5), (24, cfg_whole.hidden_size))
    stack = {k[len("layers."):]: v for k, v in params.items()
             if k.startswith("layers.")}
    names = ("router", "router_bias", "moe_gate", "moe_up", "moe_down")
    lp = {n: stack[n][0] for n in names}
    with jax.default_matmul_precision("highest"):
        whole = mla._moe_mlp(m, lp, cfg_whole)
        total = jnp.zeros_like(whole)
        for share in range(16):
            hf = _hf(n_routed_experts=1, n_routed_experts_published=16,
                     num_experts_per_tok=4, expert_share_index=share)
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
    """The layers run through mla.walk_layer_kinds, ONE scan over the
    periods of the layer kinds: the lowered program of 25 layers (four
    periods) has as many instructions as that of 13 (two)."""

    def lowered(layers):
        hf = _hf(num_hidden_layers=layers,
                 hybrid_layer_pattern=[0] + [1, 1, 1, 1, 0, 1] * 4,
                 moe_layer_freq=[0] + [1] * 24)
        cfg = ModelConfig.from_hf_config(hf)
        statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="xla")
        params = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
        kv = jax.eval_shape(lambda: llama.init_kv_cache(cfg, NUM_BLOCKS, BS))
        i32 = jnp.int32
        s = jax.ShapeDtypeStruct
        if program == "prefill":
            return _PREFILL.lower(params, kv, s((32,), i32), s((8,), i32),
                                  s((), i32), s((), i32), statics).as_text()
        return _DECODE.lower(params, kv, s((2,), i32), s((2,), i32),
                             s((2, 8), i32), statics).as_text()

    thirteen, twentyfive = lowered(13), lowered(25)
    assert len(thirteen.splitlines()) == len(twentyfive.splitlines())
    assert "stablehlo.while" in thirteen


def test_the_walker_exists_once():
    """dots3_note's latent layers and this model's grouped-query ones run
    through the same function."""
    import inspect
    assert "walk_layer_kinds(" in inspect.getsource(mla._run_layers_mixed)
    assert mimo.walk_layer_kinds is mla.walk_layer_kinds
    # this module brings the attention block of each kind, and no scan
    assert "lax.scan(" not in inspect.getsource(mimo)


# ------------------------------------------- the kernels against their XLA

def _paged_case(seed=0):
    B, H, KVH, dk, dv, NB = 3, 8, 2, 192, 64, 40
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, dk), jnp.float32)
    kc = jax.random.normal(ks[1], (NB * 8, KVH * dk), jnp.float32)
    vc = jax.random.normal(ks[2], (NB * 8, KVH * dv), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(seed).permutation(
        np.arange(1, NB))[:B * 9].reshape(B, 9), jnp.int32)
    return (q, kc, vc, tables, jnp.asarray([70, 5, 33], jnp.int32),
            jnp.asarray([40, -1, 10], jnp.int32),
            2.0 * jax.random.normal(ks[3], (H,)))


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
def test_the_decode_kernel_is_its_xla_form(sink, window):
    """paged_attention with value heads of another size than the keys' (dk
    192 is no multiple of 128: the ROW is), with and without the window's
    lower bound and the sink, interpreted against the XLA gather."""
    q, kc, vc, tables, seq_lens, win_lo, sinks = _paged_case()
    kw = dict(block_size=8, scale=0.1, v_dim=64,
              win_lo=win_lo if window else None,
              sink=sinks if sink else None)
    want = attention.paged_attention(q, kc, vc, tables, seq_lens,
                                     impl="xla", **kw)
    got = attention.paged_attention(
        q, kc, vc, tables, seq_lens, impl="pallas_interpret",
        chunk_blocks=4, name="gqa_test_read", **kw)
    assert want.shape == (3, 8, 64)
    assert float(jnp.abs(got - want).max()) < 1e-5
    if sink:
        plain = attention.paged_attention(
            q, kc, vc, tables, seq_lens, impl="xla", **dict(kw, sink=None))
        assert float(jnp.abs(plain - want).max()) > 1e-3


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, 7], ids=["full", "window"])
def test_the_prefill_kernel_is_its_xla_form(sink, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    T, S, H, KVH, dk, dv = 20, 64, 8, 2, 192, 64
    q = jax.random.normal(ks[0], (T, H, dk))
    k = jax.random.normal(ks[1], (S, KVH, dk))
    v = jax.random.normal(ks[2], (S, KVH, dv))
    sinks = 2.0 * jax.random.normal(ks[3], (H,)) if sink else None
    want = attention.causal_attention(q, k, v, scale=0.1, kv_offset=30,
                                      length=50, window=window, sink=sinks)
    got = attention.flash_prefill(
        q, k, v, scale=0.1, start_pos=30, seq_len=50,
        sliding=window is not None, window=window, sink=sinks, q_chunk=8,
        kv_chunk=16, interpret=True, name="gqa_test_prefill")
    assert want.shape == (T, H, dv)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_the_partial_prefill_kernel_folds_to_the_whole():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (20, 8, 192))
    k = jax.random.normal(ks[1], (64, 2, 192))
    v = jax.random.normal(ks[2], (64, 2, 64))
    acc, _m, l = attention.flash_prefill_partial(
        q, k, v, scale=0.1, start_pos=30, seq_len=50, q_chunk=8,
        kv_chunk=16, interpret=True, name="gqa_test_partial")
    want = attention.causal_attention(q, k, v, scale=0.1, kv_offset=30,
                                      length=50)
    assert float(jnp.abs(acc / l[..., None] - want).max()) < 1e-5


def test_the_sink_takes_mass_and_adds_no_value():
    scores = jnp.asarray([[1.0, 2.0, 3.0]])
    p = attention.sink_softmax(scores, jnp.asarray([2.5]))
    want = np.exp([1.0, 2.0, 3.0]) / (np.exp([1.0, 2.0, 3.0]).sum()
                                      + np.exp(2.5))
    np.testing.assert_allclose(np.asarray(p)[0], want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(attention.sink_softmax(scores, None)),
        np.asarray(jax.nn.softmax(scores)), rtol=1e-6)


@pytest.mark.parametrize("geometry, want", [
    ((64, 4, 192, 16, None, 128), True),       # the full layers' rows
    ((64, 8, 192, 16, None, 128), True),       # the window layers'
    ((64, 4, 192, 16, jnp.int8, 128), False),  # int8 rows: one width
    ((4, 2, 24, 16, None, 16), False),         # the fixture: XLA
    ((64, 4, 192, 16, None, None), True),      # no v_dim: as before
])
def test_pallas_supported_answers_for_the_new_geometry(geometry, want):
    H, KVH, dk, bs, dtype, dv = geometry
    assert attention.pallas_supported(H, KVH, dk, bs, kv_dtype=dtype,
                                      v_dim=dv) is want


@pytest.mark.parametrize("impl", ["pallas_interpret"])
def test_the_model_runs_its_kernels_interpreted(ref, impl):
    """The served reads at a width the kernels tile (key rows of 256 and 512 lanes,
    value rows of 128 and 256), interpreted: prefill through both prefill kernels, decode
    through both decode reads, against the reference."""
    hf = _hf(head_dim=128, swa_head_dim=128, v_head_dim=64,
             swa_v_head_dim=64, num_hidden_layers=3)
    cfg, params, kv, _ = _setup(hf)
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl=impl)
    assert mimo.decode_kernels_tile(cfg, BS)
    seq = _tokens(cfg, 44)
    logits, kv = _prefill(params, kv, statics, seq[:40])
    want = ref.logits_for(params, hf, seq, 5)
    assert _err_std(logits, want[0]) < 10 * TOL_STD
    for i in range(2):
        logits, kv = _decode(params, kv, statics, int(seq[40 + i]), 40 + i)
        assert _err_std(logits, want[i + 1]) < 10 * TOL_STD


def test_a_forced_kernel_on_the_fixtures_widths_raises():
    cfg, params, kv, _ = _setup(_hf(head_dim=44, swa_head_dim=44))
    statics = ModelStatics(cfg=cfg, block_size=BS, attn_impl="pallas")
    with pytest.raises(ValueError, match="does not tile"):
        _prefill(params, kv, statics, _tokens(cfg, 8))


# ------------------------------------------------- the window pool's size

@pytest.mark.parametrize("name, blocks, seqs, want", [
    ("dots3-note-prev", 14336, 64, 9395),
    ("mimo-v2.5", 24576, 64, 1 + 64 * 9 + (16 + 9) + 2048),
])
def test_window_pool_blocks_are_bounded_in_bytes(name, blocks, seqs, want):
    """Derived, no flag: every slot's ring, one prefill dispatch, and an
    evictable part bounded by hit boundaries a slot and by bytes against
    the paged pool (docs/hybrid_cache.md part three); dots3's count is what
    it was."""
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in config.items() if k not in EXTRAS})
    module = mla if cfg.kv_lora_rank else llama
    layout = module.cache_layout(cfg, 16, 2)
    assert layout.window_pool_blocks(blocks, seqs, 256) == want
    from dynamo_tpu.llm.kv.hybrid import WINDOW_CACHE_BYTES_RATIO
    num, den = WINDOW_CACHE_BYTES_RATIO
    window_block = layout.window_layers * (layout.window_row_bytes
                                           or layout.row_bytes)
    cached = want - 1 - seqs * layout.ring_blocks - (
        16 + layout.ring_blocks)
    assert cached * window_block * den <= (
        num * blocks * layout.paged_layers * layout.row_bytes)


def test_the_published_rows():
    with open(os.path.join(BENCH, "configs", "mimo-v2.5.json")) as f:
        config = json.load(f)
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in config.items() if k not in EXTRAS})
    layout = llama.cache_layout(cfg, 16, 2)
    assert (layout.row_bytes, layout.window_row_bytes) == (2560, 5120)
    assert (layout.paged_layers, layout.window_layers) == (3, 10)
    assert (layout.ring_blocks, layout.window_reach_blocks) == (9, 8)
    assert mimo.decode_kernels_tile(cfg, 16)


# ------------------------------------------------------------ the engine

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
    """A document is served once; a prompt that shares it is then served by
    a hit over BOTH groups (the paged blocks of the whole prefix and the
    window blocks before its boundary) and gives the tokens and logprobs of
    an engine without reuse and of the reference; with the window blocks
    before the boundary gone, the hit is cut back to where they are."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    warm = _engine(params, cfg, prefill_chunk=16, prefill_buckets=[16])
    cold = _engine(params, cfg, enable_prefix_reuse=False,
                   prefill_chunk=16, prefill_buckets=[16])
    assert warm.has_window_pool and set(warm.kv) == {"k", "v", "win_k",
                                                     "win_v"}
    doc = _tokens(cfg, 6 * BS, seed=7).tolist()
    hashes = TokenBlockSequence(BS, doc).sequence_hashes
    wp = warm.kv_manager.win_pool
    try:
        with jax.default_matmul_precision("highest"):
            _, _, req = await _serve(
                warm, "doc", doc + _tokens(cfg, 5, seed=8).tolist(), n=4)
            assert req.prefix_hit_tokens == 0
            assert all(wp.has(h) for h in hashes)
            for rid, shared, seed, hit in (("on", 6, 9, 96),
                                           ("inside", 5, 10, 80)):
                prompt = doc[:shared * BS] + _tokens(cfg, 13, seed).tolist()
                toks, lps, req = await _serve(warm, rid, prompt)
                want_toks, want_lps, _ = await _serve(cold, rid, prompt)
                assert req.prefix_hit_tokens == hit
                assert toks == want_toks, rid
                np.testing.assert_allclose(lps, want_lps, atol=1e-4)
                _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        admits = {r["rid"]: r for r in warm.flight.dump()
                  if r["kind"] == "prefill"}
        assert (admits["on"]["hit_tokens"],
                admits["on"]["hit_cut_tokens"]) == (96, 0)
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
    """840 tokens of context (40 windows of 21), prefilled by chunks of 32
    and decoded on: no sequence ever holds more than the ring's three
    window blocks a layer in a decode step, and the stream is the
    reference's."""
    hf = _hf()
    cfg, params, _, _ = _setup(hf)
    core = _engine(params, cfg, max_model_len=1024, num_kv_blocks=128,
                   prefill_chunk=32, prefill_buckets=[32])
    wp = core.kv_manager.win_pool
    # every slot's ring, one dispatch, and the evictable part: bounded by
    # 4 boundaries a slot here (2 slots x 4 x a reach of 2)
    assert wp.num_blocks == 1 + 2 * 3 + (2 + 3) + 16
    prompt = _tokens(cfg, 840, seed=12)
    try:
        with jax.default_matmul_precision("highest"):
            toks, lps, _ = await _serve(core, "long", prompt, n=24)
        _held_to_the_reference(ref, params, hf, prompt, toks, lps)
        decode = [r for r in core.flight.dump() if r["kind"] == "decode"
                  and r["batch_fill"]]
        assert decode and max(r["win_blocks_live"] for r in decode) == 3
        assert all(r["win_tokens"] == 21 * r["emitted"] for r in decode)
        assert wp.used_blocks == 0 and wp.released >= 50
    finally:
        await core.stop()


def test_block_moves_carry_each_group_under_its_own_ids():
    from dynamo_tpu.engine.block_copy import move_blocks
    cfg, params, kv, statics = _setup(_hf())
    _, kv = _prefill(params, kv, statics, _tokens(cfg, 60))
    before = {k: np.asarray(v) for k, v in kv.items()}
    moved = move_blocks(kv, [1, 2, 3], [9, 10, 11], BS,
                        win_src=[2, 4], win_dst=[13, 12])
    for name in ("k", "v"):
        arr = np.asarray(moved[name])
        assert np.abs(before[name][:, BS:4 * BS]).max() > 0
        np.testing.assert_array_equal(arr[:, 9 * BS:12 * BS],
                                      before[name][:, BS:4 * BS])
    for name in ("win_k", "win_v"):
        win = np.asarray(moved[name])
        np.testing.assert_array_equal(win[:, 13 * BS:14 * BS],
                                      before[name][:, 2 * BS:3 * BS])
        np.testing.assert_array_equal(win[:, 12 * BS:13 * BS],
                                      before[name][:, 4 * BS:5 * BS])
        np.testing.assert_array_equal(win[:, 9 * BS:12 * BS],
                                      before[name][:, 9 * BS:12 * BS])
