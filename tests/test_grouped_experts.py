"""The grouped form of the expert MLP (models/llama.py run_experts_grouped
over engine/grouped_matmul.py, Pallas interpret mode on the CPU) against
the dense-over-experts form it stands in for, and the chooser between
them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.grouped_matmul import ROW_TILE, grouped_matmul
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.quant import quantize_array

D, F = 64, 32          # tiny widths: the interpreter tiles any shape


def _route(rng, n, experts, k, held=None, offset=0):
    """Qwen's routing (softmax over all, top-k unnormalised) over
    ``experts`` published experts, of which this chip holds ``held``
    starting at ``offset`` (mla._moe_mlp's subtraction)."""
    logits = jnp.asarray(rng.standard_normal((n, experts)), jnp.float32)
    top_w, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return top_idx - offset, top_w, held or experts


def _case(name, rng):
    """→ (N, E, top_idx, top_w, valid_rows)."""
    if name == "qwen-top4-of-60":
        idx, w, E = _route(rng, 48, 60, 4)
        return 48, E, idx, w, None
    if name == "an-expert-without-rows":
        idx, w, _ = _route(rng, 40, 4, 2)
        idx = idx + (idx >= 2)                # expert 2 of 5: no row
        E = 5
        return 40, E, idx, w, None
    if name == "every-row-to-one-expert":
        w = jnp.asarray(rng.uniform(0.1, 1.0, (150, 1)), jnp.float32)
        return 150, 6, jnp.full((150, 1), 3, jnp.int32), w, None
    if name == "pairs-off-the-row-tile":
        idx, w, E = _route(rng, 37, 7, 3)
        assert (37 * 3) % ROW_TILE
        return 37, E, idx, w, None
    if name == "share-most-picks-outside":
        idx, w, E = _route(rng, 64, 32, 4, held=4, offset=8)
        inside = (idx >= 0) & (idx < E)
        assert 0 < int(inside.sum()) < idx.size // 2
        return 64, E, idx, w, None
    if name == "share-all-picks-outside":
        idx, w, E = _route(rng, 32, 16, 2, held=4, offset=16)
        assert not bool(jnp.any((idx >= 0) & (idx < E)))
        return 32, E, idx, w, None
    if name == "rows-past-true-len":
        idx, w, E = _route(rng, 96, 8, 2)
        return 96, E, idx, w, 41
    raise AssertionError(name)


CASES = ["qwen-top4-of-60", "an-expert-without-rows",
         "every-row-to-one-expert", "pairs-off-the-row-tile",
         "share-most-picks-outside", "share-all-picks-outside",
         "rows-past-true-len"]
FORMS = ["f32", "bf16", "int8-fused", "int8-separate-f32"]


def _stacks(rng, E, form):
    """→ (dtype of x, kwargs of run_experts_*: gate/up/down or gateup)."""
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * s[-2] ** -0.5,  # noqa: E731
                                jnp.float32)
    gate, up, down = mk(E, D, F), mk(E, D, F), mk(E, F, D)
    quant = lambda w: quantize_array(w, keep_axes=(0, -1))      # noqa: E731
    if form == "f32":
        return jnp.float32, dict(gate_w=gate, up_w=up, down_w=down)
    if form == "bf16":
        b = lambda w: w.astype(jnp.bfloat16)                    # noqa: E731
        return jnp.bfloat16, dict(gate_w=b(gate), up_w=b(up), down_w=b(down))
    if form == "int8-fused":
        return jnp.bfloat16, dict(
            gate_w=None, up_w=None, down_w=quant(down.astype(jnp.bfloat16)),
            gateup_w=quant(jnp.concatenate([gate, up], -1)
                           .astype(jnp.bfloat16)))
    return jnp.float32, dict(gate_w=quant(gate), up_w=quant(up),
                             down_w=quant(down))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", CASES)
def test_grouped_form_equals_dense(case, form):
    """Same arguments, same result: in float32 to 1e-5, with int8 stacks
    under float32 activations to test_quant's fused-vs-dequantized 2e-4,
    in bf16 to two units in the last place of the largest output. Pairs
    that must compute nothing add exactly nothing."""
    rng = np.random.default_rng(CASES.index(case))
    N, E, top_idx, top_w, valid = _case(case, rng)
    dtype, w = _stacks(rng, E, form)
    x = jnp.asarray(rng.standard_normal((N, D)), dtype)
    dense = np.asarray(llama.run_experts_dense(
        x, top_idx=top_idx, top_w=top_w, **w), np.float32)
    grouped = np.asarray(llama.run_experts_grouped(
        x, top_idx=top_idx, top_w=top_w, interpret=True,
        valid_rows=None if valid is None else jnp.asarray(valid, jnp.int32),
        **w), np.float32)
    assert np.isfinite(grouped).all()
    rows = slice(0, valid)            # the dense form computes padding too
    if dtype == jnp.bfloat16:
        tol = 2 ** -6 * max(np.abs(dense).max(), 1e-6)
        assert np.abs(grouped[rows] - dense[rows]).max() <= tol
    else:
        tol = 1e-5 if form == "f32" else 2e-4
        np.testing.assert_allclose(grouped[rows], dense[rows],
                                   rtol=tol, atol=tol)
    if case == "share-all-picks-outside":
        assert not grouped.any() and not dense.any()
    if valid is not None:
        # padding computes nothing and changes nothing: its rows read
        # exactly zero, the valid rows as in a run that never had it
        assert not grouped[valid:].any()
        alone = np.asarray(llama.run_experts_grouped(
            x[:valid], top_idx=top_idx[:valid], top_w=top_w[:valid],
            interpret=True, **w), np.float32)
        assert np.array_equal(grouped[:valid], alone)


def test_rows_behind_the_last_group_are_not_computed():
    """The kernel's contract: a row past sum(group_sizes) joins no visit;
    NaN rows there reach no output row of a group."""
    rng = np.random.default_rng(0)
    sizes = jnp.asarray([5, 0, 20, 3], jnp.int32)
    x = jnp.asarray(rng.standard_normal((32, D)), jnp.float32)
    x = x.at[28:].set(jnp.nan)
    w = jnp.asarray(rng.standard_normal((4, D, F)), jnp.float32)
    out = np.asarray(grouped_matmul(x, w, sizes, tm=8, interpret=True))
    want = np.concatenate([np.asarray(x[a:b]) @ np.asarray(w[g])
                           for g, (a, b) in enumerate(
                               [(0, 5), (5, 5), (5, 25), (25, 28)])])
    np.testing.assert_allclose(out[:28], want, rtol=1e-5, atol=1e-5)


def _has_kernel(n_rows, sharded, E=8, k=2, d=128, f=128):
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda x, g, u, dn, i, w: llama.run_experts(
            x, g, u, dn, i, w, sharded=sharded))(
        sds((n_rows, d), jnp.bfloat16), sds((E, d, f), jnp.bfloat16),
        sds((E, d, f), jnp.bfloat16), sds((E, f, d), jnp.bfloat16),
        sds((n_rows, k), jnp.int32), sds((n_rows, k), jnp.float32))
    return "pallas_call" in str(jaxpr)


def test_the_form_is_chosen_from_rows_shapes_and_layout():
    """One function of what the program sees: dense at a decode step's 64
    rows and under every mesh, grouped at 1,024 and 2,048 rows on one
    device; run_experts and the engine's counter follow it."""
    qwen = dict(num_experts=60, top_k=4, d_model=2048, d_ff=1408)
    pick = llama.experts_run_grouped
    assert not pick(64, sharded=False, **qwen)
    assert pick(1024, sharded=False, **qwen)
    assert pick(2048, sharded=False, **qwen)
    assert not pick(1024, sharded=True, **qwen)
    assert not pick(2048, sharded=True, **qwen)
    assert pick(llama.GROUPED_MIN_ROWS, sharded=False, **qwen)
    assert not pick(llama.GROUPED_MIN_ROWS - 1, sharded=False, **qwen)
    # every expert picked anyway: nothing to skip; widths off the lane
    # grid: the kernel does not tile them
    assert not pick(2048, 4, 4, 2048, 1408, False)
    assert not pick(2048, 60, 4, 2048 + 64, 1408, False)
    # deepseek's share: 16 held of 256 published, top-8
    assert pick(1024, 16, 8, 7168, 2048, False)

    assert _has_kernel(1024, sharded=False)
    assert _has_kernel(2048, sharded=False)
    assert not _has_kernel(64, sharded=False)
    assert not _has_kernel(1024, sharded=True)

    cfg = ModelConfig(vocab_size=256, hidden_size=256, intermediate_size=128,
                      num_layers=1, num_heads=2, num_kv_heads=2, head_dim=64,
                      num_experts=8, num_experts_per_tok=2)
    one = llama.ModelStatics(cfg=cfg, block_size=16)
    assert not one.sharded
    assert llama.grouped_prefill_rows(one, 2048, 1100) == 1100
    assert llama.grouped_prefill_rows(one, 64, 40) == 0
    import dataclasses
    meshed = dataclasses.replace(one, sharded=True)
    assert llama.grouped_prefill_rows(meshed, 2048, 1100) == 0
    dense_model = llama.ModelStatics(
        cfg=dataclasses.replace(cfg, num_experts=0), block_size=16)
    assert llama.grouped_prefill_rows(dense_model, 2048, 1100) == 0


def _qwen_like():
    cfg = ModelConfig(
        vocab_size=256, hidden_size=128, intermediate_size=128,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=64,
        max_position_embeddings=512, num_experts=6, num_experts_per_tok=2,
        moe_norm_topk=False, shared_expert_size=128)
    params = llama.fuse_stacked_matmuls(dict(llama.init_params(
        cfg, jax.random.PRNGKey(3), dtype=jnp.float32)), cfg)
    assert "layers.moe_gateup" in params
    return llama, cfg, params, llama.init_kv_cache(cfg, 20, 16,
                                                   dtype=jnp.float32)


def _deepseek_share():
    from dynamo_tpu.engine.models import mla
    cfg = ModelConfig.from_hf_config({
        "model_type": "deepseek_v3", "vocab_size": 256, "hidden_size": 128,
        "intermediate_size": 256, "moe_intermediate_size": 128,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "q_lora_rank": 64, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "v_head_dim": 32,
        "first_k_dense_replace": 1, "n_routed_experts": 4,
        "n_routed_experts_published": 8, "expert_share_index": 1,
        "n_group": 2, "topk_group": 1, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 512})
    assert cfg.first_k_dense == 1 and cfg.num_experts_total == 8
    params = mla.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    return mla, cfg, params, mla.init_kv_cache(cfg, 20, 16,
                                               dtype=jnp.float32)


@pytest.mark.parametrize("family", ["llama-qwen2moe", "mla-deepseek-share"])
def test_prefill_is_the_same_function_in_both_forms(family, monkeypatch):
    """A whole prefill of 256 rows (of which 201 hold tokens): the
    program whose expert stacks stay beside the layer scan and are read
    by layer index (grouped: one device) gives the logits and the cache
    of the program that slices them per layer (dense: what a mesh
    keeps). On models/mla.py the expert layers follow a dense one, and
    this chip holds experts 4-7 of 8."""
    import dataclasses
    mod, cfg, params, kv = (_qwen_like if family.startswith("llama")
                            else _deepseek_share)()
    T, n = 256, 201
    assert T >= llama.GROUPED_MIN_ROWS
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(np.where(np.arange(T) < n,
                                  rng.integers(1, 256, T), 0), jnp.int32)
    table = jnp.arange(1, 17, dtype=jnp.int32)
    grouped = llama.ModelStatics(cfg=cfg, block_size=16, attn_impl="xla")
    calls = []
    real = llama.run_experts_grouped
    monkeypatch.setattr(llama, "run_experts_grouped",
                        lambda *a, **k: calls.append(k) or real(*a, **k))
    seen = []
    out = {}
    for name, statics in (("dense", dataclasses.replace(
            grouped, sharded=True)), ("grouped", grouped)):
        out[name] = mod.prefill_forward(
            params, jax.tree.map(jnp.copy, kv), tokens, table,
            jnp.asarray(0, jnp.int32), jnp.asarray(n, jnp.int32), statics)
        assert bool(calls) == (name == "grouped"), name
        seen = list(calls)
    assert all(c["layer"] is not None and c["valid_rows"] is not None
               for c in seen)
    (lg, kvg), (ld, kvd) = out["grouped"], out["dense"]
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    for name in kvg:
        # block 0 is the trash block: the padding's rows land there, and
        # the padding's experts are what the grouped form does not run
        np.testing.assert_allclose(np.asarray(kvg[name])[:, 16:],
                                   np.asarray(kvd[name])[:, 16:],
                                   rtol=2e-4, atol=2e-4)

