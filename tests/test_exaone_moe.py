"""exaone_moe (K-EXAONE-236B-A23B) on ``models/mimo.py``: QK-normed window /
NoPE full grouped-query layers over the two pools, norms on the sub-layers'
outputs, sigmoid experts with a shared one, and the multi-token-prediction
module resident as the drafter (``--spec-k 1``; docs/speculative.md "A
resident drafter", docs/hybrid_cache.md part four).

Tiny widths (``benchmark/fixtures/tiny-exaone-moe.json`` at eight layers:
L L L G | L L L G, a window of 21 so that 16-row blocks, the ring of 3 blocks
and the window's edge are all crossed within a few dozen tokens), float32,
drawn weights: the engine is held to ``benchmark/references/exaone_moe.py``
to rounding, so every breakage that moves the logits at all is told apart.
"""

import asyncio
import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH, os.path.join(BENCH, "references")]
try:
    import exaone_moe as ref                       # noqa: E402
    import exaone_moe_check as check               # noqa: E402
    import reference                               # noqa: E402
finally:
    del sys.path[:2]

from dynamo_tpu.engine.config import EngineConfig, ModelConfig  # noqa: E402
from dynamo_tpu.engine.core import (FINISH_SENTINEL, EngineCore,  # noqa: E402
                                    EngineRequest)
from dynamo_tpu.engine.models import llama, mimo, mla  # noqa: E402
from dynamo_tpu.engine.sampling import SlotSampling  # noqa: E402

BENCH_KEYS = ("source", "reduced", "assumed", "deployment",
              "memory_analysis", "notes", "reference")
# float32 against float32: rounding, in standard deviations of the logits
EXACT = 2e-3


def _hf(**over):
    with open(os.path.join(BENCH, "fixtures", "tiny-exaone-moe.json")) as f:
        config = json.load(f)
    return {**{k: v for k, v in config.items() if k not in BENCH_KEYS},
            "num_hidden_layers": 8, **over}


HF = _hf()
CFG = ModelConfig.from_hf_config(HF)


def _engine(spec_k, **kw):
    cfg = EngineConfig(**{**dict(
        max_model_len=256, num_kv_blocks=64, max_num_seqs=4,
        kv_block_size=16, seed=5, spec_k=spec_k), **kw})
    return EngineCore(CFG, cfg, param_dtype=jnp.float32)


async def _serve(core, prompts, n, sampling=None):
    reqs = [EngineRequest(
        rid=f"r{i}", prompt=list(p),
        sampling=sampling or SlotSampling(temperature=0.0),
        max_new_tokens=n, eos_ids=frozenset())
        for i, p in enumerate(prompts)]
    outs = [([], []) for _ in reqs]
    try:
        for r in reqs:
            await core.submit(r)
        for r, (ids, lps) in zip(reqs, outs):
            while True:
                tok, lp = await r.out_queue.get()
                if tok is FINISH_SENTINEL:
                    break
                ids.append(tok)
                lps.append(lp)
    finally:
        await core.stop()
    return outs


def _prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).tolist() for n in sizes]


def test_the_configuration_is_read_from_its_published_keys():
    assert (CFG.model_type, CFG.num_layers, CFG.first_k_dense) == (
        "exaone_moe", 8, 1)
    assert mla.layer_kinds(CFG) == tuple("SSSFSSSF")
    # layer 0 is dense AND sliding: the walker's prefix is of either kind
    assert mla.layer_plan(CFG) == (1, ("S", "S", "F", "S"), 1,
                                   ("S", "S", "F"))
    assert CFG.qk_norm and CFG.norm_on_output and CFG.nope_full
    assert (CFG.swa_window, CFG.mtp_layers, CFG.routed_scaling) == (21, 1, 2.5)
    assert (CFG.num_experts, CFG.router_width, CFG.shared_expert_size) == (
        4, 32, 32)
    layout = llama.cache_layout(CFG, 16)
    # the module's rows are a third full layer's in the paged group
    assert (layout.paged_layers, layout.window_layers, layout.ring_blocks) \
        == (3, 6, 3)
    shapes = llama.param_shapes(CFG)
    assert list(shapes)[-1] == "mtp.final_norm" and \
        shapes["mtp.eh_proj"] == (1, 128, 64)
    # without the module the main weights are drawn the same: the module's
    # leaves come last
    plain = dataclasses.replace(CFG, mtp_layers=0)
    assert list(llama.param_shapes(plain)) == [
        n for n in shapes if not n.startswith("mtp.")]


@pytest.mark.parametrize("problem, over", [
    ("layer_types of kind", {"layer_types": ["linear_attention"] * 8}),
    ("more than one multi-token", {"num_nextn_predict_layers": 2}),
    ("n_group", {"n_group": 2}),
    ("sliding_windows that differ", {"sliding_windows": [21] * 8}),
    ("without a leading dense", {"mlp_layer_types": ["sparse"] * 8}),
])
def test_what_the_block_does_not_run_is_refused_by_name(problem, over):
    with pytest.raises(ValueError, match=problem):
        ModelConfig.from_hf_config(_hf(**over))


def test_spec_k_refusals_say_what_still_does_not_run():
    e = EngineConfig(max_model_len=256, num_kv_blocks=64, max_num_seqs=4,
                     kv_block_size=16, spec_k=1)
    assert mimo.refusals(CFG, e, None) == []
    two = mimo.refusals(CFG, dataclasses.replace(e, spec_k=2), None)
    assert [r.split(" (")[0] for r in two] == ["--spec-k > 1"]
    # a model of this module with no module of its own: the n-gram drafter
    # over a window pool stays refused, by name
    ngram = mimo.refusals(dataclasses.replace(CFG, mtp_layers=0), e, None)
    assert [r.split(" (")[0] for r in ngram] == [
        "--spec-k with the n-gram drafter"]


def test_quantised_weights_take_the_modules_matmuls():
    from dynamo_tpu.engine.quant import init_params_quantized
    params = llama.fuse_stacked_matmuls(
        dict(init_params_quantized(CFG, jax.random.PRNGKey(0))), CFG)
    for name in ("layers.wqkv", "layers.swa_wqkv", "layers.sh_gateup",
                 "layers.moe_gateup", "mtp.eh_proj", "mtp.wqkv", "mtp.wo",
                 "mtp.moe_gateup", "mtp.moe_down", "mtp.sh_down"):
        assert params[name].q.dtype == jnp.int8, name
    assert params["mtp.eh_proj"].q.shape == (1, 128, 64)
    for name in ("mtp.enorm", "mtp.hnorm", "mtp.final_norm", "mtp.q_norm",
                 "mtp.router", "layers.swa_q_norm", "layers.ln1"):
        assert not hasattr(params[name], "q"), name


def test_seeded_weights_follow_the_rule_of_this_family():
    params = llama.init_params(CFG, jax.random.PRNGKey(4), jnp.float32)
    assert float(params["layers.swa_q_norm"].min()) == pytest.approx(
        llama.QK_NORM_SEEDED)
    assert float(params["layers.k_norm"].max()) == 1.0
    assert float(params["layers.ln1"].max()) == pytest.approx(
        llama.OUTPUT_NORM_SEEDED)
    assert float(params["mtp.hnorm"].max()) == llama.MTP_HNORM_SEEDED
    assert float(params["mtp.enorm"].max()) == 1.0
    # no projection sets a scale under the norms: fan_in^-0.5
    for name, fan_in in (("layers.swa_wq", 64), ("layers.sh_down", 32),
                         ("mtp.eh_proj", 128), ("mtp.wo", 128)):
        assert llama.seeded_std(CFG, name, fan_in) == fan_in ** -0.5
    # ... but for the routed experts' down-projection: what a flipped
    # choice moves is damped
    for name in ("layers.moe_down", "mtp.moe_down"):
        assert llama.seeded_std(CFG, name, 32) == 0.25 * 32 ** -0.5
    assert float(jnp.abs(params["mtp.router_bias"]).max()) > 0
    # mimo_v2's rule is as it was
    mimo_cfg = dataclasses.replace(CFG, norm_on_output=False, qk_norm=False)
    assert llama.seeded_std(mimo_cfg, "layers.wq", 64) == pytest.approx(
        1.6 * 64 ** -0.5)


def test_a_checkpoint_brings_the_module(tmp_path):
    """weights.load_llama_params under the assumed tensor names (exaone4's
    for the block: output norms, q_norm / k_norm; DeepSeek-V3's for the
    module under model.layers.{L}): the module's tensors are MAPPED, its
    copies of the embedding and the head are not read, of all the published
    experts the share held here, and an index beyond the declared modules
    is still refused."""
    from safetensors.numpy import save_file
    from dynamo_tpu.engine.weights import load_llama_params
    params = llama.init_params(CFG, jax.random.PRNGKey(2), jnp.float32)
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    rng = np.random.default_rng(1)
    out = {"model.embed_tokens.weight": p["embed"],
           "model.norm.weight": p["final_norm"],
           "lm_head.weight": p["lm_head"].T}

    def block(lay, stack, at, pre, mi):
        get = lambda n: p[f"{stack}{pre}{n}"][at]          # noqa: E731
        for n in "qkvo":
            out[lay + f"self_attn.{n}_proj.weight"] = get(f"w{n}").T
        out[lay + "self_attn.q_norm.weight"] = get("q_norm")
        out[lay + "self_attn.k_norm.weight"] = get("k_norm")
        if mi is None:
            for n in ("gate", "up", "down"):
                out[lay + f"mlp.{n}_proj.weight"] = p[
                    f"{stack}dense_{n}"][0].T
            return
        out[lay + "mlp.gate.weight"] = p[stack + "router"][mi].T
        out[lay + "mlp.gate.e_score_correction_bias"] = p[
            stack + "router_bias"][mi]
        for n in ("gate", "up", "down"):
            out[lay + f"mlp.shared_experts.{n}_proj.weight"] = p[
                f"{stack}sh_{n}"][mi].T
            for e in range(CFG.router_width):   # all the published experts
                w = p[f"{stack}moe_{n}"][mi]
                out[lay + f"mlp.experts.{e}.{n}_proj.weight"] = (
                    w[e].T if e < CFG.num_experts
                    else rng.standard_normal(w[0].T.shape, np.float32))

    seen = {"F": 0, "S": 0}
    for i, kind in enumerate(mla.layer_kinds(CFG)):
        lay = f"model.layers.{i}."
        out[lay + "post_attention_layernorm.weight"] = p["layers.ln1"][i]
        out[lay + "post_feedforward_layernorm.weight"] = p["layers.ln2"][i]
        block(lay, "layers.", seen[kind], "swa_" if kind == "S" else "",
              None if i == 0 else i - 1)
        seen[kind] += 1
    lay = f"model.layers.{CFG.num_layers}."
    out[lay + "enorm.weight"] = p["mtp.enorm"][0]
    out[lay + "hnorm.weight"] = p["mtp.hnorm"][0]
    out[lay + "eh_proj.weight"] = p["mtp.eh_proj"][0].T
    out[lay + "shared_head.norm.weight"] = p["mtp.final_norm"][0]
    out[lay + "shared_head.head.weight"] = p["lm_head"].T     # not read
    out[lay + "embed_tokens.weight"] = p["embed"]             # not read
    out[lay + "post_attention_layernorm.weight"] = p["mtp.ln1"][0]
    out[lay + "post_feedforward_layernorm.weight"] = p["mtp.ln2"][0]
    block(lay, "mtp.", 0, "", 0)
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    loaded = load_llama_params(str(tmp_path), CFG, dtype=jnp.float32)
    assert set(loaded) == set(params)
    for name, want in params.items():
        np.testing.assert_array_equal(np.asarray(loaded[name]),
                                      np.asarray(want), err_msg=name)
    out[f"model.layers.{CFG.num_layers + 1}.enorm.weight"] = p["mtp.enorm"][0]
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="beyond the config"):
        load_llama_params(str(tmp_path), CFG, dtype=jnp.float32)


@pytest.fixture(scope="module")
def served():
    """The same three prompts (crossing the window's edge, the ring's wrap
    at 48 rows and block boundaries while decoding) with the module off and
    on."""
    prompts = _prompts((19, 40, 33))
    out = {}
    for k in (0, 1):
        core = _engine(k)
        out[k] = (core, asyncio.run(_serve(core, prompts, 40)))
    return prompts, out


@pytest.mark.parametrize("spec_k", [0, 1])
def test_engine_is_the_reference_through_both_pools(served, spec_k):
    prompts, out = served
    core, streams = out[spec_k]
    for prompt, (ids, lps) in zip(prompts, streams):
        rep = reference.compare(core.params, HF, prompt, ids, lps,
                                forward=ref.logits_for)
        assert rep["worst_logprob_err_std"] < EXACT, rep
        assert rep["worst_argmax_gap_std"] < EXACT, rep


def test_two_row_streams_are_the_one_row_streams(served):
    _prompts_, out = served
    assert [ids for ids, _ in out[1][1]] == [ids for ids, _ in out[0][1]]
    core = out[1][0]
    assert core.spec_dispatches > 0 and \
        core.spec_drafted_tokens >= core.spec_dispatches
    assert core.spec_rewound_rows == (core.spec_drafted_tokens
                                      - core.spec_accepted_tokens)
    rec = [r for r in core.flight.dump() if r["kind"] == "decode"]
    assert rec and all(r["rows"] == 2 * r["batch_fill"] and r["K"] == 1
                       and r["emitted"] == r["batch_fill"] + r["accepted"]
                       for r in rec)
    assert not [r for r in core.flight.dump() if r["kind"] == "verify"]
    # the one-row engine holds no module
    assert not any(n.startswith("mtp.") for n in out[0][0].params)
    assert out[0][0].kv["k"].shape[0] == 2 and core.kv["k"].shape[0] == 3


def test_the_row_path_records_a_pass_a_row(served):
    """Off the kernel (the CPU's XLA gather, this fixture's 64-lane rows)
    every row of a slot is a sequence of its own: the ``decode`` record of a
    two-row step says two passes over a slot's cache."""
    core = served[1][1][0]
    rec = [r for r in core.flight.dump() if r["kind"] == "decode"]
    assert rec and {r["cache_passes"] for r in rec} == {2}
    assert llama.decode_cache_passes(core.statics, 1) == 1
    assert not any("cache_passes" in r for r in served[1][0][0].flight.dump())


def test_the_kernel_is_handed_a_slots_rows_in_one_call(monkeypatch):
    """At a width the kernel tiles (128-lane rows) the two-row step hands
    each of its three reads ONE sequence a slot with ``rows=2``: the last
    row's table, ring view and length, and a lower bound a row in that
    view's frame. Held here to the contract of ``rows`` (a call that stands
    for the rows as sequences of their own under the slot's table, row r
    seeing R - 1 - r keys fewer: ``tests/test_paged_attention_kernel.py``
    holds the kernel to the same contract), by which it gives the tokens,
    the draft logits and the pools of the row path. Three slots: one whose
    window still starts at the sequence's start (a bound that does not
    slide with the row), one whose rows lie in one block, one whose second
    row opens a block, past the window's edge and the ring's wrap."""
    cfg = ModelConfig.from_hf_config(_hf(head_dim=64, num_hidden_layers=4))
    bs, M, slots = 16, 4, 3
    xla = llama.ModelStatics(cfg=cfg, block_size=bs, attn_impl="xla",
                             table_blocks=M)
    kernel = dataclasses.replace(xla, attn_impl="pallas_interpret")
    assert mimo.decode_kernels_tile(cfg, bs)
    assert (llama.decode_cache_passes(kernel, 2),
            llama.decode_cache_passes(xla, 2)) == (1, 2)
    calls, real = [], mimo.paged_attention

    def by_the_contract(q, k, v, tables, lens, *, impl, name, rows=1,
                        win_lo=None, **kw):
        calls.append((name, impl, rows, tables.shape[0]))
        back = jnp.tile(jnp.arange(rows - 1, -1, -1, dtype=jnp.int32),
                        tables.shape[0])
        return real(q, k, v, jnp.repeat(tables, rows, axis=0),
                    jnp.repeat(lens, rows) - back, impl="xla", name=name,
                    win_lo=win_lo, **kw)

    monkeypatch.setattr(mimo, "paged_attention", by_the_contract)
    rng = np.random.default_rng(3)
    draw = lambda shape: jnp.asarray(  # noqa: E731
        0.05 * rng.standard_normal(shape), jnp.float32)
    params = {n: draw(shape) for n, shape in llama.param_shapes(cfg).items()}
    kv = {n: draw(a.shape) for n, a in llama.init_kv_cache(
        cfg, 16, bs, dtype=jnp.float32).items()}
    R = mla.swa_ring_blocks(cfg, bs)
    pos = np.asarray([15, 37, 47], np.int32)       # 15 + 1, 47 + 1: a block
    tables = 1 + np.stack([rng.permutation(15)[:M + R] for _ in range(slots)])
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=2 * slots),
                         jnp.int32)
    args = (tokens, jnp.asarray(np.repeat(pos, 2) + [0, 1] * slots),
            jnp.asarray(np.repeat(tables, 2, axis=0), jnp.int32))

    def sample(logits):
        return jnp.argmax(logits, -1).astype(jnp.int32), jnp.max(logits, -1)

    got, want = (jax.jit(lambda kv, s=s: mimo.decode_forward_mtp(
        params, kv, *args, s, sample, rows=2))(kv) for s in (kernel, xla))
    assert {c for c in calls if c[1] != "xla"} == {
        (name, "pallas_interpret", 2, slots)
        for name in ("gqa_full_read", "gqa_window_read", "mtp_full_read")}
    assert {c[2:] for c in calls if c[1] == "xla"} == {(1, 2 * slots)}
    assert np.array_equal(got[0], want[0])
    for g, w in zip(jax.tree.leaves(got[1:]), jax.tree.leaves(want[1:])):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < 1e-5 * max(
            1.0, float(np.abs(np.asarray(w)).max()))


# in float32 the engine stands ~1e-5 off the reference, so a breakage that
# served bf16 logits do not show with room to spare (``FINE``: small by
# nature under seeded weights) is still told apart here, at a tenth of the
# served tolerance: fifty times this file's rounding bound
F32_TOL = 0.025


@pytest.mark.parametrize("broken", ref.BREAKAGES)
def test_every_breakage_is_outside_the_tolerance(served, broken):
    prompts, out = served
    core, streams = out[1]
    tol = F32_TOL if broken in ref.FINE else reference.TOL_STD
    if broken in ref.MTP_BREAKAGES:
        prompt, (ids, _lps) = prompts[1], streams[1]
        seq = prompt + ids[:9]
        right = ref.mtp_logits_for(core.params, HF, seq, 4)
        wrong = ref.mtp_logits_for(core.params, HF, seq, 4, broken)
        assert max(check.distance(r, w) for r, w in zip(right, wrong)) > tol
        return
    worst = 0.0
    for prompt, (ids, lps) in zip(prompts, streams):
        rep = reference.compare(core.params, HF, prompt, ids[:8], lps[:8],
                                broken=broken, forward=ref.logits_for)
        worst = max(worst, rep["worst_logprob_err_std"],
                    rep["worst_argmax_gap_std"])
    assert worst > tol, worst


def test_the_served_breakages_are_those_of_the_main_head():
    assert set(ref.breakages_for(HF)) == (
        set(ref.BREAKAGES) - set(ref.MTP_BREAKAGES) - set(ref.FINE))
    assert set(ref.FINE) <= set(ref.BREAKAGES)


@pytest.mark.parametrize("block", ["main layer", "mtp block"])
def test_the_shares_add_up_to_the_uncut_layer(block):
    """The eight shares' routed parts + the shared expert once = the layer
    over all 32 experts: the engine's ``_moe_mlp`` of each share against the
    reference's uncut layer."""
    n_shares = CFG.router_width // CFG.num_experts
    whole = dataclasses.replace(CFG, num_experts=CFG.router_width,
                                num_experts_total=0)
    params = llama.init_params(whole, jax.random.PRNGKey(3), jnp.float32)
    prefix, li = ("layers.", 2) if block == "main layer" else ("mtp.", 0)
    names = ("router", "router_bias", "moe_gate", "moe_up", "moe_down",
             "sh_gate", "sh_up", "sh_down")
    full = {n: params[prefix + n][li] for n in names}
    x = jax.random.normal(jax.random.PRNGKey(4), (24, CFG.hidden_size),
                          jnp.float32)
    E = CFG.num_experts
    fam = ref.family(HF)
    shares, engine_sum = [], 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(n_shares):
            lw = {n: (w[i * E:(i + 1) * E] if n.startswith("moe_") else w)
                  for n, w in full.items()}
            shares.append(lw)
            cfg_i = dataclasses.replace(CFG, expert_share_index=i)
            engine_sum = engine_sum + mla._moe_mlp(x, lw, cfg_i,
                                                   sharded=False)
        shared = llama.swiglu(x, full["sh_gate"], full["sh_up"],
                              full["sh_down"])
        engine = engine_sum - (n_shares - 1) * shared
        by_shares = ref.uncut_moe(fam, x, shares)
        uncut = ref.moe_block({**fam, "held": CFG.router_width,
                               "first_held": 0})(x, full)
    scale = float(jnp.std(uncut))
    assert float(jnp.abs(by_shares - uncut).max()) / scale < 1e-4
    assert float(jnp.abs(engine - uncut).max()) / scale < 1e-4
    # one share alone is NOT the layer
    assert float(jnp.abs(mla._moe_mlp(x, shares[0], CFG, sharded=False)
                         - uncut).max()) / scale > 0.1


@pytest.mark.parametrize("case", ["chunked prefill", "prefix hit",
                                  "accepted and rejected steps"])
def test_draft_logits_are_the_references(case):
    """The engine's DRAFT logits, carried out of its compiled programs
    (``exaone_moe_check.Tap``), against ``mtp_logits_for`` on the tokens
    that were consumed."""
    prompt = _prompts((45,), seed=7)[0]
    kw = {"prefill_chunk": 16, "prefill_buckets": (16, 64)} \
        if case == "chunked prefill" else {}
    with check.Tap() as tap:
        core = _engine(1, **kw)
        out = check.drive(
            core, tap, prompt,
            steps=8 if case == "accepted and rejected steps" else 1,
            hit=32 if case == "prefix hit" else 0)
    assert len(out["drafts"]) > 1
    if case == "accepted and rejected steps":
        rows = [where.rsplit(" ", 1)[1] for where, _s, _l in out["drafts"][1:]]
        assert rows.count("1") >= 4 and rows.count("0") >= 3, rows
        rep = reference.compare(core.params, HF, prompt, out["ids"],
                                out["logprobs"], forward=ref.logits_for)
        assert rep["worst_logprob_err_std"] < EXACT, rep
    for where, seq, logits in out["drafts"]:
        want = ref.mtp_logits_for(core.params, HF, seq, 1)[0]
        assert np.abs(logits - want).max() / want.std() < EXACT, where


# ------------------------------------------------ exactness under the loop

class Forced:
    """Drafts by fiat, where a step reads them: the draft logits of both
    served programs (``model_mod.prefill_forward_mtp``, ``decode_forward_mtp``)
    become one-hot rows, so that the prefill's first draft and every
    step's ``drafts`` output, the carry a chained step is fed from
    included, are the token the row's stream holds next (``accept(q)`` of
    its position q: an accepted draft) or one it does not (rejected). A
    host callback looks the row up by (input token, position, sampled
    token) in the known sequences, so one compiled engine serves under any
    pattern (``use``). Patches the family's module: engines built inside
    the test that asked for the fixture."""

    def __init__(self, monkeypatch):
        self.known, self.accept = [], lambda q: True
        real = {n: getattr(llama, n)
                for n in ("prefill_forward_mtp", "decode_forward_mtp")}

        def forced(logits, inputs, positions, sampled):
            want = jax.pure_callback(
                self._lookup, jax.ShapeDtypeStruct(sampled.shape, jnp.int32),
                inputs, positions, sampled)
            return jax.nn.one_hot(want, logits.shape[-1], dtype=logits.dtype)

        def prefill(params, kv, tokens, table, start_pos, true_len, nxt,
                    statics, sample):
            tok, lp, logits, kv = real["prefill_forward_mtp"](
                params, kv, tokens, table, start_pos, true_len, nxt, statics,
                sample)
            last = jnp.maximum(true_len - 1, 0)
            return tok, lp, forced(logits[None], tokens[last][None],
                                   (start_pos + last)[None], tok[None])[0], kv

        def decode(params, kv, tokens, positions, tables, statics, sample,
                   **rows):
            toks, lps, logits, kv = real["decode_forward_mtp"](
                params, kv, tokens, positions, tables, statics, sample,
                **rows)
            return toks, lps, forced(logits, tokens, positions, toks), kv

        monkeypatch.setattr(llama, "prefill_forward_mtp", prefill)
        monkeypatch.setattr(llama, "decode_forward_mtp", decode)

    def use(self, prompts, streams, accept):
        self.known = [np.asarray(list(p) + list(ids))
                      for p, ids in zip(prompts, streams)]
        self.accept = accept

    def _lookup(self, inputs, positions, sampled):
        """Row (x at p, sampled t) lies in the sequence with x at p and t
        at p + 1 (a rejected row's is in none: whatever it drafts is
        dropped) → that sequence's token at p + 2, or a miss."""
        out = np.zeros(sampled.shape, np.int32)
        for i, (x, p, t) in enumerate(zip(inputs, positions, sampled)):
            for seq in self.known:
                if p + 2 < len(seq) and seq[p] == x and seq[p + 1] == t:
                    miss = not self.accept(int(p) + 2)
                    out[i] = (seq[p + 2] + miss) % CFG.vocab_size
                    break
        return out


@pytest.fixture
def forced(monkeypatch):
    return Forced(monkeypatch)


ACCEPT = {"accepted": lambda q: True, "alternating": lambda q: q % 3 != 0,
          "rejected": lambda q: False}


def hold_the_window(core):
    """Holds the release rule before every step: the window blocks that the
    step's rows read, wherever the step in flight leaves them (the union
    over the positions it may advance by), are all held, in a ring that no
    two of them share an entry of. → {"held": slots checked, "ahead":
    those with a step in flight}."""
    bs, W = core.cfg.kv_block_size, core.model_cfg.swa_window
    rows = core.cfg.spec_k + 1
    state = {"held": 0, "ahead": 0, "most": 0}
    prepare = core._prepare_multi

    def prepared(K, ahead_mask=None, sit_out=None):
        ok = prepare(K, ahead_mask=ahead_mask, sit_out=sit_out)
        for i, s in enumerate(core.slots):
            if s is None or not s.ready or (sit_out is not None
                                            and sit_out[i]):
                continue
            ahead = bool(ahead_mask is not None and ahead_mask[i])
            if not ok and ahead:
                continue          # drained: prepared again from its harvest
            lo, hi = s.pos + ahead, s.pos + rows * (1 + ahead)
            need = range(max(0, lo - (W - 1)) // bs, (hi - 1) // bs + 1)
            assert all(b in s.win.held for b in need), (s.pos, s.win.held)
            assert len(s.win.held) <= core.R
            assert len({b % core.R for b in s.win.held}) == len(s.win.held)
            state["held"] += 1
            state["ahead"] += ahead
            state["most"] = max(state["most"], len(s.win.held))
        return ok

    core._prepare_multi = prepared
    return state


@pytest.mark.parametrize("drafts", ["accepted", "alternating", "rejected"])
def test_forced_drafts_leave_the_stream_as_it_is(served, forced, drafts):
    """Across the window's edge, a ring wrap and block boundaries: every
    draft accepted (two tokens a step, the window advancing by two, two
    tokens registered in a block), two of three, none. The steps are
    chained: acceptance is the program's own."""
    prompts, out = served
    want = [ids for ids, _ in out[0][1]]
    core = _engine(1)
    forced.use(prompts, want, ACCEPT[drafts])
    state = hold_the_window(core)
    got = asyncio.run(_serve(core, prompts, 40))
    assert [ids for ids, _ in got] == want
    assert state["held"] > 0 and state["ahead"] > 0.8 * state["held"]
    emitted = core.spec_emitted_tokens
    if drafts == "accepted":
        # but for a request's last step, whose second token is over budget
        assert core.spec_accepted_tokens >= 0.9 * (emitted / 2)
        assert core.spec_dispatches <= 0.62 * 39
    if drafts == "rejected":
        assert core.spec_accepted_tokens == 0
    assert core.spec_rewound_rows == (core.spec_drafted_tokens
                                      - core.spec_accepted_tokens)
    # the logprobs are the one-row engine's too
    for (_, a), (_, b) in zip(got, out[0][1]):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("drafts", ["model", "alternating"])
def test_a_preemption_leaves_the_stream_as_it_is(request, drafts):
    """A paged pool too small for three growing sequences: one is preempted
    and prefilled again (its re-prefill returns a draft), with the module's
    own drafts and with every other one forced to be accepted. Sampled, not
    greedy: the lockstep keys are held too."""
    prompts = _prompts((30, 28, 26), seed=11)
    samp = SlotSampling(temperature=0.8, top_k=20, seed=3)
    small = {"num_kv_blocks": 11}
    base = _engine(0, **small)
    want = asyncio.run(_serve(base, prompts, 36, samp))
    core = _engine(1, **small)
    if drafts == "alternating":
        request.getfixturevalue("forced").use(
            prompts, [ids for ids, _ in want], ACCEPT["alternating"])
    got = asyncio.run(_serve(core, prompts, 36, samp))
    assert base.preemptions > 0 and core.preemptions > 0
    # a growth that fails under a step in flight preempts nothing: it
    # drains, and the fresh step preempts from harvested state
    assert core.pipeline_drains.get("kv_growth", 0) > 0
    assert [ids for ids, _ in got] == [ids for ids, _ in want]


def test_a_prefix_hit_brings_the_modules_rows():
    """The module's rows lie in the paged group under the same block ids: a
    second request over the first one's prompt hits its blocks, prefills
    the rest alone, and drafts as if it had prefilled everything."""
    prompt = _prompts((70,), seed=5)[0]
    first, again = prompt[:52] + [1, 2, 3], prompt
    core = _engine(1)

    async def both():
        a = await _serve_keep(core, first)
        b = await _serve_keep(core, again)
        await core.stop()
        return a, b

    async def _serve_keep(core, p):
        req = EngineRequest(rid=f"h{len(p)}", prompt=list(p),
                            sampling=SlotSampling(temperature=0.0),
                            max_new_tokens=6, eos_ids=frozenset())
        await core.submit(req)
        ids = []
        while True:
            tok, _lp = await req.out_queue.get()
            if tok is FINISH_SENTINEL:
                return req, ids
            ids.append(tok)

    (_ra, _ia), (rb, ids_b) = asyncio.run(both())
    assert rb.prefix_hit_tokens == 32   # 3 blocks match, the last is computed again
    cold = _engine(1)
    (ids_cold, _), = asyncio.run(_serve(cold, [again], 6))
    assert ids_b == ids_cold
    # the drafts were the same too: as many accepted, step for step
    hit_steps = [(r["emitted"], r["accepted"]) for r in core.flight.dump()
                 if r["kind"] == "decode"][-len(ids_b) + 1:]
    cold_steps = [(r["emitted"], r["accepted"]) for r in cold.flight.dump()
                  if r["kind"] == "decode"]
    assert hit_steps == cold_steps[-len(hit_steps):]


# ------------------------------------ the other families' programs stay

# sha256 of the lowered text of ``_prefill_jit`` (smallest bucket) and
# ``_decode_k_jit`` of an engine built through the launcher's flags on the
# fixture, drawn weights, --quantization none: at the parent commit 4ac4c06
# (my CPU run, PR 50: /root/scratch/sha_lowered.py on a clone of the parent).
# tiny-mimo-v2's pair is PR 54's: ``mla.layer_plan``'s one rule scans the
# fixture's run of four window layers (S S S S | F S) where the rule before
# unrolled all six; the configurations the benchmark serves keep their plan
# (tests/test_kimi_linear.py), and tiny-dots3-note's pair is still 4ac4c06's
PARENT_SHA = {
    ("tiny-mimo-v2", "prefill"):
        "6dc0b3787d4cb2e257125020ecc872927154c71d48550284a48ed03ba3aeb963",
    ("tiny-mimo-v2", "decode"):
        "de4df886ee409dcecb14367e0040867191e49494bbf18266bb91ca94736c5fa1",
    ("tiny-dots3-note", "prefill"):
        "75e15961a7590ef2fa6ed6c48c305a1b3073c86d368c0d654cb2766716d89c72",
    ("tiny-dots3-note", "decode"):
        "a7928e4821dd96774a71a5ab47558a827a2a284360210f893a1268f0db449ea4",
}


def lowered_sha(name: str) -> dict:
    """{"prefill" | "decode": sha256 of the program's lowered text}."""
    from dynamo_tpu.engine import models
    from dynamo_tpu.launch import run as launcher
    with open(os.path.join(BENCH, "fixtures", name + ".json")) as f:
        config = json.load(f)
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in config.items() if k not in BENCH_KEYS})
    engine_cfg = dataclasses.replace(launcher.engine_config(
        launcher.build_parser().parse_args(
            ["in=http", "out=jax", *config["deployment"]["flags"]])),
        quantization="none")
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(0.02 * rng.standard_normal(shape), jnp.bfloat16)
              for k, shape in models.module_for(cfg).param_shapes(cfg).items()}
    core = EngineCore(cfg, engine_cfg, params=params)
    s = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    tree = lambda t: jax.tree.map(s, t)                   # noqa: E731
    i32, f32 = jnp.int32, jnp.float32
    scalar = lambda d: jax.ShapeDtypeStruct((), d)        # noqa: E731
    B, K = core.B, core.cfg.decode_steps_per_dispatch
    bucket = min(core.cfg.prefill_buckets)
    vec = lambda d: jax.ShapeDtypeStruct((B,), d)         # noqa: E731
    key = s(jax.random.PRNGKey(0))
    prefill = core._prefill_jit.lower(
        tree(core.params), tree(core.kv),
        jax.ShapeDtypeStruct((bucket,), i32),
        s(core._prefill_table([], 0)), scalar(i32), scalar(i32), key,
        scalar(f32), scalar(i32), scalar(f32)).as_text()
    decode = core._decode_k_jit.lower(
        tree(core.params), tree(core.kv), vec(i32), vec(i32),
        s(core._block_tables), vec(jnp.int64), vec(jnp.int64), vec(f32),
        vec(i32), vec(f32), jax.ShapeDtypeStruct((K, B), i32),
        jax.ShapeDtypeStruct((K, B), jnp.bool_), key).as_text()
    return {"prefill": hashlib.sha256(prefill.encode()).hexdigest(),
            "decode": hashlib.sha256(decode.encode()).hexdigest()}


@pytest.mark.parametrize("name", ["tiny-mimo-v2", "tiny-dots3-note"])
def test_the_sibling_families_lower_to_the_parents_programs(name):
    """The new fields of the configuration (output norms, QK-norm, NoPE, a
    prefix of either kind, the module) leave the programs of the two
    families that share ``walk_layer_kinds`` and ``models/mimo.py`` as they
    were, byte for byte."""
    got = lowered_sha(name)
    assert got == {p: PARENT_SHA[name, p] for p in ("prefill", "decode")}
