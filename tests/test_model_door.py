"""The one door into a model family (``engine/models/__init__.py``
``module_for``), held on the nine configurations of
``benchmark/fixtures/tiny-*.json`` under the launcher's flags: the module
each is served by, what that module refuses, the pool a replay builds
against the engine's own, and the key sets of the ``prefill`` and ``decode``
flight records (the benchmark's per-layer readers read them by name).

The first two read the configurations alone. The other two share one engine
a fixture (module scope) and its one served request, recorded and replayed:
one prefill bucket and the decode step compiled on each. The engines take
drawn weights at ``--quantization none`` (the fixtures' int8 costs ~90
small compiles an engine and moves none of what is held here). A file of its
own, so that ``--dist loadfile`` gives it a worker.
"""

import asyncio
import dataclasses
import glob
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import models
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.models import (granite_hybrid, kimi_linear, llama, mla,
                                      sambay)

FIXTURE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "fixtures")
# keys of a fixture that are the benchmark's own, not the config.json's
BENCH_KEYS = ("source", "reduced", "assumed", "deployment",
              "memory_analysis", "notes", "reference")

# fixture -> (the module that serves it, its arrays, its own keys of a
# prefill record, its own keys of a decode record)
FAMILIES = {
    "tiny-dense": (llama, {"k", "v"}, set(), set()),
    "tiny-qwen2moe": (llama, {"k", "v"}, set(), set()),
    "tiny-mimo-v2": (llama, {"k", "v", "win_k", "win_v"}, set(), set()),
    # served with its multi-token-prediction module resident (--spec-k 1):
    # its decode step scores two rows a slot and says so, and how many
    # passes over a slot's cache a read made for them
    "tiny-exaone-moe": (llama, {"k", "v", "win_k", "win_v"}, set(),
                        {"rows", "accepted", "cache_passes"}),
    "tiny-deepseek-v2": (mla, {"kv"}, set(), set()),
    "tiny-kimi-k2": (mla, {"kv"}, set(), set()),
    "tiny-deepseek-v32": (mla, {"kv", "idx"},
                          {"dsa_blocks", "dsa_blocks_run"},
                          {"key_waves", "key_run_waves"}),
    "tiny-dots3-note": (mla, {"kv", "idx", "win"},
                        {"dsa_blocks", "dsa_blocks_run"},
                        {"key_waves", "key_run_waves"}),
    "tiny-phi4flash": (sambay, {"k", "v", "win_k", "win_v", "ssm", "conv"},
                       set(), set()),
    "tiny-kimi-linear": (kimi_linear, {"kv", "kda", "conv"}, {"kda_chunks"},
                         set()),
    "tiny-granite-moe-hybrid": (granite_hybrid, {"k", "v", "ssd", "conv"},
                                {"ssd_chunks"}, set()),
}

# the keys every family's records carry, as at PR 48 (the parent of the PR
# that moved the families' counters into their modules)
PREFILL_KEYS = {
    "kind", "t", "rid", "prompt", "planned_tokens", "batch_fill",
    "hit_device", "hit_host", "hit_disk", "hit_remote", "hit_tokens",
    "hit_cut_tokens", "precomputed", "grouped_rows", "scan_tokens",
    "key_tokens", "host_ms", "dispatch_ms", "wait_ms", "queue_wait_ms"}
DECODE_KEYS = {
    "kind", "t", "K", "batch_fill", "chained", "planned_tokens", "emitted",
    "ctx_tokens", "sel_tokens", "win_tokens", "win_blocks_live",
    "state_bytes", "device_ms", "host_gap_ms", "sweep_ms", "complete_ms",
    "admit_ms", "build_ms", "dispatch_ms", "wait_ms", "post_ms", "yield_ms",
    "admits", "admit_tokens", "yield_iters",
    # the build log's running totals, on every cycle record since PR 56
    "built", "built_ms", "built_trace_ms"}
# a decode record says why no successor was launched behind it, where none
# was: present on some cycles only
SOMETIMES = {"drain"}

PROMPT_TOKENS, NEW_TOKENS = 24, 4


def test_the_fixtures_are_those_the_table_names():
    found = {os.path.basename(p)[:-5]
             for p in glob.glob(os.path.join(FIXTURE_DIR, "tiny-*.json"))}
    assert found == set(FAMILIES)


def _configs(name):
    """(ModelConfig, EngineConfig) of a fixture, through the launcher's
    flags, as ``benchmark/server.py`` builds them."""
    from dynamo_tpu.launch import run as launcher
    with open(os.path.join(FIXTURE_DIR, name + ".json")) as f:
        config = json.load(f)
    cfg = ModelConfig.from_hf_config(
        {k: v for k, v in config.items() if k not in BENCH_KEYS})
    engine_cfg = launcher.engine_config(launcher.build_parser().parse_args(
        ["in=http", "out=jax", *config["deployment"]["flags"]]))
    return cfg, engine_cfg


def _engine(name, own_weights=False):
    from dynamo_tpu.engine.core import EngineCore
    cfg, engine_cfg = _configs(name)
    if own_weights:
        return EngineCore(cfg, engine_cfg)
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(0.02 * rng.standard_normal(shape), jnp.bfloat16)
              for k, shape in models.module_for(cfg).param_shapes(cfg).items()}
    return EngineCore(cfg, dataclasses.replace(engine_cfg,
                                               quantization="none"),
                      params=params)


async def _serve_one(core, prompt):
    from dynamo_tpu.engine.core import FINISH_SENTINEL, EngineRequest
    from dynamo_tpu.engine.sampling import SlotSampling
    req = EngineRequest(rid="door", prompt=prompt,
                        sampling=SlotSampling(temperature=0.0),
                        max_new_tokens=NEW_TOKENS, eos_ids=frozenset())
    try:
        await core.submit(req)
        while (await req.out_queue.get())[0] is not FINISH_SENTINEL:
            pass
    finally:
        await core.stop()


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_module_for_names_the_module(name):
    cfg, _engine_cfg = _configs(name)
    assert models.module_for(cfg) is FAMILIES[name][0]


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_module_refuses_nothing_of_its_own_deployment(name):
    cfg, engine_cfg = _configs(name)
    refusals = models.module_for(cfg).refusals
    assert refusals(cfg, engine_cfg, None) == []
    # ... and, but for the families every path was built on, a flag that is
    # not the deployment's
    ragged = refusals(cfg, dataclasses.replace(engine_cfg,
                                               ragged_dispatch=True), None)
    assert [r.split(" ", 1)[0] for r in ragged] == (
        [] if name in ("tiny-dense", "tiny-qwen2moe", "tiny-deepseek-v2",
                       "tiny-kimi-k2") else ["--ragged"])


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def built(request):
    """(fixture name, its engine)."""
    yield request.param, _engine(request.param)


def test_a_replay_builds_the_engines_pool(built):
    """Keys, shapes and dtypes: at PR 48 a phi4flash core replayed on a
    llama pool, and a dots3_note / mimo_v2 core on a window group sized by
    the module's default, not by the engine's ``window_pool_blocks``."""
    name, core = built
    assert core.model_mod is FAMILIES[name][0]
    assert set(core.kv) == FAMILIES[name][1]
    fresh = core.fresh_kv()[0]      # replay.replay's own first line
    assert ({k: (v.shape, v.dtype) for k, v in fresh.items()}
            == {k: (v.shape, v.dtype) for k, v in core.kv.items()})
    assert all(fresh[k] is not core.kv[k] for k in fresh)


def _serve_and_replay(core):
    """Serves one recorded request; -> what differs when the recorded
    schedule runs again on the pool a replay builds."""
    from dynamo_tpu.engine import replay
    core.recorder = replay.Recorder()
    asyncio.run(_serve_one(core, [1 + i % (core.model_cfg.vocab_size - 1)
                                  for i in range(PROMPT_TOKENS)]))
    events = core.recorder.events
    return replay.compare_replay(events, replay.replay(core, events))


def test_int8_weights_do_not_set_the_replays_pool_dtype():
    """At PR 48 the replay's pool took the dtype of the parameter tree's
    first leaf: the int8 embedding's, under every fixture's own flags."""
    assert _serve_and_replay(_engine("tiny-dense", own_weights=True)) == []


def test_a_served_request_keeps_its_record_keys_and_replays(built):
    name, core = built
    module, _arrays, prefill_own, decode_own = FAMILIES[name]
    differs = _serve_and_replay(core)
    records = core.flight.dump()
    prefill = [r for r in records if r["kind"] == "prefill"]
    decode = [r for r in records if r["kind"] == "decode"]
    assert len(prefill) == 1 and decode
    assert set(prefill[0]) == PREFILL_KEYS | prefill_own
    for r in decode:
        assert set(r) - SOMETIMES == DECODE_KEYS | decode_own
    # the families' counters, by the arithmetic PERF.md section 3 states
    n = PROMPT_TOKENS
    stateful = module in (sambay, kimi_linear, granite_hybrid)
    assert prefill[0]["scan_tokens"] == (n if stateful else 0)
    assert prefill[0]["key_tokens"] == (
        n * (n + 1) // 2 if module in (kimi_linear, granite_hybrid)
        or (module is mla and not prefill_own) else 0)
    if "dsa_blocks" in prefill_own:
        assert 0 < prefill[0]["dsa_blocks_run"] <= prefill[0]["dsa_blocks"]
    if "kda_chunks" in prefill_own:
        assert prefill[0]["kda_chunks"] >= 1
    if "ssd_chunks" in prefill_own:
        assert prefill[0]["ssd_chunks"] == 1
    assert differs == []
