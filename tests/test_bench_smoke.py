"""Smoke tests for the driver entry points: bench.py and __graft_entry__.

Both driver artifacts once crashed because neither was covered by a test —
bench.py drifted from the engine's decode_k signature, and dryrun_multichip
never forced the CPU platform. These tests import and RUN both on the tiny
model so any future signature or platform drift fails CI instead of the
driver run.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, env_extra, timeout=600):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout,
                          capture_output=True, text=True)


def test_bench_runs_and_prints_json():
    """bench.py end to end on FORCED CPU with the tiny model
    (BENCH_FORCE_CPU: a run that is not forced refuses any platform but
    a TPU): one compile dispatch + a couple of timed dispatches, then
    the driver's ONE JSON line.

    --spec=2 rides the same run (ISSUE 2 satellite): the line must then
    also carry the `spec` provenance dict — measured acceptance and
    effective tok/s next to the baseline row — at the marginal cost of
    the verify-program compile instead of a second engine build."""
    r = _run(
        [sys.executable, "bench.py", "--spec=2"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "4",
         "BENCH_STEPS": "8", "BENCH_PROMPT": "16", "BENCH_HARVEST": "4",
         "BENCH_QUANT": "none"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    lines = [l for l in r.stdout.strip().splitlines()
             if l.startswith("{")]
    assert lines, f"no JSON line in bench output: {r.stdout!r}"
    out = json.loads(lines[-1])
    for field in ("metric", "value", "unit", "vs_baseline"):
        assert field in out
    assert out["value"] > 0
    assert "error" not in out, out
    assert out["extra"]["platform"] == "cpu"
    # a CPU timing is never divided by a TPU's peaks
    assert not {"hbm_util", "mfu", "weights_read_bw_util"} & set(
        out["extra"])
    spec = out.get("spec")
    assert spec, f"no spec provenance in the result: {out}"
    assert spec["k"] == 2
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    for field in ("accepted_per_step", "emitted_per_step",
                  "effective_tok_per_s", "device_verify_step_ms"):
        assert field in spec, f"missing spec field {field}: {spec}"
    # a verify dispatch emits at least one token per slot per step
    assert spec["emitted_per_step"] >= 1.0
    assert spec["effective_tok_per_s"] > 0


def test_bench_kv_disk_mode(tmp_path):
    """--kv-disk rides a bench run (ISSUE 3 satellite): the result line
    must carry the `kv_disk` provenance dict — cold vs warm-restart TTFT
    against a tmpdir disk tier, with the warm run actually hitting the
    disk and the token streams bit-exact."""
    import pytest
    if os.environ.get("CI_SKIP_SLOW"):
        pytest.skip("slow smoke")
    r = _run(
        [sys.executable, "bench.py", "--kv-disk"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_KV_DISK_PROMPT": "32",
         "BENCH_KV_DISK_DIR": str(tmp_path / "kvdisk")})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    kd = out.get("kv_disk")
    assert kd, f"no kv_disk provenance in the result: {out}"
    assert kd["cold_hit_tokens"] == 0
    assert kd["warm_hit_tokens"] >= 16          # prefix came from disk
    assert kd["warm_restart_onboards"] >= 1     # onboarded, not recomputed
    assert kd["disk_blocks_after_cold"] >= 1
    assert kd["tokens_bit_exact"] is True
    assert kd["cold_ttft_ms"] > 0 and kd["warm_ttft_ms"] > 0


@pytest.mark.kvfabric
def test_bench_kv_remote_mode():
    """--kv-remote rides a bench run (ISSUE 6 satellite): the result
    line must carry the `kv_remote` provenance dict — cold-prefill vs
    remote-fetch TTFT over a REAL loopback kv_fabric RPC, bit-exact,
    with the admission model's predicted fetch/recompute/crossover
    reported next to the measured link."""
    import pytest as _pytest
    if os.environ.get("CI_SKIP_SLOW"):
        _pytest.skip("slow smoke")
    r = _run(
        [sys.executable, "bench.py", "--kv-remote"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_KV_REMOTE_PROMPT": "128"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    kr = out.get("kv_remote")
    assert kr, f"no kv_remote provenance in the result: {out}"
    assert kr["remote_hit_tokens"] >= 16        # prefix came over the wire
    assert kr["fetched_blocks"] >= 1 and kr["peer_fetches"] >= 1
    assert kr["tokens_bit_exact"] is True
    assert kr["cold_ttft_ms"] > 0 and kr["remote_ttft_ms"] > 0
    assert kr["measured_link_gbps"] > 0
    assert kr["admission_auto_verdict"] in ("admit", "reject")
    assert kr["predicted_fetch_ms"] > 0
    # ISSUE 12 satellite: the dataplane-vs-JSON A/B leg — the native
    # transport moves byte-identical payloads (same count both legs,
    # JSON's base64 framing inflates its wire bytes) with no fallback;
    # both legs' fetch times are printed, neither is gated here
    assert kr["dataplane_bytes"] == kr["json_bytes"] > 0
    assert kr["dataplane_fetches_total"] >= 1
    assert kr["dataplane_fallbacks_total"] == 0


@pytest.mark.kvfabric
def test_bench_disagg_stream_mode():
    """--disagg-stream rides a bench run (ISSUE 18 satellite): the result
    line must carry the `disagg_stream` provenance dict — the monolithic
    vs layer-streamed P→D handoff TTFT A/B over a REAL loopback TCP
    dial-back, bit-exact both legs, with the measured hidden/exposed
    transfer split reported next to the pricing model's prediction."""
    if os.environ.get("CI_SKIP_SLOW"):
        pytest.skip("slow smoke")
    r = _run(
        [sys.executable, "bench.py", "--disagg-stream"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_DISAGG_STREAM_PROMPT": "64",
         "BENCH_DISAGG_STREAM_ITERS": "3"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    ds = out.get("disagg_stream")
    assert ds, f"no disagg_stream provenance in the result: {out}"
    # both legs must produce the same greedy tokens or the TTFT A/B is
    # comparing diverged programs
    assert ds["tokens_bit_exact"] is True
    assert ds["stream_admits"] >= 1
    assert ds["stream_fallbacks"] == 0, (
        "the streamed leg degraded to monolithic mid-bench — the A/B "
        f"measured a mixed path: {ds}")
    # overlap must actually hide transfer behind prefill compute; which
    # leg's TTFT is lower is a speed result and only a chip run gives it
    assert ds["transfer_hidden_ms"] > 0, ds
    assert ds["mono_ttft_ms"] > 0 and ds["stream_ttft_ms"] > 0
    assert ds["layers"] >= 2 and ds["predicted_exposed_ms"] >= 0


@pytest.mark.kvfrag
def test_bench_kv_frag_mode():
    """--kv-frag rides a bench run (ISSUE 5 satellite): the result line
    must carry the `kv_frag` provenance dict — the CPU-side DMA-copy
    A/B between the run-allocator's contiguous layout and the reversed
    (fragmented) permutation of the same blocks. The always-on
    acceptance gate: coalescing cuts issued DMA copies >= 2x on the
    contiguous pool. (The device step-time A/B rides only on real
    hardware; this CPU smoke asserts the counting gate.)

    BENCH_KV_BS pins block_size 16 (the tiny geometry is small-C and
    would default to 64-token blocks, collapsing the smoke's short
    sequences into a single block — nothing to coalesce)."""
    r = _run(
        [sys.executable, "bench.py", "--kv-frag"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "4",
         "BENCH_STEPS": "8", "BENCH_PROMPT": "64", "BENCH_HARVEST": "4",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0", "BENCH_KV_BS": "16"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    kf = out.get("kv_frag")
    assert kf, f"no kv_frag provenance in the result: {out}"
    assert kf["waves"] > 0 and kf["coalesced_waves"] > 0
    assert kf["dma_copies_contig"] < kf["dma_copies_frag"]
    # the acceptance criterion's always-on CPU gate
    assert kf["dma_copy_ratio"] >= 2.0, kf
    assert kf["dma_copies_per_wave_frag"] > kf["dma_copies_per_wave_contig"]


def test_bench_pp_mode():
    """--pp rides a bench run (ISSUE 4): BENCH_FORCE_CPU forces a
    pp-sized virtual CPU mesh (the 8-device dryrun precedent) and the
    result line must carry the `pp` provenance dict — the interleaved
    loop's greedy-token equality with the single-device chain, the
    schedule's tick count and utilization model, and the modeled DCN
    boundary economics. Counts and correctness only: a step time on the
    CPU mesh is printed, never gated."""
    r = _run(
        [sys.executable, "bench.py", "--pp=2"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_PP_SEQ": "64", "BENCH_PP_HARVEST": "4"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    pp = out.get("pp")
    assert pp, f"no pp provenance in the result: {out}"
    assert pp["pp"] == 2 and pp["microbatch"] == pp["batch"] // 2
    # the interleaved loop must agree token-for-token with the
    # single-device chain, or the bench times a diverged program
    assert pp["tokens_match"] is True
    assert pp["v2_interleaved_step_ms"] > 0
    assert pp["dispatch_ticks"] == 4 * 2 + 1
    assert 0.0 < pp["bubble_fraction"] < 0.2
    assert pp["utilization_model"] == pytest.approx(8 / 9, abs=1e-3)
    dcn = pp["dcn"]
    assert dcn["boundary_bytes"] == pp["microbatch"] * 256 * 2
    assert dcn["nominal_tok_per_s"] > 0
    assert dcn["worst_corner_tok_per_s"] > 0


@pytest.mark.ragged
def test_bench_ragged_mode():
    """--ragged rides a bench run (ISSUE 10 satellite): the result line
    must carry the `ragged` provenance dict — the mixed-traffic A/B
    between the split prefill/decode program path and the unified
    ragged dispatch. The acceptance gates: FEWER dispatches per emitted
    token, a REDUCED compiled-program count (one ragged program vs the
    per-bucket prefill family + decode), genuinely mixed batches, and
    stream agreement up to each request's first numeric boundary."""
    if os.environ.get("CI_SKIP_SLOW"):
        pytest.skip("slow smoke")
    r = _run(
        [sys.executable, "bench.py", "--ragged"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_RAGGED_BATCH": "4", "BENCH_RAGGED_PROMPT": "48",
         "BENCH_RAGGED_SEQ_ROWS": "16"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    rg = out.get("ragged")
    assert rg, f"no ragged provenance in the result: {out}"
    # the acceptance criteria's always-on CPU gates
    assert rg["ragged_dispatches_per_token"] \
        < rg["split_dispatches_per_token"], rg
    assert rg["ragged_compiled_programs"] \
        < rg["split_compiled_programs"], rg
    assert rg["ragged_dispatches_saved"] >= 1
    assert 0.0 < rg["ragged_fill_ratio"] <= 1.0
    assert rg["ragged_mixed_ratio"] > 0.0, (
        "the staggered workload never mixed prefill rows into a decode "
        "dispatch — the A/B measured nothing ragged")
    assert rg["tokens_exact_to_boundary"] is True


@pytest.mark.ragged
def test_bench_ragged_spec_leg():
    """--ragged --spec combination leg (round 11): the result's ragged
    dict must carry the `spec` sub-dict — the split spec path (prefill
    + decode + verify programs) vs spec spans riding the ONE ragged
    program. Acceptance gates: compiled programs stay 1, dispatches per
    emitted token strictly below the split spec path under mixed
    traffic, drafts actually accepted, and a positive wave-prefetch
    hit ratio."""
    if os.environ.get("CI_SKIP_SLOW"):
        pytest.skip("slow smoke")
    r = _run(
        [sys.executable, "bench.py", "--ragged", "--spec=3"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0",
         "BENCH_RAGGED_BATCH": "4", "BENCH_RAGGED_PROMPT": "48",
         "BENCH_RAGGED_REQUESTS": "8", "BENCH_RAGGED_SEQ_ROWS": "16"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    out = json.loads([l for l in r.stdout.strip().splitlines()
                      if l.startswith("{")][-1])
    assert "error" not in out, f"bench fell back instead of running: {out}"
    sp = out.get("ragged", {}).get("spec")
    assert sp, f"no ragged spec leg in the result: {out.get('ragged')}"
    assert sp["ragged_compiled_programs"] == 1, (
        "ragged×spec must stay at ONE compiled program — the verify "
        "program's flattening IS a ragged batch")
    assert sp["ragged_spec_dispatches_per_token"] \
        < sp["split_spec_dispatches_per_token"], sp
    assert sp["ragged_spec_accepted"] > 0, (
        "repetitive workload accepted zero drafts through ragged spans")
    assert sp["ragged_spec_rows"] > 0
    assert sp["prefetch_hit_ratio"] > 0.0, (
        "concurrent spans never chained a wave prefetch")
    assert sp["tokens_exact_to_boundary"] is True


def test_bench_mla_geometry_runs():
    """The MLA bench path (latent {"kv"} pool, absorbed-decode flop
    accounting): bench.py must run the deepseek-class geometry — the
    device-truth run uses BENCH_MODEL=mla; this smokes the same code
    with CI-sized shapes."""
    r = _run(
        [sys.executable, "bench.py"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny_mla",
         "BENCH_BATCH": "2", "BENCH_STEPS": "4", "BENCH_PROMPT": "16",
         "BENCH_HARVEST": "2", "BENCH_QUANT": "none"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    lines = [l for l in r.stdout.strip().splitlines()
             if l.startswith("{")]
    out = json.loads(lines[-1])
    assert out["value"] > 0 and "error" not in out
    assert "tiny_mla" in out["metric"]


def test_bench_pipelined_and_unpipelined():
    """Both harvest modes run (the round-1 breakage was in the multi-step
    dispatch path specifically)."""
    for pipeline in ("0", "1"):
        r = _run(
            [sys.executable, "bench.py"],
            {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny",
             "BENCH_BATCH": "2", "BENCH_STEPS": "4", "BENCH_PROMPT": "8",
             "BENCH_HARVEST": "2", "BENCH_PIPELINE": pipeline,
             "BENCH_QUANT": "none"})
        assert r.returncode == 0, (
            f"bench.py pipeline={pipeline} crashed:\n{r.stderr[-4000:]}")
        out = json.loads([l for l in r.stdout.strip().splitlines()
                          if l.startswith("{")][-1])
        assert "error" not in out, (
            f"pipeline={pipeline} fell back instead of running: {out}")


def _result_lines(stdout: str) -> list:
    return [l for l in stdout.strip().splitlines() if l.startswith("{")]


def test_bench_failure_exits_nonzero_without_result_line():
    """A failed bench is a traceback and a non-zero exit — never a result
    line (no replayed history, no zero-valued placeholder a reader could
    take for a measurement)."""
    r = _run([sys.executable, "bench.py"],
             {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "no_such_model"})
    assert r.returncode != 0
    assert not _result_lines(r.stdout), r.stdout
    assert "Traceback" in r.stderr and "no_such_model" in r.stderr


def test_device_peaks_unknown_kind_is_an_error(monkeypatch):
    """A device kind with no row in DEVICE_PEAKS raises; it is never
    priced against another chip's peaks."""
    monkeypatch.syspath_prepend(REPO)
    import importlib
    bench = importlib.import_module("bench")
    assert bench._device_peaks("TPU v5 lite")[0] == 197e12
    with pytest.raises(ValueError, match="no peak specs"):
        bench._device_peaks("TPU v99 imaginary")
    with pytest.raises(ValueError, match="no peak specs"):
        bench._device_peaks("cpu")


def test_bench_unforced_run_on_cpu_host_exits_nonzero():
    """Without BENCH_FORCE_CPU a host where JAX finds no TPU is an
    error: the bench never reports a CPU run as device truth."""
    r = _run([sys.executable, "bench.py"],
             {"JAX_PLATFORMS": "cpu", "BENCH_FORCE_CPU": "0",
              "BENCH_MODEL": "tiny"}, timeout=120)
    assert r.returncode != 0
    assert not _result_lines(r.stdout), r.stdout
    assert "needs a TPU" in r.stderr


def _tree_state() -> str:
    """What git calls the checkout's state, untracked files included."""
    return subprocess.run(
        ["git", "status", "--porcelain", "--untracked-files=all"],
        cwd=REPO, capture_output=True, text=True, check=True).stdout


def test_bench_run_leaves_the_checkout_clean():
    """A successful run writes no tracked or untracked file into the
    checkout (the compile cache lives in a git-ignored directory)."""
    before = _tree_state()
    r = _run(
        [sys.executable, "bench.py"],
        {"BENCH_FORCE_CPU": "1", "BENCH_MODEL": "tiny", "BENCH_BATCH": "2",
         "BENCH_STEPS": "4", "BENCH_PROMPT": "8", "BENCH_HARVEST": "2",
         "BENCH_QUANT": "none", "BENCH_DEVICE": "0"})
    assert r.returncode == 0, f"bench.py crashed:\n{r.stderr[-4000:]}"
    assert _result_lines(r.stdout)
    assert _tree_state() == before


def test_dryrun_multichip_forces_cpu():
    """dryrun_multichip(8) in a fresh process with NO helpful env: the
    function itself must force the CPU platform + device count (the round-1
    failure was relying on the caller to do it)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO, env=env, timeout=600, capture_output=True, text=True)
    assert r.returncode == 0, f"dryrun crashed:\n{r.stderr[-4000:]}"
    assert "dryrun_multichip OK" in r.stdout


def test_entry_compiles():
    """entry() returns a jittable fn + args that run single-device
    (forced CPU: the test does not depend on a chip)."""
    r = _run(
        [sys.executable, "-c",
         "import __graft_entry__ as g\n"
         "g.force_cpu_devices(1)\n"
         "import jax\n"
         "fn, args = g.entry()\n"
         "out = jax.jit(fn)(*args)\n"
         "jax.block_until_ready(out[0])\n"
         "print('entry OK', out[0].shape)"],
        {})
    assert r.returncode == 0, f"entry crashed:\n{r.stderr[-4000:]}"
    assert "entry OK" in r.stdout
