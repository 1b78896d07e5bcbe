"""Tests of the bring-up repairs (ISSUE 25): one process per chip, the
placeable compile cache, digest-named native libraries, attention kernels
per tp shard, and CPU rehearsals of ``chip_smoke.py``'s control flow."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, cwd=REPO, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for key, value in (env_extra or {}).items():
        if value is None:
            env.pop(key, None)           # None: the variable is unset
        else:
            env[key] = value
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=cwd, env=env, timeout=timeout,
                          capture_output=True, text=True)


# ----------------------------------------------------- one process per chip

def test_allocator_leaves_the_jax_backend_uninitialised():
    """The serve supervisor builds TpuAllocator() and resolves the graph,
    then spawns the workers that need the chips: neither step may start a
    JAX backend (a parent that holds libtpu locks its children out)."""
    r = _py("""
        import sys
        from dynamo_tpu.sdk.allocator import TpuAllocator
        from dynamo_tpu.sdk.serve_worker import resolve_service
        alloc = TpuAllocator()
        resolve_service("examples.llm.graphs.disagg_router:Frontend").graph()
        initialised = False
        if "jax" in sys.modules:
            from jax._src import xla_bridge
            initialised = xla_bridge.backends_are_initialized()
        print("TOTAL", alloc.total, "INITIALISED", initialised)
    """)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "INITIALISED False" in r.stdout


def test_allocator_counts_device_nodes_and_never_guesses(monkeypatch):
    from dynamo_tpu.sdk import allocator
    nodes = {"/dev/accel[0-9]*": ["/dev/accel0", "/dev/accel1"],
             "/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1",
                                  "/dev/vfio/2", "/dev/vfio/3"]}
    monkeypatch.setattr(allocator.glob, "glob", lambda pat: nodes[pat])
    # both kinds shown: the accel nodes are the chips, counted once
    assert allocator.TpuAllocator().total == 2
    nodes["/dev/accel[0-9]*"] = []
    nodes["/dev/vfio/[0-9]*"].append("/dev/vfio/vfio")   # control node
    assert allocator.TpuAllocator().total == 4
    monkeypatch.setattr(allocator.glob, "glob", lambda pat: [])
    alloc = allocator.TpuAllocator()
    assert alloc.total == 0          # a host without chips has none, not 4
    with pytest.raises(RuntimeError, match="wants 1 chips"):
        alloc.allocate("worker", 1)
    assert allocator.TpuAllocator(total_chips=4).total == 4


# ------------------------------------------------------------ compile cache

_CACHE_PROBE = """
    import os, jax
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    print("RETURNED", enable_compile_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_honours_the_variable_placed_from_outside(tmp_path):
    placed = str(tmp_path / "placed-cache")
    r = _py(_CACHE_PROBE, env_extra={"JAX_COMPILATION_CACHE_DIR": placed})
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"RETURNED {placed}" in r.stdout
    assert f"CONFIG {placed}" in r.stdout   # JAX read it; code set nothing


def test_compile_cache_is_one_fixed_path_inside_the_checkout(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": None}
    outs = [_py(_CACHE_PROBE, cwd=cwd, env_extra=env).stdout
            for cwd in (REPO, str(tmp_path))]
    want = os.path.join(REPO, ".jax_cache")
    for out in outs:
        assert f"RETURNED {want}" in out and f"CONFIG {want}" in out, out


def test_compile_cache_helper_runs_at_entry_points_never_at_import():
    """Importing the package (or the helper's module) configures nothing;
    every entry point under which an EngineCore is built calls it."""
    r = _py("""
        import jax
        import dynamo_tpu.launch.run, dynamo_tpu.sdk.serve_worker
        import dynamo_tpu.utils.compile_cache
        print("CONFIG", jax.config.jax_compilation_cache_dir)
    """, env_extra={"JAX_COMPILATION_CACHE_DIR": None})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CONFIG None" in r.stdout
    for rel in ("dynamo_tpu/launch/run.py", "dynamo_tpu/sdk/serve_worker.py",
                "bench.py", "chip_smoke.py", "tools/decode_profile.py",
                "tools/serve_bench.py", "tools/disagg_bench.py",
                "tools/multiturn_bench.py", "tools/interference_bench.py"):
        with open(os.path.join(REPO, rel)) as f:
            assert "enable_compile_cache()" in f.read(), rel


# ------------------------------------------------------- native libraries

@pytest.fixture
def scratch_csrc(tmp_path, monkeypatch):
    """utils.native pointed at a scratch csrc/ with one tiny source."""
    from dynamo_tpu.utils import native
    csrc = tmp_path / "csrc"
    csrc.mkdir()
    (csrc / "answer.cpp").write_text(
        'extern "C" int answer() { return 42; }\n')
    monkeypatch.setattr(native, "_CSRC", str(csrc))
    monkeypatch.setattr(native, "_BUILD_DIR", str(csrc / "build"))
    monkeypatch.setattr(native, "_CACHE", {})
    return native, csrc


def test_native_load_ignores_a_library_whose_digest_does_not_match(
        scratch_csrc):
    """A stale .so from another tree (csrc/build is git-ignored and
    travels with a copied directory) is never loaded: the name carries a
    digest of sources and flags, and a changed source builds anew."""
    native, csrc = scratch_csrc
    build = csrc / "build"
    build.mkdir()
    # what the old mtime rule would have loaded: a newer file under the
    # old name, and one under a foreign digest — both garbage
    (build / "libanswer.so").write_bytes(b"not a shared object")
    (build / "libanswer.0123456789abcdef.so").write_bytes(b"nor this")
    lib = native.load("answer", ["answer.cpp"])
    assert lib.answer() == 42
    first = os.path.basename(lib._name)
    assert first not in ("libanswer.so", "libanswer.0123456789abcdef.so")
    (csrc / "answer.cpp").write_text(
        'extern "C" int answer() { return 43; }\n')
    native._CACHE.clear()
    lib2 = native.load("answer", ["answer.cpp"])
    assert lib2.answer() == 43
    assert os.path.basename(lib2._name) != first
    native._CACHE.clear()
    assert os.path.basename(
        native.load("answer", ["answer.cpp"], ["-DX=1"])._name) != \
        os.path.basename(lib2._name)          # flags are in the digest


def test_native_build_failure_raises_and_python_pool_is_by_name(
        scratch_csrc):
    """No silent pure-Python fallback: a failed build is an error. The
    Python pool stays available where a caller names it."""
    native, csrc = scratch_csrc
    (csrc / "broken.cpp").write_text("this is not C++\n")
    with pytest.raises(native.NativeLibError, match="broken"):
        native.load("broken", ["broken.cpp"])
    from dynamo_tpu.llm.kv.pool import KvBlockPool, make_kv_block_pool
    assert isinstance(make_kv_block_pool(8, prefer_native=False),
                      KvBlockPool)


# ------------------------------------------ attention kernels per tp shard

def test_attention_kernels_run_per_tp_shard_and_match_xla():
    """Under a tp mesh the Pallas kernels run inside shard_map (the TPU
    compiler refuses to partition them); per-head math is unchanged, so
    interpret-mode kernels on a tp=2 mesh agree with the XLA engine."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.models import llama
    from dynamo_tpu.parallel.sharding import (make_mesh, shard_kv,
                                              shard_params)

    cfg = ModelConfig(vocab_size=256, hidden_size=256, intermediate_size=256,
                      num_layers=2, num_heads=8, num_kv_heads=4, head_dim=64,
                      max_position_embeddings=256,
                      tie_word_embeddings=False)
    mesh = make_mesh(tp=2)
    params = shard_params(
        llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        mesh, cfg)
    bs, nblk, M, B = 8, 16, 4, 2

    def fresh_kv():
        return shard_kv(llama.init_kv_cache(cfg, nblk, bs,
                                            dtype=jnp.float32), mesh)

    base = llama.ModelStatics(cfg=cfg, block_size=bs, attn_impl="xla",
                              mesh=mesh)
    kern = dataclasses.replace(base, attn_impl="pallas_interpret")
    assert kern.tp == 2
    tokens = jnp.asarray(np.arange(3, 3 + 16, dtype=np.int32))
    table = jnp.asarray(np.arange(1, 1 + M, dtype=np.int32))
    outs = {}
    for name, st in (("xla", base), ("kernel", kern)):
        logits, kv = jax.jit(
            lambda p, kv, st=st: llama.prefill_forward(
                p, kv, tokens, table, jnp.int32(0), jnp.int32(13), st))(
                    params, fresh_kv())
        dtok = jnp.asarray(np.array([5, 9], np.int32))
        dpos = jnp.asarray(np.array([13, 0], np.int32))
        tables = jnp.asarray(np.stack([np.arange(1, 1 + M),
                                       np.zeros(M)]).astype(np.int32))
        dlogits, _ = jax.jit(
            lambda p, kv, st=st: llama.decode_forward(
                p, kv, dtok, dpos, tables, st))(params, kv)
        outs[name] = (np.asarray(logits), np.asarray(dlogits)[0])
    for a, b in zip(outs["xla"], outs["kernel"]):
        assert np.isfinite(b).all()
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
    text = jax.jit(lambda p, kv: llama.decode_forward(
        p, kv, dtok, dpos, tables, kern)).lower(
            params, fresh_kv()).as_text()
    assert "shard_map" in text or "manual" in text


# -------------------------------------------------- chip_smoke rehearsals

_REHEARSAL = """
    import sys
    import jax
    import chip_smoke as cs
    # steered HERE, not by an option of the program: the platform assert,
    # the sizes, interpret-mode kernels, and what only the chip's
    # compiler can put into a program
    cs.require_tpu = lambda: jax.devices()
    cs.MODEL_CONFIG.update(hidden_size=256, intermediate_size=512,
                           num_hidden_layers=2, num_attention_heads=8,
                           num_key_value_heads=4, head_dim=32,
                           vocab_size=1024, max_position_embeddings=2048)
    cs.MODEL_CONFIG.pop("rope_scaling")
    cs.EXTRA_FLAGS = ["--max-model-len", "256", "--num-kv-blocks", "64"]
    cs.PARITY.update(B=2, M=4, blocks=16, T=128, valid=100)
    cs.PALLAS_IMPL = "pallas_interpret"
    cs.kernels_in = lambda text: ["jit(step)/jit(lm_head_int8)/pallas_call"]

    def live_bytes(devices):
        per = {d: 0 for d in devices}
        for arr in jax.live_arrays():
            for shard in arr.addressable_shards:
                per[shard.device] += shard.data.nbytes
        return [per[d] for d in devices]
    cs._bytes_in_use = live_bytes       # CPU devices report no memory_stats
    sys.exit(cs.main(sys.argv[1:]))
"""


def _last_line_is_the_contract(stdout: str, count: int) -> None:
    last = stdout.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": count}}
    assert list(json.loads(last)) == ["ok", "device"]


def test_chip_smoke_refuses_a_host_without_a_tpu():
    """As the driver first runs it (sandbox, no steering): non-zero exit
    at the platform check and no result line."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       timeout=120, capture_output=True, text=True)
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert not r.stdout.strip()


def test_chip_smoke_cpu_rehearsal_drives_http_end_to_end(tmp_path):
    """Tiny widths, interpret-mode kernels: both serve phases answer
    their HTTP requests and the last line has the contract's shape."""
    r = _py(_REHEARSAL.replace("sys.argv[1:]", "[]"), env_extra={
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    _last_line_is_the_contract(r.stdout, 1)
    for needle in ("# phase[bf16]: tokens_generated=160",
                   "# phase[int8]: tokens_generated=96",
                   "# decode_kernel_vs_xla_max_abs_err",
                   "# program[_prefill_jit]", "# program[_decode_k_jit]",
                   "# native_libs_loaded"):
        assert needle in r.stdout, needle
    # one decode program per phase: the one-step path serves through the
    # K-step program too, and the host-keyed _decode_jit is never built
    assert r.stdout.count("# program[_decode_k_jit]") == 2
    assert "# program[_decode_jit]" not in r.stdout


def test_chip_smoke_four_chip_rehearsal_on_virtual_devices(tmp_path):
    """--chips 4 on four virtual CPU devices: only the tp=4 path and its
    tp=1 twin run, the model is really spread (a quarter per device),
    the decode program holds an all-reduce, count is 4."""
    r = _py(_REHEARSAL.replace("sys.argv[1:]", "['--chips', '4']"),
            env_extra={
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    _last_line_is_the_contract(r.stdout, 4)
    assert "# phase[tp4]" in r.stdout and "# phase[tp1]" in r.stdout
    assert "# phase[bf16]" not in r.stdout      # no one-chip phase ran
    assert "# tp4_vs_tp1_first_step_logits" in r.stdout


# what a broken reduction of the row-parallel attention output leaves in
# the residual stream, stated on the weights (the matmul is linear in wo):
# the all-reduce applied to a sum that already holds every shard's partial
# twice, or not applied at all (only the first shard's rows contribute)
_BROKEN_REDUCTION = {
    "doubled": "wo * 2",
    "missing": "wo.at[:, wo.shape[1] // 4:, :].set(0)",
}


@pytest.mark.parametrize("fault", sorted(_BROKEN_REDUCTION))
def test_chip_smoke_four_chip_check_trips_on_a_broken_reduction(
        tmp_path, fault):
    """TP_LOGITS_RTOL is wide enough for a changed reduction order (0.085
    of the logits' spread on the chip) only if a wrong reduction still
    lands far outside it: with the tp=4 engine's wo reduction doubled or
    missing, the first-step comparison fails and no result is printed."""
    code = _REHEARSAL.replace(
        "sys.exit(cs.main(sys.argv[1:]))", f"""
    first_step_logits = cs._first_step_logits

    def broken(core, prompt_ids):
        if core.mesh is not None:                  # the tp=4 engine only
            wo = core.params["layers.wo"]
            core.params = dict(core.params,
                               **{{"layers.wo": {_BROKEN_REDUCTION[fault]}}})
        return first_step_logits(core, prompt_ids)
    cs._first_step_logits = broken
    sys.exit(cs.main(['--chips', '4']))""")
    r = _py(code, env_extra={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode != 0, r.stdout[-2000:]
    assert ("chip_smoke check failed: tp=4 first-step logits agree"
            in r.stderr), r.stderr[-2000:]
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
    rel = float(r.stdout.split("# tp4_vs_tp1_first_step_logits:")[1]
                .split("rel=")[1].split()[0])
    assert rel > 0.5, rel       # nowhere near the tolerance's edge


def test_chip_smoke_any_failed_phase_exits_nonzero(tmp_path):
    """An impossible geometry (a tolerance nothing meets) fails the
    kernel-comparison phase: non-zero exit, no result line."""
    code = _REHEARSAL.replace(
        "sys.exit(cs.main(sys.argv[1:]))",
        "cs.KERNEL_ATOL = -1.0\n    sys.exit(cs.main([]))")
    r = _py(code, env_extra={
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode != 0
    assert "chip_smoke check failed" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
