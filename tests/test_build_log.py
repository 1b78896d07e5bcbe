"""The build log (engine/flight_recorder.py BuildLog): every XLA program the
process builds is one entry with its three stage times, the persistent
cache's verdict and the loop's phase; each entry is a ``build`` flight record,
every cycle record carries the running totals, and the benchmark's two
readers split set-up by them. No test here builds an engine."""

import gc
import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring

from dynamo_tpu.engine.flight_recorder import (BUILD_LOG, BuildLog,
                                               FlightRecorder)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE, LOWER, COMPILE = BuildLog.TRACE, BuildLog.LOWER, BuildLog.COMPILE
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def feed(log, events):
    """``events`` as JAX fires them: (event, seconds, fun_name) for a
    duration, (event,) for a plain one."""
    for event, *rest in events:
        if rest:
            log.on_duration(event, rest[0], fun_name=rest[1])
        else:
            log.on_event(event)


# what JAX 0.9 fires for jit(outer) calling jit(inner) twice, read from the
# cache; then a program of a scan (its lowering traces helpers of its own
# before the lowering's event) that is compiled; then one whose jaxpr JAX
# kept and that no cache was asked about
NESTED = [(TRACE, 0.003, "inner"), (TRACE, 0.00001, "inner"),
          (TRACE, 0.005, "outer"), (LOWER, 0.007, "jit(outer)"),
          ("/jax/compilation_cache/compile_requests_use_cache",),
          (HIT,), ("/jax/compilation_cache/cache_retrieval_time_sec", 0.002,
                   ""), (COMPILE, 0.004, "jit(outer)")]
SCAN = [(TRACE, 0.001, "inner"), (TRACE, 0.020, "decode_k"),
        (TRACE, 0.0004, "less"), (TRACE, 0.0003, "add"),
        (LOWER, 0.011, "jit(decode_k)"), (MISS,),
        (COMPILE, 30.0, "jit(decode_k)")]
KEPT = [(LOWER, 0.002, "jit(prefill)"), (COMPILE, 0.5, "jit(prefill)")]


@pytest.mark.parametrize("events,want", [
    (NESTED, ("outer", 5.0, 7.0, 4.0, "hit")),
    (SCAN, ("decode_k", 20.0, 11.0, 30000.0, "miss")),
    (KEPT, ("prefill", 0.0, 2.0, 500.0, "none")),
    # a trace that no lowering followed (eval_shape) is not the next
    # program's; nor is a lowering under another name
    ([(TRACE, 0.9, "shape_only"), (LOWER, 0.8, "jit(other)"), *KEPT[1:]],
     ("prefill", 0.0, 0.0, 500.0, "none")),
], ids=["nested-hit", "scan-miss", "kept-jaxpr", "strays"])
def test_synthetic_events_close_one_entry_a_program(events, want):
    log = BuildLog()
    before = time.time()
    feed(log, events)
    (entry,) = log.entries
    program, trace_ms, lower_ms, compile_ms, cache = want
    assert entry["program"] == program and entry["cache"] == cache
    assert (entry["trace_ms"], entry["lower_ms"], entry["compile_ms"]) == (
        pytest.approx(trace_ms), pytest.approx(lower_ms),
        pytest.approx(compile_ms))
    assert entry["host_ms"] == pytest.approx(trace_ms + lower_ms + compile_ms)
    assert entry["phase"] == "init"             # no recorder beside this log
    assert before <= entry["t"] <= time.time()
    assert log.totals() == {
        "built": 1, "built_ms": pytest.approx(entry["host_ms"]),
        "built_trace_ms": pytest.approx(trace_ms + lower_ms),
        "cache_misses": int(cache == "miss")}


def test_totals_only_grow_and_the_deque_is_bounded():
    log = BuildLog(capacity=2)
    seen = [log.totals()]
    for events in (NESTED, SCAN, KEPT, NESTED):
        feed(log, events)
        seen.append(log.totals())
    for a, b in zip(seen, seen[1:]):
        assert all(b[k] >= a[k] for k in a) and b["built"] == a["built"] + 1
    assert seen[-1]["built"] == 4 and seen[-1]["cache_misses"] == 1
    assert seen[-1]["built_trace_ms"] == pytest.approx(2 * 12.0 + 31.0 + 2.0)
    assert [e["program"] for e in log.entries] == ["prefill", "outer"]
    assert [e["program"] for e in log.costliest(1)] == ["prefill"]


def test_builds_on_two_threads_do_not_mix():
    log = BuildLog()
    feed(log, SCAN[:5])                          # this thread: lowered

    def other():
        feed(log, NESTED)
    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    feed(log, SCAN[5:])
    assert [(e["program"], e["trace_ms"], e["cache"]) for e in log.entries] \
        == [("outer", 5.0, "hit"), ("decode_k", 20.0, "miss")]


# ------------------------------------------------------------- a real jit

def test_one_real_jit_that_calls_a_jit_is_one_entry():
    BUILD_LOG.install()
    BUILD_LOG.install()                          # idempotent
    fired = []

    def listen(event, secs, **kw):
        fired.append((event, kw.get("fun_name")))

    @jax.jit
    def build_log_inner(x):
        return x * 2 + 1

    @jax.jit
    def build_log_outer(x):
        return build_log_inner(x) + build_log_inner(x + 1)

    x = jnp.ones(4)                              # its programs: built here
    monitoring.register_event_duration_secs_listener(listen)
    try:
        before = BUILD_LOG.totals()
        t0 = time.monotonic()
        build_log_outer(x).block_until_ready()
        wall_ms = 1e3 * (time.monotonic() - t0)
        after = BUILD_LOG.totals()
        assert [e for e in fired if e[0] == COMPILE] == [
            (COMPILE, "jit(build_log_outer)")]
        ours = [e for e in BUILD_LOG.entries
                if e["program"] in ("build_log_inner", "build_log_outer")]
        assert [e["program"] for e in ours] == ["build_log_outer"]
        (entry,) = ours
        assert entry["trace_ms"] > 0 and entry["lower_ms"] > 0
        assert entry["compile_ms"] > 0
        assert entry["host_ms"] <= wall_ms
        # one listener a kind: a second install() would count it twice
        assert after["built"] == before["built"] + 1
        assert after["built_ms"] == pytest.approx(
            before["built_ms"] + entry["host_ms"], abs=0.01)
        # a built program fires no event again
        del fired[:]
        build_log_outer(x).block_until_ready()
        assert not fired and BUILD_LOG.totals() == after
    finally:
        monitoring.unregister_event_duration_listener(listen)


# ------------------------------------------------- a recorder beside the log

def test_a_build_lands_in_the_ring_with_the_clocks_phase():
    fr, idle = FlightRecorder(capacity=8), FlightRecorder(capacity=8)
    fr.clock.enter("dispatch")

    @jax.jit
    def build_log_step(x):
        return x - 3

    x = jnp.ones(3)
    fr._ring.clear()                             # what building x recorded
    idle._ring.clear()
    build_log_step(x).block_until_ready()
    (rec,) = fr.dump()
    assert rec["kind"] == "build" and rec["program"] == "build_log_step"
    assert rec["phase"] == "dispatch" and rec["cache"] in ("hit", "miss",
                                                            "none")
    assert set(rec) == {"kind", "t", "program", "phase", "trace_ms",
                        "lower_ms", "compile_ms", "host_ms", "cache"}
    assert rec["host_ms"] == pytest.approx(
        rec["trace_ms"] + rec["lower_ms"] + rec["compile_ms"], abs=0.002)
    # every live recorder gets it, under its own clock's phase
    assert [(r["program"], r["phase"]) for r in idle.dump()] == [
        ("build_log_step", "yield")]
    json.dumps(rec)                              # scalars only
    fr.clock.enter("post")
    fr.record_cycle("decode", K=1, batch_fill=1)
    cycle = fr.dump()[-1]
    assert {k: cycle[k] for k in ("built", "built_ms", "built_trace_ms")} \
        == {k: v for k, v in BUILD_LOG.totals().items()
            if k != "cache_misses"}
    assert cycle["built"] >= 1 and cycle["built_ms"] > cycle["built_trace_ms"]
    stats = fr.stats()
    assert stats["built"] == cycle["built"] and stats["cache_misses"] >= 0
    assert 1 <= len(stats["costliest_builds"]) <= 5
    costs = [e["host_ms"] for e in stats["costliest_builds"]]
    assert costs == sorted(costs, reverse=True)
    assert fr.metrics_kw() == {
        "programs_built_total": cycle["built"],
        "program_build_seconds_total": pytest.approx(
            cycle["built_ms"] / 1e3, abs=1e-5)}


def test_a_collected_recorder_drops_out_of_the_log():
    log = BuildLog()
    fr = FlightRecorder(capacity=4)
    log.attach(fr)
    fr.clock.enter("admit")
    feed(log, KEPT)
    assert log.entries[-1]["phase"] == "admit"
    assert [r["phase"] for r in fr.dump() if r.get("program") == "prefill"] \
        == ["admit"]
    del fr
    gc.collect()
    feed(log, KEPT)
    assert log.entries[-1]["phase"] == "init" and log.built == 2


# ------------------------------------------------------------- the readers

def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cycle(kind, built_ms=None, built_trace_ms=None):
    rec = {"kind": kind, "t": 0.0, "device_ms": 1.0, "host_gap_ms": 15.0}
    if built_ms is not None:
        rec.update(built=140, built_ms=built_ms,
                   built_trace_ms=built_trace_ms)
    return rec


WINDOW = [{"kind": "prefill", "t": 0.0, "host_ms": 30.0},
          {"kind": "build", "t": 0.0, "program": "f", "host_ms": 9.0},
          cycle("verify", 48250.0, 20125.0),     # the first cycle record wins
          cycle("decode", 99999.0, 88888.0)]


@pytest.mark.parametrize("name,want", [
    ("setup.build_trace_s", 20.125),
    ("setup.build_load_s", 28.125),
])
def test_setup_reader_takes_the_first_cycle_record(name, want):
    read = reader(name)
    assert read({"flight": WINDOW}) == pytest.approx(want)
    assert read({"flight": WINDOW[3:]}) == pytest.approx(
        88.888 if name.endswith("trace_s") else 11.111)
    # a parent's records, a window with no cycle record, an empty window
    assert read({"flight": [cycle("decode")]}) is None
    assert read({"flight": WINDOW[:2]}) is None
    assert read({"flight": []}) is None


def test_benchmark_lists_both_setup_readers_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("setup.build_trace_s", "setup.build_load_s"):
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": "Engine step",
            "moves": "setup_s", "workloads": cells[:10]}
