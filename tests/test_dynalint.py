"""dynalint test suite (tier-1, `lint` marker).

Three layers:
1. seeded-violation fixtures — every rule must FIRE on its seeded bug
   and stay silent on the clean twin (the analyzer's own regression
   harness);
2. the repo-wide gate — `run_lint` over the real tree must report ZERO
   unbaselined findings inside the tier-1 time budget (this is the
   check that makes dynalint a merge gate rather than a suggestion);
3. behavior regressions for the real violations this PR fixed
   (prepare_prefill exception-edge pin release, the event_count mirror).
"""

import json
import os
import subprocess
import sys

import pytest

from tools.dynalint.engine import load_context, run_lint
from tools.dynalint.rules.dl004_schema import update_lock

pytestmark = pytest.mark.lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_repo(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return str(tmp_path)


def lint_fixture(root, rules, scan_roots=("pkg",), **overrides):
    ctx = load_context(root, scan_roots=scan_roots, **overrides)
    findings, suppressed, _ = run_lint(
        root, rules=rules, ctx=ctx,
        baseline_path=os.path.join(root, "no-baseline.json"))
    return findings, suppressed


# ---------------------------------------------------------------- DL001

DL001_SRC = """
import asyncio
import time


def helper():
    time.sleep(1)           # blocking primitive


def offloaded_helper():
    time.sleep(1)           # same primitive, but only reached off-loop


async def bad_direct():
    data = open("f").read()     # seeded violation: open() on the loop
    return data


async def bad_via_chain():
    helper()                    # seeded violation: async -> sync -> sleep


async def clean():
    await asyncio.to_thread(offloaded_helper)
    await asyncio.sleep(0)      # asyncio.sleep is not time.sleep
"""


def test_dl001_fires_and_clean_twin(tmp_path):
    root = make_repo(tmp_path, {"pkg/app.py": DL001_SRC})
    findings, _ = lint_fixture(root, ["DL001"])
    msgs = [f.message for f in findings]
    assert any("open()" in m and "bad_direct" in m for m in msgs), msgs
    assert any("time.sleep" in m and "bad_via_chain" in m for m in msgs)
    # the offloaded helper and asyncio.sleep must NOT fire
    assert not any("offloaded_helper" in m for m in msgs)
    assert len(findings) == 2


def test_dl001_inline_waiver(tmp_path):
    src = DL001_SRC.replace(
        'data = open("f").read()     # seeded violation: open() on the loop',
        'data = open("f").read()  # dynalint: ok DL001 startup-only read')
    root = make_repo(tmp_path, {"pkg/app.py": src})
    findings, suppressed = lint_fixture(root, ["DL001"])
    assert not any("open()" in f.message for f in findings)
    assert any("open()" in f.message for f in suppressed)


# ---------------------------------------------------------------- DL002

DL002_CV_SRC = """
import contextvars

_cv = contextvars.ContextVar("x", default=None)


def leak(v):
    _cv.set(v)              # seeded violation: no reset


def ok(v):
    tok = _cv.set(v)
    try:
        return 1
    finally:
        _cv.reset(tok)


def detach():
    _cv.set(None)           # the cure, not the disease
"""

DL002_TRACING_SRC = """
def current_trace():
    return None


def detach_trace():
    pass
"""

DL002_TASK_SRC = """
import asyncio

from .tracing import current_trace, detach_trace


async def pump():
    while True:             # seeded violation: loops + reads ambient,
        current_trace()     # never detaches


async def good_pump():
    detach_trace()
    while True:
        current_trace()


def start():
    loop = asyncio.get_event_loop()
    loop.create_task(pump())
    loop.create_task(good_pump())
"""


def test_dl002_token_discipline(tmp_path):
    root = make_repo(tmp_path, {"pkg/cv.py": DL002_CV_SRC})
    findings, _ = lint_fixture(root, ["DL002"])
    assert len(findings) == 1
    assert findings[0].symbol == "leak:set"


def test_dl002_task_detach(tmp_path):
    root = make_repo(tmp_path, {"pkg/tracing.py": DL002_TRACING_SRC,
                                "pkg/app.py": DL002_TASK_SRC})
    findings, _ = lint_fixture(root, ["DL002"])
    assert len(findings) == 1
    assert "pump" in findings[0].message
    assert "good_pump" not in findings[0].message


# ---------------------------------------------------------------- DL003

DL003_SRC = """
def validate(x):
    return x


def leaked(store, hashes):
    store.pin(hashes)       # seeded violation: pinned, never released,
    n = len(hashes)         # never handed to an owner (len() is
    return n                # bookkeeping, not an ownership transfer)


def exception_edge(store, hashes):
    got = store.match_prefix(hashes, pin=True)
    validate(got)           # can raise -> pins leak on the raise edge
    store.unpin(got)
    return len(got)


def clean_finally(store, hashes):
    got = store.match_prefix(hashes, pin=True)
    try:
        validate(got)
    finally:
        store.unpin(got)
    return len(got)


def clean_transfer(store, hashes, job_cls):
    store.pin(hashes)
    return job_cls(pinned=hashes)   # ownership transferred to the job
"""


def test_dl003_fires_and_clean_twins(tmp_path):
    root = make_repo(tmp_path, {"pkg/pins.py": DL003_SRC})
    findings, _ = lint_fixture(root, ["DL003"])
    syms = sorted(f.symbol for f in findings)
    assert "exception_edge:store.match_prefix:exc" in syms, syms
    assert "leaked:store.pin" in syms, syms
    assert not any("clean_finally" in s or "clean_transfer" in s
                   for s in syms)
    assert len(findings) == 2


# ---------------------------------------------------------------- DL004

DL004_V1 = """
import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class WireThing:
    request_id: str
    blocks: List[int]
    tier: str = "device"
"""

# drifted: `tier` type mutated, `blocks` removed, new field w/o default
DL004_V2 = """
import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class WireThing:
    request_id: str
    tier: int = 0
    mandatory_new: str
"""

DL004_BAD_TYPE = """
import dataclasses
import socket


@dataclasses.dataclass
class WireThing:
    request_id: str
    conn: socket.socket = None
"""


def test_dl004_lock_ritual_and_drift(tmp_path):
    root = make_repo(tmp_path, {"pkg/proto.py": DL004_V1})
    overrides = dict(schema_paths=("pkg/proto.py",),
                     schema_lock_path="lock.json")
    # no lockfile yet -> the missing-lock finding
    findings, _ = lint_fixture(root, ["DL004"], **overrides)
    assert any(f.symbol == "lockfile:missing" for f in findings)
    # the one-command ritual: generate, then clean
    ctx = load_context(root, scan_roots=("pkg",), **overrides)
    update_lock(ctx)
    findings, _ = lint_fixture(root, ["DL004"], **overrides)
    assert findings == []
    # drift the schema: removed field + changed type + defaultless new
    (tmp_path / "pkg/proto.py").write_text(DL004_V2)
    findings, _ = lint_fixture(root, ["DL004"], **overrides)
    syms = {f.symbol for f in findings}
    assert "WireThing.blocks:removed" in syms, syms
    assert "WireThing.tier:type-changed" in syms
    assert "WireThing.mandatory_new:no-default" in syms
    # ritual again -> clean again
    ctx = load_context(root, scan_roots=("pkg",), **overrides)
    update_lock(ctx)
    findings, _ = lint_fixture(root, ["DL004"], **overrides)
    assert findings == []


def test_dl004_non_json_type(tmp_path):
    root = make_repo(tmp_path, {"pkg/proto.py": DL004_BAD_TYPE})
    overrides = dict(schema_paths=("pkg/proto.py",),
                     schema_lock_path="lock.json")
    ctx = load_context(root, scan_roots=("pkg",), **overrides)
    update_lock(ctx)
    findings, _ = lint_fixture(root, ["DL004"], **overrides)
    assert any(f.symbol == "WireThing.conn:type" for f in findings)


# ---------------------------------------------------------------- DL005

DL005_SRC = """
import time

import jax


@jax.jit
def bad_clock(x):
    return x * time.time()      # seeded violation: wall clock in trace


@jax.jit
def good(x, t):
    return x * t


def make_programs():
    def bad_wrapped(x):
        import random
        return x * random.random()   # seeded violation: stdlib random
    return jax.jit(bad_wrapped)
"""


def test_dl005_fires_and_clean_twin(tmp_path):
    root = make_repo(tmp_path, {"pkg/kern.py": DL005_SRC})
    findings, _ = lint_fixture(root, ["DL005"])
    msgs = [f.message for f in findings]
    assert any("time.time" in m and "bad_clock" in m for m in msgs), msgs
    assert any("random" in m and "bad_wrapped" in m for m in msgs)
    assert not any("good" in f.symbol for f in findings)


# ---------------------------------------------------------------- DL006

DL006_CPP = """
#include <cstdint>

extern "C" {

int64_t abc_add(void* p, int64_t a, int64_t b) { return a + b; }

void abc_stats(void* p, int64_t* out) {
    out[0] = 1;
    out[1] = 2;
}

void abc_orphan(void* p) { }

}  // extern "C"
"""

DL006_PY = """
import ctypes


def setup(lib):
    lib.abc_add.restype = ctypes.c_int64
    lib.abc_add.argtypes = [ctypes.c_void_p, ctypes.c_int64]  # 2 != 3
    lib.abc_missing.argtypes = [ctypes.c_void_p]


def stats(lib, h):
    buf = (ctypes.c_int64 * 3)()      # C writes out[0..1] -> width 2
    lib.abc_stats(h, buf)
    return list(buf)
"""


def test_dl006_mirror_drift(tmp_path):
    root = make_repo(tmp_path, {"native.cpp": DL006_CPP,
                                "pkg/wrap.py": DL006_PY})
    findings, _ = lint_fixture(
        root, ["DL006"],
        mirror_pairs=(("native.cpp", "pkg/wrap.py", ("abc_",)),))
    syms = {f.symbol for f in findings}
    assert "abc_add:arity" in syms, syms
    assert "abc_missing:missing-export" in syms
    assert "abc_orphan:orphan-export" in syms
    assert "abc_stats:out-buffer" in syms


def test_dl006_clean_twin(tmp_path):
    clean_py = DL006_PY.replace(
        "[ctypes.c_void_p, ctypes.c_int64]  # 2 != 3",
        "[ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]"
    ).replace("    lib.abc_missing.argtypes = [ctypes.c_void_p]\n", ""
              ).replace("(ctypes.c_int64 * 3)()", "(ctypes.c_int64 * 2)()")
    clean_cpp = DL006_CPP.replace(
        "void abc_orphan(void* p) { }\n\n", "")
    root = make_repo(tmp_path, {"native.cpp": clean_cpp,
                                "pkg/wrap.py": clean_py})
    findings, _ = lint_fixture(
        root, ["DL006"],
        mirror_pairs=(("native.cpp", "pkg/wrap.py", ("abc_",)),))
    assert findings == []


# ---------------------------------------------------------------- DL008

DL008_SRC = """
import asyncio


class Engine:
    def __init__(self):
        self.slots = {}
        self.table = {}
        self._runner = None
        self._lock = asyncio.Lock()

    async def stale_snapshot(self, rid):
        slot = self.slots[rid]            # snapshot of shared state
        await asyncio.sleep(0)            # world moves
        self.table.pop(slot)              # seeded: stale index mutation

    async def revalidated(self, rid):
        slot = self.slots[rid]
        await asyncio.sleep(0)
        if slot in self.slots.values():   # re-read of the root
            self.table.pop(slot)

    async def guard_race(self):
        if self._runner is not None:      # seeded: check ...
            await self._runner.cleanup()  # ... await ...
            self._runner = None           # ... then act

    async def claim_first(self):
        runner, self._runner = self._runner, None   # claim BEFORE await
        if runner is not None:
            await runner.cleanup()

    async def locked_guard(self):
        async with self._lock:            # sanctioned double-checked lock
            if self._runner is None:
                await asyncio.sleep(0)
                self._runner = object()

    async def owned_key(self, fut):
        rid = self.next_rid
        self.table[rid] = fut             # our own entry ...
        await asyncio.sleep(0)
        self.table.pop(rid)               # ... popping it is ownership
"""


def test_dl008_fires_and_clean_twins(tmp_path):
    root = make_repo(tmp_path, {"pkg/eng.py": DL008_SRC})
    findings, _ = lint_fixture(root, ["DL008"])
    syms = sorted(f.symbol for f in findings)
    assert any("stale_snapshot" in s for s in syms), syms
    assert any("guard_race" in s for s in syms), syms
    # the disciplined twins must NOT fire
    for clean in ("revalidated", "claim_first", "locked_guard",
                  "owned_key"):
        assert not any(clean in s for s in syms), syms
    assert len(findings) == 2


def test_dl008_inline_waiver(tmp_path):
    src = DL008_SRC.replace(
        "            self._runner = None           # ... then act",
        "            self._runner = None  # dynalint: ok DL008 single-caller shutdown")
    root = make_repo(tmp_path, {"pkg/eng.py": src})
    findings, suppressed = lint_fixture(root, ["DL008"])
    assert not any("guard_race" in f.symbol for f in findings)
    assert any("guard_race" in f.symbol for f in suppressed)


# ---------------------------------------------------------------- DL009

DL009_RECORDER = """
class Core:
    def emit(self):
        self.recorder.rec("prefill", x=1)
        self.recorder.rec("dispatch", x=1)
        self.recorder.rec("harvest", x=1)
        self.recorder.rec("mystery", x=1)    # seeded: no home anywhere
"""

DL009_REPLAY = """
HOST_EVENTS = frozenset({"harvest"})


def replay(events):
    for ev in events:
        kind = ev["ev"]
        if kind in HOST_EVENTS:
            continue
        if kind == "prefill":
            pass
        elif kind == "dispatch":
            pass
"""

DL009_MULTIHOST = """
WIRE_EVENTS = frozenset({"prefill", "dispatch", "phantom"})


def run_follower(sock):
    while True:
        ev = recv(sock)
        kind = ev["ev"]
        if kind == "__shutdown__":
            break
        if kind == "prefill":
            pass
        elif kind == "dispatch":
            pass
        elif kind == "ragged":
            pass                       # seeded: handled but not on wire
"""


def dl009_overrides(extra=None):
    ov = dict(recorder_emit_paths=("pkg/core.py",),
              replay_module="pkg/replay.py",
              multihost_module="pkg/multihost.py",
              faults_module="pkg/faults.py",
              chaos_test_path="pkg/test_chaos.py")
    ov.update(extra or {})
    return ov


def test_dl009_event_closure_fires(tmp_path):
    root = make_repo(tmp_path, {"pkg/core.py": DL009_RECORDER,
                                "pkg/replay.py": DL009_REPLAY,
                                "pkg/multihost.py": DL009_MULTIHOST})
    findings, _ = lint_fixture(root, ["DL009"], **dl009_overrides())
    syms = {f.symbol for f in findings}
    assert "mystery:no-home" in syms, syms
    assert "ragged:dropped-on-wire" in syms, syms
    assert "phantom:unhandled-on-follower" in syms, syms
    assert "phantom:not-offline-replayable" in syms, syms
    # the properly-closed events stay silent
    assert not any(s.startswith(("prefill:", "dispatch:", "harvest:"))
                   for s in syms), syms


def test_dl009_event_closure_clean_twin(tmp_path):
    clean_rec = DL009_RECORDER.replace(
        '        self.recorder.rec("mystery", x=1)    # seeded: no home anywhere\n',
        "")
    clean_mh = DL009_MULTIHOST.replace(
        '"prefill", "dispatch", "phantom"', '"prefill", "dispatch", "ragged"'
    )
    root = make_repo(tmp_path, {"pkg/core.py": clean_rec,
                                "pkg/replay.py": DL009_REPLAY,
                                "pkg/multihost.py": clean_mh})
    findings, _ = lint_fixture(root, ["DL009"], **dl009_overrides())
    # one remaining: ragged handled by the follower but not offline —
    # close it too for the fully-clean twin
    clean_replay = DL009_REPLAY.replace(
        'elif kind == "dispatch":\n            pass',
        'elif kind in ("dispatch", "ragged"):\n            pass')
    root = make_repo(tmp_path, {"pkg/core.py": clean_rec,
                                "pkg/replay.py": clean_replay,
                                "pkg/multihost.py": clean_mh})
    findings, _ = lint_fixture(root, ["DL009"], **dl009_overrides())
    assert findings == [], [f.symbol for f in findings]


DL009_FAULTS = """
SITES = {"net.call": "one rpc", "disk.write": "one write",
         "ghost.site": "registered, never hit or tested"}
"""

DL009_HITTER = """
from .faults import hit


def call():
    hit("net.call")
    hit("disk.write")
    hit("typo.site")          # seeded: unregistered
"""

DL009_CHAOS = """
def test_net():
    arm("net.call", "error")


def test_disk():
    arm("disk.write", "enospc")
"""


def test_dl009_failpoint_coverage(tmp_path):
    root = make_repo(tmp_path, {"pkg/faults.py": DL009_FAULTS,
                                "pkg/io.py": DL009_HITTER,
                                "pkg/test_chaos.py": DL009_CHAOS})
    findings, _ = lint_fixture(root, ["DL009"], **dl009_overrides())
    syms = {f.symbol for f in findings}
    assert "ghost.site:untested" in syms, syms
    assert "ghost.site:never-hit" in syms, syms
    assert "typo.site:unregistered" in syms, syms
    assert not any(s.startswith(("net.call:", "disk.write:"))
                   for s in syms), syms


# ---------------------------------------------------------------- DL010

DL010_PROTO = """
import dataclasses


@dataclasses.dataclass
class ForwardPassMetrics:
    active_slots: int = 0
    orphan_counter: int = 0          # seeded: no gauge table consumes it
"""

DL010_METRICS = """
from prometheus_client import Gauge

PREFIX = "nv_test"

_GAUGE_FIELDS = ("active_slots",)

_EXTRA_GAUGES = {"plotted": "nv_test_plotted",
                 "unplotted": "nv_test_unplotted"}   # seeded: not on dash
"""

DL010_MOCK = """
def stats():
    return {"active_slots": 1, "plotted": 2}    # "unplotted" never fed
"""

DL010_DASH = '{"panels": [{"targets": [{"expr": "nv_test_active_slots"}, {"expr": "nv_test_plotted"}]}]}'


def dl010_overrides():
    return dict(metrics_module="pkg/metrics.py",
                metrics_protocol_module="pkg/proto.py",
                mock_worker_module="pkg/mock.py",
                grafana_dashboard_path="dash.json")


def test_dl010_metrics_closure_fires(tmp_path):
    root = make_repo(tmp_path, {"pkg/proto.py": DL010_PROTO,
                                "pkg/metrics.py": DL010_METRICS,
                                "pkg/mock.py": DL010_MOCK,
                                "dash.json": DL010_DASH})
    findings, _ = lint_fixture(root, ["DL010"], **dl010_overrides())
    syms = {f.symbol for f in findings}
    assert "ForwardPassMetrics.orphan_counter:unscraped" in syms, syms
    assert "nv_test_unplotted:unplotted" in syms, syms
    assert "unplotted:unfed" in syms, syms
    assert not any("active_slots" in s for s in syms), syms


def test_dl010_metrics_closure_clean_twin(tmp_path):
    proto = DL010_PROTO.replace(
        "    orphan_counter: int = 0          # seeded: no gauge table consumes it\n",
        "")
    metrics = DL010_METRICS.replace(
        ',\n                 "unplotted": "nv_test_unplotted"}   # seeded: not on dash',
        "}")
    root = make_repo(tmp_path, {"pkg/proto.py": proto,
                                "pkg/metrics.py": metrics,
                                "pkg/mock.py": DL010_MOCK,
                                "dash.json": DL010_DASH})
    findings, _ = lint_fixture(root, ["DL010"], **dl010_overrides())
    assert findings == [], [f.symbol for f in findings]


# --------------------------------------------------- repo-wide seeded drift

def test_metrics_plane_catches_seeded_drift(tmp_path):
    """Acceptance: the metrics-plane closure must catch DELIBERATE drift
    against the real tree — a new ForwardPassMetrics field nobody wires
    fires DL010 without any fixture scaffolding."""
    import shutil
    root = tmp_path / "tree"
    for rel in ("dynamo_tpu/components/metrics.py",
                "dynamo_tpu/components/mock_worker.py",
                "dynamo_tpu/llm/kv_router/protocols.py",
                "deploy/metrics/grafana-dashboard.json"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO_ROOT, rel), dst)
    proto = root / "dynamo_tpu/llm/kv_router/protocols.py"
    src = proto.read_text().replace(
        "    tenant_stats: dict = dataclasses.field(default_factory=dict)",
        "    tenant_stats: dict = dataclasses.field(default_factory=dict)\n"
        "    drifted_new_counter: int = 0")
    proto.write_text(src)
    ctx = load_context(str(root), scan_roots=("dynamo_tpu",))
    findings, _, _ = run_lint(str(root), rules=["DL010"], ctx=ctx,
                              baseline_path=str(root / "nb.json"))
    assert any(f.symbol ==
               "ForwardPassMetrics.drifted_new_counter:unscraped"
               for f in findings), [f.symbol for f in findings]


def test_event_replay_closure_catches_seeded_drift(tmp_path):
    """Acceptance: deliberately drop `ragged` from WIRE_EVENTS on a copy
    of the real tree — DL009 must report the dropped-on-wire gap this PR
    found (and fixed) for real."""
    import shutil
    root = tmp_path / "tree"
    for rel in ("dynamo_tpu/engine/core.py", "dynamo_tpu/engine/replay.py",
                "dynamo_tpu/engine/multihost.py",
                "dynamo_tpu/runtime/faults.py"):
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO_ROOT, rel), dst)
    mh = root / "dynamo_tpu/engine/multihost.py"
    src = mh.read_text().replace('"ragged", "verify",', '"verify",')
    assert src != mh.read_text()
    mh.write_text(src)
    ctx = load_context(str(root), scan_roots=("dynamo_tpu",),
                       chaos_test_path="absent.py")
    findings, _, _ = run_lint(str(root), rules=["DL009"], ctx=ctx,
                              baseline_path=str(root / "nb.json"))
    assert any(f.symbol == "ragged:dropped-on-wire" for f in findings), \
        [f.symbol for f in findings]


# ---------------------------------------------------------------- DL011

DL011_KEYS = """
PREFIX = "ctl/"


def foo_control_key(ns):
    return f"{PREFIX}foo/{ns}"


def bar_control_key(ns):
    return f"{PREFIX}bar/{ns}"
"""

DL011_CTL = """
from .keys import bar_control_key, foo_control_key


async def set_foo(store, ns, v):
    await store.kv_put(foo_control_key(ns), v)


async def set_bar(store, ns, v):
    await store.kv_put(bar_control_key(ns), v)   # seeded: no reader
"""

DL011_WATCH = """
from .keys import foo_control_key


async def watch_foo_loop(store, ns):
    entry = await store.kv_get(foo_control_key(ns))
    return entry


async def watch_orphan_loop(store, ns):       # seeded: nobody spawns it
    return await store.kv_get_prefix("other/")
"""

DL011_WIRING = """
import asyncio

from .watchers import watch_foo_loop


def wire(loop, store, ns):
    loop.create_task(watch_foo_loop(store, ns))
"""


def test_dl011_control_key_closure(tmp_path):
    root = make_repo(tmp_path, {"pkg/keys.py": DL011_KEYS,
                                "pkg/ctl.py": DL011_CTL,
                                "pkg/watchers.py": DL011_WATCH,
                                "pkg/run.py": DL011_WIRING})
    findings, _ = lint_fixture(root, ["DL011"],
                               llmctl_module="pkg/ctl.py")
    syms = {f.symbol for f in findings}
    assert any("bar_control_key" in s for s in syms), syms
    assert "watch_orphan_loop:orphan-watcher" in syms, syms
    assert not any("foo" in s for s in syms), syms
    assert len(findings) == 2


def test_dl011_inline_waiver(tmp_path):
    ctl = DL011_CTL.replace(
        "    await store.kv_put(bar_control_key(ns), v)   # seeded: no reader",
        "    # audit trail: written for operators, read by humans only\n"
        "    await store.kv_put(bar_control_key(ns), v)  # dynalint: ok DL011 write-only audit key")
    root = make_repo(tmp_path, {"pkg/keys.py": DL011_KEYS,
                                "pkg/ctl.py": ctl,
                                "pkg/watchers.py": DL011_WATCH,
                                "pkg/run.py": DL011_WIRING})
    findings, suppressed = lint_fixture(root, ["DL011"],
                                        llmctl_module="pkg/ctl.py")
    assert not any("bar_control_key" in f.symbol for f in findings)
    assert any("bar_control_key" in f.symbol for f in suppressed)


# ---------------------------------------------------------------- DL012

DL012_SRC = """
import random
import time


class Sim:
    def __init__(self, seed):
        self.rng = random.Random(seed)
        self.draining = set()

    def tick(self):
        t = time.monotonic()              # seeded: wall clock
        j = random.random()               # seeded: ambient module RNG
        for w in self.draining:           # seeded: hash-order iteration
            self.log(w)
        for w in sorted(self.draining):   # clean twin
            self.log(w)
        ok = self.rng.random()            # clean: seeded instance
        n = len(self.draining)            # clean: len() doesn't order
        return t, j, ok, n
"""


def test_dl012_fires_and_clean_twins(tmp_path):
    root = make_repo(tmp_path, {"pkg/sim.py": DL012_SRC})
    findings, _ = lint_fixture(root, ["DL012"],
                               determinism_paths=("pkg/",))
    syms = sorted(f.symbol for f in findings)
    assert "Sim.tick:time.monotonic" in syms, syms
    assert "Sim.tick:random.random" in syms, syms
    assert any("set-iteration" in s for s in syms), syms
    assert len(findings) == 3


def test_dl012_out_of_scope_is_silent(tmp_path):
    root = make_repo(tmp_path, {"pkg/sim.py": DL012_SRC})
    findings, _ = lint_fixture(root, ["DL012"],
                               determinism_paths=("elsewhere/",))
    assert findings == []


# ------------------------------------------------ dataflow layer units

def test_dataflow_string_constants(tmp_path):
    src = """
PREFIX = "faults/"
NAMES = frozenset({"a", "b"}) | {"c"}
TABLE = {"x": "nv_x", "y": "nv_y"}


def key(ns):
    return f"{PREFIX}control/{ns}"
"""
    root = make_repo(tmp_path, {"pkg/m.py": src})
    ctx = load_context(root, scan_roots=("pkg",))
    mod = ctx.graph.modules["pkg/m.py"]
    consts = ctx.graph.consts
    assert consts.const_str(mod, "PREFIX") == "faults/"
    assert consts.str_set(mod, "NAMES") == {"a", "b", "c"}
    assert consts.str_dict(mod, "TABLE") == {"x": "nv_x", "y": "nv_y"}
    ret = mod.functions["key"].node.body[0].value
    assert consts.resolve_str_expr(mod, ret) == "faults/control/\x00"


def test_dataflow_attr_type_resolution(tmp_path):
    """The DL001-blind-spot closure: a typed self-attribute chain
    (annotated assignment + annotated __init__ param alias) resolves to
    the concrete method, connecting async code to a blocking call two
    attribute hops away."""
    wal = """
import os


class Wal:
    def append(self, rec):
        os.fsync(1)                       # the blocking primitive
"""
    server = """
from typing import Optional

from .wal import Wal


class Server:
    def __init__(self):
        self.wal: Optional[Wal] = Wal()

    def wal_append(self, rec):
        self.wal.append(rec)


class Session:
    def __init__(self, server: "Server"):
        self.server = server

    async def dispatch(self, msg):
        log = self.server.wal_append       # bound-method alias
        log(msg)
"""
    root = make_repo(tmp_path, {"pkg/wal.py": wal, "pkg/srv.py": server})
    findings, _ = lint_fixture(root, ["DL001"])
    assert any("os.fsync" in f.message and "dispatch" in f.message
               for f in findings), [f.message for f in findings]


# ------------------------------------------------------- repo-wide gate

# ---------------------------------------------------------------- DL007

DL007_SRC = """
import asyncio


async def bad_receive(rx):
    f = await rx.next_frame()            # seeded: unbounded frame wait
    p = await rx.wait_connected()        # seeded: unbounded dial-back
    item = await q.dequeue()             # seeded: unbounded queue pop
    return f, p, item


async def bad_engine_queue(req):
    out = await req.out_queue.get()      # seeded: unbounded engine queue
    return out


async def clean(rx, q, req):
    f = await rx.next_frame(timeout=0.5)
    p = await rx.wait_connected(timeout=10.0)
    item = await q.dequeue(1.0, ack_deadline=30.0)   # positional timeout
    out = await asyncio.wait_for(req.out_queue.get(), 30)  # wrapped
    return f, p, item, out


async def explicit_none_is_flagged(rx):
    return await rx.next_frame(timeout=None)   # seeded: explicit opt-out
"""


def test_dl007_fires_and_clean_twin(tmp_path):
    root = make_repo(tmp_path, {"pkg/app.py": DL007_SRC})
    findings, _ = lint_fixture(root, ["DL007"])
    msgs = [f"{f.symbol} {f.message}" for f in findings]
    assert any(".next_frame()" in m and "bad_receive" in m for m in msgs)
    assert any(".wait_connected()" in m for m in msgs), msgs
    assert any(".dequeue()" in m for m in msgs), msgs
    assert any(".out_queue.get()" in m and "bad_engine_queue" in m
               for m in msgs), msgs
    assert any("explicit_none_is_flagged" in m for m in msgs), msgs
    # the bounded twins must NOT fire
    assert not any("clean" in f.symbol for f in findings), msgs
    assert len(findings) == 5


def test_dl007_inline_waiver(tmp_path):
    src = DL007_SRC.replace(
        "out = await req.out_queue.get()      # seeded: unbounded engine queue",
        "out = await req.out_queue.get()  # dynalint: ok DL007 event pump")
    root = make_repo(tmp_path, {"pkg/app.py": src})
    findings, suppressed = lint_fixture(root, ["DL007"])
    assert not any("bad_engine_queue" in f.symbol for f in findings)
    assert any("bad_engine_queue" in f.symbol for f in suppressed)


def test_repo_wide_zero_findings():
    """THE gate: the real tree holds zero unbaselined findings. Every
    rule (all 12, dataflow pass included) runs; waivers/baseline entries
    are visible in `suppressed` so deferred debt stays countable."""
    findings, suppressed, stats = run_lint(REPO_ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)
    # the gate must fit tier-1: the ISSUE-15 acceptance budget is 45s
    # with the dataflow pass; hold a stricter practical bound so slow
    # creep is visible long before the budget is at risk
    assert stats["elapsed_s"] < 45, stats
    # per-rule timing rides the stats so FUTURE rules can be budgeted
    # (the --json satellite): every registered rule reports a time and
    # a finding count
    assert set(stats["per_rule_s"]) == set(stats["per_rule_findings"])
    assert len(stats["per_rule_s"]) >= 12, stats["per_rule_s"]
    # sanity: the analyzer actually scanned the tree
    assert stats["files"] > 100 and stats["functions"] > 1000, stats


def test_changed_only_one_file_diff_is_fast(tmp_path):
    """ISSUE-15 satellite: --changed-only on a one-file diff does scoped
    work — the pre-commit speed contract, held as a count (a wall time
    on a shared CPU is no result): the rules see the diff's reverse
    closure and nothing else, and for a leaf module that closure is a
    small share of the tree. In-process, the same work the CLI flag
    performs (context load + reverse closure + scoped rules)."""
    from tools.dynalint.engine import changed_closure

    ctx = load_context(REPO_ROOT)
    closure = changed_closure(ctx.graph, {"dynamo_tpu/sim/report.py"})
    findings, _, stats = run_lint(REPO_ROOT, ctx=ctx, only_paths=closure)
    assert findings == [], "\n".join(f.render() for f in findings)
    assert "dynamo_tpu/sim/report.py" in closure
    assert stats["scoped_files"] == len(closure)
    assert 10 * len(closure) < stats["files"], (len(closure), stats)


def test_changed_only_scopes_rules(tmp_path):
    """--changed-only semantics: a seeded violation OUTSIDE the closure
    is not reported; the same violation inside the closure is."""
    root = make_repo(tmp_path, {
        "pkg/dirty.py": DL001_SRC,
        "pkg/other.py": "def unrelated():\n    return 1\n"})
    ctx = load_context(root, scan_roots=("pkg",))
    # closure = only the untouched file → the dirty file's findings are
    # out of scope
    findings, _, _ = run_lint(root, rules=["DL001"], ctx=ctx,
                              baseline_path=os.path.join(root, "nb.json"),
                              only_paths={"pkg/other.py"})
    assert findings == []
    ctx2 = load_context(root, scan_roots=("pkg",))
    findings, _, _ = run_lint(root, rules=["DL001"], ctx=ctx2,
                              baseline_path=os.path.join(root, "nb.json"),
                              only_paths={"pkg/dirty.py"})
    assert len(findings) == 2


def test_changed_only_cli_smoke():
    """`python -m tools.dynalint --changed-only` is the committed
    pre-commit interface: exits 0 against the real tree whether the
    worktree is dirty (scoped scan) or clean (nothing to do)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynalint", "--changed-only"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert ("changed-only" in proc.stdout
            or "nothing to scan" in proc.stdout), proc.stdout


def test_cli_entrypoint_runs():
    """`python -m tools.dynalint` is the committed interface (CI and
    humans share it)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dynalint", "--rules", "DL006"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_schema_lock_is_current():
    """The committed lockfile matches the tree — i.e. nobody edited a
    wire dataclass without running --update-schemas."""
    from tools.dynalint.rules.dl004_schema import extract_schemas
    ctx = load_context(REPO_ROOT)
    current = extract_schemas(ctx)
    with open(os.path.join(REPO_ROOT,
                           "tools/dynalint/schemas.lock.json")) as f:
        locked = json.load(f)
    assert current == locked, (
        "wire schemas drifted from the lockfile — if intentional, run "
        "`python -m tools.dynalint --update-schemas` and commit the diff")


# --------------------------------------- behavior regressions (fixes)

class _RecordingDisk:
    """DiskKvStore-shaped stub: matches the first hash offered, records
    pin/unpin traffic."""

    def __init__(self):
        self.pinned = []
        self.unpinned = []

    def match_prefix(self, hashes, pin=False):
        hit = list(hashes[:1])
        if pin:
            self.pinned.extend(hit)
        return hit

    def unpin(self, hashes):
        self.unpinned.extend(hashes)


class _ExplodingRemote:
    def match_prefix(self, hashes, pin=False):
        raise RuntimeError("buggy remote store")

    def unpin(self, hashes):
        pass


def test_prepare_prefill_releases_pins_on_exception():
    """The DL003 fix: an unexpected raise mid-cascade (here: a buggy
    remote store) must release the device holds AND the disk pins taken
    earlier in the same prepare_prefill call. Before the fix the disk
    pins leaked and the entries were unevictable forever."""
    from dynamo_tpu.llm.kv.pool import KvBlockManager

    disk = _RecordingDisk()
    mgr = KvBlockManager(num_blocks=16, block_size=4,
                         disk_store=disk, remote_store=_ExplodingRemote(),
                         prefer_native=False)
    free_before = mgr.pool.free_blocks
    with pytest.raises(RuntimeError, match="buggy remote store"):
        mgr.prepare_prefill(list(range(12)))
    # every pin taken before the raise was released on the way out
    assert disk.pinned, "fixture must actually exercise the disk rung"
    assert disk.unpinned == disk.pinned
    # and no device block is left held
    assert mgr.pool.free_blocks == free_before


def test_radix_index_event_count_mirror():
    """The DL006 fix: dyn_kv_index_event_count was exported by the C++
    index but wrapped by neither twin. Both now expose event_count()
    with identical semantics (one bump per apply/remove op)."""
    from dynamo_tpu.llm.kv_router.indexer import (RadixIndexPython,
                                                  make_radix_index)

    def drive(idx):
        idx.apply_stored(1, None, [11, 12])
        idx.apply_stored(2, None, [11])
        idx.apply_removed(1, [12])
        idx.remove_worker(2)
        return idx.event_count()

    assert drive(RadixIndexPython()) == 4
    native = make_radix_index(prefer_native=True)
    if type(native).__name__ == "RadixIndexNative":
        assert drive(native) == 4
