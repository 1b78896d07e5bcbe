"""tools/trace_gaps.py over a hand-made profile: the programs of the first
device plane, the gaps between them, and the host events over a gap."""

import importlib.util
import os
from types import SimpleNamespace as NS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "trace_gaps", os.path.join(ROOT, "tools", "trace_gaps.py"))
trace_gaps = importlib.util.module_from_spec(spec)
spec.loader.exec_module(trace_gaps)

MS = 1_000_000


def event(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


PLANES = [
    NS(name="/host:CPU", lines=[
        NS(name="engine", events=[event("loop.admit", 95, 12),
                                  event("loop.dispatch", 107, 3),
                                  event("loop.wait", 110, 400),
                                  event("loop.post", 510, 2)]),
        NS(name="harness", events=[event("bench_anchor", 50, 0.01)])]),
    NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[event("%fusion.1", 0, 1)]),
        NS(name="XLA Modules", events=[
            event("jit_decode_k(123)", 120, 20),     # out of order on purpose
            event("jit_decode_k(123)", 0, 20),
            event("jit_prefill(9)", 30, 70),
            event("jit__move_blocks(7)", 500, 1)])]),
    NS(name="/device:TPU:1", lines=[
        NS(name="XLA Modules", events=[event("jit_other(1)", 0, 1)])]),
]


def test_programs_are_the_first_device_planes_in_time_order():
    mods = trace_gaps.programs(PLANES)
    assert [m[2] for m in mods] == ["jit_decode_k", "jit_prefill",
                                    "jit_decode_k", "jit__move_blocks"]
    assert mods[0][:2] == (0, 20 * MS)
    assert trace_gaps.programs(PLANES[:1]) == []


def test_host_events_over_a_gap_and_the_anchor():
    # the gap between the second decode step's end and the copy
    over = trace_gaps.host_overlaps(PLANES, 140 * MS, 500 * MS)
    assert [(line, name, round(ns / MS)) for line, name, ns, _ in over] == [
        ("/host:CPU / engine", "loop.wait", 360)]
    # an event that overlaps by under 5 ms is not listed; a device plane's
    # own events never are
    assert trace_gaps.host_overlaps(PLANES, 100 * MS, 104 * MS) == []
    assert trace_gaps.anchor_ns(PLANES) == 50 * MS
    assert trace_gaps.anchor_ns(PLANES[1:]) is None
