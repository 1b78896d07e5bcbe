"""From a ``jax.profiler`` trace to device busy time, the device operations
that took most time and the longest idle gaps. Reads the ``.xplane.pb`` with
nothing but JAX (``jax.profiler.ProfileData``).

Two steps, so that the second can be checked on a small recorded list of
events (``fixtures/trace_events.json``) with no profiler at hand:

* ``device_events(path)`` → per device, the ``(name, start_ns, dur_ns)`` of
  every event on the device's operation line;
* ``reduce(events_by_device, window)`` → the numbers: what the result line
  prints (busy time, the longest operations and idle gaps) and, for the
  per-layer readers, ``ops``: every operation with its seconds and count.
  ``program_table`` does the same for the line of whole programs.

Busy time is the union of the intervals in which an operation ran on the
device, so operations that overlap (a copy under a matmul) count once. A
``while`` or ``conditional`` op encloses the operations of its body on the
same line: the union takes care of that for busy time, and the table of
operations leaves out an event that encloses others, so that a scanned
layer stack is listed by what ran inside it.
"""

from __future__ import annotations

import glob
import os
import re

# the lines of a TPU device plane that hold one event per executed op
OP_LINES = ("XLA Ops",)
# and the line that holds one event per executed program
MODULE_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def describe(path: str) -> list:
    """Planes and lines with their event counts: what to look at by hand
    before trusting a reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append((plane.name, line.name, len(events),
                        [e.name for e in events[:3]]))
    return out


def short_name(hlo: str) -> str:
    """An op's event name is its whole HLO line. Keep what tells ops
    apart: the result's name, its element type and shape, the opcode and,
    for a custom call, its target — ``%fusion.151 bf16[1024,28672] fusion``."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    shape = m.group(1) if m else ""
    op = re.search(r"\)?\s([a-z][a-z0-9-]*)\(", rhs)
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    parts = [lhs, shape, op.group(1) if op else "",
             target.group(1) if target else ""]
    return " ".join(p for p in parts if p)[:120]


def scope_of(hlo: str):
    """The ``jax.named_scope`` path of an op whose HLO line carries its
    ``op_name`` (``jit(decode_k)/decode/attention/dot_general`` →
    ``decode/attention``: without the program and the primitive), else
    None. The TPU profiler of JAX 0.9.0 / libtpu 0.0.34 writes no
    ``op_name`` into its events (PERF.md section 7)."""
    m = re.search(r'op_name="([^"]+)"', hlo)
    if not m:
        return None
    return "/".join(m.group(1).split("/")[1:-1]) or None


def device_events(path: str, lines: tuple = OP_LINES,
                  scopes: dict = None) -> dict:
    """{device plane name: [(short op name, start_ns, dur_ns), ...]}.
    ``scopes``, where given, is filled with {short op name: named-scope
    path} for the ops whose events carry one (``scope_of``)."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        events = []
        for line in plane.lines:
            if line.name not in lines:
                continue
            for e in line.events:
                name = short_name(e.name)
                events.append((name, int(e.start_ns), int(e.duration_ns)))
                if scopes is not None and name not in scopes:
                    scope = scope_of(e.name)
                    if scope:
                        scopes[name] = scope
        out[plane.name] = events
    return out


def program_table(events_by_device: dict) -> list:
    """[[program name, seconds, dispatches]] by device time, from the
    events of the line that holds one per executed program
    (``device_events(path, lines=(MODULE_LINE,))``), averaged over the
    devices; the compiler's fingerprint in brackets is dropped, so every
    bucket of ``jit_prefill`` adds up."""
    totals = {}
    for events in events_by_device.values():
        for name, _, dur in events:
            row = totals.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0])
            row[0] += dur
            row[1] += 1
    n = max(1, len(events_by_device))
    return sorted(([k, ns / n / 1e9, count / n]
                   for k, (ns, count) in totals.items()),
                  key=lambda row: -row[1])


def host_events(path: str, name: str) -> list:
    """[(start_ns, dur_ns)] of the host-plane events called ``name`` (the
    benchmark's own TraceAnnotations: the anchor that ties the profiler's
    clock to the host's)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((int(e.start_ns), int(e.duration_ns))
                       for e in line.events if e.name == name)
    return out


def union(intervals: list) -> list:
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _leaves(events: list) -> list:
    """Events that enclose no other event of the same line."""
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    leaves, stack = [], []      # stack of (end, index into ordered, has_child)
    for name, start, dur in ordered:
        end = start + dur
        while stack and stack[-1][0] <= start:
            done = stack.pop()
            if not done[2]:
                leaves.append(done[1])
        if stack:
            stack[-1][2] = True
        stack.append([end, (name, start, dur), False])
    leaves.extend(item[1] for item in stack if not item[2])
    return leaves


def reduce(events_by_device: dict, window: tuple = None,
           host_spans: list = None, top: int = 10,
           scopes: dict = None) -> dict:
    """→ {"busy_s", "window_s", "device_ops", "idle_gaps", "ops"} and,
    where ``scopes`` ({op name: named-scope path}, from
    ``device_events``) names any op, "scopes".

    ``device_ops`` are the ``top`` operations by time, ``ops`` all of them
    as [name, seconds, count]; both leave out an op that encloses others.

    ``window``: (start_ns, end_ns) on the trace's clock; by default from
    the first device event to the last. ``busy_s`` is averaged over the
    devices. ``host_spans``: [(label, start_ns, end_ns)] of what the host
    was doing; each idle gap takes the label of the span that covers most
    of it, or "none"."""
    if not events_by_device or not any(events_by_device.values()):
        raise ValueError("the trace holds no device operation")
    if window is None:
        starts = [s for evs in events_by_device.values() for _, s, _ in evs]
        ends = [s + d for evs in events_by_device.values() for _, s, d in evs]
        window = (min(starts), max(ends))
    w0, w1 = window
    busy, per_op, gaps = [], {}, []      # per_op: name → [ns, count]
    for events in events_by_device.values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in events
                   if s < w1 and s + d > w0]
        merged = union(clipped)
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in _leaves(events):
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                row = per_op.setdefault(name, [0, 0])
                row[0] += hi - lo
                row[1] += 1
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(events_by_device)

    def label(gap):
        best, cover = "none", 0
        for name, s, e in host_spans or ():
            c = min(e, gap[1]) - max(s, gap[0])
            if c > cover:
                best, cover = name, c
        return best

    by_label = {}
    for gap in gaps:
        key = label(gap)
        by_label[key] = by_label.get(key, 0) + (gap[1] - gap[0])
    ops = sorted(([name, ns / n_dev / 1e9, count / n_dev]
                  for name, (ns, count) in per_op.items()),
                 key=lambda row: -row[1])
    out = {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [row[:2] for row in ops[:top]],
        "ops": ops,
        "idle_gaps": [[name, ns / n_dev / 1e9] for name, ns in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max((g[1] - g[0] for g in gaps), default=0) / 1e9,
    }
    by_scope = {}
    for name, seconds, _ in ops:
        if name in (scopes or ()):
            by_scope[scopes[name]] = by_scope.get(scopes[name], 0) + seconds
    if by_scope:
        out["scopes"] = sorted(([k, v] for k, v in by_scope.items()),
                               key=lambda row: -row[1])
    return out
