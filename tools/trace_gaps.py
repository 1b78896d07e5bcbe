"""The device's long idle gaps in a kept profiler trace, and what the host
was doing over each.

A traced benchmark run that keeps its trace (``benchmark/run.py --trace 1
--keep-trace`` leaves it in ``.bench_work/trace``) reports ``device.idle_pct``
and the gaps' total; this lists every gap between two programs of the first
device plane that is longer than a threshold, the programs on both sides, and
the host-plane events that overlap it by 5 ms or more: among them the engine
loop's ``loop.<phase>`` annotations (``engine/flight_recorder.py``
``PhaseClock``) and the harness's ``bench_anchor``, which the profiler's
start precedes. A gap that ends before ``bench_anchor`` is the profiler
starting up on the shared thread, not the loop; one covered by a
``loop.<phase>`` is that phase's.

Usage: JAX_PLATFORMS=cpu python tools/trace_gaps.py <trace dir> [min gap ms]
(reads the file only: no device needed).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

OVERLAP_NS = 5e6        # a host event is listed from this much overlap
HOST_EVENTS_A_LINE = 6


def programs(planes) -> list:
    """[(start_ns, dur_ns, name)] of the executed programs of the first
    device plane that has any, in time order."""
    import trace_reduce
    for plane in planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.MODULE_LINE:
                return sorted((int(e.start_ns), int(e.duration_ns),
                               e.name.split("(")[0]) for e in line.events)
    return []


def host_overlaps(planes, g0: int, g1: int) -> list:
    """[(plane / line, event name, overlap ns, count)] of the host-plane
    events over the gap [g0, g1], the longest overlaps of each line."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            hits = {}
            for e in line.events:
                start, dur = int(e.start_ns), int(e.duration_ns)
                overlap = min(start + dur, g1) - max(start, g0)
                if overlap > OVERLAP_NS:
                    row = hits.setdefault(e.name[:90], [0, 0])
                    row[0] += overlap
                    row[1] += 1
            top = sorted(hits.items(), key=lambda kv: -kv[1][0])
            out.extend((f"{plane.name} / {line.name}", name, ns, n)
                       for name, (ns, n) in top[:HOST_EVENTS_A_LINE])
    return out


def anchor_ns(planes):
    """Start of the harness's ``bench_anchor`` annotation, or None."""
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench_anchor":
                    return int(e.start_ns)
    return None


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    import trace_reduce
    from jax.profiler import ProfileData
    min_gap_ns = float(argv[1]) * 1e6 if len(argv) > 1 else 20e6
    planes = list(ProfileData.from_file(
        trace_reduce.find_xplane(argv[0])).planes)
    mods = programs(planes)
    if not mods:
        print("no device plane with a line of programs in this trace")
        return 1
    t0 = mods[0][0]
    anchor = anchor_ns(planes)
    print(f"{len(mods)} programs; the first starts at 0, the last ends at "
          f"{(mods[-1][0] + mods[-1][1] - t0) / 1e6:.1f} ms; bench_anchor at "
          + ("none" if anchor is None else f"{(anchor - t0) / 1e6:.1f} ms"))
    gaps = [(a[0] + a[1], b[0], a[2], b[2]) for a, b in zip(mods, mods[1:])
            if b[0] - a[0] - a[1] > min_gap_ns]
    for g0, g1, before, after in gaps:
        where = ("" if anchor is None else
                 " (ends before bench_anchor)" if g1 <= anchor else
                 " (after bench_anchor)")
        print(f"\nGAP {(g1 - g0) / 1e6:.1f} ms at {(g0 - t0) / 1e6:.1f} ms, "
              f"after {before}, before {after}{where}")
        for line, name, ns, n in host_overlaps(planes, g0, g1):
            print(f"   {line}: {name}  overlap {ns / 1e6:.1f} ms x{n}")
        around = [(round((s - t0) / 1e6, 1), round(d / 1e6, 1), n)
                  for s, d, n in mods if g0 - 300e6 < s < g1 + 100e6]
        print("   programs around it (start ms, ms, name):", around[:40])
    if not gaps:
        print(f"no gap over {min_gap_ns / 1e6:.0f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
