"""Share of the decode cycles' time spent in the admission pass (KV plan and
prefill dispatch): 100 * sum(admit_ms) / sum(device_ms + host_gap_ms) over the
``decode`` flight records. Phase clock of the engine loop; a program without
it has nothing to read."""


def read(ctx):
    records = [r for r in ctx["flight"]
               if r["kind"] == "decode" and "admit_ms" in r]
    cycle = sum(r["device_ms"] + r["host_gap_ms"] for r in records)
    if not cycle:
        return None
    return 100.0 * sum(r["admit_ms"] for r in records) / cycle
