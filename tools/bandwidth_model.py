"""Bandwidth-bound models for the host-KV tier and the disagg wire plane.

Why this exists: an e2e measurement of the host tier or the TCP wire
plane is only as good as the link it ran over. This tool states explicit
bounds instead: analytic transfer budgets at stated link speeds, from
(a) prefill/decode throughput constants (not measured on this round's
chip, PERF.md) and (b) the wire serialization cost MEASURED live on this
host.

Reference claims being bounded: docs/architecture.md:91 (+40% TTFT from
KV reuse) and the NIXL bulk-transfer role (SURVEY §5.8).

Usage: python tools/bandwidth_model.py [--model 1b|8b|70b] [--json]
"""

import argparse
import json
import sys
import time

import numpy as np

# (layers, kv_heads, head_dim, params) — bf16 KV
GEOMETRIES = {
    "1b": (16, 8, 64, 1.24e9),
    "8b": (32, 8, 128, 8.0e9),
    "70b": (80, 8, 128, 70e9),
}

V5E_BF16_PEAK = 197e12
# measured anchor (PERF.md "Prefill"): flash prefill runs at ~56% MFU on
# the chip, so prefill throughput for a geometry is 0.56 * peak / 2P
PREFILL_MFU = 0.56

D2H_GBPS = (10.0, 30.0, 100.0)      # TPU-VM device↔host links
DCN_GBITS = (10.0, 25.0)            # cross-host links (Gb/s)


def kv_bytes_per_token(model: str, itemsize: int = 2) -> int:
    L, kvh, dh, _ = GEOMETRIES[model]
    return 2 * L * kvh * dh * itemsize


def prefill_tok_per_s(model: str) -> float:
    _, _, _, params = GEOMETRIES[model]
    return PREFILL_MFU * V5E_BF16_PEAK / (2.0 * params)


def measure_serialization_ms(model: str, tokens: int,
                             block_size: int = 16) -> float:
    """Time the REAL wire pack (engine/block_copy.to_wire_format) for this
    many tokens of KV on this host — measured, not modeled."""
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    from dynamo_tpu.engine.block_copy import to_wire_format
    L, kvh, dh, _ = GEOMETRIES[model]
    n = max(tokens // block_size, 1)
    slab = np.zeros((L, n, block_size, kvh * dh), np.float16)
    t0 = time.monotonic()
    to_wire_format(slab, kvh)
    return 1e3 * (time.monotonic() - t0) * 2      # k and v


def host_tier_table(model: str) -> list:
    """Restore-vs-recompute: reusing `hit` tokens of host KV pays iff the
    h2d restore beats re-prefilling them. Rows per d2h bandwidth."""
    bpt = kv_bytes_per_token(model)
    pf = prefill_tok_per_s(model)
    rows = []
    for gbps in D2H_GBPS:
        # break-even: restore wins for any hit length when link tok/s
        # exceeds prefill tok/s (both scale linearly; dispatch overhead
        # ~1 ms is shared noise)
        link_tok_s = gbps * 1e9 / bpt
        hit = 2048
        restore_ms = 1e3 * hit * bpt / (gbps * 1e9) + 1.0
        recompute_ms = 1e3 * hit / pf
        rows.append({
            "d2h_gbps": gbps,
            "link_tok_per_s": round(link_tok_s),
            "prefill_tok_per_s": round(pf),
            "restore_ms_2k_hit": round(restore_ms, 2),
            "recompute_ms_2k_hit": round(recompute_ms, 2),
            "tier_pays": bool(link_tok_s > pf),
            "ttft_saving_pct_2k": round(
                100.0 * (recompute_ms - restore_ms)
                / max(recompute_ms, 1e-9), 1),
        })
    return rows


def wire_plane_table(model: str, isl: int = 3072) -> list:
    """Disagg KV handoff across hosts: serialization (measured here) +
    bytes over DCN, compared to the agg baseline prefill."""
    bpt = kv_bytes_per_token(model)
    ser_ms = measure_serialization_ms(model, isl)
    pf_ms = 1e3 * isl / prefill_tok_per_s(model)
    rows = []
    for gbits in DCN_GBITS:
        xfer_ms = 1e3 * isl * bpt / (gbits * 1e9 / 8)
        overhead = ser_ms + xfer_ms
        rows.append({
            "dcn_gbit": gbits,
            "kv_mb": round(isl * bpt / 1e6, 1),
            "serialize_ms_measured": round(ser_ms, 2),
            "transfer_ms": round(xfer_ms, 2),
            "overhead_ms": round(overhead, 2),
            "agg_prefill_ms": round(pf_ms, 2),
            "overhead_vs_agg_pct": round(100.0 * overhead / pf_ms, 1),
        })
    return rows


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=sorted(GEOMETRIES), default="1b")
    p.add_argument("--isl", type=int, default=3072)
    p.add_argument("--json", action="store_true")
    args = p.parse_args()

    host = host_tier_table(args.model)
    wire = wire_plane_table(args.model, args.isl)
    if args.json:
        print(json.dumps({"model": args.model, "isl": args.isl,
                          "host_tier": host, "wire_plane": wire}))
        return
    bpt = kv_bytes_per_token(args.model)
    print(f"# {args.model}: {bpt} KV bytes/token, prefill "
          f"{prefill_tok_per_s(args.model):,.0f} tok/s "
          f"(measured {PREFILL_MFU:.0%} MFU anchor)\n")
    print("## host tier (restore 2048-token hit vs recompute)")
    print("| d2h GB/s | link tok/s | restore ms | recompute ms | pays | "
          "TTFT saving |")
    print("|---|---|---|---|---|---|")
    for r in host:
        print(f"| {r['d2h_gbps']} | {r['link_tok_per_s']:,} | "
              f"{r['restore_ms_2k_hit']} | {r['recompute_ms_2k_hit']} | "
              f"{'yes' if r['tier_pays'] else 'no'} | "
              f"{r['ttft_saving_pct_2k']}% |")
    print(f"\n## wire plane (disagg handoff, ISL={args.isl})")
    print("| DCN Gb/s | KV MB | serialize ms (measured) | transfer ms | "
          "overhead ms | vs agg prefill |")
    print("|---|---|---|---|---|---|")
    for r in wire:
        print(f"| {r['dcn_gbit']} | {r['kv_mb']} | "
              f"{r['serialize_ms_measured']} | {r['transfer_ms']} | "
              f"{r['overhead_ms']} | {r['overhead_vs_agg_pct']}% |")


if __name__ == "__main__":
    main()
