"""Deterministic schedule recording + replay for the engine core.

Debugging aid for async-interleaving bugs (KNOWN_ISSUES: the pipelined
dispatch + preemption exactness race). The reference debugs its engine-side
races with deterministic in-process mock transports
(lib/runtime/tests/common/mock.rs); our engine's nondeterminism lives in
the asyncio-loop interleaving of admissions/harvests against in-flight XLA
dispatches, so the analogous tool is: record the complete scheduler
decision log of a live run (every dispatched program's HOST inputs, in
device order), then

- `replay()` re-executes the identical dispatch sequence synchronously
  (block_until_ready between programs). If the replay reproduces the live
  run's (corrupt) tokens, the bug is deterministic given the schedule and
  lives in the recorded inputs or step semantics; if the replay diverges
  from the live run, the corruption needed real async overlap — a buffer
  lifetime / donation hazard.
- `check_log()` simulates pool-slot ownership over the log and flags any
  dispatch that READS a KV pool slot last written by a different request —
  the stale-read signature — plus input-consistency invariants
  (chained positions/tokens, table/ownership mismatches), with no model
  evaluation at all.

Recording copies only small host arrays; it does not synchronize the
device, so it can run inside the adversarial sweeps without perturbing
the interleaving materially.

Pipeline-parallel runs record and replay through the SAME event set:
the pp core's _prefill_jit/_decode_k_jit keep the single-device host
contracts (engine/core._compile_jits_pp), so exec_prefill_event /
exec_dispatch_event marshal a recorded pp schedule into the
token-interleaved stage programs untouched — replay() against a
same-config pp core is bit-exact (tests/test_pipeline_parallel.py), and
the live multihost follower consumes the identical stream.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


# Leader-side host bookkeeping: events the recorder emits for stream
# accounting, divergence diffing (compare_replay), and preemption-policy
# forensics — they carry NO device-state transition, so neither the
# offline replayer nor a multihost follower executes them. Every event
# the recorder emits must be EITHER replayed below OR listed here
# (dynalint DL009 enforces the classification is total and disjoint
# from multihost.WIRE_EVENTS).
HOST_EVENTS = frozenset(
    {"admit", "first_token", "harvest", "ragged_harvest", "spec_harvest",
     "preempt", "release"})


class Recorder:
    """Collects scheduler events in device-dispatch order."""

    def __init__(self) -> None:
        self.events: List[dict] = []
        self.dispatch_seq = 0

    def rec(self, ev: str, **kw) -> None:
        kw["ev"] = ev
        self.events.append(kw)

    def next_dispatch_id(self) -> int:
        self.dispatch_seq += 1
        return self.dispatch_seq


# --------------------------------------------------------------------------
# Synchronous replay of the recorded dispatch sequence
# --------------------------------------------------------------------------


def _exec_prefill(core, kv, ev: dict, sp: bool):
    """The ONE home of recorded-event → prefill-jit marshalling (used by
    both the offline replayer and the live multihost follower). The sp
    variant issues _prefill_sp_jit and has no start_pos (the sp path
    never has a prefix hit); everything else is identical by
    construction. Returns (tok_device, kv)."""
    import jax.numpy as jnp

    from .sampling import make_slot_keys

    key = make_slot_keys(core.cfg.seed, jnp.asarray([ev["samp_seed"]]),
                         jnp.asarray(ev["key_step"]))[0]
    head = (jnp.asarray(ev["padded"]), jnp.asarray(ev["table"]))
    pos = (() if sp
           else (jnp.asarray(ev["start_pos"], jnp.int32),))
    tail = (jnp.asarray(ev["true_len"], jnp.int32), key,
            jnp.asarray(ev["temp"], jnp.float32),
            jnp.asarray(ev["top_k"], jnp.int32),
            jnp.asarray(ev["top_p"], jnp.float32))
    if "next_tok" in ev:
        # a resident drafter's prefill: the token after the chunk goes in,
        # the first draft comes out behind kv (engine/core.py)
        tail += (jnp.asarray(ev["next_tok"], jnp.int32),)
    fn = core._prefill_sp_jit if sp else core._prefill_jit
    tok, _lp, kv, *_draft = fn(core.params, kv, *head, *pos, *tail)
    return tok, kv


def exec_prefill_event(core, kv, ev: dict):
    return _exec_prefill(core, kv, ev, sp=False)


def exec_sp_prefill_event(core, kv, ev: dict):
    return _exec_prefill(core, kv, ev, sp=True)


def exec_kv_store_event(kv, ev: dict, pool, block_size: int,
                        spill_stage: Optional[dict] = None) -> None:
    """Mirror one of the leader's offload commits: gather the SAME device
    blocks from ``kv`` (bit-identical by the replay/stream induction) and
    apply the literal hash→slot placements to ``pool``. Single home of
    the kv_store event, shared by the offline replayer and the live
    multihost follower (engine/multihost.py).

    ``spill_stage``: when the leader runs a disk (G3) tier, the event's
    ``spills`` list names the evicted hashes its spill queue accepted —
    stage a copy of each such row (read from the mirror arena BEFORE the
    eviction overwrites it) keyed by hash, so the later "kv_disk_store"
    commit can apply the leader's literal disk placements from
    bit-identical bytes (exec_kv_disk_store_event)."""
    from .block_copy import gather_blocks_to_host

    spills = set(ev.get("spills") or ())
    ids = [int(it[3]) for it in ev["items"]]
    values = gather_blocks_to_host(kv, ids, block_size, pool.num_kv_heads)
    for i, (h, hslot, evicted, _bid) in enumerate(ev["items"]):
        if (spill_stage is not None and evicted is not None
                and evicted in spills):
            vslot = pool._by_hash.get(evicted)
            if vslot is not None and pool._arena is not None:
                spill_stage[evicted] = pool.row_copy(vslot)
        pool.apply_store(h, hslot, evicted,
                         {key: arr[:, :, i]
                          for key, arr in values.items()})


def exec_kv_disk_store_event(ev: dict, disk_store, pool,
                             spill_stage: dict) -> None:
    """Apply one of the leader's disk-tier spill commits to a mirror
    store: literal placements (hash + the leader's eviction set), bytes
    from the staged row copy (eviction-driven spills) or straight from
    the host mirror arena (flush-driven spills — the row is still
    resident there). Never re-runs the LRU policy. Shared by the offline
    replayer and the live multihost follower."""
    for h, th, ph, evicted in ev["items"]:
        values = spill_stage.pop(h, None)
        if values is None:
            slot = pool._by_hash.get(h) if pool is not None else None
            if slot is None:
                raise ValueError(
                    f"kv_disk_store for hash {h:#x} has no staged row "
                    f"copy and no host-mirror residence — the leader's "
                    f"kv_store spills list and this mirror diverged")
            values = pool.row_copy(slot)
        disk_store.apply_put(h, list(evicted), values,
                             tokens_hash=th, parent_hash=ph)


def exec_kv_remote_restore_event(kv, ev: dict, block_size: int,
                                 remote_store=None):
    """Re-execute a remote (G4) tier restore: scatter the leader's
    FETCHED bytes into the same device targets with the same program
    the leader's admission ran. Single home of the kv_remote_restore
    event (offline replayer + live multihost follower).

    Fetch-or-bytes: the event normally carries ``values`` (the stacked
    wire dict the leader fetched — the fleet-shared tier cannot be
    re-walked per rank); when absent, the hashes are fetched from
    ``remote_store`` instead — correct whenever the store shares the
    leader's content-addressed object root, where equal hash ⇒ equal
    bytes by construction. Returns the new kv."""
    from .block_copy import prep_host_values, scatter_prepped

    vals = ev.get("values")
    if vals is None:
        if remote_store is None:
            raise ValueError(
                "kv_remote_restore carries no values and no remote "
                "store was provided — replay with the recorded engine "
                "config (kv_remote_dir) or a bytes-mode recording")
        vals = remote_store.fetch(list(ev["remote_hashes"]))
    ids, pv = prep_host_values(list(ev["remote_targets"]), vals)
    return scatter_prepped(kv, ids, pv, block_size)


def exec_host_restore_event(kv, ev: dict, pool, block_size: int,
                            disk_store=None):
    """Re-execute a host/disk-tier h2d restore from the mirror tiers:
    same slots/hashes, same device targets, same scatter program as the
    leader's admission. Single home of the hit_transfer restore path
    (see exec_kv_store_event). Returns the new kv."""
    from .block_copy import prep_host_values, scatter_prepped

    parts = []
    targets: list = []
    if ev.get("host_slots"):
        parts.append(pool.fetch(list(ev["host_slots"])))
        targets += list(ev["host_targets"])
    if ev.get("disk_hashes"):
        if disk_store is None:
            raise ValueError(
                "hit_transfer references disk-tier hashes but no mirror "
                "disk store was provided — replay with the recorded "
                "engine config (kv_disk_dir/kv_disk_blocks)")
        parts.append(disk_store.fetch(list(ev["disk_hashes"])))
        targets += list(ev["disk_targets"])
    vals = (parts[0] if len(parts) == 1 else
            {k: np.concatenate([p[k] for p in parts], axis=2)
             for k in parts[0]})
    ids, vals = prep_host_values(targets, vals)
    return scatter_prepped(kv, ids, vals, block_size)


def exec_dispatch_event(core, kv, ev: dict, chain):
    """Issue the recorded K-step decode dispatch against `kv`. ``chain`` is
    the chained-from dispatch's [K, B] device tokens (None when host-fed).
    Single home of the event → _decode_k_jit marshalling, like
    exec_prefill_event. Returns (toks_k, kv)."""
    import jax.numpy as jnp

    host_tokens = jnp.array(np.asarray(ev["tokens"]))
    if ev["chained_from"] is not None:
        tokens_in = core._merge_jit(
            chain, host_tokens, jnp.array(np.asarray(ev["mask"])))
    else:
        tokens_in = host_tokens
    K = int(ev["K"])
    B = np.asarray(ev["tokens"]).shape[0]
    planned = np.asarray(ev.get("planned", np.zeros((K, B), np.int32)))
    pmask = np.asarray(ev.get("planned_mask", np.zeros((K, B), bool)))
    toks_k, _lps, kv = core._decode_k_jit(
        core.params, kv, tokens_in,
        jnp.array(ev["positions"]), jnp.array(ev["tables"]),
        jnp.array(ev["seeds"]), jnp.array(ev["steps"]),
        jnp.array(ev["temperature"]), jnp.array(ev["top_k"]),
        jnp.array(ev["top_p"]),
        jnp.array(planned), jnp.array(pmask), core._base_key)
    return toks_k, kv


def exec_verify_event(core, kv, ev: dict):
    """Issue the recorded speculative verify dispatch (engine/spec/)
    against ``kv``. Single home of the event → _verify_jit marshalling
    (offline replayer + live multihost follower). Returns
    (toks [B, Tv], kv)."""
    import jax.numpy as jnp

    if core._verify_jit is None or \
            core.cfg.spec_k + 1 != np.asarray(ev["tokens"]).shape[1]:
        raise NotImplementedError(
            f"recorded verify dispatch has {np.asarray(ev['tokens']).shape[1]}"
            f" rows/slot but this core compiled spec_k={core.cfg.spec_k} — "
            f"replay with the recorded engine config")
    # a resident drafter's two-row step takes a carry (a recorded step is
    # harvested before the next is built: nothing chained) and returns its
    # drafts and its own carry behind kv: the program that served
    toks, _lps, kv, *_drafts = core._verify_jit(
        core.params, kv, jnp.array(np.asarray(ev["tokens"])),
        jnp.array(ev["positions"]), jnp.array(ev["tables"]),
        jnp.array(ev["seeds"]), jnp.array(ev["steps"]),
        jnp.array(ev["temperature"]), jnp.array(ev["top_k"]),
        jnp.array(ev["top_p"]),
        *(core._carry_zero if core.resident_drafter else ()))
    return toks, kv


def exec_ragged_event(core, kv, ev: dict, chain=None):
    """Issue the recorded unified ragged dispatch (engine/ragged.py)
    against ``kv``. Single home of the event → _ragged_jit marshalling
    (offline replayer + live multihost follower). ``chain`` is the
    chained-from dispatch's device tokens for a pipelined ragged event
    (None when host-fed). Returns (toks [S or capacity], kv)."""
    import jax.numpy as jnp

    if core._ragged_jit is None:
        raise NotImplementedError(
            "recorded ragged dispatch but this core compiled without "
            "ragged_dispatch — replay with the recorded engine config")
    if core.cfg.ragged_max_tokens != np.asarray(ev["tokens"]).shape[0]:
        raise NotImplementedError(
            f"recorded ragged dispatch has "
            f"{np.asarray(ev['tokens']).shape[0]} token rows but this "
            f"core compiled ragged_max_tokens="
            f"{core.cfg.ragged_max_tokens} — replay with the recorded "
            f"engine config")
    # the steps array's shape IS the sampling-variant marker: [B+1]
    # slot steps (spec_k == 0) vs [capacity] row steps (the spec-
    # enabled row-sampled program) — a mismatch means the replaying
    # core compiled the other variant
    row_sampled = (np.asarray(ev["steps"]).shape[0]
                   == np.asarray(ev["tokens"]).shape[0])
    if row_sampled != core._ragged_row_sampled:
        raise NotImplementedError(
            f"recorded ragged dispatch was "
            f"{'row' if row_sampled else 'slot'}-sampled but this core "
            f"compiled spec_k={core.cfg.spec_k} — replay with the "
            f"recorded engine config")
    host_tokens = jnp.array(np.asarray(ev["tokens"]))
    if ev.get("chained_from") is not None:
        tokens_in = core._ragged_merge_jit(
            chain, jnp.array(np.asarray(ev["srows"])), host_tokens,
            jnp.array(np.asarray(ev["mask"])))
    else:
        tokens_in = host_tokens
    toks, _lps, kv = core._ragged_jit(
        core.params, kv, tokens_in,
        jnp.array(np.asarray(ev["positions"])),
        jnp.array(np.asarray(ev["tables"])),
        jnp.array(np.asarray(ev["row_slot"])),
        jnp.array(np.asarray(ev["starts"])),
        jnp.array(np.asarray(ev["counts"])),
        jnp.array(np.asarray(ev["sample_rows"])),
        jnp.array(np.asarray(ev["seeds"])),
        jnp.array(np.asarray(ev["steps"])),
        jnp.array(np.asarray(ev["temperature"])),
        jnp.array(np.asarray(ev["top_k"])),
        jnp.array(np.asarray(ev["top_p"])))
    return toks, kv


class _MemDiskMirror:
    """In-memory stand-in for DiskKvStore during offline replay (the
    replayer applies the leader's literal disk placements; durability is
    the live store's concern, not the replay's): apply_put / fetch /
    contains with the same signatures."""

    def __init__(self) -> None:
        self._blocks: Dict[int, dict] = {}

    def apply_put(self, h, evicted, values, tokens_hash=None,
                  parent_hash=None) -> None:
        for e in evicted:
            self._blocks.pop(e, None)
        self._blocks[h] = values

    def contains(self, h) -> bool:
        return h in self._blocks

    def fetch(self, hashes) -> dict:
        blocks = [self._blocks[h] for h in hashes]
        return {k: np.ascontiguousarray(
                    np.stack([b[k] for b in blocks], axis=2))
                for k in blocks[0]}


def replay(core, events: List[dict], fingerprint: bool = False) -> dict:
    """Re-execute the recorded schedule against a fresh KV cache, strictly
    synchronously. `core` supplies params and compiled jits (its own KV is
    untouched). Returns {"prefill": {seq: tok}, "dispatch": {id: [K,B]},
    "verify": {id: [B,Tv]}, "fingerprints": [(label, digest), ...]}.
    """
    import jax

    # a zeroed cache of the recording core's own making, array for array
    # (an int8-KV engine replayed against a bf16 pool would retrace the
    # unquantized branch and report phantom divergence; so would a family's
    # index keys, rings, state or window pool, or a group sized otherwise
    # than the engine sizes it)
    kv = core.fresh_kv()[0]
    out = {"prefill": {}, "dispatch": {}, "verify": {}, "ragged": {},
           "fingerprints": []}
    disp_toks: Dict[int, object] = {}
    disk_mirror = None     # disk (G3) mirror, built from kv_disk_store
    spill_stage: Dict[int, dict] = {}   # hash → staged evicted-row copy
    mirror = None          # host-tier mirror pool, built from kv_store
    # events exactly like a multihost follower's (engine/multihost.py):
    # gather the SAME blocks from the replay KV, apply literal placements
    mirrored_slots: set = set()   # host slots with an IN-LOG store
    # pool slots written by in-log prefills/dispatches: a prefix hit whose
    # blocks were registered BEFORE recording began has no in-log writer —
    # the fresh replay KV holds zeros there and every downstream compare
    # would report phantom mismatches (advisor round-1 finding)
    bs = core.cfg.kv_block_size
    written: set = set()

    def fp(label):
        if not fingerprint:
            return
        import hashlib
        h = hashlib.blake2b(digest_size=16)
        # key-agnostic (llama {"k","v"}, MLA {"kv"}); sorted so the
        # fingerprint is stable across dict orders
        for key in sorted(kv):
            h.update(np.asarray(kv[key]).tobytes())
        out["fingerprints"].append((label, h.hexdigest()))

    for ev in events:
        kind = ev["ev"]
        if kind in HOST_EVENTS:
            # leader-side bookkeeping (see HOST_EVENTS): the replay
            # re-derives device state only; compare_replay reads the
            # harvest family out of the SAME event list for the diff
            continue
        if kind == "prefill_unsupported":
            raise NotImplementedError(
                f"run used an unrecorded admission path "
                f"({ev.get('path')}, rid={ev.get('rid')}); replay would "
                f"silently diverge — record only runs without disagg "
                f"onboarding")
        if kind == "precomputed_device_admit":
            # live multihost followers resolve this from their own
            # process bridge (their prefill replica's parked shard); an
            # OFFLINE replay has no bridge and the arrays were never
            # logged (device-resident by design)
            raise NotImplementedError(
                f"device-plane disagg admission for rid={ev.get('rid')} "
                f"is not offline-replayable: the payload's arrays are "
                f"device-resident and not in the log — record with the "
                f"wire plane (precomputed_admit) for replayable disagg "
                f"runs")
        if kind == "handoff_gather":
            # read-only device program (prefill epilogue gather); its
            # output feeds the handoff plane, not the KV pool — offline
            # replay of pool state may skip it
            continue
        if kind == "kv_store":
            from ..llm.kv.offload import make_host_pool
            if mirror is None:
                if core.cfg.host_kv_blocks <= 0:
                    raise NotImplementedError(
                        "the record offloaded to a host tier but the "
                        "replaying core has host_kv_blocks=0 — replay "
                        "with the recorded engine config")
                rows = next(iter(core.kv.values()))
                mirror = make_host_pool(
                    core.cfg.host_kv_blocks, core.model_cfg, bs,
                    core.cfg.kv_quantization, int(rows.shape[-1]),
                    rows.dtype)
            top = max(it[1] for it in ev["items"])
            if top >= core.cfg.host_kv_blocks:
                raise NotImplementedError(
                    f"recorded host-pool slot {top} exceeds this core's "
                    f"host_kv_blocks={core.cfg.host_kv_blocks} — replay "
                    f"with the recorded engine config")
            for b in (int(it[3]) for it in ev["items"]):
                for o in range(bs):
                    if b * bs + o not in written:
                        raise NotImplementedError(
                            f"kv_store gathers block {b} with no in-log "
                            f"writer — its content predates the "
                            f"recording; start recording before any "
                            f"blocks are stored")
            exec_kv_store_event(kv, ev, mirror, bs,
                                spill_stage=spill_stage)
            mirrored_slots.update(int(it[1]) for it in ev["items"])
        if kind == "kv_disk_store":
            # the leader's spill-pump commit: apply its literal disk
            # placements from the rows staged at the kv_store eviction
            # (or still host-mirror-resident, for flush-driven spills)
            if disk_mirror is None:
                disk_mirror = _MemDiskMirror()
            exec_kv_disk_store_event(ev, disk_mirror, mirror, spill_stage)
        if kind == "kv_remote_restore":
            # remote (G4) tier restore: scatter the leader's fetched
            # bytes (carried on the event — the fleet-shared tier is not
            # per-rank replayable) into the same targets; ordered BEFORE
            # the admission's hit_transfer, so the restored blocks gain
            # their in-log writer before the hit walk below reads them
            kv = exec_kv_remote_restore_event(kv, ev, bs,
                                              remote_store=core.remote_store)
            written.update(int(b) * bs + o
                           for b in ev["remote_targets"]
                           for o in range(bs))
            fp(("kv_remote_restore", ev.get("rid")))
        if kind == "hit_transfer" and int(ev.get("hit", 0)) > 0:
            if int(ev.get("disk_hit", 0)) > 0:
                if disk_mirror is None:
                    raise NotImplementedError(
                        f"disk-restored hit for rid={ev.get('rid')} "
                        f"references disk blocks with no in-log "
                        f"kv_disk_store — those spills happened before "
                        f"recording began")
                # handles the combined case too (host_slots may be
                # non-empty alongside the disk hashes)
                kv = exec_host_restore_event(kv, ev, mirror, bs,
                                             disk_store=disk_mirror)
                written.update(int(b) * bs + o
                               for b in (list(ev.get("host_targets") or [])
                                         + list(ev["disk_targets"]))
                               for o in range(bs))
                fp(("disk_restore", ev.get("rid")))
            elif int(ev.get("host_hit", 0)) > 0:
                # host-tier hit: replay the h2d restore from the mirror
                # (exactly the follower's path); the restored target
                # blocks gain an in-log writer for the check below
                if ev.get("host_slots") is None or \
                        ev.get("host_targets") is None:
                    raise NotImplementedError(
                        f"host-restored hit for rid={ev.get('rid')} has "
                        f"no host_slots/host_targets — this log was "
                        f"recorded by a pre-r3 engine; host restores "
                        f"are not replayable for that log version")
                missing_slots = [s for s in ev["host_slots"]
                                 if s not in mirrored_slots]
                if mirror is None or missing_slots:
                    raise NotImplementedError(
                        f"host-restored hit for rid={ev.get('rid')} "
                        f"references host slots {missing_slots[:4]} with "
                        f"no in-log kv_store — those offloads happened "
                        f"before recording began; the mirror would "
                        f"scatter zeros and report phantom divergence")
                kv = exec_host_restore_event(kv, ev, mirror, bs)
                written.update(int(b) * bs + o
                               for b in ev["host_targets"]
                               for o in range(bs))
                fp(("host_restore", ev.get("rid")))
            table = list(ev["blocks"])
            for p in range(int(ev["hit"])):
                ps = table[p // bs] * bs + p % bs
                if ps not in written:
                    raise NotImplementedError(
                        f"prefix hit for rid={ev.get('rid')} reads pool "
                        f"slot {ps} (kv position {p}) with no in-log "
                        f"writer — its blocks were registered before "
                        f"recording began, so the fresh replay KV is zeros "
                        f"there and compare_replay would report phantom "
                        f"mismatches; start recording before any prefix "
                        f"blocks are stored")
        if kind == "precomputed_admit":
            # wire-plane disagg admission: the record carries the remote
            # prefill's KV values, so the replay applies the identical
            # scatter and those slots gain an in-log writer
            from .block_copy import scatter_blocks_from_host
            kv = scatter_blocks_from_host(kv, list(ev["targets"]),
                                          ev["values"], bs)
            written.update(int(b) * bs + o for b in ev["targets"]
                           for o in range(bs))
            fp(("precomputed_admit", ev.get("rid")))
        if kind == "kv_layer_stream":
            # streaming layer-wise disagg admission (llm/kv/stream.py):
            # one event per arrived layer, carrying the already-sliced
            # suffix values — replay applies the identical single-layer
            # scatter. Target blocks gain their in-log writer at the
            # LAST layer, when the live engine marked the slot ready.
            from .block_copy import scatter_layer_from_host
            kv = scatter_layer_from_host(kv, list(ev["targets"]),
                                         int(ev["layer"]), ev["values"],
                                         bs)
            if int(ev["layer"]) == int(ev["num_layers"]) - 1:
                written.update(int(b) * bs + o for b in ev["targets"]
                               for o in range(bs))
            fp(("kv_layer_stream", ev.get("rid"), int(ev["layer"])))
        if kind in ("prefill", "prefill_sp"):
            tok, kv = (exec_prefill_event(core, kv, ev)
                       if kind == "prefill"
                       else exec_sp_prefill_event(core, kv, ev))
            tok = jax.block_until_ready(tok)
            out["prefill"][ev["pf_seq"]] = int(tok)
            table = np.asarray(ev["table"])
            start = int(ev.get("start_pos", 0))   # sp path: always 0
            n = int(ev["true_len"])
            written.update(
                int(table[p // bs]) * bs + p % bs
                for p in range(start, start + n))
            fp(("prefill", ev["pf_seq"]))
        elif kind == "dispatch":
            K = int(ev["K"])
            chain = (disp_toks[ev["chained_from"]]
                     if ev["chained_from"] is not None else None)
            toks_k, kv = exec_dispatch_event(core, kv, ev, chain)
            toks_k = jax.block_until_ready(toks_k)
            disp_toks[ev["id"]] = toks_k
            out["dispatch"][ev["id"]] = np.asarray(toks_k).copy()
            tables = np.asarray(ev["tables"])
            positions = np.asarray(ev["positions"])
            for i, rid in enumerate(ev.get("reqs", [])):
                if rid is None:
                    continue
                p0 = int(positions[i])
                written.update(
                    int(tables[i, p // bs]) * bs + p % bs
                    for p in range(p0, p0 + K))
            fp(("dispatch", ev["id"]))
        elif kind == "ragged":
            # unified ragged dispatch (engine/ragged.py): every span's
            # rows wrote their positions' pool slots through the span's
            # slot table — prefill chunks, decode rows, and spec spans
            # alike; pipelined events chain off the previous ragged
            # dispatch's device tokens
            chain = (disp_toks[ev["chained_from"]]
                     if ev.get("chained_from") is not None else None)
            toks_r, kv = exec_ragged_event(core, kv, ev, chain)
            toks_r = jax.block_until_ready(toks_r)
            disp_toks[ev["id"]] = toks_r
            out["ragged"][ev["id"]] = np.asarray(toks_r).copy()
            tables = np.asarray(ev["tables"])
            positions = np.asarray(ev["positions"])
            starts = np.asarray(ev["starts"])
            counts = np.asarray(ev["counts"])
            for slot in range(counts.shape[0]):
                for r in range(int(counts[slot])):
                    p = int(positions[starts[slot] + r])
                    written.add(int(tables[slot, p // bs]) * bs + p % bs)
            fp(("ragged", ev["id"]))
        elif kind == "verify":
            # speculative verify (engine/spec/): every row — accepted,
            # rejected, pad — wrote its position's pool slot, so all of
            # them count as written (stale rows are rewritten by later
            # events before any read, exactly as in the live run)
            toks_v, kv = exec_verify_event(core, kv, ev)
            toks_v = jax.block_until_ready(toks_v)
            out["verify"][ev["id"]] = np.asarray(toks_v).copy()
            tables = np.asarray(ev["tables"])
            positions = np.asarray(ev["positions"])
            n_rows = np.asarray(ev["n_rows"])
            for i, rid in enumerate(ev.get("reqs", [])):
                if rid is None:
                    continue
                p0 = int(positions[i])
                written.update(
                    int(tables[i, p // bs]) * bs + p % bs
                    for p in range(p0, p0 + int(n_rows[i])))
            fp(("verify", ev["id"]))
    # expose the mirror tiers: follower-equivalence tests compare their
    # contents against the live engine's pools bit-for-bit
    out["host_mirror"] = mirror
    out["disk_mirror"] = disk_mirror
    return out


def compare_replay(events: List[dict], replayed: dict) -> List[str]:
    """Diff the live run's harvested tokens / first tokens against the
    synchronous replay. Returns human-readable mismatch lines."""
    diffs = []
    for ev in events:
        if ev["ev"] == "harvest":
            rep = replayed["dispatch"].get(ev["id"])
            if rep is None:
                continue
            live = np.asarray(ev["toks"])
            if not np.array_equal(live, rep):
                bad = np.argwhere(live != rep)
                diffs.append(
                    f"dispatch {ev['id']}: live != replay at (k,slot) "
                    f"{bad.tolist()} live={live.tolist()} "
                    f"replay={rep.tolist()}")
        elif ev["ev"] == "spec_harvest":
            rep = replayed.get("verify", {}).get(ev["id"])
            if rep is None:
                continue
            live = np.asarray(ev["toks"])
            if not np.array_equal(live, rep):
                bad = np.argwhere(live != rep)
                diffs.append(
                    f"verify {ev['id']}: live != replay at (slot,row) "
                    f"{bad.tolist()} live={live.tolist()} "
                    f"replay={rep.tolist()}")
        elif ev["ev"] == "ragged_harvest":
            rep = replayed.get("ragged", {}).get(ev["id"])
            if rep is None:
                continue
            live = np.asarray(ev["toks"])
            if not np.array_equal(live, rep):
                bad = np.argwhere(live != rep)
                diffs.append(
                    f"ragged {ev['id']}: live != replay at slots "
                    f"{bad.tolist()} live={live.tolist()} "
                    f"replay={rep.tolist()}")
        elif ev["ev"] == "first_token":
            rep = replayed["prefill"].get(ev["pf_seq"])
            if rep is not None and rep != ev["tok"]:
                diffs.append(
                    f"prefill {ev['pf_seq']} ({ev['rid']}): live tok "
                    f"{ev['tok']} != replay {rep}")
    return diffs


# --------------------------------------------------------------------------
# Pure log analysis: pool-slot ownership + stale-read detection
# --------------------------------------------------------------------------


@dataclasses.dataclass
class StaleRead:
    dispatch_id: int
    slot: int
    rid: str
    kv_pos: int
    pool_slot: int
    writer: Optional[str]

    def __str__(self) -> str:
        return (f"dispatch {self.dispatch_id} slot {self.slot} ({self.rid}) "
                f"reads kv position {self.kv_pos} from pool slot "
                f"{self.pool_slot}, last written by {self.writer!r}")


def check_log(events: List[dict], block_size: int) -> List[StaleRead]:
    """Simulate per-pool-slot last-writer over the recorded device order and
    report reads of slots whose last writer is a different request.

    Device order == log order for prefill/dispatch events (one stream).
    A prefill writes positions start_pos..start_pos+true_len-1 through its
    table (pads go to the trash block). A K-step dispatch, for each active
    slot, writes the input token's KV at positions p..p+K-1 and at step k
    reads every position <= p+k through its table. Writes to the trash
    block (id 0) are ignored.
    """
    last_writer: Dict[int, str] = {}
    stale: List[StaleRead] = []

    def write(pool_slot: int, rid: str) -> None:
        if pool_slot // block_size != 0:       # trash block: ignore
            last_writer[pool_slot] = rid

    for ev in events:
        if ev["ev"] == "hit_transfer":
            # prefix-cache hit (recorded before the admission's prefill):
            # the first `hit` positions are legitimately shared with their
            # original writer — transfer read rights so by-design sharing
            # isn't reported as a stale read
            table = list(ev["blocks"])
            for p in range(int(ev["hit"])):
                ps = table[p // block_size] * block_size + p % block_size
                write(ps, ev["rid"])
        if ev["ev"] == "precomputed_admit":
            # wire-plane disagg scatter writes whole target blocks
            for b in ev["targets"]:
                for o in range(block_size):
                    write(int(b) * block_size + o, ev["rid"])
        if ev["ev"] == "kv_layer_stream":
            # streaming disagg scatter: each layer event writes the same
            # whole target blocks (per-slot ownership is layer-agnostic)
            for b in ev["targets"]:
                for o in range(block_size):
                    write(int(b) * block_size + o, ev["rid"])
        if ev["ev"] in ("prefill", "prefill_sp"):
            table = np.asarray(ev["table"])
            rid = ev["rid"]
            start = int(ev.get("start_pos", 0))   # sp path: always 0
            n = int(ev["true_len"])
            # reads: the chunk attends to everything < start+n through the
            # same table (prefix continuation) — check those too
            for p in range(0, start + n):
                ps = int(table[p // block_size]) * block_size + p % block_size
                if p >= start:
                    write(ps, rid)
                else:
                    w = last_writer.get(ps)
                    if w is not None and w != rid:
                        stale.append(StaleRead(-1, -1, rid, p, ps, w))
        elif ev["ev"] == "ragged":
            # a ragged dispatch (engine/ragged.py) is counts[slot]
            # fused steps per slot from the pool's perspective: span
            # row r writes position pos0+r and reads everything <= it
            # through the slot's table — the verify event's ownership
            # semantics with per-slot row counts
            tables = np.asarray(ev["tables"])
            positions = np.asarray(ev["positions"])
            starts = np.asarray(ev["starts"])
            counts = np.asarray(ev["counts"])
            for i, rid in enumerate(ev["reqs"]):
                if rid is None or int(counts[i]) == 0:
                    continue
                for r in range(int(counts[i])):
                    p = int(positions[int(starts[i]) + r])
                    ps = (int(tables[i, p // block_size]) * block_size
                          + p % block_size)
                    write(ps, rid)
                    for q in range(0, p + 1):
                        qs = (int(tables[i, q // block_size])
                              * block_size + q % block_size)
                        w = last_writer.get(qs)
                        if w is not None and w != rid:
                            stale.append(StaleRead(
                                ev["id"], i, rid, q, qs, w))
        elif ev["ev"] in ("dispatch", "verify"):
            # a verify dispatch (engine/spec/) is K=n_rows[i] fused
            # steps per slot from the pool's perspective: row t writes
            # position p0+t and reads everything <= it through the same
            # table — identical ownership semantics to a K-step scan
            tables = np.asarray(ev["tables"])
            positions = np.asarray(ev["positions"])
            n_rows = (np.asarray(ev["n_rows"])
                      if ev["ev"] == "verify" else None)
            for i, rid in enumerate(ev["reqs"]):
                if rid is None:
                    continue
                K = int(ev["K"]) if n_rows is None else int(n_rows[i])
                p0 = int(positions[i])
                for k in range(K):
                    p = p0 + k
                    ps = (int(tables[i, p // block_size]) * block_size
                          + p % block_size)
                    write(ps, rid)
                    # reads: every position <= p via this table
                    for q in range(0, p + 1):
                        qs = (int(tables[i, q // block_size]) * block_size
                              + q % block_size)
                        w = last_writer.get(qs)
                        if w is not None and w != rid:
                            stale.append(StaleRead(
                                ev["id"], i, rid, q, qs, w))
    # dedupe (same slot re-read every later step)
    seen = set()
    uniq = []
    for s in stale:
        key = (s.rid, s.kv_pos, s.pool_slot, s.writer)
        if key not in seen:
            seen.add(key)
            uniq.append(s)
    return uniq


def check_inputs(events: List[dict]) -> List[str]:
    """Input-consistency invariants over the log, reconstructed purely from
    admit/harvest/dispatch events: chained dispatches must run K ahead on
    positions/steps and their request mapping must equal the chained-from
    dispatch's; host-fed dispatches must feed the request's last harvested
    token at its current position."""
    problems = []
    state: Dict[str, dict] = {}       # rid -> {pos, key_step, last_tok}
    disp: Dict[int, dict] = {}
    rag_disp: Dict[int, dict] = {}    # ragged events by id (harvest
    #                                   needs starts for row-sampled toks)
    for ev in events:
        if ev["ev"] == "admit":
            state[ev["rid"]] = {
                "pos": ev["pos"], "key_step": ev["key_step"],
                "last": None}         # last token may be deferred
        elif ev["ev"] == "first_token":
            if ev["rid"] in state:
                state[ev["rid"]]["last"] = ev["tok"]
        elif ev["ev"] == "dispatch":
            disp[ev["id"]] = ev
            positions = np.asarray(ev["positions"])
            steps = np.asarray(ev["steps"])
            tokens = np.asarray(ev["tokens"])
            mask = np.asarray(ev["mask"])
            if ev["chained_from"] is not None:
                src = disp.get(ev["chained_from"])
                for i, rid in enumerate(ev["reqs"]):
                    if mask[i] and (src is None or src["reqs"][i] != rid):
                        problems.append(
                            f"dispatch {ev['id']} slot {i} chained but "
                            f"chained-from mapping differs")
            for i, rid in enumerate(ev["reqs"]):
                if rid is None or rid not in state:
                    continue
                st = state[rid]
                ahead = int(ev["K"]) if mask[i] else 0
                if int(positions[i]) != st["pos"] + ahead:
                    problems.append(
                        f"dispatch {ev['id']} slot {i} ({rid}): position "
                        f"{int(positions[i])} != state {st['pos']}+{ahead}")
                if int(steps[i]) != st["key_step"] + ahead:
                    problems.append(
                        f"dispatch {ev['id']} slot {i} ({rid}): key step "
                        f"{int(steps[i])} != state {st['key_step']}+{ahead}")
                pm = np.asarray(ev["planned_mask"]) if "planned_mask" in ev \
                    else None
                planned_first = bool(pm is not None and pm[0, i])
                if (not mask[i] and not planned_first
                        and st["last"] is not None
                        and int(tokens[i]) != st["last"]):
                    problems.append(
                        f"dispatch {ev['id']} slot {i} ({rid}): host token "
                        f"{int(tokens[i])} != last harvested {st['last']}")
        elif ev["ev"] == "verify":
            positions = np.asarray(ev["positions"])
            steps = np.asarray(ev["steps"])
            tokens = np.asarray(ev["tokens"])
            for i, rid in enumerate(ev["reqs"]):
                if rid is None or rid not in state:
                    continue
                st = state[rid]
                if int(positions[i]) != st["pos"]:
                    problems.append(
                        f"verify {ev['id']} slot {i} ({rid}): position "
                        f"{int(positions[i])} != state {st['pos']}")
                if int(steps[i]) != st["key_step"]:
                    problems.append(
                        f"verify {ev['id']} slot {i} ({rid}): key step "
                        f"{int(steps[i])} != state {st['key_step']}")
                if (st["last"] is not None
                        and int(tokens[i, 0]) != st["last"]):
                    problems.append(
                        f"verify {ev['id']} slot {i} ({rid}): row-0 "
                        f"token {int(tokens[i, 0])} != last harvested "
                        f"{st['last']}")
        elif ev["ev"] == "ragged":
            rag_disp[ev["id"]] = ev
            positions = np.asarray(ev["positions"])
            starts = np.asarray(ev["starts"])
            counts = np.asarray(ev["counts"])
            steps = np.asarray(ev["steps"])
            # [capacity] row steps = the spec-enabled row-sampled
            # variant; [B+1] slot steps = the slot-sampled one
            row_sampled = steps.shape[0] == positions.shape[0]
            mask = (np.asarray(ev["mask"])
                    if ev.get("chained_from") is not None else None)
            for i, rid in enumerate(ev["reqs"]):
                if rid is None or rid not in state \
                        or int(counts[i]) == 0:
                    continue
                st = state[rid]
                # pipelined ragged: chained spans run one un-harvested
                # token ahead of host state (the dispatch-event mask
                # convention; chained spans are single decode rows)
                ahead = int(mask is not None
                            and mask[int(starts[i])])
                p0 = int(positions[int(starts[i])])
                if p0 != st["pos"] + ahead:
                    problems.append(
                        f"ragged {ev['id']} slot {i} ({rid}): first-row "
                        f"position {p0} != state {st['pos']}+{ahead}")
                if row_sampled:
                    # row r keys at key_step + r — check the first row
                    if int(steps[int(starts[i])]) \
                            != st["key_step"] + ahead:
                        problems.append(
                            f"ragged {ev['id']} slot {i} ({rid}): "
                            f"first-row key step "
                            f"{int(steps[int(starts[i])])} != state "
                            f"{st['key_step']}+{ahead}")
                elif int(steps[i]) != (st["key_step"] + ahead
                                       + int(counts[i]) - 1):
                    # the span's LAST row samples at key_step + len - 1
                    # (the lane skew convention)
                    problems.append(
                        f"ragged {ev['id']} slot {i} ({rid}): sample "
                        f"key step {int(steps[i])} != state "
                        f"{st['key_step']}+{ahead}+{int(counts[i]) - 1}")
        elif ev["ev"] == "ragged_harvest":
            toks = np.asarray(ev["toks"])
            src = rag_disp.get(ev["id"])
            for slot, rid, n, emitted in ev["applied"]:
                if rid in state:
                    st = state[rid]
                    st["pos"] += n
                    st["key_step"] += n
                    if emitted and n > 0:
                        if (src is not None and toks.shape[0]
                                == np.asarray(src["positions"]).shape[0]):
                            # row-sampled: the last APPLIED row's token
                            # (spec spans may rewind before the span end)
                            start = int(np.asarray(src["starts"])[slot])
                            st["last"] = int(toks[start + n - 1])
                        else:
                            st["last"] = int(toks[slot])
        elif ev["ev"] == "harvest":
            toks = np.asarray(ev["toks"])
            for slot, rid, n in ev["applied"]:
                if rid in state:
                    st = state[rid]
                    st["pos"] += n
                    st["key_step"] += n
                    if n > 0:
                        st["last"] = int(toks[n - 1, slot])
        elif ev["ev"] == "spec_harvest":
            toks = np.asarray(ev["toks"])      # [B, Tv]
            for slot, rid, n, _accepted in ev["applied"]:
                if rid in state:
                    st = state[rid]
                    st["pos"] += n
                    st["key_step"] += n
                    if n > 0:
                        st["last"] = int(toks[slot, n - 1])
        elif ev["ev"] == "preempt":
            state.pop(ev["rid"], None)
    return problems
