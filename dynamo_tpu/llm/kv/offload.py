"""Host-memory KV tier: offloaded prefix blocks in TPU-VM DRAM.

Reference: the "KV cache offload to system memory" pillar — kv/storage.rs
``StorageType::{Device,Pinned,System}`` + CudaPinnedMemory staging +
``KvStorageManager::prepare_prefill_offload`` (kv/manager.rs:21-168), which
buys +40% TTFT on multi-turn workloads (docs/architecture.md:91). TPU-native
redesign: the host tier is one preallocated numpy arena (TPU-VM DRAM is the
pinned tier — no cudaHostAlloc analog needed), blocks keyed by chained
sequence hash with LRU eviction, and device↔host movement is the XLA
gather/scatter + single-transfer path in engine/block_copy.py.

Two pieces:
- :class:`HostKvPool` — the arena: slot allocation, hash→slot map, LRU.
- :class:`KvOffloadEngine` — async pump: drains an offload queue (device →
  host) off the engine's critical path, and performs synchronous onboarding
  (host → device) during admission, where the data is needed *now*.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger("dynamo_tpu.kv.offload")

__all__ = ["HostKvPool", "KvOffloadEngine", "OffloadJob", "make_host_pool"]


class HostKvPool:
    """Preallocated host arena of KV blocks keyed by sequence hash.

    Shapes: per block the head-major WIRE layout [L, H_kv, bs, D] for k and
    v — i.e. engine/block_copy.py's ``fetch_wire``/``to_wire_format`` output
    sliced per block (the device pool itself is block-major; convert before
    storing).
    """

    def __init__(self, capacity_blocks: int, num_layers: int,
                 num_kv_heads: int, block_size: int, head_dim: int,
                 dtype=np.float32, opaque_rows: bool = False):
        self.capacity = capacity_blocks
        self.num_kv_heads = num_kv_heads
        # the arena materializes on FIRST store: on a multi-controller
        # mesh each rank's pool holds only its local head shard, whose
        # count is known from the first fetched values, not the config
        # (engine/block_copy.py fetch_wire)
        self._shape_tail = (num_layers, num_kv_heads, block_size, head_dim)
        self._dtype = np.dtype(dtype)
        # opaque_rows (int8 pools): blocks are whole pool rows — values
        # plus in-row scale lanes — shipped as ONE wire "head" whose
        # width is the row width (make_host_pool). A multi-controller
        # rank's shard is then a clean fraction of that width, the same
        # laziness the head count has for full-precision pools.
        self.opaque_rows = opaque_rows
        self._arena: Optional[dict] = None
        self._free: List[int] = list(range(capacity_blocks - 1, -1, -1))
        self._by_hash: Dict[int, int] = {}       # seq_hash → slot
        self._lru: Dict[int, None] = {}          # EVICTABLE hashes, LRU order
        # hashes parked out of the eviction queue because their slot was
        # pinned when an eviction considered them; unpin re-queues them.
        # Keeping them out of _lru makes victim selection O(1) amortized
        # (each park/unpark pairs with one pin cycle) instead of a full
        # scan past every pinned entry per eviction.
        self._lru_parked: Dict[int, None] = {}
        self._hash_by_slot: Dict[int, int] = {}
        self._pins: Dict[int, int] = {}          # slot → pin count
        # per-hash (tokens_hash, parent_hash) — carried so a disk-tier
        # spill of an evicted block can re-announce it to the router's
        # radix index (diskstore.py; publisher tier tags)
        self._meta: Dict[int, tuple] = {}
        # write-behind spill hook: called with (evicted_hash, tokens_hash,
        # parent_hash, values_copy) BEFORE the arena row is overwritten —
        # the disk (G3) tier's feed. values_copy is a fresh per-block
        # dict the callee owns outright.
        self.on_evict: Optional[Callable] = None
        # multi-tenant quota enforcement (llm/tenancy.py): when a
        # TenantBlockLedger is attached, stores note each hash's tenant
        # in the "host" tier (owner remembered from the device tier's
        # registration) and victim selection prefers an OVER-QUOTA
        # tenant's blocks (bounded scan) before the plain LRU front.
        # None keeps eviction byte-identical to the untenanted pool.
        self.tenancy = None
        self.tenant_evictions = 0
        # stats
        self.stored_blocks_total = 0
        self.evicted_blocks_total = 0
        self.match_queries = 0
        self.match_hits = 0
        self.evict_scan_steps = 0   # pinned-candidate requeues (O(1) test)

    def __len__(self) -> int:
        return len(self._by_hash)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def _touch(self, seq_hash: int) -> None:
        """Freshen a resident hash's LRU position. Parked hashes (pinned
        at some eviction check) stay parked — unpin re-queues them."""
        if seq_hash in self._lru_parked:
            return
        self._lru.pop(seq_hash, None)
        self._lru[seq_hash] = None

    def _place(self, seq_hash: int, slot: int) -> None:
        self._by_hash[seq_hash] = slot
        self._hash_by_slot[slot] = seq_hash
        self._lru_parked.pop(seq_hash, None)
        self._lru[seq_hash] = None

    def _slot_for(self, seq_hash: int):
        """(slot, evicted_hash) — existing slot, else a fresh/evicted one.
        (None, None) if nothing is placeable (capacity 0 / all pinned).

        Victim selection is O(1) amortized: candidates pop from the
        evictable LRU front; a PINNED candidate is PARKED out of the
        queue entirely (re-queued by unpin) instead of being skipped in
        place — the old O(n) scan walked past every pinned entry on
        every eviction, O(n·m) for m stores against a mostly-pinned
        pool. Each park/unpark pairs with one pin cycle, so the
        amortized per-eviction cost is constant."""
        slot = self._by_hash.get(seq_hash)
        if slot is not None:
            self._touch(seq_hash)
            return slot, None
        evicted = None
        if not self._free:
            victim = None
            if self.tenancy is not None:
                # quota preference: the first unpinned over-quota
                # tenant's block within a bounded LRU-front scan evicts
                # before anyone else's (llm/tenancy.py)
                for i, h in enumerate(self._lru):
                    if i >= 64:
                        break
                    if self._pins.get(self._by_hash[h]):
                        continue
                    if self.tenancy.is_over_quota_hash(h, "host"):
                        victim = h
                        self.tenant_evictions += 1
                        break
            while victim is None and self._lru:
                h = next(iter(self._lru))
                if self._pins.get(self._by_hash[h]):
                    self._lru.pop(h)
                    self._lru_parked[h] = None   # park pinned candidate
                    self.evict_scan_steps += 1
                    continue
                victim = h
                break
            if victim is None:       # empty, or everything pinned mid-fetch
                return None, None
            self._lru.pop(victim)
            vslot = self._by_hash.pop(victim)
            self._hash_by_slot.pop(vslot, None)
            self.evicted_blocks_total += 1
            if self.tenancy is not None:
                self.tenancy.forget(victim, "host")
            if self.on_evict is not None and self._arena is not None:
                th, ph = self._meta.get(victim, (None, None))
                try:
                    self.on_evict(victim, th, ph,
                                  {key: arena[vslot].copy()
                                   for key, arena in self._arena.items()})
                except Exception:  # noqa: BLE001 — spill is best-effort
                    logger.exception("host-tier evict hook failed")
            self._meta.pop(victim, None)
            self._free.append(vslot)
            evicted = victim
        slot = self._free.pop()
        self._place(seq_hash, slot)
        return slot, evicted

    def store(self, seq_hashes: Sequence[int], values: dict,
              tokens_hashes: Optional[Sequence[int]] = None,
              parent_hashes: Optional[Sequence[Optional[int]]] = None
              ) -> list:
        """Write stacked blocks (e.g. {"k": [L, H, n, bs, D], "v": …};
        MLA latent pools ship one "kv" entry) under their hashes — the
        arena mirrors whatever key set the device pool has. Returns the
        literal placement decisions ``[(hash, slot, evicted_hash |
        None)]`` — len(result) blocks were stored (capacity may stop
        early). Multihost follower mirrors replay these decisions
        verbatim instead of re-running the LRU policy (apply_store).
        ``tokens_hashes``/``parent_hashes`` (aligned with seq_hashes)
        ride along so a later disk-tier spill can re-announce the block
        to the router's radix index with its chain intact."""
        decisions = []
        for i, h in enumerate(seq_hashes):
            slot, evicted = self._slot_for(h)
            if slot is None:
                break
            if tokens_hashes is not None:
                self._meta[h] = (tokens_hashes[i],
                                 parent_hashes[i] if parent_hashes
                                 is not None else None)
            self._ensure_arena(values)
            for key, arena in self._arena.items():
                arena[slot] = values[key][:, :, i]
            self.stored_blocks_total += 1
            if self.tenancy is not None:
                # owner carried over from the device-tier registration
                # (ledger hash→tenant memory, llm/tenancy.py)
                self.tenancy.note(h, None, "host")
            decisions.append((h, slot, evicted))
        return decisions

    def _ensure_arena(self, values: dict) -> None:
        if self._arena is None:
            first = next(iter(values.values()))
            # per-block shape: stacked values drop the n axis (store),
            # per-block dicts arrive without it (apply_store)
            blk = (first.shape[:2] + first.shape[3:]
                   if first.ndim == 5 else first.shape)
            L, _h, bs, d = self._shape_tail
            got_d = blk[3]
            d_ok = (d % got_d == 0 if self.opaque_rows else got_d == d)
            if (blk[0], blk[2]) != (L, bs) or not d_ok:
                raise ValueError(
                    f"host-tier block shape {tuple(blk)} does not "
                    f"match config {self._shape_tail} (heads — and for "
                    f"opaque int8 rows the row width — may differ per "
                    f"rank; layers/block_size may not)")
            shape = (self.capacity,) + tuple(blk)
            self._arena = {key: np.zeros(shape, self._dtype)
                           for key in values}

    def apply_store(self, seq_hash: int, slot: int,
                    evicted_hash: Optional[int],
                    block_values: dict) -> None:
        """Apply one of the leader's literal store decisions to a mirror
        pool (multihost follower): same hash→slot placement, same
        eviction, arena bytes from the FOLLOWER's own device KV (which is
        bit-identical to the leader's by the dispatch-stream induction).
        ``block_values``: key → ONE block [L, H, bs, D]."""
        if evicted_hash is not None:
            old = self._by_hash.pop(evicted_hash, None)
            self._lru.pop(evicted_hash, None)
            self._lru_parked.pop(evicted_hash, None)
            self._meta.pop(evicted_hash, None)
            if old is not None:
                self._hash_by_slot.pop(old, None)
                if old != slot:
                    self._free.append(old)
            self.evicted_blocks_total += 1
        if self._by_hash.get(seq_hash) != slot:
            try:
                self._free.remove(slot)
            except ValueError:
                pass
        self._place(seq_hash, slot)
        self._ensure_arena(block_values)
        for key, arena in self._arena.items():
            arena[slot] = block_values[key]
        self.stored_blocks_total += 1

    def match_prefix(self, seq_hashes: Sequence[int]) -> List[int]:
        """Longest leading run of hashes present. Returns their slots and
        freshens LRU order."""
        out: List[int] = []
        for h in seq_hashes:
            self.match_queries += 1
            slot = self._by_hash.get(h)
            if slot is None:
                break
            self.match_hits += 1
            self._touch(h)
            out.append(slot)
        return out

    def fetch(self, slots: Sequence[int]) -> dict:
        """Stacked values for ``slots``, keyed like the device pool:
        {key: [L, H, n, bs, D]}."""
        idx = np.asarray(slots, dtype=np.int64)
        return {key: np.ascontiguousarray(
                    arena[idx].transpose(1, 2, 0, 3, 4))
                for key, arena in self._arena.items()}

    def pin(self, slots: Sequence[int]) -> None:
        """Exclude ``slots`` from LRU eviction while an async onboarding
        fetch reads them off the loop thread (the offload pump's stores
        could otherwise evict+reuse an arena row mid-copy)."""
        for s in slots:
            self._pins[s] = self._pins.get(s, 0) + 1

    def unpin(self, slots: Sequence[int]) -> None:
        for s in slots:
            n = self._pins.get(s, 0) - 1
            if n <= 0:
                self._pins.pop(s, None)
                # re-queue a candidate parked while this slot was pinned
                # (to the LRU back — the documented requeue semantics)
                h = self._hash_by_slot.get(s)
                if h is not None and h in self._lru_parked:
                    self._lru_parked.pop(h)
                    self._lru[h] = None
            else:
                self._pins[s] = n

    def contains(self, seq_hash: int) -> bool:
        return seq_hash in self._by_hash

    def hit_rate(self) -> float:
        return self.match_hits / max(self.match_queries, 1)

    def meta_for(self, seq_hash: int) -> tuple:
        """(tokens_hash, parent_hash) recorded at store time (None, None
        when the storer carried no chain info)."""
        return self._meta.get(seq_hash, (None, None))

    def resident_entries(self) -> List[tuple]:
        """Every resident block as (seq_hash, tokens_hash, parent_hash,
        slot) — the flush-to-disk inventory (EngineCore
        flush_host_to_disk / llmctl kv flush)."""
        return [(h, *self._meta.get(h, (None, None)), slot)
                for h, slot in self._by_hash.items()]

    def row_copy(self, slot: int) -> dict:
        """Fresh per-block copy of one arena row ({key: [L, H, bs, D]})
        — what a spill job owns."""
        return {key: arena[slot].copy()
                for key, arena in self._arena.items()}


def make_host_pool(capacity_blocks: int, model_cfg, block_size: int,
                   kv_quantization: str, pool_row_lanes: int,
                   param_dtype) -> HostKvPool:
    """The one way to build a host pool matched to an engine's device
    pool (core.py and the offline replayer share it so they can't
    drift). Full-precision llama pools use the head-major wire layout
    [L, KVH, bs, Dh]; int8 pools AND MLA latent pools ship whole rows
    (``pool_row_lanes`` wide — values + in-row scale lanes for int8,
    rank+rope lanes for MLA) as one opaque wire "head" — a bit-exact
    round trip with no requantization error."""
    if kv_quantization != "none":
        return HostKvPool(capacity_blocks, model_cfg.num_layers, 1,
                          block_size, pool_row_lanes, dtype=np.int8,
                          opaque_rows=True)
    if model_cfg.kv_lora_rank > 0:
        return HostKvPool(capacity_blocks, model_cfg.num_layers, 1,
                          block_size, pool_row_lanes, dtype=param_dtype,
                          opaque_rows=True)
    return HostKvPool(capacity_blocks, model_cfg.num_layers,
                      model_cfg.num_kv_heads, block_size,
                      model_cfg.head_dim, dtype=param_dtype)


class KvStoreEmitError(RuntimeError):
    """The on_store (dispatch-stream) emission failed AFTER the host pool
    committed a store: multihost follower mirrors can no longer be proven
    identical. Never swallowed by the pump's best-effort handler — the
    pump dies and the broken stream fails every later recorded admission
    (engine/multihost.py DispatchStreamLeader.rec)."""


@dataclasses.dataclass
class OffloadJob:
    """Device blocks to write back to host. The enqueuer pre-holds
    ``block_ids`` in the device pool (an extra refcount) so they cannot be
    reused mid-copy; :class:`KvOffloadEngine` releases that hold via its
    ``release_holds`` callback once the copy lands (or fails)."""

    block_ids: List[int]
    seq_hashes: List[int]
    # local (unchained) hashes aligned with seq_hashes; optional — when
    # present the host pool records them so disk-tier spills can
    # re-announce evicted blocks with their chain intact (diskstore.py).
    # Jobs always start at a sequence's block 0 (core._release_slot), so
    # parent_hashes derive as [None, seq_hashes[0], seq_hashes[1], ...].
    tokens_hashes: Optional[List[int]] = None


class KvOffloadEngine:
    """Asynchronous device→host write-back pump.

    The engine enqueues jobs when sequences finish (their full blocks hold
    valid KV); the pump batches jobs, gathers once on device, transfers once,
    and releases the device holds. Mirrors the role of the reference's
    CopyStream + offload path (kv/layer.rs CopyStream, manager.rs
    prepare_prefill_offload) with XLA DMA instead of CUDA streams.
    """

    def __init__(self, host_pool: HostKvPool, block_size: int,
                 get_kv: Callable[[], dict],
                 release_holds: Optional[Callable[[List[int]], None]] = None,
                 max_batch_blocks: int = 64,
                 simulated_gbps: Optional[float] = None,
                 on_store: Optional[Callable[[list], None]] = None,
                 max_queue_jobs: int = 512):
        self.host_pool = host_pool
        self.block_size = block_size
        self.get_kv = get_kv
        self.release_holds = release_holds
        # multihost: called with [(hash, slot, evicted_hash, device_block)]
        # after each committed batch, BEFORE the device holds are released
        # — so the dispatch stream orders the event ahead of any program
        # that could overwrite a reused block (engine/multihost.py)
        self.on_store = on_store
        self.max_batch_blocks = max_batch_blocks
        # injectable d2h link model: when set, each write-back batch is
        # paced to `bytes / simulated_gbps` wall time, so an e2e run on a
        # FAST local link (CPU tests) exercises the tier under a
        # TPU-VM-like link
        self.simulated_gbps = simulated_gbps
        # bounded write-back queue: saturation DROPS the job (with its
        # device holds released and a counter bumped) instead of letting
        # an unbounded backlog pin device blocks — losing a cache
        # write-back under pressure is strictly better than KV-pool
        # starvation. Previously the drop was impossible but the queue
        # was unbounded and silent.
        self.max_queue_jobs = max_queue_jobs
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self.offloaded_blocks_total = 0
        self.dropped_jobs_total = 0
        self.simulated_wait_s = 0.0

    def enqueue(self, job: OffloadJob) -> None:
        if self._queue.qsize() >= self.max_queue_jobs:
            self.dropped_jobs_total += 1
            if self.release_holds is not None:
                self.release_holds(job.block_ids)
            return
        self._queue.put_nowait(job)
        self._ensure_task()

    def _ensure_task(self) -> None:
        if self._task is None or self._task.done():
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                return
            self._task = loop.create_task(self._run(), name="kv-offload")

    async def _run(self) -> None:
        while True:
            job: OffloadJob = await self._queue.get()
            jobs = [job]
            total = len(job.block_ids)
            while total < self.max_batch_blocks and not self._queue.empty():
                j = self._queue.get_nowait()
                jobs.append(j)
                total += len(j.block_ids)
            try:
                await self._process(jobs)
            except KvStoreEmitError:
                logger.critical(
                    "kv_store stream emission failed after the pool "
                    "committed — multihost mirrors are unprovable; "
                    "killing the pump (the broken stream stops serving)")
                raise
            except Exception:  # noqa: BLE001 — write-back is best-effort
                logger.exception("kv offload batch failed")
            finally:
                if self.release_holds is not None:
                    for j in jobs:
                        self.release_holds(j.block_ids)
                for _ in jobs:
                    self._queue.task_done()
            await asyncio.sleep(0)  # yield to the engine loop

    async def _process(self, jobs: List[OffloadJob]) -> None:
        from ...engine.block_copy import fetch_wire, gather_blocks_dispatch

        block_ids = [b for j in jobs for b in j.block_ids]
        seq_hashes = [h for j in jobs for h in j.seq_hashes]
        # chain meta per block: jobs start at block 0 of their sequence,
        # so parents are the preceding seq hash within the job
        tok_hashes = [th for j in jobs
                      for th in (j.tokens_hashes
                                 or [None] * len(j.seq_hashes))]
        parents = [p for j in jobs
                   for p in ([None] + list(j.seq_hashes[:-1]))]
        # skip blocks already resident on host (multi-turn re-offload)
        keep = [i for i, h in enumerate(seq_hashes)
                if not self.host_pool.contains(h)]
        if not keep:
            return
        ids = [block_ids[i] for i in keep]
        hashes = [seq_hashes[i] for i in keep]
        toks = [tok_hashes[i] for i in keep]
        pars = [parents[i] for i in keep]
        # dispatch the on-device gather HERE, on the loop thread: it orders
        # correctly against the engine's donated decode steps and returns a
        # fresh (never-donated) buffer
        n = len(ids)
        stacked = gather_blocks_dispatch(self.get_kv(), ids, self.block_size)
        # ...then do the blocking device→DRAM transfer off-thread so decode
        # keeps stepping during the DMA
        t0 = time.monotonic()
        values = await asyncio.to_thread(
            fetch_wire, stacked, n, self.host_pool.num_kv_heads)
        if self.simulated_gbps:
            nbytes = sum(v.nbytes for v in values.values()) \
                if isinstance(values, dict) else values.nbytes
            target = nbytes / (self.simulated_gbps * 1e9)
            wait = target - (time.monotonic() - t0)
            if wait > 0:
                self.simulated_wait_s += wait
                await asyncio.sleep(wait)
        decisions = self.host_pool.store(hashes, values,
                                         tokens_hashes=toks,
                                         parent_hashes=pars)
        self.offloaded_blocks_total += len(decisions)
        if self.on_store is not None and decisions:
            try:
                self.on_store([(h, slot, evicted, ids[i])
                               for i, (h, slot, evicted)
                               in enumerate(decisions)])
            except Exception as e:  # noqa: BLE001
                raise KvStoreEmitError(str(e)) from e

    async def drain(self) -> None:
        self._ensure_task()
        await self._queue.join()

    async def stop(self) -> None:
        """Flush pending write-backs, then cancel the pump."""
        try:
            await asyncio.wait_for(self.drain(), timeout=10)
        except asyncio.TimeoutError:
            logger.warning("kv offload drain timed out; dropping queue")
            while not self._queue.empty():
                job = self._queue.get_nowait()
                if self.release_holds is not None:
                    self.release_holds(job.block_ids)
                self._queue.task_done()
        if self._task is not None:
            self._task.cancel()
            self._task = None
