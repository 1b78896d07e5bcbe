"""KV indexer: the router's global radix/prefix index of which worker holds
which KV blocks.

Reference: lib/llm/src/kv_router/indexer.rs:139-790 (`RadixTree`,
`KvIndexer::new` single-writer event task, `compute_block_hash_for_seq`,
`KvIndexerSharded`). The tree itself is native C++ (csrc/kv_radix_index.cpp)
behind ctypes, with a pure-Python twin chosen by name
(``prefer_native=False``); both sit behind the same
single-writer asyncio task so event application is serialized exactly like
the reference's mpsc actor.
"""

from __future__ import annotations

import asyncio
import ctypes
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

from ...utils import native
from ..kv.blocks import compute_block_hashes
from .protocols import RouterEvent

__all__ = ["OverlapScores", "KvIndexer", "RadixIndexNative",
           "RadixIndexPython", "make_radix_index"]


class OverlapScores:
    """worker_id → number of consecutive leading request blocks that worker
    already holds (reference `OverlapScores`). With frequency tracking on
    (an ``expiration_s`` on the index), ``frequencies`` lists the matched
    blocks' recent-use counts inside the expiration window, outermost
    first — the scheduler's hotness signal (reference add_frequency,
    indexer.rs:429-436)."""

    def __init__(self, scores: Optional[Dict[int, int]] = None,
                 frequencies: Optional[List[int]] = None,
                 weighted: Optional[Dict[int, float]] = None,
                 remote_blocks: Optional[Dict[int, int]] = None):
        self.scores: Dict[int, int] = scores or {}
        self.frequencies: List[int] = frequencies or []
        # tier-discounted effective overlap per worker (scoring.py
        # TIER_WEIGHTS): equals ``scores`` when every matched block is
        # device-resident. The scheduler consumes this, so a worker whose
        # matched prefix lives on disk wins ties only against recompute,
        # not against an HBM-resident copy elsewhere.
        self.weighted: Dict[int, float] = (
            dict(weighted) if weighted is not None else dict(self.scores))
        # worker → how many of its matched blocks carry tier "remote"
        # (a fabric fetch away, not local). The scheduler's NetKV
        # scoring keeps their credit only when that worker's modeled
        # transfer beats its modeled recompute (scoring.py
        # network_adjusted_overlap).
        self.remote_blocks: Dict[int, int] = dict(remote_blocks or {})

    @property
    def fleet_depth(self) -> int:
        """Deepest overlap any worker holds — the fabric makes those
        blocks fetchable by every attached candidate."""
        return max(self.scores.values(), default=0)

    def best(self) -> Optional[int]:
        if not self.scores:
            return None
        return max(self.scores, key=lambda w: self.scores[w])

    def __repr__(self) -> str:
        if self.frequencies:
            return f"OverlapScores({self.scores}, freq={self.frequencies})"
        return f"OverlapScores({self.scores})"


# ---------------------------------------------------------------------------
# Native tree (C++ via ctypes)
# ---------------------------------------------------------------------------


class RadixIndexNative:
    MAX_WORKERS = 4096
    MAX_DEPTH = 65536      # frequency out-buffer bound (blocks per request)

    def __init__(self, expiration_s: Optional[float] = None):
        lib = native.load("dynkv", ["kv_radix_index.cpp"])
        self._lib = lib
        # normalize: <=0 means off, matching the C++ gate (expiration > 0)
        if expiration_s is not None and expiration_s <= 0:
            expiration_s = None
        self.expiration_s = expiration_s
        lib.dyn_kv_index_new.restype = ctypes.c_void_p
        lib.dyn_kv_index_free.argtypes = [ctypes.c_void_p]
        lib.dyn_kv_index_apply_stored.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t]
        lib.dyn_kv_index_apply_removed.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t]
        lib.dyn_kv_index_remove_worker.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int64]
        lib.dyn_kv_index_find_matches.restype = ctypes.c_size_t
        lib.dyn_kv_index_find_matches.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t, ctypes.c_int]
        lib.dyn_kv_index_node_count.restype = ctypes.c_size_t
        lib.dyn_kv_index_node_count.argtypes = [ctypes.c_void_p]
        lib.dyn_kv_index_event_count.restype = ctypes.c_uint64
        lib.dyn_kv_index_event_count.argtypes = [ctypes.c_void_p]
        lib.dyn_kv_index_set_expiration.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_double]
        lib.dyn_kv_index_find_matches2.restype = ctypes.c_size_t
        lib.dyn_kv_index_find_matches2.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_size_t, ctypes.c_int, ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_size_t)]
        self._ptr = lib.dyn_kv_index_new()
        if expiration_s is not None:
            lib.dyn_kv_index_set_expiration(self._ptr, float(expiration_s))
        # reusable output buffers: find_matches is the routing hot path and
        # the index is single-reader by design, so one pair suffices
        self._out_w = (ctypes.c_int64 * self.MAX_WORKERS)()
        self._out_c = (ctypes.c_uint32 * self.MAX_WORKERS)()
        self._out_f = (ctypes.c_uint32 * self.MAX_DEPTH)()
        self._out_nf = ctypes.c_size_t(0)

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.dyn_kv_index_free(ptr)
            self._ptr = None

    @staticmethod
    def _arr(hashes: Sequence[int]):
        return (ctypes.c_uint64 * len(hashes))(*[h & 0xFFFFFFFFFFFFFFFF
                                                 for h in hashes])

    def apply_stored(self, worker_id: int, parent_hash: Optional[int],
                     block_hashes: Sequence[int]) -> None:
        self._lib.dyn_kv_index_apply_stored(
            self._ptr, worker_id, (parent_hash or 0) & 0xFFFFFFFFFFFFFFFF,
            self._arr(block_hashes), len(block_hashes))

    def apply_removed(self, worker_id: int,
                      block_hashes: Sequence[int]) -> None:
        self._lib.dyn_kv_index_apply_removed(
            self._ptr, worker_id, self._arr(block_hashes), len(block_hashes))

    def remove_worker(self, worker_id: int) -> None:
        self._lib.dyn_kv_index_remove_worker(self._ptr, worker_id)

    def find_matches(self, block_hashes: Sequence[int],
                     now: Optional[float] = None) -> OverlapScores:
        out_w, out_c = self._out_w, self._out_c
        if self.expiration_s is None:
            n = self._lib.dyn_kv_index_find_matches(
                self._ptr, self._arr(block_hashes), len(block_hashes),
                out_w, out_c, self.MAX_WORKERS, 1)
            return OverlapScores(
                {int(out_w[i]): int(out_c[i]) for i in range(n)})
        n = self._lib.dyn_kv_index_find_matches2(
            self._ptr, self._arr(block_hashes),
            min(len(block_hashes), self.MAX_DEPTH),
            out_w, out_c, self.MAX_WORKERS, 1,
            float(time.monotonic() if now is None else now),
            self._out_f, ctypes.byref(self._out_nf))
        freqs = [int(self._out_f[i]) for i in range(self._out_nf.value)]
        return OverlapScores(
            {int(out_w[i]): int(out_c[i]) for i in range(n)}, freqs)

    def node_count(self) -> int:
        return int(self._lib.dyn_kv_index_node_count(self._ptr))

    def event_count(self) -> int:
        """Events applied (stored/removed/remove_worker) since creation —
        the staleness/liveness stat the router status surface reads."""
        return int(self._lib.dyn_kv_index_event_count(self._ptr))


# ---------------------------------------------------------------------------
# Python fallback (same semantics)
# ---------------------------------------------------------------------------


class _PyNode:
    __slots__ = ("hash", "parent", "children", "workers", "recent_uses")

    def __init__(self, h: int = 0, parent=None):
        self.hash = h
        self.parent = parent
        self.children: Dict[int, "_PyNode"] = {}
        self.workers: set = set()
        self.recent_uses: deque = deque()   # timestamps inside the window


class RadixIndexPython:
    def __init__(self, expiration_s: Optional[float] = None):
        self._root = _PyNode()
        self._by_hash: Dict[int, _PyNode] = {}
        self._worker_nodes: Dict[int, set] = {}
        # normalize: <=0 means off, matching the native tree's gate
        if expiration_s is not None and expiration_s <= 0:
            expiration_s = None
        self.expiration_s = expiration_s
        self._event_count = 0    # mirrors RadixIndex::event_count

    def _find(self, h: Optional[int]) -> Optional[_PyNode]:
        if not h:
            return self._root
        return self._by_hash.get(h)

    def apply_stored(self, worker_id, parent_hash, block_hashes) -> None:
        self._event_count += 1
        node = self._find(parent_hash) or self._root
        for h in block_hashes:
            child = node.children.get(h)
            if child is None:
                child = _PyNode(h, node)
                node.children[h] = child
                self._by_hash[h] = child
            child.workers.add(worker_id)
            self._worker_nodes.setdefault(worker_id, set()).add(child)
            node = child

    def _detach_if_empty(self, node: _PyNode) -> None:
        while (node is not None and node is not self._root
               and not node.workers and not node.children):
            parent = node.parent
            if self._by_hash.get(node.hash) is node:  # only the map's holder
                del self._by_hash[node.hash]
            parent.children.pop(node.hash, None)
            node = parent

    def apply_removed(self, worker_id, block_hashes) -> None:
        self._event_count += 1
        for h in block_hashes:
            node = self._by_hash.get(h)
            if node is None:
                continue
            node.workers.discard(worker_id)
            nodes = self._worker_nodes.get(worker_id)
            if nodes:
                nodes.discard(node)
            self._detach_if_empty(node)

    def remove_worker(self, worker_id) -> None:
        # mirror the native tree exactly: snapshot hash values, then detach
        # via the flat map's current holder (kv_radix_index.cpp remove_worker)
        self._event_count += 1
        nodes = self._worker_nodes.pop(worker_id, set())
        hashes = []
        for node in nodes:
            node.workers.discard(worker_id)
            hashes.append(node.hash)
        for h in hashes:
            node = self._by_hash.get(h)
            if node is not None:
                self._detach_if_empty(node)

    def find_matches(self, block_hashes,
                     now: Optional[float] = None) -> OverlapScores:
        scores: Dict[int, int] = {}
        freqs: List[int] = []
        exp = self.expiration_s
        if exp is not None and now is None:
            now = time.monotonic()
        node = self._root
        for depth, h in enumerate(block_hashes):
            node = node.children.get(h)
            if node is None:
                break
            any_advance = False
            for w in node.workers:
                if scores.get(w, 0) == depth:
                    scores[w] = depth + 1
                    any_advance = True
            if exp is not None:
                # expire stale uses, report survivors, record this access
                # (reference find_matches, indexer.rs:252-263)
                uses = node.recent_uses
                while uses and now - uses[0] > exp:
                    uses.popleft()
                if uses:
                    freqs.append(len(uses))
                uses.append(now)
            if not any_advance:
                break
        return OverlapScores(scores, freqs)

    def node_count(self) -> int:
        # count actual tree nodes, not the flat map: duplicate hashes from
        # out-of-order re-roots occupy two tree positions but one map slot
        def cnt(n: _PyNode) -> int:
            return 1 + sum(cnt(c) for c in n.children.values())
        return cnt(self._root) - 1

    def event_count(self) -> int:
        """Events applied — mirrors RadixIndexNative.event_count."""
        return self._event_count


def make_radix_index(prefer_native: bool = True,
                     expiration_s: Optional[float] = None):
    if prefer_native:
        return RadixIndexNative(expiration_s)
    return RadixIndexPython(expiration_s)


# ---------------------------------------------------------------------------
# KvIndexer: single-writer event application + query API
# ---------------------------------------------------------------------------


class KvIndexer:
    """Applies RouterEvents to the tree from one task; queries compute block
    hashes for the request tokens then walk the tree (reference
    KvIndexer::new / find_matches_for_request)."""

    def __init__(self, block_size: int, prefer_native: bool = True,
                 expiration_s: Optional[float] = None):
        """``expiration_s`` enables frequency tracking: matched blocks
        report their recent-use counts inside that window via
        OverlapScores.frequencies (reference KvIndexer::new_with_frequency,
        indexer.rs:525-560)."""
        self.block_size = block_size
        self.tree = make_radix_index(prefer_native, expiration_s)
        # (worker_id, seq_hash) → tier, tracked OUTSIDE the tree (both
        # tree backends stay tier-agnostic; device is the implicit
        # default and never stored here)
        self._tiers: Dict[tuple, str] = {}
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None

    # -- event side
    def apply_event(self, event: RouterEvent) -> None:
        if event.stored is not None:
            self.tree.apply_stored(event.worker_id, event.stored.parent_hash,
                                   event.stored.block_hashes)
            tier = getattr(event.stored, "tier", "device") or "device"
            for h in event.stored.block_hashes:
                key = (event.worker_id, h)
                if tier == "device":
                    # promotion back to HBM restores full weight
                    self._tiers.pop(key, None)
                else:
                    self._tiers[key] = tier
        if event.removed is not None:
            self.tree.apply_removed(event.worker_id,
                                    event.removed.block_hashes)
            for h in event.removed.block_hashes:
                self._tiers.pop((event.worker_id, h), None)

    async def enqueue_event(self, event: RouterEvent) -> None:
        self._ensure_task()
        await self._queue.put(event)

    def _ensure_task(self) -> None:
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="kv-indexer")

    async def _run(self) -> None:
        while True:
            ev = await self._queue.get()
            self.apply_event(ev)

    async def drain(self) -> None:
        while not self._queue.empty():
            await asyncio.sleep(0)

    def remove_worker(self, worker_id: int) -> None:
        self.tree.remove_worker(worker_id)
        self._tiers = {k: v for k, v in self._tiers.items()
                       if k[0] != worker_id}

    # -- query side
    def find_matches(self, block_hashes: Sequence[int]) -> OverlapScores:
        scores = self.tree.find_matches(block_hashes)
        if self._tiers:
            from .scoring import TIER_WEIGHTS
            for w, depth in scores.scores.items():
                eff = 0.0
                remote = 0
                for i in range(depth):
                    tier = self._tiers.get((w, block_hashes[i]), "device")
                    eff += TIER_WEIGHTS.get(tier, 1.0)
                    if tier == "remote":
                        remote += 1
                scores.weighted[w] = eff
                if remote:
                    scores.remote_blocks[w] = remote
        return scores

    def find_matches_for_request(self, token_ids: Sequence[int]
                                 ) -> OverlapScores:
        return self.find_matches(
            compute_block_hashes(token_ids, self.block_size))


class KvIndexerSharded:
    """N independent trees, events partitioned by worker id — bounds
    single-writer throughput at high event rates (reference
    `KvIndexerSharded`). Queries fan out and merge."""

    def __init__(self, block_size: int, shards: int = 4,
                 prefer_native: bool = True,
                 expiration_s: Optional[float] = None):
        self.block_size = block_size
        self.shards = [KvIndexer(block_size, prefer_native, expiration_s)
                       for _ in range(shards)]

    def _shard(self, worker_id: int) -> KvIndexer:
        return self.shards[worker_id % len(self.shards)]

    def apply_event(self, event: RouterEvent) -> None:
        self._shard(event.worker_id).apply_event(event)

    def remove_worker(self, worker_id: int) -> None:
        self._shard(worker_id).remove_worker(worker_id)

    def find_matches_for_request(self, token_ids) -> OverlapScores:
        hashes = compute_block_hashes(token_ids, self.block_size)
        merged: Dict[int, int] = {}
        weighted: Dict[int, float] = {}
        remote: Dict[int, int] = {}
        freqs: List[int] = []
        for sh in self.shards:
            r = sh.find_matches(hashes)
            merged.update(r.scores)
            weighted.update(r.weighted)
            remote.update(r.remote_blocks)
            # each shard tracks its own subtree's uses; take the
            # elementwise max as the merged hotness view
            for i, f in enumerate(r.frequencies):
                if i < len(freqs):
                    freqs[i] = max(freqs[i], f)
                else:
                    freqs.append(f)
        return OverlapScores(merged, freqs, weighted, remote)
