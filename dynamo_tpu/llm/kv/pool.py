"""Refcounted device-block pool with prefix reuse and LRU+priority eviction.

TPU-native redesign of the reference's three cooperating pieces
(lib/llm/src/kv/manager.rs `KvStorageManager`, kv/reuse.rs `AvailableBlocks`
with its `PriorityKey{priority, return_tick, seq_hash}` eviction order, and
kv/reserved.rs `ReservedBlocks`): one pool object owning every block of the
engine's flat paged HBM pool.

States per block:
- uninitialized: free, content garbage (`_free_uninit`)
- inflight: refcount > 0, attached to ≥1 running sequence
- reusable: refcount == 0 but content valid & registered under its
  sequence hash — eligible for prefix matching, evicted priority-then-LRU
  when uninitialized blocks run out.

Single-threaded by design (one pool per engine loop — the same actor
discipline the reference enforces with its mpsc progress engine,
reuse.rs:638; here the asyncio loop IS the actor).
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import logging
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .blocks import TokenBlockSequence

logger = logging.getLogger("dynamo_tpu.kv.pool")


class FreeRunIndex:
    """Coalescing index over the uninitialized free blocks: maximal runs
    of physically-adjacent block ids, with best-fit-run allocation.

    This is the device-pool half of the contiguity story (docs/
    kv_layout.md): logically paged KV does not have to be physically
    scattered — when a sequence's blocks land as few maximal runs, the
    decode kernel coalesces each run into ONE DMA per wave
    (engine/attention.py wave-coalescing) instead of one per block.

    Determinism contract (the native C++ pool mirrors this EXACTLY —
    tests/test_kv_pool.py differential fuzz): best fit = the smallest
    run with length >= n, ties broken by smallest start; when no run
    fits, take the LARGEST run (ties: smallest start) whole and repeat.
    Blocks are handed out ascending from each run's start.
    """

    def __init__(self):
        self._start: Dict[int, int] = {}   # run start -> length
        self._end: Dict[int, int] = {}     # run end (exclusive) -> start
        self._sorted: List[Tuple[int, int]] = []  # (length, start) sorted
        self.count = 0

    def __len__(self) -> int:
        return self.count

    @property
    def num_runs(self) -> int:
        return len(self._start)

    @property
    def largest_run(self) -> int:
        return self._sorted[-1][0] if self._sorted else 0

    def _remove_run(self, start: int, length: int) -> None:
        del self._start[start]
        del self._end[start + length]
        i = bisect.bisect_left(self._sorted, (length, start))
        assert self._sorted[i] == (length, start)
        self._sorted.pop(i)

    def _insert_run(self, start: int, length: int) -> None:
        self._start[start] = length
        self._end[start + length] = start
        bisect.insort(self._sorted, (length, start))

    def add(self, bid: int) -> None:
        """Return one block, coalescing with adjacent free runs."""
        start, length = bid, 1
        left = self._end.get(bid)
        if left is not None:                 # run ends exactly at bid
            llen = self._start[left]
            self._remove_run(left, llen)
            start, length = left, llen + 1
        rlen = self._start.get(bid + 1)
        if rlen is not None:                 # run starts right after bid
            self._remove_run(bid + 1, rlen)
            length += rlen
        self._insert_run(start, length)
        self.count += 1

    def take(self, n: int) -> List[int]:
        """Allocate n blocks as few maximal runs (contract above).
        Caller guarantees n <= len(self)."""
        out: List[int] = []
        while n > 0:
            i = bisect.bisect_left(self._sorted, (n, -1))
            if i < len(self._sorted):        # best fit: smallest len >= n
                length, start = self._sorted[i]
                take = n
            else:                            # largest run (tie: min start)
                length = self._sorted[-1][0]
                j = bisect.bisect_left(self._sorted, (length, -1))
                length, start = self._sorted[j]
                take = length
            self._remove_run(start, length)
            if take < length:
                self._insert_run(start + take, length - take)
            out.extend(range(start, start + take))
            n -= take
        self.count -= len(out)
        return out


@dataclasses.dataclass
class BlockMeta:
    block_id: int
    seq_hash: Optional[int] = None        # set when registered
    tokens_hash: Optional[int] = None     # local (unchained) hash
    parent_hash: Optional[int] = None
    refcount: int = 0
    priority: int = 0                     # lower evicts first
    return_tick: int = 0                  # LRU tiebreak


class KvBlockPool:
    """Owns block ids [1, num_blocks) — block 0 is the engine's trash block."""

    def __init__(self, num_blocks: int,
                 on_stored: Optional[Callable] = None,
                 on_removed: Optional[Callable] = None):
        self.num_blocks = num_blocks
        self._meta: Dict[int, BlockMeta] = {
            i: BlockMeta(i) for i in range(1, num_blocks)}
        # run-tracking free structure: maximal runs of adjacent block
        # ids, best-fit allocation — a sequence's new blocks land as few
        # physically-contiguous runs (the decode kernel's coalesced-DMA
        # contract, engine/attention.py)
        self._free_uninit = FreeRunIndex()
        for i in range(1, num_blocks):
            self._free_uninit.add(i)
        self._by_hash: Dict[int, int] = {}          # seq_hash → block_id
        self._reusable: Dict[int, int] = {}         # block_id → seq_hash (dict = insertion/LRU order)
        # lazy eviction heap keyed (priority, return_tick, bid): pushed
        # when a block becomes reusable; stale entries (re-matched,
        # re-registered with a new priority, already evicted) are
        # skipped at pop time by comparing against live meta — the
        # amortized-victim-selection treatment HostKvPool._slot_for got
        # (was an O(n) min() scan per eviction)
        self._evict_heap: List[Tuple[int, int, int]] = []
        self.evict_heap_skips = 0     # stale entries popped (regression stat)
        self._tick = 0
        self.on_stored = on_stored
        self.on_removed = on_removed
        # multi-tenant quota enforcement (llm/tenancy.py,
        # docs/multi_tenant.md): when a TenantBlockLedger is attached,
        # register() notes each hash's tenant in the device tier and
        # _evict_one prefers victims belonging to an OVER-QUOTA tenant
        # (bounded scan) — one tenant's eviction storm lands on its own
        # blocks first. None (the default) keeps eviction byte-identical
        # to the untenanted pool (the C++ mirror's differential-fuzz
        # contract is untouched).
        self.tenancy = None
        self.tenant_evictions = 0     # victims taken by quota preference
        # stats
        self.match_queries = 0
        self.match_hits = 0
        # contiguity accounting (nv_llm_kv_* layout gauges): how many
        # maximal runs each alloc was served as, vs the one-run ideal
        self.alloc_blocks_total = 0
        self.alloc_runs_total = 0
        self.alloc_requests_total = 0
        self.defrag_moves_total = 0

    # ------------------------------------------------------------- queries
    @property
    def free_blocks(self) -> int:
        return len(self._free_uninit) + len(self._reusable)

    @property
    def used_blocks(self) -> int:
        return (self.num_blocks - 1) - self.free_blocks

    @property
    def reusable_blocks(self) -> int:
        return len(self._reusable)

    @property
    def free_uninit_blocks(self) -> int:
        """Uninitialized free blocks only (no reusable content at
        stake) — the defrag pass allocates its target runs strictly
        from these so a layout move never evicts cached prefixes."""
        return len(self._free_uninit)

    def hit_rate(self) -> float:
        return self.match_hits / max(self.match_queries, 1)

    @property
    def contig_runs(self) -> int:
        """Maximal free runs in the uninit index (1 = fully coalesced)."""
        return self._free_uninit.num_runs

    def frag_ratio(self) -> float:
        """Fragmentation of the uninit free space: 1 - largest_run/free.
        0 = one maximal run (or nothing free); → 1 as the free space
        shatters into single blocks."""
        n = len(self._free_uninit)
        if n == 0:
            return 0.0
        return 1.0 - self._free_uninit.largest_run / n

    def contiguity_ratio(self) -> float:
        """Adjacency delivered / adjacency possible across all allocs:
        an n-block alloc served as r runs delivers n - r of its n - 1
        possible adjacent pairs. 1.0 = every alloc was one run."""
        possible = self.alloc_blocks_total - self.alloc_requests_total
        if possible <= 0:
            return 1.0
        return (self.alloc_blocks_total
                - self.alloc_runs_total) / possible

    @staticmethod
    def count_runs(blocks: Sequence[int]) -> int:
        """Maximal runs of consecutive ids in an ORDERED block list —
        the per-sequence fragmentation score the defrag pass ranks by."""
        if not blocks:
            return 0
        return 1 + sum(1 for a, b in zip(blocks, blocks[1:])
                       if b != a + 1)

    # ------------------------------------------------------------ matching
    def match_prefix(self, seq_hashes: Sequence[int]) -> List[int]:
        """Longest-prefix match: returns device block ids whose registered
        content equals the leading chained hashes. Matched blocks get a
        refcount hold (caller must release them later)."""
        out: List[int] = []
        for h in seq_hashes:
            self.match_queries += 1
            bid = self._by_hash.get(h)
            if bid is None:
                break
            self.match_hits += 1
            meta = self._meta[bid]
            if meta.refcount == 0:
                self._reusable.pop(bid, None)
            meta.refcount += 1
            out.append(bid)
        return out

    def peek_prefix(self, seq_hashes: Sequence[int]) -> int:
        """Length (in blocks) of the longest matchable prefix, without
        taking holds or touching stats — the disagg router's cheap estimate
        of local prefix overlap (reference disagg_router.rs prefix_hit_len
        input, computed by the worker before the remote/local decision)."""
        n = 0
        for h in seq_hashes:
            if h not in self._by_hash:
                break
            n += 1
        return n

    # ----------------------------------------------------------- allocate
    def alloc_uninit(self, n: int) -> Optional[List[int]]:
        """n fresh blocks (content garbage) as few maximal runs of
        adjacent ids (best-fit over the free-run index). When the uninit
        index runs short, reusable blocks are evicted FIRST — in strict
        priority-then-LRU order, preserving the eviction contract — and
        returned to the index (coalescing), THEN the runs are carved.
        Returns None if even eviction can't satisfy."""
        if n > self.free_blocks:
            return None
        for _ in range(n - len(self._free_uninit)):
            self._free_uninit.add(self._evict_one())
        out = self._free_uninit.take(n)
        for bid in out:
            self._meta[bid].refcount = 1
        if n:
            self.alloc_requests_total += 1
            self.alloc_blocks_total += n
            self.alloc_runs_total += self.count_runs(out)
        return out

    TENANT_EVICT_SCAN = 64   # bounded over-quota preference scan depth

    def _evict_one(self) -> int:
        # priority first (lower first), then LRU by return_tick — the
        # reference's PriorityKey ordering (reuse.rs) — via the lazy
        # heap: stale entries (block re-matched / re-keyed since push)
        # are skipped by comparing against live meta.
        if self.tenancy is not None:
            bid = self._evict_one_tenant_preferred()
            if bid is not None:
                self.tenant_evictions += 1
                self._invalidate(bid)
                return bid
        while True:
            prio, tick, bid = heapq.heappop(self._evict_heap)
            meta = self._meta[bid]
            if (bid in self._reusable and meta.priority == prio
                    and meta.return_tick == tick):
                break
            self.evict_heap_skips += 1
        self._invalidate(bid)
        return bid

    def _evict_one_tenant_preferred(self) -> Optional[int]:
        """Bounded scan of the eviction heap for a victim whose tenant
        is over its device-tier quota (llm/tenancy.py). Live entries
        passed over are pushed back (heap order preserved — they were
        popped, so no duplicates); stale entries are dropped exactly as
        the normal pop would. None = no over-quota victim in scan range
        → the caller falls through to the standard priority/LRU pop."""
        stash: List[Tuple[int, int, int]] = []
        found: Optional[int] = None
        for _ in range(min(len(self._evict_heap), self.TENANT_EVICT_SCAN)):
            if not self._evict_heap:
                break
            prio, tick, bid = heapq.heappop(self._evict_heap)
            meta = self._meta[bid]
            if not (bid in self._reusable and meta.priority == prio
                    and meta.return_tick == tick):
                self.evict_heap_skips += 1
                continue
            if self.tenancy.is_over_quota_hash(meta.seq_hash, "device"):
                found = bid
                break
            stash.append((prio, tick, bid))
        for e in stash:
            heapq.heappush(self._evict_heap, e)
        return found

    def _invalidate(self, bid: int) -> None:
        meta = self._meta[bid]
        self._reusable.pop(bid, None)
        if meta.seq_hash is not None:
            self._by_hash.pop(meta.seq_hash, None)
            if self.tenancy is not None:
                self.tenancy.forget(meta.seq_hash, "device")
            if self.on_removed is not None:
                self.on_removed([meta.seq_hash])
        meta.seq_hash = None
        meta.tokens_hash = None
        meta.parent_hash = None

    # ------------------------------------------------------------ register
    def register(self, bid: int, seq_hash: int, tokens_hash: int,
                 parent_hash: Optional[int], priority: int = 0,
                 tenant: Optional[str] = None) -> None:
        """Declare a block's content: it now holds the KV for the block whose
        chained hash is seq_hash. Emits a `stored` event. ``tenant``
        attributes the block in the attached TenantBlockLedger (quota
        accounting; no-op without a ledger)."""
        if self.tenancy is not None and tenant is not None:
            # note even on the duplicate/early-return paths below: the
            # content exists and serves this tenant's prefix either way
            self.tenancy.note(seq_hash, tenant, "device")
        meta = self._meta[bid]
        if meta.seq_hash == seq_hash:
            return
        existing = self._by_hash.get(seq_hash)
        if existing is not None and existing != bid:
            # duplicate content (two seqs computed the same prefix block):
            # keep the first registration; this block stays unregistered and
            # will return to the uninit pool on release.
            return
        if meta.seq_hash is not None:
            self._by_hash.pop(meta.seq_hash, None)
        meta.seq_hash = seq_hash
        meta.tokens_hash = tokens_hash
        meta.parent_hash = parent_hash
        if meta.priority != priority and bid in self._reusable:
            # re-key the lazy-heap entry: the old one goes stale and is
            # skipped at pop time (the C++ pool re-keys its set entry)
            heapq.heappush(self._evict_heap,
                           (priority, meta.return_tick, bid))
        meta.priority = priority
        self._by_hash[seq_hash] = bid
        if self.on_stored is not None:
            self.on_stored(bid, seq_hash, tokens_hash, parent_hash)

    def hold(self, blocks: Sequence[int]) -> None:
        """Add one reference to already-held blocks (pins them across an
        async copy, e.g. host offload write-back)."""
        for bid in blocks:
            if bid != 0:
                self._meta[bid].refcount += 1

    # ------------------------------------------------------------- release
    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference from each block; refcount-0 blocks become
        reusable (if registered) or uninitialized."""
        for bid in blocks:
            if bid == 0:
                continue
            meta = self._meta[bid]
            if meta.refcount == 0:
                continue          # double release is a no-op
            meta.refcount -= 1
            if meta.refcount == 0:
                self._tick += 1
                meta.return_tick = self._tick
                if meta.seq_hash is not None:
                    self._reusable[bid] = meta.seq_hash
                    heapq.heappush(
                        self._evict_heap,
                        (meta.priority, meta.return_tick, bid))
                else:
                    self._free_uninit.add(bid)

    def reset(self) -> None:
        """Drop all reusable content (reference reuse.rs `reset`)."""
        for bid in list(self._reusable):
            self._invalidate(bid)
            self._free_uninit.add(bid)

    # ------------------------------------------------------------ relocate
    def refcounts(self, blocks: Sequence[int]) -> List[int]:
        """Live refcounts (0 for the trash block) — the defrag pass
        skips blocks shared across sequences (refcount != 1)."""
        return [0 if bid == 0 else self._meta[bid].refcount
                for bid in blocks]

    def relocate(self, moves: Sequence[Tuple[int, int]]) -> None:
        """Rebind resident blocks old→new after the engine copied their
        DEVICE contents (engine/core.py defrag): hash registrations and
        refcounts follow the move, the old ids return to the free-run
        index. Each `new` must be a freshly alloc_uninit'd block
        (refcount 1, unregistered) and each `old` a resident block; no
        stored/removed events fire — the hashes are unchanged and block
        ids are worker-local."""
        for old, new in moves:
            m_old, m_new = self._meta[old], self._meta[new]
            if m_new.seq_hash is not None or m_new.refcount != 1:
                raise ValueError(
                    f"relocate target {new} is not a fresh uninit block")
            if m_old.refcount < 1:
                raise ValueError(f"relocate source {old} is not resident")
            m_new.refcount = m_old.refcount
            m_new.priority = m_old.priority
            m_new.return_tick = m_old.return_tick
            if m_old.seq_hash is not None:
                m_new.seq_hash = m_old.seq_hash
                m_new.tokens_hash = m_old.tokens_hash
                m_new.parent_hash = m_old.parent_hash
                self._by_hash[m_new.seq_hash] = new
            m_old.seq_hash = None
            m_old.tokens_hash = None
            m_old.parent_hash = None
            m_old.refcount = 0
            self._free_uninit.add(old)
            self.defrag_moves_total += 1

    # --------------------------------------------------------- reannounce
    def registered_entries(self) -> List[Tuple[int, int, int, Optional[int]]]:
        """Every registered block as (bid, seq_hash, tokens_hash,
        parent_hash) — the pool-side inventory behind ``reannounce``."""
        out = []
        for seq_hash, bid in self._by_hash.items():
            m = self._meta[bid]
            out.append((bid, seq_hash, m.tokens_hash, m.parent_hash))
        return out

    def reannounce(self, announce: Optional[Callable] = None) -> int:
        """Re-publish every registered block through ``announce`` (default:
        the ``on_stored`` sink), parents before children so a radix indexer
        re-chains without re-rooting. The recovery hook for a transient
        lease expiry: the router wiped this worker's index on the DELETE
        watch events, the lease reclaim replayed only discovery KEYS —
        this replays the KV content announcements (KNOWN_ISSUES)."""
        announce = announce or self.on_stored
        if announce is None:
            return 0
        pending = self.registered_entries()
        emitted: set = set()
        n = 0
        while pending:
            progress = False
            deferred = []
            for bid, seq_hash, tokens_hash, parent in pending:
                if parent is None or parent in emitted:
                    announce(bid, seq_hash, tokens_hash, parent)
                    emitted.add(seq_hash)
                    n += 1
                    progress = True
                else:
                    deferred.append((bid, seq_hash, tokens_hash, parent))
            if not progress:
                # orphans (parent evicted): emit anyway — the indexer
                # re-roots unknown parents at the top
                for bid, seq_hash, tokens_hash, parent in deferred:
                    announce(bid, seq_hash, tokens_hash, parent)
                    n += 1
                break
            pending = deferred
        return n


# priority of a window block that a prefix hit has ended on: what a next
# hit at that boundary needs, kept while window blocks no hit ended on
# (priority 0: a prefix's body, a finished request's own tail) go first
WINDOW_TAIL_PRIORITY = 1


class WindowBlockPool(KvBlockPool):
    """The pool of a window group's blocks (llm/kv/hybrid.py: dots3_note's
    window layers): block ids of its own, the same hashes as the paged
    pool's. A running sequence holds only the blocks its window still
    reaches; a released block whose hash is registered stays as evictable
    cache for a prefix hit that ends within a window after it."""

    def __init__(self, num_blocks: int, on_tail_evicted=None):
        super().__init__(num_blocks)
        self.on_tail_evicted = on_tail_evicted
        self.released = 0      # blocks let go because the window moved on
        self.evicted = 0       # cached blocks taken back for new rows

    def has(self, seq_hash: int) -> bool:
        return seq_hash in self._by_hash

    def hold_hash(self, seq_hash: int) -> int:
        """One more reference to the block registered under ``seq_hash``
        (a prefix hit's); it becomes a tail (WINDOW_TAIL_PRIORITY)."""
        bid = self._by_hash[seq_hash]
        meta = self._meta[bid]
        if meta.refcount == 0:
            self._reusable.pop(bid, None)
        meta.refcount += 1
        meta.priority = WINDOW_TAIL_PRIORITY
        return bid

    def _invalidate(self, bid: int) -> None:
        meta = self._meta[bid]
        seq_hash, tail = meta.seq_hash, meta.priority > 0
        super()._invalidate(bid)
        meta.priority = 0
        if seq_hash is not None:
            self.evicted += 1
            if tail and self.on_tail_evicted is not None:
                self.on_tail_evicted(seq_hash)


class WindowBlocks:
    """The window-pool blocks one sequence holds: logical block index →
    block id, and how many of its leading full blocks were registered."""

    __slots__ = ("held", "registered")

    def __init__(self):
        self.held: Dict[int, int] = {}
        self.registered = 0

    def table(self, n: int) -> List[int]:
        """The block of every logical block < n (0: not held)."""
        out = [0] * n
        for i, bid in self.held.items():
            if i < n:
                out[i] = bid
        return out


def make_kv_block_pool(num_blocks: int, on_stored=None, on_removed=None,
                       prefer_native: bool = True):
    """Pool factory: the C++ pool (csrc/kv_reuse_pool.cpp) unless the
    caller (``prefer_native=False``) or DYN_NATIVE_KVPOOL=0 asks for the
    Python implementation above by name. Both expose the identical
    interface; a failed native build raises (utils/native.py)."""
    if prefer_native and os.environ.get("DYN_NATIVE_KVPOOL", "1") != "0":
        from .native_pool import NativeKvBlockPool
        return NativeKvBlockPool(num_blocks, on_stored=on_stored,
                                 on_removed=on_removed)
    return KvBlockPool(num_blocks, on_stored=on_stored,
                       on_removed=on_removed)


@dataclasses.dataclass
class PrefillPlan:
    """Outcome of preparing a sequence for prefill (reference
    `KvStorageManager::prepare_prefill_sequence` /
    `prepare_prefill_offload`, kv/manager.rs:21-168)."""

    hit_blocks: List[int]
    new_blocks: List[int]
    hit_tokens: int
    seq: TokenBlockSequence
    # host-tier hits: slots in the HostKvPool whose content must be copied
    # into the first len(host_slots) entries of new_blocks before prefill
    host_slots: List[int] = dataclasses.field(default_factory=list)
    # disk-tier (G3) hits: chained hashes resident in the DiskKvStore,
    # promoted into new_blocks[len(host_slots):len(host_slots) +
    # len(disk_hashes)] through the same off-thread onboard path. The
    # matched entries are PINNED against spill-pump eviction until the
    # admission completes (match_prefix(pin=True)).
    disk_hashes: List[int] = dataclasses.field(default_factory=list)
    # remote (G4) fabric hits: chained hashes reachable through the
    # RemoteKvStore (a peer worker's disk over the kv_fabric RPC plane,
    # or the shared object store) — the tail of the onboard run, after
    # the disk hits. Admission-gated at match time (remotestore.py:
    # modeled fetch must beat modeled recompute) and fetched on the same
    # off-thread onboard path; a fetch failure clears this list and the
    # engine gracefully recomputes the tail (never an error).
    remote_hashes: List[int] = dataclasses.field(default_factory=list)
    # a layout with a window pool: the window blocks the hit needs (held),
    # and the tokens of a longer paged match that were given up because
    # the window blocks before its boundary were gone
    win: Optional["WindowBlocks"] = None
    hit_cut_tokens: int = 0

    @property
    def all_blocks(self) -> List[int]:
        return self.hit_blocks + self.new_blocks

    @property
    def host_hit_tokens(self) -> int:
        return len(self.host_slots) * self.seq.block_size

    @property
    def disk_hit_tokens(self) -> int:
        return len(self.disk_hashes) * self.seq.block_size

    @property
    def remote_hit_tokens(self) -> int:
        return len(self.remote_hashes) * self.seq.block_size


class KvBlockManager:
    """Pool + hashing glue the engine admit path calls. Optionally backed by
    a host (TPU-VM DRAM) tier, a persistent disk (G3) tier, and a remote
    (G4) fleet-fabric tier: device misses cascade host → disk → remote
    (reference `prepare_prefill_offload` extended down the
    Device→Pinned→Disk→Remote ladder)."""

    def __init__(self, num_blocks: int, block_size: int,
                 on_stored=None, on_removed=None, enable_reuse: bool = True,
                 host_pool=None, disk_store=None, remote_store=None,
                 prefer_native: bool = True, layout=None,
                 win_blocks: int = 0):
        # a hybrid model's kinds of memory (llm/kv/hybrid.py): the pool
        # below pages the first kind; window rings and recurrent state are
        # per slot and need no allocator. With state, no prefix can be
        # resumed from a block boundary: nothing is matched and nothing is
        # registered. Window rows that are pool blocks (layout.window_pool)
        # get a pool of their own, and a prefix hit needs both pools' blocks
        self.layout = layout
        self.stateful = layout is not None and layout.has_state
        if self.stateful:
            enable_reuse = False
        self.block_size = block_size
        self.win_pool: Optional[WindowBlockPool] = None
        if layout is not None and layout.window_pool:
            self.win_pool = WindowBlockPool(
                win_blocks or num_blocks,
                on_tail_evicted=self._on_window_tail_evicted)
            # hashes the router was told are gone because a hit through
            # them would be cut back (their window tail was evicted),
            # though the paged pool still holds them
            self._hidden: set = set()
            self._announce, self._retract = on_stored, on_removed
            on_removed = self._on_paged_removed
        self.pool = make_kv_block_pool(num_blocks, on_stored=on_stored,
                                       on_removed=on_removed,
                                       prefer_native=prefer_native)
        self.enable_reuse = enable_reuse
        self.host_pool = host_pool
        self.disk_store = disk_store
        self.remote_store = remote_store
        # multi-tenant ledger (llm/tenancy.py) — attached by
        # EngineCore.enable_tenancy alongside the per-tier hooks
        self.tenancy = None

    def prepare_prefill(self, prompt: Sequence[int], extra_blocks: int = 1,
                        seq: Optional[TokenBlockSequence] = None,
                        cold: bool = False
                        ) -> Optional[PrefillPlan]:
        """Match the prompt's full blocks against the pool (device tier, then
        host tier), allocate the remainder (+ room for `extra_blocks` of
        generation). None = out of memory. At least one prompt token is
        always left to recompute so prefill produces the first-token
        logits. ``seq`` may carry the prompt's already-computed hash chain
        (e.g. from the disagg router's estimate) to avoid re-hashing.
        ``cold=True`` skips the host/disk/remote cascade entirely (device
        hits need no onboard) — the engine's graceful fallback after a
        tier onboard prep failed (EngineRequest.cold_admission)."""
        if seq is None:
            seq = TokenBlockSequence(self.block_size, prompt)
        matchable = seq.sequence_hashes
        # never match the *entire* prompt — hold back the final block so at
        # least one token runs through prefill
        if len(prompt) % self.block_size == 0 and matchable:
            matchable = matchable[:-1]
        hit_blocks = (self.pool.match_prefix(matchable)
                      if self.enable_reuse else [])
        if (hit_blocks and self.layout is not None
                and self.layout.rows_read_next_token):
            # the last matched block's last row was computed from another
            # sequence's next token (hybrid.py): compute that block again
            self.pool.release(hit_blocks[-1:])
            hit_blocks = hit_blocks[:-1]
        win, hit_cut = None, 0
        if self.win_pool is not None:
            # the hit rule over both groups: keep the longest boundary
            # whose window blocks are there too, and hold those
            keep = self._window_cut(matchable, len(hit_blocks))
            hit_cut = (len(hit_blocks) - keep) * self.block_size
            self.pool.release(hit_blocks[keep:])
            hit_blocks = hit_blocks[:keep]
            win = WindowBlocks()
            for i in range(max(0, keep - self.layout.window_reach_blocks),
                           keep):
                win.held[i] = self.win_pool.hold_hash(matchable[i])
            win.registered = keep
        hit_tokens = len(hit_blocks) * self.block_size
        host_slots: List[int] = []
        disk_hashes: List[int] = []
        if self.enable_reuse and not cold and self.host_pool is not None:
            host_slots = self.host_pool.match_prefix(
                matchable[len(hit_blocks):])
        if self.enable_reuse and not cold and self.disk_store is not None:
            # G3 cascade: the run of hashes past the host hits. pin=True
            # holds the matched entries against the spill pump's
            # capacity evictions (worker thread) until the admission's
            # off-thread read completes (core unpins)
            disk_hashes = self.disk_store.match_prefix(
                matchable[len(hit_blocks) + len(host_slots):], pin=True)
        remote_hashes: List[int] = []
        # Everything between the pin-taking disk match above and the
        # returned plan (which transfers pin ownership to the caller)
        # runs under an except-all: an unexpected raise — a buggy remote
        # store, a native-pool ABI error in alloc_uninit — must release
        # the device holds and tier pins before propagating, or the
        # engine slot leaks spill-pump victims forever (dynalint DL003,
        # PR 5's runtime assert made static for exception edges too).
        try:
            if (self.enable_reuse and not cold
                    and self.remote_store is not None):
                # G4 cascade: the run past the disk hits, reachable
                # through the fleet fabric (peer disk over RPC, or the
                # shared object store). The store's match is
                # admission-gated — it reports a miss when the modeled
                # fetch loses to recompute — and pin=True holds
                # object-held entries against the capacity reaper until
                # the admission's off-thread read completes.
                remote_hashes = self.remote_store.match_prefix(
                    matchable[len(hit_blocks) + len(host_slots)
                              + len(disk_hashes):], pin=True)
            total_needed = (len(prompt) + extra_blocks * self.block_size
                            + self.block_size - 1) // self.block_size
            n_new = total_needed - len(hit_blocks)
            new_blocks = self.pool.alloc_uninit(n_new)
            if new_blocks is None:
                self.pool.release(hit_blocks)
                self.window_release(win)
                if disk_hashes:
                    self.disk_store.unpin(disk_hashes)
                if remote_hashes:
                    self.remote_store.unpin(remote_hashes)
                return None
            if len(new_blocks) < (len(host_slots) + len(disk_hashes)
                                  + len(remote_hashes)):
                # the onboard path scatters host/disk/remote hits into
                # new_blocks[:n_onboard] — a plan where the allocation
                # can't cover the pinned tier hits would silently DROP
                # them (or scatter past the allocation). The cascade
                # math above guarantees this never happens; if a tier's
                # match_prefix over-returns (a buggy store), fail loudly
                # instead of serving garbage. The except below releases
                # every hold so the loud failure doesn't also leak pool
                # refcounts / tier pins.
                self.pool.release(new_blocks)
                raise RuntimeError(
                    f"prepare_prefill invariant violated: "
                    f"{len(new_blocks)} new blocks cannot cover "
                    f"{len(host_slots)} host + {len(disk_hashes)} disk "
                    f"+ {len(remote_hashes)} remote tier hits (prompt "
                    f"{len(prompt)}, device hits {len(hit_blocks)})")
        except Exception:
            self.pool.release(hit_blocks)
            self.window_release(win)
            if disk_hashes:
                self.disk_store.unpin(disk_hashes)
            if remote_hashes:
                self.remote_store.unpin(remote_hashes)
            raise
        return PrefillPlan(hit_blocks=hit_blocks, new_blocks=new_blocks,
                           hit_tokens=hit_tokens, seq=seq,
                           host_slots=host_slots, disk_hashes=disk_hashes,
                           remote_hashes=remote_hashes, win=win,
                           hit_cut_tokens=hit_cut)

    def abort_plan(self, plan: "PrefillPlan") -> None:
        """Release a plan that will never admit: device block holds drop
        and the disk/remote-tier pins (taken at match) release."""
        self.pool.release(plan.all_blocks)
        self.window_release(plan.win)
        if plan.disk_hashes and self.disk_store is not None:
            self.disk_store.unpin(plan.disk_hashes)
        if plan.remote_hashes and self.remote_store is not None:
            self.remote_store.unpin(plan.remote_hashes)

    def register_full_blocks(self, plan_blocks: List[int],
                             seq: TokenBlockSequence,
                             already_registered: int,
                             tenant: Optional[str] = None) -> int:
        """Register every newly-full block of `seq` (device block order ==
        block-hash order). Returns the new count of registered blocks.
        ``tenant`` attributes the blocks for per-tenant quota accounting
        (llm/tenancy.py; no-op without an attached ledger)."""
        if self.stateful:
            return already_registered
        n_full = seq.num_full_blocks
        for i in range(already_registered, n_full):
            if i >= len(plan_blocks):
                break
            parent = seq.sequence_hashes[i - 1] if i > 0 else None
            self.pool.register(plan_blocks[i], seq.sequence_hashes[i],
                               seq.block_hashes[i], parent, tenant=tenant)
        return min(n_full, len(plan_blocks))

    # ------------------------------------------------- the window group
    def _window_cut(self, hashes: Sequence[int], n: int) -> int:
        """The longest boundary n' <= n (in blocks, possibly 0) whose
        window blocks [n' - reach, n') are all registered: what a hit of n
        paged blocks is cut back to (docs/hybrid_cache.md, the hit rule)."""
        reach, has = self.layout.window_reach_blocks, self.win_pool.has
        while n > 0:
            gap = next((i for i in range(n - 1, max(0, n - reach) - 1, -1)
                        if not has(hashes[i])), None)
            if gap is None:
                return n
            n = gap          # no boundary in (gap, gap + reach] can be hit
        return 0

    def window_grow(self, win: "WindowBlocks", lo: int, hi: int) -> bool:
        """Window blocks for the logical blocks [lo, hi) that ``win`` does
        not hold yet. False: the pool is out of blocks (nothing taken)."""
        need = [i for i in range(lo, hi) if i not in win.held]
        new = self.win_pool.alloc_uninit(len(need))
        if new is None:
            return False
        win.held.update(zip(need, new))
        return True

    def window_register(self, win: "WindowBlocks", seq: TokenBlockSequence,
                        blocks: Sequence[int], computed: int) -> None:
        """Register the window blocks of ``seq``'s full blocks whose rows
        are written (those before position ``computed``) under the paged
        blocks' hashes (``blocks``: the paged table, for the router's sake:
        a hash it was told is gone comes back with its window block)."""
        if not self.enable_reuse:
            return
        n_full = min(seq.num_full_blocks, computed // self.block_size)
        for i in range(win.registered, n_full):
            bid = win.held.get(i)
            if bid is None:
                continue
            h = seq.sequence_hashes[i]
            parent = seq.sequence_hashes[i - 1] if i > 0 else None
            self.win_pool.register(bid, h, seq.block_hashes[i], parent)
            if h in self._hidden and i < len(blocks):
                self._hidden.discard(h)
                if self._announce is not None:
                    self._announce(blocks[i], h, seq.block_hashes[i], parent)
        win.registered = max(win.registered, n_full)

    def window_slide(self, win: "WindowBlocks", position: int) -> None:
        """Let go of the blocks wholly behind the window of the query at
        ``position``: to the evictable cache where registered, else free."""
        first = max(0, position - (self.layout.window - 1)) // self.block_size
        gone = [i for i in win.held if i < first]
        self.win_pool.release([win.held.pop(i) for i in gone])
        self.win_pool.released += len(gone)

    def window_release(self, win: Optional["WindowBlocks"]) -> None:
        """Everything ``win`` holds, at finish, cancel and preemption."""
        if win is not None and win.held:
            self.win_pool.release(list(win.held.values()))
            win.held.clear()

    def window_stats(self) -> dict:
        w = self.win_pool
        return {"window_blocks_released": w.released,
                "window_blocks_evicted": w.evicted,
                "window_blocks_cached": w.reusable_blocks,
                "window_blocks_used": w.used_blocks}

    def _on_window_tail_evicted(self, seq_hash: int) -> None:
        # a hit through this block's boundaries would now be cut back: the
        # router is told the paged block is gone until the window block is
        # computed again (window_register)
        if self.pool.peek_prefix([seq_hash]) and seq_hash not in self._hidden:
            self._hidden.add(seq_hash)
            if self._retract is not None:
                self._retract([seq_hash])

    def _on_paged_removed(self, seq_hashes: list) -> None:
        told = [h for h in seq_hashes if h not in self._hidden]
        self._hidden.difference_update(seq_hashes)
        if told and self._retract is not None:
            self._retract(told)
