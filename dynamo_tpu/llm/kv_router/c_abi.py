"""ctypes bridge to the C KV-event ABI (csrc/kv_event_abi.cpp).

Reference: lib/bindings/c/src/lib.rs:51-297 — the cdylib external engines
load to publish KV cache events (`dynamo_llm_init`,
`dynamo_kv_event_publish_stored/removed`), consumed via ctypes from the
vLLM patch's KVCacheEventManager (patch lines 302-416). Here the native lib
queues events and :class:`CtypesKvEventPublisher.drain` converts them to
:class:`RouterEvent`s for the message-bus sink — identical wire shape to the
in-process :class:`~dynamo_tpu.llm.kv_router.publisher.KvEventPublisher`
(the parity test feeds both into one indexer).
"""

from __future__ import annotations

import asyncio
import ctypes
import json
from typing import Awaitable, Callable, List, Optional, Sequence

from ...utils import native
from ..kv.blocks import hash_tokens
from .protocols import KvRemovedEvent, KvStoredEvent, RouterEvent

DYN_OK = 0


def load_abi() -> ctypes.CDLL:
    lib = native.load("dynkvabi", ["kv_event_abi.cpp"])
    lib.dynamo_llm_init.restype = ctypes.c_int64
    lib.dynamo_llm_init.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                    ctypes.c_int64, ctypes.c_uint32]
    lib.dynamo_llm_shutdown.restype = ctypes.c_int64
    lib.dynamo_kv_event_publish_stored.restype = ctypes.c_int64
    lib.dynamo_kv_event_publish_stored.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64]
    lib.dynamo_kv_event_publish_removed.restype = ctypes.c_int64
    lib.dynamo_kv_event_publish_removed.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t]
    lib.dyn_kv_event_poll.restype = ctypes.c_void_p
    lib.dyn_kv_event_str_free.argtypes = [ctypes.c_void_p]
    lib.dyn_kv_event_pending.restype = ctypes.c_size_t
    lib.dyn_kv_event_dropped.restype = ctypes.c_uint64
    lib.dyn_kv_abi_info.restype = ctypes.c_void_p
    return lib


def _take_string(lib: ctypes.CDLL, ptr: int) -> Optional[str]:
    if not ptr:
        return None
    try:
        return ctypes.string_at(ptr).decode("utf-8")
    finally:
        lib.dyn_kv_event_str_free(ptr)


class CtypesKvEventPublisher:
    """Engine-facing handle over the C ABI, plus the runtime-side drain.

    The publish methods take exactly what the C signatures take, so an
    out-of-process engine written against the ABI and this in-process
    wrapper exercise the same code path.
    """

    def __init__(self, namespace: str, component: str, worker_id: int,
                 kv_block_size: int):
        self.lib = load_abi()
        rc = self.lib.dynamo_llm_init(namespace.encode(), component.encode(),
                                      worker_id, kv_block_size)
        if rc != DYN_OK:
            raise RuntimeError(f"dynamo_llm_init failed: rc={rc}")
        self.worker_id = worker_id

    def shutdown(self) -> None:
        self.lib.dynamo_llm_shutdown()

    # ---- engine-facing (mirrors the C signatures) ----
    def publish_stored(self, event_id: int, blocks_tokens: Sequence[Sequence[int]],
                       block_hashes: Sequence[int],
                       parent_hash: Optional[int] = None,
                       lora_id: int = 0) -> int:
        flat: List[int] = [t for blk in blocks_tokens for t in blk]
        n = len(block_hashes)
        token_arr = (ctypes.c_uint32 * max(len(flat), 1))(*flat)
        sizes = (ctypes.c_size_t * max(n, 1))(*[len(b) for b in blocks_tokens])
        hashes = (ctypes.c_uint64 * max(n, 1))(*block_hashes)
        parent = (ctypes.c_uint64(parent_hash) if parent_hash is not None
                  else None)
        return self.lib.dynamo_kv_event_publish_stored(
            event_id, token_arr, sizes, hashes, n,
            ctypes.byref(parent) if parent is not None else None, lora_id)

    def publish_removed(self, event_id: int,
                        block_hashes: Sequence[int]) -> int:
        n = len(block_hashes)
        hashes = (ctypes.c_uint64 * max(n, 1))(*block_hashes)
        return self.lib.dynamo_kv_event_publish_removed(event_id, hashes, n)

    # ---- runtime-facing drain ----
    @property
    def pending(self) -> int:
        return self.lib.dyn_kv_event_pending()

    @property
    def dropped(self) -> int:
        return self.lib.dyn_kv_event_dropped()

    def info(self) -> Optional[dict]:
        raw = _take_string(self.lib, self.lib.dyn_kv_abi_info())
        return None if raw is None else json.loads(raw)

    def poll(self) -> Optional[RouterEvent]:
        """Pop one queued event, computing local token hashes (xxh3 seed
        1337) exactly as the in-process engine does."""
        raw = _take_string(self.lib, self.lib.dyn_kv_event_poll())
        if raw is None:
            return None
        d = json.loads(raw)
        ev = RouterEvent(worker_id=d["worker_id"], event_id=d["event_id"])
        if "stored" in d:
            s = d["stored"]
            ev.stored = KvStoredEvent(
                parent_hash=s["parent_hash"],
                block_hashes=list(s["block_hashes"]),
                tokens_hashes=[hash_tokens(b) for b in s["blocks_tokens"]],
                lora_id=s.get("lora_id", 0))
        if "removed" in d:
            ev.removed = KvRemovedEvent(
                block_hashes=list(d["removed"]["block_hashes"]))
        return ev

    async def drain(self, sink: Callable[[RouterEvent], Awaitable[None]],
                    poll_interval: float = 0.01) -> None:
        """Forward queued events to ``sink`` until cancelled (the runtime
        spawns this next to the bus publisher)."""
        while True:
            ev = self.poll()
            if ev is None:
                await asyncio.sleep(poll_interval)
                continue
            await sink(ev)

    async def drain_pending(self,
                            sink: Callable[[RouterEvent], Awaitable[None]]
                            ) -> int:
        """Drain whatever is queued right now (test/shutdown helper)."""
        count = 0
        while True:
            ev = self.poll()
            if ev is None:
                return count
            await sink(ev)
            count += 1
