"""Multi-host single-engine serving: leader drives, followers live-replay.

The reference runs one engine across hosts with Ray leader/follower
(lib/llm/src/engines/vllm/ray.rs:1-387, vllm.rs:39-87) and sglang's
per-rank subprocess split (lib/llm/src/engines/sglang/worker.rs:304-336).
The TPU-native analog is multi-controller SPMD: every process holds the
same jitted programs over one global ``jax.sharding.Mesh`` (formed by
``parallel.multihost.initialize_multihost``); XLA collectives span hosts
over ICI/DCN. What still needs framework plumbing is HOST control flow:
every process must issue the SAME sequence of device programs with the
SAME host inputs, or the collectives deadlock.

Design: the leader runs the real engine — scheduler, HTTP ingress, KV
manager, detokenizer — exactly as on one host. Its scheduler decisions
already stream through the :class:`engine.replay.Recorder` event format
(every dispatched program's host inputs, in device order). A follower is
a live replay consumer: it receives that stream over TCP and issues the
identical programs against its own EngineCore (same config, same weights
path, same global mesh). Device state (params, KV pool) stays
bit-identical by induction; sampled tokens come back replicated, the
leader harvests them (rank-0 token egress), followers drop theirs.

Lockstep comes for free from XLA: if the leader runs ahead, its programs
wait at the first cross-host collective until the follower catches up;
the leader's event send happens synchronously BEFORE its own dispatch,
so the follower can always make progress.

Wire format: length-prefixed pickle frames of the recorder's numpy-only
event dicts. The stream shares the deployment's trust domain with
``jax.distributed`` itself (same hosts, same network) — it is an
intra-engine control channel, not a public endpoint.

sp ring prefill and chunked prefill ARE streamed (the "prefill_sp"
event; chunks record as plain "prefill" events) — sp's cross-host
ppermute rides ICI on real hardware. Wire-plane disagg onboarding IS
streamed too ("precomputed_admit" forwards the remote prefill's KV
values; each rank scatters its head shard). DEVICE-plane disagg
payloads are streamed as metadata only ("precomputed_device_admit":
rid + target blocks): the payload's arrays are device-resident, so in a
multihost disagg deployment every rank runs an SPMD replica of the
prefill engine, parks its own shard of the payload in its process
bridge (kv_transport.DeviceKvBridge.park), and scatters it when the
leader's admission event arrives — the per-rank routing the wire plane
already uses, without bulk KV on the control stream. This closed the
last multihost refusal (round 4); "prefill_unsupported" remains as a
defensive guard for any future unstreamable path.

The host-KV tier IS streamed: followers keep a MIRROR host pool. The
leader's offload pump emits its literal placement decisions ("kv_store":
hash → slot, eviction, source device block) at commit time — before the
device holds release, so the stream orders the event ahead of any
program that could overwrite a reused block. The follower gathers the
SAME device blocks from its own bit-identical KV and applies the
decisions verbatim (HostKvPool.apply_store) — arena bytes equal by
induction, no bulk KV on the wire. A host-restored admission then
replays h2d locally: "hit_transfer" carries the mirror slots + device
targets and the follower runs the same scatter program the leader ran.

Pipeline parallelism rides this stream UNCHANGED: a pp engine's stage
dispatches are ordinary "prefill"/"dispatch" events — the pp core's
_prefill_jit/_decode_k_jit keep the single-device host contracts
(engine/core._compile_jits_pp), so followers re-issue the recorded
events through their OWN pp-compiled programs and enter the stage
ring's ppermutes in lockstep. The one pp-specific requirement is the
standing one: every rank builds from identical flags (--pp/--tp
included), or the shard_map programs disagree at the first collective.
attach() keeps enforcing decode_steps_per_dispatch > 1, which a pp
config guarantees (EngineConfig refuses pp with K=1).

The disk (G3) tier extends the same contract one rung down: each
"kv_store" event additionally names the evicted hashes the leader's
disk spill queue ACCEPTED ("spills" — the enqueue decision, made
synchronously inside the pool store); the follower stages a copy of
exactly those rows from its mirror arena before the eviction overwrites
them. The spill pump's later durable commit streams "kv_disk_store"
(hash + the leader's literal disk-eviction set) and the follower applies
it verbatim to its OWN local disk store from the staged bytes
(DiskKvStore.apply_put — no LRU policy re-run, no bulk KV on the wire).
A disk-promoted admission rides "hit_transfer"'s disk_hashes/
disk_targets, restored from the follower's mirror disk store.

The remote (G4) fleet tier closed the LAST tier refusal (round 12):
the object store / peer fleet is shared state no follower can re-walk,
so a remote-assisted admission streams as "kv_remote_restore" — the
fetched hashes plus the fetched BYTES — ordered before its
hit_transfer; the follower scatters the literal bytes with the same
program the leader ran (replay.exec_kv_remote_restore_event). A
follower whose own remote store shares the leader's content-addressed
object root may fetch the hashes instead of reading the event's bytes
(fetch-or-bytes): equal hash ⇒ equal bytes by construction.
"""

from __future__ import annotations

import logging
import pickle
import socket
import struct
import time
from collections import OrderedDict
from typing import List

from .replay import Recorder

logger = logging.getLogger("dynamo_tpu.engine.multihost")

__all__ = ["DispatchStreamLeader", "connect_follower", "run_follower"]

# events a follower needs for device-state lockstep; everything else the
# recorder sees (replay.HOST_EVENTS: admit/harvest/first_token/preempt/
# release) is leader-side host bookkeeping. dynalint DL009 holds this
# set equal to run_follower's handled kinds — `ragged` and `verify` were
# missing here while run_follower already handled them, so a ragged or
# speculative leader silently dropped those dispatches on the floor and
# follower device state diverged.
WIRE_EVENTS = frozenset(
    {"prefill", "prefill_sp", "dispatch", "ragged", "verify",
     "hit_transfer", "kv_store", "kv_disk_store", "kv_remote_restore",
     "precomputed_admit", "precomputed_device_admit", "handoff_gather",
     "prefill_unsupported", "kv_layer_stream"})
_SHUTDOWN = {"ev": "__shutdown__"}

_LEN = struct.Struct(">I")


def _send_frame(sock: socket.socket, obj: dict) -> None:
    data = pickle.dumps(obj, protocol=5)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("dispatch stream closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> dict:
    (n,) = _LEN.unpack(_recv_exact(sock, 4))
    return pickle.loads(_recv_exact(sock, n))


class DispatchStreamLeader(Recorder):
    """Leader-side recorder that forwards device-order events to follower
    sockets instead of buffering them.

    Attach as ``core.recorder``. ``rec`` sends synchronously (blocking
    sendall) so the event is on the wire BEFORE the leader's own jit
    dispatch for that event — the ordering that makes follower progress
    independent of the leader's device state. TCP backpressure bounds
    leader run-ahead naturally.
    """

    def __init__(self, port: int, num_followers: int,
                 host: str = "0.0.0.0", accept_timeout: float = 120.0):
        super().__init__()
        self._listener = socket.create_server((host, port))
        self.port = self._listener.getsockname()[1]
        self.num_followers = num_followers
        self._accept_timeout = accept_timeout
        self.socks: List[socket.socket] = []
        self.sent = 0
        self.broken = False

    def attach(self, core) -> None:
        """Validate the engine is in a configuration whose EVERY device
        program flows through the recorder stream, then become its
        recorder. A program the follower never hears about deadlocks the
        first cross-host collective (the unrecorded single-step path of
        old taught us this the hard way). One step per dispatch is in the
        stream now, harvested before the next is built while a recorder
        is attached, but stays refused until a follower has replayed it."""
        if core.cfg.decode_steps_per_dispatch <= 1:
            raise ValueError(
                "multihost serving requires decode_steps_per_dispatch > 1 "
                "(no follower has replayed the one-step path's per-slot "
                "chained dispatches)")
        pool = core.kv_manager.host_pool
        if pool is not None and len(pool) > 0:
            # followers mirror only post-attach stores; a pre-attach
            # offload would later host-hit with slots no follower holds
            raise ValueError(
                "attach the dispatch stream before the engine offloads "
                f"anything (host pool already holds {len(pool)} blocks)")
        if core.disk_store is not None and len(core.disk_store) > 0:
            # same staleness hazard one tier down: a warm-started disk
            # store holds blocks no follower can prove it mirrors
            raise ValueError(
                "multihost serving cannot start from a warm disk KV "
                f"store ({len(core.disk_store)} blocks at "
                f"{core.disk_store.root}) — clear it (llmctl kv flush "
                f"--clear) or point --kv-disk-dir at a fresh directory")
        core.recorder = self

    def wait_for_followers(self) -> None:
        """Block until every follower has connected."""
        self._listener.settimeout(self._accept_timeout)
        while len(self.socks) < self.num_followers:
            try:
                s, addr = self._listener.accept()
            except socket.timeout:
                raise TimeoutError(
                    f"only {len(self.socks)}/{self.num_followers} followers "
                    f"connected within {self._accept_timeout}s")
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.socks.append(s)
            logger.info("follower %d/%d connected from %s",
                        len(self.socks), self.num_followers, addr)

    def rec(self, ev: str, **kw) -> None:
        if ev not in WIRE_EVENTS:
            return
        if self.broken:
            # fail FAST and deterministically: after any send failure some
            # follower may have missed an event, so device state can no
            # longer be proven bit-identical — serving must stop, not
            # silently diverge
            raise RuntimeError(
                "multihost dispatch stream is broken (a prior event send "
                "failed); the engine cannot guarantee follower lockstep")
        kw["ev"] = ev
        # serialize ONCE: precomputed_admit carries bulk KV values, and
        # per-socket pickling would redo megabytes of work on the loop
        data = pickle.dumps(kw, protocol=5)
        frame = _LEN.pack(len(data)) + data
        try:
            for s in self.socks:
                s.sendall(frame)
        except OSError:
            self.broken = True
            raise
        self.sent += 1

    def close(self) -> None:
        for s in self.socks:
            try:
                _send_frame(s, _SHUTDOWN)
                s.close()
            except OSError:
                pass
        self._listener.close()


def connect_follower(addr: str, timeout: float = 120.0) -> socket.socket:
    """Dial the leader's dispatch stream, retrying while it boots."""
    host, port = addr.rsplit(":", 1)
    deadline = time.monotonic() + timeout
    delay = 0.1
    while True:
        try:
            sock = socket.create_connection((host, int(port)), timeout=5.0)
            sock.settimeout(None)   # connect timeout only — the stream
            # idles for as long as the leader has nothing to dispatch
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            return sock
        except OSError:
            if time.monotonic() + delay > deadline:
                raise
            time.sleep(delay)
            delay = min(delay * 2, 1.0)


def run_follower(core, sock: socket.socket,
                 max_chain_keep: int = 8) -> dict:
    """Consume the leader's dispatch stream against a local EngineCore
    until shutdown. Blocking; run as the follower process's main loop.

    The event→program marshalling is shared with the offline replayer
    (replay.exec_prefill_event / exec_dispatch_event) so the jit-call
    signatures live in exactly one place; this loop only adds the live
    carry (``core.kv``) and a bounded chain window.
    """
    from .replay import (exec_dispatch_event, exec_host_restore_event,
                         exec_kv_disk_store_event,
                         exec_kv_remote_restore_event, exec_kv_store_event,
                         exec_prefill_event, exec_ragged_event,
                         exec_sp_prefill_event, exec_verify_event)

    disp_toks: "OrderedDict[int, object]" = OrderedDict()
    # disk-tier staging: evicted-row copies taken at kv_store replay for
    # the hashes the leader's spill queue accepted, consumed by the
    # matching kv_disk_store commit. Bounded: a leader-side disk-write
    # failure orphans its staged rows, and an unbounded dict would leak.
    spill_stage: "OrderedDict[int, dict]" = OrderedDict()
    MAX_STAGE = 1024
    stats = {"prefills": 0, "dispatches": 0, "kv_stores": 0,
             "host_restores": 0}

    while True:
        ev = _recv_frame(sock)
        kind = ev["ev"]
        logger.debug("follower event %s", kind)
        if kind == "__shutdown__":
            break
        if kind == "prefill_unsupported":
            raise NotImplementedError(
                f"leader used an admission path the multihost follower "
                f"cannot replay ({ev.get('path')}, rid={ev.get('rid')}); "
                f"disable disagg onboarding on a multihost engine")
        if kind == "precomputed_admit":
            # wire-plane disagg admission: the leader forwarded the
            # remote prefill's (global-head) KV values; scatter our
            # shard into the same target blocks
            from .block_copy import scatter_blocks_from_host
            core.kv = scatter_blocks_from_host(
                core.kv, list(ev["targets"]), ev["values"],
                core.cfg.kv_block_size)
            stats["precomputed"] = stats.get("precomputed", 0) + 1
            continue
        if kind == "kv_layer_stream":
            # streaming layer-wise disagg admission (llm/kv/stream.py):
            # one event per arrived layer with its (global-head) suffix
            # values — run the same single-layer scatter the leader ran,
            # slicing our shard's heads; device order is preserved
            # because the leader records adjacent to its own scatter
            from .block_copy import scatter_layer_from_host
            core.kv = scatter_layer_from_host(
                core.kv, list(ev["targets"]), int(ev["layer"]),
                ev["values"], core.cfg.kv_block_size)
            stats["layer_streams"] = stats.get("layer_streams", 0) + 1
            continue
        if kind == "handoff_gather":
            # prefill-engine follower: run the leader's handoff gather (a
            # device program — skipping it would deadlock the next
            # collective). For device-plane handoffs (park=True) hold
            # this rank's shard of the gather output in the process
            # bridge so a co-located decode follower can claim it.
            from .block_copy import gather_blocks_dispatch
            stacked = gather_blocks_dispatch(core.kv, list(ev["ids"]),
                                             core.cfg.kv_block_size)
            if ev.get("park"):
                from ..llm.kv_transport import DeviceKvPayload, bridge
                bridge().park(ev["rid"], DeviceKvPayload(
                    # followers never read the token fields — the scatter
                    # consumes only stacked/n_blocks/block_size
                    request_id=ev["rid"], first_token=None,
                    first_logprob=None, seq_hashes=[],
                    stacked=stacked, n_blocks=int(ev["n_blocks"]),
                    block_size=core.cfg.kv_block_size))
            stats["handoff_gathers"] = stats.get("handoff_gathers", 0) + 1
            continue
        if kind == "precomputed_device_admit":
            # decode-engine follower: the payload's arrays never ride the
            # stream — this rank's prefill-engine replica parked its OWN
            # shard in the process bridge ("handoff_gather" park=True);
            # run the same scatter program the leader ran. The prefill
            # replica consumes a DIFFERENT stream, so rendezvous with a
            # bounded wait rather than assuming it already parked.
            from ..llm.kv_transport import bridge, scatter_blocks_device
            deadline = time.monotonic() + 120.0
            payload = bridge().take_parked(ev["rid"])
            while payload is None and time.monotonic() < deadline:
                time.sleep(0.01)
                payload = bridge().take_parked(ev["rid"])
            if payload is None:
                raise ValueError(
                    f"leader admitted a device-plane payload for "
                    f"rid={ev.get('rid')} but nothing was parked in this "
                    f"rank's bridge within 120s — is the prefill engine "
                    f"replica running on this rank with its dispatch "
                    f"stream attached?")
            if ev["targets"]:
                core.kv = scatter_blocks_device(
                    core.kv, list(ev["targets"]), payload,
                    int(ev["skip"]), int(ev["n_needed"]), mesh=core.mesh)
            # else: full prefix hit — claiming (and dropping) the parked
            # shard was the point; nothing to scatter
            stats["precomputed_device"] = (
                stats.get("precomputed_device", 0) + 1)
            continue
        if kind == "kv_store":
            # mirror the leader's offload commit: gather the SAME device
            # blocks from our bit-identical KV, apply the leader's literal
            # hash→slot placements (no LRU policy re-run on followers) —
            # shared with the offline replayer (replay.exec_kv_store_event)
            pool = core.kv_manager.host_pool
            if pool is None:
                raise ValueError(
                    "leader streams host-KV-tier stores but this follower "
                    "was built with host_kv_blocks=0 — ranks must share "
                    "one engine config")
            exec_kv_store_event(core.kv, ev, pool, core.cfg.kv_block_size,
                                spill_stage=spill_stage)
            while len(spill_stage) > MAX_STAGE:
                spill_stage.popitem(last=False)
            stats["kv_stores"] += 1
            continue
        if kind == "kv_disk_store":
            # mirror the leader's disk-tier spill commit: literal
            # placements, bytes from the staged row copies (or the host
            # mirror, for flush-driven spills) — shared with the offline
            # replayer (replay.exec_kv_disk_store_event)
            if core.disk_store is None:
                raise ValueError(
                    "leader streams disk-tier stores but this follower "
                    "was built with kv_disk_blocks=0 — ranks must share "
                    "one engine config (kv_disk_dir is per-rank local)")
            exec_kv_disk_store_event(ev, core.disk_store,
                                     core.kv_manager.host_pool,
                                     spill_stage)
            stats["kv_disk_stores"] = stats.get("kv_disk_stores", 0) + 1
            continue
        if kind == "kv_remote_restore":
            # remote (G4) tier restore: scatter the leader's fetched
            # bytes (or fetch the hashes from OUR remote store when the
            # event omitted them and this rank shares the leader's
            # content-addressed object root) into the same device
            # targets — shared with the offline replayer
            # (replay.exec_kv_remote_restore_event)
            core.kv = exec_kv_remote_restore_event(
                core.kv, ev, core.cfg.kv_block_size,
                remote_store=core.remote_store)
            stats["remote_restores"] = stats.get("remote_restores", 0) + 1
            continue
        if kind == "hit_transfer":
            if (int(ev.get("host_hit", 0)) > 0
                    or int(ev.get("disk_hit", 0)) > 0):
                # replay the leader's h2d restore from the mirror tiers —
                # shared with the offline replayer
                # (replay.exec_host_restore_event)
                pool = core.kv_manager.host_pool
                if int(ev.get("host_hit", 0)) > 0 and (
                        pool is None or pool._arena is None):
                    raise ValueError(
                        "host restore references slots this follower "
                        "never mirrored (no kv_store seen) — the leader "
                        "must attach the stream before any offloads")
                core.kv = exec_host_restore_event(
                    core.kv, ev, pool, core.cfg.kv_block_size,
                    disk_store=core.disk_store)
                stats["host_restores"] += 1
            continue   # device-hit-only: prefix hits reuse resident KV
        if kind == "prefill":
            _tok, core.kv = exec_prefill_event(core, core.kv, ev)
            stats["prefills"] += 1
        elif kind == "prefill_sp":
            _tok, core.kv = exec_sp_prefill_event(core, core.kv, ev)
            stats["prefills"] += 1
        elif kind == "dispatch":
            chain = (disp_toks[ev["chained_from"]]
                     if ev["chained_from"] is not None else None)
            toks_k, core.kv = exec_dispatch_event(core, core.kv, ev, chain)
            disp_toks[ev["id"]] = toks_k
            while len(disp_toks) > max_chain_keep:
                disp_toks.popitem(last=False)
            stats["dispatches"] += 1
        elif kind == "verify":
            # speculative verify (engine/spec/) is a device program —
            # run the identical dispatch; acceptance is leader-side
            # bookkeeping the follower never needs
            _toks, core.kv = exec_verify_event(core, core.kv, ev)
            stats["verifies"] = stats.get("verifies", 0) + 1
        elif kind == "ragged":
            # unified ragged dispatch (engine/ragged.py) is a device
            # program with the same host contract as dispatch/verify —
            # run the identical packing; span bookkeeping (lane
            # consumption, boundary samples, spec acceptance) is
            # leader-side. Pipelined ragged events chain off the
            # previous ragged dispatch's device tokens, so the follower
            # keeps them in the same bounded chain window.
            chain = (disp_toks.get(ev["chained_from"])
                     if ev.get("chained_from") is not None else None)
            if ev.get("chained_from") is not None and chain is None:
                raise NotImplementedError(
                    f"ragged dispatch {ev['id']} chains from "
                    f"{ev['chained_from']} which left the follower's "
                    f"chain window — raise max_chain_keep")
            toks_r, core.kv = exec_ragged_event(core, core.kv, ev,
                                                chain)
            disp_toks[ev["id"]] = toks_r
            while len(disp_toks) > max_chain_keep:
                disp_toks.popitem(last=False)
            stats["ragged"] = stats.get("ragged", 0) + 1
    logger.info("follower done: %s", stats)
    return stats
