"""Fleet KV fabric: peer-to-peer block transfer + latency-aware admission.

The transport half of the G4 remote tier (llm/kv/remotestore.py): every
worker registers a ``kv_fabric`` RPC endpoint next to its serving
endpoint — discovered through the kvstore like any component — that
serves its OWN disk/host-resident KV blocks to the fleet. A worker whose
admission cascade bottoms out locally fetches the prefix from whichever
peer announced it (the same tier-tagged ``kv_events`` the router
consumes feed the hash→holder index), onboards it through the existing
off-thread promote path, and decodes bit-exact vs local recompute —
prefix KV produced anywhere in the fleet is reusable everywhere
(FlowKV, arXiv:2504.03775, low-latency disaggregated KV transfer).

What makes it production-shaped rather than a dumb cache:

- :class:`PeerLinkTable` — measured link-cost tables: each peer is
  probed at attach (RTT + bandwidth) and every real transfer updates a
  decay-averaged estimate, so the model tracks the link the fleet
  actually has, not a config constant (tools/bandwidth_model.py holds
  the analytic anchors this extends).
- :class:`AdmissionGate` — promote a remote hit only when the modeled
  fetch time (RTT + bytes/bandwidth) beats the modeled recompute time
  (prefix depth / measured prefill rate). A remote hit slower than
  re-prefilling is reported as a miss and the engine recomputes.
- NetKV-style router scoring (kv_router/scoring.py, arXiv:2606.03910)
  consumes the same link model via ForwardPassMetrics ``remote_link_*``:
  decode-instance selection subtracts modeled transfer cost from
  tier-discounted overlap instead of chasing overlap depth alone.

Wire format: blocks travel as the self-describing npz bytes of
remotestore.pack_block_bytes over the NATIVE data plane — the request
plane carries only a small ``fetch_native`` control message naming the
hashes and a dial-back address; the serving peer then streams each
block as one length-prefixed two-part frame (csrc/data_plane.cpp via
runtime/tcp.open_stream_sender: framing + socket writes on a dedicated
C++ thread, falling through to the pure-asyncio sender with identical
frames when the toolchain is missing) and the fetching side unpacks the
raw frame bytes off its event loop. When the native library is absent
on the serving peer it declines and the fetch gracefully falls back to
the legacy base64-over-JSON ``fetch`` op (counted in
``dataplane_fallbacks_total``) — the block payload is byte-identical on
both paths by construction (tests/test_kv_fabric.py differential).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ...runtime.codec import ConnectionInfo, FrameKind
from ...runtime.engine import AsyncEngine, Context, ManyOut, ResponseStream
from .remotestore import (RemoteKvStore, pack_block_bytes,
                          unpack_block_bytes)

logger = logging.getLogger("dynamo_tpu.kv.fabric")

__all__ = ["FABRIC_ENDPOINT", "LinkStats", "PeerLinkTable", "AdmissionGate",
           "PrefillRateEstimator", "KvFabricServer", "KvFabric",
           "CircuitBreaker", "dataplane_serving_available"]

FABRIC_ENDPOINT = "kv_fabric"
PROBE_BYTES = 256 * 1024
# ops/test lever: DYN_KV_FABRIC_DATAPLANE=0 forces the JSON fallback on
# both sides (the differential test drives each path deliberately)
DATAPLANE_ENV = "DYN_KV_FABRIC_DATAPLANE"


def dataplane_serving_available() -> bool:
    """Whether THIS process serves native-dataplane fetches: the env
    gate is on (the C++ data plane, csrc/data_plane.cpp, then loads or
    raises). A peer with the gate off declines ``fetch_native`` and the
    fetching side takes the JSON path."""
    if os.environ.get(DATAPLANE_ENV, "1") == "0":
        return False
    from ...runtime.native_tcp import load_data_plane_lib
    load_data_plane_lib()
    return True


# ---------------------------------------------------------------------------
# Link-cost model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LinkStats:
    """Decay-averaged link estimate for one peer (or the object store)."""

    rtt_s: float = 1e-3
    gbps: float = 1.0
    samples: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class CircuitBreaker:
    """Per-peer circuit breaker: consecutive-failure / latency-SLO trip
    → open (the peer earns NO fetch traffic, NO admission-gate credit)
    → half-open after ``cooldown_s`` (exactly ONE trial fetch allowed)
    → closed on trial success, re-opened on trial failure.

    Why latency trips too: a browning-out peer — alive enough to answer
    probes, slow enough to lose to recompute — never produces a hard
    failure, yet every fetch routed to it burns the caller's TTFT. When
    ``latency_slo_s`` is set, ``failure_threshold`` consecutive
    transfers slower than the SLO trip the breaker exactly like errors.

    ``now`` is injectable (tests, the virtual-clock sim) — the breaker
    never reads a clock the caller didn't choose."""

    def __init__(self, failure_threshold: int = 3,
                 cooldown_s: float = 30.0,
                 latency_slo_s: Optional[float] = None,
                 now=time.monotonic):
        self.failure_threshold = int(failure_threshold)
        self.cooldown_s = float(cooldown_s)
        self.latency_slo_s = latency_slo_s
        self._now = now
        self.state = "closed"             # closed | open | half_open
        self.consecutive_failures = 0
        self.slow_streak = 0
        self.trips_total = 0
        self._opened_at = 0.0
        self._trial_inflight = False

    def _trip(self) -> None:
        if self.state != "open":
            self.trips_total += 1
        self.state = "open"
        self._opened_at = self._now()
        self._trial_inflight = False

    def _refresh(self) -> None:
        if (self.state == "open"
                and self._now() - self._opened_at >= self.cooldown_s):
            self.state = "half_open"      # cooldown elapsed: probe-able
            self._trial_inflight = False

    def would_allow(self) -> bool:
        """Pure check (pricing/holder filtering): could a fetch be
        routed here right now? Never consumes the half-open trial slot."""
        self._refresh()
        if self.state == "closed":
            return True
        if self.state == "open":
            return False
        return not self._trial_inflight   # half-open: one trial at a time

    def allow(self) -> bool:
        """Consuming check (the fetch path): like :meth:`would_allow`,
        but a half-open True CLAIMS the single trial slot — released by
        record_success/record_failure."""
        if not self.would_allow():
            return False
        if self.state == "half_open":
            self._trial_inflight = True
        return True

    def record_success(self, latency_s: Optional[float] = None) -> None:
        self._trial_inflight = False
        self.consecutive_failures = 0
        if (self.latency_slo_s is not None and latency_s is not None
                and latency_s > self.latency_slo_s):
            # "success" slower than the SLO is a brownout datapoint, not
            # a recovery — streaks of them trip exactly like failures
            self.slow_streak += 1
            if self.state == "half_open":
                self._trip()              # trial was too slow: back off
            elif self.slow_streak >= self.failure_threshold:
                self._trip()
            return
        self.slow_streak = 0
        if self.state in ("half_open", "open"):
            self.state = "closed"         # half-open trial passed
        # closed stays closed — success never flaps state (hysteresis)

    def record_failure(self) -> None:
        self._trial_inflight = False
        self.consecutive_failures += 1
        if self.state == "half_open":
            self._trip()                  # trial failed: full cooldown again
        elif self.consecutive_failures >= self.failure_threshold:
            self._trip()

    def describe(self) -> dict:
        return {"state": self.state,
                "consecutive_failures": self.consecutive_failures,
                "slow_streak": self.slow_streak,
                "trips_total": self.trips_total}


class PeerLinkTable:
    """Measured per-peer link costs. Probed once at attach, then every
    real transfer folds into an exponential moving average (alpha 0.3:
    responsive to a changed path, stable against one slow batch).

    Every peer also carries a :class:`CircuitBreaker`: tripped peers are
    skipped by ``link_for_holders`` (their holdings price as a dead link
    → the admission gate rejects → the engine recomputes), which is how
    a browning-out peer loses NetKV routing credit without any central
    coordination."""

    ALPHA = 0.3

    def __init__(self, default_gbps: float = 1.0,
                 default_rtt_s: float = 1e-3,
                 breaker_failure_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 breaker_latency_slo_s: Optional[float] = None,
                 now=time.monotonic):
        self.default = LinkStats(rtt_s=default_rtt_s, gbps=default_gbps)
        self._links: Dict[int, LinkStats] = {}
        self._now = now
        self._breaker_kw = dict(
            failure_threshold=breaker_failure_threshold,
            cooldown_s=breaker_cooldown_s,
            latency_slo_s=breaker_latency_slo_s)
        self._breakers: Dict[int, CircuitBreaker] = {}
        # a LinkStats with no bandwidth: what a fully-tripped holder set
        # prices as (modeled fetch = inf → the gate always rejects)
        self._dead = LinkStats(rtt_s=float("inf"), gbps=0.0)

    def get(self, worker_id: Optional[int]) -> LinkStats:
        if worker_id is None:
            return self.default
        return self._links.get(worker_id, self.default)

    def _entry(self, worker_id: int) -> LinkStats:
        link = self._links.get(worker_id)
        if link is None:
            link = LinkStats(rtt_s=self.default.rtt_s,
                             gbps=self.default.gbps)
            self._links[worker_id] = link
        return link

    def observe_rtt(self, worker_id: int, rtt_s: float) -> None:
        link = self._entry(worker_id)
        if link.samples == 0:
            link.rtt_s = rtt_s
        else:
            link.rtt_s += self.ALPHA * (rtt_s - link.rtt_s)
        link.samples += 1

    def observe_transfer(self, worker_id: int, nbytes: int,
                         seconds: float) -> None:
        if seconds <= 0 or nbytes <= 0:
            return
        link = self._entry(worker_id)
        gbps = nbytes / seconds / 1e9
        if link.samples == 0:
            link.gbps = gbps
        else:
            link.gbps += self.ALPHA * (gbps - link.gbps)
        link.samples += 1

    def drop(self, worker_id: int) -> None:
        self._links.pop(worker_id, None)
        self._breakers.pop(worker_id, None)

    # ------------------------------------------------------ circuit breaker
    def breaker(self, worker_id: int) -> CircuitBreaker:
        b = self._breakers.get(worker_id)
        if b is None:
            b = CircuitBreaker(now=self._now, **self._breaker_kw)
            self._breakers[worker_id] = b
        return b

    def usable(self, worker_id: int) -> bool:
        """False while the peer's breaker is open (and not yet due for a
        half-open trial) — the RemoteKvStore.peer_usable plug. Pure:
        never claims the half-open trial slot (the fetch path does)."""
        return self.breaker(worker_id).would_allow()

    def record_success(self, worker_id: int,
                       latency_s: Optional[float] = None) -> None:
        self.breaker(worker_id).record_success(latency_s)

    def record_failure(self, worker_id: int) -> None:
        self.breaker(worker_id).record_failure()

    def open_breaker_count(self) -> int:
        return sum(1 for b in self._breakers.values()
                   if b.state != "closed")

    def breaker_trips_total(self) -> int:
        return sum(b.trips_total for b in self._breakers.values())

    def breaker_snapshot(self) -> Dict[int, dict]:
        return {wid: b.describe() for wid, b in self._breakers.items()}

    def link_for_holders(self, holders: Sequence[Sequence[int]]) -> LinkStats:
        """The link the fetch of a matched run would ride: the first
        UNTRIPPED peer holder's measured link, the object-store default
        when every block is object-held, or a dead link (gbps=0 →
        modeled fetch inf → the gate rejects) when every holder's
        breaker is open — a browning-out peer's blocks price like a
        miss, so the engine recomputes instead of waiting it out."""
        any_peer = False
        for hs in holders:
            for wid in hs:
                any_peer = True
                b = self._breakers.get(wid)
                if b is None or b.would_allow():
                    return self.get(wid)
        return self._dead if any_peer else self.default

    def avg_gbps(self) -> float:
        if not self._links:
            return self.default.gbps
        return sum(l.gbps for l in self._links.values()) / len(self._links)

    def avg_rtt_s(self) -> float:
        if not self._links:
            return self.default.rtt_s
        return sum(l.rtt_s for l in self._links.values()) / len(self._links)

    def snapshot(self) -> Dict[int, dict]:
        return {wid: l.to_dict() for wid, l in self._links.items()}


# ---------------------------------------------------------------------------
# Latency-aware admission
# ---------------------------------------------------------------------------


class PrefillRateEstimator:
    """Age-weighted measured prefill rate (ROADMAP KV-fabric item (c)):
    the admission gate's recompute side.

    A cumulative tokens/wall ratio is the wrong estimator on a YOUNG
    engine: the first prefill admissions include XLA compilation, so
    their rate is 10-100x below steady state and a cumulative mean stays
    skewed for thousands of admissions — making modeled recompute look
    expensive and over-admitting remote fetches that lose to a warmed-up
    recompute. This estimator

    - EXCLUDES the first ``warmup_samples`` admissions outright (while
      young it reports 0.0 — "rate unknown", which the gate and the
      router's NetKV model already treat as admit-optimistically, the
      correct posture for a cold engine), and
    - decay-averages per-admission rates afterwards (EMA, same alpha
      discipline as PeerLinkTable), so one anomalous admission — a GC
      pause, a host stall — washes out instead of anchoring the price.
    """

    def __init__(self, warmup_samples: int = 2, alpha: float = 0.3):
        self.warmup_samples = int(warmup_samples)
        self.alpha = float(alpha)
        self.samples = 0
        self.warmup_skipped = 0
        self._rate = 0.0

    def observe(self, tokens: int, wall_s: float) -> None:
        if tokens <= 0 or wall_s <= 0:
            return
        self.samples += 1
        if self.samples <= self.warmup_samples:
            self.warmup_skipped += 1
            return
        r = tokens / wall_s
        if self._rate <= 0:
            self._rate = r
        else:
            self._rate += self.alpha * (r - self._rate)

    def rate(self) -> float:
        """tok/s estimate; 0.0 until warmup passes (unknown → the gate
        admits, matching the tiers' optimistic cold behavior)."""
        return self._rate


class AdmissionGate:
    """Promote a remote hit only when the modeled fetch beats the modeled
    recompute at that depth.

    - fetch(n)     = rtt + n · bytes_per_block / bandwidth
    - recompute(n) = n · block_size / prefill_tok_per_s

    ``prefill_tok_per_s`` is a callable so the gate tracks the engine's
    MEASURED prefill rate (EngineCore.measured_prefill_tok_per_s), not a
    spec-sheet constant; before the first prefill lands (rate unknown)
    the gate admits — the tiers below make the same optimistic choice.
    ``mode``: "auto" (the model), "always" / "never" (ops overrides,
    also the test escape hatch)."""

    def __init__(self, bytes_per_block: int, block_size: int,
                 prefill_tok_per_s, mode: str = "auto"):
        if mode not in ("auto", "always", "never"):
            raise ValueError(f"unknown admission mode {mode!r}")
        self.bytes_per_block = int(bytes_per_block)
        self.block_size = int(block_size)
        self._prefill_rate = prefill_tok_per_s
        self.mode = mode
        self.accepts_total = 0
        self.rejects_total = 0

    def prefill_tok_per_s(self) -> float:
        rate = self._prefill_rate
        return float(rate() if callable(rate) else rate)

    def modeled_fetch_s(self, n_blocks: int, link: LinkStats) -> float:
        if link.gbps <= 0:
            return float("inf")
        return link.rtt_s + n_blocks * self.bytes_per_block / (link.gbps
                                                               * 1e9)

    def modeled_fetch_overlap_s(self, n_blocks: int, link: LinkStats,
                                n_layers: int,
                                hidden_compute_s: float = 0.0) -> float:
        """Overlap-aware fetch model (llm/kv/stream.py): when the bytes
        arrive as a per-layer stream the consumer scatters layer l while
        layer l+1 is on the wire, so only max(serial/L, serial − hidden)
        of the transfer is EXPOSED on the critical path. n_layers ≤ 1
        (monolithic payload) degrades to modeled_fetch_s exactly."""
        if link.gbps <= 0:
            return float("inf")
        from .stream import exposed_transfer_s
        serial = n_blocks * self.bytes_per_block / (link.gbps * 1e9)
        return link.rtt_s + exposed_transfer_s(serial, n_layers,
                                               hidden_compute_s)

    def modeled_recompute_s(self, n_blocks: int) -> float:
        rate = self.prefill_tok_per_s()
        if rate <= 0:
            return float("inf")          # unknown rate: admit (see class doc)
        return n_blocks * self.block_size / rate

    def admit(self, n_blocks: int, link: LinkStats) -> bool:
        if self.mode == "always":
            self.accepts_total += 1
            return True
        if self.mode == "never":
            self.rejects_total += 1
            return False
        ok = (self.modeled_fetch_s(n_blocks, link)
              < self.modeled_recompute_s(n_blocks))
        if ok:
            self.accepts_total += 1
        else:
            self.rejects_total += 1
        return ok

    def crossover_blocks(self, link: LinkStats) -> float:
        """Smallest hit depth (blocks) at which the fetch starts paying:
        rtt / (per-block recompute − per-block transfer). inf when the
        link's per-block cost never beats recompute."""
        rate = self.prefill_tok_per_s()
        if rate <= 0:
            return 0.0                   # unknown rate: everything admits
        if link.gbps <= 0:
            return float("inf")
        per_block_gain = (self.block_size / rate
                          - self.bytes_per_block / (link.gbps * 1e9))
        if per_block_gain <= 0:
            return float("inf")
        return link.rtt_s / per_block_gain

    def crossover_blocks_overlap(self, link: LinkStats,
                                 n_layers: int) -> float:
        """crossover_blocks under the streaming bound: with L layers
        pipelined, the exposed per-block transfer is 1/L of the serial
        cost (the other L−1 frames hide under the consumer's scatter),
        so the fetch starts paying at a SHALLOWER depth. n_layers ≤ 1
        degrades to crossover_blocks exactly."""
        rate = self.prefill_tok_per_s()
        if rate <= 0:
            return 0.0                   # unknown rate: everything admits
        if link.gbps <= 0:
            return float("inf")
        layers = max(int(n_layers), 1)
        per_block_gain = (self.block_size / rate
                          - self.bytes_per_block / (link.gbps * 1e9)
                          / layers)
        if per_block_gain <= 0:
            return float("inf")
        return link.rtt_s / per_block_gain


# ---------------------------------------------------------------------------
# RPC plane: per-worker kv_fabric endpoint
# ---------------------------------------------------------------------------


class KvFabricServer(AsyncEngine):
    """Serves THIS worker's disk/host-resident blocks to the fleet.

    Ops (request = one JSON dict, response = one JSON dict):
    - ``probe``: echo ``nbytes`` of payload — the client times the round
      trip to measure RTT (nbytes=0) and bandwidth (nbytes large).
    - ``match``: which of ``hashes`` this worker can serve.
    - ``fetch_native``: the DEFAULT block transport — the request names
      the hashes plus the caller's dial-back ``conn`` (its process
      stream server, runtime/tcp.TcpStreamServer); the blocks stream
      back as raw length-prefixed two-part frames on the native data
      plane (csrc/data_plane.cpp), one DATA frame per block with the
      hash in the JSON header and the npz bytes as the data part —
      no base64, no JSON in the bulk path. A peer without the native
      lib (or with DYN_KV_FABRIC_DATAPLANE=0) declines with
      ``fallback`` and the caller retries over ``fetch``.
    - ``fetch``: the JSON fallback — packed npz, base64-framed in the
      response dict. Byte-identical payloads to the native path.

    Missing hashes are reported, never fatal — the caller recomputes.
    File reads and frame unpacks run off-thread; the serving loop never
    blocks on I/O (the disk tier's loop-stall contract extended to
    serving peers)."""

    def __init__(self, core):
        self.core = core
        self.fetches_served = 0
        self.blocks_served = 0
        self.probes_served = 0
        self.dataplane_fetches_served = 0

    def _read_block(self, seq_hash: int) -> Optional[bytes]:
        """One packed block from the coldest-first local tiers (runs in a
        worker thread)."""
        disk = self.core.disk_store
        if disk is not None and disk.contains(seq_hash):
            disk.pin([seq_hash])
            try:
                stacked = disk.fetch([seq_hash])
            except KeyError:
                return None
            finally:
                disk.unpin([seq_hash])
            e = next((en for en in disk.registered_entries()
                      if en[0] == seq_hash), (seq_hash, None, None))
            values = {k: v[:, :, 0] for k, v in stacked.items()}
            return pack_block_bytes(values, e[1], e[2])
        host = self.core.kv_manager.host_pool
        if host is not None and host.contains(seq_hash):
            slot = host._by_hash.get(seq_hash)
            if slot is None:
                return None
            host.pin([slot])
            try:
                values = host.row_copy(slot)
            finally:
                host.unpin([slot])
            th, ph = host.meta_for(seq_hash)
            return pack_block_bytes(values, th, ph)
        return None

    def _serveable(self, seq_hash: int) -> bool:
        disk = self.core.disk_store
        host = self.core.kv_manager.host_pool
        return ((disk is not None and disk.contains(seq_hash))
                or (host is not None and host.contains(seq_hash)))

    def _read_all(self, hashes: Sequence[int]):
        """Packed bytes per hash (worker thread) → ({hash: bytes},
        [missing]). Shared by both transports — byte-identical payloads
        by construction."""
        blocks, missing = {}, []
        for h in hashes:
            data = self._read_block(h)
            if data is None:
                missing.append(h)
            else:
                blocks[h] = data
        return blocks, missing

    async def _stream_native(self, conn: dict, hashes: Sequence[int],
                             blocks: Dict[int, bytes]) -> bool:
        """Dial the caller back and stream one two-part frame per block
        over the native data plane (open_stream_sender picks the C++
        sender; identical frames from the asyncio sender otherwise).
        Returns False when the dial-back itself failed — the caller
        falls back to the JSON path; a mid-stream failure surfaces to
        the caller as a torn stream (→ recompute), never an error."""
        from ...runtime.faults import hit_async as _fault
        from ...runtime.faults import mangle as _mangle
        from ...runtime.tcp import open_stream_sender
        try:
            await _fault("fabric.dialback", exc=ConnectionError)
            sender = await open_stream_sender(
                ConnectionInfo.from_dict(conn), timeout=5.0)
        except Exception:  # noqa: BLE001 — caller's server unreachable
            logger.warning("fabric dataplane dial-back to %s failed",
                           conn.get("address"), exc_info=True)
            return False
        try:
            for h in hashes:
                # torn-frame chaos site: truncated npz bytes must surface
                # on the fetching side as a failed unpack → recompute
                await sender.send(_mangle("dataplane.frame", blocks[h]),
                                  header=json.dumps({"h": int(h)}).encode())
            await sender.finish()
        except Exception as e:  # noqa: BLE001 — torn stream: caller recomputes
            logger.warning("fabric dataplane stream failed mid-fetch: %s", e)
            try:
                await sender.finish(error=str(e))
            except Exception:  # noqa: BLE001
                pass
        return True

    async def _probe_stream(self, conn: dict, nbytes: int) -> bool:
        """Dial the prober back and stream ``nbytes`` of payload over
        the native data plane — the SAME path fetches ride, so the
        measured bandwidth prices the transfers that will actually
        happen (the request-plane echo measured the wrong path once
        dataplane fetch was the default). False = dial-back failed →
        the prober falls back to the request-plane echo."""
        from ...runtime.tcp import open_stream_sender
        try:
            sender = await open_stream_sender(
                ConnectionInfo.from_dict(conn), timeout=5.0)
        except Exception:  # noqa: BLE001 — prober's server unreachable
            logger.warning("fabric probe dial-back to %s failed",
                           conn.get("address"), exc_info=True)
            return False
        chunk = bytes(min(max(nbytes, 1), 1 << 18))
        sent = 0
        try:
            while sent < nbytes:
                part = chunk[:nbytes - sent] if nbytes - sent < len(chunk) \
                    else chunk
                await sender.send(part, header=b"{}")
                sent += len(part)
            await sender.finish()
        except Exception as e:  # noqa: BLE001 — torn probe: prober times out
            logger.warning("fabric probe stream failed: %s", e)
            try:
                await sender.finish(error=str(e))
            except Exception:  # noqa: BLE001
                pass
        return True

    async def _handle(self, d: dict) -> dict:
        import base64
        op = d.get("op")
        if op == "probe":
            self.probes_served += 1
            n = int(d.get("nbytes", 0))
            return {"ok": True, "payload": "0" * n}
        if op == "probe_native":
            # bandwidth probe over the native data plane (the path
            # fetches ride); decline → request-plane echo fallback
            self.probes_served += 1
            if not await asyncio.to_thread(dataplane_serving_available):
                return {"ok": True, "fallback": "json"}
            n = int(d.get("nbytes", 0))
            if not await self._probe_stream(d.get("conn") or {}, n):
                return {"ok": True, "fallback": "json"}
            return {"ok": True, "dataplane": True, "nbytes": n}
        if op == "match":
            hashes = [int(h) for h in d.get("hashes", [])]
            return {"ok": True,
                    "resident": [self._serveable(h) for h in hashes]}
        if op in ("fetch", "fetch_native"):
            hashes = [int(h) for h in d.get("hashes", [])]
            native = (op == "fetch_native")
            if native and not await asyncio.to_thread(
                    dataplane_serving_available):
                # lib absent / env-gated: decline, the caller rides JSON
                return {"ok": True, "fallback": "json"}

            # the requesting worker forwarded its request's TraceContext:
            # serve the fetch under a CHILD trace so the peer-side read
            # lands in the same fleet tree the collector assembles
            from ...runtime.tracing import Trace, use_trace
            tctx = d.get("trace")
            if tctx:
                with use_trace(Trace.from_wire(
                        tctx, tctx.get("trace_id", "?"),
                        role="kv_peer")) as ptrace:
                    with ptrace.span("fabric.fetch", blocks=len(hashes),
                                     dataplane=native):
                        blocks, missing = await asyncio.to_thread(
                            self._read_all, hashes)
                    if missing:
                        ptrace.event("fabric.missing", n=len(missing))
            else:
                blocks, missing = await asyncio.to_thread(
                    self._read_all, hashes)
            if missing:
                # caller recomputes; nothing streams (native included)
                return {"ok": True, "blocks": {}, "missing": missing}
            if native:
                if not await self._stream_native(d.get("conn") or {},
                                                 hashes, blocks):
                    return {"ok": True, "fallback": "json"}
                self.fetches_served += 1
                self.dataplane_fetches_served += 1
                self.blocks_served += len(blocks)
                return {"ok": True, "dataplane": True,
                        "blocks": len(blocks), "missing": []}
            self.fetches_served += 1
            self.blocks_served += len(blocks)
            # bulk base64 is CPU work — encode off the serving loop
            enc = await asyncio.to_thread(
                lambda: {str(h): base64.b64encode(b).decode()
                         for h, b in blocks.items()})
            return {"ok": True, "blocks": enc, "missing": []}
        return {"ok": False, "error": f"unknown fabric op {op!r}"}

    async def generate(self, request) -> ManyOut:
        resp = await self._handle(request.data)
        return ResponseStream.from_iterable([resp], request.ctx)

    def stats(self) -> dict:
        return {"fabric_fetches_served": self.fetches_served,
                "fabric_blocks_served": self.blocks_served}


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------


class KvFabric:
    """One worker's view of the fleet KV fabric.

    ``attach`` wires the whole thing: serve our ``kv_fabric`` endpoint,
    start the peer client (discovery-watched like any component),
    subscribe the component's ``kv_events`` to feed the hash→holder
    index, probe every live peer for its link cost, and hand the engine
    a :class:`RemoteKvStore` that sits behind the existing
    KvBlockManager cascade."""

    FETCH_TIMEOUT_S = 60.0

    def __init__(self, store: RemoteKvStore, links: PeerLinkTable,
                 gate: AdmissionGate, worker_id: Optional[int] = None,
                 runtime=None):
        self.store = store
        self.links = links
        self.gate = gate
        self.worker_id = worker_id
        self.server: Optional[KvFabricServer] = None
        self.client = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._runtime = runtime       # dial-back stream server for fetches
        self._sub = None
        self._tasks: List[asyncio.Task] = []
        self._known_peers: set = set()
        self.peer_fetches_total = 0
        # native-dataplane fetch accounting (the nv_llm_kv_remote_
        # dataplane_* gauge feeds): fallbacks = fetches that had to ride
        # the JSON path because the peer declined (lib absent/env off)
        self.dataplane_fetches_total = 0
        self.dataplane_fallbacks_total = 0
        # probes that had to ride the request-plane echo because the
        # peer declined the native-dataplane probe (ROADMAP PaaS ext.)
        self.probe_fallbacks_total = 0
        self.use_dataplane = os.environ.get(DATAPLANE_ENV, "1") != "0"
        store.peer_fetch = self.fetch_sync
        store.admission = self._admit
        # circuit breaker (docs/chaos.md): tripped peers vanish from the
        # store's holder view, so their matched runs fall through to
        # recompute instead of waiting out a browning-out link
        store.peer_usable = links.usable

    # ------------------------------------------------------------ wiring
    @classmethod
    async def attach(cls, core, runtime, endpoint,
                     default_gbps: float = 1.0,
                     probe_peers: bool = True) -> "KvFabric":
        """Build + wire a fabric for ``core`` next to its serving
        ``endpoint`` (the fabric endpoint shares the component:
        ``dyn://{ns}/{comp}/kv_fabric``)."""
        component = runtime.namespace(endpoint.namespace).component(
            endpoint.component)
        fabric_ep = component.endpoint(FABRIC_ENDPOINT)

        store = core.remote_store
        if store is None:
            store = RemoteKvStore()       # peer-only fabric (no object dir)
        links = PeerLinkTable(default_gbps=default_gbps)
        gate = AdmissionGate(
            bytes_per_block=core.kv_bytes_per_block(),
            block_size=core.cfg.kv_block_size,
            prefill_tok_per_s=core.measured_prefill_tok_per_s,
            mode=core.cfg.kv_remote_admission)
        self = cls(store, links, gate, runtime=runtime)
        self._loop = asyncio.get_running_loop()

        # serve our blocks to the fleet
        self.server = KvFabricServer(core)
        await fabric_ep.serve(self.server,
                              decode_req=lambda raw: json.loads(raw))
        lease = await runtime.primary_lease()
        self.worker_id = lease.id

        # peer client over the same endpoint's discovery prefix
        self.client = fabric_ep.client()
        self.client.on_instances_changed = self._instances_changed
        await self.client.start()
        self._known_peers = {wid for wid in self.client.instance_ids()
                             if wid != self.worker_id}

        # hash→holder feed: the same tier-tagged kv_events the router eats
        self._sub = await component.subscribe_event("kv_events")
        self._tasks.append(asyncio.get_running_loop().create_task(
            self._event_loop(), name="kv-fabric-events"))

        core.attach_kv_fabric(self)
        if probe_peers:
            for wid in list(self._known_peers):
                try:
                    await self.probe(wid)
                except Exception:  # noqa: BLE001 — a dark peer is not fatal
                    logger.warning("fabric probe of peer %x failed", wid)
        logger.info("kv fabric attached: worker %s, %d live peer(s)",
                    f"{self.worker_id:x}" if self.worker_id else "?",
                    len(self._known_peers))
        return self

    def _instances_changed(self, present: set) -> None:
        present = {wid for wid in present if wid != self.worker_id}
        for gone in self._known_peers - present:
            self.store.forget_peer(gone)
            self.links.drop(gone)
        new = present - self._known_peers
        self._known_peers = present
        for wid in new:
            # probe the newcomer off the watch callback
            t = asyncio.get_running_loop().create_task(
                self._probe_safe(wid), name=f"kv-fabric-probe-{wid:x}")
            self._tasks.append(t)

    async def _probe_safe(self, wid: int) -> None:
        try:
            await self.probe(wid)
        except Exception:  # noqa: BLE001
            logger.warning("fabric probe of new peer %x failed", wid)

    async def _event_loop(self) -> None:
        from ..kv_router.protocols import RouterEvent
        async for msg in self._sub:
            try:
                ev = RouterEvent.from_dict(json.loads(msg.payload))
            except Exception:  # noqa: BLE001
                continue
            if ev.worker_id == self.worker_id or ev.worker_id < 0:
                continue
            if ev.stored is not None:
                # only tiers the peer's fabric server can actually serve
                if getattr(ev.stored, "tier", "device") in ("host", "disk"):
                    self.store.note_peer_stored(ev.worker_id,
                                                ev.stored.block_hashes)
            if ev.removed is not None:
                self.store.note_peer_removed(ev.worker_id,
                                             ev.removed.block_hashes)

    # -------------------------------------------------------------- probes
    RPC_TIMEOUT_S = 15.0

    async def _call(self, worker_id: int, payload: dict,
                    trace_ctx: Optional[dict] = None) -> dict:
        # explicit propagation (metadata override in runtime/egress.py):
        # this coroutine runs off the request's async chain, so the
        # request's trace identity arrives by value, not contextvar
        ctx = Context(payload,
                      metadata={"trace_context": trace_ctx}
                      if trace_ctx else None)

        async def call_once() -> dict:
            stream = await self.client.direct(ctx, worker_id)
            async for item in stream:
                if not item.get("ok"):
                    raise RuntimeError(item.get("error",
                                                "fabric call failed"))
                return item
            raise RuntimeError(
                "fabric peer closed the stream without a reply")

        # bounded: a partitioned peer must fail this worker's admission
        # in RPC_TIMEOUT_S, not hold the onboard path for the transport
        # stack's worst case (chaos contract: no unbounded fabric await)
        try:
            return await asyncio.wait_for(call_once(), self.RPC_TIMEOUT_S)
        except (asyncio.TimeoutError, TimeoutError):
            raise RuntimeError(
                f"fabric call to peer {worker_id:x} timed out after "
                f"{self.RPC_TIMEOUT_S:.0f}s (partitioned?)") from None

    async def _probe_native(self, worker_id: int,
                            nbytes: int) -> Optional[tuple]:
        """Bandwidth probe over the native data plane — the SAME path
        fetches ride (csrc/data_plane.cpp), so the measured gbps prices
        real transfers instead of the request-plane JSON hop. Returns
        (bytes_received, wall_s) or None when the peer declined (lib
        absent / env off) or we have no dial-back server — the caller
        falls back to the request-plane echo."""
        rt = self._runtime
        if rt is None or not self.use_dataplane:
            return None
        await rt.tcp.start()
        rx = rt.tcp.register()
        try:
            t0 = time.monotonic()
            r = await self._call(worker_id, {
                "op": "probe_native", "nbytes": int(nbytes),
                "conn": rt.tcp.connection_info(rx).to_dict()})
            if not r.get("dataplane"):
                return None               # peer declined → echo fallback
            got = 0
            loop = asyncio.get_running_loop()
            deadline = loop.time() + self.RPC_TIMEOUT_S
            while True:
                f = await rx.next_frame(
                    timeout=max(deadline - loop.time(), 0.001))
                if f is None or f.kind == FrameKind.ERROR:
                    raise RuntimeError(
                        f"dataplane probe of peer {worker_id:x} tore")
                if f.kind == FrameKind.SENTINEL:
                    break
                if f.kind == FrameKind.DATA:
                    got += len(f.data)
            return got, time.monotonic() - t0
        finally:
            rx.close()
            rt.tcp.unregister(rx.stream_id)

    async def probe(self, worker_id: int,
                    nbytes: int = PROBE_BYTES) -> LinkStats:
        """Measure the peer's link at attach: a zero-payload round trip
        for RTT, then a bulk transfer for bandwidth — over the NATIVE
        data plane by default (the path fetches actually ride; ROADMAP
        PaaS extension), falling back to the request-plane echo when
        either side lacks the native lib. Decay-averaged into the link
        table (later real transfers keep refining it)."""
        t0 = time.monotonic()
        await self._call(worker_id, {"op": "probe", "nbytes": 0})
        rtt = time.monotonic() - t0
        self.links.observe_rtt(worker_id, rtt)
        native = None
        try:
            native = await self._probe_native(worker_id, nbytes)
        except Exception:  # noqa: BLE001 — torn probe: echo still works
            logger.warning("native dataplane probe of peer %x failed; "
                           "falling back to request-plane echo",
                           worker_id, exc_info=True)
        if native is not None:
            got, dt = native
            # the control RPC's round trip rides inside dt — subtract
            # the measured rtt so the estimate reflects the stream
            self.links.observe_transfer(worker_id, got,
                                        max(dt - rtt, 1e-6))
            return self.links.get(worker_id)
        self.probe_fallbacks_total += 1
        t0 = time.monotonic()
        r = await self._call(worker_id, {"op": "probe", "nbytes": nbytes})
        dt = time.monotonic() - t0
        got = len(r.get("payload", ""))
        self.links.observe_transfer(worker_id, got, dt)
        return self.links.get(worker_id)

    # ------------------------------------------------------------- fetches
    async def _fetch_blobs_native(self, worker_id: int,
                                  seq_hashes: Sequence[int],
                                  trace_ctx: Optional[dict] = None
                                  ) -> Optional[List[bytes]]:
        """Native-dataplane fetch: register a dial-back stream on this
        process's TcpStreamServer, send the control RPC, drain one
        two-part frame per block. Returns the packed bytes in request
        order; None when the peer DECLINED (lib absent / env off — the
        caller falls back to JSON); KeyError on missing hashes or a
        torn/timed-out stream (the caller recomputes)."""
        rt = self._runtime
        if rt is None:
            return None
        await rt.tcp.start()
        rx = rt.tcp.register()
        try:
            payload = {"op": "fetch_native",
                       "hashes": [int(h) for h in seq_hashes],
                       "conn": rt.tcp.connection_info(rx).to_dict()}
            if trace_ctx:
                payload["trace"] = trace_ctx
            r = await self._call(worker_id, payload, trace_ctx=trace_ctx)
            if r.get("missing"):
                raise KeyError(f"peer {worker_id:x} no longer holds "
                               f"{len(r['missing'])} requested block(s)")
            if not r.get("dataplane"):
                return None               # peer declined → JSON fallback
            by_hash: Dict[int, bytes] = {}
            loop = asyncio.get_running_loop()
            deadline = loop.time() + self.FETCH_TIMEOUT_S
            while True:
                f = await rx.next_frame(
                    timeout=max(deadline - loop.time(), 0.001))
                if f is None:
                    raise KeyError(
                        f"dataplane fetch from peer {worker_id:x} timed "
                        f"out after {self.FETCH_TIMEOUT_S:.0f}s")
                if f.kind == FrameKind.DATA:
                    by_hash[int(f.header_json()["h"])] = f.data
                elif f.kind == FrameKind.SENTINEL:
                    break
                elif f.kind == FrameKind.ERROR:
                    raise KeyError(
                        f"dataplane fetch from peer {worker_id:x} tore "
                        f"mid-stream: "
                        f"{f.header_json().get('error', 'stream error')}")
            try:
                blobs = [by_hash[int(h)] for h in seq_hashes]
            except KeyError:
                raise KeyError(
                    f"dataplane fetch from peer {worker_id:x} ended "
                    f"with {len(by_hash)}/{len(seq_hashes)} block frames")
            self.dataplane_fetches_total += 1
            return blobs
        finally:
            rx.close()
            rt.tcp.unregister(rx.stream_id)

    async def _fetch_blobs_json(self, worker_id: int,
                                seq_hashes: Sequence[int],
                                trace_ctx: Optional[dict] = None
                                ) -> List[bytes]:
        """Legacy request-plane fetch (base64-framed JSON) — the
        graceful fallback when the peer lacks the native data plane."""
        import base64
        payload = {"op": "fetch",
                   "hashes": [int(h) for h in seq_hashes]}
        if trace_ctx:
            payload["trace"] = trace_ctx
        r = await self._call(worker_id, payload, trace_ctx=trace_ctx)
        if r.get("missing"):
            raise KeyError(f"peer {worker_id:x} no longer holds "
                           f"{len(r['missing'])} requested block(s)")
        blocks = r["blocks"]
        return await asyncio.to_thread(
            lambda: [base64.b64decode(blocks[str(int(h))])
                     for h in seq_hashes])

    async def fetch_async(self, worker_id: int, seq_hashes: Sequence[int],
                          trace_ctx: Optional[dict] = None) -> dict:
        """One peer fetch for a run of blocks → stacked wire values
        ({key: [L, H, n, bs, D]}). Block bytes ride the native data
        plane by default (length-prefixed binary frames, zero-copy
        unpack off the loop); a peer without the native lib serves the
        base64-over-JSON fallback with byte-identical payloads.
        KeyError when the peer cannot serve every requested hash
        (evicted since the announce) or the stream tears — the
        graceful-fallback-to-recompute signal. ``trace_ctx``
        (TraceContext dict) rides the RPC so the peer serves under a
        child trace.

        Every outcome feeds the peer's circuit breaker: failures and
        SLO-slow transfers trip it (the peer loses holder credit and
        admission eligibility until a half-open trial passes);
        successes close it."""
        from ...runtime.faults import hit_async as _fault
        t0 = time.monotonic()
        if not self.links.breaker(worker_id).allow():
            raise KeyError(f"peer {worker_id:x} circuit breaker is open")
        try:
            await _fault("fabric.fetch", exc=KeyError)
            blobs = None
            if self.use_dataplane:
                blobs = await self._fetch_blobs_native(
                    worker_id, seq_hashes, trace_ctx)
                if blobs is None:
                    self.dataplane_fallbacks_total += 1
            if blobs is None:
                blobs = await self._fetch_blobs_json(worker_id, seq_hashes,
                                                     trace_ctx)
        except Exception:
            self.links.record_failure(worker_id)
            raise

        def unpack_all():
            # npz decode + stack is bulk CPU work — decode keeps stepping
            # on this loop while the fetched run is unpacked off-thread
            blocks = [unpack_block_bytes(b)[0] for b in blobs]
            return {k: np.ascontiguousarray(
                        np.stack([b[k] for b in blocks], axis=2))
                    for k in blocks[0]}

        try:
            unpacked = await asyncio.to_thread(unpack_all)
        except Exception:
            # torn frames (truncated npz) are a peer-quality signal too
            self.links.record_failure(worker_id)
            raise
        elapsed = time.monotonic() - t0
        self.links.record_success(worker_id, elapsed)
        self.links.observe_transfer(worker_id, sum(len(b) for b in blobs),
                                    elapsed)
        self.peer_fetches_total += 1
        return unpacked

    def fetch_sync(self, worker_id: int, seq_hashes: Sequence[int],
                   trace_ctx: Optional[dict] = None) -> dict:
        """RemoteKvStore.peer_fetch plug: called from the admission's
        off-thread onboard prep, so blocking on the loop's RPC future is
        safe (and the loop keeps decoding throughout). ``trace_ctx`` is
        passed explicitly because contextvars don't cross the thread
        hop — the requesting request's trace identity travels by value."""
        if self._loop is None:
            raise KeyError("fabric not attached")
        fut = asyncio.run_coroutine_threadsafe(
            self.fetch_async(worker_id, seq_hashes, trace_ctx), self._loop)
        try:
            return fut.result(timeout=self.FETCH_TIMEOUT_S)
        except Exception as e:
            fut.cancel()
            if isinstance(e, KeyError):
                raise
            raise KeyError(f"fabric fetch from peer {worker_id:x} "
                           f"failed: {e}") from e

    def _admit(self, n_blocks: int,
               holders: Sequence[Sequence[int]]) -> bool:
        return self.gate.admit(n_blocks,
                               self.links.link_for_holders(holders))

    # -------------------------------------------------------------- stats
    def metrics(self) -> dict:
        """The nv_llm_kv_remote_* ForwardPassMetrics slice."""
        s = self.store
        return {
            "remote_used_blocks": s.used_blocks,
            "remote_capacity_blocks": s.capacity,
            "remote_peer_blocks": s.peer_block_count(),
            "remote_stored_total": s.stored_blocks_total,
            "remote_hit_rate": s.hit_rate(),
            "remote_fetch_failures_total": s.fetch_failures_total,
            "remote_admission_rejects_total": s.admission_rejects_total,
            "remote_link_gbps": self.links.avg_gbps(),
            "remote_link_rtt_s": self.links.avg_rtt_s(),
            "remote_dataplane_fetches_total": self.dataplane_fetches_total,
            "remote_dataplane_fallbacks_total":
                self.dataplane_fallbacks_total,
            # circuit breaker (the Grafana "Degradation" row): peers
            # currently tripped/half-open + cumulative trips
            "remote_breaker_open_peers": self.links.open_breaker_count(),
            "remote_breaker_trips_total":
                self.links.breaker_trips_total(),
        }

    async def close(self) -> None:
        if self._sub is not None:
            self._sub.close()
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        if self.client is not None:
            await self.client.close()
