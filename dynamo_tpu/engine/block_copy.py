"""Paged-KV block gather/scatter: the TPU-native analog of the reference's
CUDA copy kernels (lib/llm/src/kernels/block_copy.cu:41-758 —
``copy_blocks_kernel`` strided gather/scatter, ``copy_stream_*`` staging API).

On TPU these are XLA ops, not hand kernels: a block copy is a take /
dynamic-update along the paged token axis, which XLA lowers to efficient HBM
DMA; host staging is ``jax.device_put`` / ``device_get`` through TPU-VM DRAM
(the pinned-memory tier, reference kv/storage.rs:241-316 CudaPinnedMemory).
The TP-reshard-on-transfer permute (block_copy.cu:558-728) is likewise not a
kernel here: resharding is a sharding annotation change and XLA inserts the
collective (SURVEY.md §5.8).

Device cache layout (engine/models/llama.py init_kv_cache) is BLOCK-MAJOR:
    {"k": [L, num_blocks*block_size, H_kv*D], "v": same}
block b occupies token-row slice [b*bs, (b+1)*bs). The WIRE/HOST format for
stacked blocks stays head-major ``[L, H, n, bs, D]`` (the disagg handoff
protocol and the host offload arena predate the device-layout change);
gather/scatter convert between the two inside the jitted op.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

KVCache = Dict[str, jax.Array]

__all__ = ["gather_blocks", "scatter_blocks", "gather_blocks_dispatch",
           "gather_blocks_to_host", "scatter_blocks_from_host",
           "prep_host_values", "scatter_prepped", "to_wire_format",
           "from_wire_format", "fetch_wire", "move_blocks",
           "fetch_wire_layer", "prep_layer_values", "scatter_layer_prepped",
           "scatter_layer_from_host"]


@functools.partial(jax.jit, static_argnames=("block_size",))
def gather_blocks(kv: KVCache, block_ids: jax.Array,
                  block_size: int) -> KVCache:
    """Stack ``n`` blocks out of the paged pool -> {"k": [L, n, bs, H*D]}
    (block-major, same lane packing as the pool; convert to the head-major
    wire format with ``to_wire_format`` / ``fetch_wire``)."""

    def one(arr: jax.Array) -> jax.Array:
        L, _T, HD = arr.shape
        paged = arr.reshape(L, -1, block_size, HD)
        picked = jnp.take(paged, block_ids, axis=1)     # [L, n, bs, HD]
        return picked

    return {k: one(v) for k, v in kv.items()}


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def scatter_blocks(kv: KVCache, block_ids: jax.Array, values: KVCache,
                   block_size: int) -> KVCache:
    """Write stacked block values ([L, n, bs, H*D]) into pool row slices
    ``block_ids``; kv is donated so XLA updates HBM in place."""

    def one(arr: jax.Array, val: jax.Array) -> jax.Array:
        L, _T, HD = arr.shape
        paged = arr.reshape(L, -1, block_size, HD)
        paged = paged.at[:, block_ids].set(val.astype(arr.dtype))
        return paged.reshape(L, -1, HD)

    return {k: one(arr, values[k]) for k, arr in kv.items()}


def _pad_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# arrays of a cache's WINDOW group (llm/kv/hybrid.py), whose blocks carry
# ids of a pool of their own: models/mla.py dots3_note's window layers
# ("win"), models/mimo.py's ("win_k", "win_v": block arrays there, per-slot
# rings that hold no blocks in models/sambay.py, told apart by their rank)
WINDOW_GROUP = ("win", "win_k", "win_v")


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def _move_blocks(kv: KVCache, src_ids: jax.Array, dst_ids: jax.Array,
                 win_src: jax.Array, win_dst: jax.Array,
                 block_size: int) -> KVCache:
    def one(name: str, arr: jax.Array) -> jax.Array:
        if arr.ndim != 3:
            # a per-slot array of a hybrid cache (models/sambay.py: window
            # rings, recurrent state): it holds no blocks, nothing moves
            return arr
        # every array of a group moves under that group's ids
        src, dst = ((win_src, win_dst) if name in WINDOW_GROUP
                    else (src_ids, dst_ids))
        L, _T, HD = arr.shape
        paged = arr.reshape(L, -1, block_size, HD)
        vals = jnp.take(paged, src, axis=1)
        paged = paged.at[:, dst].set(vals)
        return paged.reshape(L, -1, HD)

    return {k: one(k, v) for k, v in kv.items()}


def move_blocks(kv: KVCache, src_ids, dst_ids, block_size: int,
                win_src=(), win_dst=()) -> KVCache:
    """On-device block migration src→dst inside the same paged pool (the
    defrag pass, engine/core.py _maybe_defrag): gather + in-place scatter
    in ONE donated jit, never staging through the host. Id counts pad to
    a power of two with trash-block self-copies (block 0 → block 0, its
    content is never read) so XLA compiles O(log n) programs.
    ``win_src`` / ``win_dst`` (at most as many): the same for the arrays of
    the window group, under that pool's ids, in the same program."""
    n = _pad_pow2(len(src_ids))
    if len(win_src) > n:
        raise ValueError("more window-group moves than paged moves")

    def ids(xs):
        return jnp.asarray(np.asarray(list(xs) + [0] * (n - len(xs)),
                                      np.int32))

    return _move_blocks(kv, ids(src_ids), ids(dst_ids), ids(win_src),
                        ids(win_dst), block_size)


def to_wire_format(picked: np.ndarray, num_heads: int) -> np.ndarray:
    """[L, n, bs, H*D] (block-major) -> wire [L, H, n, bs, D]."""
    L, n, bs, HD = picked.shape
    d = HD // num_heads
    return np.ascontiguousarray(
        picked.reshape(L, n, bs, num_heads, d).transpose(0, 3, 1, 2, 4))


def from_wire_format(vals: np.ndarray) -> np.ndarray:
    """wire [L, H, n, bs, D] -> [L, n, bs, H*D] (block-major)."""
    L, H, n, bs, d = vals.shape
    return np.ascontiguousarray(
        vals.transpose(0, 2, 3, 1, 4).reshape(L, n, bs, H * d))


def gather_blocks_dispatch(kv: KVCache, block_ids, block_size: int) -> KVCache:
    """Dispatch (but do not fetch) the on-device gather of ``block_ids``.

    Block-id count is padded to a power of two (with the trash block, id 0)
    so XLA compiles O(log n) gather programs, not one per count; callers
    slice ``[:n]`` on the block axis after fetching. Dispatching eagerly
    orders the read before any later donated in-place KV update (single
    device stream = program order), so the caller may fetch off-thread.
    Result layout: [L, n_padded, bs, H*D] per entry."""
    n = len(block_ids)
    padded = list(block_ids) + [0] * (_pad_pow2(n) - n)
    ids = jnp.asarray(np.asarray(padded, dtype=np.int32))
    return gather_blocks(kv, ids, block_size)


def _local_np(x) -> np.ndarray:
    """np.asarray for possibly multi-process arrays: when ``x`` spans
    non-addressable devices (a multi-controller mesh), assemble THIS
    process's contiguous portion from its addressable shards. Only the
    last (lane-packed H*D) axis may be partitioned across processes —
    the KV layouts this module moves shard heads over tp and replicate
    the rest."""
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    by_start: dict = {}
    for s in x.addressable_shards:
        idx = s.index
        for ax, sl in enumerate(idx[:-1]):
            if not (sl.start in (None, 0) and sl.stop in (None, x.shape[ax])):
                raise NotImplementedError(
                    f"multi-process KV partitioned on axis {ax}; only "
                    f"last-axis (head) sharding is supported here")
        start = idx[-1].start or 0
        if start not in by_start:      # replicated shards: fetch once
            by_start[start] = np.asarray(s.data)
    return np.concatenate([by_start[st] for st in sorted(by_start)],
                          axis=-1)


def fetch_wire(stacked: KVCache, n: int, num_heads: int) -> dict:
    """Fetch a dispatched gather ([L, n_padded, bs, H*D] device arrays) to
    the host and convert to wire format {"k": [L, H, n, bs, D]} — the one
    device->wire harvest used by offload, handoff, and gather_blocks_to_host
    (keep in sync by calling, not copying).

    ``num_heads`` is the GLOBAL kv-head count; on a multi-controller mesh
    each process harvests only its local head shard and the result's H
    axis is the local count (the host tier is per-rank — multihost mirror
    pools hold each rank's shard, engine/multihost.py).

    int8 pools are OPAQUE rows (values + in-row scales; one wire "head",
    core.wire_kv_heads): the proportional head arithmetic cannot
    subdivide a single head, so each rank ships its whole local lane
    shard as one head of whatever width it holds."""
    out = {}
    for k, v in stacked.items():
        arr = _local_np(v)[:, :n]
        heads = (1 if v.dtype == jnp.int8
                 else num_heads * arr.shape[-1] // v.shape[-1])
        out[k] = to_wire_format(arr, heads)
    return out


def fetch_wire_layer(stacked: KVCache, n: int, num_heads: int,
                     layer: int) -> dict:
    """ONE layer of a dispatched gather → per-layer wire format
    {"k": [H, n, bs, D]} — the producer half of the streaming layer-wise
    handoff (llm/kv/stream.py). Only that layer's slice crosses
    device→host, so layer ``l+1``'s fetch overlaps layer ``l``'s wire
    send. Per-layer arrays stacked over the layer axis are bit-identical
    to ``fetch_wire``'s [L, H, n, bs, D] (same transpose, same opaque
    one-head int8 rows).

    Requires a fully-addressable gather (the caller gates: a
    multi-controller prefill engine keeps the monolithic handoff)."""
    out = {}
    for k, v in stacked.items():
        arr = np.asarray(v[layer])[:n]          # [n, bs, H*D], one layer
        heads = (1 if v.dtype == jnp.int8
                 else num_heads * arr.shape[-1] // v.shape[-1])
        nb, bs, HD = arr.shape
        d = HD // heads
        out[k] = np.ascontiguousarray(
            arr.reshape(nb, bs, heads, d).transpose(2, 0, 1, 3))
    return out


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def _scatter_layer(kv: KVCache, block_ids: jax.Array, layer: jax.Array,
                   values: KVCache, block_size: int) -> KVCache:
    """Write one layer's stacked block values ([n, bs, H*D]) into pool
    row slices ``block_ids`` of layer ``layer`` (traced, so every layer
    shares one compiled program); kv is donated — in-place HBM update."""

    def one(arr: jax.Array, val: jax.Array) -> jax.Array:
        L, _T, HD = arr.shape
        paged = arr.reshape(L, -1, block_size, HD)
        paged = jax.lax.dynamic_update_index_in_dim(
            paged, paged[layer].at[block_ids].set(val.astype(arr.dtype)),
            layer, axis=0)
        return paged.reshape(L, -1, HD)

    return {k: one(arr, values[k]) for k, arr in kv.items()}


def prep_layer_values(block_ids, layer_values: dict) -> tuple:
    """Pure-numpy half of a per-layer host→device scatter: per-layer wire
    {"k": [H, n, bs, D]} → block-major [n_padded, bs, H*D] + pow2-padded
    ids. Safe OFF the loop thread (the streaming onboard runs it in
    asyncio.to_thread like the tier-onboard prep). Padding targets the
    trash block (id 0), whose content is never read."""
    n = len(block_ids)
    pad = _pad_pow2(n) - n
    ids = np.asarray(list(block_ids) + [0] * pad, dtype=np.int32)
    out = {}
    for k, v in layer_values.items():
        v = np.asarray(v)
        H, nb, bs, d = v.shape
        v = np.ascontiguousarray(
            v.transpose(1, 2, 0, 3).reshape(nb, bs, H * d))
        if pad:
            v = np.concatenate(
                [v, np.zeros((pad,) + v.shape[1:], v.dtype)], axis=0)
        out[k] = v
    return ids, out


def scatter_layer_prepped(kv: KVCache, layer: int, ids: np.ndarray,
                          vals: dict, block_size: int) -> KVCache:
    """Run the per-layer h2d scatter for prep_layer_values output against
    ``kv``'s actual placement (single-process direct upload; multi-
    controller assembles per-rank head shards like scatter_prepped)."""
    sample = next(iter(kv.values()))
    if getattr(sample, "is_fully_addressable", True):
        vj = {k: jnp.asarray(v) for k, v in vals.items()}
    else:
        sh = sample.sharding
        spec = tuple(sh.spec) + (None,) * (sample.ndim - len(sh.spec))
        vsh = jax.sharding.NamedSharding(
            sh.mesh, jax.sharding.PartitionSpec(None, None, spec[-1]))
        vj = {k: jax.make_array_from_process_local_data(vsh, v)
              for k, v in vals.items()}
    return _scatter_layer(kv, jnp.asarray(ids),
                          jnp.asarray(layer, jnp.int32), vj, block_size)


def slice_local_lanes(kv: KVCache, host_values: dict) -> dict:
    """Slice GLOBAL-head wire values down to THIS process's lane shard of
    a multi-controller ``kv`` (identity on a fully-addressable cache).
    Works for whole-stack ([L, H, n, bs, D]) and per-layer
    ([H, n, bs, D]) wire arrays — the head axis is axis -4 either way."""
    sample = next(iter(kv.values()))
    if getattr(sample, "is_fully_addressable", True):
        return host_values
    lo, hi = _local_lane_range(sample)
    if sample.dtype == jnp.int8:
        # opaque int8 rows ride the wire as ONE head (fetch_wire): a
        # rank's shard is a lane slice of it, not a head subrange
        return {k: v[..., lo:hi] for k, v in host_values.items()}
    d = next(iter(host_values.values())).shape[-1]
    return {k: v[..., lo // d:hi // d, :, :, :]
            for k, v in host_values.items()}


def scatter_layer_from_host(kv: KVCache, block_ids, layer: int,
                            layer_values: dict,
                            block_size: int) -> KVCache:
    """TPU-VM DRAM → device for ONE layer: the replay/follower half of
    the ``kv_layer_stream`` event (engine/replay.py, engine/multihost.py)
    and the synchronous form of the engine's streaming onboard.
    ``layer_values`` is GLOBAL-head per-layer wire format [H, n, bs, D];
    multi-controller ranks slice their local head shard first."""
    ids, vals = prep_layer_values(
        block_ids, slice_local_lanes(kv, layer_values))
    return scatter_layer_prepped(kv, layer, ids, vals, block_size)


def gather_blocks_to_host(kv: KVCache, block_ids, block_size: int,
                          num_heads: int) -> dict:
    """Device -> TPU-VM DRAM: gather on device (one DMA-friendly slice), then
    a single transfer. Returns numpy wire format {"k": [L, H, n, bs, D]}."""
    stacked = gather_blocks_dispatch(kv, block_ids, block_size)
    return fetch_wire(stacked, len(block_ids), num_heads)


def prep_host_values(block_ids, host_values: dict) -> tuple:
    """The pure-numpy half of a host→device block scatter: wire→block-major
    transposes + pow2 padding. Returns (ids int32 [n_padded], values
    {"k": [L, n_padded, bs, H*D]}). Safe to run OFF the loop thread —
    async onboarding does (llm/kv/offload.py), so admission never stalls
    on these copies.

    Padding targets the trash block (id 0), whose content is never read."""
    n = len(block_ids)
    pad = _pad_pow2(n) - n
    ids = np.asarray(list(block_ids) + [0] * pad, dtype=np.int32)
    out = {}
    for k, v in host_values.items():
        v = from_wire_format(np.asarray(v))
        if pad:
            v = np.concatenate(
                [v, np.zeros((v.shape[0], pad) + v.shape[2:], v.dtype)],
                axis=1)
        out[k] = v
    return ids, out


def scatter_prepped(kv: KVCache, ids: np.ndarray, vals: dict,
                    block_size: int) -> KVCache:
    """Run the h2d scatter for prep_host_values output against ``kv``'s
    actual placement: on a single-process mesh the values upload directly;
    on a multi-controller mesh each rank holds only its local head shard
    (fetch_wire), so the global values array is assembled from the
    process-local data under kv's own last-axis sharding."""
    sample = next(iter(kv.values()))
    if getattr(sample, "is_fully_addressable", True):
        vj = {k: jnp.asarray(v) for k, v in vals.items()}
    else:
        sh = sample.sharding
        spec = tuple(sh.spec) + (None,) * (sample.ndim - len(sh.spec))
        vsh = jax.sharding.NamedSharding(
            sh.mesh, jax.sharding.PartitionSpec(None, None, None, spec[-1]))
        vj = {k: jax.make_array_from_process_local_data(vsh, v)
              for k, v in vals.items()}
    return scatter_blocks(kv, jnp.asarray(ids), vj, block_size)


def scatter_blocks_from_host(kv: KVCache, block_ids, host_values: dict,
                             block_size: int) -> KVCache:
    """TPU-VM DRAM -> device: one transfer, then an on-device scatter into
    the paged pool. ``host_values`` is GLOBAL-head wire format
    [L, H, n, bs, D]; on a multi-controller mesh each rank slices its
    local head shard before uploading (scatter_prepped assembles the
    global array from the per-rank locals). Returns the new
    (donated-in-place) cache."""
    ids, vals = prep_host_values(
        block_ids, slice_local_lanes(kv, host_values))
    return scatter_prepped(kv, ids, vals, block_size)


def _local_lane_range(x) -> tuple:
    """This process's contiguous [start, stop) span of the last (lane)
    axis of a multi-process array (same contiguity assumption _local_np
    validates)."""
    starts = {s.index[-1].start or 0 for s in x.addressable_shards}
    stops = {s.index[-1].stop or x.shape[-1] for s in x.addressable_shards}
    return min(starts), max(stops)
